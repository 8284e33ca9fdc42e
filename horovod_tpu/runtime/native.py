"""ctypes bindings to the native engine core (libhvd_tpu_core.so).

The reference loads its C++ engine the same way — a ctypes wrapper over a C
ABI (`horovod/common/basics.py:27-31`). The library is built from
`horovod_tpu/_core/` by `make` (:func:`build`); if missing, it is built on
first use (the toolchain is part of the supported environment). When it
cannot be built, :func:`load_library` logs why and the engine takes the
pure-Python controller (``HVD_TPU_NATIVE=0`` asks for that outright);
``Engine.native`` says which controller a process got.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

from . import wire
from .messages import RequestType, Response, TensorTableEntry

_CORE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "_core")
_LIB_PATH = os.path.join(_CORE_DIR, "libhvd_tpu_core.so")

logger = logging.getLogger("horovod_tpu")

_lib = None
_lib_lock = threading.Lock()

# numpy dtype name -> DType code (common.h)
_DTYPE_CODES = {
    "float16": 0, "bfloat16": 1, "float32": 2, "float64": 3,
    "int8": 4, "int16": 5, "int32": 6, "int64": 7,
    "uint8": 8, "uint16": 9, "uint32": 10, "uint64": 11, "bool": 12,
}


def dtype_code(dtype) -> int:
    return _DTYPE_CODES.get(str(dtype), 2)


def build() -> None:
    """``make`` the native core from the sources in ``_core/`` (a no-op
    when the library is newer than all of them). Raises ``RuntimeError``
    saying why when the library cannot be built — no ``make``, no
    compiler, or a compile error."""
    try:
        r = subprocess.run(["make", "-C", _CORE_DIR], capture_output=True,
                           text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as exc:
        raise RuntimeError(
            f"cannot run make in {_CORE_DIR}: {exc}") from exc
    if r.returncode or not os.path.exists(_LIB_PATH):
        raise RuntimeError(
            f"make -C {_CORE_DIR} failed (exit {r.returncode}): "
            f"{(r.stderr or r.stdout).strip()[-2000:]}")


def load_library():
    """Load (building if needed) the native core; returns None, after
    logging the reason, when it cannot be built or loaded."""
    global _lib
    with _lib_lock:
        if os.environ.get("HVD_TPU_NATIVE", "1") in ("0", "false"):
            return None
        if _lib is not None:
            return _lib
        try:
            if not os.path.exists(_LIB_PATH):
                build()
            lib = _load_and_bind()
            if lib is None:
                # a prebuilt .so can predate newly added C entry points
                # (the build products are gitignored): rebuild, retry once
                build()
                lib = _load_and_bind()
        except RuntimeError as exc:
            logger.warning("native core unavailable: %s", exc)
            return None
        if lib is None:
            logger.warning("native core unavailable: %s does not load or "
                           "lacks a symbol this version binds", _LIB_PATH)
        _lib = lib
        return _lib


def _load_and_bind():
    """dlopen + bind every C symbol; None if the library is unloadable or
    missing a symbol (stale build)."""
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError:
        return None
    try:
        _bind(lib)
    except AttributeError:
        return None
    return lib


def _bind(lib) -> None:
    lib.hvd_core_create.restype = ctypes.c_int64
    lib.hvd_core_create.argtypes = [
        ctypes.c_int32, ctypes.c_int64, ctypes.c_double, ctypes.c_double,
        ctypes.c_int64, ctypes.c_int32, ctypes.c_char_p, ctypes.c_int32,
        ctypes.c_double, ctypes.c_int32, ctypes.c_int32]
    lib.hvd_core_destroy.argtypes = [ctypes.c_int64]
    lib.hvd_core_submit.restype = ctypes.c_int64
    lib.hvd_core_submit.argtypes = [
        ctypes.c_int64, ctypes.c_char_p, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int32, ctypes.c_int32, ctypes.c_double, ctypes.c_double,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int32]
    lib.hvd_core_join.restype = ctypes.c_int64
    lib.hvd_core_join.argtypes = [ctypes.c_int64, ctypes.c_int32]
    lib.hvd_core_tick.restype = ctypes.c_int64
    lib.hvd_core_tick.argtypes = [ctypes.c_int64,
                                  ctypes.POINTER(ctypes.c_char_p)]
    lib.hvd_core_shutdown.restype = ctypes.c_int64
    lib.hvd_core_shutdown.argtypes = [ctypes.c_int64,
                                      ctypes.POINTER(ctypes.c_char_p)]
    for f in ("hvd_core_timeline_op_start", "hvd_core_timeline_activity"):
        getattr(lib, f).argtypes = [ctypes.c_int64, ctypes.c_char_p,
                                    ctypes.c_char_p]
    lib.hvd_core_timeline_op_end.argtypes = [ctypes.c_int64,
                                             ctypes.c_char_p]
    lib.hvd_core_timeline_cycle.argtypes = [ctypes.c_int64]
    lib.hvd_core_timeline_cache.argtypes = [ctypes.c_int64,
                                            ctypes.c_uint64,
                                            ctypes.c_uint64]
    lib.hvd_core_report_score.restype = ctypes.c_int32
    lib.hvd_core_report_score.argtypes = [ctypes.c_int64, ctypes.c_int64,
                                          ctypes.c_double]
    lib.hvd_core_fusion_threshold.restype = ctypes.c_int64
    lib.hvd_core_fusion_threshold.argtypes = [ctypes.c_int64]
    lib.hvd_core_cycle_time_ms.restype = ctypes.c_double
    lib.hvd_core_cycle_time_ms.argtypes = [ctypes.c_int64]
    lib.hvd_core_cache_hits.restype = ctypes.c_uint64
    lib.hvd_core_cache_hits.argtypes = [ctypes.c_int64]
    lib.hvd_core_cache_misses.restype = ctypes.c_uint64
    lib.hvd_core_cache_misses.argtypes = [ctypes.c_int64]
    lib.hvd_tuner_active.restype = ctypes.c_int32
    lib.hvd_tuner_active.argtypes = [ctypes.c_int64]
    lib.hvd_core_autotune_active.restype = ctypes.c_int32
    lib.hvd_core_autotune_active.argtypes = [ctypes.c_int64]
    lib.hvd_tuner_create.restype = ctypes.c_int64
    lib.hvd_tuner_create.argtypes = [ctypes.c_int64, ctypes.c_double,
                                     ctypes.c_uint64]
    lib.hvd_tuner_configure.argtypes = [
        ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_double]
    lib.hvd_core_tuner_configure.argtypes = [
        ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_double]
    lib.hvd_tuner_update.restype = ctypes.c_int32
    lib.hvd_tuner_update.argtypes = [ctypes.c_int64, ctypes.c_int64,
                                     ctypes.c_double]
    lib.hvd_tuner_threshold.restype = ctypes.c_int64
    lib.hvd_tuner_threshold.argtypes = [ctypes.c_int64]
    lib.hvd_tuner_cycle_ms.restype = ctypes.c_double
    lib.hvd_tuner_cycle_ms.argtypes = [ctypes.c_int64]
    lib.hvd_tuner_destroy.argtypes = [ctypes.c_int64]


def autotune_env_knobs():
    """Parse the reference's four HOROVOD_AUTOTUNE_* tuning knobs
    (`horovod/common/parameter_manager.cc:42-59`): warmup samples,
    steps per sample, Bayes-opt max samples, GP noise. Unset/invalid maps
    to the sentinel (-1 / -1.0) the native ``Configure()`` treats as
    keep-default (warmup accepts an explicit 0)."""
    def _int(name: str) -> int:
        v = os.environ.get(name, "")
        try:
            return int(v) if v else -1
        except ValueError:
            return -1

    def _flt(name: str) -> float:
        v = os.environ.get(name, "")
        try:
            return float(v) if v else -1.0
        except ValueError:
            return -1.0

    return (_int("HOROVOD_AUTOTUNE_WARMUP_SAMPLES"),
            _int("HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE"),
            _int("HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"),
            _flt("HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE"))


class NativeTuner:
    """Standalone GP/EI parameter manager (autotune.cc) for the cross-process
    coordinator: rank 0 feeds aggregated throughput scores and reads back the
    tuned (fusion_threshold, cycle_time) to broadcast in its ResponseList —
    the coordinated analogue of the in-process autotune path. Raises if the
    native core cannot be loaded (coordinated autotune is native-only; the
    caller degrades to no-tuning with a warning)."""

    def __init__(self, fusion_threshold: int, cycle_time_ms: float,
                 seed: int = 0, knobs=None):
        lib = load_library()
        if lib is None:
            raise RuntimeError("native core unavailable")
        self._lib = lib
        self._h = lib.hvd_tuner_create(fusion_threshold, cycle_time_ms, seed)
        # the four HOROVOD_AUTOTUNE_* sub-knobs (env unless given explicitly)
        w, s, m, n = knobs if knobs is not None else autotune_env_knobs()
        lib.hvd_tuner_configure(self._h, w, s, m, n)

    def update(self, nbytes: int, seconds: float) -> bool:
        """Record one scored interval; True if tuned params changed."""
        return bool(self._lib.hvd_tuner_update(self._h, nbytes, seconds))

    def active(self) -> bool:
        """True while the GP is still exploring (False once settled)."""
        return bool(self._lib.hvd_tuner_active(self._h))

    def fusion_threshold(self) -> int:
        return self._lib.hvd_tuner_threshold(self._h)

    def cycle_time_ms(self) -> float:
        return self._lib.hvd_tuner_cycle_ms(self._h)

    def close(self) -> None:
        if self._h:
            self._lib.hvd_tuner_destroy(self._h)
            self._h = 0


class NativeController:
    """Thin stateful wrapper over one native engine instance.

    Interface consumed by runtime.engine.Engine: submit/join/tick/shutdown +
    timeline hooks + autotune scoring. Tensor data never crosses this
    boundary — only metadata and handles.
    """

    SUBMIT_DUPLICATE = -1
    SUBMIT_SHUTDOWN = -2

    def __init__(self, world: int, fusion_threshold: int,
                 stall_warning_s: float, stall_shutdown_s: float,
                 cache_capacity: int, fusion_enabled: bool,
                 timeline_path: Optional[str], autotune: bool,
                 cycle_time_ms: float, local_only: bool = False,
                 self_rank: int = 0):
        self._lib = load_library()
        if self._lib is None:
            raise RuntimeError("native core unavailable")
        self._eng = self._lib.hvd_core_create(
            world, fusion_threshold, stall_warning_s, stall_shutdown_s,
            cache_capacity, int(fusion_enabled),
            timeline_path.encode() if timeline_path else None,
            int(autotune), cycle_time_ms, int(local_only), self_rank)
        self._dead = False
        if autotune:
            self._lib.hvd_core_tuner_configure(self._eng,
                                               *autotune_env_knobs())

    def submit(self, entry: TensorTableEntry) -> int:
        shape = np.asarray(entry.array.shape, dtype=np.int64)
        dims = shape.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)) \
            if shape.size else ctypes.POINTER(ctypes.c_int64)()
        nil = ctypes.POINTER(ctypes.c_int64)()
        if entry.splits is not None:
            sp = np.asarray(entry.splits, dtype=np.int64)
            spp = sp.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)) \
                if sp.size else nil
            nsp = int(sp.size)
        else:
            spp, nsp = nil, 0
        return self._lib.hvd_core_submit(
            self._eng, entry.tensor_name.encode(), entry.rank,
            int(entry.request_type), dtype_code(entry.array.dtype),
            len(entry.array.shape), dims, entry.root_rank,
            int(entry.average), entry.prescale_factor, entry.postscale_factor,
            spp, nsp)

    def join(self, rank: int) -> int:
        return self._lib.hvd_core_join(self._eng, rank)

    def tick(self):
        p = ctypes.c_char_p()
        n = self._lib.hvd_core_tick(self._eng, ctypes.byref(p))
        if n <= 0:
            return None
        buf = ctypes.string_at(p, n)
        return wire.decode_tick(buf)

    def shutdown(self) -> List[int]:
        if self._dead:
            return []
        self._dead = True
        p = ctypes.c_char_p()
        n = self._lib.hvd_core_shutdown(self._eng, ctypes.byref(p))
        orphans = wire.decode_handle_list(ctypes.string_at(p, n)) if n > 0 else []
        self._lib.hvd_core_destroy(self._eng)
        return orphans

    # ---- timeline / autotune
    def timeline_op_start(self, tensor: str, op: str) -> None:
        self._lib.hvd_core_timeline_op_start(self._eng, tensor.encode(),
                                             op.encode())

    def timeline_activity(self, tensor: str, activity: str) -> None:
        self._lib.hvd_core_timeline_activity(self._eng, tensor.encode(),
                                             activity.encode())

    def timeline_op_end(self, tensor: str) -> None:
        self._lib.hvd_core_timeline_op_end(self._eng, tensor.encode())

    def timeline_cycle(self) -> None:
        self._lib.hvd_core_timeline_cycle(self._eng)

    def timeline_cache(self, hits: int, misses: int) -> None:
        self._lib.hvd_core_timeline_cache(self._eng, hits, misses)

    def report_score(self, nbytes: int, seconds: float) -> bool:
        return bool(self._lib.hvd_core_report_score(self._eng, nbytes,
                                                    seconds))

    def autotune_active(self) -> bool:
        return bool(self._lib.hvd_core_autotune_active(self._eng))

    def fusion_threshold(self) -> int:
        return self._lib.hvd_core_fusion_threshold(self._eng)

    def cycle_time_ms(self) -> float:
        return self._lib.hvd_core_cycle_time_ms(self._eng)

    def cache_stats(self) -> Tuple[int, int]:
        return (self._lib.hvd_core_cache_hits(self._eng),
                self._lib.hvd_core_cache_misses(self._eng))

    def excluded_ranks(self) -> frozenset:
        """The C++ core predates the straggler policy and never excludes a
        rank — the "absent ⇒ full participation" agreement across
        controllers (runtime/straggler.py)."""
        return frozenset()
