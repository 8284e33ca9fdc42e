"""Process model and global state — TPU-native equivalent of horovod's C ABI.

Reference parity: `horovod/common/basics.py` (HorovodBasics ctypes wrapper) and the
C API `horovod_init/shutdown/rank/size/local_rank/local_size/cross_rank/cross_size`
(`horovod/common/operations.cc:642-779`).

TPU-native design: there is no MPI. A *rank* is either
  - a JAX process in a multi-host job (``jax.distributed``-initialized; the launcher
    populates coordinator address / process id the way ``horovodrun`` populates
    ``HOROVOD_RANK``/``HOROVOD_GLOO_RENDEZVOUS_ADDR``; see `horovod/run/gloo_run.py:210-285`), or
  - a *thread-rank* bound to one local device, used by the in-process local cluster
    (the analogue of ``horovodrun -np N -H localhost:N`` for tests/benchmarks — the
    reference runs its whole test matrix this way, `.buildkite/gen-pipeline.sh:104-200`).

The MPI communicator triple GLOBAL/LOCAL/CROSS (`horovod/common/mpi/mpi_context.cc:150-158`)
maps onto device topology: LOCAL = ranks sharing a host (collectives ride ICI),
CROSS = one rank per host (collectives ride DCN).
"""

from __future__ import annotations

import contextvars
import logging
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import numpy as np

from .exceptions import NotInitializedError
from .metrics import phases

logger = logging.getLogger("horovod_tpu")

# Reduce-op constants: parity with horovod/common/basics.py (Average/Sum/Adasum
# exported from horovod.torch / horovod.tensorflow).
Average = 0
Sum = 1
Adasum = 2

# Rank identity for the calling thread. In process mode this is unused (the
# process has exactly one rank); in local-cluster mode each worker thread carries
# its rank here.
_rank_ctx: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "hvd_tpu_rank", default=None
)


@dataclass
class _GlobalState:
    """Aggregate runtime state; mirrors HorovodGlobalState (`global_state.h:42-125`)."""

    initialized: bool = False
    mode: str = "standalone"  # standalone | cluster | multiprocess
    size: int = 1
    local_size: int = 1
    cross_size: int = 1
    rank0: int = 0  # this process's rank in multiprocess mode
    local_rank0: int = 0
    cross_rank0: int = 0
    # rank -> jax device that rank's tensors live on (cluster mode: 1:1;
    # process mode: this process's first addressable device).
    rank_devices: Sequence[Any] = field(default_factory=list)
    mesh: Any = None  # replica mesh: ALL devices, axis "hvd" (SPMD fast path)
    rank_mesh: Any = None  # one device per rank (eager engine collectives)
    engine: Any = None
    # elastic job (HVD_ELASTIC=1): jax.distributed is skipped so workers can
    # die/join; the engine routes collectives over the coordinator's host
    # wire instead of cross-process XLA (docs/elastic.md)
    elastic: bool = False
    lock: threading.Lock = field(default_factory=threading.Lock)


_state = _GlobalState()
_init_lock = threading.Lock()

MESH_AXIS = "hvd"


def _build_mesh(devices):
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices), (MESH_AXIS,))


# reference logging.h level names (TRACE/FATAL have no stdlib equivalents;
# map to the nearest level the way glog-style loggers are usually bridged)
_LOG_LEVELS = {"TRACE": logging.DEBUG, "DEBUG": logging.DEBUG,
               "INFO": logging.INFO, "WARNING": logging.WARNING,
               "ERROR": logging.ERROR, "FATAL": logging.CRITICAL}


def _setup_logging() -> None:
    """Apply HOROVOD_LOG_LEVEL / HOROVOD_LOG_HIDE_TIME to the framework
    logger (reference `common/logging.{h,cc}`: leveled macro logger driven
    by the same envs, exported by the launcher's --log-level /
    --log-hide-timestamp flags). Only touches the ``horovod_tpu`` logger —
    never the root — and only adds a handler if the app hasn't."""
    level = os.environ.get("HOROVOD_LOG_LEVEL", "").upper()
    if level in _LOG_LEVELS:
        logger.setLevel(_LOG_LEVELS[level])
    if logger.handlers or logging.getLogger().handlers:
        return  # the application configured logging; respect it
    from .utils.env import env_on

    handler = logging.StreamHandler()
    fmt = "[%(asctime)s] %(levelname)s %(name)s: %(message)s"
    if env_on("HOROVOD_LOG_HIDE_TIME"):
        fmt = "%(levelname)s %(name)s: %(message)s"
    handler.setFormatter(logging.Formatter(fmt))
    logger.addHandler(handler)


def init(
    ranks: Optional[Sequence[int]] = None,
    *,
    _cluster_size: Optional[int] = None,
    _devices: Optional[Sequence[Any]] = None,
) -> None:
    """Initialize the framework. Idempotent (InitializeHorovodOnce,
    `operations.cc:585-631`).

    Modes:
      * **multiprocess** — launcher (or the user) set ``HVD_COORDINATOR_ADDR`` /
        ``HVD_NUM_PROCS`` / ``HVD_PROCESS_ID`` or already called
        ``jax.distributed.initialize``; each process is one rank.
      * **cluster** — internal: ``local_cluster``/``run_cluster`` passes
        ``_cluster_size`` and each worker thread is a rank bound to one device.
      * **standalone** — single process, rank 0 of 1; the SPMD fast path still
        uses every local device through the mesh.

    ``ranks`` (subset init, `basics.py:33-65` in the reference) is accepted for
    API parity; subsetting is only meaningful in multiprocess mode.
    """
    if _state.initialized:
        return
    phases.install_jax_listeners()
    with phases.phase("init"):
        _init(_cluster_size, _devices)


def _init(_cluster_size, _devices) -> None:
    import jax

    global _state
    with _init_lock:
        if _state.initialized:
            return
        _setup_logging()
        coord = os.environ.get("HVD_COORDINATOR_ADDR")
        if _cluster_size is not None:
            devices = list(_devices) if _devices is not None else list(jax.devices())
            if _cluster_size > len(devices):
                raise ValueError(
                    f"local cluster size {_cluster_size} exceeds device count "
                    f"{len(devices)}"
                )
            devices = devices[:_cluster_size]
            st = _GlobalState(
                initialized=True,
                mode="cluster",
                size=_cluster_size,
                local_size=_cluster_size,
                cross_size=1,
                rank_devices=devices,
                mesh=_build_mesh(devices),
                rank_mesh=_build_mesh(devices),
            )
        elif os.environ.get("HVD_ELASTIC", "") not in ("", "0"):
            # Elastic job: jax.distributed is deliberately NOT initialized —
            # XLA's cross-process runtime cannot survive a worker dying, and
            # the whole point here is that the job outlives its members.
            # Each process runs single-process JAX; collective payloads ride
            # the coordinator's TCP channel (elastic/executor.py).
            nproc = int(os.environ.get("HVD_NUM_PROCS", "1"))
            pid = int(os.environ.get("HVD_PROCESS_ID", "0"))
            local_rank = int(os.environ.get("HVD_LOCAL_RANK", 0))
            local_size = int(os.environ.get("HVD_LOCAL_SIZE", 1))
            cross_rank = int(os.environ.get("HVD_CROSS_RANK", pid))
            cross_size = int(os.environ.get("HVD_CROSS_SIZE", nproc))
            devices = list(jax.devices())
            # every rank "lives" on this process's first device; size the list
            # past nproc so late joiners (pid >= initial nproc) still resolve
            rank_devices = [devices[0]] * max(nproc, pid + 1)
            st = _GlobalState(
                initialized=True,
                mode="multiprocess",
                size=nproc,
                local_size=local_size,
                cross_size=cross_size,
                rank0=pid,
                local_rank0=local_rank,
                cross_rank0=cross_rank,
                rank_devices=rank_devices,
                mesh=_build_mesh(devices[:1]),
                rank_mesh=_build_mesh(devices[:1]),
                elastic=True,
            )
        elif coord or jax.process_count() > 1:
            if coord:
                # must run BEFORE any backend-initializing jax call
                # (jax.distributed requirement); idempotent via try
                try:
                    jax.distributed.initialize(
                        coordinator_address=coord,
                        num_processes=int(os.environ["HVD_NUM_PROCS"]),
                        process_id=int(os.environ["HVD_PROCESS_ID"]),
                    )
                except RuntimeError as e:
                    # tolerate only double-initialization; a genuine
                    # coordination failure (bad address, timeout) must NOT
                    # silently degrade to un-synchronized single-process
                    # training
                    if "already" not in str(e).lower():
                        raise
            nproc = jax.process_count()
            pid = jax.process_index()
            # local/cross decomposition: ranks sharing a host form LOCAL (ICI);
            # one per host forms CROSS (DCN). Host identity from device process
            # affinity; launcher also exports HVD_LOCAL_RANK/SIZE.
            local_rank = int(os.environ.get("HVD_LOCAL_RANK", 0))
            local_size = int(os.environ.get("HVD_LOCAL_SIZE", 1))
            cross_rank = int(os.environ.get("HVD_CROSS_RANK", pid))
            cross_size = int(os.environ.get("HVD_CROSS_SIZE", nproc))
            # rank r's "home" device = first device owned by process r
            per_proc = {}
            for d in jax.devices():
                per_proc.setdefault(d.process_index, d)
            rank_devices = [per_proc[i] for i in range(nproc)]
            st = _GlobalState(
                initialized=True,
                mode="multiprocess",
                size=nproc,
                local_size=local_size,
                cross_size=cross_size,
                rank0=pid,
                local_rank0=local_rank,
                cross_rank0=cross_rank,
                rank_devices=rank_devices,
                mesh=_build_mesh(jax.devices()),
                rank_mesh=_build_mesh(rank_devices),
            )
        else:
            devices = list(jax.devices())
            st = _GlobalState(
                initialized=True,
                mode="standalone",
                size=1,
                local_size=1,
                cross_size=1,
                rank_devices=[devices[0]],
                mesh=_build_mesh(devices),
                rank_mesh=_build_mesh(devices[:1]),
            )
        with phases.phase("init/engine"):
            from .runtime.engine import Engine

            st.engine = Engine(st)
            st.engine.start()
        _state = st
        if st.rank0 == 0:
            # aggregating process: serve /metrics when HOROVOD_METRICS_PORT
            # is set (rank 0 in multiprocess mode; the one process otherwise)
            from .metrics import maybe_start_server

            maybe_start_server()
            # live anomaly watch over the aggregated hvd_* registry when
            # HOROVOD_ANOMALY_WATCH is set (docs/observability.md)
            from .blackbox import watch as _watch

            _watch.maybe_start_watch()


_shutdown_hooks = []


def register_shutdown_hook(fn) -> None:
    """Framework surfaces register per-module cleanup (e.g. the torch
    handle-side maps) to run whenever the engine is torn down. Dedup by
    qualified name: module reimports (tests pop sys.modules) must replace
    their old hook, not accumulate copies that pin stale module objects."""
    key = (getattr(fn, "__module__", None), getattr(fn, "__qualname__", None))
    for i, existing in enumerate(_shutdown_hooks):
        if (getattr(existing, "__module__", None),
                getattr(existing, "__qualname__", None)) == key:
            _shutdown_hooks[i] = fn
            return
    _shutdown_hooks.append(fn)


def shutdown() -> None:
    """Stop the background engine and reset state (`operations.cc:636-640`)."""
    if not _state.initialized:
        return
    with phases.phase("shutdown"):
        _shutdown()


def _shutdown() -> None:
    global _state
    with _init_lock:
        if not _state.initialized:
            return
        mode, rank0, world = _state.mode, _state.rank0, _state.size
        if _state.engine is not None:
            _state.engine.shutdown()
        _state = _GlobalState()
        from .metrics import clear_reports, instruments, stop_server
        from .goodput import ledger as _goodput_ledger

        # final-flush the attribution ledger and mark the process down
        # before the endpoint disappears
        _goodput_ledger.detach()
        instruments.up().set(0.0)
        stop_server()
        clear_reports()
        # engine shutdown already pushed/drained the final span batches;
        # rank 0 (or the single process) now owns writing the merged trace
        from . import tracing

        out = tracing.finalize(mode=mode, rank=rank0, world_size=world)
        if out:
            logger.info("merged trace written to %s (hvdprof report %s)",
                        out, out)
        # the black box only speaks on abnormal exit: a clean shutdown
        # just stops the watch and resets the recorder state
        from . import blackbox
        from .blackbox import watch as _watch

        _watch.stop_watch()
        blackbox.finalize()
    for fn in _shutdown_hooks:
        try:
            fn()
        except Exception:
            logger.exception("shutdown hook %r failed", fn)


def is_initialized() -> bool:
    return _state.initialized


def _require_init() -> _GlobalState:
    if not _state.initialized:
        raise NotInitializedError(
            "horovod_tpu has not been initialized; call hvd.init() first."
        )
    return _state


def rank() -> int:
    """Global rank of the caller (`operations.cc:665-668`)."""
    st = _require_init()
    if st.mode == "cluster":
        r = _rank_ctx.get()
        return 0 if r is None else r
    return st.rank0


def size() -> int:
    """Number of ranks (`operations.cc:677-680`)."""
    return _require_init().size


def local_rank() -> int:
    """Rank within the host / ICI domain (`operations.cc:670-674`)."""
    st = _require_init()
    if st.mode == "cluster":
        return rank()
    return st.local_rank0


def local_size() -> int:
    return _require_init().local_size


def cross_rank() -> int:
    """Host index / DCN-domain rank (`operations.cc` cross accessors)."""
    st = _require_init()
    if st.mode == "cluster":
        return 0
    return st.cross_rank0


def cross_size() -> int:
    return _require_init().cross_size


def mesh():
    """The 1-D rank mesh (axis name ``"hvd"``) collectives execute over."""
    return _require_init().mesh


def num_replicas() -> int:
    """Total devices participating in the SPMD fast path (= mesh size).

    In standalone mode this exceeds ``size()``: one process drives all local
    chips and the jitted step data-parallelizes over them.
    """
    return int(np.prod(list(_require_init().mesh.shape.values())))


def rank_device(r: Optional[int] = None):
    st = _require_init()
    return st.rank_devices[rank() if r is None else r]


def _engine():
    st = _require_init()
    return st.engine


def set_thread_rank(r: Optional[int]) -> None:
    """Bind the calling thread to rank ``r`` (local-cluster worker threads)."""
    _rank_ctx.set(r)


def is_homogeneous() -> bool:
    """True if every node in the job has the same number of ranks
    (reference `common/basics.py:122-129`). The launcher computes this
    GLOBAL fact over the whole hostfile and exports it identically to
    every rank as ``HVD_UNIFORM_LOCAL_SIZE`` (0 when heterogeneous) — a
    rank-local ``size == local_size * cross_size`` test is NOT exact
    (e.g. node sizes 4,2,1,1 satisfy it on one rank). Jobs without the
    launcher env (standalone / thread-cluster) are single-node and
    homogeneous by construction."""
    _require_init()
    uniform = os.environ.get("HVD_UNIFORM_LOCAL_SIZE")
    if uniform:  # empty string == unset (a wrapper's `export VAR=`)
        try:
            return int(uniform) > 0
        except ValueError:
            raise ValueError(
                f"HVD_UNIFORM_LOCAL_SIZE={uniform!r} is not an integer; "
                "the launcher exports the uniform local size (0 when "
                "hosts hold unequal rank counts)")
    return True


# --- build-capability probes: parity with horovod/common/basics.py ------------
def mpi_threads_supported() -> bool:
    return False


def mpi_enabled() -> bool:
    """Runtime-mode probe (`basics.py:151-160`): MPI is never the control
    or data plane here — the coordinator service + XLA collectives are."""
    return False


def gloo_enabled() -> bool:
    """Runtime-mode probe (`basics.py:171-179`): reports whether the
    non-MPI (coordinated / jax.distributed) control plane is active, the
    role Gloo mode plays in the reference."""
    return is_initialized()


def mpi_built() -> bool:
    return False


def gloo_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def mlsl_built() -> bool:
    return False


def xla_built() -> bool:
    """TPU-native data plane: XLA collectives over ICI/DCN."""
    return True
