"""What every job shares: finding a cell's files by name, the device gate,
set-up bookkeeping (first calls, compilations), the profiler slice, and the
``Window`` that per-layer metric readers are handed.

Nothing here knows a particular cell, configuration, architecture, mix or
metric: those are files found by name (``workloads/``, ``configs/``,
``families/``, ``objectives/``, ``mixes/``, ``jobs/``, ``layer_metrics/``,
``op_classes/``).
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import pkgutil
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from . import trace_reduce, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: what a family module gives (``families/<model_type>.py``)
FAMILY_NAMES = ("build_model", "reference_forward", "train_flops_per_token",
                "attention_train_costs", "expected_first_loss", "REHEARSAL")

#: what an objective module gives (``objectives/<name>.py``)
OBJECTIVE_NAMES = ("make_batches", "loss", "model_inputs", "abstract_batch",
                   "first_loss")


class BenchmarkError(Exception):
    """The benchmark cannot run as asked (no chip, unknown cell, ...)."""


def say(message: str) -> None:
    """Progress goes to stderr and to earlier stdout lines, never last."""
    print(message, file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


# -------------------------------------------------------------------- cell
@dataclass
class Cell:
    name: str
    spec: dict            # workloads/<name>.json
    config: dict          # configs/<config>.json
    mix: dict             # mixes/<traffic>.json

    @property
    def chips(self) -> int:
        return int(self.spec["chips"])

    @property
    def job(self) -> str:
        return self.mix["job"]

    @property
    def vocab_rows(self) -> int:
        return int(self.config["assumed"]["padded_vocab_size"]["value"])

    @property
    def family(self):
        """The module ``families/<model_type>.py``: what of the cell is its
        architecture's (:data:`FAMILY_NAMES`)."""
        return load_family(self.config["model_type"])

    @property
    def objective(self):
        """The module ``objectives/<name>.py`` a training mix names: what of
        the cell is its training objective's (:data:`OBJECTIVE_NAMES`)."""
        if "objective" not in self.mix:
            raise BenchmarkError(
                f"chipbench/mixes/{self.spec['traffic']}.json has no "
                f"\"objective\": a training mix names its "
                f"chipbench/objectives/<name>.py")
        return load_objective(self.mix["objective"])


def load_module(folder: str, name: str, wanted: tuple, why: str):
    """``chipbench/<folder>/<name>.py``, which has to give ``wanted``."""
    path = f"chipbench/{folder}/{name}.py"
    module_name = f"{__package__}.{folder}.{name}"
    try:
        module = importlib.import_module(module_name)
    except ModuleNotFoundError as exc:
        if exc.name != module_name:
            raise
        raise BenchmarkError(f"no {path} {why}")
    missing = [n for n in wanted if not hasattr(module, n)]
    if missing:
        raise BenchmarkError(f"{path} lacks {missing}")
    return module


def load_family(model_type: str):
    return load_module("families", model_type, FAMILY_NAMES,
                       f"for model_type {model_type!r}")


def load_objective(name: str):
    return load_module("objectives", name, OBJECTIVE_NAMES,
                       f"for the mix's objective {name!r}")


def load_cell(name: str, rehearse: bool = False) -> Cell:
    try:
        spec = load_json("workloads", f"{name}.json")
    except FileNotFoundError:
        raise BenchmarkError(f"no cell chipbench/workloads/{name}.json")
    config = load_json("configs", f"{spec['config']}.json")
    mix = traffic.load(spec["traffic"])
    if rehearse:   # the family's toy size, its table padded like the real one
        config = {**config, **load_family(config["model_type"]).REHEARSAL}
        config["assumed"] = {**config["assumed"], "padded_vocab_size": {
            "value": -(-config["vocab_size"] // 128) * 128}}
        mix = {**mix, **mix.get("rehearsal", {})}
    return Cell(name, spec, config, mix)


def declared_metrics(cell: str) -> Dict[str, List[dict]]:
    """The ``end_to_end`` and ``per_layer`` entries that apply to ``cell``
    (an entry without ``workloads`` applies to all): from ``BENCHMARK.json``
    or, for a cell it does not list yet, from ``pending/<cell>.json``, which
    holds the entries a later PR adds to it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if cell not in {w["name"] for w in bench["workloads"]}:
        try:
            pending = load_json("pending", f"{cell}.json")
        except FileNotFoundError:
            raise BenchmarkError(f"neither BENCHMARK.json nor chipbench/"
                                 f"pending/ lists a workload {cell!r}")
        for kind in ("end_to_end", "per_layer"):
            bench[kind] = bench[kind] + pending[kind]
    return {kind: [m for m in bench[kind]
                   if cell in m.get("workloads", [cell])]
            for kind in ("end_to_end", "per_layer")}


# ------------------------------------------------------------------ device
def require_devices(chips: int, rehearse: bool) -> dict:
    """The peaks entry of the attached device kind. Anything but ``chips``
    or more TPU chips of a kind in ``peaks.json`` is an error, never a
    smaller run; ``--rehearse`` takes whatever backend there is."""
    import jax

    devices = jax.devices()
    peaks = load_json("peaks.json")
    kind = devices[0].device_kind
    if rehearse:
        if len(devices) < chips:
            raise BenchmarkError(f"rehearsal needs {chips} devices, JAX "
                                 f"found {len(devices)}")
        return next(iter(peaks.values()))
    if devices[0].platform != "tpu":
        raise BenchmarkError(f"no accelerator: JAX found platform "
                             f"{devices[0].platform!r}")
    if kind not in peaks:
        raise BenchmarkError(f"no peaks recorded for device kind {kind!r}; "
                             f"add it to chipbench/peaks.json with its source")
    if len(devices) < chips:
        raise BenchmarkError(f"the cell needs {chips} chips, JAX found "
                             f"{len(devices)}")
    return peaks[kind]


def memory_peak_bytes(devices) -> int:
    """Peak device memory on the fullest chip, as JAX's ``memory_stats``
    report it. ``peak_bytes_in_use`` counts the buffers JAX holds; what a
    running program allocates for its temporaries is reserved beside them
    and counted under ``peak_bytes_reserved`` (PERF.md section 6, PR 22).
    The peak while the job's main program runs is the buffers in use then
    plus that reservation, so call this with the job's state still on the
    device and before any larger program of the benchmark's own."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, stats.get("peak_bytes_in_use", 0),
                   stats.get("bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0))
    return int(peak)


def device_report(memory_peak: int) -> dict:
    import jax

    first = jax.devices()[0]
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": memory_peak}


# ------------------------------------------------------------------ set-up
class CompileCounter:
    """Counts programs JAX hands to the backend (a cache hit included: a
    program that was not warmed up either way). ``jax.monitoring`` has no
    way to unregister, so one counter lives for the process."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _seconds, **_):
        if name == self.EVENT:
            self.count += 1


@dataclass
class Context:
    """One run: what was asked, and the bookkeeping of its set-up."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_start: float                      # perf_counter at process start
    peak: dict
    compiles: CompileCounter
    first_calls: List[tuple] = field(default_factory=list)
    in_setup: bool = True

    def first_call(self, program: str, fn: Callable, *args) -> Any:
        """Call ``fn`` for the first time (it traces, compiles or loads
        from the cache, and runs once) and record the host time."""
        import jax

        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        seconds = time.perf_counter() - t0
        self.first_calls.append((program, seconds, self.in_setup))
        say(f"  first call of {program}: {seconds:.2f} s")
        return out

    def open_window(self) -> float:
        """Set-up ends here: the seconds since the process started."""
        self.in_setup = False
        return time.perf_counter() - self.t_start


# ------------------------------------------------------------------- trace
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")


@contextlib.contextmanager
def profiler_slice():
    """Trace the block into a fixed directory inside the checkout (emptied
    first); read it back with :func:`trace_summary`."""
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(TRACE_DIR)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def trace_summary(units: int, host_span_names=()) -> trace_reduce.TraceSummary:
    path = trace_reduce.find_xplane(TRACE_DIR)
    return trace_reduce.summarize(trace_reduce.read_xplane(path), units,
                                  host_span_names)


# ------------------------------------------------------------------ window
@dataclass
class Window:
    """What a job hands back, and what per-layer readers read."""
    cell: Cell
    peak: dict
    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    measured: Dict[str, Any]             # the job's own numbers, by name
    counters: Dict[str, Any]             # program counters over the window
    first_calls: List[tuple]
    memory_peak_bytes: int
    trace: Optional[trace_reduce.TraceSummary] = None
    notes: List[str] = field(default_factory=list)


def layer_metric_modules() -> list:
    """Every module of ``chipbench/layer_metrics``, by listing it."""
    from . import layer_metrics

    return [importlib.import_module(f"{layer_metrics.__name__}.{m.name}")
            for m in pkgutil.iter_modules(layer_metrics.__path__)]


def read_layer_metrics(window: Window) -> Dict[str, float]:
    """Every reader that applies to the cell's job; one that finds nothing
    to read returns None and is left out."""
    return {mod.NAME: mod.read(window) for mod in layer_metric_modules()
            if window.cell.job in mod.JOBS}
