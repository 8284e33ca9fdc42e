"""Host spans of the compiled path: what a process did before its first step.

The eager planes have their own telemetry; the compiled path
(``hvd.init()`` -> ``spmd.make_train_step``) has no host code in its loop,
so what is worth a span there is set-up: the package import, ``hvd.init()``,
and every program JAX traces, lowers and compiles or loads from its
persistent cache. This module is that path's one host-span API
(docs/observability.md, "Reading a slow start"):

* :func:`phase` — a context manager around a piece of host work;
* :func:`record` — the same for an interval that is already over;
* :func:`install_jax_listeners` — turns JAX's own compile events
  (``jax.monitoring``) into ``compile/*`` spans and ``hvd_compile*``
  counters, by program.

Spans are kept on ``time.perf_counter`` in a bounded list that outlives
``hvd.shutdown()``; each also feeds ``hvd_phase_seconds_total{phase}`` (its
**self** time: the interval less what spans nested in it on the same thread
cover, so the phases of one thread add up to wall time and nested
``compile/trace`` events are not counted twice) and ``hvd_phase_total``.
Where ``jax`` is loaded a :func:`phase` also holds a
``jax.profiler.TraceAnnotation("hvd/<name>")``, so under an active profile
the span lies on the profiler's clock beside the device's operations. The
module itself never imports ``jax``.
"""

from __future__ import annotations

import logging
import sys
import threading
import time
from typing import Dict, Iterable, List, NamedTuple, Optional

from . import instruments

logger = logging.getLogger("horovod_tpu")

#: spans kept; later ones are counted in ``hvd_phase_spans_dropped_total``
MAX_SPANS = 4096
#: closed spans a thread remembers as possible children of one still open,
#: listed or not (a step's trace has thousands of direct children); past it
#: the oldest are forgotten and stay in their parent's self time
MAX_PENDING = 16384
#: distinct ``program`` labels of ``hvd_compiles_total``; the rest are
#: ``_other`` (every eager operation compiles a program of its own)
MAX_PROGRAM_LABELS = 48
#: a ``compile/trace`` event shorter than this is counted and not listed: a
#: step's trace passes through thousands of ``jnp`` functions, each a nested
#: trace event of microseconds (7,136 for gpt2-medium's step, 89 of them a
#: millisecond or more)
MIN_TRACE_SPAN_S = 1e-3
#: slack when deciding that a span computed from a duration lies inside
#: another: JAX times its events on ``time.time``
_NEST_SLACK_S = 1e-4


class Span(NamedTuple):
    id: int
    name: str
    program: str             # the jitted function of a ``compile/*`` span
    start: float             # perf_counter
    end: float
    parent: Optional[int]    # id of the span around it, once that has closed
    thread: int
    outcome: str             # ``compile/backend``: hit | miss | unkept | ""


class Recorder:
    """The span list of one process (tests make their own)."""

    def __init__(self, max_spans: int = MAX_SPANS):
        self.max_spans = max_spans
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self._next_id = 0
        self._local = threading.local()

    def record(self, name: str, start: float, end: float, program: str = "",
               outcome: str = "", keep: bool = True) -> None:
        """Count one closed span and, if ``keep``, list it. Spans of a
        thread close in the order they end, so what it closed since
        ``start`` is nested in this one: those spans get it as ``parent``,
        and what they cover leaves its self time. A span that is not kept
        takes no lock: its count waits in the thread's tally for the next
        listed span that closes there (a trace is followed by its lowering,
        or closes inside a longer trace)."""
        local = self._local
        try:
            pending = local.pending   # (list index or None, start, end)
        except AttributeError:
            pending, local.tally = [], {}
            local.pending = pending
        cover, children = 0.0, []
        while pending and pending[-1][1] >= start - _NEST_SLACK_S:
            index, c_start, c_end = pending.pop()
            cover += c_end - c_start
            if index is not None:
                children.append(index)
        own = max(0.0, end - start - cover)
        tally = local.tally
        count, seconds = tally.get(name, (0, 0.0))
        tally[name] = (count + 1, seconds + own)
        index = None
        if keep:
            with self._lock:
                span_id = self._next_id
                self._next_id += 1
                for child in children:
                    self._spans[child] = self._spans[child]._replace(
                        parent=span_id)
                if len(self._spans) < self.max_spans:
                    index = len(self._spans)
                    self._spans.append(Span(
                        span_id, name, program, start, end, None,
                        threading.get_ident(), outcome))
            if index is None:
                instruments.phase_spans_dropped().inc()
            for phase_name, (count, seconds) in tally.items():
                instruments.phase_seconds().labels(phase=phase_name).inc(
                    seconds)
                instruments.phase_count().labels(phase=phase_name).inc(count)
            tally.clear()
        pending.append((index, start, end))
        if len(pending) > 2 * MAX_PENDING:   # in batches: the cut copies
            del pending[:-MAX_PENDING]

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)


_RECORDER = Recorder()


def reset() -> Recorder:
    """Swap in an empty recorder (tests)."""
    global _RECORDER
    _RECORDER = Recorder()
    return _RECORDER


def spans() -> List[Span]:
    """Every span kept so far, in closing order."""
    return _RECORDER.spans()


def record(name: str, start: float, end: float, program: str = "",
           outcome: str = "", keep: bool = True) -> None:
    """Keep a span whose interval (``perf_counter``) is already over."""
    _RECORDER.record(name, start, end, program, outcome, keep)


class phase:
    """``with phase("init"):`` — a host span from enter to exit."""

    __slots__ = ("name", "program", "_start", "_annotation")

    def __init__(self, name: str, program: str = ""):
        self.name = name
        self.program = program

    def __enter__(self):
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)   # None while jax imports
        self._annotation = None
        if profiler is not None:
            self._annotation = profiler.TraceAnnotation(f"hvd/{self.name}")
            self._annotation.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        record(self.name, self._start, end, self.program)
        return False


# ---------------------------------------------------------- reading spans
def union_seconds(intervals: Iterable[tuple]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def self_seconds(spans: Iterable[Span]) -> Dict[str, float]:
    """Wall time by span name, every instant counted once: it goes to the
    innermost span that covers it (the one that started last). So a name's
    figure is the union of its spans less what spans nested in them cover,
    and the figures sum to the union of all spans, whatever the threads."""
    edges = []
    for i, s in enumerate(spans):
        if s.end > s.start:
            edges.append((s.start, 1, i, s))
            edges.append((s.end, 0, i, s))
    edges.sort(key=lambda e: e[:3])
    out: Dict[str, float] = {}
    active: Dict[int, Span] = {}
    last = 0.0
    for at, opens, i, s in edges:
        if active and at > last:
            inner = max(active.values(), key=lambda a: (a.start, -a.end))
            out[inner.name] = out.get(inner.name, 0.0) + at - last
        last = at
        if opens:
            active[i] = s
        else:
            del active[i]
    return out


# -------------------------------------------------- JAX's compile events
_DURATION_PHASE = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile/cache_read",
}
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_OUTCOME = {"/jax/compilation_cache/cache_hits": "hit",
            "/jax/compilation_cache/cache_misses": "miss"}

_lock = threading.Lock()   # the install flag and the label set
_installed = False
_program_labels: set = set()
_cache_state = threading.local()   # .outcome of the request in flight


def program_name(fun_name: str) -> str:
    """One label for a program: the trace event names ``step``, the lower
    and backend events ``jit(step)``."""
    for prefix in ("jit(", "pmap("):
        if fun_name.startswith(prefix) and fun_name.endswith(")"):
            return fun_name[len(prefix):-1]
    return fun_name


def _program_label(program: str) -> str:
    if program in _program_labels:
        return program
    with _lock:
        if len(_program_labels) < MAX_PROGRAM_LABELS:
            _program_labels.add(program)
            return program
    return "_other"


def _on_duration(event: str, seconds: float, fun_name: str = "", **_) -> None:
    end = time.perf_counter()
    try:
        if event == _SAVED:   # negative where loading took longer
            instruments.compile_seconds_saved().inc(max(0.0, seconds))
            return
        name = _DURATION_PHASE.get(event)
        if name is None:
            return
        if name == "compile/trace" and seconds < MIN_TRACE_SPAN_S:
            record(name, end - seconds, end, keep=False)
            return
        program, outcome = program_name(fun_name), ""
        if name == "compile/backend":
            outcome = _cache_state.__dict__.pop("outcome", "")
            instruments.compiles().labels(
                program=_program_label(program)).inc()
        record(name, end - seconds, end, program, outcome)
    except Exception:   # a listener must never fail JAX's compile
        logger.exception("phases: compile event %s not recorded", event)


def _on_event(event: str, **_) -> None:
    try:
        if event == _REQUEST:
            # neither hit nor miss follows where the program is compiled and
            # not written: under JAX's compile-time or size threshold
            _cache_state.outcome = "unkept"
            instruments.compile_cache_requests().inc()
        elif event in _OUTCOME:
            _cache_state.outcome = _OUTCOME[event]
            instruments.compile_cache().labels(outcome=_OUTCOME[event]).inc()
    except Exception:
        logger.exception("phases: cache event %s not recorded", event)


def install_jax_listeners() -> None:
    """Register the two listeners, once a process: ``jax.monitoring`` has no
    way to unregister. They resolve the registry and the recorder at event
    time, so ``reset_registry()`` and :func:`reset` keep working."""
    global _installed
    import jax

    with _lock:
        if _installed:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _installed = True
