"""``setup_s`` split by what the program says it was doing.

The program records host spans of its own set-up
(``horovod_tpu/metrics/phases.py``): ``import`` (the package's imports),
``init`` (``hvd.init()``, with children ``init/*``), and for every program
JAX compiles ``compile/trace``, ``compile/lower``, ``compile/backend`` and,
inside a backend span that hit the persistent cache, ``compile/cache_read``;
a backend span carries the cache's ``outcome``. They lie on
``time.perf_counter``, the clock ``setup_s`` is taken on, so the set-up's are
those that ended before ``T_START + setup_s``: the reference check's compiles
come after the window and are cut off by time, not by name.

Every instant goes to the innermost span that covers it
(``phases.self_seconds``), so the six phase metrics never count a nested span
twice, and with ``setup_unattributed_s`` (``setup_s`` less the union of those
spans) they sum to ``setup_s``. What no span covers: the interpreter's start,
``import jax`` and reaching the chip (``harness.require_devices`` runs before
the program is imported), flax and optax, making arrays, the first execution
of each program (``compile_s`` less trace, lower, cache read and backend) and
the warm-up steps.

A program without ``metrics/phases.py`` gives nothing to read: every reader
returns None and the line leaves its metric out.
"""

from __future__ import annotations

import sys
from typing import Dict, Optional

from . import harness

#: metric -> the span names it reads (a name and its ``name/...`` children)
PHASES = {
    "setup_import_s": "import",
    "setup_init_s": "init",
    "setup_trace_s": "compile/trace",
    "setup_lower_s": "compile/lower",
    "setup_cache_read_s": "compile/cache_read",
    "setup_backend_compile_s": "compile/backend",
}


def t_start() -> Optional[float]:
    """``chipbench.run``'s ``T_START``: the module is ``__main__`` under
    ``python -m``, and ``chipbench.run`` where a test imports it."""
    for name in ("__main__", "chipbench.run"):
        at = getattr(sys.modules.get(name), "T_START", None)
        if at is not None:
            return at
    return None


def metric_of(span_name: str) -> Optional[str]:
    for metric, name in PHASES.items():
        if span_name == name or span_name.startswith(name + "/"):
            return metric
    return None


def split(spans, start: float, setup_s: float) -> Dict[str, float]:
    """The eight metrics from the program's ``spans``, for a set-up that
    ran from ``start`` for ``setup_s`` seconds."""
    from horovod_tpu.metrics import phases

    ours = [s for s in spans
            if s.end <= start + setup_s and metric_of(s.name) is not None]
    out = dict.fromkeys(PHASES, 0.0)
    for name, seconds in phases.self_seconds(ours).items():
        out[metric_of(name)] += seconds
    out["setup_unattributed_s"] = setup_s - phases.union_seconds(
        (s.start, s.end) for s in ours)
    lookups = [s.outcome for s in ours
               if s.name == "compile/backend" and s.outcome]
    if lookups:   # none where the persistent cache is off
        out["setup_cache_hit_share"] = 100.0 * lookups.count("hit") \
            / len(lookups)
    return out


def read(window: harness.Window, metric: str) -> Optional[float]:
    """One metric of :func:`split` for the run ``window`` closes, or None
    where the program records no spans."""
    try:
        from horovod_tpu.metrics import phases
    except ImportError:
        return None
    start = t_start()
    if start is None:
        return None
    spans = phases.spans()
    if metric == "setup_unattributed_s":   # said once a run, not eight times
        from horovod_tpu.metrics import instruments

        later = sum(s.end > start + window.end_to_end["setup_s"]
                    for s in spans)
        harness.say(f"set-up spans: {len(spans) - later}, {later} after the "
                    f"window opened, "
                    f"{int(instruments.phase_spans_dropped().value)} dropped "
                    f"at the program's cap")
    return split(spans, start, window.end_to_end["setup_s"]).get(metric)
