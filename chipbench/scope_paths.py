"""Device time by raw scope path: an overlay on the trace's operations for
the readers of one architecture's own parts.

``op_scopes.table`` books every operation to one scope *class*, and the
classes partition the step (``blocks_fwd`` / ``blocks_bwd`` / ... sum to
``xla_ops_ms``). A part that lives **inside** a block (the routed
feed-forward, the short conv's gating) is read here by a regex over the
operation's own path instead, forward, backward and recomputed alike, so
that it is counted without being taken out of the block it belongs to.
"""

from __future__ import annotations

import re
from typing import Optional

from . import harness, op_scopes, trace_reduce


def ms_under(window: harness.Window, pattern: str) -> Optional[float]:
    """ms a step of the first chip's operations, of any op class, whose
    scope path matches ``pattern`` (``re.search``); None for an untraced
    run, a trace that carries no scope, or a program in which no operation
    is under such a path (which is not the same as taking no time)."""
    if window.trace is None:
        return None
    first = window.trace.first
    scopes = op_scopes.read(
        trace_reduce.find_xplane(harness.TRACE_DIR)).get(first.device)
    if not scopes:
        return None
    regex = re.compile(pattern)
    parts = [seconds for op, seconds in first.op_s.items()
             if regex.search(scopes.get(op, ""))]
    return 1e3 * sum(parts) / window.trace.units if parts else None
