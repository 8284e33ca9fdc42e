"""Env-driven fault injection for the control plane (docs/fault-tolerance.md).

``HOROVOD_FAULT_SPEC`` (grammar in :mod:`.spec`) describes deterministic
faults — connection drops, stalls, partial writes, corrupted/truncated
frames — injected at named points of the coordinator wire on either side.
The harness exists so the hardening in `runtime/coordinator.py` (reconnect,
replay, heartbeats, CRC frame checks) is provable from tests and
chaos drills rather than only observable in production incidents.

Usage from instrumented code::

    faults = faultinject.for_rank(rank)       # None when no spec is set
    if faults is not None:
        faults.fire("tick")                   # named-point hook
        sock = faults.wrap(sock)              # frame-granular faults

The spec is re-read from the environment on every :func:`for_rank` call, so
tests can monkeypatch ``HOROVOD_FAULT_SPEC`` per scenario; with the variable
unset the layer costs one dict lookup and adds nothing to the hot path.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional, Tuple

from .injector import FaultSocket, Injector, Partition
from .spec import FaultRule, parse_spec

__all__ = ["FaultRule", "FaultSocket", "Injector", "Partition", "parse_spec",
           "for_rank", "shared_for_rank", "reset_shared",
           "partition_for_rank"]

ENV_VAR = "HOROVOD_FAULT_SPEC"

# long-lived injectors for callers that re-resolve per event (the integrity
# layer, collective enqueue): hit counters must survive across calls, unlike
# the fresh instance for_rank() hands a controller that keeps its own ref.
# Keyed on (rank, spec text) so a monkeypatched spec starts fresh counters.
_shared: Dict[Tuple[int, str], Injector] = {}
_shared_lock = threading.Lock()


def for_rank(rank: int) -> Optional[Injector]:
    """Build this rank's injector from ``HOROVOD_FAULT_SPEC``; None when the
    spec is unset/empty or matches no rule for this rank."""
    text = os.environ.get(ENV_VAR, "").strip()
    if not text:
        return None
    inj = Injector(parse_spec(text), rank)
    return inj if inj.active() else None


def shared_for_rank(rank: int) -> Optional[Injector]:
    """Like :func:`for_rank` but returns one cached injector per
    (rank, spec) for the process's lifetime, so per-event callers get
    cumulative hit counting. Cleared on ``hvd.shutdown()``."""
    text = os.environ.get(ENV_VAR, "").strip()
    if not text:
        return None
    key = (rank, text)
    with _shared_lock:
        inj = _shared.get(key)
        if inj is None:
            inj = Injector(parse_spec(text), rank)
            _shared[key] = inj
    return inj if inj.active() else None


def partition_for_rank(rank: int) -> Optional[Partition]:
    """This rank's active :class:`Partition` rule, if any — used by KV-side
    callers (the leadership lease) that must observe the cut without owning
    a wrapped socket. Shares the process-cached injector so the partition
    clock matches what the sockets see."""
    inj = shared_for_rank(rank)
    return None if inj is None else inj.partition


def reset_shared() -> None:
    """Drop cached injectors (and their hit counters); a shutdown/re-init
    cycle replays specs from hit 1, mirroring the auto-name counter reset
    in `ops/collective_ops.py`."""
    with _shared_lock:
        _shared.clear()
