"""Which part of the program each device operation belongs to.

JAX's name stack (Flax's module names, ``jax.named_scope``, and ``jvp(`` /
``transpose(`` for the forward and the backward pass) goes into every HLO
instruction's ``op_name``, and the profiler writes it into the trace: on a
TPU plane as the ``tf_op`` stat of an operation's *event metadata*,
``jit(step)/transpose(jvp(<model class>))/block_1/mlp_in/dot_general:``.
``jax.profiler.ProfileData`` hands out an event's own stats and not its
metadata's, so :func:`read` takes them from the ``.xplane.pb`` wire format
itself: a few dozen lines, no protobuf package. The CPU backend writes no
such stat; there the same path is in the program's ``HloProto``, which the
profiler keeps on the ``/host:metadata`` plane, so that the whole chain can
be rehearsed without a chip (never reported as a device number).

A scope path's class comes from ``scope_classes/<class>.json`` as an
operation's class comes from ``op_classes/``: a regex over the path and a
priority, the first match wins, and the class with the empty regex takes
what is left. A fusion carries the one path XLA gives it (its matmul's
where it has one): what is fused into another scope's fusion is booked
there.

    python3 -m chipbench.op_scopes <file.xplane.pb[.gz]> <steps traced>

prints one trace's table.
"""

from __future__ import annotations

import argparse
import functools
import gzip
import os
import sys
from collections import defaultdict
from typing import Dict, Iterator, Optional, Tuple, Union

from . import harness, trace_reduce

SCOPE_CLASSES = os.path.join(harness.HERE, "scope_classes")
HLO_PLANE, HLO_STAT, CPU_PLANE = "/host:metadata", "Hlo Proto", "/host:CPU"

# field numbers (tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto)
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_VALUE = 2                                    # of a map's entry
EVENT_METADATA_NAME, EVENT_METADATA_STATS = 2, 5
STAT_METADATA_ID, STAT_STR, STAT_BYTES, STAT_REF = 1, 5, 6, 7      # XStat
STAT_ID, STAT_NAME = 1, 2                                  # XStatMetadata
HLO_MODULE, MODULE_COMPUTATIONS, COMPUTATION_INSTRUCTIONS = 1, 3, 2
INSTRUCTION_NAME, INSTRUCTION_METADATA, OP_METADATA_OP_NAME = 1, 7, 2

Value = Union[int, memoryview]


def fields(buf: memoryview) -> Iterator[Tuple[int, Value]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint, the bytes (not parsed, so skipping costs nothing) for a
    length-delimited or fixed-width field."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        value = shift = 0
        while True:
            byte = buf[i]
            i += 1
            value |= (byte & 0x7F) << shift
            if byte < 0x80:
                return value
            shift += 7

    while i < n:
        tag = varint()
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            yield number, varint()
            continue
        if wire == 2:
            size = varint()
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not a message "
                             f"this reader knows")
        if i + size > n:
            raise ValueError(f"field {number} runs past the message's end")
        yield number, buf[i:i + size]
        i += size


def each(buf: memoryview, number: int) -> Iterator[Value]:
    """The values of a repeated field."""
    return (value for k, value in fields(buf) if k == number)


def text(value: Value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _plane(buf: memoryview):
    """``(name, [event metadata], {stat metadata id: name})`` of a plane;
    its lines (field 3, nearly all of a trace) are passed over."""
    name, events, stat_names = "", [], {}
    for number, value in fields(buf):
        if number == PLANE_NAME:
            name = text(value)
        elif number == PLANE_EVENT_METADATA:
            events.extend(each(value, MAP_VALUE))
        elif number == PLANE_STAT_METADATA:
            for entry in each(value, MAP_VALUE):
                meta = dict(fields(entry))
                stat_names[meta.get(STAT_ID, 0)] = text(
                    meta.get(STAT_NAME, b""))
    return name, events, stat_names


def _stat(event: memoryview, stat_names: Dict[int, str], wanted: str):
    """``(metadata name, the stat called wanted or None)``."""
    name, found = "", None
    for number, value in fields(event):
        if number == EVENT_METADATA_NAME:
            name = text(value)
        elif number == EVENT_METADATA_STATS:
            stat = dict(fields(value))
            if stat_names.get(stat.get(STAT_METADATA_ID)) != wanted:
                continue
            if STAT_REF in stat:       # a string kept once, as a stat's name
                found = stat_names.get(stat[STAT_REF], "").encode()
            else:
                found = stat.get(STAT_STR, stat.get(STAT_BYTES))
    return name, found


def _hlo_scopes(proto: memoryview) -> Dict[str, str]:
    """``{op name: op_name metadata}`` of every instruction of an
    ``HloProto`` that has one."""
    out = {}
    for module in each(proto, HLO_MODULE):
        for computation in each(module, MODULE_COMPUTATIONS):
            for instruction in each(computation, COMPUTATION_INSTRUCTIONS):
                inst = dict(fields(instruction))
                scope = dict(fields(inst.get(INSTRUCTION_METADATA, b""))
                             ).get(OP_METADATA_OP_NAME)
                if scope:
                    out[trace_reduce.op_name(
                        text(inst[INSTRUCTION_NAME]))] = text(scope)
    return out


@functools.lru_cache(maxsize=2)
def read(path: str) -> Dict[str, Dict[str, str]]:
    """``{device plane: {trace_reduce.op_name(metadata name): scope path}}``
    of an ``.xplane.pb`` (``path`` may be gzipped), under the plane names
    :func:`trace_reduce.read_xplane` gives. An operation with no scope (a
    copy the compiler inserted) gets ``""``; a plane whose trace holds no
    scope at all is left out."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    programs = []
    for plane in each(space, SPACE_PLANES):
        name, events, stat_names = _plane(plane)
        if name.startswith("/device:"):
            scopes = {}
            for event in events:
                raw, tf_op = _stat(event, stat_names, "tf_op")
                # "<name stack>:<op type>", the type empty under JAX
                scope = text(tf_op).rsplit(":", 1)[0] if tf_op else ""
                key = trace_reduce.op_name(raw)
                scopes[key] = scope or scopes.get(key, "")
            if any(scopes.values()):
                out[name] = scopes
        elif name == HLO_PLANE:
            programs = [_stat(event, stat_names, HLO_STAT)[1]
                        for event in events]
    if not out:                    # the CPU backend: no device plane
        from_hlo: Dict[str, str] = {}
        for proto in programs:
            if proto is not None:
                from_hlo.update(_hlo_scopes(proto))
        if from_hlo:
            out[CPU_PLANE] = from_hlo
    return out


def table(summary: trace_reduce.TraceSummary,
          path: str) -> Dict[Tuple[str, str], float]:
    """Own time of the first device's operations by (op class, scope
    class), in ms a traced unit; empty where ``path`` holds no scope."""
    first = summary.first
    scopes = read(path).get(first.device)
    if not scopes:
        return {}
    classes = trace_reduce.load_classes(SCOPE_CLASSES)
    out: Dict[Tuple[str, str], float] = defaultdict(float)
    for op, seconds in first.op_s.items():
        scope = trace_reduce.classify(scopes.get(op, ""), classes)
        out[first.op_class[op], scope] += 1e3 * seconds / summary.units
    return dict(out)


def scope_ms(window: harness.Window) -> Dict[Tuple[str, str], float]:
    """:func:`table` of the run's own trace; empty for an untraced run."""
    if window.trace is None:
        return {}
    return table(window.trace, trace_reduce.find_xplane(harness.TRACE_DIR))


def ms(window: harness.Window, op_class: str,
       scope_class: str) -> Optional[float]:
    """What a per-layer reader returns: ms a step of ``op_class`` under
    ``scope_class``, or None where no operation of the trace is under it:
    the program does not write that scope (or the trace carries none),
    which is not the same as taking no time."""
    by = scope_ms(window)
    if not any(scope == scope_class for _, scope in by):
        return None
    return by.get((op_class, scope_class), 0.0)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("path", help=".xplane.pb, may be gzipped")
    parser.add_argument("units", type=int, help="steps the trace holds")
    args = parser.parse_args(argv)
    summary = trace_reduce.summarize(trace_reduce.read_xplane(args.path),
                                     args.units)
    for (op_class, scope), value in sorted(table(summary, args.path).items()):
        print(f"{op_class:18s} {scope:18s} {value:10.4f} ms/step")
    return 0


if __name__ == "__main__":
    sys.exit(main())
