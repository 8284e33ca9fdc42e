"""From a profiler trace (``.xplane.pb``) to the numbers metrics read.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A device
plane (``/device:TPU:<n>``) carries one line of XLA's operations; a trace
taken on the CPU backend carries them on the host plane, marked by an
``hlo_op`` stat, and is reduced the same way so that the reduction can be
rehearsed and tested without a chip (never reported as a device number).

All arithmetic is on plain ``(name, start, end)`` tuples in seconds:

* busy time is the *union* of the operations' intervals, so nested or
  overlapping events are not counted twice;
* an operation's own time is its interval minus what its nested children
  cover, so class sums add up to the busy union;
* a class's span is the union of its operations' intervals and of the
  intervals in which one of its asynchronous operations is in flight (the
  ``Async XLA Ops`` line); its exposed part is the part of that span during
  which no operation of another class runs on that device;
* idle gaps are the window minus the busy union, attributed to the host
  spans (``jax.profiler.TraceAnnotation``) that overlap them.

An operation is named ``"<opcode> <name>"`` (:func:`op_name`); its class
comes from ``op_classes/<class>.json``: a regex over that name and a
priority; what nothing matches is ``xla_op``.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
Event = Tuple[str, float, float]        # name, start s, end s
Interval = Tuple[float, float]
UNMATCHED = "xla_op"
UNATTRIBUTED = "host_unattributed"


# ---------------------------------------------------------------- classes
def load_classes(directory: Optional[str] = None) -> List[Tuple[str, "re.Pattern"]]:
    """``[(class, compiled regex)]`` from every ``op_classes/*.json``,
    highest priority first (ties by name, so the order is fixed)."""
    directory = directory or os.path.join(HERE, "op_classes")
    specs = []
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        with open(path) as f:
            specs.append(json.load(f))
    specs.sort(key=lambda s: (-s["priority"], s["name"]))
    return [(s["name"], re.compile(s["regex"])) for s in specs]


def classify(name: str, classes) -> str:
    for cls, pattern in classes:
        if pattern.search(name):
            return cls
    return UNMATCHED


# -------------------------------------------------------------- intervals
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of ``a`` that ``b`` does not cover (both any order); one
    sweep over the two sorted unions."""
    out: List[Interval] = []
    b = union(b)
    j = 0
    for s, e in union(a):
        while j < len(b) and b[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    a = union(a)
    return subtract(a, subtract(a, b))


def own_times(events: Sequence[Event]) -> List[float]:
    """Each event's duration minus the part its nested children cover, in
    the order given. Events on one line nest or follow one another."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [events[i][2] - events[i][1] for i in range(len(events))]
    stack: List[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            own[parent] -= min(e, events[parent][2]) - s
        stack.append(i)
    return [max(0.0, t) for t in own]


# ----------------------------------------------------------------- reading
@dataclass
class Trace:
    """What one ``.xplane.pb`` holds that the reduction needs."""
    devices: Dict[str, List[Event]] = field(default_factory=dict)
    in_flight: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


#: a TPU device plane's lines: the core's own timeline, and the spans
#: during which an asynchronous operation (``*-start`` .. ``*-done``) is in
#: flight beside it
OP_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"

_HLO = re.compile(r"^(%[^ ]+) = .*?\s([a-z][a-z0-9\-]*)\(")


def op_name(raw: str) -> str:
    """``"<opcode> <name>"`` for an event of an op line.

    On a TPU plane an event is named by its whole HLO instruction,
    ``%fusion.15 = (f32[...]) fusion(...), kind=...``: keep the opcode and
    the instruction's name. A Pallas kernel is a ``custom-call`` whose
    target is ``tpu_custom_call`` and is given that opcode. The CPU backend
    names an event ``dot.6``: the opcode is what precedes the number."""
    m = _HLO.match(raw)
    if m is None:
        return f"{raw.split('.')[0]} {raw}"
    name, opcode = m.groups()
    if opcode == "custom-call" and 'custom_call_target="tpu_custom_call"' in raw:
        opcode = "tpu_custom_call"
    return f"{opcode} {name}"


def _spans(line) -> List[Event]:
    return [(op_name(ev.name), ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9)
            for ev in line.events if ev.duration_ns > 0]


def read_xplane(path: str) -> Trace:
    """Device planes' op lines and the host plane's named spans
    (``path`` may be gzipped)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    trace = Trace()
    cpu_ops: List[Event] = []
    # device planes first: only a trace without one needs the host's ops
    for plane in sorted(data.planes,
                        key=lambda p: not p.name.startswith("/device:")):
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OP_LINE:
                    trace.devices[plane.name] = _spans(line)
                elif line.name == ASYNC_LINE:
                    trace.in_flight[plane.name] = _spans(line)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns <= 0 or ev.name.startswith("$"):
                        continue   # "$file:line fn" are Python frames
                    start = ev.start_ns * 1e-9
                    end = start + ev.duration_ns * 1e-9
                    if not trace.devices and any(
                            k == "hlo_op" for k, _ in ev.stats):
                        cpu_ops.append((op_name(ev.name), start, end))
                    else:
                        trace.host.append((ev.name, start, end))
    if not trace.devices and cpu_ops:   # the CPU backend: no device plane
        trace.devices["/host:CPU"] = cpu_ops
    return trace


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


# ---------------------------------------------------------------- reducing
@dataclass
class DeviceSummary:
    device: str
    window: Interval
    busy_s: float
    class_s: Dict[str, float]                 # own time by class
    span_s: Dict[str, float]                  # class running or in flight
    exposed_s: Dict[str, float]               # ... with no other class busy
    op_s: Dict[str, float]                    # own time by op name
    op_class: Dict[str, str]
    gaps: List[Interval]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else 0.0


def reduce_device(device: str, events: Sequence[Event], classes,
                  window: Optional[Interval] = None,
                  in_flight: Sequence[Event] = ()) -> DeviceSummary:
    """One device's summary. ``window`` defaults to first op start .. last
    op end; events are clipped to it. ``in_flight`` are the spans of
    asynchronous operations beside the op line: they add to their class's
    ``span_s`` and ``exposed_s``, not to busy or own time."""
    if window is None:
        window = (min(e[1] for e in events), max(e[2] for e in events))
    lo, hi = window

    def clip(evs):
        return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                if e > lo and s < hi]

    events, in_flight = clip(events), clip(in_flight)
    busy = union((s, e) for _, s, e in events)
    own = own_times(events)
    class_s: Dict[str, float] = defaultdict(float)
    op_s: Dict[str, float] = defaultdict(float)
    op_class: Dict[str, str] = {}
    by_class: Dict[str, List[Interval]] = defaultdict(list)
    for (name, s, e), t in zip(events, own):
        cls = op_class.setdefault(name, classify(name, classes))
        class_s[cls] += t
        op_s[name] += t
        by_class[cls].append((s, e))
    spans_of = {cls: list(spans) for cls, spans in by_class.items()}
    for name, s, e in in_flight:
        cls = classify(name, classes)
        if cls != UNMATCHED:
            spans_of.setdefault(cls, []).append((s, e))
    span_s, exposed = {}, {}
    for cls, spans in spans_of.items():
        others = [iv for c, ivs in by_class.items() if c != cls for iv in ivs]
        span_s[cls] = total(union(spans))
        exposed[cls] = total(subtract(spans, others))
    return DeviceSummary(device, window, total(busy), dict(class_s), span_s,
                         exposed, dict(op_s), op_class,
                         subtract([window], busy))


def attribute_gaps(gaps: Sequence[Interval], host: Sequence[Event],
                   names: Iterable[str]) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each gap's overlap with
    the host spans called one of ``names``; the rest is unattributed."""
    out: Dict[str, float] = {}
    covered: List[Interval] = []
    for name in names:
        spans = [(s, e) for n, s, e in host if n == name]
        hit = intersect(gaps, spans)
        if hit:
            out[name] = total(hit)
            covered.extend(hit)
    rest = total(subtract(gaps, covered))
    if rest > 0:
        out[UNATTRIBUTED] = rest
    return out


@dataclass
class TraceSummary:
    """The reduced trace handed to the per-layer metric readers."""
    devices: List[DeviceSummary]
    idle_by_host_span: Dict[str, float]
    units: int                                # steps (or engine steps) traced

    @property
    def first(self) -> DeviceSummary:
        return self.devices[0]

    @property
    def busy_s(self) -> float:
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    @property
    def window_s(self) -> float:
        return sum(d.window_s for d in self.devices) / len(self.devices)

    def ms_per_unit(self, table: str, cls: str) -> float:
        """``class_s``, ``span_s`` or ``exposed_s`` of ``cls`` on the first
        device, in ms a traced unit (a step)."""
        return 1e3 * getattr(self.first, table).get(cls, 0.0) / self.units

    def breakdown(self, top: int = 10) -> dict:
        d = self.first
        ops = sorted(d.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_host_span.items(),
                      key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[f"{n} [{d.op_class[n]}]", s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def summarize(trace: Trace, units: int, host_span_names: Iterable[str] = (),
              classes=None) -> TraceSummary:
    """Reduce every device of ``trace``; gaps are attributed on the first
    device (sorted by name: ``/device:TPU:0``)."""
    if not trace.devices:
        raise ValueError("the trace holds no device operation")
    classes = load_classes() if classes is None else classes
    devices = [reduce_device(name, trace.devices[name], classes,
                             in_flight=trace.in_flight.get(name, ()))
               for name in sorted(trace.devices)]
    idle = attribute_gaps(devices[0].gaps, trace.host, host_span_names)
    return TraceSummary(devices, idle, units)
