"""Dependency-free metrics primitives: Counter / Gauge / Histogram.

The reference stack leans on the Chrome-trace timeline for post-mortem
analysis; this module is the live-signals counterpart.  Everything here
is plain Python on purpose — no prometheus_client, no numpy — so the
registry can run inside the engine tick loop and inside the coordinator
server thread without adding imports to the hot path.

Design points:

* Metrics are created through a ``MetricsRegistry`` and identified by
  name.  Creating the same name twice returns the same object (so
  instrumentation sites don't need to coordinate import order).
* Labels follow the Prometheus child model: ``c.labels(op="allreduce")``
  returns a per-label-set child sharing the parent's storage.
* ``snapshot()`` produces a plain-dict representation that survives the
  wire codec (runtime/wire.py) and merges across ranks with
  ``merge_snapshots``: counters and histograms sum; gauges combine per
  their declared ``agg`` mode (``max`` / ``min`` / ``sum`` / ``last``).
* ``render_prometheus`` turns one (possibly merged) snapshot into the
  Prometheus text exposition format.
"""

from __future__ import annotations

import math
import threading


def _label_key(labels: dict) -> tuple:
    return tuple(sorted(labels.items()))


def _fmt_value(v) -> str:
    if v == float("inf"):
        return "+Inf"
    if v == float("-inf"):
        return "-Inf"
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _fmt_labels(items) -> str:
    if not items:
        return ""
    parts = []
    for k, v in items:
        s = str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
        parts.append(f'{k}="{s}"')
    return "{" + ",".join(parts) + "}"


def exponential_buckets(start: float, factor: float, count: int):
    """Prometheus-style exponential bucket bounds (upper edges, no +Inf)."""
    assert start > 0 and factor > 1 and count >= 1
    return [start * factor ** i for i in range(count)]


#: Default latency buckets: 20 exponential buckets from 50us to ~26s.
LATENCY_BUCKETS = exponential_buckets(50e-6, 2.0, 20)


def quantile_from_buckets(buckets, counts, q):
    """Estimate the q-quantile from per-bucket (non-cumulative) counts.

    ``buckets`` are the upper bounds (no +Inf); ``counts`` has one extra
    trailing slot for the implicit +Inf overflow bucket, matching the
    Histogram snapshot layout.  Returns the upper bound of the bucket the
    quantile falls in, ``2 * buckets[-1]`` when it lands in the overflow
    bucket, or ``None`` when there are no observations.  Shared by the
    anomaly watch (serving p99), the SLO engine, hvdtop and the serving
    bench so every consumer agrees on the estimate.
    """
    total = sum(counts)
    if total <= 0:
        return None
    target = q * total
    acc = 0
    for i, b in enumerate(buckets):
        acc += counts[i] if i < len(counts) else 0
        if acc >= target:
            return b
    return buckets[-1] * 2.0 if buckets else None


class _Child:
    """One label-set instance of a metric."""

    __slots__ = ("_metric", "_key")

    def __init__(self, metric, key):
        self._metric = metric
        self._key = key

    def inc(self, amount=1.0):
        self._metric._inc(self._key, amount)

    def set(self, value):
        self._metric._set(self._key, value)

    def observe(self, value):
        self._metric._observe(self._key, value)

    @property
    def value(self):
        return self._metric._get(self._key)


class _Metric:
    kind = "untyped"

    def __init__(self, name, help, label_names=(), **kw):
        self.name = name
        self.help = help
        self.label_names = tuple(label_names)
        self._lock = threading.Lock()
        self._children = {}

    def labels(self, **labels):
        if set(labels) != set(self.label_names):
            raise ValueError(
                f"{self.name}: expected labels {self.label_names}, got "
                f"{tuple(labels)}")
        key = _label_key(labels)
        with self._lock:
            if key not in self._children:
                self._children[key] = self._zero()
        return _Child(self, key)

    def _default_child(self):
        if self.label_names:
            raise ValueError(f"{self.name}: labeled metric needs .labels()")
        return self.labels()

    # -- storage ops, overridden per kind ---------------------------------
    def _zero(self):
        return 0.0

    def _inc(self, key, amount):
        raise NotImplementedError

    def _set(self, key, value):
        raise NotImplementedError

    def _observe(self, key, value):
        raise NotImplementedError

    def _get(self, key):
        with self._lock:
            return self._children.get(key)

    def snapshot_values(self):
        with self._lock:
            return {k: self._copy_value(v) for k, v in self._children.items()}

    @staticmethod
    def _copy_value(v):
        return v


class Counter(_Metric):
    kind = "counter"

    def inc(self, amount=1.0):
        self._default_child().inc(amount)

    @property
    def value(self):
        return self._default_child().value

    def _inc(self, key, amount):
        if amount < 0:
            raise ValueError(f"{self.name}: counters only go up")
        with self._lock:
            self._children[key] = self._children.get(key, 0.0) + amount

    def _set(self, key, value):
        raise TypeError(f"{self.name}: counters have no set()")

    def _observe(self, key, value):
        raise TypeError(f"{self.name}: counters have no observe()")


class Gauge(_Metric):
    kind = "gauge"

    def __init__(self, name, help, label_names=(), agg="last"):
        super().__init__(name, help, label_names)
        if agg not in ("last", "max", "min", "sum"):
            raise ValueError(f"{name}: unknown gauge agg {agg!r}")
        self.agg = agg

    def set(self, value):
        self._default_child().set(value)

    def inc(self, amount=1.0):
        self._default_child().inc(amount)

    @property
    def value(self):
        return self._default_child().value

    def _inc(self, key, amount):
        with self._lock:
            self._children[key] = self._children.get(key, 0.0) + amount

    def _set(self, key, value):
        with self._lock:
            self._children[key] = float(value)

    def set_each(self, values):
        """``labels(<the one label>=str(i)).set(v)`` for every ``i, v`` of
        ``values``, under one lock: a vector that arrives whole (an
        expert's load a report, from inside a step)."""
        (label,) = self.label_names
        with self._lock:
            for i, value in enumerate(values):
                self._children[((label, str(i)),)] = float(value)

    def _observe(self, key, value):
        raise TypeError(f"{self.name}: gauges have no observe()")


class _HistValue:
    __slots__ = ("counts", "sum", "count")

    def __init__(self, nbuckets):
        self.counts = [0] * nbuckets  # per-bucket (non-cumulative) counts
        self.sum = 0.0
        self.count = 0


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name, help, label_names=(), buckets=None):
        super().__init__(name, help, label_names)
        bounds = list(buckets if buckets is not None else LATENCY_BUCKETS)
        if sorted(bounds) != bounds:
            raise ValueError(f"{name}: bucket bounds must be sorted")
        if bounds and bounds[-1] == float("inf"):
            bounds = bounds[:-1]
        self.buckets = bounds  # upper bounds, +Inf implicit

    def observe(self, value):
        self._default_child().observe(value)

    def _zero(self):
        return _HistValue(len(self.buckets) + 1)

    def _inc(self, key, amount):
        raise TypeError(f"{self.name}: histograms have no inc()")

    def _set(self, key, value):
        raise TypeError(f"{self.name}: histograms have no set()")

    def _observe(self, key, value):
        v = float(value)
        idx = len(self.buckets)
        for i, b in enumerate(self.buckets):
            if v <= b:
                idx = i
                break
        with self._lock:
            h = self._children.get(key)
            if h is None:
                h = self._children[key] = self._zero()
            h.counts[idx] += 1
            h.sum += v
            h.count += 1

    @staticmethod
    def _copy_value(v):
        c = _HistValue(len(v.counts))
        c.counts = list(v.counts)
        c.sum = v.sum
        c.count = v.count
        return c


class MetricsRegistry:
    """Holds every metric of one process; snapshot/merge/render live here."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics = {}

    # -- factories --------------------------------------------------------
    def counter(self, name, help="", labels=()):
        return self._get_or_create(Counter, name, help, labels)

    def gauge(self, name, help="", labels=(), agg="last"):
        return self._get_or_create(Gauge, name, help, labels, agg=agg)

    def histogram(self, name, help="", labels=(), buckets=None):
        return self._get_or_create(Histogram, name, help, labels,
                                   buckets=buckets)

    def _get_or_create(self, cls, name, help, labels, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if not isinstance(m, cls):
                    raise TypeError(
                        f"{name} already registered as {m.kind}, not "
                        f"{cls.kind}")
                return m
            m = cls(name, help, labels, **kw)
            self._metrics[name] = m
            return m

    def get(self, name):
        with self._lock:
            return self._metrics.get(name)

    # -- snapshot ---------------------------------------------------------
    def snapshot(self) -> dict:
        """Plain-dict snapshot: wire-codec friendly and merge-ready.

        ``{name: {"kind", "help", "agg"?, "buckets"?, "series":
        [{"labels": {...}, ...value fields...}]}}``
        """
        out = {}
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            entry = {"kind": m.kind, "help": m.help, "series": []}
            if m.kind == "gauge":
                entry["agg"] = m.agg
            if m.kind == "histogram":
                entry["buckets"] = list(m.buckets)
            for key, val in sorted(m.snapshot_values().items()):
                series = {"labels": dict(key)}
                if m.kind == "histogram":
                    series["counts"] = list(val.counts)
                    series["sum"] = val.sum
                    series["count"] = val.count
                else:
                    series["value"] = float(val)
                entry["series"].append(series)
            out[m.name] = entry
        return out


# -- process-global registry ----------------------------------------------

_GLOBAL = MetricsRegistry()
_GLOBAL_LOCK = threading.Lock()


def get_registry() -> MetricsRegistry:
    """The process-global registry every instrumentation site writes to.
    One per process (threads of a local cluster share it — their counters
    sum naturally, matching the cross-process merge semantics)."""
    return _GLOBAL


def reset_registry() -> MetricsRegistry:
    """Swap in a fresh registry (tests).  Instrument accessors re-resolve
    on every call, so no handle goes stale."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        _GLOBAL = MetricsRegistry()
        return _GLOBAL


# -- cross-rank merge ------------------------------------------------------

def merge_snapshots(snapshots) -> dict:
    """Merge per-rank snapshots: counters/histograms sum, gauges use their
    declared ``agg`` mode.  Later snapshots win for ``last`` gauges."""
    merged = {}
    for snap in snapshots:
        if not snap:
            continue
        for name, entry in snap.items():
            dst = merged.get(name)
            if dst is None:
                dst = merged[name] = {
                    "kind": entry["kind"],
                    "help": entry.get("help", ""),
                    "series": [],
                    "_index": {},
                }
                if "agg" in entry:
                    dst["agg"] = entry["agg"]
                if "buckets" in entry:
                    dst["buckets"] = list(entry["buckets"])
            index = dst["_index"]
            for series in entry.get("series", []):
                key = _label_key(series.get("labels", {}))
                cur = index.get(key)
                if cur is None:
                    cur = {"labels": dict(series.get("labels", {}))}
                    if entry["kind"] == "histogram":
                        cur["counts"] = [0] * len(series.get("counts", []))
                        cur["sum"] = 0.0
                        cur["count"] = 0
                    index[key] = cur
                    dst["series"].append(cur)
                if entry["kind"] == "histogram":
                    counts = series.get("counts", [])
                    if len(cur["counts"]) < len(counts):
                        cur["counts"] += [0] * (len(counts) - len(cur["counts"]))
                    for i, c in enumerate(counts):
                        cur["counts"][i] += c
                    cur["sum"] += series.get("sum", 0.0)
                    cur["count"] += series.get("count", 0)
                elif entry["kind"] == "counter":
                    cur["value"] = cur.get("value", 0.0) + series.get("value", 0.0)
                else:  # gauge
                    agg = dst.get("agg", "last")
                    v = series.get("value", 0.0)
                    if "value" not in cur:
                        cur["value"] = v
                    elif agg == "max":
                        cur["value"] = max(cur["value"], v)
                    elif agg == "min":
                        cur["value"] = min(cur["value"], v)
                    elif agg == "sum":
                        cur["value"] += v
                    else:
                        cur["value"] = v
    for entry in merged.values():
        entry.pop("_index", None)
    return merged


# -- Prometheus text exposition --------------------------------------------

def render_prometheus(snapshot: dict) -> str:
    """Render one (merged) snapshot in the Prometheus text format."""
    lines = []
    for name in sorted(snapshot):
        entry = snapshot[name]
        kind = entry["kind"]
        if entry.get("help"):
            lines.append(f"# HELP {name} {entry['help']}")
        lines.append(f"# TYPE {name} {kind}")
        for series in entry.get("series", []):
            items = sorted(series.get("labels", {}).items())
            if kind == "histogram":
                bounds = entry.get("buckets", [])
                cum = 0
                counts = series.get("counts", [])
                for i, b in enumerate(bounds):
                    cum += counts[i] if i < len(counts) else 0
                    lbl = _fmt_labels(items + [("le", _fmt_value(b))])
                    lines.append(f"{name}_bucket{lbl} {cum}")
                total = series.get("count", 0)
                lbl = _fmt_labels(items + [("le", "+Inf")])
                lines.append(f"{name}_bucket{lbl} {total}")
                lines.append(
                    f"{name}_sum{_fmt_labels(items)} "
                    f"{_fmt_value(series.get('sum', 0.0))}")
                lines.append(f"{name}_count{_fmt_labels(items)} {total}")
            else:
                lines.append(
                    f"{name}{_fmt_labels(items)} "
                    f"{_fmt_value(series.get('value', 0.0))}")
    return "\n".join(lines) + "\n"


def parse_prometheus(text: str) -> dict:
    """Tiny parser for the text format: ``{sample_name: {label_tuple:
    value}}``.  Used by tests and the CI smoke check — intentionally
    strict: raises ValueError on lines it can't parse."""
    out = {}
    for raw in text.splitlines():
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        if "{" in line:
            name, rest = line.split("{", 1)
            lbl_str, _, val_str = rest.rpartition("}")
            labels = []
            for part in _split_labels(lbl_str):
                if not part:
                    continue
                k, _, v = part.partition("=")
                if not v.startswith('"') or not v.endswith('"'):
                    raise ValueError(f"bad label in line: {raw!r}")
                labels.append((k.strip(), _unescape_label(v[1:-1])))
            key = tuple(sorted(labels))
        else:
            name, _, val_str = line.partition(" ")
            key = ()
        val_str = val_str.strip()
        if not name or not val_str:
            raise ValueError(f"bad sample line: {raw!r}")
        try:
            value = float(val_str.replace("+Inf", "inf"))
        except ValueError:
            raise ValueError(f"bad value in line: {raw!r}")
        out.setdefault(name.strip(), {})[key] = value
    return out


def _unescape_label(s: str) -> str:
    """Inverse of the ``_fmt_labels`` escaping.  Walks escape sequences
    left to right — chained ``str.replace`` would corrupt ``\\\\n`` (an
    escaped backslash followed by 'n') into a newline."""
    if "\\" not in s:
        return s
    out, i, n = [], 0, len(s)
    while i < n:
        ch = s[i]
        if ch == "\\" and i + 1 < n:
            nxt = s[i + 1]
            if nxt == "n":
                out.append("\n")
                i += 2
                continue
            if nxt in ('"', "\\"):
                out.append(nxt)
                i += 2
                continue
        out.append(ch)
        i += 1
    return "".join(out)


def _split_labels(s: str):
    """Split 'a="x",b="y,z"' on commas outside quotes."""
    parts, cur, inq, esc = [], [], False, False
    for ch in s:
        if esc:
            cur.append(ch)
            esc = False
            continue
        if ch == "\\":
            cur.append(ch)
            esc = True
            continue
        if ch == '"':
            inq = not inq
            cur.append(ch)
            continue
        if ch == "," and not inq:
            parts.append("".join(cur).strip())
            cur = []
            continue
        cur.append(ch)
    if cur:
        parts.append("".join(cur).strip())
    return parts
