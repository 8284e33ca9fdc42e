"""metrics/phases.py: the compiled path's host spans and compile counters
(docs/observability.md, "Reading a slow start"). CPU only; nothing here is
a device number."""

import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

import horovod_tpu as hvd
from horovod_tpu.metrics import (get_registry, instruments, phases,
                                 reset_registry)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    reset_registry()
    phases.reset()
    # an earlier test file of this worker that called ``hvd.init()`` and
    # jitted a model has filled the process's label set to its cap
    # (MAX_PROGRAM_LABELS): every program here would count as "_other"
    monkeypatch.setattr(phases, "_program_labels", set())
    phases.install_jax_listeners()
    # the toy functions here trace in microseconds: list them all the same
    monkeypatch.setattr(phases, "MIN_TRACE_SPAN_S", 0.0)
    yield
    reset_registry()
    phases.reset()


def values(instrument):
    return {dict(k).get(instrument.label_names[0]) if instrument.label_names
            else None: v for k, v in instrument.snapshot_values().items()}


def make_probe(name):
    """A new function object each call: the same program to XLA, a program
    ``jax.jit`` has not seen."""
    def probe(x):
        return (x * 3 + 1).sum()
    probe.__name__ = probe.__qualname__ = name
    return probe


def of(program):
    return [s for s in phases.spans() if s.program == program]


# ------------------------------------------------------------ the listener
def test_a_compile_is_three_spans_and_one_count_and_a_second_call_none():
    f = jax.jit(make_probe("probe_spans"))
    x = jnp.ones((4, 4), jnp.float32)
    before = time.perf_counter()
    f(x)
    after = time.perf_counter()
    mine = of("probe_spans")
    assert [s.name for s in mine] == ["compile/trace", "compile/lower",
                                      "compile/backend"]
    for s in mine:
        assert before - 1e-3 <= s.start <= s.end <= after
        assert s.thread == threading.get_ident()
    assert [a.end <= b.start + 1e-3 for a, b in zip(mine, mine[1:])] == \
        [True, True]
    assert values(instruments.compiles())["probe_spans"] == 1
    seconds = values(instruments.phase_seconds())
    assert {"compile/trace", "compile/lower", "compile/backend"} <= set(seconds)
    n = len(phases.spans())
    f(x)
    assert len(phases.spans()) == n
    assert values(instruments.compiles())["probe_spans"] == 1


def test_nested_jits_are_children_of_the_trace_around_them():
    inner = jax.jit(make_probe("probe_inner"))

    def outer(x):
        return inner(x) + inner(x * 2)
    outer.__name__ = "probe_outer"
    jax.jit(outer)(jnp.ones((3,), jnp.float32))
    (trace,) = [s for s in of("probe_outer") if s.name == "compile/trace"]
    nested = of("probe_inner")
    assert nested and all(s.name == "compile/trace" and s.parent == trace.id
                          for s in nested)
    # the counter holds self time: the nested trace is not counted twice
    traces = [s for s in phases.spans() if s.name == "compile/trace"]
    assert values(instruments.phase_seconds())["compile/trace"] == \
        pytest.approx(phases.self_seconds(traces)["compile/trace"], abs=2e-3)


def test_a_short_nested_trace_is_counted_and_not_listed(monkeypatch):
    monkeypatch.setattr(phases, "MIN_TRACE_SPAN_S", 1e-3)
    trace = "/jax/core/compile/jaxpr_trace_duration"
    phases._on_duration(trace, 2e-6, fun_name="add")   # before the step's
    time.sleep(0.002)
    t0 = time.perf_counter()
    for _ in range(2000):
        phases._on_duration(trace, 2e-6, fun_name="add")
    # an unlisted span takes no lock: its count waits for a listed one
    assert values(instruments.phase_count()) == {}
    time.sleep(0.002)
    phases._on_duration(trace, 0.0015, fun_name="block")
    assert values(instruments.phase_count()) == {"compile/trace": 2002}
    phases._on_duration(trace, time.perf_counter() - t0, fun_name="step")
    block, step = phases.spans()
    assert (block.program, step.program) == ("block", "step")
    assert block.parent == step.id
    assert values(instruments.phase_count()) == {"compile/trace": 2003}
    assert instruments.phase_spans_dropped().value == 0
    # self time: the step's interval once, the 2001 traces inside it left
    # out, the one before it beside it
    assert values(instruments.phase_seconds())["compile/trace"] == \
        pytest.approx(step.end - step.start + 2e-6, rel=1e-6)


@pytest.fixture
def persistent_cache(tmp_path):
    """A temporary persistent cache that keeps every program, the cache
    reset around the test (conftest resets the directory after it)."""
    from jax.experimental.compilation_cache import compilation_cache

    saved = (jax.config.jax_persistent_cache_min_compile_time_secs,
             jax.config.jax_persistent_cache_min_entry_size_bytes)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[0])
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", saved[1])


def test_a_cache_miss_then_a_hit_with_the_read_inside_the_backend_span(
        persistent_cache):
    x = jnp.ones((5, 5), jnp.float32)
    reset_registry()   # making ``x`` compiled a program of its own
    jax.jit(make_probe("probe_cache"))(x)
    (backend,) = [s for s in of("probe_cache") if s.name == "compile/backend"]
    assert backend.outcome == "miss"
    assert values(instruments.compile_cache()) == {"miss": 1}
    assert not [s for s in phases.spans() if s.name == "compile/cache_read"]

    jax.jit(make_probe("probe_cache"))(x)   # the same program, a new trace
    backends = [s for s in of("probe_cache") if s.name == "compile/backend"]
    assert [s.outcome for s in backends] == ["miss", "hit"]
    (read,) = [s for s in phases.spans() if s.name == "compile/cache_read"]
    assert read.parent == backends[1].id and read.program == ""
    assert backends[1].start - 1e-3 <= read.start <= read.end <= backends[1].end
    assert values(instruments.compile_cache()) == {"miss": 1, "hit": 1}
    assert instruments.compile_cache_requests().value == 2
    assert values(instruments.compiles())["probe_cache"] == 2
    assert instruments.compile_seconds_saved().value >= 0


def test_a_program_under_the_threshold_is_a_request_that_is_neither(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    compilation_cache.reset_cache()   # JAX's default: a second to compile
    x = jnp.ones((2,), jnp.float32)
    reset_registry()
    jax.jit(make_probe("probe_unkept"))(x)
    (backend,) = [s for s in of("probe_unkept") if s.name == "compile/backend"]
    assert backend.outcome == "unkept"
    assert instruments.compile_cache_requests().value == 1
    assert values(instruments.compile_cache()) == {}


def test_installing_twice_registers_once():
    from jax._src import monitoring

    phases.install_jax_listeners()
    phases.install_jax_listeners()
    assert monitoring.get_event_duration_listeners().count(
        phases._on_duration) == 1
    assert monitoring.get_event_listeners().count(phases._on_event) == 1


def test_the_listener_takes_what_jax_may_send_without_raising():
    phases._on_duration(phases._SAVED, -0.5)   # a load slower than a compile
    assert instruments.compile_seconds_saved().value == 0
    phases._on_duration("/jax/some/other_duration", 1.0, fun_name="f")
    phases._on_event("/jax/some/other_event")
    phases._on_duration("/jax/core/compile/backend_compile_duration", 0.25)
    (span,) = phases.spans()
    assert (span.name, span.program, span.outcome) == ("compile/backend", "", "")
    assert span.end - span.start == pytest.approx(0.25)


@pytest.mark.parametrize("fun_name,program", [
    ("step", "step"), ("jit(step)", "step"), ("pmap(step)", "step"),
    ("jit(<lambda>)", "<lambda>"), ("jit(_ssd_fwd)", "_ssd_fwd"),
    ("jit(", "jit("), ("", "")])
def test_one_label_for_a_program(fun_name, program):
    assert phases.program_name(fun_name) == program


def test_the_program_label_of_the_counter_is_capped(monkeypatch):
    monkeypatch.setattr(phases, "_program_labels", set())
    monkeypatch.setattr(phases, "MAX_PROGRAM_LABELS", 3)
    event = "/jax/core/compile/backend_compile_duration"
    for i in range(5):
        phases._on_duration(event, 0.0, fun_name=f"jit(op{i})")
    phases._on_duration(event, 0.0, fun_name="jit(op1)")
    assert values(instruments.compiles()) == {
        "op0": 1, "op1": 2, "op2": 1, "_other": 2}
    assert len({s.program for s in phases.spans()}) == 5   # every name kept


# --------------------------------------------------------------- phase()
def test_phase_nests_records_self_time_and_annotates_the_profile(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with phases.phase("outer"):
        time.sleep(0.02)
        with phases.phase("outer/inner", program="p"):
            time.sleep(0.03)
    inner, outer = phases.spans()
    assert (inner.name, inner.program, inner.parent) == ("outer/inner", "p",
                                                         outer.id)
    assert outer.parent is None and outer.start <= inner.start
    assert inner.end <= outer.end
    assert seen == [("enter", "hvd/outer"), ("enter", "hvd/outer/inner"),
                    ("exit", "hvd/outer/inner"), ("exit", "hvd/outer")]
    seconds, count = (values(instruments.phase_seconds()),
                      values(instruments.phase_count()))
    assert count == {"outer": 1, "outer/inner": 1}
    assert seconds["outer/inner"] == pytest.approx(inner.end - inner.start)
    assert seconds["outer"] == pytest.approx(
        (outer.end - outer.start) - (inner.end - inner.start))
    assert 0.02 <= seconds["outer"] < seconds["outer/inner"] + 0.02


def test_a_phase_that_raises_is_still_a_span():
    with pytest.raises(KeyError):
        with phases.phase("failing"):
            raise KeyError("x")
    assert [s.name for s in phases.spans()] == ["failing"]


def test_the_cap_drops_and_counts():
    recorder = phases.Recorder(max_spans=3)
    for i in range(5):
        recorder.record(f"p{i}", float(i), i + 0.5)
    recorder.record("around", -1.0, 9.0)   # adopts kept and dropped alike
    kept = recorder.spans()
    assert [s.name for s in kept] == ["p0", "p1", "p2"]
    assert {s.parent for s in kept} == {5}
    assert instruments.phase_spans_dropped().value == 3
    assert values(instruments.phase_count())["p4"] == 1   # counted all the same
    assert values(instruments.phase_seconds())["around"] == pytest.approx(7.5)


def test_a_thread_remembers_a_bounded_number_of_closed_spans(monkeypatch):
    monkeypatch.setattr(phases, "MAX_PENDING", 4)
    recorder = phases.Recorder()
    for i in range(20):
        recorder.record("tick", float(i), i + 0.5)
        assert len(recorder._local.pending) <= 8
    recorder.record("around", -1.0, 30.0)
    adopted = [s for s in recorder.spans() if s.parent == 20]
    assert 4 <= len(adopted) <= 8 and adopted[-1].start == 19.0
    # the forgotten ones stay in the parent's self time
    assert values(instruments.phase_seconds())["around"] == pytest.approx(
        31.0 - 0.5 * len(adopted))


def test_many_threads_lose_no_span():
    threads, each = 4 * (os.cpu_count() or 4), 200
    recorder = phases.reset()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(each):
                with phases.phase("busy"):
                    with phases.phase("busy/inner"):
                        pass
        workers = [threading.Thread(target=work) for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(interval)
    total = threads * each
    spans = recorder.spans()
    assert len(spans) + instruments.phase_spans_dropped().value == 2 * total
    assert len({s.id for s in spans}) == len(spans)
    assert values(instruments.phase_count()) == {"busy": total,
                                                 "busy/inner": total}
    by_id = {s.id: s for s in spans}
    for s in spans:
        if s.name == "busy/inner" and s.parent in by_id:
            assert by_id[s.parent].thread == s.thread


# ------------------------------------------------------- reading the spans
def span(name, start, end, i=0, thread=1):
    return phases.Span(i, name, "", start, end, None, thread, "")


@pytest.mark.parametrize("intervals,want", [
    ([], 0.0),
    ([(0, 1)], 1.0),
    ([(0, 1), (2, 3)], 2.0),
    ([(0, 2), (1, 3)], 3.0),
    ([(0, 10), (2, 3), (4, 5)], 10.0),
    ([(5, 6), (0, 1), (0.5, 5.5)], 6.0),
])
def test_union_seconds(intervals, want):
    assert phases.union_seconds(intervals) == pytest.approx(want)


@pytest.mark.parametrize("spans,want", [
    # a trace with two nested traces: a union, never a sum
    ([span("compile/trace", 0, 10), span("compile/trace", 1, 3),
      span("compile/trace", 4, 5)], {"compile/trace": 10}),
    # the cache read inside the backend span is not the backend's
    ([span("compile/backend", 0, 10), span("compile/cache_read", 1, 9)],
     {"compile/backend": 2, "compile/cache_read": 8}),
    # three levels, and a span beside them
    ([span("init", 0, 10), span("init/engine", 2, 8),
      span("compile/lower", 3, 4), span("import", 20, 21)],
     {"init": 4, "init/engine": 5, "compile/lower": 1, "import": 1}),
    # two threads compile at once: an instant is counted once, for the
    # span that started last
    ([span("compile/backend", 0, 6, thread=1),
      span("compile/lower", 4, 8, thread=2)],
     {"compile/backend": 4, "compile/lower": 4}),
    # an empty span is nothing
    ([span("init", 3, 3)], {}),
])
def test_self_seconds_counts_every_instant_once(spans, want):
    got = phases.self_seconds(spans)
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(
        phases.union_seconds((s.start, s.end) for s in spans))


# ------------------------------------------------------- where it is wired
def test_the_module_does_not_import_jax():
    code = (
        "import sys, types\n"
        "pkg = types.ModuleType('horovod_tpu')\n"
        f"pkg.__path__ = [{os.path.join(REPO, 'horovod_tpu')!r}]\n"
        "sys.modules['horovod_tpu'] = pkg\n"
        "import horovod_tpu.metrics.phases as phases\n"
        "with phases.phase('x'):\n"
        "    pass\n"
        "assert [s.name for s in phases.spans()] == ['x']\n"
        "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_importing_the_package_is_a_span():
    code = ("import time; t0 = time.perf_counter()\n"
            "import horovod_tpu\n"
            "t1 = time.perf_counter()\n"
            "from horovod_tpu.metrics import phases\n"
            "(s,) = [s for s in phases.spans() if s.name == 'import']\n"
            "assert t0 <= s.start < s.end <= t1, (t0, s, t1)\n"
            "assert s.end - s.start > 0.5 * (t1 - t0)\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_init_and_shutdown_are_spans_that_outlive_shutdown():
    hvd.init()
    hvd.init()   # idempotent: no second span
    jax.jit(make_probe("probe_run"))(jnp.ones((2, 2), jnp.float32))
    hvd.shutdown()
    hvd.shutdown()
    names = [s.name for s in phases.spans()]
    assert names.count("init") == 1 and names.count("shutdown") == 1
    by_name = {s.name: s for s in phases.spans()}
    assert by_name["init/engine"].parent == by_name["init"].id
    snapshot = hvd.metrics()
    phase_names = {s["labels"]["phase"]
                   for s in snapshot["hvd_phase_seconds_total"]["series"]}
    assert {"init", "init/engine", "shutdown", "compile/trace",
            "compile/lower", "compile/backend"} <= phase_names
    assert {s["labels"]["program"]: s["value"] for s in
            snapshot["hvd_compiles_total"]["series"]}["probe_run"] == 1
    assert "hvd_phase_total" in snapshot
    text = hvd.metrics(prometheus=True)
    assert 'hvd_compiles_total{program="probe_run"} 1' in text
    assert 'hvd_phase_total{phase="init"} 1' in text


def test_enabling_the_compile_cache_installs_the_listeners(monkeypatch):
    from horovod_tpu.utils import compile_cache

    monkeypatch.setattr(phases, "_installed", False)
    called = []
    monkeypatch.setattr(
        jax.monitoring, "register_event_duration_secs_listener", called.append)
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        called.append)
    compile_cache.enable()
    assert called == [phases._on_duration, phases._on_event]


def test_the_catalog_lists_every_new_name():
    with open(os.path.join(REPO, "docs", "metrics.md")) as f:
        catalog = f.read()
    for accessor in (instruments.phase_seconds, instruments.phase_count,
                     instruments.phase_spans_dropped, instruments.compiles,
                     instruments.compile_cache_requests,
                     instruments.compile_cache,
                     instruments.compile_seconds_saved):
        assert f"`{accessor().name}`" in catalog, accessor().name
    assert get_registry().get("hvd_phase_seconds_total") is not None
