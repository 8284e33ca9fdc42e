"""``chipbench/setup_phases.py`` on hand-made spans: the cut by time, nesting
counted once, the sum, and a program that records nothing."""

import sys
import types

import pytest

from chipbench import setup_phases

phases = pytest.importorskip("horovod_tpu.metrics.phases")

SECONDS = ("setup_import_s", "setup_init_s", "setup_trace_s", "setup_lower_s",
           "setup_cache_read_s", "setup_backend_compile_s",
           "setup_unattributed_s")


def span(name, start, end, outcome="", program=""):
    return phases.Span(0, name, program, start, end, None, 1, outcome)


#: a process that started at 100 and opened its window at 140
SPANS = [
    span("import", 103, 105),
    span("init/engine", 105.5, 105.9), span("init", 105, 106),
    # init_params: compiled, written
    span("compile/trace", 107, 108, program="<lambda>"),
    span("compile/lower", 108, 109, program="<lambda>"),
    span("compile/backend", 109, 119, "miss", "<lambda>"),
    # an eager operation: compiled every run, never written
    span("compile/backend", 119.5, 120, "unkept", "broadcast_in_dim"),
    # the step: a trace with a nested jit's trace, loaded from the cache
    span("compile/trace", 121, 122, program="_ssd_fwd"),
    span("compile/trace", 120, 124, program="step"),
    span("compile/lower", 124, 127, program="step"),
    span("compile/cache_read", 127.5, 133.5),
    span("compile/backend", 127, 134, "hit", "step"),
    # after the window opened: the reference check's programs, a name the
    # set-up's programs share, and the shutdown
    span("compile/trace", 175, 176, program="<lambda>"),
    span("compile/backend", 176, 186, "miss", "<lambda>"),
    span("shutdown", 190, 191),
]


def test_the_split_counts_every_second_of_set_up_once():
    got = setup_phases.split(SPANS, 100.0, 40.0)
    assert got == pytest.approx({
        "setup_import_s": 2.0, "setup_init_s": 1.0, "setup_trace_s": 5.0,
        "setup_lower_s": 4.0, "setup_cache_read_s": 6.0,
        "setup_backend_compile_s": 10 + 0.5 + 1.0,
        "setup_unattributed_s": 40 - 29.5,
        "setup_cache_hit_share": 100.0 / 3})
    assert sum(got[name] for name in SECONDS) == pytest.approx(40.0)


def test_what_compiled_after_the_window_opened_is_left_out():
    early = [s for s in SPANS if s.end <= 140]
    assert setup_phases.split(SPANS, 100.0, 40.0) == \
        setup_phases.split(early, 100.0, 40.0)
    later = setup_phases.split(SPANS, 100.0, 95.0)   # the same process, read late
    assert later["setup_backend_compile_s"] == pytest.approx(21.5)
    assert later["setup_cache_hit_share"] == pytest.approx(25.0)
    assert "shutdown" not in later and later["setup_init_s"] == 1.0


def test_no_lookup_is_no_hit_share():
    got = setup_phases.split([span("import", 1, 2),
                              span("compile/backend", 3, 4)], 0.0, 10.0)
    assert "setup_cache_hit_share" not in got
    assert got["setup_backend_compile_s"] == 1.0
    assert got["setup_unattributed_s"] == 8.0


def test_metric_of_reads_a_name_and_its_children():
    assert setup_phases.metric_of("init/engine") == "setup_init_s"
    assert setup_phases.metric_of("compile/cache_read") == "setup_cache_read_s"
    assert setup_phases.metric_of("compile/backend") == \
        "setup_backend_compile_s"
    assert setup_phases.metric_of("initial") is None
    assert setup_phases.metric_of("shutdown") is None


def window(setup_s):
    return types.SimpleNamespace(end_to_end={"setup_s": setup_s})


@pytest.mark.parametrize("name", SECONDS + ("setup_cache_hit_share",))
def test_a_program_that_records_no_spans_gives_nothing_to_read(
        monkeypatch, name):
    """The parent of the PR that added the spans: the readers return None
    and do not raise, so the line leaves the metric out."""
    monkeypatch.setitem(sys.modules, "chipbench.run",
                        types.SimpleNamespace(T_START=100.0))
    monkeypatch.setitem(sys.modules, "horovod_tpu.metrics.phases", None)
    monkeypatch.delattr(sys.modules["horovod_tpu.metrics"], "phases")
    assert setup_phases.read(window(40.0), name) is None


def test_read_takes_t_start_from_the_run_module(monkeypatch):
    monkeypatch.setitem(sys.modules, "chipbench.run",
                        types.SimpleNamespace(T_START=100.0))
    recorder = phases.reset()
    try:
        for s in SPANS:
            recorder.record(s.name, s.start, s.end, s.program, s.outcome)
        assert setup_phases.read(window(40.0), "setup_lower_s") == \
            pytest.approx(4.0)
        assert setup_phases.read(window(40.0), "setup_unattributed_s") == \
            pytest.approx(10.5)
        monkeypatch.delitem(sys.modules, "chipbench.run")
        assert setup_phases.read(window(40.0), "setup_lower_s") is None
    finally:
        phases.reset()
