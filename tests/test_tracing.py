"""Cross-rank distributed tracing + hvdprof tests (docs/tracing.md).

Unit layer: the monotonic trace clock and the NTP-style offset pick, the
span recorder's ring buffer + drop accounting, the MSG_TRACE / MSG_CLOCK
wire codecs, the merged-trace writer's strict-JSON guarantee, the
analyzer's interval-union math, and the hvdprof CLI. Regression: the
Timeline's old clock-domain mixing (wall-clock ``ts`` stepping backward
under NTP) can no longer produce an end-before-begin span. Acceptance:
with ``HOROVOD_TRACE`` unset the engine allocates ZERO trace objects per
tick; with it set, a local cluster run leaves one strictly-valid merged
trace that hvdprof reports on. Integration: spans survive a
``conn_drop@frame`` fault and an elastic epoch bump (worker death) in
real 2-process jobs without corrupting the merged trace.
"""

import json
import os
import pickle
import subprocess
import sys
import time

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import testing, tracing
from horovod_tpu.metrics import instruments
from horovod_tpu.runtime import wire
from horovod_tpu.tracing import (K_COLLECTIVE, K_MARK, K_STEP, K_WAIT,
                                 T_DONE, T_ENQ, T_NEG, T_WIRE_END,
                                 T_WIRE_START, Span, SpanRecorder,
                                 allocation_count, analyzer, clock)
from horovod_tpu.tracing.cli import main as hvdprof_main
from horovod_tpu.tracing.spans import buffer_capacity
from horovod_tpu.tracing.writer import spans_to_events, write_merged
from horovod_tpu.utils.timeline import Timeline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_tracing(monkeypatch):
    """Tracing off and module state clean on both sides of every test."""
    monkeypatch.delenv("HOROVOD_TRACE", raising=False)
    monkeypatch.delenv("HOROVOD_TRACE_BUFFER", raising=False)
    tracing.reset_for_tests()
    yield
    tracing.reset_for_tests()


# ------------------------------------------------------------------- clock
class TestClock:
    def test_local_us_monotonic(self):
        stamps = [clock.local_us() for _ in range(200)]
        assert stamps == sorted(stamps)

    def test_trace_us_applies_offset(self):
        base = clock.trace_us()
        clock.set_offset_us(5_000_000)
        assert clock.trace_us() - base >= 5_000_000
        clock.reset()
        assert clock.offset_us() == 0

    def test_compute_offset_picks_min_rtt(self):
        # sample 2 has the smallest round trip -> its estimate wins:
        # offset = server - (t0 + t1)/2 = 5000 - 10 = 4990
        samples = [(0, 1000, 200), (0, 5000, 20), (0, 9999, 500)]
        assert clock.compute_offset_us(samples) == 4990

    def test_compute_offset_skips_negative_rtt(self):
        assert clock.compute_offset_us([(100, 50, 90)]) == 0

    def test_sync_offset_installs_probe_result(self):
        skew = 123_456

        def probe(t_send):
            return clock.local_us() + skew

        off = clock.sync_offset(probe, rounds=3)
        assert off == clock.offset_us()
        # the probe replies mid-roundtrip, so the estimate lands within
        # the observed RTT of the true skew
        assert abs(off - skew) < 50_000
        clock.reset()


class TestTimelineMonotonic:
    def test_wall_clock_step_cannot_reorder_spans(self, tmp_path,
                                                  monkeypatch):
        """Regression for the clock-domain mixing bug: the Timeline used to
        stamp events with ``time.time()``, so an NTP step between B and E
        produced an end-before-begin span. All stamps now come from the
        perf_counter-anchored trace clock — stepping the wall clock
        backward mid-span must not move ``ts`` backward."""
        path = tmp_path / "timeline.json"
        tl = Timeline(str(path))
        tl.negotiate_start("t0", rank=0)
        # simulate the wall clock stepping 1000 s into the past
        monkeypatch.setattr(time, "time", lambda: time.time_ns() / 1e9 - 1000)
        tl.op_start("t0", "ALLREDUCE")
        tl.op_end("t0")
        tl.close()
        events = json.loads(path.read_text())  # strictly valid array
        stamps = [e["ts"] for e in events if "ts" in e]
        assert stamps == sorted(stamps), \
            f"timeline stamps went backward: {stamps}"

    def test_closed_timeline_is_strict_json(self, tmp_path):
        path = tmp_path / "empty.json"
        Timeline(str(path)).close()
        assert json.loads(path.read_text()) == []


# ---------------------------------------------------------------- recorder
class TestSpanRecorder:
    def test_collective_lifecycle(self):
        rec = SpanRecorder(capacity=16)
        rec.begin_collective(3, "grad/w", "ALLREDUCE", 4096, t=100)
        rec.mark(3, "grad/w", T_NEG, 150)
        rec.set_fused(3, "grad/w", 4)
        rec.mark(3, "grad/w", T_WIRE_START, 160)
        rec.mark(3, "grad/w", T_WIRE_END, 400)
        rec.finish(3, "grad/w", 420)
        (sp,) = rec.drain()
        assert sp.kind == K_COLLECTIVE and sp.op == "ALLREDUCE"
        assert sp.nbytes == 4096 and sp.fused == 4
        assert sp.ts == [100, 150, 160, 400, 420]
        assert sp.span_id >> 40 == 4  # rank+1 in the high bits
        assert rec.open_count() == 0

    def test_mark_ignores_unknown_and_filled_slots(self):
        rec = SpanRecorder(capacity=16)
        rec.mark(0, "ghost", T_NEG, 1)  # never begun: no-op, no crash
        rec.begin_collective(0, "t", "ALLREDUCE", 0, t=10)
        rec.mark(0, "t", T_NEG, 20)
        rec.mark(0, "t", T_NEG, 99)  # first writer wins
        rec.finish(0, "t", 30)
        (sp,) = rec.drain()
        assert sp.ts[T_NEG] == 20

    def test_duplicate_open_name_pushes_previous(self):
        rec = SpanRecorder(capacity=16)
        rec.begin_collective(0, "t", "ALLREDUCE", 0, t=10)
        rec.begin_collective(0, "t", "ALLREDUCE", 0, t=50)
        rec.finish(0, "t", 60)
        spans = rec.drain()
        assert [sp.ts[T_ENQ] for sp in spans] == [10, 50]
        assert spans[0].ts[T_DONE] == 0  # the leaked one, pushed as-is

    def test_ring_buffer_drops_oldest_and_counts(self):
        before = instruments.trace_dropped_events().value
        rec = SpanRecorder(capacity=4)
        for i in range(10):
            rec.add_wait(0, t0=i, t1=i + 1)
        assert rec.pending() == 4
        kept = [sp.ts[0] for sp in rec.drain()]
        assert kept == [6, 7, 8, 9]  # oldest six dropped
        assert instruments.trace_dropped_events().value - before == 6

    def test_buffer_capacity_env(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_TRACE_BUFFER", "16")
        assert buffer_capacity() == 16
        monkeypatch.setenv("HOROVOD_TRACE_BUFFER", "not-a-number")
        assert buffer_capacity() == 65536
        monkeypatch.setenv("HOROVOD_TRACE_BUFFER", "-5")
        assert buffer_capacity() == 1

    def test_abort_discards_open_span(self):
        rec = SpanRecorder(capacity=4)
        rec.begin_collective(0, "t", "ALLREDUCE", 0, t=10)
        rec.abort(0, "t")
        assert rec.open_count() == 0 and rec.drain() == []


# -------------------------------------------------------------- wire codec
class TestWireCodec:
    def test_trace_batch_roundtrip(self):
        spans = [
            Span(K_COLLECTIVE, 1, "grad/dense/kernel", op="ALLREDUCE",
                 span_id=(2 << 40) | 7, nbytes=1 << 20, fused=3,
                 ts=[10, 20, 30, 40, 50]),
            Span(K_WAIT, 1, "WAIT", span_id=(2 << 40) | 8,
                 ts=[60, 70, 0, 0, 0]),
            Span(K_MARK, 1, "EPOCH_2", span_id=(2 << 40) | 9,
                 ts=[80, 0, 0, 0, 0]),
        ]
        sender, out = wire.decode_trace_batch(
            wire.encode_trace_batch(1, spans))
        assert sender == 1 and len(out) == 3
        for a, b in zip(spans, out):
            assert (a.kind, a.rank, a.name, a.op, a.span_id, a.nbytes,
                    a.fused, a.ts) == (b.kind, b.rank, b.name, b.op,
                                       b.span_id, b.nbytes, b.fused, b.ts)

    def test_empty_batch_roundtrip(self):
        sender, out = wire.decode_trace_batch(wire.encode_trace_batch(5, []))
        assert sender == 5 and out == []

    def test_clock_probe_and_reply_roundtrip(self):
        t = 1_234_567_890_123
        assert wire.decode_clock_probe(wire.encode_clock_probe(t)) == t
        server, tid = wire.decode_clock_reply(
            wire.encode_clock_reply(t + 5, 0xABCDEF0123))
        assert (server, tid) == (t + 5, 0xABCDEF0123)

    def test_trace_frame_roundtrip_through_framing(self):
        """A MSG_TRACE payload survives the full control-plane framing
        (length prefix + CRC + HMAC), like any other frame."""
        import socket
        import threading

        from horovod_tpu.runtime.coordinator import MSG_TRACE

        payload = wire.encode_trace_batch(
            1, [Span(K_WAIT, 1, "WAIT", ts=[1, 2, 0, 0, 0])])
        a, b = socket.socketpair()
        try:
            wire.send_frame(a, "s3cret", MSG_TRACE, 42, 1, payload)
            frame = wire.recv_frame(b, "s3cret", threading.Event())
        finally:
            a.close()
            b.close()
        assert (frame.msg_type, frame.seq, frame.rank) == (MSG_TRACE, 42, 1)
        assert frame.payload == payload


# --------------------------------------------------------- writer/analyzer
def _synthetic_spans():
    """Two ranks, one step each; rank 1 enqueues 300 us late (straggler)."""
    spans = []
    for rank, lag in ((0, 0), (1, 300)):
        step = Span(K_STEP, rank, "STEP", span_id=rank + 1,
                    ts=[1000, 11000, 0, 0, 0])
        coll = Span(K_COLLECTIVE, rank, "grad/w", op="ALLREDUCE",
                    span_id=((rank + 1) << 40) | 1, nbytes=4096,
                    ts=[2000 + lag, 3000, 3000, 5000, 5200])
        wait = Span(K_WAIT, rank, "WAIT", span_id=((rank + 1) << 40) | 2,
                    ts=[3000, 5000, 0, 0, 0])
        spans += [step, coll, wait]
    spans.append(Span(K_MARK, 0, "EPOCH_1", ts=[6000, 0, 0, 0, 0]))
    return spans


class TestWriterAndAnalyzer:
    def test_union_us_merges_overlaps(self):
        assert analyzer.union_us([(0, 10), (5, 10), (30, 5)]) == 20
        assert analyzer.union_us([]) == 0
        assert analyzer.union_us([(7, 0)]) == 0

    def test_merged_trace_is_strict_json_with_metadata(self, tmp_path):
        path = str(tmp_path / "trace.json")
        write_merged(path, _synthetic_spans(), trace_id=0xBEEF,
                     world_size=2)
        doc = json.load(open(path))  # strict parser
        assert doc["metadata"]["trace_id"] == "0xbeef"
        assert doc["metadata"]["world_size"] == 2
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"STEP", "NEGOTIATE", "WIRE", "DEQUEUE", "WAIT",
                "EPOCH_1", "process_name", "thread_name"} <= names

    def test_partial_lifecycle_skips_empty_phases(self):
        # error path: wire never started — only NEGOTIATE renders
        sp = Span(K_COLLECTIVE, 0, "t", op="ALLREDUCE",
                  ts=[100, 200, 0, 0, 250])
        names = [e["name"] for e in spans_to_events([sp]) if e["ph"] == "X"]
        assert names == ["NEGOTIATE"]

    def test_analyze_report_numbers(self, tmp_path):
        path = str(tmp_path / "trace.json")
        write_merged(path, _synthetic_spans(), trace_id=1)
        rep = analyzer.analyze(path)
        for rank in (0, 1):
            r = rep["ranks"][rank]
            assert r["steps"] == 1 and r["step_us"] == 10000
            assert r["wait_us"] == 2000 and r["compute_us"] == 8000
            assert r["exposed_comm_pct"] == pytest.approx(20.0)
            assert r["wire_us"] == 2000
        assert rep["overall"]["exposed_comm_pct"] == pytest.approx(20.0)
        # rank 1 enqueued 300 us behind rank 0
        assert rep["overall"]["max_skew_us"] == 300
        assert rep["skew"][1]["max_us"] == 300 and rep["skew"][0]["max_us"] == 0
        assert rep["counts"]["wire_spans"] == 2
        assert rep["slowest"][0]["tensor"] == "grad/w"
        text = analyzer.format_report(rep, path=path)
        assert "exposed communication: 20.0%" in text
        assert "max cross-rank skew: 300 us" in text

    def test_bare_array_form_accepted(self, tmp_path):
        path = str(tmp_path / "bare.json")
        with open(path, "w") as f:
            json.dump(spans_to_events(_synthetic_spans()), f)
        assert analyzer.analyze(path)["counts"]["wire_spans"] == 2


class TestCLI:
    def test_report_and_validate(self, tmp_path, capsys):
        path = str(tmp_path / "trace.json")
        write_merged(path, _synthetic_spans(), trace_id=1)
        assert hvdprof_main(["validate", path]) == 0
        assert hvdprof_main(["report", path]) == 0
        out = capsys.readouterr().out
        assert "per-rank step breakdown" in out
        assert hvdprof_main(["report", path, "--json"]) == 0
        rep = json.loads(capsys.readouterr().out)
        assert rep["counts"]["wire_spans"] == 2

    def test_validate_rejects_garbage(self, tmp_path, capsys):
        bad = tmp_path / "bad.json"
        bad.write_text('{"traceEvents": [}')
        assert hvdprof_main(["validate", str(bad)]) == 1
        assert hvdprof_main(["report", str(bad)]) == 1
        assert hvdprof_main([]) == 2

    def test_validate_rejects_empty_file(self, tmp_path, capsys):
        """A zero-byte trace (the run died before the final flush) must
        fail validation, not pass as vacuously-valid JSON."""
        empty = tmp_path / "empty.json"
        empty.write_text("")
        assert hvdprof_main(["validate", str(empty)]) == 1
        assert "invalid trace" in capsys.readouterr().err

    def test_validate_rejects_truncated_file(self, tmp_path, capsys):
        path = str(tmp_path / "trunc.json")
        write_merged(path, _synthetic_spans(), trace_id=1)
        whole = open(path).read()
        with open(path, "w") as f:
            f.write(whole[:len(whole) // 2])  # killed mid-write
        assert hvdprof_main(["validate", path]) == 1
        assert "invalid trace" in capsys.readouterr().err

    def test_validate_rejects_zero_events(self, tmp_path, capsys):
        """Parseable JSON carrying no events is a failed capture: exit
        nonzero with a clear message instead of 'ok (0 events)'."""
        for doc in ("{}", '{"traceEvents": []}', "[]"):
            p = tmp_path / "zero.json"
            p.write_text(doc)
            assert hvdprof_main(["validate", str(p)]) == 1, doc
            assert "no trace events" in capsys.readouterr().err

    def test_bin_hvdprof_entrypoint(self, tmp_path):
        path = str(tmp_path / "trace.json")
        write_merged(path, _synthetic_spans(), trace_id=1)
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "hvdprof"),
             "report", path], capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr
        assert "per-rank step breakdown" in r.stdout


# ------------------------------------------------------------ module state
class TestModuleState:
    def test_inactive_without_env(self):
        assert tracing.maybe_activate() is None
        assert tracing.active() is None and not tracing.enabled()

    def test_activate_resolves_path(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_TRACE", "1")
        assert tracing.maybe_activate() is not None
        assert tracing.trace_path() == "hvd_trace.json"

    def test_trace_id_mint_and_install(self):
        tid = tracing.ensure_trace_id()
        assert tid != 0 and tracing.ensure_trace_id() == tid  # stable
        tracing.set_trace_id(0x1234)
        assert tracing.trace_id() == 0x1234

    def test_store_overflow_drops_and_counts(self, monkeypatch, tmp_path):
        monkeypatch.setenv("HOROVOD_TRACE", str(tmp_path / "t.json"))
        monkeypatch.setenv("HOROVOD_TRACE_BUFFER", "2")  # store cap = 16
        tracing.maybe_activate()
        before = instruments.trace_dropped_events().value
        tracing.store_batch(
            [Span(K_WAIT, 0, "WAIT", ts=[i, i + 1, 0, 0, 0])
             for i in range(40)])
        assert tracing.store_size() == 16
        assert instruments.trace_dropped_events().value - before == 24

    def test_finalize_writes_merged_and_resets(self, monkeypatch, tmp_path):
        path = str(tmp_path / "out.json")
        monkeypatch.setenv("HOROVOD_TRACE", path)
        tr = tracing.maybe_activate()
        tr.add_wait(0, 100, 200)
        clock.set_offset_us(777)
        assert tracing.finalize(mode="standalone", rank=0) == path
        assert json.load(open(path))["traceEvents"]
        # full reset: tracer gone, offset dropped
        assert tracing.active() is None and clock.offset_us() == 0

    def test_worker_fallback_writes_rank_suffixed(self, monkeypatch,
                                                  tmp_path):
        path = str(tmp_path / "out.json")
        monkeypatch.setenv("HOROVOD_TRACE", path)
        tr = tracing.maybe_activate()
        tr.add_wait(3, 100, 200)
        out = tracing.finalize(mode="multiprocess", rank=3)
        assert out == path + ".rank3" and os.path.exists(out)


# -------------------------------------------------- engine-path acceptance
class TestEnginePath:
    def test_noop_fast_path_allocates_nothing(self):
        """Acceptance: HOROVOD_TRACE unset -> zero trace allocations across
        a full init / allreduce / optimizer-step / shutdown cycle."""
        assert "HOROVOD_TRACE" not in os.environ
        before = allocation_count()

        def fn():
            import jax.numpy as jnp
            import optax

            params = {"w": jnp.zeros((8,))}
            tx = hvd.DistributedOptimizer(optax.sgd(0.1))
            opt = tx.init(params)
            for i in range(3):
                g = hvd.allreduce(np.ones((8,), np.float32), name=f"g{i}",
                                  op=hvd.Sum)
                updates, opt = tx.update({"w": jnp.ones((8,))}, opt, params)
            return float(np.asarray(g)[0])

        res = testing.run_cluster(fn, np=2)
        assert res == [2.0, 2.0]
        hvd.shutdown()
        assert tracing.active() is None
        assert allocation_count() == before, \
            "tracing-off engine path allocated trace objects"

    def test_local_cluster_end_to_end(self, monkeypatch, tmp_path):
        """Acceptance: a traced local-cluster training run leaves ONE
        strictly-valid merged trace with WIRE and STEP spans that hvdprof
        reports on."""
        path = str(tmp_path / "trace.json")
        monkeypatch.setenv("HOROVOD_TRACE", path)
        monkeypatch.setenv("HOROVOD_TRACE_INTERVAL", "0.2")

        def fn():
            import jax
            import jax.numpy as jnp
            import optax

            params = {"w": jnp.zeros((16,))}
            tx = hvd.DistributedOptimizer(optax.sgd(0.1))
            opt = tx.init(params)
            grad_fn = jax.jit(jax.grad(lambda p: jnp.mean(p["w"] ** 2)))
            for _ in range(3):
                grads = grad_fn(params)
                updates, opt = tx.update(grads, opt, params)
                params = optax.apply_updates(params, updates)
            return True

        assert all(testing.run_cluster(fn, np=2))
        hvd.shutdown()
        doc = json.load(open(path))  # strict JSON
        names = [e["name"] for e in doc["traceEvents"]]
        assert "WIRE" in names and "STEP" in names and "WAIT" in names
        rep = analyzer.analyze(path)
        assert rep["counts"]["wire_spans"] > 0
        assert sum(r["steps"] for r in rep["ranks"].values()) >= 3
        assert hvdprof_main(["report", path]) == 0

    def test_exposed_comm_gauge_always_on(self):
        """hvd_exposed_comm_seconds moves even with tracing off."""
        before = instruments.exposed_comm_seconds().value

        def fn():
            h = hvd.allreduce_async(np.ones((4,), np.float32), name="x",
                                    op=hvd.Sum)
            return float(np.asarray(hvd.synchronize(h))[0])

        assert testing.run_cluster(fn, np=2) == [2.0, 2.0]
        hvd.shutdown()
        assert instruments.exposed_comm_seconds().value > before

    def test_straggler_skew_gauge_set_by_negotiation(self, monkeypatch):
        # pin the pure-Python controller: the skew instrumentation lives in
        # PyController/CoordState arrival tracking
        monkeypatch.setenv("HVD_TPU_NATIVE", "0")

        def fn():
            if hvd.rank() == 1:
                time.sleep(0.05)  # deliberate straggler
            return float(np.asarray(hvd.allreduce(
                np.ones((4,), np.float32), name="s", op=hvd.Sum))[0])

        assert testing.run_cluster(fn, np=2) == [2.0, 2.0]
        hvd.shutdown()
        assert instruments.straggler_skew_seconds().value >= 0.02


# ------------------------------------------------------------- integration
def _traced_chaos_worker():
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.rank()
    params = {"w": jnp.zeros((32,))}
    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    opt = tx.init(params)
    grad_fn = jax.jit(jax.grad(lambda p: jnp.mean(p["w"] ** 2)))
    for _ in range(6):
        grads = grad_fn(params)
        updates, opt = tx.update(grads, opt, params)
        params = optax.apply_updates(params, updates)
    import time as _t

    _t.sleep(0.6)  # > HOROVOD_TRACE_INTERVAL: final batches ship
    hvd.shutdown()
    return r


@pytest.mark.integration
def test_mp_trace_survives_conn_drop(tmp_path):
    """Satellite acceptance: a real 2-process traced job with a
    ``conn_drop@frame`` fault injected on rank 1 must still deliver BOTH
    ranks' spans into one strictly-valid merged trace — the reconnect+replay
    path carries MSG_TRACE like any other frame."""
    from horovod_tpu.run.api import run

    here = os.path.dirname(os.path.abspath(__file__))
    trace = str(tmp_path / "chaos_trace.json")
    env = {
        "JAX_PLATFORMS": "cpu",
        "HVD_ELASTIC": "1",
        "HOROVOD_TRACE": trace,
        "HOROVOD_TRACE_INTERVAL": "0.2",
        "HOROVOD_FAULT_SPEC": "conn_drop@frame:10#1",
        "PYTHONPATH": os.pathsep.join([os.path.dirname(here), here]),
    }
    out = run(_traced_chaos_worker, np=2, env=env, start_timeout=120)
    assert sorted(out) == [0, 1]
    doc = json.load(open(trace))  # strict JSON despite the mid-run drop
    pids = {e["pid"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert pids == {0, 1}, f"expected spans from both ranks, got {pids}"
    rep = analyzer.analyze(trace)
    assert rep["counts"]["wire_spans"] > 0


def _traced_elastic_fn():
    import os as _os
    import time as _t

    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    state = hvd.elastic.ElasticState(w=np.array([4.0], np.float32), step=0)

    @hvd.elastic.run_fn
    def train(state):
        while state.step < 8:
            if hvd.rank() != 0 and state.step == 3:
                _t.sleep(0.6)  # let the last trace batch ship first
                _os._exit(17)  # hard kill: no BYE, no cleanup
            g = 2.0 * (np.asarray(state.w) - 1.0)
            avg = hvd.allreduce(g, name=f"grad{state.step}", op=hvd.Average)
            state.w = np.asarray(state.w) - 0.1 * np.asarray(avg)
            state.step += 1
            state.commit()
        return True

    ok = train(state)
    hvd.shutdown()  # rank 0 writes the merged trace here
    return ok


@pytest.mark.integration
def test_mp_trace_survives_elastic_epoch_bump(tmp_path):
    """Satellite acceptance: killing a worker mid-training (elastic epoch
    bump) must not corrupt the merged trace — rank 0 still writes strict
    JSON holding the dead rank's shipped spans plus the EPOCH_1 marker."""
    import cloudpickle

    from horovod_tpu.run import rendezvous

    here = os.path.dirname(os.path.abspath(__file__))
    trace = str(tmp_path / "elastic_trace.json")
    secret = rendezvous.make_secret()
    kv = rendezvous.KVStoreServer(secret).start()
    addr = f"127.0.0.1:{kv.port}"
    client = rendezvous.KVStoreClient(addr, secret)
    client.put("runfunc", "fn",
               cloudpickle.dumps((_traced_elastic_fn, (), {})))

    procs = []
    try:
        for r in range(2):
            env = dict(os.environ)
            env.update({
                "HVD_NUM_PROCS": "2",
                "HVD_PROCESS_ID": str(r),
                "HVD_KV_ADDR": addr,
                "HVD_SECRET": secret,
                "HVD_ELASTIC": "1",
                "HOROVOD_RECONNECT_GRACE": "2",
                "HOROVOD_TRACE": trace,
                "HOROVOD_TRACE_INTERVAL": "0.2",
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": os.pathsep.join(
                    [os.path.dirname(here), here]),
            })
            env.pop("XLA_FLAGS", None)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "horovod_tpu.run.task"], env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))

        deadline = time.time() + 150
        blob = None
        while time.time() < deadline:
            blob = client.get("result", "0")
            if blob is not None:
                break
            if procs[0].poll() is not None:
                time.sleep(1.0)
                blob = client.get("result", "0")
                break
            time.sleep(0.25)
        assert blob is not None, "rank 0 produced no result (deadlocked?)"
        ok, payload = pickle.loads(blob)
        assert ok, f"rank 0 raised:\n{payload}"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        kv.stop()

    assert procs[1].wait(timeout=10) == 17  # died with its marker code
    doc = json.load(open(trace))  # strict JSON through the epoch bump
    events = doc["traceEvents"]
    pids = {e["pid"] for e in events if e.get("ph") == "X"}
    assert 0 in pids, "rank 0's own spans missing"
    assert 1 in pids, "dead rank 1's shipped spans lost in the merge"
    assert any(e["name"].startswith("EPOCH_") and e.get("ph") == "i"
               for e in events), "no epoch marker in the merged trace"
    assert analyzer.analyze(trace)["counts"]["wire_spans"] > 0
