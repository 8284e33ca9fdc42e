"""Inference serving subsystem tests (docs/inference.md).

Unit layer: the paged KV cache (block math, upfront reservation,
double-free detection, padded gather), the continuous-batching scheduler
(FCFS admission control, strict-FIFO head-of-line semantics,
iteration-level prefill/decode interleave), the SERVE_* wire codecs, the
serving-latency anomaly-watch signals and the hvddoctor
``latency_regression`` detector. Acceptance: batched decode through the
:class:`ServingEngine` is BIT-IDENTICAL to sequential decode of the same
prompts (the fixed-shape + exact-masking invariant), and a real
frontend + 2 worker-replica pod survives a SIGKILL mid-flight with the
dead replica's requests re-admitted onto the survivor — zero lost.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from horovod_tpu.blackbox import doctor, signatures as sigs
from horovod_tpu.blackbox.watch import AnomalyWatch
from horovod_tpu.runtime import wire
from horovod_tpu.serving import (BlockAllocator, ContinuousBatchingScheduler,
                                 KVCacheFull, PagedKVCache, QueueFull,
                                 Request, ServingConfig, ServingEngine,
                                 blocks_for_tokens)
from horovod_tpu.serving.scheduler import ACTIVE, DONE, FAILED, QUEUED

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- block math
class TestBlockMath:
    def test_ceil_division(self):
        assert blocks_for_tokens(1, 16) == 1
        assert blocks_for_tokens(16, 16) == 1
        assert blocks_for_tokens(17, 16) == 2
        assert blocks_for_tokens(64, 16) == 4

    def test_zero_tokens_still_owns_a_block(self):
        assert blocks_for_tokens(0, 16) == 1

    def test_allocator_alloc_free_roundtrip(self):
        a = BlockAllocator(8)
        assert a.free_blocks == 8 and a.used_blocks == 0
        got = a.allocate(3)
        assert len(got) == 3 and len(set(got)) == 3
        assert a.used_blocks == 3
        a.free(got)
        assert a.free_blocks == 8

    def test_allocator_exhaustion_raises(self):
        a = BlockAllocator(4)
        assert a.can_allocate(4) and not a.can_allocate(5)
        a.allocate(4)
        with pytest.raises(KVCacheFull):
            a.allocate(1)

    def test_double_free_detected(self):
        a = BlockAllocator(4)
        got = a.allocate(2)
        a.free(got)
        with pytest.raises(ValueError, match="double free"):
            a.free(got)

    def test_free_unknown_block_rejected(self):
        with pytest.raises(ValueError, match="unknown block"):
            BlockAllocator(4).free([7])


# ---------------------------------------------------------- paged KV cache
def _cache(num_blocks=8, block_size=4, layers=2, heads=2, dh=3):
    return PagedKVCache(num_blocks, block_size, layers, heads, dh)


def _kv(layers, t, heads, dh, base):
    k = np.arange(layers * t * heads * dh, dtype=np.float32).reshape(
        layers, t, heads, dh) + base
    return k, -k


class TestPagedKVCache:
    def test_upfront_reservation_and_occupancy(self):
        c = _cache()
        assert c.allocate("a", 10) == 3  # ceil(10/4)
        assert c.used_blocks == 3 and c.occupancy() == 3 / 8
        assert c.block_table("a") and c.length("a") == 0
        assert c.requests() == ["a"]

    def test_duplicate_allocate_rejected(self):
        c = _cache()
        c.allocate("a", 4)
        with pytest.raises(ValueError, match="already allocated"):
            c.allocate("a", 4)

    def test_append_tracks_tokens_and_respects_reservation(self):
        c = _cache()
        c.allocate("a", 6)  # 2 blocks = 8 slots
        k, v = _kv(2, 5, 2, 3, base=1.0)
        c.append("a", k, v)
        assert c.length("a") == 5 and c.used_tokens == 5
        c.append("a", *_kv(2, 3, 2, 3, base=9.0))  # 8 total: exactly fits
        with pytest.raises(KVCacheFull, match="reservation"):
            c.append("a", *_kv(2, 1, 2, 3, base=0.0))

    def test_gather_roundtrips_data_across_block_boundaries(self):
        c = _cache(block_size=4)
        c.allocate("a", 12)
        k, v = _kv(2, 7, 2, 3, base=5.0)  # spans two blocks
        c.append("a", k, v)
        gk, gv, mask, lengths = c.gather(["a"], capacity=12)
        assert gk.shape == (2, 1, 12, 2, 3)
        np.testing.assert_array_equal(gk[:, 0, :7], k)
        np.testing.assert_array_equal(gv[:, 0, :7], v)
        assert mask[0, :7].all() and not mask[0, 7:].any()
        assert lengths[0] == 7
        # padding slots are exactly zero — the masking precondition
        assert not gk[:, 0, 7:].any()

    def test_gather_pads_absent_requests_with_false_rows(self):
        c = _cache()
        c.allocate("a", 4)
        c.append("a", *_kv(2, 2, 2, 3, base=1.0))
        gk, _, mask, lengths = c.gather(["a", "", "ghost"], capacity=8)
        assert gk.shape[1] == 3
        assert mask[0, :2].all()
        assert not mask[1].any() and not mask[2].any()
        assert list(lengths) == [2, 0, 0]

    def test_gather_capacity_overflow_raises(self):
        c = _cache(num_blocks=8, block_size=4)
        c.allocate("a", 8)
        c.append("a", *_kv(2, 6, 2, 3, base=0.0))
        with pytest.raises(ValueError, match="capacity"):
            c.gather(["a"], capacity=4)

    def test_free_returns_whole_blocks_to_pool(self):
        c = _cache()
        c.allocate("a", 10)
        c.allocate("b", 4)
        assert c.used_blocks == 4
        assert c.free("a") == 3
        assert c.used_blocks == 1 and c.requests() == ["b"]
        assert c.used_tokens == 0


# --------------------------------------------------------------- scheduler
def _sched(num_blocks=8, block_size=4, **kw):
    return ContinuousBatchingScheduler(_cache(num_blocks, block_size), **kw)


class TestScheduler:
    def test_admission_reserves_blocks_and_caps_prefills(self):
        s = _sched(prefill_per_step=1)
        a = s.submit(Request([1, 2], 2))
        b = s.submit(Request([3], 2))
        prefills, decodes = s.schedule()
        assert prefills == [a] and decodes == []
        assert a.state == ACTIVE and b.state == QUEUED
        assert s.cache.used_blocks == 1  # a's 4-token budget reserved

    def test_prefilled_requests_decode_next_step(self):
        s = _sched(prefill_per_step=2)
        a = s.submit(Request([1], 1))
        b = s.submit(Request([2], 1))
        prefills, decodes = s.schedule()
        assert prefills == [a, b] and decodes == []
        prefills, decodes = s.schedule()
        assert prefills == [] and decodes == [a, b]

    def test_batch_slot_limit(self):
        s = _sched(num_blocks=32, max_batch=2, prefill_per_step=4)
        reqs = [s.submit(Request([1], 1)) for _ in range(3)]
        prefills, _ = s.schedule()
        assert prefills == reqs[:2]  # third waits for a slot
        assert s.queue_depth() == 1 and s.active_count() == 2

    def test_queue_bound_rejects_with_queuefull(self):
        s = _sched(max_queue=1)
        s.submit(Request([1], 1))
        with pytest.raises(QueueFull):
            s.submit(Request([2], 1))
        assert s.rejected == 1

    def test_oversized_request_rejected_at_submit(self):
        s = _sched(max_context=8)
        with pytest.raises(ValueError, match="max_context"):
            s.submit(Request([1] * 6, 3))

    def test_strict_fifo_head_blocks_admission(self):
        # 2 free blocks of 4; the head wants 3 blocks and must not be
        # overtaken by the small request behind it
        s = _sched(num_blocks=2, block_size=4, strict_fifo=True,
                   max_context=16)
        big = s.submit(Request([1] * 9, 3))  # 12 tokens = 3 blocks
        small = s.submit(Request([2], 1))
        prefills, _ = s.schedule()
        assert prefills == []
        assert big.state == QUEUED and small.state == QUEUED

    def test_non_fifo_lets_small_requests_overtake(self):
        s = _sched(num_blocks=2, block_size=4, strict_fifo=False,
                   max_context=16)
        big = s.submit(Request([1] * 9, 3))
        small = s.submit(Request([2], 1))
        prefills, _ = s.schedule()
        assert prefills == [small] and big.state == QUEUED

    def test_complete_frees_blocks_and_fires_future(self):
        s = _sched()
        done = []
        r = s.submit(Request([1, 2], 2, callback=done.append))
        s.schedule()
        r.output.extend([7, 8])
        s.complete(r, DONE)
        assert r.result(timeout=1) == [7, 8]
        assert r.latency() is not None
        assert s.cache.used_blocks == 0
        assert s.completed == 1 and done == [r]

    def test_failed_result_raises(self):
        s = _sched()
        r = s.submit(Request([1], 1))
        s.schedule()
        s.complete(r, FAILED, "boom")
        with pytest.raises(RuntimeError, match="boom"):
            r.result(timeout=1)
        assert s.failed == 1

    def test_drain_fails_everything(self):
        s = _sched(prefill_per_step=1)
        a = s.submit(Request([1], 1))
        b = s.submit(Request([2], 1))
        s.schedule()  # a active, b queued
        doomed = s.drain("shutdown")
        assert set(doomed) == {a, b}
        assert a.state == FAILED and b.state == FAILED
        assert not s.has_work() and s.cache.used_blocks == 0

    def test_request_validation(self):
        with pytest.raises(ValueError, match="empty prompt"):
            Request([], 1)
        with pytest.raises(ValueError, match="max_new_tokens"):
            Request([1], 0)


# -------------------------------------------------------------- wire codecs
class TestServeWire:
    def test_frame_names_registered(self):
        assert wire._FRAME_NAMES[wire.MSG_SERVE_HELLO] == "SERVE_HELLO"
        assert wire._FRAME_NAMES[wire.MSG_SERVE_SUBMIT] == "SERVE_SUBMIT"
        assert wire._FRAME_NAMES[wire.MSG_SERVE_RESULT] == "SERVE_RESULT"

    def test_hello_roundtrip(self):
        buf = wire.encode_serve_hello(wire.SERVE_ROLE_WORKER, "w-1", 8)
        assert wire.decode_serve_hello(buf) == (wire.SERVE_ROLE_WORKER,
                                                "w-1", 8)

    def test_submit_roundtrip(self):
        buf = wire.encode_serve_submit("r1", [5, -3, 250], 64, 2)
        assert wire.decode_serve_submit(buf) == ("r1", [5, -3, 250], 64, 2)

    def test_submit_eos_none_encodes_as_minus_one(self):
        buf = wire.encode_serve_submit("r2", [1], 4, None)
        assert wire.decode_serve_submit(buf)[3] is None

    def test_result_roundtrip(self):
        buf = wire.encode_serve_result("r3", wire.SERVE_OK, [9, 8, 7],
                                       error="", latency=0.125)
        assert wire.decode_serve_result(buf) == ("r3", wire.SERVE_OK,
                                                 [9, 8, 7], "", 0.125)

    def test_rejected_result_carries_error(self):
        buf = wire.encode_serve_result("r4", wire.SERVE_REJECTED, [],
                                       error="queue full", latency=0.0)
        rid, status, tokens, error, _ = wire.decode_serve_result(buf)
        assert status == wire.SERVE_REJECTED and tokens == []
        assert error == "queue full"


# ----------------------------------------------------------- serving engine
@pytest.fixture(scope="module")
def lm():
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab_size=97, num_layers=2, num_heads=2,
                          d_model=32, max_seq_len=32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


def _engine(lm, **kw):
    model, params = lm
    cfg = ServingConfig(block_size=kw.pop("block_size", 4),
                        num_blocks=kw.pop("num_blocks", 32),
                        max_context=kw.pop("max_context", 32), **kw)
    return ServingEngine(model, params, cfg)


PROMPTS = [[3, 1, 4], [1, 5, 9, 2, 6, 5], [3, 5], [8, 9, 7, 9, 3, 2, 3, 8]]


class TestServingEngine:
    def test_batched_decode_bit_identical_to_sequential(self, lm):
        """The acceptance invariant: a request's tokens do not depend on
        who shares its decode batch. Four mixed-length prompts decoded as
        one continuous batch must equal the same prompts decoded one at a
        time through a max_batch=1 engine (different compiled shapes,
        same bits)."""
        eng = _engine(lm, max_batch=4, prefill_per_step=4)
        reqs = [eng.submit(p, 6) for p in PROMPTS]
        eng.run_until_idle(timeout=120)
        batched = [r.result(timeout=1) for r in reqs]

        seq = _engine(lm, max_batch=1)
        sequential = []
        for p in PROMPTS:
            r = seq.submit(p, 6)
            seq.run_until_idle(timeout=120)
            sequential.append(r.result(timeout=1))
        assert batched == sequential
        assert all(len(out) == 6 for out in batched)

    def test_kv_blocks_fully_freed_after_completion(self, lm):
        eng = _engine(lm, max_batch=2)
        for p in PROMPTS[:2]:
            eng.submit(p, 4)
        eng.run_until_idle(timeout=120)
        assert eng.cache.used_blocks == 0 and eng.cache.used_tokens == 0
        assert eng.stats()["completed"] == 2

    def test_eos_stops_generation_early(self, lm):
        eng = _engine(lm, max_batch=1)
        r = eng.submit(PROMPTS[0], 6)
        eng.run_until_idle(timeout=120)
        full = r.result(timeout=1)
        # stop at the eos token's FIRST occurrence in the same stream
        eos = full[1]
        eng2 = _engine(lm, max_batch=1)
        r2 = eng2.submit(PROMPTS[0], 6, eos_id=eos)
        eng2.run_until_idle(timeout=120)
        assert r2.result(timeout=1) == full[:full.index(eos) + 1]

    def test_prompt_exceeding_bucket_rejected(self, lm):
        eng = _engine(lm)
        with pytest.raises(ValueError, match="prompt bucket"):
            eng.submit([1] * 33, 1)
        with pytest.raises(ValueError, match="max_context"):
            eng.submit([1] * 30, 8)  # 30 + 8 > 32 window

    def test_queuefull_backpressure(self, lm):
        eng = _engine(lm, max_queue=1)
        eng.submit([1, 2], 2)  # loop not running: stays queued
        with pytest.raises(QueueFull):
            eng.submit([3, 4], 2)

    def test_background_thread_mode(self, lm):
        eng = _engine(lm, max_batch=4).start()
        try:
            reqs = [eng.submit(p, 4) for p in PROMPTS]
            outs = [r.result(timeout=120) for r in reqs]
            assert all(len(o) == 4 for o in outs)
        finally:
            eng.stop()
        stats = eng.stats()
        assert stats["completed"] >= 4 and stats["kv_blocks_used"] == 0

    def test_max_context_cannot_exceed_model_window(self, lm):
        model, params = lm
        with pytest.raises(ValueError, match="max_seq_len"):
            ServingEngine(model, params,
                          ServingConfig(max_context=model.max_seq_len + 1))


# ------------------------------------------------- anomaly watch + doctor
def _serving_snapshot(counts, queue=2.0):
    """Aggregated-registry shape for the serving families: per-bucket
    cumulative counts (last slot = +Inf overflow) plus the queue gauge."""
    return {
        "hvd_serving_request_latency_seconds": {
            "kind": "histogram", "help": "", "buckets": [0.01, 0.1, 1.0],
            "series": [{"labels": {"stage": "total"}, "sum": 0.0,
                        "count": float(sum(counts)),
                        "counts": [float(c) for c in counts]}]},
        "hvd_serving_queue_depth": {
            "kind": "gauge", "help": "",
            "series": [{"labels": {}, "value": float(queue)}]},
    }


class TestServingAnomalyWatch:
    def test_p99_spike_trips_latency_regression(self):
        w = AnomalyWatch(interval=1.0, window=8, factor=3.0, min_samples=2)
        fired = []
        counts = [0, 0, 0, 0]
        for _ in range(6):  # steady: every request lands in the 10ms bucket
            counts[0] += 10
            fired += w.observe_snapshot(_serving_snapshot(counts))
        assert fired == []
        counts[2] += 10  # this interval's requests all took ~1s
        fired = w.observe_snapshot(_serving_snapshot(counts))
        assert [s["id"] for s in fired] == ["latency_regression"]
        assert fired[0]["evidence"]["signal"] == "serving_p99_seconds"
        assert "serving_p99_seconds" in w.state()["active"]

    def test_queue_depth_spike_trips_latency_regression(self):
        w = AnomalyWatch(interval=1.0, window=8, factor=3.0, min_samples=2)
        counts = [5, 0, 0, 0]
        for _ in range(5):
            assert w.observe_snapshot(_serving_snapshot(counts, queue=2)) == []
        fired = w.observe_snapshot(_serving_snapshot(counts, queue=50))
        assert [s["evidence"]["signal"] for s in fired] == \
            ["serving_queue_depth"]
        assert fired[0]["id"] == "latency_regression"

    def test_training_only_snapshots_carry_no_serving_signals(self):
        w = AnomalyWatch(interval=1.0)
        out = w.extract({"hvd_allreduce_latency_seconds": {
            "kind": "histogram", "help": "", "buckets": [],
            "series": [{"labels": {}, "sum": 1.0, "count": 10.0,
                        "counts": []}]}})
        assert "serving_p99_seconds" not in out
        assert "serving_queue_depth" not in out


def _anomaly_bundle(events):
    return {0: {"blackbox": 1, "rank": 0, "world_size": 1, "reason": "test",
                "events": events, "metrics": {}, "open_spans": []}}


class TestLatencyRegressionDetector:
    def _ev(self, name, detail="p99 deviates from baseline"):
        return {"t": 1.0, "rank": 0, "kind": "anomaly", "name": name,
                "detail": detail}

    def test_detects_and_dedupes_serving_anomalies(self):
        bundle = _anomaly_bundle([
            self._ev("serving_p99_seconds"),
            self._ev("serving_p99_seconds", "still burning"),  # duplicate
            self._ev("serving_queue_depth"),
            self._ev("step_seconds"),  # training anomaly: not this detector
        ])
        out = sigs.detect_latency_regression(bundle)
        assert [s["id"] for s in out] == ["latency_regression"] * 2
        assert sorted(s["evidence"]["signal"] for s in out) == \
            ["serving_p99_seconds", "serving_queue_depth"]

    def test_doctor_diagnose_surfaces_it(self):
        diag = doctor.diagnose(_anomaly_bundle(
            [self._ev("serving_p99_seconds")]))
        assert "latency_regression" in [s["id"] for s in diag["signatures"]]

    def test_clean_bundle_is_silent(self):
        assert sigs.detect_latency_regression(_anomaly_bundle([])) == []


# ------------------------------------------------------- pod integration
@pytest.mark.integration
def test_pod_worker_kill_readmits_without_loss():
    """A real frontend + 2 worker-replica subprocesses: SIGKILL one replica
    with requests in flight; every request must still complete (re-admitted
    onto the survivor, exactly-once via the dedupe cache) and the frontend
    must count the re-admissions."""
    from horovod_tpu.serving import ServingClient, ServingFrontend

    fe = ServingFrontend(heartbeat_grace=2.0).start()
    host, port = fe.addr
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               HOROVOD_HEARTBEAT_INTERVAL="0.5")
    procs = [subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.serving.worker",
         "--addr", f"{host}:{port}", "--rank", str(i + 1),
         "--name", f"w{i}", "--max-batch", "4"],
        env=env, cwd=REPO) for i in range(2)]
    cli = None
    try:
        fe.wait_for_workers(2, timeout=180)
        cli = ServingClient(host, port, name="t")
        # warm both replicas' compile caches before the kill window
        for f in [cli.submit([1, 2, 3], 2) for _ in range(8)]:
            f.result(timeout=180)

        futs = [cli.submit([(j + i) % 40 + 1 for i in range(6)], 24)
                for j in range(12)]
        time.sleep(0.1)  # let the frontend dispatch to both replicas
        procs[0].kill()
        results = [f.result(timeout=180) for f in futs]

        assert all(len(r) == 24 for r in results)  # zero lost, full decodes
        stats = fe.stats()
        assert stats["completed"] >= 20  # 8 warm + 12 load
        assert stats["readmitted"] >= 1, stats
        assert len(stats["workers"]) == 1, stats
        # replicas restore the same checkpoint: a re-admitted request's
        # tokens are identical to what the dead replica would have produced
        ref = cli.submit([i % 40 + 1 for i in range(6)], 24)
        assert ref.result(timeout=180) == results[0]
    finally:
        if cli is not None:
            cli.close()
        for pr in procs:
            if pr.poll() is None:
                pr.terminate()
        for pr in procs:
            try:
                pr.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pr.kill()
        fe.stop()
