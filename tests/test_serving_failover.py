"""Survivable serving plane tests (docs/inference.md failure matrix).

Unit layer: the SERVE_* wire extensions (deadline/priority trailer,
cancel/drain/snapshot/journal codecs) pinned byte-identical to the
pre-failover format when every knob is unset; scheduler + engine
cancellation and the TTL sweep returning KV blocks to the pool; the
deterministic reconnect-jitter envelope; frontend behaviors driven by
raw-socket fake peers (dedupe of duplicate worker results, readmit on
worker death, client-disconnect cleanup, fence rejection of deposed
frames, shed/brownout admission, circuit breaker, hedged decode); the
standby replication stream and stream-loss promotion; and the new
observability surfaces (serving_shed_rate watch signal, the
serving_overload / serving_failover doctor signatures, the jepsen
serving-delivery checker).

Acceptance: a real frontend subprocess SIGKILLed mid-load hands the
serving plane to the warm standby via the rendezvous lease — every
request completes exactly once, a deposed-epoch frame is fence-rejected,
and re-decoded token streams are bit-identical to the original answers.
"""

import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

import pytest

from horovod_tpu.blackbox import signatures as sigs
from horovod_tpu.blackbox.watch import AnomalyWatch
from horovod_tpu.faultinject import jepsen
from horovod_tpu.runtime import wire
from horovod_tpu.runtime.coordinator import _backoff_schedule
from horovod_tpu.serving import (ContinuousBatchingScheduler, PagedKVCache,
                                 QueueFull, Request, ServingConfig,
                                 ServingEngine, ServingFrontend,
                                 ServingStandby)
from horovod_tpu.serving.scheduler import ACTIVE, CANCELLED, QUEUED

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: encode_serve_submit("r1", [1, 2, 3], 8, None) as frozen at the wire
#: format's introduction — the deadline/priority trailer must not change
#: a single byte of it while both knobs hold their defaults.
GOLD_SUBMIT_HEX = ("02000000723103000000010000000200000003000000"
                   "08000000ffffffff")


# ----------------------------------------------------- wire compatibility
class TestWireCompat:
    def test_submit_golden_hex_pinned(self):
        buf = wire.encode_serve_submit("r1", [1, 2, 3], 8, None)
        assert buf.hex() == GOLD_SUBMIT_HEX

    def test_default_deadline_and_priority_add_no_bytes(self):
        buf = wire.encode_serve_submit("r1", [1, 2, 3], 8, None, 0.0,
                                       wire.SERVE_PRIO_HIGH)
        assert buf.hex() == GOLD_SUBMIT_HEX

    def test_legacy_decoder_reads_extended_frames(self):
        buf = wire.encode_serve_submit("r1", [4, 5], 6, 2, 1.5,
                                       wire.SERVE_PRIO_BEST_EFFORT)
        assert wire.decode_serve_submit(buf) == ("r1", [4, 5], 6, 2)

    def test_submit_ex_roundtrip(self):
        buf = wire.encode_serve_submit("r9", [7], 3, None, 2.25,
                                       wire.SERVE_PRIO_BEST_EFFORT)
        assert wire.decode_serve_submit_ex(buf) == (
            "r9", [7], 3, None, 2.25, wire.SERVE_PRIO_BEST_EFFORT)

    def test_submit_ex_defaults_on_legacy_frames(self):
        buf = wire.encode_serve_submit("r1", [1, 2, 3], 8, None)
        assert wire.decode_serve_submit_ex(buf) == (
            "r1", [1, 2, 3], 8, None, 0.0, wire.SERVE_PRIO_HIGH)

    def test_cancel_roundtrip(self):
        buf = wire.encode_serve_cancel("abc", "deadline exceeded")
        assert wire.decode_serve_cancel(buf) == ("abc", "deadline exceeded")

    def test_drain_roundtrip(self):
        assert wire.decode_serve_drain(
            wire.encode_serve_drain("rolling restart")) == "rolling restart"

    def test_snapshot_roundtrip(self):
        results = [wire.encode_serve_result("a", wire.SERVE_OK, [1, 2])]
        pending = [wire.encode_serve_submit("b", [3], 2, None)]
        epoch, r, p = wire.decode_serve_snapshot(
            wire.encode_serve_snapshot(7, results, pending))
        assert epoch == 7 and r == results and p == pending

    def test_journal_roundtrip(self):
        blob = wire.encode_serve_cancel("x", "ttl")
        assert wire.decode_serve_journal(
            wire.encode_serve_journal(wire.SERVE_J_CANCEL, blob)) == \
            (wire.SERVE_J_CANCEL, blob)

    def test_frame_names_registered(self):
        assert wire._FRAME_NAMES[wire.MSG_SERVE_CANCEL] == "SERVE_CANCEL"
        assert wire._FRAME_NAMES[wire.MSG_SERVE_DRAIN] == "SERVE_DRAIN"


# --------------------------------------------- scheduler cancellation/TTL
def _sched(num_blocks=8, block_size=4, **kw):
    cache = PagedKVCache(num_blocks, block_size, 2, 2, 3)
    return ContinuousBatchingScheduler(cache, **kw)


class TestSchedulerCancel:
    def test_cancel_active_frees_blocks(self):
        s = _sched()
        r = s.submit(Request([1, 2], 2))
        s.schedule()
        assert r.state == ACTIVE and s.cache.used_blocks > 0
        assert s.cancel(r.id, "client gone")
        assert r.state == CANCELLED
        assert s.cache.used_blocks == 0
        assert s.cancelled == 1

    def test_cancel_queued_request(self):
        s = _sched()
        r = s.submit(Request([1], 1))
        assert r.state == QUEUED
        assert s.cancel(r.id)
        assert r.state == CANCELLED and not s.has_work()

    def test_cancel_unknown_id_is_noop(self):
        s = _sched()
        assert not s.cancel("ghost")
        assert s.cancelled == 0

    def test_ttl_sweep_reaps_orphans_and_returns_blocks(self):
        """The leak regression: a request nobody will ever collect must
        not pin KV blocks forever — the max-lifetime sweep reaps it and
        the pool refills."""
        s = _sched(request_ttl=0.05)
        r = s.submit(Request([1, 2, 3], 4))
        s.schedule()
        assert s.cache.used_blocks > 0
        time.sleep(0.08)
        expired, missed = s.sweep()
        assert expired == [r] and missed == []
        assert r.state == CANCELLED and "ttl" in r.error
        assert s.cache.used_blocks == 0
        assert s.expired == 1

    def test_deadline_sweep_separates_from_ttl(self):
        s = _sched()
        r = s.submit(Request([1], 4, deadline=0.02))
        s.schedule()
        time.sleep(0.05)
        expired, missed = s.sweep()
        assert expired == [] and missed == [r]
        assert r.state == CANCELLED
        assert s.cache.used_blocks == 0

    def test_queued_past_deadline_evicted_at_schedule(self):
        s = _sched()
        r = s.submit(Request([1], 1, deadline=0.01))
        time.sleep(0.03)
        prefills, decodes = s.schedule()
        assert prefills == [] and decodes == []
        assert r.state == CANCELLED

    def test_evict_queued_spares_active(self):
        s = _sched(prefill_per_step=1)
        a = s.submit(Request([1], 1))
        b = s.submit(Request([2], 1))
        s.schedule()  # a active, b queued
        evicted = s.evict_queued()
        assert evicted == [b]
        assert a.state == ACTIVE and b.state == QUEUED  # b left intact
        assert s.queue_depth() == 0

    @staticmethod
    def _lock_free_probe(sched, results):
        """Callback asserting the scheduler lock is NOT held: a foreign
        thread must be able to take it while the callback runs (finish()
        can block on a slow result send — holding the lock there stalls
        every submit/cancel/schedule caller)."""
        def cb(req):
            got = []

            def probe():
                if sched.lock.acquire(timeout=2.0):
                    sched.lock.release()
                    got.append(True)
            t = threading.Thread(target=probe)
            t.start()
            t.join()
            results.append(bool(got))
        return cb

    def test_deadline_eviction_finishes_outside_lock(self):
        s = _sched()
        free = []
        s.submit(Request([1], 1, deadline=0.01,
                         callback=self._lock_free_probe(s, free)))
        time.sleep(0.03)
        s.schedule()
        assert free == [True]

    def test_cancel_finishes_outside_lock(self):
        s = _sched()
        free = []
        r = s.submit(Request([1], 1,
                             callback=self._lock_free_probe(s, free)))
        assert s.cancel(r.id, "client gone")
        assert free == [True]

    def test_ttl_knob_read_from_env(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_SERVING_REQUEST_TTL", "12.5")
        assert _sched().request_ttl == 12.5
        monkeypatch.setenv("HOROVOD_SERVING_REQUEST_TTL", "0")
        assert _sched().request_ttl is None


# ------------------------------------------------------ engine cancellation
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


class TestEngineCancel:
    def test_cancel_reclaims_within_one_sweep_no_queuefull_after(self, lm):
        eng = _engine(lm, max_queue=2, max_batch=2)
        a = eng.submit([1, 2], 4)
        eng.submit([3, 4], 4)
        with pytest.raises(QueueFull):
            eng.submit([5, 6], 4)
        eng.cancel(a.id, "client timeout")
        eng.step()  # the between-step cancellation point
        assert a.state == CANCELLED
        eng.submit([5, 6], 4)  # the freed admission slot is back

    def test_deadline_cancel_frees_kv_blocks(self, lm):
        eng = _engine(lm, max_batch=2)
        r = eng.submit([1, 2, 3], 8, deadline=0.02)
        eng.step()  # prefill: blocks reserved
        time.sleep(0.05)
        eng.step()  # sweep fires before the decode
        assert r.state == CANCELLED
        eng.run_until_idle(timeout=30)
        assert eng.cache.used_blocks == 0

    def test_step_delay_knob(self, lm, monkeypatch):
        monkeypatch.setenv("HOROVOD_SERVING_STEP_DELAY", "0.123")
        assert _engine(lm).step_delay == 0.123

    def test_saturated_resource_names_the_bottleneck(self, lm):
        eng = _engine(lm, max_batch=1, prefill_per_step=1)
        assert eng.saturated_resource() == "queue"
        eng.submit([1, 2], 2)
        eng.step()
        assert eng.saturated_resource() == "decode_slots"


# ----------------------------------------------------- worker handback
class TestWorkerHandback:
    def _worker(self, lm, host="127.0.0.1", port=1, **kw):
        from horovod_tpu.serving.worker import ServingWorker
        return ServingWorker(host, port, _engine(lm, **kw))

    def test_queuefull_handback_forgets_request_id(self, lm):
        """The readmit-loop regression: a QueueFull rejection hands the
        request back to the frontend, which may re-dispatch it to this
        same replica (guaranteed with one replica under load) — the retry
        must not be swallowed by the dedupe set, or the request hangs
        forever and the frontend's inflight slot leaks."""
        w = self._worker(lm, max_queue=1, max_batch=1)
        filler = w.engine.submit([9, 9], 2)
        payload = wire.encode_serve_submit("r1", [1, 2], 2, None)
        w._on_submit(payload)  # replica queue full: handed back
        assert "r1" not in w._seen
        assert wire.decode_serve_result(w._unsent["r1"])[1] == \
            wire.SERVE_REJECTED
        # capacity frees up; the frontend re-dispatches the same id —
        # it must be accepted, not dropped as a duplicate
        w.engine.cancel(filler.id, "test")
        w.engine.step()
        w._unsent.clear()
        w._on_submit(payload)
        assert "r1" in w._seen
        assert w.engine.scheduler.queue_depth() == 1

    def test_a_result_lost_with_its_frontend_does_not_swallow_the_redispatch(
            self, lm):
        """The SIGKILL drill's hang (a request not done 300 s after the
        standby's promotion): a result sent as the frontend dies is taken
        by the kernel and lost; the promoted standby re-dispatches the id,
        and the replica must run it again."""
        w = self._worker(lm)
        w._send = lambda msg_type, payload: True   # sent, never received
        payload = wire.encode_serve_submit("r1", [1, 2], 2, None)
        w._on_submit(payload)
        w.engine.run_until_idle(timeout=30)
        assert not w._unsent and "r1" not in w._seen
        w._on_submit(payload)
        assert w.engine.scheduler.queue_depth() == 1
        w.engine.run_until_idle(timeout=30)
        assert not w._unsent

    def test_draining_cleared_on_new_session(self, lm):
        """A drain is scoped to the frontend session that issued it: after
        reconnecting (e.g. to a promoted standby that knows nothing of the
        drain) the replica must serve again, not reject forever."""
        srv = socket.socket()
        try:
            srv.bind(("127.0.0.1", 0))
            srv.listen(1)
            w = self._worker(lm, port=srv.getsockname()[1])
            w.draining = True
            sock = w._connect()
            assert w.draining is False
            sock.close()
        finally:
            srv.close()


# ---------------------------------------------------------- reconnect jitter
class TestReconnectJitter:
    def test_delay_within_envelope(self):
        for rank in (0, 1, 7, 63):
            for attempt in range(1, 7):
                base = min(0.1 * 2 ** (attempt - 1), 5.0)
                d = _backoff_schedule(rank, attempt, 0.1, 5.0, 0.3)
                assert base <= d < base * 1.3, (rank, attempt, d)

    def test_deterministic_per_entity(self):
        a = _backoff_schedule(3, 2, 0.1, 5.0, 0.5)
        assert a == _backoff_schedule(3, 2, 0.1, 5.0, 0.5)
        # distinct entities spread out somewhere in the schedule
        assert any(_backoff_schedule(3, k, 0.1, 5.0, 0.5)
                   != _backoff_schedule(4, k, 0.1, 5.0, 0.5)
                   for k in range(1, 5))

    def test_zero_jitter_is_pure_exponential(self):
        assert _backoff_schedule(9, 3, 0.1, 5.0, 0.0) == pytest.approx(0.4)


# ------------------------------------------------- frontend via fake peers
def _recv(sock, timeout=10.0):
    """Read one frame; raises instead of hanging when nothing arrives
    (recv_exact retries socket timeouts until the stop event fires)."""
    sock.settimeout(0.2)
    stop = threading.Event()
    timer = threading.Timer(timeout, stop.set)
    timer.start()
    try:
        return wire.recv_frame(sock, "", stop)
    finally:
        timer.cancel()


def _dial(addr, role, name, capacity=0, fence=0):
    s = socket.create_connection(addr, timeout=5)
    wire.send_frame(s, "", wire.MSG_SERVE_HELLO, 1, 0,
                    wire.encode_serve_hello(role, name, capacity),
                    fence=fence)
    return s


def _submit(sock, rid, prompt=(1, 2, 3), max_new=4, deadline=0.0,
            priority=wire.SERVE_PRIO_HIGH, fence=0):
    wire.send_frame(sock, "", wire.MSG_SERVE_SUBMIT, 2, 0,
                    wire.encode_serve_submit(rid, list(prompt), max_new,
                                             None, deadline, priority),
                    fence=fence)


def _result(sock, rid, status=wire.SERVE_OK, tokens=(9, 9), fence=0):
    wire.send_frame(sock, "", wire.MSG_SERVE_RESULT, 3, 0,
                    wire.encode_serve_result(rid, status, list(tokens),
                                             "", 0.01), fence=fence)


def _wait(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


@pytest.fixture
def fe():
    frontend = ServingFrontend(secret="", heartbeat_grace=30.0).start()
    yield frontend
    frontend.stop()


class TestFrontendLedger:
    def test_duplicate_worker_result_suppressed(self, fe):
        """A worker that dies between sending its result and seeing it
        land will resend after reconnect — the client must see exactly
        one answer, and a replay of the submit hits the dedupe LRU."""
        cs = _dial(fe.addr, wire.SERVE_ROLE_CLIENT, "c")
        ws = _dial(fe.addr, wire.SERVE_ROLE_WORKER, "w", capacity=4)
        try:
            _submit(cs, "r1")
            frame = _recv(ws)
            assert frame.msg_type == wire.MSG_SERVE_SUBMIT
            _result(ws, "r1", tokens=(5, 6))
            _result(ws, "r1", tokens=(5, 6))  # the post-reconnect resend
            got = _recv(cs)
            rid, status, tokens, _, _ = wire.decode_serve_result(
                got.payload)
            assert (rid, status, tokens) == ("r1", wire.SERVE_OK, [5, 6])
            assert _wait(lambda: fe.completed == 1)
            # replayed submit answered straight from the ledger
            _submit(cs, "r1")
            replay = _recv(cs)
            assert wire.decode_serve_result(replay.payload)[:3] == \
                ("r1", wire.SERVE_OK, [5, 6])
            assert fe.completed == 1  # no second dispatch happened
        finally:
            cs.close()
            ws.close()

    def test_worker_death_readmits_inflight(self, fe):
        cs = _dial(fe.addr, wire.SERVE_ROLE_CLIENT, "c")
        w1 = _dial(fe.addr, wire.SERVE_ROLE_WORKER, "w1", capacity=4)
        try:
            _submit(cs, "r1")
            assert _recv(w1).msg_type == wire.MSG_SERVE_SUBMIT
            w1.close()  # dies holding the request
            assert _wait(lambda: fe.stats()["readmitted"] >= 1)
            w2 = _dial(fe.addr, wire.SERVE_ROLE_WORKER, "w2", capacity=4)
            try:
                frame = _recv(w2)  # the re-dispatch
                rid = wire.decode_serve_submit(frame.payload)[0]
                assert rid == "r1"
                _result(w2, "r1", tokens=(7,))
                got = _recv(cs)
                assert wire.decode_serve_result(got.payload)[:3] == \
                    ("r1", wire.SERVE_OK, [7])
            finally:
                w2.close()
        finally:
            cs.close()

    def test_readmitted_request_with_dead_client_drops_cleanly(self, fe):
        """Client submits, disconnects; the worker hands the request back
        (drain-style SERVE_REJECTED). The readmit must neither crash nor
        leak: the request re-dispatches, finishes into the dedupe LRU,
        and pending empties."""
        cs = _dial(fe.addr, wire.SERVE_ROLE_CLIENT, "c")
        ws = _dial(fe.addr, wire.SERVE_ROLE_WORKER, "w", capacity=4)
        try:
            _submit(cs, "r1")
            assert _recv(ws).msg_type == wire.MSG_SERVE_SUBMIT
            cs.close()
            assert _wait(lambda: all(
                p.client is None for p in fe.pending.values()))
            _result(ws, "r1", status=wire.SERVE_REJECTED, tokens=())
            frame = _recv(ws)  # readmitted → re-dispatched to us
            assert wire.decode_serve_submit(frame.payload)[0] == "r1"
            _result(ws, "r1", tokens=(1, 2))
            assert _wait(lambda: fe.completed == 1)
            assert fe.pending == {}
            assert fe.results["r1"][0] == wire.SERVE_OK
        finally:
            ws.close()

    def test_client_cancel_tombstones_and_propagates(self, fe):
        cs = _dial(fe.addr, wire.SERVE_ROLE_CLIENT, "c")
        ws = _dial(fe.addr, wire.SERVE_ROLE_WORKER, "w", capacity=4)
        try:
            _submit(cs, "r1")
            assert _recv(ws).msg_type == wire.MSG_SERVE_SUBMIT
            wire.send_frame(cs, "", wire.MSG_SERVE_CANCEL, 4, 0,
                            wire.encode_serve_cancel("r1", "user hit ^C"))
            # worker is told to stop burning compute on it
            frame = _recv(ws)
            assert frame.msg_type == wire.MSG_SERVE_CANCEL
            assert wire.decode_serve_cancel(frame.payload)[0] == "r1"
            # client gets the terminal CANCELLED answer
            got = _recv(cs)
            assert wire.decode_serve_result(got.payload)[1] == \
                wire.SERVE_CANCELLED
            assert _wait(lambda: fe.cancelled == 1)
            assert fe.results["r1"][0] == wire.SERVE_CANCELLED
            # the straggler result from the worker no longer counts
            _result(ws, "r1")
            time.sleep(0.1)
            assert fe.completed == 0
        finally:
            cs.close()
            ws.close()


class TestFrontendFencing:
    def test_stale_epoch_frame_rejected_at_handshake(self):
        fe = ServingFrontend(secret="", fence_epoch=2).start()
        try:
            fresh = _dial(fe.addr, wire.SERVE_ROLE_WORKER, "w-new",
                          capacity=4, fence=2)
            assert _wait(lambda: "w-new" in fe.stats()["workers"])
            stale = _dial(fe.addr, wire.SERVE_ROLE_WORKER, "w-old",
                          capacity=4, fence=1)
            stale.settimeout(10)
            assert stale.recv(1) == b""  # cut before registration
            assert "w-old" not in fe.stats()["workers"]
            fresh.close()
            stale.close()
        finally:
            fe.stop()

    def test_guard_learns_higher_epochs(self):
        fe = ServingFrontend(secret="", fence_epoch=2)
        assert fe.guard.epoch == 2
        fe.guard.observe(5)
        assert fe.guard.epoch == 5
        fe.guard.observe(3)  # never regresses
        assert fe.guard.epoch == 5
        fe.listener.close()


class TestFrontendOverload:
    def test_best_effort_shed_high_admitted(self, fe):
        fe.shed_frac = 0.5
        fe.max_backlog = 8  # shed point 4, brownout from 2
        cs = _dial(fe.addr, wire.SERVE_ROLE_CLIENT, "c")
        try:
            for i in range(4):  # no workers: occupancy parks at 4
                _submit(cs, f"h{i}")
            assert _wait(lambda: len(fe.pending) == 4)
            _submit(cs, "be1", priority=wire.SERVE_PRIO_BEST_EFFORT)
            got = _recv(cs)
            rid, status, _, error, _ = wire.decode_serve_result(got.payload)
            assert (rid, status) == ("be1", wire.SERVE_SHED)
            assert "shed" in error
            assert fe.shed == 1
            _submit(cs, "h9")  # high priority still rides through
            assert _wait(lambda: "h9" in fe.pending)
        finally:
            cs.close()

    def test_brownout_halves_best_effort_budget(self, fe):
        fe.shed_frac = 0.5
        fe.max_backlog = 8
        cs = _dial(fe.addr, wire.SERVE_ROLE_CLIENT, "c")
        try:
            for i in range(2):
                _submit(cs, f"h{i}")
            assert _wait(lambda: len(fe.pending) == 2)
            _submit(cs, "be1", max_new=8,
                    priority=wire.SERVE_PRIO_BEST_EFFORT)
            assert _wait(lambda: "be1" in fe.pending)
            decoded = wire.decode_serve_submit_ex(fe.pending["be1"].payload)
            assert decoded[2] == 4  # max_new halved in the stored dispatch
        finally:
            cs.close()

    def test_backlog_full_rejects_with_retryable_status(self, fe):
        fe.max_backlog = 2
        cs = _dial(fe.addr, wire.SERVE_ROLE_CLIENT, "c")
        try:
            _submit(cs, "a")
            _submit(cs, "b")
            assert _wait(lambda: len(fe.pending) == 2)
            _submit(cs, "c")
            got = _recv(cs)
            assert wire.decode_serve_result(got.payload)[1] == \
                wire.SERVE_REJECTED
        finally:
            cs.close()

    def test_inflight_dispatch_not_counted_against_admission(self, fe):
        """max_backlog bounds requests WAITING for worker capacity (the
        class docstring's contract): work already dispatched to a replica
        is bounded by that replica's capacity and must not eat into the
        admission budget, or a pod with plenty of free decode slots
        rejects traffic it could absorb."""
        fe.max_backlog = 2
        cs = _dial(fe.addr, wire.SERVE_ROLE_CLIENT, "c")
        ws = _dial(fe.addr, wire.SERVE_ROLE_WORKER, "w", capacity=4)
        try:
            _submit(cs, "a")
            _submit(cs, "b")
            # both dispatched to the worker: queue empty, 2 in flight
            assert _wait(lambda: len(fe.pending) == 2
                         and not fe.backlog)
            _submit(cs, "c")  # would be rejected under an open-request cap
            assert _wait(lambda: "c" in fe.pending)
        finally:
            cs.close()
            ws.close()


class TestCircuitBreaker:
    def _worker(self):
        from horovod_tpu.serving.server import _Worker

        a, b = socket.socketpair()
        self._socks = (a, b)
        return _Worker(a, "w", 4)

    def test_trips_on_error_streak_and_recovers(self):
        w = self._worker()
        now = 100.0
        for _ in range(3):
            w.record_outcome(False, now, hold=2.0)
        assert w.breaker_open(now)
        assert not w.breaker_open(now + 2.5)  # hold elapsed: half-open
        for s in self._socks:
            s.close()

    def test_successes_keep_it_closed(self):
        w = self._worker()
        now = 50.0
        for ok in (True, True, False, True, False, True):
            w.record_outcome(ok, now, hold=2.0)
        assert not w.breaker_open(now)
        for s in self._socks:
            s.close()


class TestHedging:
    def test_first_winner_cancels_loser(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_SERVING_HEDGE", "1.0")
        fe = ServingFrontend(secret="", heartbeat_grace=30.0)
        fe.hedge_delay_override = 0.1
        fe.start()
        cs = _dial(fe.addr, wire.SERVE_ROLE_CLIENT, "c")
        w1 = _dial(fe.addr, wire.SERVE_ROLE_WORKER, "w1", capacity=4)
        try:
            assert _wait(lambda: len(fe.stats()["workers"]) == 1)
            w2 = _dial(fe.addr, wire.SERVE_ROLE_WORKER, "w2", capacity=4)
            assert _wait(lambda: len(fe.stats()["workers"]) == 2)
            _submit(cs, "r1")
            first = _recv(w1, timeout=5)
            # the primary stalls; the hedge loop re-dispatches to the
            # other replica after the override delay
            second = _recv(w2, timeout=10)
            assert wire.decode_serve_submit(first.payload)[0] == "r1"
            assert wire.decode_serve_submit(second.payload)[0] == "r1"
            assert _wait(lambda: fe.stats()["hedged"] >= 1)
            _result(w2, "r1", tokens=(3, 3))  # hedge wins
            got = _recv(cs)
            assert wire.decode_serve_result(got.payload)[:3] == \
                ("r1", wire.SERVE_OK, [3, 3])
            # loser is told to stop
            frame = _recv(w1)
            assert frame.msg_type == wire.MSG_SERVE_CANCEL
            w2.close()
        finally:
            cs.close()
            w1.close()
            fe.stop()


# ------------------------------------------------------- standby promotion
class TestStandbyPromotion:
    def test_snapshot_journal_replication_and_promote(self):
        fe = ServingFrontend(secret="", heartbeat_grace=30.0).start()
        sb = None
        cs = ws = None
        try:
            cs = _dial(fe.addr, wire.SERVE_ROLE_CLIENT, "c")
            ws = _dial(fe.addr, wire.SERVE_ROLE_WORKER, "w", capacity=4)
            # r0 completes pre-attach (snapshot path), r1 stays pending
            _submit(cs, "r0")
            assert _recv(ws).msg_type == wire.MSG_SERVE_SUBMIT
            _result(ws, "r0", tokens=(4, 2))
            _recv(cs)
            assert _wait(lambda: fe.completed == 1)
            ws.close()

            sb = ServingStandby(fe.addr, "", rank=1).start()
            assert _wait(lambda: fe._repl_sinks, timeout=10)
            _submit(cs, "r1")  # journaled live to the standby
            assert _wait(lambda: "r1" in sb._pending, timeout=10)
            assert "r0" in sb._results

            # crash the primary without a BYE: promote on stream loss
            fe.listener.close()
            fe._stop.set()
            for p in list(fe._repl_sinks):
                p.close()
            assert sb.wait_promoted(timeout=30)
            fe2 = sb.frontend
            # replicated ledger answers the replayed duplicate…
            cs2 = _dial(fe2.addr, wire.SERVE_ROLE_CLIENT, "c")
            _submit(cs2, "r0")
            got = _recv(cs2)
            assert wire.decode_serve_result(got.payload)[:3] == \
                ("r0", wire.SERVE_OK, [4, 2])
            # …and the open submit was re-queued for dispatch; the client
            # replays it (the reconnect protocol) to re-own the answer
            _submit(cs2, "r1")
            w2 = _dial(fe2.addr, wire.SERVE_ROLE_WORKER, "w2", capacity=4)
            frame = _recv(w2)
            assert wire.decode_serve_submit(frame.payload)[0] == "r1"
            _result(w2, "r1", tokens=(8,))
            got = _recv(cs2)
            assert wire.decode_serve_result(got.payload)[:3] == \
                ("r1", wire.SERVE_OK, [8])
            cs2.close()
            w2.close()
        finally:
            if cs is not None:
                cs.close()
            if sb is not None:
                sb.stop()
            fe.stop()

    def test_clean_bye_stands_down(self):
        fe = ServingFrontend(secret="", heartbeat_grace=30.0).start()
        sb = ServingStandby(fe.addr, "", rank=1).start()
        try:
            assert _wait(lambda: fe._repl_sinks, timeout=10)
            fe.stop()  # clean shutdown sends MSG_BYE
            time.sleep(0.5)
            assert not sb.promoted
        finally:
            sb.stop()
            fe.stop()

    def test_journal_cancel_tombstones_replica_state(self):
        sb = ServingStandby(("127.0.0.1", 1), "", rank=1)
        sb._pending["r1"] = wire.encode_serve_submit("r1", [1], 2, None)
        sb._apply_journal(wire.encode_serve_journal(
            wire.SERVE_J_CANCEL, wire.encode_serve_cancel("r1", "ttl")))
        assert "r1" not in sb._pending
        status = wire.decode_serve_result(sb._results["r1"])[1]
        assert status == wire.SERVE_CANCELLED


# ------------------------------------------------ watch / doctor / jepsen
def _shed_snapshot(total):
    return {"hvd_serving_shed_total": {
        "kind": "counter", "help": "",
        "series": [{"labels": {"class": "best_effort"},
                    "value": float(total)}]}}


class TestShedRateSignal:
    def test_shed_burst_trips_serving_overload(self):
        w = AnomalyWatch(interval=1.0, window=8, factor=3.0, min_samples=2)
        total, fired = 0, []
        for _ in range(6):
            total += 1  # steady trickle: baseline ~1/s
            fired += w.observe_snapshot(_shed_snapshot(total))
        assert fired == []
        total += 500  # the overload burst
        fired = w.observe_snapshot(_shed_snapshot(total))
        assert [s["id"] for s in fired] == ["serving_overload"]
        assert fired[0]["evidence"]["signal"] == "serving_shed_rate"

    def test_absent_family_emits_no_signal(self):
        w = AnomalyWatch(interval=1.0)
        assert "serving_shed_rate" not in w.extract({})


def _bundle(events):
    return {0: {"blackbox": 1, "rank": 0, "world_size": 2, "reason": "t",
                "events": events, "metrics": {}, "open_spans": []}}


class TestServingDoctorSignatures:
    def test_overload_signature_names_class_and_resource(self):
        out = sigs.detect_serving_overload(_bundle([
            {"t": 1.0, "rank": 0, "kind": "anomaly", "name": "serving_shed",
             "detail": "shedding class=best_effort resource=queue "
                       "backlog=5/8"},
            {"t": 1.2, "rank": 0, "kind": "anomaly",
             "name": "serving_saturation",
             "detail": "replica w0 saturated resource=kv_blocks"},
        ]))
        assert [s["id"] for s in out] == ["serving_overload"]
        assert "class=best_effort" in out[0]["summary"]
        assert "kv_blocks" in out[0]["summary"]

    def test_failover_signature_fires_for_serving_promotion(self):
        ev = {"t": 2.0, "rank": 1, "kind": "failover", "name": "serving",
              "detail": "serving standby promoted to frontend at "
                        "127.0.0.1:9 (epoch 2, 3 results, 1 pending "
                        "re-queued) after stream loss"}
        out = sigs.detect_serving_failover(_bundle([ev]))
        assert [s["id"] for s in out] == ["serving_failover"]
        # and it is NOT double-reported as a coordinator failover
        assert sigs.detect_coordinator_failover(_bundle([ev])) == []

    def test_shed_events_do_not_masquerade_as_latency_regression(self):
        out = sigs.detect_latency_regression(_bundle([
            {"t": 1.0, "rank": 0, "kind": "anomaly", "name": "serving_shed",
             "detail": "shedding class=best_effort resource=queue "
                       "backlog=5/8"}]))
        assert out == []


class TestJepsenServingChecker:
    def test_clean_history_passes(self):
        v = jepsen.check_serving_history(_bundle([]), ["a", "b"],
                                         ["a", "b"])
        assert v["lost"] == 0 and v["duplicates"] == 0
        assert v["exactly_once"] and v["violations"] == []

    def test_lost_request_flagged(self):
        v = jepsen.check_serving_history(_bundle([]), ["a", "b"], ["a"])
        assert v["lost"] == 1
        assert any("lost request" in s for s in v["violations"])

    def test_duplicate_delivery_flagged(self):
        v = jepsen.check_serving_history(_bundle([]), ["a"], ["a", "a"])
        assert v["duplicates"] == 1 and not v["exactly_once"]
        assert any("duplicate delivery" in s for s in v["violations"])


# --------------------------------------------------------- pod integration
@pytest.mark.integration
def test_frontend_sigkill_failover_exactly_once(monkeypatch):
    """The tentpole acceptance drill: frontend subprocess SIGKILLed with
    requests in flight; the warm standby wins the rendezvous lease and
    takes over; every request completes exactly once; a frame stamped
    with the deposed epoch is fence-rejected; and re-decodes of the same
    prompts are bit-identical to the answers produced across the
    failover."""
    from horovod_tpu import blackbox as _blackbox
    from horovod_tpu.blackbox import doctor
    from horovod_tpu.run.rendezvous import KVStoreServer
    from horovod_tpu.serving import ServingClient
    from horovod_tpu.serving.worker import (ServingWorker,
                                            build_replica_engine)

    tmp = tempfile.mkdtemp(prefix="hvd_serve_failover_")
    kv = KVStoreServer("", host="127.0.0.1").start()
    for k, v in (("HVD_KV_ADDR", f"127.0.0.1:{kv.port}"),
                 ("HVD_SECRET", ""), ("HOROVOD_LEASE_TTL", "1.0"),
                 ("HOROVOD_SERVING_STANDBY", "1"),
                 ("HOROVOD_BLACKBOX", "1"), ("HOROVOD_BLACKBOX_DIR", tmp),
                 ("HOROVOD_RECONNECT_JITTER", "0.3"),
                 ("HOROVOD_HEARTBEAT_INTERVAL", "0.5")):
        monkeypatch.setenv(k, v)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    fe_proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.serving.server",
         "--rank", "0", "--gen", "0", "--flush-every", "0.2"],
        env=env, cwd=REPO, stdout=subprocess.PIPE, text=True)
    sb = cli = None
    workers = []
    try:
        line = fe_proc.stdout.readline()
        assert line.startswith("SERVING_FRONTEND"), line
        host, port = line.split()[1].rsplit(":", 1)
        addr = (host, int(port))
        _blackbox.maybe_activate()
        _blackbox.set_identity(1, 4)

        sb = ServingStandby(addr, "", rank=1, gen=0).start()
        cfg = ServingConfig(block_size=4, num_blocks=64, max_batch=4,
                            max_context=64)
        workers = [
            ServingWorker(addr[0], addr[1], build_replica_engine(
                max_seq_len=64, config=cfg), name=f"w{i}", rank=2 + i,
                gen=0).start()
            for i in range(2)]
        cli = ServingClient(addr[0], addr[1], name="t", gen=0,
                            max_retries=64)
        prompts = [[(j * 5 + i) % 40 + 1 for i in range(6)]
                   for j in range(10)]
        # warm the compile caches before the kill window
        for f in [cli.submit([1, 2, 3], 2) for _ in range(4)]:
            f.result(timeout=180)

        futs = [cli.submit(p, 8, request_id=f"req-{j}")
                for j, p in enumerate(prompts[:4])]
        time.sleep(0.3)  # in flight
        fe_proc.kill()
        futs += [cli.submit(p, 8, request_id=f"req-{j + 4}")
                 for j, p in enumerate(prompts[4:])]
        answers = [f.result(timeout=300) for f in futs]
        assert sb.promoted
        fe2 = sb.frontend
        assert fe2.fence_epoch >= 2

        # a frame from the deposed epoch is fence-rejected at the
        # promoted frontend
        stale = socket.create_connection(fe2.addr, timeout=5)
        wire.send_frame(stale, "", wire.MSG_SERVE_HELLO, 1, 0,
                        wire.encode_serve_hello(wire.SERVE_ROLE_CLIENT,
                                                "ghost", 0), fence=1)
        stale.settimeout(15)
        assert stale.recv(1) == b""
        stale.close()

        # bit-identical reference: the same prompts re-decoded fresh
        refs = [cli.submit(p, 8).result(timeout=300) for p in prompts]
        assert answers == refs

        # exactly-once ledger over the merged blackbox bundle
        _blackbox.dump("failover integration complete", force=True)
        verdict = jepsen.check_serving_history(
            doctor.load_bundle(tmp),
            [f"req-{j}" for j in range(10)],
            [f"req-{j}" for j in range(10)])
        assert verdict["violations"] == [], verdict
        assert verdict["single_writer"] and verdict["exactly_once"]
    finally:
        if cli is not None:
            cli.close()
        for w in workers:
            w.stop()
        if sb is not None:
            sb.stop()
        if fe_proc.poll() is None:
            fe_proc.kill()
        fe_proc.wait(timeout=10)
        kv.stop()
