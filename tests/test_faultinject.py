"""Fault-injection harness + control-plane hardening (docs/fault-tolerance.md).

Unit layer: HOROVOD_FAULT_SPEC grammar, CRC32/size-bounded framing in
runtime/wire.py, coordinator-side replay/dedupe, liveness accounting.
Socket layer: worker reconnect through a live CoordinatorServer, with and
without injected faults. Integration layer: the acceptance scenario — a real
2-process job with a connection drop and a corrupted frame injected
mid-training converging to the same allreduce results as a fault-free run,
with the reconnect counters visible in the metrics snapshot.
"""

import os
import socket
import struct
import threading
import time

import pytest

from horovod_tpu import faultinject
from horovod_tpu.exceptions import ShutdownError
from horovod_tpu.metrics import instruments
from horovod_tpu.runtime import wire
from horovod_tpu.runtime.coordinator import (
    MSG_HELLO, MSG_LIST, MSG_RESP, CoordController, CoordState)
from horovod_tpu.runtime.messages import RequestType

ALLREDUCE = int(RequestType.ALLREDUCE)


def meta(name, shape=(4,), rtype=ALLREDUCE, dtype="float32", **kw):
    return wire.ReqMeta(name, rtype, dtype, shape, **kw)


def req(metas, flags=0, epoch=-1):
    return wire.encode_request_list(flags, [], metas, epoch=epoch)


def make_state(world=2, elastic=False, **kw):
    kwargs = dict(cache_capacity=64, stall_warning_s=60.0,
                  stall_shutdown_s=0.0, elastic=elastic)
    kwargs.update(kw)
    return CoordState(world, 64 << 20, **kwargs)


# ------------------------------------------------------------- spec grammar
class TestSpecParsing:
    def test_issue_example(self):
        rules = faultinject.parse_spec(
            "conn_drop@tick:3;delay@exchange:0.5;corrupt@frame:1")
        assert [(r.kind, r.point) for r in rules] == [
            ("conn_drop", "tick"), ("delay", "exchange"),
            ("corrupt", "frame")]
        assert rules[0].nth == 3
        assert rules[1].seconds == 0.5 and rules[1].nth is None
        assert rules[2].nth == 1
        assert all(r.ranks is None for r in rules)

    def test_rank_filter(self):
        (r,) = faultinject.parse_spec("truncate@frame:2#1,3")
        assert r.applies_to(1) and r.applies_to(3)
        assert not r.applies_to(0) and not r.applies_to(2)

    def test_delay_with_nth(self):
        (r,) = faultinject.parse_spec("delay@tick:0.25:7")
        assert r.seconds == 0.25 and r.nth == 7

    def test_empty_and_whitespace(self):
        assert faultinject.parse_spec("") == []
        assert faultinject.parse_spec(" ; ;") == []

    @pytest.mark.parametrize("bad", [
        "explode@tick:1",     # unknown kind
        "corrupt@:1",         # no point
        "corrupt",            # no @point at all
        "corrupt@frame:0",    # nth must be >= 1
        "corrupt@frame:x",    # non-integer nth
        "delay@tick",         # delay requires seconds
        "corrupt@frame:1#a",  # bad rank list
    ])
    def test_bad_rules_raise_with_rule_text(self, bad):
        with pytest.raises(ValueError) as ei:
            faultinject.parse_spec(bad)
        assert "HOROVOD_FAULT_SPEC" in str(ei.value)

    def test_for_rank_filters_and_env(self, monkeypatch):
        monkeypatch.delenv(faultinject.ENV_VAR, raising=False)
        assert faultinject.for_rank(0) is None
        monkeypatch.setenv(faultinject.ENV_VAR, "conn_drop@tick:1#1")
        assert faultinject.for_rank(0) is None   # rule is rank-1 only
        assert faultinject.for_rank(1) is not None

    def test_hit_counting_fires_exactly_once(self):
        inj = faultinject.Injector(
            faultinject.parse_spec("corrupt@frame:3"), rank=0)
        fired = [inj.actions_for("frame") for _ in range(5)]
        assert [len(f) for f in fired] == [0, 0, 1, 0, 0]


# ---------------------------------------------------------- frame integrity
class _Pair:
    """socketpair with the receive side configured like the control plane."""

    def __enter__(self):
        self.a, self.b = socket.socketpair()
        self.b.settimeout(0.5)
        self.stop = threading.Event()
        return self

    def __exit__(self, *exc):
        for s in (self.a, self.b):
            try:
                s.close()
            except OSError:
                pass


class TestFrameIntegrity:
    @pytest.mark.parametrize("secret", ["", "s3cret"])
    def test_roundtrip(self, secret):
        with _Pair() as p:
            wire.send_frame(p.a, secret, MSG_LIST, 41, 3, b"payload")
            f = wire.recv_frame(p.b, secret, p.stop)
            assert (f.msg_type, f.seq, f.rank, f.payload) == \
                (MSG_LIST, 41, 3, b"payload")

    def test_corrupted_payload_rejected_by_crc(self):
        before = instruments.frames_rejected().value
        with _Pair() as p:
            # intercept a valid frame, flip its last payload byte, resend
            wire.send_frame(p.a, "", MSG_LIST, 1, 0, b"payload")
            raw = p.b.recv(4096)
            p.a.sendall(raw[:-1] + bytes([raw[-1] ^ 0xFF]))
            with pytest.raises(wire.FrameError) as ei:
                wire.recv_frame(p.b, "", p.stop)
            assert "CRC32" in str(ei.value)
        assert instruments.frames_rejected().value >= before + 1

    def test_faultsocket_corrupt_rule_rejected(self):
        with _Pair() as p:
            inj = faultinject.Injector(
                faultinject.parse_spec("corrupt@frame:1"), rank=0)
            wire.send_frame(inj.wrap(p.a), "", MSG_LIST, 7, 1, b"abcdef")
            with pytest.raises(wire.FrameError):
                wire.recv_frame(p.b, "", p.stop)

    def test_faultsocket_truncate_breaks_connection(self):
        with _Pair() as p:
            inj = faultinject.Injector(
                faultinject.parse_spec("truncate@frame:1"), rank=0)
            with pytest.raises(ConnectionError):
                wire.send_frame(inj.wrap(p.a), "", MSG_LIST, 7, 1,
                                b"abcdef" * 10)
            # the receiver observes EOF mid-frame, not a hang
            with pytest.raises(ConnectionError):
                wire.recv_frame(p.b, "", p.stop)

    def test_partial_writes_reassembled(self):
        """Satellite: byte-at-a-time writes must reassemble — the receiver
        loops to the declared length instead of assuming whole frames."""
        with _Pair() as p:
            inj = faultinject.Injector(
                faultinject.parse_spec("partial@frame:1"), rank=0)
            payload = bytes(range(256)) * 4
            t = threading.Thread(
                target=wire.send_frame,
                args=(inj.wrap(p.a), "sec", MSG_LIST, 9, 1, payload))
            t.start()
            f = wire.recv_frame(p.b, "sec", p.stop)
            t.join(timeout=10)
            assert f.payload == payload and f.seq == 9

    def test_oversized_length_prefix_rejected(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_FRAME_LIMIT_MB", "1")
        before = instruments.frames_rejected().value
        with _Pair() as p:
            p.a.sendall(struct.pack("<I", 2 << 20))  # 2 MB > 1 MB bound
            with pytest.raises(wire.FrameError) as ei:
                wire.recv_frame(p.b, "", p.stop)
        assert "HOROVOD_FRAME_LIMIT_MB" in str(ei.value)
        assert instruments.frames_rejected().value == before + 1

    def test_hmac_mismatch_rejected(self):
        before = instruments.frames_rejected().value
        with _Pair() as p:
            wire.send_frame(p.a, "secret-A", MSG_LIST, 1, 0, b"x")
            with pytest.raises(wire.FrameError) as ei:
                wire.recv_frame(p.b, "secret-B", p.stop)
            assert "HMAC" in str(ei.value)
        assert instruments.frames_rejected().value == before + 1


# ------------------------------------------------------------- replay cache
class TestReplayCache:
    def test_replayed_seq_not_double_applied(self):
        st = make_state(world=1)
        out1 = st.exchange(0, 0, req([meta("a")]))
        out2 = st.exchange(0, 0, req([meta("a")]))  # reconnect replay
        assert out1 == out2
        hits, misses = st.cache_stats()
        assert (hits, misses) == (0, 1), \
            "the replay must be served from cache, not renegotiated"
        assert st.resps == {} and st.fetched == {}

    def test_duplicate_inflight_waits_for_original(self):
        """A replay racing the original serve thread must not enter the
        barrier twice (a double entry would double-count ``fetched`` and
        strand the other rank)."""
        st = make_state(world=2)
        payload = req([meta("d")])
        out = {}

        def run(tag, rank, p):
            out[tag] = st.exchange(rank, 0, p)

        t1 = threading.Thread(target=run, args=("orig", 1, payload))
        t1.start()
        time.sleep(0.1)  # rank 1 is parked in the barrier
        t2 = threading.Thread(target=run, args=("dup", 1, payload))
        t2.start()
        time.sleep(0.1)
        t0 = threading.Thread(target=run, args=("r0", 0, req([meta("d")])))
        t0.start()
        for t in (t0, t1, t2):
            t.join(timeout=10)
            assert not t.is_alive()
        assert out["orig"] == out["dup"]
        decoded = wire.decode_response_list(out["orig"])
        assert decoded[2][0].tensor_names == ["d"]
        assert st.resps == {} and st.fetched == {}, \
            "barrier accounting must see exactly one fetch per rank"

    def test_data_exchange_replay(self):
        import numpy as np

        st = make_state(world=1, elastic=True)
        st.members = {0}
        arr = np.arange(4, dtype=np.float32)
        payload = wire.encode_data_request(0, 0, ALLREDUCE, -1, "float32",
                                           arr.shape, arr.tobytes())
        out1 = st.data_exchange(0, payload)
        out2 = st.data_exchange(0, payload)  # replay
        assert out1 == out2
        status, _, nparts, _, raw = wire.decode_data_result(out1)
        assert status == wire.DATA_OK and nparts == 1
        assert np.frombuffer(raw, np.float32).tolist() == arr.tolist()


# ----------------------------------------------------------------- liveness
class TestLiveness:
    def test_heartbeat_misses_counted_and_timeout_kills(self):
        st = make_state(world=2, elastic=True)
        before = instruments.heartbeat_misses().value
        st.mark_alive(1)
        with st.cv:
            st.last_seen[1] -= 10.0  # silent for ten seconds
        st.check_liveness(grace_s=100.0, hb_interval=1.0, hb_timeout=5.0)
        assert instruments.heartbeat_misses().value >= before + 9
        assert 1 not in st.members and st.epoch == 1

    def test_misses_not_recounted(self):
        st = make_state(world=2, elastic=True)
        before = instruments.heartbeat_misses().value
        st.mark_alive(1)
        with st.cv:
            st.last_seen[1] -= 3.0
        st.check_liveness(grace_s=100.0, hb_interval=1.0, hb_timeout=0.0)
        st.check_liveness(grace_s=100.0, hb_interval=1.0, hb_timeout=0.0)
        delta = instruments.heartbeat_misses().value - before
        assert 3 <= delta <= 4, "each missed interval is charged once"
        assert 1 in st.members  # timeout disabled: counted, not killed

    def test_disconnect_grace_expiry_feeds_rank_lost(self):
        st = make_state(world=2, elastic=True)
        st.rank_disconnected(1, "connection reset by peer")
        st.check_liveness(grace_s=100.0, hb_interval=0.0, hb_timeout=0.0)
        assert 1 in st.members  # still inside the grace window
        time.sleep(0.02)
        st.check_liveness(grace_s=0.01, hb_interval=0.0, hb_timeout=0.0)
        assert 1 not in st.members and st.epoch == 1
        assert "grace window" in st.reset_reason

    def test_resume_cancels_grace_clock(self):
        st = make_state(world=2, elastic=True)
        st.rank_disconnected(1, "reset")
        st.rank_reconnected(1, last_acked=5)
        time.sleep(0.02)
        st.check_liveness(grace_s=0.01, hb_interval=0.0, hb_timeout=0.0)
        assert 1 in st.members and st.epoch == 0

    def test_non_elastic_death_sets_bye(self):
        st = make_state(world=2, elastic=False)
        st.rank_disconnected(1, "gone")
        time.sleep(0.02)
        st.check_liveness(grace_s=0.01, hb_interval=0.0, hb_timeout=0.0)
        assert st.bye
        assert "rank 1" in st.shutdown_reason
        assert "grace window" in st.shutdown_reason


# ------------------------------------------------- socket-level reconnect
class TestReconnect:
    """Two CoordControllers over a live CoordinatorServer (rank 0 hosts)."""

    def _controllers(self, monkeypatch, fault_spec=None, **env):
        from horovod_tpu.run import rendezvous

        secret = rendezvous.make_secret()
        kv = rendezvous.KVStoreServer(secret).start()
        monkeypatch.setenv("HVD_KV_ADDR", f"127.0.0.1:{kv.port}")
        monkeypatch.setenv("HVD_SECRET", secret)
        monkeypatch.setenv("HOROVOD_HEARTBEAT_INTERVAL", "0")
        monkeypatch.setenv("HOROVOD_RECONNECT_BACKOFF", "0.01")
        if fault_spec is not None:
            monkeypatch.setenv("HOROVOD_FAULT_SPEC", fault_spec)
        else:
            monkeypatch.delenv("HOROVOD_FAULT_SPEC", raising=False)
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        common = dict(world=2, fusion_threshold=64 << 20,
                      stall_warning_s=60.0, stall_shutdown_s=0.0,
                      cache_capacity=64, fusion_enabled=True,
                      timeline_path=None, autotune=False, cycle_time_ms=5.0)
        c0 = CoordController(self_rank=0, **common)
        c1 = CoordController(self_rank=1, **common)
        return c0, c1, kv

    def _entry(self, name, value, rank):
        import numpy as np

        from horovod_tpu.runtime.messages import TensorTableEntry

        return TensorTableEntry(
            tensor_name=name, rank=rank, request_type=RequestType.ALLREDUCE,
            array=np.full((4,), value, np.float32))

    def _round(self, c0, c1, name):
        h0 = c0.submit(self._entry(name, 1.0, 0))
        h1 = c1.submit(self._entry(name, 2.0, 1))
        assert h0 >= 0 and h1 >= 0
        out = {}
        t = threading.Thread(target=lambda: out.setdefault(0, c0.tick()))
        t.start()
        out[1] = c1.tick()
        t.join(timeout=30)
        assert not t.is_alive()
        for r in (0, 1):
            responses, _, _, _, _, _ = out[r]
            assert responses[0].tensor_names == [name]

    def test_transparent_reconnect_and_replay(self, monkeypatch):
        before = instruments.control_reconnects().value
        c0, c1, kv = self._controllers(monkeypatch)
        try:
            self._round(c0, c1, "r0")
            # sever rank 1's connection out from under it
            c1._sock.close()
            self._round(c0, c1, "r1")
            self._round(c0, c1, "r2")
            assert instruments.control_reconnects().value >= before + 1
        finally:
            c1.shutdown()
            c0.shutdown()
            kv.stop()

    def test_injected_corrupt_frame_resyncs(self, monkeypatch):
        """corrupt@frame via HOROVOD_FAULT_SPEC: the coordinator rejects the
        frame on CRC, drops the connection, and the worker transparently
        reconnects and replays — training-level result unchanged."""
        rec0 = instruments.control_reconnects().value
        rej0 = instruments.frames_rejected().value
        # frame 1 is rank 1's HELLO; frame 2 its first MSG_LIST
        c0, c1, kv = self._controllers(monkeypatch,
                                       fault_spec="corrupt@frame:2#1")
        try:
            self._round(c0, c1, "z0")
            self._round(c0, c1, "z1")
            assert instruments.frames_rejected().value >= rej0 + 1
            assert instruments.control_reconnects().value >= rec0 + 1
        finally:
            c1.shutdown()
            c0.shutdown()
            kv.stop()

    def test_reconnect_exhaustion_names_the_failure(self, monkeypatch):
        """Satellite: when reconnects run out, the ShutdownError carries the
        coordinator address, rank, last sent/acked seq and the final
        errno — not a bare 'connection lost'."""
        c0, c1, kv = self._controllers(
            monkeypatch, HOROVOD_RECONNECT_ATTEMPTS="2")
        try:
            self._round(c0, c1, "e0")
            addr = c1._addr
            c0._server.stop()   # nothing left to reconnect to
            c1._sock.close()
            c1.submit(self._entry("e1", 2.0, 1))
            with pytest.raises(ShutdownError) as ei:
                c1.tick()
            msg = str(ei.value)
            assert addr in msg
            assert "rank 1" in msg
            assert "last sent seq" in msg and "last acked seq" in msg
            assert "2 reconnect attempts" in msg
            assert "errno" in msg
        finally:
            c1.shutdown()
            c0.shutdown()
            kv.stop()


# -------------------------------------------------------- integration (2p)
def _worker_chaos():
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.metrics import instruments as _ins

    hvd.init()
    r = hvd.rank()
    outs = []
    for i in range(8):
        v = hvd.allreduce(np.full((4,), float(r + i), np.float32),
                          name=f"cz{i}", op=hvd.Sum)
        outs.append([float(x) for x in np.asarray(v)])
    snap = hvd.metrics()
    visible = "hvd_control_reconnects_total" in snap \
        and "hvd_heartbeat_misses_total" in snap
    return (r, outs, float(_ins.control_reconnects().value), visible)


@pytest.mark.integration
def test_mp_chaos_convergence():
    """Acceptance: a 2-process job with a connection drop AND a corrupted
    frame injected mid-training (HOROVOD_FAULT_SPEC) converges to exactly
    the same allreduce results as the fault-free run — no double-applied
    request list — and the reconnect counter is visible via hvd.metrics()."""
    from horovod_tpu.run.api import run

    here = os.path.dirname(os.path.abspath(__file__))
    # HVD_ELASTIC routes allreduce over the coordinator host-wire data
    # plane (the only cross-process eager path on CPU) — which also puts
    # the data-plane replay cache under test, not just the control plane
    env = {
        "JAX_PLATFORMS": "cpu",
        "HVD_ELASTIC": "1",
        "PYTHONPATH": os.pathsep.join([os.path.dirname(here), here]),
    }
    baseline = run(_worker_chaos, np=2, env=env, start_timeout=120)
    chaos_env = dict(env)
    chaos_env["HOROVOD_FAULT_SPEC"] = \
        "conn_drop@tick:4#1;corrupt@frame:6#1"
    chaos = run(_worker_chaos, np=2, env=chaos_env, start_timeout=120)

    base_by_rank = {r: outs for r, outs, _, _ in baseline}
    for r, outs, reconnects, visible in chaos:
        assert outs == base_by_rank[r], \
            "faulted run must converge to the fault-free results"
        assert visible, "reconnect counters must appear in hvd.metrics()"
        if r == 1:
            assert reconnects >= 1, \
                "rank 1 must have reconnected at least once"
