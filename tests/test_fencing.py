"""Fenced coordinator leadership tests (docs/fault-tolerance.md).

Unit layer: KV compare-and-swap, the optional fencing-epoch wire field
(with a golden-hex pin of the knobs-unset layout), FenceGuard admission,
the ``partition@net`` fault grammar and socket semantics, the lease
state machine against a real KV server, the jepsen-lite history checker,
and the coordinator's fenced park. Integration layer: a real 2-process
partition — the standby acquires the lease, the old coordinator
self-fences before the TTL expires, the healed partition produces
fenced-frame rejections, and the survivor's parameters are bit-identical
to an unpartitioned reference run.
"""

import json
import os
import pickle
import socket
import struct
import subprocess
import sys
import threading
import time

import pytest

from horovod_tpu.faultinject import injector as fi_injector
from horovod_tpu.faultinject import jepsen
from horovod_tpu.faultinject.injector import Injector, Partition
from horovod_tpu.faultinject.spec import parse_spec
from horovod_tpu.metrics import instruments
from horovod_tpu.runtime import lease as lease_mod
from horovod_tpu.runtime import wire
from horovod_tpu.runtime.coordinator import (
    MSG_FENCED, MSG_REPL_HELLO, CoordState, CoordinatorFencedError,
    CoordinatorServer)
from horovod_tpu.runtime.lease import LeaseManager, read_lease_epoch


def make_state(world=2, **kw):
    kwargs = dict(cache_capacity=1024, stall_warning_s=60.0,
                  stall_shutdown_s=0.0)
    kwargs.update(kw)
    return CoordState(world, 64 << 20, **kwargs)


def start_kv(monkeypatch):
    from horovod_tpu.run import rendezvous

    secret = rendezvous.make_secret()
    kv = rendezvous.KVStoreServer(secret).start()
    monkeypatch.setenv("HVD_KV_ADDR", f"127.0.0.1:{kv.port}")
    monkeypatch.setenv("HVD_SECRET", secret)
    monkeypatch.delenv("HOROVOD_LEASE_TTL", raising=False)
    monkeypatch.delenv("HOROVOD_LEASE_RENEW", raising=False)
    return kv, secret


# --------------------------------------------------------------- KV put_if
class TestPutIf:
    def test_cas_semantics(self, monkeypatch):
        from horovod_tpu.run import rendezvous

        kv, secret = start_kv(monkeypatch)
        try:
            c = rendezvous.KVStoreClient(f"127.0.0.1:{kv.port}", secret)
            # absent-CAS: succeeds only while the key does not exist
            assert c.put_if("s", "k", b"v1", None)
            assert not c.put_if("s", "k", b"v2", None)
            assert c.get("s", "k") == b"v1"
            # matching expected swaps; stale expected does not
            assert c.put_if("s", "k", b"v2", b"v1")
            assert not c.put_if("s", "k", b"v3", b"v1")
            assert c.get("s", "k") == b"v2"
            # two racers over the same expected value: exactly one wins
            wins = [c.put_if("s", "k", b"a", b"v2"),
                    c.put_if("s", "k", b"b", b"v2")]
            assert wins == [True, False]
            assert c.get("s", "k") == b"a"
        finally:
            kv.stop()


# ------------------------------------------------------- wire fencing field
class _CaptureSock:
    def __init__(self):
        self.buf = b""

    def sendall(self, data):
        self.buf += data


class TestWireFence:
    def test_knobs_unset_frame_is_golden_hex(self):
        """fence=0 frames must stay byte-identical to the pre-fencing
        layout: len | head(<BIi) | crc32 | [hmac] | payload. Pinned as a
        literal so a struct-format or field-order drift fails loudly."""
        s = _CaptureSock()
        wire.send_frame(s, "", 2, 7, 3, b"abc")
        assert s.buf.hex() == "030000000207000000030000003ecf5845616263"
        s = _CaptureSock()
        wire.send_frame(s, "s3cret", 3, 123456, -1, b"\x00\x01\x02")
        assert s.buf.hex() == (
            "030000000340e20100ffffffff93b4e96bcea4dee490d977cccf25a3505ce4"
            "eba3cac3d224af3ada3876409abf2b74bae7000102")
        # and explicitly: no FENCE_BIT on the default path
        assert s.buf[4] & wire.FENCE_BIT == 0

    def test_fenced_frame_layout(self):
        """fence != 0 sets the high msg_type bit and inserts one u32 after
        the fixed head, covered by CRC (and HMAC when keyed)."""
        s = _CaptureSock()
        wire.send_frame(s, "", 2, 7, 3, b"abc", fence=9)
        assert s.buf[4] == 2 | wire.FENCE_BIT
        assert struct.unpack("<I", s.buf[13:17])[0] == 9
        # 4 len + 9 head + 4 fence + 4 crc + payload
        assert len(s.buf) == 4 + 9 + 4 + 4 + 3

    def test_roundtrip_and_guard_learns_epoch(self):
        a, b = socket.socketpair()
        stop = threading.Event()
        guard = wire.FenceGuard(rank=5)
        try:
            wire.send_frame(a, "sek", 3, 42, 1, b"payload", fence=7)
            frame = wire.recv_frame(b, "sek", stop, guard=guard)
            assert (frame.msg_type, frame.seq, frame.rank,
                    frame.payload) == (3, 42, 1, b"payload")
            assert guard.epoch == 7
            # unstamped frames still pass after an epoch was learned
            wire.send_frame(a, "sek", 3, 43, 1, b"x")
            assert wire.recv_frame(b, "sek", stop, guard=guard).seq == 43
        finally:
            a.close()
            b.close()

    def test_guard_rejects_lower_epoch_and_counts(self):
        a, b = socket.socketpair()
        stop = threading.Event()
        guard = wire.FenceGuard(rank=2)
        guard.observe(5)
        before = instruments.frames_fenced().value
        try:
            wire.send_frame(a, "", 3, 1, 0, b"", fence=3)
            with pytest.raises(wire.FenceError):
                wire.recv_frame(b, "", stop, guard=guard)
            assert instruments.frames_fenced().value - before == 1
            # FenceError is connection-fatal, not frame-corrupting: it is
            # a ConnectionError so every reconnect path already handles it
            assert issubclass(wire.FenceError, ConnectionError)
        finally:
            a.close()
            b.close()

    def test_guard_observe_is_monotonic(self):
        guard = wire.FenceGuard()
        guard.observe(4)
        guard.observe(2)
        assert guard.epoch == 4
        guard.admit(6, 3, 0)  # higher stamp raises the tracked epoch
        assert guard.epoch == 6
        guard.admit(0, 3, 0)  # epoch 0 = pre-fencing peer, always admitted


# -------------------------------------------------- partition fault grammar
class TestPartitionSpec:
    def test_parse_minimal(self):
        (r,) = parse_spec("partition@net:0|1")
        assert r.kind == "partition" and r.point == "net"
        assert r.groups == (frozenset({0}), frozenset({1}))
        assert r.seconds == 0.0 and r.start == 0.0

    def test_parse_groups_heal_start(self):
        (r,) = parse_spec("partition@net:0,3|1,2:6:2.5")
        assert r.groups == (frozenset({0, 3}), frozenset({1, 2}))
        assert r.seconds == 6.0 and r.start == 2.5

    @pytest.mark.parametrize("bad", [
        "partition@frame:0|1",      # wrong point
        "partition@net",            # no groups
        "partition@net:01",         # no separator
        "partition@net:|1",         # empty group
        "partition@net:0|0,1",      # overlapping groups
        "partition@net:0|1:-1",     # negative heal
        "partition@net:0|1:5:-2",   # negative start
        "partition@net:a|b",        # non-integer ranks
    ])
    def test_parse_rejects(self, bad):
        with pytest.raises(ValueError):
            parse_spec(bad)


class TestPartitionSemantics:
    def _part(self, monkeypatch, spec):
        monkeypatch.setattr(fi_injector, "_PART_T0", time.monotonic())
        return Partition(parse_spec(spec)[0])

    def test_active_cut_is_bidirectional_and_cross_group_only(
            self, monkeypatch):
        p = self._part(monkeypatch, "partition@net:0|1,2")
        assert p.active()
        assert p.blocks(0, 1) and p.blocks(1, 0)
        assert p.blocks(0, 2) and p.blocks(2, 0)
        assert not p.blocks(1, 2)          # same side
        assert not p.blocks(0, 0)
        assert not p.blocks(None, 1) and not p.blocks(0, None)

    def test_first_group_loses_the_kv(self, monkeypatch):
        p = self._part(monkeypatch, "partition@net:0|1")
        assert p.blocks_kv(0) and not p.blocks_kv(1)

    def test_future_start_is_inactive(self, monkeypatch):
        p = self._part(monkeypatch, "partition@net:0|1:0:30")
        assert not p.active() and not p.blocks(0, 1)
        assert not p.blocks_kv(0)

    def test_deterministic_heal(self, monkeypatch):
        p = self._part(monkeypatch, "partition@net:0|1:0.15")
        assert p.active() and p.blocks(0, 1)
        deadline = time.monotonic() + 5
        while p.active() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not p.active() and not p.blocks(0, 1) and not p.blocks_kv(0)

    def test_zero_heal_never_heals(self, monkeypatch):
        p = self._part(monkeypatch, "partition@net:0|1")
        assert p._heal is None and p.active()


class TestFaultSocketPartition:
    def test_cross_cut_sendall_severs(self, monkeypatch):
        monkeypatch.setattr(fi_injector, "_PART_T0", time.monotonic())
        inj = Injector(parse_spec("partition@net:0|1"), rank=0)
        a, b = socket.socketpair()
        try:
            fs = inj.wrap(a)
            fs.set_peer(1)
            with pytest.raises(ConnectionError):
                fs.sendall(b"frame")
            # the cut-wire model: the socket is closed, not left hanging
            with pytest.raises(OSError):
                a.sendall(b"x")
        finally:
            b.close()

    def test_unknown_peer_and_same_side_pass(self, monkeypatch):
        monkeypatch.setattr(fi_injector, "_PART_T0", time.monotonic())
        inj = Injector(parse_spec("partition@net:0|1,2"), rank=1)
        a, b = socket.socketpair()
        try:
            fs = inj.wrap(a)
            fs.set_peer(None)          # unattributed: never partitioned
            fs.sendall(b"hello")
            fs.set_peer(2)             # same side of the cut
            fs.sendall(b"again")
            assert b.recv(64) == b"helloagain"
        finally:
            a.close()
            b.close()


# ------------------------------------------------------------ lease machine
class TestLeaseManager:
    def test_acquire_initial_and_supersede(self, monkeypatch):
        from horovod_tpu.run import rendezvous

        kv, secret = start_kv(monkeypatch)
        try:
            lm = LeaseManager(gen=901, rank=0)
            assert lm.acquire_initial() == 1
            c = rendezvous.KVStoreClient(f"127.0.0.1:{kv.port}", secret)
            assert c.get(lease_mod.LEASE_SCOPE, "lease.901") == b"1:0:0"
            # a restarted coordinator supersedes its own leftover value
            lm2 = LeaseManager(gen=901, rank=0)
            assert lm2.acquire_initial() == 2
            assert read_lease_epoch(901) == 2
            assert read_lease_epoch(40404) == 0
        finally:
            kv.stop()

    def test_acquire_over_cas(self, monkeypatch):
        kv, _ = start_kv(monkeypatch)
        try:
            holder = LeaseManager(gen=902, rank=0)
            holder.acquire_initial()
            acq = LeaseManager(gen=902, rank=1)
            cur = acq.read()
            assert acq.acquire_over(cur) == 2
            # the observed value is now stale: a second takeover attempt
            # from it loses the CAS and restores the acquirer's state
            assert acq.acquire_over(cur) is None
            assert acq.epoch == 2
            assert acq.read() == b"2:1:0"
        finally:
            kv.stop()

    def test_renewal_then_deposed_fences(self, monkeypatch):
        from horovod_tpu.run import rendezvous

        kv, secret = start_kv(monkeypatch)
        monkeypatch.setenv("HOROVOD_LEASE_TTL", "5")
        monkeypatch.setenv("HOROVOD_LEASE_RENEW", "0.1")
        fenced = threading.Event()
        why = []
        renewed0 = instruments.lease_renewals().value
        lm = LeaseManager(gen=903, rank=0)
        try:
            lm.acquire_initial()
            lm.start_renewing(lambda r: (why.append(r), fenced.set()))
            deadline = time.monotonic() + 10
            while (instruments.lease_renewals().value <= renewed0
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert instruments.lease_renewals().value > renewed0
            assert not fenced.is_set()
            # somebody else moves the lease: the holder is deposed and
            # must fence immediately, not at the renewal-timeout deadline
            c = rendezvous.KVStoreClient(f"127.0.0.1:{kv.port}", secret)
            c.put(lease_mod.LEASE_SCOPE, "lease.903", b"99:1:0")
            assert fenced.wait(10), "deposed holder never fenced"
            assert "deposed" in why[0]
        finally:
            lm.stop()
            kv.stop()

    def test_unreachable_kv_fences_before_ttl(self, monkeypatch):
        kv, _ = start_kv(monkeypatch)
        monkeypatch.setenv("HOROVOD_LEASE_TTL", "0.8")
        monkeypatch.setenv("HOROVOD_LEASE_RENEW", "0.1")
        fenced = threading.Event()
        why = []
        lm = LeaseManager(gen=904, rank=0)
        try:
            lm.acquire_initial()
            t0 = time.monotonic()
            kv.stop()
            lm.start_renewing(lambda r: (why.append(r), fenced.set()))
            assert fenced.wait(10), "unrenewable holder never fenced"
            # self-fencing fires at FENCE_FRACTION * TTL — strictly before
            # the full TTL any acquirer must observe in stasis
            assert time.monotonic() - t0 < 0.8 + 2.0
            assert "could not renew" in why[0]
        finally:
            lm.stop()

    def test_partitioned_holder_self_fences(self, monkeypatch):
        """Regression: the renewal loop must ask the partition rule itself
        — the KV client rides a plain socket the FaultSocket cut never
        touches, so a partitioned holder would otherwise renew forever."""
        import horovod_tpu.faultinject as faultinject

        kv, _ = start_kv(monkeypatch)
        monkeypatch.setenv("HOROVOD_LEASE_TTL", "0.8")
        monkeypatch.setenv("HOROVOD_LEASE_RENEW", "0.1")
        lm = LeaseManager(gen=906, rank=0)
        try:
            lm.acquire_initial()
            monkeypatch.setattr(fi_injector, "_PART_T0", time.monotonic())
            part = Partition(parse_spec("partition@net:0|1")[0])
            monkeypatch.setattr(faultinject, "partition_for_rank",
                                lambda rank: part)
            fenced = threading.Event()
            why = []
            lm.start_renewing(lambda r: (why.append(r), fenced.set()))
            assert fenced.wait(10), "partitioned holder never self-fenced"
            assert "could not renew" in why[0]
        finally:
            lm.stop()
            kv.stop()

    def test_partitioned_kv_counts_as_unreachable(self, monkeypatch):
        import horovod_tpu.faultinject as faultinject

        kv, _ = start_kv(monkeypatch)
        monkeypatch.setattr(fi_injector, "_PART_T0", time.monotonic())
        part = Partition(parse_spec("partition@net:0|1")[0])
        monkeypatch.setattr(faultinject, "partition_for_rank",
                            lambda rank: part)
        try:
            lm = LeaseManager(gen=905, rank=0)
            with pytest.raises(ConnectionError):
                lm.read()
            lm1 = LeaseManager(gen=905, rank=1)
            assert lm1.read() is None  # majority side still sees the KV
        finally:
            kv.stop()


# ------------------------------------------------------ jepsen-lite checker
def _doc(*events):
    return {"events": [
        {"kind": k, "name": "", "detail": d, "t": t, "rank": r}
        for (k, d, t, r) in events]}


def _lease_ev(what, epoch, t, rank):
    return ("fence", "%s epoch=%d" % (what, epoch), t, rank)


class TestJepsen:
    def test_clean_history_passes(self):
        bundle = {
            0: _doc(_lease_ev("lease_acquired", 1, 0.0, 0),
                    _lease_ev("lease_renewed", 1, 1.0, 0),
                    _lease_ev("lease_renewed", 1, 2.0, 0),
                    _lease_ev("self_fenced", 1, 3.0, 0)),
            1: _doc(_lease_ev("lease_acquired", 2, 4.0, 1),
                    _lease_ev("lease_renewed", 2, 5.0, 1)),
        }
        v = jepsen.check_history(bundle, step_logs={0: [0, 1], 1: [0, 1, 2]})
        assert v["single_writer"] and v["exactly_once"]
        assert v["violations"] == []
        assert len(v["intervals"]) == 2
        assert v["intervals"][0]["fenced"] is True
        assert v["intervals"][1]["fenced"] is False

    def test_overlap_is_split_brain(self):
        bundle = {
            0: _doc(_lease_ev("lease_acquired", 1, 0.0, 0),
                    _lease_ev("lease_renewed", 1, 10.0, 0)),
            1: _doc(_lease_ev("lease_acquired", 2, 5.0, 1),
                    _lease_ev("lease_renewed", 2, 9.0, 1)),
        }
        v = jepsen.check_history(bundle)
        assert not v["single_writer"]
        assert any("split-brain" in s for s in v["violations"])

    def test_one_epoch_two_holders(self):
        bundle = {
            0: _doc(_lease_ev("lease_acquired", 1, 0.0, 0),
                    _lease_ev("self_fenced", 1, 1.0, 0)),
            1: _doc(_lease_ev("lease_acquired", 1, 2.0, 1)),
        }
        v = jepsen.check_history(bundle)
        assert any("two holders" in s for s in v["violations"])

    def test_epoch_regression(self):
        bundle = {
            0: _doc(_lease_ev("lease_acquired", 5, 0.0, 0),
                    _lease_ev("self_fenced", 5, 1.0, 0)),
            1: _doc(_lease_ev("lease_acquired", 3, 2.0, 1)),
        }
        v = jepsen.check_history(bundle)
        assert any("regression" in s for s in v["violations"])

    def test_duplicate_step_breaks_exactly_once(self):
        bundle = {0: _doc(_lease_ev("lease_acquired", 1, 0.0, 0))}
        v = jepsen.check_history(bundle, step_logs={1: [0, 1, 1, 2]})
        assert v["single_writer"] and not v["exactly_once"]
        assert any("duplicate apply" in s for s in v["violations"])

    def test_fenced_frame_count(self):
        bundle = {
            1: _doc(("fence", "fenced_frame type=FENCED from_epoch=1 "
                     "local_epoch=2 sender_rank=0", 9.0, 1),
                    ("fence", "fenced_frame type=LIST from_epoch=1 "
                     "local_epoch=2 sender_rank=0", 9.5, 1)),
        }
        assert jepsen.fenced_frame_count(bundle) == 2
        assert jepsen.check_history(bundle)["fenced_frames"] == 2

    def test_split_brain_doctor_signature(self):
        from horovod_tpu.blackbox import signatures

        clean = {0: _doc(_lease_ev("lease_acquired", 1, 0.0, 0))}
        assert signatures.detect_split_brain(clean) == []
        bad = {
            0: _doc(_lease_ev("lease_acquired", 1, 0.0, 0),
                    _lease_ev("lease_renewed", 1, 10.0, 0)),
            1: _doc(_lease_ev("lease_acquired", 2, 5.0, 1),
                    _lease_ev("lease_renewed", 2, 9.0, 1)),
        }
        (sig,) = signatures.detect_split_brain(bad)
        assert sig["id"] == "split_brain"
        assert sig["severity"] == signatures.SEV_CRITICAL
        assert sig["evidence"]["violations"]


# --------------------------------------------------- coordinator-side fence
class TestCoordinatorFence:
    def _payload(self):
        return wire.encode_request_list(
            0, [], [wire.ReqMeta("t", 0, "float32", (4,))])

    def test_fence_parks_the_exchange(self):
        st = make_state(world=2)
        st.fence("lost the lease (test)")
        with pytest.raises(CoordinatorFencedError):
            st.exchange(0, 1, self._payload())
        # idempotent: the first reason wins
        st.fence("second reason")
        assert st.fence_reason == "lost the lease (test)"

    def test_fence_releases_blocked_waiters(self):
        st = make_state(world=2)
        err = []
        done = threading.Event()

        def waiter():
            try:
                st.exchange(0, 1, self._payload())
            except CoordinatorFencedError as exc:
                err.append(exc)
            done.set()

        t = threading.Thread(target=waiter, daemon=True)
        t.start()
        time.sleep(0.2)  # let the waiter enter the barrier wait
        st.fence("deposed mid-barrier")
        assert done.wait(5), "fence never released the blocked exchange"
        assert err and isinstance(err[0], CoordinatorFencedError)

    def test_fenced_server_answers_dials_with_fenced_frame(self):
        st = make_state(world=2)
        server = CoordinatorServer(st, "sek")
        server.fence_epoch = 5
        st.fence("renewal timeout (test)")
        stop = threading.Event()
        guard = wire.FenceGuard(rank=1)
        try:
            s = socket.create_connection(("127.0.0.1", server.port),
                                         timeout=5)
            wire.send_frame(s, "sek", MSG_REPL_HELLO, 0, 1)
            frame = wire.recv_frame(s, "sek", stop, guard=guard)
            assert frame.msg_type == MSG_FENCED
            assert b"renewal timeout" in frame.payload
            # the FENCED answer carries the deposed epoch: a dialer that
            # follows a newer leader learns nothing; one that follows none
            # (epoch 0) learns where the fence line sits
            assert guard.epoch == 5
            s.close()
        finally:
            server.stop()


# ----------------------- satellite: promotion racing an elastic epoch bump
class TestPromotionJoinRace:
    def test_joiner_admitted_between_snapshot_and_promote(self, monkeypatch):
        """A rank admitted AFTER the standby's snapshot but BEFORE the
        primary dies must survive failover: the journal record for the
        join's epoch bump is applied by the standby, so the promoted state
        carries the post-join member set, not the snapshot's."""
        from horovod_tpu.runtime.standby import StandbyCoordinator

        kv, secret = start_kv(monkeypatch)
        st = make_state(world=2, elastic=True)
        server = CoordinatorServer(st, secret)
        sb = StandbyCoordinator(
            rank=1, gen=801, host="127.0.0.1", port=server.port,
            secret=secret,
            make_state=lambda: make_state(world=2, elastic=True),
            should_promote=lambda: True)
        sb.start()
        try:
            deadline = time.monotonic() + 10
            while not sb._have_snapshot and time.monotonic() < deadline:
                time.sleep(0.05)
            assert sb._have_snapshot
            assert sb._members == [0, 1] and sb._epoch == 0
            # rank 2 joins at a commit boundary: one journaled epoch bump
            with st.cv:
                st.pending_joins.add(2)
                st._pending_join_last_t = time.monotonic() - 60
                st.committed |= set(st.members)
                st._maybe_admit_locked()
            assert st.epoch == 1 and st.members == {0, 1, 2}
            deadline = time.monotonic() + 10
            while sb._epoch != 1 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert sb._epoch == 1 and sb._members == [0, 1, 2]
            # the primary dies right behind the join's journal record
            server.die()
            deadline = time.monotonic() + 15
            while not sb.promoted and time.monotonic() < deadline:
                time.sleep(0.05)
            assert sb.promoted
            # promotion = the join bump PLUS the rank-0 loss, never a
            # rollback to the snapshot membership
            assert sb.server.state.epoch == 2
            assert sb.server.state.members == {1, 2}
        finally:
            sb.stop()
            server.stop()
            kv.stop()


# ----------------------------------------- standby lease-gated promotion
class TestLeaseGatedPromotion:
    def test_standby_promotes_only_by_acquiring_the_lease(self, monkeypatch):
        from horovod_tpu.runtime.standby import StandbyCoordinator

        kv, secret = start_kv(monkeypatch)
        monkeypatch.setenv("HOROVOD_LEASE_TTL", "1.0")
        monkeypatch.setenv("HOROVOD_LEASE_RENEW", "0.2")
        st = make_state(world=2, elastic=True)
        server = CoordinatorServer(st, secret)
        holder = LeaseManager(gen=802, rank=0)
        assert holder.acquire_initial() == 1
        sb = StandbyCoordinator(
            rank=1, gen=802, host="127.0.0.1", port=server.port,
            secret=secret,
            make_state=lambda: make_state(world=2, elastic=True),
            should_promote=lambda: True)
        sb.start()
        try:
            deadline = time.monotonic() + 10
            while not sb._have_snapshot and time.monotonic() < deadline:
                time.sleep(0.05)
            assert sb._have_snapshot
            # the primary dies and never renews again: the standby must
            # wait out a full TTL of observed stasis, then CAS the lease
            server.die()
            assert not sb.promoted
            deadline = time.monotonic() + 20
            while not sb.promoted and time.monotonic() < deadline:
                time.sleep(0.05)
            assert sb.promoted, "standby never acquired the expired lease"
            # the promoted server stamps its frames with the CAS-ed epoch
            assert sb.server.fence_epoch == 2
            assert sb._guard.epoch == 2
            assert read_lease_epoch(802) == 2
        finally:
            sb.stop()
            server.stop()
            holder.stop()
            kv.stop()


# ---------------------------------- integration: partition chaos, 2 ranks
def _fence_partition_train_fn():
    """2 ranks with the lease plane on. In chaos runs a ``partition@net``
    cut isolates rank 0 (with the coordinator) from rank 1 (with the
    standby) mid-training: rank 0 self-fences before the TTL expires,
    rank 1's standby acquires the lease, promotes, and finishes the run;
    after the heal the old primary's FENCED answer is rejected by the
    promoted side's fence guard (hvd_frames_fenced_total > 0). The
    gradient is identical on every rank, so averaging over ANY member set
    reproduces it bit-exactly — the final parameters must match an
    unpartitioned reference run bit for bit."""
    import os
    import time

    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import blackbox
    from horovod_tpu.metrics import instruments

    chaos = bool(os.environ.get("HOROVOD_FAULT_SPEC"))
    hvd.init()
    rank = hvd.rank()
    state = hvd.elastic.ElasticState(w=np.array([4.0], np.float32), step=0)
    applied = []

    @hvd.elastic.run_fn
    def train(state):
        while state.step < 12:
            if chaos:
                # pace the run so the partition window lands mid-training
                time.sleep(0.7)
            w = np.asarray(state.w, np.float32)
            g = (w - np.float32(1.0)).astype(np.float32)
            avg = hvd.allreduce(g, name=f"grad{state.step}", op=hvd.Average)
            state.w = (w - np.float32(0.1)
                       * np.asarray(avg, np.float32)).astype(np.float32)
            step = state.step
            state.step += 1
            state.commit()
            applied.append(step)  # logged only once the commit landed
        return np.asarray(state.w, np.float32)

    try:
        w = train(state)
        fenced_seen = 0
        if chaos:
            # post-heal evidence: the promoted standby's lease-mode redial
            # reaches the old primary, whose FENCED answer carries the
            # deposed epoch and is rejected by the fence guard
            deadline = time.monotonic() + 25
            while time.monotonic() < deadline:
                fenced_seen = int(instruments.frames_fenced().value)
                if fenced_seen:
                    break
                time.sleep(0.25)
        blackbox.dump("fencing harness end", force=True)
        return ("done", applied, w.tobytes().hex(), fenced_seen)
    except Exception as exc:  # the fenced side of the cut lands here
        if chaos and rank == 0:
            # stay alive past the heal so the fenced server can answer
            # the promoted standby's redial with its FENCED frame
            time.sleep(12.0)
        blackbox.dump("fencing harness end", force=True)
        return ("fenced", repr(exc), applied)


def _run_fence_job(chaos: bool, bb_dir: str):
    import cloudpickle

    from horovod_tpu.run import rendezvous

    here = os.path.dirname(os.path.abspath(__file__))
    secret = rendezvous.make_secret()
    kv = rendezvous.KVStoreServer(secret).start()
    addr = f"127.0.0.1:{kv.port}"
    client = rendezvous.KVStoreClient(addr, secret)
    client.put("runfunc", "fn",
               cloudpickle.dumps((_fence_partition_train_fn, (), {})))

    procs = []
    results = {}
    try:
        for r in range(2):
            env = dict(os.environ)
            env.update({
                "HVD_NUM_PROCS": "2",
                "HVD_PROCESS_ID": str(r),
                "HVD_KV_ADDR": addr,
                "HVD_SECRET": secret,
                "HVD_ELASTIC": "1",
                "HOROVOD_STANDBY_COORD": "1",
                "HOROVOD_LEASE_TTL": "1.2",
                "HOROVOD_LEASE_RENEW": "0.25",
                "HOROVOD_RECONNECT_GRACE": "20",
                "HOROVOD_BLACKBOX": "1",
                "HOROVOD_BLACKBOX_DIR": bb_dir,
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": os.pathsep.join(
                    [os.path.dirname(here), here]),
            })
            env.pop("XLA_FLAGS", None)
            if chaos:
                # cut 0 | 1 eight seconds in (safely past rendezvous),
                # heal six seconds later; rank 0's side loses the KV
                env["HOROVOD_FAULT_SPEC"] = "partition@net:0|1:6:8"
            else:
                env.pop("HOROVOD_FAULT_SPEC", None)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "horovod_tpu.run.task"], env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))

        deadline = time.time() + 240
        while time.time() < deadline and len(results) < 2:
            for r in range(2):
                if r not in results:
                    blob = client.get("result", str(r))
                    if blob is not None:
                        results[r] = blob
            if len(results) < 2 and all(p.poll() is not None for p in procs):
                time.sleep(1.0)  # final PUTs may still be in flight
                for r in range(2):
                    blob = client.get("result", str(r))
                    if blob is not None:
                        results[r] = blob
                break
            time.sleep(0.25)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        kv.stop()
    assert len(results) == 2, (
        f"job incomplete (chaos={chaos}): results from {sorted(results)}, "
        f"exit codes {[p.poll() for p in procs]}")
    out = {}
    for r, blob in results.items():
        ok, payload = pickle.loads(blob)
        assert ok, f"rank {r} raised:\n{payload}"
        out[r] = payload
    return out


@pytest.mark.integration
def test_partition_failover_fenced_bit_identical(tmp_path):
    """ISSUE acceptance: partition rank 0 (coordinator side) from rank 1
    (standby side) mid-training. The standby acquires the lease and takes
    over; the old coordinator self-fences before the TTL expires; fencing
    epochs on the wire reject the deposed side's traffic after the heal;
    the jepsen-lite checker passes the merged history; and the survivor's
    final parameters are bit-identical to an unpartitioned reference."""
    chaos_dir = str(tmp_path / "chaos_bb")
    chaos = _run_fence_job(chaos=True, bb_dir=chaos_dir)

    # rank 1 finished all 12 steps exactly once on the promoted coordinator
    assert chaos[1][0] == "done", chaos[1]
    _, applied1, w1_hex, fenced_seen = chaos[1]
    assert applied1 == list(range(12)), applied1
    # wire-level proof that fencing bit: a stamped frame from the deposed
    # epoch was rejected on the survivor side after the heal
    assert fenced_seen > 0, "no fenced-frame rejection observed on rank 1"

    # rank 0 was fenced out of the run, never finishing its steps
    assert chaos[0][0] == "fenced", chaos[0]

    # merged blackbox history: single-writer leadership, exactly-once
    bundle = {}
    for r in range(2):
        with open(os.path.join(chaos_dir, f"rank_{r}.json")) as f:
            bundle[r] = json.load(f)
    verdict = jepsen.check_history(
        bundle, step_logs={1: applied1, 0: chaos[0][2]})
    assert verdict["single_writer"], verdict["violations"]
    assert verdict["exactly_once"], verdict["violations"]
    assert verdict["fenced_frames"] > 0
    intervals = verdict["intervals"]
    by_rank = {iv["rank"]: iv for iv in intervals}
    # the old coordinator held epoch 1 and explicitly self-fenced; the
    # promoted standby acquired a strictly higher epoch
    assert by_rank[0]["epoch"] == 1 and by_rank[0]["fenced"]
    assert by_rank[1]["epoch"] > by_rank[0]["epoch"]
    # rank 0's own log shows the renewal-timeout fence (KV lost to the cut)
    details = [e.get("detail") or "" for e in bundle[0]["events"]]
    assert any("self_fenced" in d and "renewal_timeout" in d
               for d in details), "rank 0 never recorded its self-fence"

    # reference run without the partition: bit-identical trajectory
    ref = _run_fence_job(chaos=False, bb_dir=str(tmp_path / "ref_bb"))
    assert ref[0][0] == "done" and ref[1][0] == "done"
    assert ref[1][1] == list(range(12))
    assert w1_hex == ref[1][2], (
        "survivor parameters diverged from the unpartitioned reference")
    assert ref[0][2] == ref[1][2]
