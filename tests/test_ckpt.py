"""Async sharded checkpointing + peer-redundant recovery tests
(docs/checkpoint.md).

Unit layer: the MSG_CKPT_MARK/DONE and buddy-journal wire codecs, the
exact byte-partition (`optim.zero.shard_bounds`), bundle manifest
atomicity (a crash mid-write leaves the previous complete bundle
authoritative and no temp-file litter), journal delta bit-exactness,
a live BuddyServer/BuddyClient stream, the coordinator's bundle
consistency stamps, the async writer's ~0 step-path stall and
freshest-wins double buffer, the manager's commit/restore paths, the
legacy ``checkpoint.save`` delegation + symmetric overwrite guard, the
``stale_checkpoint`` doctor signature, and the bundle-age anomaly
signal. Integration layer: a real 2-process CPU job where one worker is
hard-killed mid-training and a same-rank replacement restores its shard
from the buddy journal — the resumed trajectory must be bit-identical
to an uninterrupted run.
"""

import os
import pickle
import subprocess
import sys
import threading
import time
import zlib

import numpy as np
import pytest

from horovod_tpu import blackbox
from horovod_tpu.blackbox import signatures as sigs
from horovod_tpu.blackbox.watch import AnomalyWatch
from horovod_tpu.ckpt import buddy as buddy_mod
from horovod_tpu.ckpt import bundle, manager
from horovod_tpu.ckpt.writer import AsyncShardWriter
from horovod_tpu.elastic import ElasticState
from horovod_tpu.optim.zero import shard_bounds
from horovod_tpu.runtime import wire
from horovod_tpu.runtime.coordinator import CoordState

_ENV = ("HOROVOD_CKPT_DIR", "HOROVOD_CKPT_INTERVAL", "HOROVOD_CKPT_BUDDY",
        "HOROVOD_CKPT_KEEP", "HOROVOD_ELASTIC_RESPAWN")


@pytest.fixture(autouse=True)
def _fresh_ckpt(monkeypatch):
    """Knobs unset and the process-global manager torn down around every
    test — a leaked manager would leak its writer/buddy threads into the
    next test's assertions."""
    for var in _ENV:
        monkeypatch.delenv(var, raising=False)
    manager.shutdown()
    yield
    manager.shutdown()


# ------------------------------------------------------------------ codecs
class TestWireCodecs:
    def test_frame_ids_and_names(self):
        # ids 26/27 are the checkpoint stamps; both are named so the
        # blackbox frame taps see them like any other control frame
        assert wire.MSG_CKPT_MARK == 26 and wire.MSG_CKPT_DONE == 27
        assert wire._FRAME_NAMES[26] == "CKPT_MARK"
        assert wire._FRAME_NAMES[27] == "CKPT_DONE"

    def test_ckpt_mark_roundtrip(self):
        buf = wire.encode_ckpt_mark(1 << 40, 7, 3)
        assert wire.decode_ckpt_mark(buf) == (1 << 40, 7, 3)

    def test_ckpt_done_roundtrip_masks_crc(self):
        buf = wire.encode_ckpt_done(12, 2, 1, 9 << 30, 0x1_2345_6789)
        step, epoch, index, nbytes, crc = wire.decode_ckpt_done(buf)
        assert (step, epoch, index, nbytes) == (12, 2, 1, 9 << 30)
        assert crc == 0x2345_6789  # u32 on the wire

    def test_shard_snapshot_roundtrip(self):
        for data in (b"", b"\x00" * 17, os.urandom(1000)):
            buf = wire.encode_shard_snapshot(4, 99, data)
            assert wire.decode_shard_snapshot(buf) == (4, 99, data)

    def test_shard_journal_roundtrip(self):
        blocks = [(0, b"abc"), (1 << 20, os.urandom(64)), (7, b"")]
        buf = wire.encode_shard_journal(2, 55, 3 << 20, blocks)
        assert wire.decode_shard_journal(buf) == (2, 55, 3 << 20, blocks)
        buf = wire.encode_shard_journal(0, 1, 10, [])
        assert wire.decode_shard_journal(buf) == (0, 1, 10, [])


# --------------------------------------------------------------- partition
class TestShardBounds:
    @pytest.mark.parametrize("total,world", [(0, 1), (1, 1), (11, 2),
                                             (11, 3), (64, 8), (7, 16)])
    def test_partition_is_exact_cover(self, total, world):
        cursor = 0
        for i in range(world):
            lo, hi = shard_bounds(total, world, i)
            assert lo == cursor and lo <= hi <= total
            cursor = hi
        assert cursor == total

    def test_block_alignment(self):
        lo, hi = shard_bounds(100, 3, 1, block=16)
        assert lo % 16 == 0 and lo == 48 and hi == 96
        # last shard absorbs the ragged tail, clamped to total
        assert shard_bounds(100, 3, 2, block=16) == (96, 100)

    def test_concat_reassembles_bytes(self):
        blob = os.urandom(1000)
        parts = [blob[slice(*manager.partition_bounds(len(blob), 3, i))]
                 for i in range(3)]
        assert b"".join(parts) == blob


# ------------------------------------------------------------------ bundle
class TestBundle:
    def _land(self, root, step, shards, epoch=0, finalize=True):
        infos = {}
        for i, data in shards.items():
            n, c = bundle.write_shard(root, step, i, data)
            infos[i] = {"nbytes": n, "crc": c}
        if finalize:
            bundle.finalize_manifest(root, step, epoch, infos)
        return infos

    def test_roundtrip_and_completeness(self, tmp_path):
        root = str(tmp_path)
        self._land(root, 3, {0: b"hello", 1: b"world"})
        assert bundle.complete_steps(root) == [3]
        assert bundle.read_shard(root, 3, 0) == b"hello"
        assert bundle.read_shard(root, 3, 1) == b"world"

    def test_manifest_is_the_commit_record(self, tmp_path):
        """Shards landed but no manifest = incomplete: the previous
        complete bundle stays authoritative."""
        root = str(tmp_path)
        self._land(root, 1, {0: b"old0", 1: b"old1"})
        self._land(root, 2, {0: b"new0", 1: b"new1"}, finalize=False)
        assert bundle.latest_complete_step(root) == 1
        with pytest.raises(FileNotFoundError):
            bundle.read_bundle_bytes(root, 2)

    def test_crash_mid_write_leaves_no_litter(self, tmp_path, monkeypatch):
        root = str(tmp_path)
        path = os.path.join(root, "blob")
        bundle.atomic_write_bytes(path, b"v1")

        def boom(src, dst):
            raise OSError("disk full")

        monkeypatch.setattr(os, "replace", boom)
        with pytest.raises(OSError):
            bundle.atomic_write_bytes(path, b"v2")
        monkeypatch.undo()
        assert open(path, "rb").read() == b"v1"
        assert not [n for n in os.listdir(root)
                    if n.startswith(".ckpt_tmp_")]

    def test_corrupt_or_short_bundle_is_skipped(self, tmp_path):
        root = str(tmp_path)
        self._land(root, 1, {0: b"good"})
        self._land(root, 2, {0: b"xxxx"})
        # truncate step 2's shard after the manifest landed
        with open(bundle.shard_path(root, 2, 0), "wb") as f:
            f.write(b"x")
        assert bundle.complete_steps(root) == [1]
        # corrupt manifest json reads as None
        with open(os.path.join(bundle.step_dir(root, 2),
                               bundle.MANIFEST), "wb") as f:
            f.write(b"{nope")
        assert bundle.read_manifest(root, 2) is None

    def test_crc_verified_on_read(self, tmp_path):
        root = str(tmp_path)
        self._land(root, 1, {0: b"payload"})
        with open(bundle.shard_path(root, 1, 0), "wb") as f:
            f.write(b"tampered")  # same path, wrong bytes
        with pytest.raises(OSError):
            bundle.read_shard(root, 1, 0)

    def test_read_bundle_bytes_trims_total_len(self, tmp_path):
        root = str(tmp_path)
        blob = os.urandom(100)
        infos = {}
        for i in range(3):
            lo, hi = manager.partition_bounds(len(blob), 3, i)
            n, c = bundle.write_shard(root, 5, i, blob[lo:hi])
            infos[i] = {"nbytes": n, "crc": c}
        bundle.finalize_manifest(root, 5, 0, infos, total_len=len(blob))
        assert bundle.read_bundle_bytes(root, 5) == blob

    def test_prune_keeps_newest_and_drops_debris(self, tmp_path):
        root = str(tmp_path)
        for s in (1, 2, 3):
            self._land(root, s, {0: b"v%d" % s})
        self._land(root, 2, {0: b"zz"}, finalize=False)  # overwrite ok
        # incomplete debris older than the newest complete bundle
        bundle.write_shard(root, 0, 0, b"crash-leftover")
        removed = bundle.prune_bundles(root, keep=2)
        assert removed == [0, 1]
        assert bundle.complete_steps(root) == [2, 3]


# ------------------------------------------------------------------- delta
class TestJournalDelta:
    def test_roundtrip_bit_exact(self):
        prev = os.urandom(200_000)
        cur = bytearray(prev)
        cur[70_000:70_100] = os.urandom(100)  # inside block 1
        cur = bytes(cur)
        blocks = buddy_mod.shard_delta(prev, cur)
        assert len(blocks) == 1 and blocks[0][0] == buddy_mod.DELTA_BLOCK
        assert buddy_mod.apply_delta(prev, len(cur), blocks) == cur

    def test_no_change_is_empty(self):
        data = os.urandom(1000)
        assert buddy_mod.shard_delta(data, data) == []
        assert buddy_mod.apply_delta(data, len(data), []) == data

    def test_length_change_degenerates_to_full_shard(self):
        prev, cur = b"a" * 100, b"b" * 150
        blocks = buddy_mod.shard_delta(prev, cur)
        assert blocks == [(0, cur)]
        assert buddy_mod.apply_delta(prev, len(cur), blocks) == cur

    def test_first_push_has_no_prev(self):
        cur = os.urandom(10)
        assert buddy_mod.shard_delta(None, cur) == [(0, cur)]
        assert buddy_mod.apply_delta(None, len(cur), [(0, cur)]) == cur


# ----------------------------------------------------------- buddy streams
class TestBuddyStream:
    def test_push_fetch_roundtrip(self):
        secret = "s3cret"
        srv = buddy_mod.BuddyServer(secret, rank=0, host="127.0.0.1")
        held = []
        srv.on_hold = held.append
        try:
            cli = buddy_mod.BuddyClient(("127.0.0.1", srv.port), secret,
                                        index=1, rank=1)
            v1 = os.urandom(150_000)
            cli.push(5, v1)
            v2 = bytearray(v1)
            v2[80_000:80_031] = os.urandom(31)
            v2 = bytes(v2)
            n = cli.push(6, v2)
            # second push rode a delta, not a second full snapshot
            assert n < len(v2)
            deadline = time.time() + 5
            while srv.head(1) != 6 and time.time() < deadline:
                time.sleep(0.01)
            assert srv.get(1) == (6, v2)
            assert held == [1]  # on_hold fired once, on first bytes
            got = buddy_mod.fetch_shard(("127.0.0.1", srv.port), secret, 1,
                                        rank=9)
            assert got == (6, v2)
            # empty slot answers BYE -> None
            assert buddy_mod.fetch_shard(("127.0.0.1", srv.port), secret,
                                         3, rank=9) is None
            cli.close()
        finally:
            srv.stop()


# ------------------------------------------------------------ async writer
class TestAsyncShardWriter:
    def test_write_behind_and_on_written(self, tmp_path):
        done = []
        w = AsyncShardWriter(str(tmp_path),
                             on_written=lambda *a: done.append(a))
        data = os.urandom(50_000)
        stall = w.submit(7, 1, 2, data)
        assert w.drain(10)
        # the step path paid only the buffer hand-off
        assert stall < 0.05
        assert bundle.read_shard(str(tmp_path), 7, 2, verify=False) == data
        assert done == [(7, 1, 2, len(data),
                         zlib.crc32(data) & 0xFFFFFFFF)]
        w.stop()

    def test_double_buffer_keeps_freshest(self, tmp_path, monkeypatch):
        gate = threading.Event()
        real = bundle.write_shard

        def slow(root, step, index, data):
            gate.wait(5)
            return real(root, step, index, data)

        monkeypatch.setattr(bundle, "write_shard", slow)
        w = AsyncShardWriter(str(tmp_path))
        w.submit(1, 0, 0, b"one")
        time.sleep(0.1)          # writer thread is now blocked inside slow
        w.submit(2, 0, 0, b"two")
        w.submit(3, 0, 0, b"three")  # replaces pending step 2
        gate.set()
        assert w.drain(10)
        assert w.dropped == 1
        assert not os.path.exists(bundle.shard_path(str(tmp_path), 2, 0))
        assert bundle.read_shard(str(tmp_path), 3, 0,
                                 verify=False) == b"three"
        w.stop()

    def test_replica_rides_slot_zero_submit(self, tmp_path):
        w = AsyncShardWriter(str(tmp_path))
        w.submit(4, 0, 0, b"shard", replica=b"replicated-slots")
        assert w.drain(10)
        assert bundle.read_replica(str(tmp_path), 4,
                                   verify=False) == b"replicated-slots"
        w.stop()


# ---------------------------------------------------- coordinator stamps
def _estate(world=2):
    return CoordState(world, 64 << 20, cache_capacity=1024,
                      stall_warning_s=60.0, stall_shutdown_s=0.0,
                      elastic=True)


class TestCoordinatorStamps:
    def test_finalize_only_when_every_member_landed(self):
        st = _estate()
        fired = []
        st.on_ckpt_finalize = lambda *a: fired.append(a)
        st.ckpt_mark(0, 10, 0)
        st.ckpt_mark(1, 10, 0)
        st.ckpt_done(0, 10, 0, 0, 100, 1)
        assert fired == []  # rank 1's shard has not landed
        st.ckpt_done(1, 10, 0, 1, 200, 2)
        assert fired == [(10, 0, {0: {"nbytes": 100, "crc": 1},
                                  1: {"nbytes": 200, "crc": 2}})]
        assert st.ckpt_last_final == 10

    def test_stale_epoch_and_stranger_dropped(self):
        st = _estate()
        st.ckpt_done(0, 5, 3, 0, 1, 1)   # epoch 3 != 0
        st.ckpt_done(7, 5, 0, 0, 1, 1)   # rank 7 not a member
        assert st.ckpt_pending == {}

    def test_membership_reset_clears_pending(self):
        st = _estate()
        st.ckpt_mark(0, 5, 0)
        st.ckpt_done(0, 5, 0, 0, 1, 1)
        st.rank_lost(1, "gone")          # epoch 0 -> 1
        assert st.ckpt_pending == {}
        # a straggling DONE stamped under the dead epoch stays dropped:
        # the old member set can never complete that bundle
        st.ckpt_done(0, 5, 0, 0, 1, 1)
        assert st.ckpt_pending == {}

    def test_last_final_is_monotonic(self):
        st = _estate(world=1)
        st.on_ckpt_finalize = lambda *a: None
        st.ckpt_done(0, 10, 0, 0, 1, 1)
        assert st.ckpt_last_final == 10
        st.ckpt_done(0, 8, 0, 0, 1, 1)   # late, older snapshot
        assert st.ckpt_last_final == 10


# ----------------------------------------------------------------- manager
class TestCkptManager:
    def test_single_process_bundle_lifecycle(self, tmp_path):
        root = str(tmp_path)
        mgr = manager.CkptManager(root, rank=0, world=1, buddy=False,
                                  interval=1)
        try:
            state = ElasticState(w=np.arange(4, dtype=np.float32), step=3)
            assert mgr.on_state_commit(state, 3)
            assert mgr.drain(10)
            deadline = time.time() + 5
            while (bundle.latest_complete_step(root) != 3
                   and time.time() < deadline):
                time.sleep(0.01)
            step, tree = manager.load_latest(root)
            assert step == 3
            np.testing.assert_array_equal(
                tree["slots"]["w"], np.arange(4, dtype=np.float32))
            assert tree["slots"]["step"] == 3
        finally:
            mgr.stop()

    def test_interval_gates_plain_dp_snapshots(self, tmp_path):
        mgr = manager.CkptManager(str(tmp_path), rank=0, world=1,
                                  buddy=False, interval=5)
        try:
            state = ElasticState(w=np.zeros(2), step=0)
            assert mgr.on_state_commit(state, 1)       # first is always due
            assert not mgr.on_state_commit(state, 3)   # inside interval
            assert mgr.on_state_commit(state, 6)
        finally:
            mgr.stop()

    def test_sharded_mode_splits_slots_and_replica(self, tmp_path):
        root = str(tmp_path)
        mgr = manager.CkptManager(root, rank=0, world=1, buddy=False,
                                  interval=1)
        try:
            state = ElasticState(w=np.ones(3, np.float32),
                                 opt_shard=np.full(2, 7.0, np.float32),
                                 step=1)
            state.mark_sharded("opt_shard")
            state.commit()  # refresh _committed with the marks in place
            assert mgr.on_state_commit(state, 1)
            assert mgr.drain(10)
            deadline = time.time() + 5
            while (bundle.latest_complete_step(root) != 1
                   and time.time() < deadline):
                time.sleep(0.01)
            shard = manager.unpack_tree(bundle.read_shard(root, 1, 0))
            assert sorted(shard["slots"]) == ["opt_shard"]
            rep = manager.unpack_tree(bundle.read_replica(root, 1))
            assert sorted(rep["slots"]) == ["step", "w"]
            step, tree = manager.load_latest(root)
            assert step == 1
            assert sorted(tree["slots"]) == ["opt_shard", "step", "w"]
        finally:
            mgr.stop()

    def test_restore_prefers_peer_journal(self, tmp_path, monkeypatch):
        secret = "s"
        srv = buddy_mod.BuddyServer(secret, rank=0, host="127.0.0.1")
        payload = manager.pack_tree(
            {"slots": {"opt_shard": np.full(2, 3.5, np.float32)},
             "ef": {}})
        srv.put(0, 8, payload)
        mgr = manager.CkptManager(str(tmp_path), rank=0, world=1,
                                  buddy=False, interval=1, secret=secret)
        try:
            monkeypatch.setattr(
                mgr, "_resolve", lambda key, timeout: ("127.0.0.1",
                                                       srv.port))
            state = ElasticState(w=np.zeros(1),
                                 opt_shard=np.zeros(2, np.float32))
            state.mark_sharded("opt_shard")
            assert mgr.restore_sharded_slots(state)
            np.testing.assert_array_equal(
                state.opt_shard, np.full(2, 3.5, np.float32))
            assert mgr.last_restore["source"] == "peer"
            assert mgr.last_restore["step"] == 8
        finally:
            mgr.stop()
            srv.stop()

    def test_restore_falls_back_to_disk_bundle(self, tmp_path, monkeypatch):
        root = str(tmp_path)
        mgr = manager.CkptManager(root, rank=0, world=1, buddy=False,
                                  interval=1)
        try:
            shard = manager.pack_tree(
                {"slots": {"opt_shard": np.arange(2, dtype=np.float32)},
                 "ef": {}})
            n, c = bundle.write_shard(root, 4, 0, shard)
            rep = manager.pack_tree({"slots": {"w": np.full(1, 9.0)}})
            rn, rc = bundle.write_replica(root, 4, rep)
            bundle.finalize_manifest(root, 4, 0,
                                     {0: {"nbytes": n, "crc": c}},
                                     replica={"nbytes": rn, "crc": rc})
            monkeypatch.setattr(mgr, "_resolve",
                                lambda key, timeout: None)  # no peer
            state = ElasticState(w=np.zeros(1),
                                 opt_shard=np.zeros(2, np.float32))
            state.mark_sharded("opt_shard")
            assert mgr.restore_sharded_slots(state)
            np.testing.assert_array_equal(
                state.opt_shard, np.arange(2, dtype=np.float32))
            # whole-job restart also installs the replicated slots
            np.testing.assert_array_equal(state.w, np.full(1, 9.0))
            assert mgr.last_restore["source"] == "bundle"
        finally:
            mgr.stop()

    def test_restore_skips_mismatched_world(self, tmp_path, monkeypatch):
        root = str(tmp_path)
        mgr = manager.CkptManager(root, rank=0, world=1, buddy=False,
                                  interval=1)
        try:
            n, c = bundle.write_shard(root, 2, 0, b"x")
            n1, c1 = bundle.write_shard(root, 2, 1, b"y")
            bundle.finalize_manifest(root, 2, 0,
                                     {0: {"nbytes": n, "crc": c},
                                      1: {"nbytes": n1, "crc": c1}})
            monkeypatch.setattr(mgr, "_resolve",
                                lambda key, timeout: None)
            state = ElasticState(opt_shard=np.zeros(1))
            state.mark_sharded("opt_shard")
            # bundle was cut for world=2; a 1-member job must not
            # mis-slice it
            assert not mgr.restore_sharded_slots(state)
        finally:
            mgr.stop()

    def test_knob_off_means_no_manager(self):
        state = ElasticState(w=np.zeros(1), step=0)
        state.commit()
        assert manager.active() is None
        assert manager.ensure_manager() is None

    def test_commit_drives_manager_via_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HOROVOD_CKPT_DIR", str(tmp_path))
        monkeypatch.setenv("HOROVOD_CKPT_INTERVAL", "1")
        monkeypatch.setenv("HOROVOD_CKPT_BUDDY", "0")
        state = ElasticState(w=np.full(2, 2.0, np.float32), step=0)
        state.step = 5
        state.commit()
        mgr = manager.active()
        assert mgr is not None and manager.ensure_manager() is mgr
        assert mgr.drain(10)
        deadline = time.time() + 5
        while (bundle.latest_complete_step(str(tmp_path)) != 5
               and time.time() < deadline):
            time.sleep(0.01)
        step, tree = manager.load_latest(str(tmp_path))
        assert step == 5 and tree["slots"]["step"] == 5


# -------------------------------------------------- legacy save delegation
class TestSaveDelegation:
    def test_save_is_atomic_via_bundle_writer(self, tmp_path):
        import horovod_tpu.checkpoint as hvd_ckpt

        path = str(tmp_path / "model.ckpt")
        state = {"w": np.arange(3, dtype=np.float32)}
        assert hvd_ckpt.save(path, state)
        assert not [n for n in os.listdir(str(tmp_path))
                    if n.startswith(".ckpt_tmp_")]
        out = hvd_ckpt.restore(path, {"w": np.zeros(3, np.float32)})
        np.testing.assert_array_equal(out["w"], state["w"])

    def test_overwrite_guard_names_the_path(self, tmp_path):
        import horovod_tpu.checkpoint as hvd_ckpt

        path = str(tmp_path / "model.ckpt")
        hvd_ckpt.save(path, {"w": np.zeros(1)})
        with pytest.raises(FileExistsError) as ei:
            hvd_ckpt.save(path, {"w": np.ones(1)}, overwrite=False)
        assert path in str(ei.value)


# ------------------------------------------------------------- diagnostics
def _ev(kind, name="", detail="", rank=0, t=0.0):
    return {"t": t, "rank": rank, "kind": kind, "name": name,
            "detail": detail}


def _bundle_of(events_by_rank):
    return {r: {"blackbox": 1, "rank": r, "world_size": len(events_by_rank),
                "reason": "test", "events": evs, "metrics": {},
                "open_spans": []}
            for r, evs in events_by_rank.items()}


class TestStaleCheckpointSignature:
    def test_lagging_writer_named(self):
        b = _bundle_of({
            0: [_ev(blackbox.K_CKPT, "snapshot", "step=%d index=0" % s,
                    rank=0) for s in (10, 20, 30)]
               + [_ev(blackbox.K_CKPT, "finalize", "step=10 epoch=0")],
            1: [_ev(blackbox.K_CKPT, "snapshot", "step=10 index=1",
                    rank=1)],
        })
        out = sigs.detect_stale_checkpoint(b)
        assert len(out) == 1
        assert out[0]["id"] == "stale_checkpoint"
        assert out[0]["evidence"]["rank"] == 1
        assert out[0]["evidence"]["last_finalized"] == 10
        assert "rank 1" in out[0]["summary"]

    def test_healthy_bundles_stay_silent(self):
        b = _bundle_of({
            0: [_ev(blackbox.K_CKPT, "snapshot", "step=30 index=0"),
                _ev(blackbox.K_CKPT, "finalize", "step=30 epoch=0")],
            1: [_ev(blackbox.K_CKPT, "snapshot", "step=30 index=1",
                    rank=1)],
        })
        assert sigs.detect_stale_checkpoint(b) == []

    def test_stale_restore_reported(self):
        b = _bundle_of({
            2: [_ev(blackbox.K_CKPT, "restore",
                    "source=bundle step=10 journal_head=14 index=2 "
                    "nbytes=100", rank=2)],
        })
        out = sigs.detect_stale_checkpoint(b)
        assert len(out) == 1
        assert out[0]["evidence"]["restored_step"] == 10
        assert out[0]["evidence"]["journal_head"] == 14

    def test_registered_with_doctor(self):
        assert sigs.detect_stale_checkpoint in sigs.DETECTORS


def _age_snapshot(age):
    return {"hvd_ckpt_bundle_age_steps": {
        "kind": "gauge", "help": "", "buckets": [],
        "series": [{"labels": {}, "value": age}]}}


class TestCkptAgeWatch:
    def test_threshold_fires_once_and_clears(self):
        w = AnomalyWatch(interval=1.0, window=8, factor=3.0, min_samples=2)
        # default interval 10 -> threshold 20; age grows PAST it: a
        # baseline would learn the growth as normal, the threshold doesn't
        assert w.observe_snapshot(_age_snapshot(5)) == []
        fired = w.observe_snapshot(_age_snapshot(25))
        assert [s["id"] for s in fired] == ["anomaly:ckpt_bundle_age_steps"]
        assert fired[0]["evidence"]["related"] == "stale_checkpoint"
        assert w.observe_snapshot(_age_snapshot(30)) == []  # one episode
        w.observe_snapshot(_age_snapshot(0))                # finalized
        fired = w.observe_snapshot(_age_snapshot(25))       # new episode
        assert len(fired) == 1

    def test_threshold_scales_with_interval(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_CKPT_INTERVAL", "100")
        w = AnomalyWatch(interval=1.0, window=8, factor=3.0, min_samples=2)
        assert w.observe_snapshot(_age_snapshot(150)) == []
        assert len(w.observe_snapshot(_age_snapshot(201))) == 1

    def test_absent_gauge_is_ignored(self):
        w = AnomalyWatch(interval=1.0, window=8, factor=3.0, min_samples=2)
        assert w._check_ckpt_age({}) == []


# ----------------------------------------------------------- integration
def _ckpt_train_fn():
    """2 ranks, 12 steps, one replicated slot (w) and one rank-local
    sharded slot. The HVD_CKPT_VICTIM process hard-kills itself at step 5;
    its replacement (same rank id, flag unset) must restore the shard from
    the buddy journal and the job must finish the exact trajectory an
    uninterrupted run produces. Gradients are rank-independent so the
    reference trajectory is computable in-process by the test."""
    import os
    import time

    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import ckpt

    hvd.init()
    state = hvd.elastic.ElasticState(
        w=np.array([4.0], np.float32),
        opt_shard=np.array([hvd.rank() + 1.0], np.float32),
        step=0)
    state.mark_sharded("opt_shard")
    log = []
    target = np.float32(1.0)

    @hvd.elastic.run_fn
    def train(state):
        ctrl = hvd.basics._engine().controller
        while state.step < 12:
            if (os.environ.get("HVD_CKPT_VICTIM") == "1"
                    and state.step == 5):
                os._exit(17)  # hard kill AFTER committing step 5
            if hvd.rank() == 0 and len(ctrl.members()) < 2:
                # hold the trajectory at the commit boundary until the
                # replacement is admitted: every training step must run
                # with both members or the replacement's shard misses
                # updates and bit-identity is unfalsifiable
                time.sleep(0.1)
                state.commit()
                continue
            g = np.float32(2.0) * (np.asarray(state.w, np.float32)
                                   - target)
            avg = hvd.allreduce(g, name=f"grad{state.step}",
                                op=hvd.Average)
            state.w = (np.asarray(state.w, np.float32)
                       - np.float32(0.1) * np.asarray(avg, np.float32))
            state.opt_shard = (np.float32(0.5)
                               * np.asarray(state.opt_shard, np.float32)
                               + np.asarray(avg, np.float32))
            log.append((state.step, ctrl.epoch(), list(ctrl.members())))
            state.step += 1
            state.commit()
        return log

    out = train(state)
    mgr = ckpt.active()
    restore = mgr.last_restore if mgr is not None else None
    return {"log": out, "w": np.asarray(state.w),
            "shard": np.asarray(state.opt_shard), "restore": restore,
            "rank": hvd.rank()}


def _reference_trajectory(steps=12):
    """The uninterrupted-run trajectory, op-for-op identical to the train
    fn's float32 arithmetic (avg == g exactly: (g+g)/2 is exact in IEEE,
    and the solo case is g itself)."""
    w = np.array([4.0], np.float32)
    shard = np.array([2.0], np.float32)  # rank 1's slot: rank + 1.0
    target = np.float32(1.0)
    for _ in range(steps):
        g = np.float32(2.0) * (np.asarray(w, np.float32) - target)
        w = (np.asarray(w, np.float32)
             - np.float32(0.1) * np.asarray(g, np.float32))
        shard = (np.float32(0.5) * np.asarray(shard, np.float32)
                 + np.asarray(g, np.float32))
    return w, shard


@pytest.mark.integration
def test_kill_and_replace_resumes_bit_identical(tmp_path):
    """The tentpole acceptance scenario: SIGKILL-equivalent loss of rank 1
    mid-training, then a same-rank replacement. The replacement restores
    its sharded slot from the buddy journal (O(shard), source == "peer" at
    the victim's last commit) and the finished job's state is bitwise
    equal to an uninterrupted run."""
    import cloudpickle

    from horovod_tpu.run import rendezvous

    here = os.path.dirname(os.path.abspath(__file__))
    secret = rendezvous.make_secret()
    kv = rendezvous.KVStoreServer(secret).start()
    addr = f"127.0.0.1:{kv.port}"
    client = rendezvous.KVStoreClient(addr, secret)
    client.put("runfunc", "fn",
               cloudpickle.dumps((_ckpt_train_fn, (), {})))

    def spawn(rank, victim):
        env = dict(os.environ)
        env.update({
            "HVD_NUM_PROCS": "2",
            "HVD_PROCESS_ID": str(rank),
            "HVD_KV_ADDR": addr,
            "HVD_SECRET": secret,
            "HVD_ELASTIC": "1",
            "HOROVOD_RECONNECT_GRACE": "2",
            "HOROVOD_CKPT_DIR": str(tmp_path),
            "HOROVOD_CKPT_INTERVAL": "1",
            "HVD_CKPT_VICTIM": "1" if victim else "0",
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": os.pathsep.join([os.path.dirname(here), here]),
        })
        env.pop("XLA_FLAGS", None)
        return subprocess.Popen(
            [sys.executable, "-m", "horovod_tpu.run.task"], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    procs = [spawn(0, False), spawn(1, True)]
    replacement = None
    try:
        # wait for the victim to die with its marker code
        deadline = time.time() + 120
        while procs[1].poll() is None and time.time() < deadline:
            time.sleep(0.25)
        assert procs[1].poll() == 17, "victim did not hard-exit"
        # let the reconnect grace expire so the coordinator declares
        # rank_lost — the replacement must be admitted as a JOINER under a
        # bumped epoch, not mistaken for the dead stream reconnecting
        time.sleep(3.0)
        replacement = spawn(1, False)

        blob0 = blob1 = None
        deadline = time.time() + 150
        while time.time() < deadline:
            blob0 = blob0 or client.get("result", "0")
            blob1 = blob1 or client.get("result", "1")
            if blob0 is not None and blob1 is not None:
                break
            if procs[0].poll() not in (None, 0):
                break
            time.sleep(0.25)
        assert blob0 is not None, "rank 0 produced no result"
        assert blob1 is not None, "replacement produced no result"
        ok0, res0 = pickle.loads(blob0)
        ok1, res1 = pickle.loads(blob1)
        assert ok0, f"rank 0 raised:\n{res0}"
        assert ok1, f"replacement raised:\n{res1}"
    finally:
        for p in procs + ([replacement] if replacement else []):
            if p.poll() is None:
                p.kill()
        kv.stop()

    # every step ran exactly once on rank 0, none were lost to the reset
    steps0 = [row[0] for row in res0["log"]]
    assert steps0 == list(range(12)), steps0
    # the replacement restored from the PEER journal at the victim's last
    # commit (step 5: the victim dies at the top of its step-5 iteration,
    # after the commit stamped 5 synchronously journaled its shard)
    assert res1["restore"] is not None, "replacement never restored"
    assert res1["restore"]["source"] == "peer", res1["restore"]
    assert res1["restore"]["step"] == 5, res1["restore"]
    # bit-identical trajectory vs an uninterrupted run
    ref_w, ref_shard = _reference_trajectory()
    assert res0["w"].tobytes() == ref_w.tobytes()
    assert res1["w"].tobytes() == ref_w.tobytes()
    assert res1["shard"].tobytes() == ref_shard.tobytes()
    # membership went 2 -> 1 -> 2 across the replacement
    epochs = sorted({row[1] for row in res0["log"]})
    assert epochs[0] == 0 and len(epochs) >= 2, epochs
    assert res0["log"][-1][2] == [0, 1]
