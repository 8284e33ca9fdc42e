"""Cross-process control plane tests.

Unit layer: CoordState negotiation logic (validation/fusion/join/cache) and
the TCP exchange, driven in-process. Integration layer: the four VERDICT
scenarios as real 2-process jobs through ``run()`` — coordinated ERROR on
mismatched shapes, ragged allgather, join with uneven data, and fused
multi-tensor allreduce with response-cache hits.

Parity model: `test/test_tensorflow.py:314-383` (coordinator error
responses), `test/test_torch.py` join tests, `.buildkite/gen-pipeline.sh`
multi-process runs.
"""

import os
import pickle
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from horovod_tpu.runtime import wire
from horovod_tpu.runtime.coordinator import (
    CoordController, CoordState, CoordinatorServer)
from horovod_tpu.runtime.messages import RequestType, ResponseType

ALLREDUCE = int(RequestType.ALLREDUCE)
ALLGATHER = int(RequestType.ALLGATHER)


def meta(name, shape=(4,), rtype=ALLREDUCE, dtype="float32", **kw):
    return wire.ReqMeta(name, rtype, dtype, shape, **kw)


def negotiate(state, per_rank):
    """per_rank: {rank: (flags, cached_ids, [ReqMeta])} -> decoded response
    (first 5 fields; the shutdown reason is exercised via the protocol
    tests)."""
    out = state._negotiate(per_rank)
    return wire.decode_response_list(out)[:5]


def make_state(world=2, threshold=64 << 20, **kw):
    kwargs = dict(cache_capacity=1024, stall_warning_s=60.0,
                  stall_shutdown_s=0.0)
    kwargs.update(kw)
    return CoordState(world, threshold, **kwargs)


class TestNegotiation:
    def test_ready_requires_all_ranks(self):
        st = make_state()
        flags, lj, resps, _, _ = negotiate(st, {0: (0, [], [meta("a")]),
                                                1: (0, [], [])})
        assert resps == []
        flags, lj, resps, _, _ = negotiate(st, {0: (0, [], []),
                                                1: (0, [], [meta("a")])})
        assert len(resps) == 1
        assert resps[0].response_type == ResponseType.ALLREDUCE
        assert resps[0].tensor_names == ["a"]
        assert resps[0].tensor_shapes == [(4,)]
        assert resps[0].tensor_dtype == "float32"

    def test_fusion_same_signature(self):
        st = make_state()
        reqs = [meta(n) for n in ("a", "b", "c")]
        _, _, resps, _, _ = negotiate(st, {0: (0, [], reqs),
                                           1: (0, [], reqs)})
        assert len(resps) == 1
        assert resps[0].tensor_names == ["a", "b", "c"]

    def test_fusion_respects_threshold(self):
        st = make_state(threshold=20)  # 16-byte tensors: no pair fits
        reqs = [meta(n) for n in ("a", "b", "c")]
        _, _, resps, _, _ = negotiate(st, {0: (0, [], reqs),
                                           1: (0, [], reqs)})
        assert [r.tensor_names for r in resps] == [["a"], ["b"], ["c"]]

    def test_fusion_not_across_signatures(self):
        st = make_state()
        r0 = [meta("a"), meta("b", dtype="float64")]
        _, _, resps, _, _ = negotiate(st, {0: (0, [], r0), 1: (0, [], r0)})
        assert sorted(tuple(r.tensor_names) for r in resps) == [("a",), ("b",)]

    def test_shape_mismatch_error_names_both_ranks(self):
        st = make_state()
        _, _, resps, _, _ = negotiate(
            st, {0: (0, [], [meta("x", (2,))]),
                 1: (0, [], [meta("x", (3,))])})
        assert len(resps) == 1
        assert resps[0].response_type == ResponseType.ERROR
        msg = resps[0].error_message
        assert "Mismatched tensor shapes" in msg
        assert "(2,)" in msg and "(3,)" in msg and "'x'" in msg

    def test_dtype_and_op_mismatch(self):
        st = make_state()
        _, _, resps, _, _ = negotiate(
            st, {0: (0, [], [meta("d", dtype="float32")]),
                 1: (0, [], [meta("d", dtype="int32")])})
        assert "Mismatched data types" in resps[0].error_message
        _, _, resps, _, _ = negotiate(
            st, {0: (0, [], [meta("o")]),
                 1: (0, [], [meta("o", rtype=ALLGATHER)])})
        assert "Mismatched collective operations" in resps[0].error_message

    def test_compression_mismatch(self):
        st = make_state()
        _, _, resps, _, _ = negotiate(
            st, {0: (0, [], [meta("q", compression="int8")]),
                 1: (0, [], [meta("q")])})
        assert resps[0].response_type == ResponseType.ERROR
        msg = resps[0].error_message
        assert "compression" in msg and "'int8'" in msg and "'none'" in msg
        assert "HOROVOD_COMPRESSION" in msg

    def test_compression_carried_and_not_fused_across_modes(self):
        st = make_state()
        r0 = [meta("a", compression="int8"), meta("b", compression="int8"),
              meta("c")]
        _, _, resps, _, _ = negotiate(st, {0: (0, [], r0), 1: (0, [], r0)})
        # same mode fuses and the response carries it; plain rides apart
        by_names = {tuple(r.tensor_names): r for r in resps}
        assert by_names[("a", "b")].compression == "int8"
        assert by_names[("c",)].compression == ""

    def test_ragged_allgather_sizes(self):
        st = make_state()
        _, _, resps, _, _ = negotiate(
            st, {0: (0, [], [meta("g", (1, 3), rtype=ALLGATHER)]),
                 1: (0, [], [meta("g", (5, 3), rtype=ALLGATHER)])})
        assert resps[0].response_type == ResponseType.ALLGATHER
        assert resps[0].tensor_sizes == [[1, 5]]
        # tail mismatch is an error
        _, _, resps, _, _ = negotiate(
            st, {0: (0, [], [meta("h", (1, 3), rtype=ALLGATHER)]),
                 1: (0, [], [meta("h", (1, 4), rtype=ALLGATHER)])})
        assert "beyond first dimension" in resps[0].error_message

    def test_join_then_release(self):
        st = make_state()
        # rank 0 joins; rank 1 still reduces -> tensor ready without rank 0
        _, _, resps, _, _ = negotiate(
            st, {0: (wire.REQ_JOIN, [], []), 1: (0, [], [meta("t")])})
        assert len(resps) == 1
        assert resps[0].tensor_names == ["t"]
        # rank 1 joins too -> barrier release, last_joined = 1
        flags, lj, resps, _, _ = negotiate(
            st, {0: (0, [], []), 1: (wire.REQ_JOIN, [], [])})
        assert flags & wire.RESP_JOIN_RELEASE
        assert lj == 1
        assert resps == []

    def test_allgather_rejected_while_joined(self):
        st = make_state()
        negotiate(st, {0: (wire.REQ_JOIN, [], []), 1: (0, [], [])})
        _, _, resps, _, _ = negotiate(
            st, {0: (0, [], []),
                 1: (0, [], [meta("g", (2, 2), rtype=ALLGATHER)])})
        assert "not supported while a rank has joined" in \
            resps[0].error_message

    def test_cache_assignment_and_hit(self):
        st = make_state()
        _, _, resps, cids, _ = negotiate(st, {0: (0, [], [meta("c")]),
                                              1: (0, [], [meta("c")])})
        assert cids == [[0]]
        assert st.cache_stats() == (0, 2)
        # steady state: both ranks submit the 4-byte id instead of metadata
        _, _, resps, cids2, _ = negotiate(st, {0: (0, [0], []),
                                               1: (0, [0], [])})
        assert resps[0].tensor_names == ["c"]
        assert cids2 == [[0]]
        assert st.cache_stats() == (2, 2)

    def test_stall_warning_lists_missing_ranks(self):
        st = make_state(stall_warning_s=0.0)
        _, _, _, _, warns = negotiate(st, {0: (0, [], [meta("s")]),
                                           1: (0, [], [])})
        assert len(warns) == 1
        assert "s" in warns[0] and "[1]" in warns[0]


class TestExchangeProtocol:
    """Socket-level: two controllers (rank 0 hosts the server) in-process."""

    def _controllers(self, monkeypatch, tmp_path):
        from horovod_tpu.run import rendezvous

        secret = rendezvous.make_secret()
        kv = rendezvous.KVStoreServer(secret).start()
        monkeypatch.setenv("HVD_KV_ADDR", f"127.0.0.1:{kv.port}")
        monkeypatch.setenv("HVD_SECRET", secret)
        common = dict(world=2, fusion_threshold=64 << 20, stall_warning_s=60.0,
                      stall_shutdown_s=0.0, cache_capacity=64,
                      fusion_enabled=True, timeline_path=None, autotune=False,
                      cycle_time_ms=5.0)
        c0 = CoordController(self_rank=0, **common)
        c1 = CoordController(self_rank=1, **common)
        return c0, c1, kv

    def _entry(self, name, value, rank):
        from horovod_tpu.runtime.messages import TensorTableEntry

        return TensorTableEntry(
            tensor_name=name, rank=rank, request_type=RequestType.ALLREDUCE,
            array=np.full((4,), value, np.float32))

    def test_two_rank_exchange_and_cache(self, monkeypatch, tmp_path):
        c0, c1, kv = self._controllers(monkeypatch, tmp_path)
        try:
            for round_i in range(2):
                h0 = c0.submit(self._entry(f"t{round_i}", 1.0, 0))
                h1 = c1.submit(self._entry(f"t{round_i}", 2.0, 1))
                assert h0 >= 0 and h1 >= 0
                out = {}

                def tick0():
                    out[0] = c0.tick()

                t = threading.Thread(target=tick0)
                t.start()
                out[1] = c1.tick()
                t.join(timeout=30)
                for r in (0, 1):
                    responses, pairs, _, _, _, _ = out[r]
                    assert len(responses) == 1
                    assert responses[0].tensor_names == [f"t{round_i}"]
                    assert pairs[0] == [(r, h0 if r == 0 else h1)]
            # duplicate detection is local
            c0.submit(self._entry("dup", 0.0, 0))
            assert c0.submit(self._entry("dup", 0.0, 0)) == \
                CoordController.SUBMIT_DUPLICATE
        finally:
            c1.shutdown()
            c0.shutdown()
            kv.stop()

    def test_bye_broadcasts_shutdown(self, monkeypatch, tmp_path):
        from horovod_tpu.exceptions import ShutdownError

        c0, c1, kv = self._controllers(monkeypatch, tmp_path)
        try:
            c1.interrupt()  # rank 1 leaves
            with pytest.raises(ShutdownError):
                for _ in range(50):
                    c0.tick()
        finally:
            c1.shutdown()
            c0.shutdown()
            kv.stop()

    def test_join_release_last_joined_consistent(self, monkeypatch,
                                                 tmp_path):
        """Every surviving rank must observe the SAME last-joined rank when
        the join barrier releases (join() return-value contract,
        `controller.cc` join negotiation)."""
        c0, c1, kv = self._controllers(monkeypatch, tmp_path)
        try:
            h0 = c0.join(0)
            h1 = c1.join(1)
            assert h0 >= 0 and h1 >= 0
            out = {}

            def tick0():
                out[0] = c0.tick()

            t = threading.Thread(target=tick0)
            t.start()
            out[1] = c1.tick()
            t.join(timeout=30)
            for r in (0, 1):
                _, _, join_released, last_joined, _, _ = out[r]
                assert join_released == [h0 if r == 0 else h1]
            # identical on both ranks — whichever frame the coordinator
            # consumed second is THE last joiner, everywhere
            assert out[0][3] == out[1][3]
            assert out[0][3] in (0, 1)
        finally:
            c1.shutdown()
            c0.shutdown()
            kv.stop()


class TestPyControllerJoin:
    """join() last-joined agreement on the in-process controller: every
    released join handle ships the same last-joined rank."""

    def _ctrl(self, world=2):
        from horovod_tpu.runtime.pycontroller import PyController

        return PyController(world=world, fusion_threshold=64 << 20,
                            stall_warning_s=60.0, stall_shutdown_s=0.0,
                            cache_capacity=64, fusion_enabled=True,
                            timeline_path=None, autotune=False,
                            cycle_time_ms=5.0)

    def test_all_ranks_released_with_same_last_joined(self):
        ctrl = self._ctrl()
        h0 = ctrl.join(0)
        h1 = ctrl.join(1)
        responses, pairs, join_released, last_joined, _, _ = ctrl.tick()
        assert responses == [] and pairs == []
        assert sorted(join_released) == sorted([h0, h1])
        assert last_joined == 1  # rank 1 joined last; one value for all

    def test_join_order_determines_last_joined(self):
        ctrl = self._ctrl()
        ctrl.join(1)
        ctrl.join(0)
        _, _, released, last_joined, _, _ = ctrl.tick()
        assert len(released) == 2
        assert last_joined == 0


# ----------------------------------------------------------- integration (2p)
def _worker_shape_mismatch():
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.exceptions import HorovodInternalError

    shape = (2,) if hvd.rank() == 0 else (3,)
    try:
        hvd.allreduce(np.ones(shape, np.float32), name="x", op=hvd.Sum)
        return (hvd.rank(), None)
    except HorovodInternalError as e:
        return (hvd.rank(), str(e))


def _worker_ragged_allgather():
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import basics

    r = hvd.rank()
    for _ in range(2):  # second round must hit the per-rank-sig cache
        out = np.asarray(hvd.allgather(
            np.full((r + 1, 3), float(r), np.float32), name="ag"))
    hits, _ = basics._engine().controller.cache_stats()
    return (r, out.shape, float(out.sum()), hits)


def _worker_join_uneven():
    import numpy as np

    import horovod_tpu as hvd

    r = hvd.rank()
    outs = []
    steps = 3 if r == 0 else 1
    for i in range(steps):
        out = hvd.allreduce(np.full((2,), float(r + 1), np.float32),
                            name=f"j{i}", op=hvd.Sum)
        outs.append(float(np.asarray(out)[0]))
    last = hvd.join()
    return (r, outs, last)


def _worker_fused_cached():
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import basics
    from horovod_tpu.ops import collective_ops as C

    r = hvd.rank()
    rounds = []
    for _ in range(2):
        hs = [C.allreduce_async(np.full((8,), float(i + r), np.float32),
                                name=f"f{i}", op=hvd.Sum) for i in range(4)]
        rounds.append([float(np.asarray(C.synchronize(h))[0]) for h in hs])
    hits, misses = basics._engine().controller.cache_stats()
    return (r, rounds, hits)


def _run2(fn):
    from horovod_tpu.run.api import run

    here = os.path.dirname(os.path.abspath(__file__))
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": os.pathsep.join([os.path.dirname(here), here]),
    }
    return run(fn, np=2, env=env, start_timeout=120)


@pytest.mark.integration
def test_mp_coordinated_shape_error():
    res = _run2(_worker_shape_mismatch)
    msgs = {r: m for r, m in res}
    assert msgs[0] is not None and msgs[0] == msgs[1]
    assert "Mismatched tensor shapes" in msgs[0]
    assert "(2,)" in msgs[0] and "(3,)" in msgs[0]


@pytest.mark.integration
def test_mp_ragged_allgather():
    res = _run2(_worker_ragged_allgather)
    for r, shape, total, hits in res:
        assert tuple(shape) == (3, 3)
        assert total == 6.0  # one row of 0s + two rows of 1s
        assert hits > 0, "ragged allgather must cache per-rank signatures"


@pytest.mark.integration
def test_mp_join_uneven_data():
    res = _run2(_worker_join_uneven)
    by_rank = {r: (outs, last) for r, outs, last in res}
    # step 0: both contribute (1 + 2); steps 1-2: rank 1 joined -> zeros
    assert by_rank[0][0] == [3.0, 1.0, 1.0]
    assert by_rank[1][0] == [3.0]
    # rank 0 was the last to join; all ranks agree
    assert by_rank[0][1] == 0 and by_rank[1][1] == 0


@pytest.mark.integration
def test_mp_fused_allreduce_with_cache_hits():
    res = _run2(_worker_fused_cached)
    for r, rounds, hits in res:
        for outs in rounds:
            assert outs == [2 * i + 1.0 for i in range(4)]
        assert hits > 0, "steady-state should hit the response cache"


class TestCacheCapacity:
    def test_saturated_cache_stays_correct(self):
        """Reference test technique: loop more names than cache capacity
        (`test/test_tensorflow.py` cache stress). Saturation evicts the
        least recently negotiated name — every negotiation stays correct and
        every name keeps getting a (fresh, never-reused) cache id."""
        st = make_state(cache_capacity=2)
        seen_ids = []
        for round_ in range(2):
            for i in range(5):
                name = f"t{i}"
                _, _, resps, cids, _ = negotiate(
                    st, {0: (0, [], [meta(name)]),
                         1: (0, [], [meta(name)])})
                assert resps[0].tensor_names == [name]
                assert cids[0][0] >= 0, (name, cids)
                seen_ids.append(cids[0][0])
        # monotonic ids, never reused: an evicted id must not alias another
        # tensor's metadata on a worker that still holds it
        assert seen_ids == sorted(seen_ids)
        assert len(set(seen_ids)) == len(seen_ids) == 10
        assert len(st.cache_ids) == 2  # capacity respected throughout
        # the survivors (most recently negotiated) still serve the fast path
        live = st.cache_ids["t4"]
        _, _, resps, cids, _ = negotiate(st, {0: (0, [live], []),
                                              1: (0, [live], [])})
        assert resps[0].tensor_names == ["t4"]
        hits, misses = st.cache_stats()
        assert hits == 2 and misses == 20

    def test_churn_reports_invalid_ids_and_recovers(self):
        """VERDICT item 4: loop 2x capacity, then present an evicted id —
        the coordinator must answer with ``invalid_ids`` (so workers purge
        their sig caches) and the name must renegotiate under a fresh id."""
        st = make_state(cache_capacity=2)
        first_cid = None
        for round_ in range(2):
            for i in range(4):  # 2x capacity
                name = f"c{i}"
                _, _, resps, cids, _ = negotiate(
                    st, {0: (0, [], [meta(name)]),
                         1: (0, [], [meta(name)])})
                assert resps[0].tensor_names == [name]
                if first_cid is None:
                    first_cid = cids[0][0]
        assert first_cid not in st.cache_meta  # c0's id was churned out
        # a rank still holding the evicted id submits it: no negotiation for
        # it happens, and the response tells the rank to forget the id
        out = st._negotiate({0: (0, [first_cid], []), 1: (0, [], [])})
        decoded = wire.decode_response_list(out)
        resps, invalid = decoded[2], decoded[9]
        assert invalid == [first_cid]
        assert resps == []  # nothing ready: c0 has no metadata this round
        # the fast path recovers: full metadata resubmission gets a fresh id
        _, _, resps, cids, _ = negotiate(
            st, {0: (0, [], [meta("c0")]), 1: (0, [], [meta("c0")])})
        assert resps[0].tensor_names == ["c0"]
        assert cids[0][0] >= 0 and cids[0][0] != first_cid

    def test_stall_invalidation_drops_cache_entry(self):
        """A stall warning invalidates the stalled tensor's cache entry:
        ranks holding its id get invalid_ids on their next submission and
        renegotiate from full metadata once the stall clears."""
        import time as _time

        st = make_state(cache_capacity=8, stall_warning_s=0.001)
        _, _, resps, cids, _ = negotiate(
            st, {0: (0, [], [meta("s")]), 1: (0, [], [meta("s")])})
        cid = cids[0][0]
        assert cid >= 0
        # rank 0 re-submits via the cached id, rank 1 lags -> pending
        negotiate(st, {0: (0, [cid], []), 1: (0, [], [])})
        _time.sleep(0.01)
        # next round observes the stall: warning + cache invalidation
        _, _, _, _, warnings = negotiate(st, {0: (0, [], []),
                                              1: (0, [], [])})
        assert warnings and "s (waiting on ranks [1]" in warnings[0]
        assert "s" not in st.cache_ids and cid not in st.cache_meta
        # the stale id now comes back as invalid...
        out = st._negotiate({0: (0, [cid], []), 1: (0, [], [])})
        assert wire.decode_response_list(out)[9] == [cid]
        # ...and a full resubmission negotiates under a fresh id (rank 0's
        # pending meta from the stalled round is still in the table)
        _, _, resps, cids, _ = negotiate(
            st, {0: (0, [], [meta("s")]), 1: (0, [], [meta("s")])})
        assert resps[0].tensor_names == ["s"]
        assert cids[0][0] >= 0 and cids[0][0] != cid


def _worker_op_matrix():
    import numpy as np

    import horovod_tpu as hvd

    r = hvd.rank()
    out = {}
    b = hvd.broadcast(np.full((3,), float(r * 5 + 2), np.float32), 1,
                      name="mp_bc")
    out["bcast"] = [float(v) for v in np.asarray(b)]
    # alltoall: rank r sends [r*10+0, r*10+1]; receives column r
    a = hvd.alltoall(np.asarray([r * 10.0, r * 10.0 + 1.0], np.float32),
                     name="mp_a2a")
    out["alltoall"] = [float(v) for v in np.asarray(a)]
    ad = hvd.allreduce(np.full((4,), 1.0 + r, np.float32), name="mp_adasum",
                       op=hvd.Adasum)
    out["adasum"] = [float(v) for v in np.asarray(ad)]
    return (r, out)


@pytest.mark.integration
def test_mp_alltoall_broadcast_adasum():
    """The remaining op matrix as a REAL 2-process job: broadcast from a
    non-zero root, alltoall exchange, and the Adasum combine — all through
    the cross-process control plane."""
    from tests_adasum_ref import numpy_adasum

    results = dict(_run2(_worker_op_matrix))
    for r in (0, 1):
        got = results[r]
        np.testing.assert_allclose(got["bcast"], [7.0] * 3)  # root 1's value
        np.testing.assert_allclose(got["alltoall"], [r, 10.0 + r])
    want = numpy_adasum([np.full((4,), 1.0, np.float32),
                         np.full((4,), 2.0, np.float32)])
    for r in (0, 1):
        np.testing.assert_allclose(results[r]["adasum"], want, rtol=1e-5)


def _worker_autotune():
    import time as _time

    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import basics
    from horovod_tpu.ops import collective_ops as C

    r = hvd.rank()
    eng = basics._engine()
    ctrl = eng.controller
    start = (ctrl.fusion_threshold(), ctrl.cycle_time_ms())

    # 12 tensors x 256 KB per round: at the 1-byte starting threshold every
    # tensor executes alone (12 programs/round); any tuned threshold >= 1 MB
    # fuses them into <= 3 — a large, robust eager-throughput difference
    data = [np.full((65536,), float(r + i), np.float32) for i in range(12)]

    def drive(rounds):
        t0 = _time.monotonic()
        for _ in range(rounds):
            hs = [C.allreduce_async(d, name=f"at_{i}", op=hvd.Sum)
                  for i, d in enumerate(data)]
            for h in hs:
                C.synchronize(h)
        return rounds / (_time.monotonic() - t0)

    drive(4)  # first executions pay compile and are not scored
    untuned_rate = drive(40)
    seen = [start[0]]
    # drive past the GP's max_samples (40 x steps_per_sample 10 scored
    # rounds) so the tuner settles on the best configuration it saw
    for _ in range(14):
        drive(32)
        th = ctrl.fusion_threshold()
        if th != seen[-1]:
            seen.append(th)
    tuned_rate = drive(40)
    end = (ctrl.fusion_threshold(), ctrl.cycle_time_ms())
    return (r, start, end, seen, untuned_rate, tuned_rate)


@pytest.mark.integration
def test_mp_coordinated_autotune():
    """VERDICT r2 #2: scores ride request frames to rank 0, the GP/EI runs
    there, and tuned (fusion_threshold, cycle_time) come back in the
    ResponseList — every rank applies the same parameters. Start at a
    1-BYTE fusion threshold (nothing fuses) on a 12-tensor stream: every
    configuration the GP explores (>= 1 MB) fuses better, so the settled-on
    best beats the untuned starting throughput."""
    from horovod_tpu.run.api import run

    here = os.path.dirname(os.path.abspath(__file__))
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": os.pathsep.join([os.path.dirname(here), here]),
        "HOROVOD_AUTOTUNE": "1",
        "HOROVOD_FUSION_THRESHOLD": "1",
    }
    res = run(_worker_autotune, np=2, env=env, start_timeout=240)
    by_rank = {r: rest for r, *rest in res}
    for r, (start, end, seen, untuned, tuned) in by_rank.items():
        assert start == (1, 5.0)
        assert end != start, f"rank {r}: autotune never moved the params"
        assert len(seen) > 1, f"rank {r}: fusion threshold never retuned"
    # the coordinator broadcast reaches every rank: identical tuned state
    assert by_rank[0][1] == by_rank[1][1], "ranks diverged on tuned params"
    assert by_rank[0][2] == by_rank[1][2], \
        "ranks saw different threshold sequences"
    # starting at the minimum fusion threshold, the settled config must
    # beat the untuned rate (the reference's whole point for autotune)
    for r, (_, _, _, untuned, tuned) in by_rank.items():
        assert tuned > untuned, (
            f"rank {r}: tuned {tuned:.1f} ops/s not faster than untuned "
            f"{untuned:.1f} ops/s")


def _worker_ragged_alltoall():
    import numpy as np

    import horovod_tpu as hvd

    r = hvd.rank()
    w = hvd.size()
    # uneven, rank-dependent splits: rank r sends r+d+1 rows to rank d
    splits = [r + d + 1 for d in range(w)]
    rows = []
    for d in range(w):
        rows += [[100.0 * r + d]] * splits[d]
    exp = []
    for src in range(w):
        exp += [[100.0 * src + r]] * (src + r + 1)
    # second call with the same name: the coordinated response-cache id
    # fast path must rebuild the identical send matrix
    for _ in range(2):
        out, rsplits = hvd.alltoall(np.asarray(rows, np.float32),
                                    splits=splits, name="a2av_mp")
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(exp, np.float32))
        assert list(np.asarray(rsplits)) == [src + r + 1 for src in range(w)]
    # mixed usage: this rank ragged, peer equal -> coordinator error
    import pytest as _pytest
    kw = {"splits": [1, 1]} if r == 0 else {}
    with _pytest.raises(hvd.HorovodInternalError, match="splits usage"):
        hvd.alltoall(np.ones((2, 1), np.float32), name="a2av_mixed", **kw)
    return (r, True)


@pytest.mark.integration
def test_mp_ragged_alltoall():
    """VERDICT r4 #4 'done' criterion: cross-process ragged alltoall with
    uneven splits against numpy ground truth — split metadata negotiated
    through the coordinator (Response.tensor_sizes send matrix), plus the
    mixed-usage error path."""
    from horovod_tpu.run.api import run

    here = os.path.dirname(os.path.abspath(__file__))
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": os.pathsep.join([os.path.dirname(here), here]),
    }
    res = run(_worker_ragged_alltoall, np=2, env=env, start_timeout=240)
    assert sorted(res) == [(0, True), (1, True)]


def _worker_autotune_knob_cadence():
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import basics
    from horovod_tpu.ops import collective_ops as C

    r = hvd.rank()
    eng = basics._engine()
    ctrl = eng.controller

    data = [np.full((65536,), float(r + i), np.float32) for i in range(4)]

    def drive_round():
        hs = [C.allreduce_async(d, name=f"akc_{i}", op=hvd.Sum)
              for i, d in enumerate(data)]
        for h in hs:
            C.synchronize(h)

    drive_round()  # first execution pays compile and is not scored
    thresholds = []
    for _ in range(14):
        drive_round()
        thresholds.append(ctrl.fusion_threshold())
    # rank 0 owns the coordinator-side GP; report whether it settled
    state = getattr(ctrl, "_state", None)
    settled = (state.tuner is not None and not state.tuner.active()) \
        if (state is not None and r == 0) else None
    return (r, thresholds, settled)


@pytest.mark.integration
def test_mp_autotune_subknob_cadence():
    """VERDICT r3 #2 'done' criterion: the warmup-samples and
    steps-per-sample knobs observably change coordinated tuner cadence
    across 2 real processes. With steps-per-sample=1, warmup-samples=1 and
    bayes-opt-max-samples=4 the rank-0 GP retunes within the first few
    scored rounds (default cadence would not move until round 10) and
    settles — threshold frozen, tuner inactive — before the run ends."""
    from horovod_tpu.run.api import run

    here = os.path.dirname(os.path.abspath(__file__))
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": os.pathsep.join([os.path.dirname(here), here]),
        "HOROVOD_AUTOTUNE": "1",
        "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "1",
        "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "1",
        "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": "4",
    }
    res = run(_worker_autotune_knob_cadence, np=2, env=env,
              start_timeout=240)
    by_rank = {r: rest for r, *rest in res}
    for r, (thresholds, settled) in by_rank.items():
        start = 64 * 1024 * 1024
        changed_at = next((i for i, t in enumerate(thresholds)
                           if t != start), None)
        assert changed_at is not None and changed_at < 9, (
            f"rank {r}: first retune at round {changed_at} — the "
            f"steps-per-sample=1 cadence never took (default is 10)")
        # settled: the last rounds ride one frozen threshold
        assert len(set(thresholds[-3:])) == 1, thresholds
    assert by_rank[0][1] is True, "max-samples=4 never settled the rank-0 GP"
    assert by_rank[0][0] == by_rank[1][0], "ranks saw different cadences"


def _worker_observability():
    import logging
    import time as _time

    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.ops import collective_ops as C

    r = hvd.rank()
    records = []

    class _Cap(logging.Handler):
        def emit(self, rec):
            records.append(rec.getMessage())

    logging.getLogger("horovod_tpu").addHandler(_Cap())

    # normal traffic -> op spans in every rank's timeline
    for i in range(3):
        C.synchronize(C.allreduce_async(
            np.full((8,), float(r), np.float32), name=f"obs{i}",
            op=hvd.Sum))
    stalled_logged = False
    if r == 0:
        # rank 0 submits a tensor rank 1 never does -> stall warning at the
        # coordinator names rank 1
        h = C.allreduce_async(np.full((4,), 1.0, np.float32), name="obs_stall",
                              op=hvd.Sum)
        _time.sleep(2.5)
    else:
        # rank 1 is the laggard: it must log the stall LOCALLY
        deadline = _time.monotonic() + 20
        while _time.monotonic() < deadline and not stalled_logged:
            stalled_logged = any("obs_stall" in m for m in records)
            _time.sleep(0.1)
        # now submit so rank 0's op completes and the job ends cleanly
        h = C.allreduce_async(np.full((4,), 1.0, np.float32), name="obs_stall",
                              op=hvd.Sum)
    C.synchronize(h)
    hvd.shutdown()  # flush the timeline file
    return (r, stalled_logged)


@pytest.mark.integration
def test_mp_worker_observability(tmp_path):
    """VERDICT r2 weak #6: multiprocess workers get (a) a local activity
    timeline at HOROVOD_TIMELINE.rank<N> with op spans, and (b) stall
    warnings delivered locally when THEY are the lagging rank."""
    import json

    from horovod_tpu.run.api import run

    here = os.path.dirname(os.path.abspath(__file__))
    tpath = str(tmp_path / "tl.json")
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": os.pathsep.join([os.path.dirname(here), here]),
        "HOROVOD_TIMELINE": tpath,
        "HOROVOD_STALL_CHECK_TIME_SECONDS": "1",
    }
    res = dict(run(_worker_observability, np=2, env=env, start_timeout=240))
    assert res[1] is True, "lagging rank never logged its stall locally"
    # rank 0 writes the shared path; rank 1 a suffixed local file
    for path in (tpath, tpath + ".rank1"):
        assert os.path.exists(path), f"missing timeline {path}"
        with open(path) as f:
            events = json.load(f)
        # op spans are B/E pairs; negotiation spans are NEGOTIATE_<name>
        names = {e.get("name") for e in events if e.get("ph") == "B"}
        assert any(n and "obs" in n for n in names), (
            path, sorted(n for n in names if n)[:10])


def test_stall_names_me_parsing():
    """Pin the coordinator warning format <-> worker filter coupling: the
    missing-rank list is the LAST 'waiting on ranks [...]' in the string, so
    adversarial tensor names cannot shadow it."""
    ctrl = CoordController.__new__(CoordController)
    ctrl._rank = 1
    warn = ("x waiting on ranks [] step "
            "(waiting on ranks [1, 3] for 2s)")
    assert ctrl._stall_names_me(warn)
    ctrl._rank = 2
    assert not ctrl._stall_names_me(warn)
    assert not ctrl._stall_names_me("no such pattern")
    # the REAL format produced by CoordState._negotiate
    st = make_state(stall_warning_s=0.0)
    _, _, _, _, warns = negotiate(st, {0: (0, [], [meta("s")]),
                                       1: (0, [], [])})
    ctrl._rank = 1
    assert ctrl._stall_names_me(warns[0])


# =================================================== survivable control plane
# (docs/control-plane.md: hierarchical negotiation, coordinator failover,
# storm-proof rendezvous)

def _req_payload(name="g", flags=0, epoch=-1):
    return wire.encode_request_list(flags, [], [meta(name)], epoch=epoch)


class TestBatchedExchange:
    def test_batch_completes_round_in_one_frame(self):
        st = make_state(world=2)
        out = st.exchange_batch([(0, 0, _req_payload()),
                                 (1, 0, _req_payload())])
        replies, deferred = out
        assert deferred == []
        assert sorted((r, s) for r, s, _ in replies) == [(0, 0), (1, 0)]
        for _, _, data in replies:
            _, _, resps, _, _ = wire.decode_response_list(data)[:5]
            assert len(resps) == 1 and resps[0].tensor_names == ["g"]
        # ONE control frame reached the state machine for the whole round
        assert st.frames_in == 1

    def test_batch_replay_is_idempotent(self):
        st = make_state(world=2)
        first, _ = st.exchange_batch([(0, 0, _req_payload()),
                                      (1, 0, _req_payload())])
        again, _ = st.exchange_batch([(0, 0, _req_payload()),
                                      (1, 0, _req_payload())])
        assert sorted(first) == sorted(again)  # answered from replay cache

    def test_batch_and_flat_interoperate(self):
        """One host batched, one rank flat: the same barrier serves both."""
        st = make_state(world=3)
        out = {}

        def flat():
            out[2] = st.exchange(2, 0, _req_payload())

        t = threading.Thread(target=flat)
        t.start()
        replies, _ = st.exchange_batch([(0, 0, _req_payload()),
                                        (1, 0, _req_payload())])
        t.join(timeout=30)
        assert not t.is_alive()
        datas = {r: d for r, _, d in replies}
        assert datas[0] == datas[1] == out[2]

    def test_elastic_joiner_is_deferred_not_blocking(self):
        """A joiner entry inside a batch must NOT stall the members' round
        (its admission spans their future commits): it comes back in the
        deferred list for the server to answer from a dedicated thread."""
        st = make_state(world=2, elastic=True)
        replies, deferred = st.exchange_batch(
            [(0, 0, _req_payload(epoch=0)),
             (1, 0, _req_payload(epoch=0)),
             (5, 0, _req_payload(epoch=0))])
        assert [(r, s) for r, s, _ in deferred] == [(5, 0)]
        assert sorted(r for r, _, _ in replies) == [0, 1]


class TestHostAggregator:
    def _echo_agg(self, linger_s=5.0):
        from horovod_tpu.runtime.hierarchy import HostAggregator

        holder = {}

        def flush(entries):
            # upstream stand-in: echo each payload back as the reply
            for r, s, p in entries:
                holder["agg"].deliver(r, s, b"re:" + p)

        holder["agg"] = HostAggregator(flush, linger_s=linger_s)
        return holder["agg"]

    def test_full_host_flushes_one_batch(self):
        agg = self._echo_agg(linger_s=60.0)  # linger must NOT be needed
        for r in range(4):
            agg.register(r)
        out = {}
        ts = [threading.Thread(target=lambda r=r: out.update(
            {r: agg.submit(r, 7, b"p%d" % r)})) for r in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert out == {r: b"re:p%d" % r for r in range(4)}
        assert agg.flushes == 1

    def test_linger_flushes_partial_batch(self):
        agg = self._echo_agg(linger_s=0.05)
        agg.register(0)
        agg.register(1)  # never submits
        t0 = time.monotonic()
        assert agg.submit(0, 0, b"x") == b"re:x"
        assert 0.04 <= time.monotonic() - t0 < 5.0
        assert agg.flushes == 1

    def test_close_releases_submitters(self):
        from horovod_tpu.runtime.hierarchy import (AggregatorClosed,
                                                   HostAggregator)

        agg = HostAggregator(lambda entries: None, linger_s=60.0)
        agg.register(0)
        agg.register(1)
        err = {}

        def blocked():
            try:
                agg.submit(0, 0, b"x")
            except AggregatorClosed as exc:
                err["got"] = exc

        t = threading.Thread(target=blocked)
        t.start()
        time.sleep(0.05)
        agg.close()
        t.join(timeout=10)
        assert not t.is_alive() and "got" in err
        # AggregatorClosed must walk the worker's ConnectionError path
        assert isinstance(err["got"], ConnectionError)


def test_hierarchical_1024_ranks_is_o_hosts():
    """Acceptance: 1024 fake ranks on 16 simulated hosts drive the REAL
    CoordState through exchange_batch. Every negotiation round must reach
    rank 0 as O(hosts) frames (16, not 1024) and complete within budget."""
    world, hosts = 1024, 16
    per_host = world // hosts
    st = make_state(world=world, threshold=0)
    payload = _req_payload()
    for rnd in range(3):
        frames_before = st.frames_in
        results = {}

        def host_thread(h, rnd=rnd):
            entries = [(h * per_host + i, rnd, payload)
                       for i in range(per_host)]
            replies, deferred = st.exchange_batch(entries)
            assert deferred == []
            results[h] = replies

        t0 = time.monotonic()
        ts = [threading.Thread(target=host_thread, args=(h,))
              for h in range(hosts)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        elapsed = time.monotonic() - t0
        assert all(not t.is_alive() for t in ts), "round deadlocked"
        assert elapsed < 30.0, f"1024-rank round took {elapsed:.1f}s"
        # O(hosts): exactly one frame per simulated host reached rank 0
        assert st.frames_in - frames_before == hosts
        assert sum(len(r) for r in results.values()) == world
        for replies in results.values():
            for _, _, data in replies:
                _, _, resps, _, _ = wire.decode_response_list(data)[:5]
                assert len(resps) == 1


class TestTierWireCodecs:
    def test_runs_helpers_roundtrip(self):
        ranks = [0, 1, 2, 5, 6, 9]
        runs = wire.ranks_to_runs(ranks)
        assert runs == [(0, 3), (5, 2), (9, 1)]
        assert wire.runs_to_ranks(runs) == ranks
        assert wire.runs_count(runs) == 6
        assert wire.runs_contain(runs, 6)
        assert not wire.runs_contain(runs, 7)

    def test_runs_set_algebra(self):
        a = wire.ranks_to_runs([0, 1, 2, 3])
        b = wire.ranks_to_runs([2, 3, 4])
        # merge takes DISJOINT lists (subtree coverage never overlaps) and
        # coalesces adjacency into one run
        assert wire.merge_runs([(0, 2)], [(2, 2), (8, 1)]) == [(0, 4),
                                                               (8, 1)]
        assert wire.runs_to_ranks(wire.runs_intersect(a, b)) == [2, 3]
        assert wire.runs_to_ranks(wire.runs_subtract(a, b)) == [0, 1]
        assert wire.runs_subtract(a, a) == []

    def test_tier_batch_roundtrip(self):
        groups = [(3, b"payload-a", [(0, 64), (128, 64)]),
                  (4, b"payload-b", [(0, 8)])]
        tier, index, got = wire.decode_tier_batch(
            wire.encode_tier_batch(2, 7, groups))
        assert (tier, index) == (2, 7)
        assert got == groups

    def test_tier_resp_and_heartbeat_roundtrip(self):
        groups = [(9, b"resp", [(0, 1000)])]
        assert wire.decode_tier_batch_resp(
            wire.encode_tier_batch_resp(groups)) == groups
        assert wire.decode_tier_heartbeat(
            wire.encode_tier_heartbeat(3, 11, [(0, 5), (8, 2)])) == (
                3, 11, [(0, 5), (8, 2)])

    def test_tagged_journal_is_backward_compatible(self):
        legacy = wire.encode_coord_journal(1, 2, [0, 1, 2], "why")
        tagged = wire.encode_coord_journal(1, 2, [0, 1, 2], "why",
                                           subtree="t2.1")
        # the untagged decoder reads both shapes (old standbys keep
        # working against a tagging primary)
        assert (wire.decode_coord_journal(legacy)
                == wire.decode_coord_journal(tagged)
                == (1, 2, [0, 1, 2], "why"))
        assert wire.decode_coord_journal_tagged(legacy) == (
            1, 2, [0, 1, 2], "why", "")
        assert wire.decode_coord_journal_tagged(tagged) == (
            1, 2, [0, 1, 2], "why", "t2.1")


class TestGroupAggregator:
    def _agg(self, linger_s=60.0):
        from horovod_tpu.runtime.hierarchy import GroupAggregator

        flushed = []
        agg = GroupAggregator(flushed.append, linger_s=linger_s)
        return agg, flushed

    def test_full_flush_merges_identical_payload_groups(self):
        agg, flushed = self._agg()
        replies = {1: [], 2: []}
        agg.register(1, lambda g, e: replies[1].append((g, e)))
        agg.register(2, lambda g, e: replies[2].append((g, e)))
        agg.deposit(1, [(0, b"p", [(0, 4)])])
        assert agg.flushes == 0  # still waiting for child 2
        agg.deposit(2, [(0, b"p", [(4, 4)])])
        assert agg.flushes == 1
        # identical (seq, payload) groups coalesce into ONE upstream group
        assert flushed == [[(0, b"p", [(0, 8)])]]

    def test_response_routes_by_run_intersection(self):
        agg, _ = self._agg()
        replies = {1: [], 2: []}
        agg.register(1, lambda g, e: replies[1].append((g, e)))
        agg.register(2, lambda g, e: replies[2].append((g, e)))
        agg.deposit(1, [(0, b"p", [(0, 4)])])
        agg.deposit(2, [(0, b"p", [(4, 4)])])
        agg.deliver_groups([(0, b"resp", [(0, 8)])])
        assert replies[1] == [([(0, b"resp", [(0, 4)])], [])]
        assert replies[2] == [([(0, b"resp", [(4, 4)])], [])]
        assert agg.inflight_merged() == []

    def test_partial_response_leaves_reshippable_remainder(self):
        agg, _ = self._agg()
        agg.register(1, lambda g, e: None)
        agg.register(2, lambda g, e: None)
        agg.deposit(1, [(0, b"p", [(0, 4)])])
        agg.deposit(2, [(0, b"p", [(4, 4)])])
        agg.deliver_groups([(0, b"resp", [(0, 4)])])
        # the unanswered half stays eligible for the reconnect re-ship
        assert agg.inflight_merged() == [(0, b"p", [(4, 4)])]

    def test_deliver_entry_routes_deferred_joiner(self):
        agg, _ = self._agg()
        replies = []
        agg.register(1, lambda g, e: replies.append((g, e)))
        agg.deposit(1, [(0, b"p", [(3, 2)])])
        agg.deliver_entry(4, 0, b"joiner")
        assert replies == [([], [(4, 0, b"joiner")])]
        # the per-rank answer subtracts exactly that rank from the ledger
        assert agg.inflight_merged() == [(0, b"p", [(3, 1)])]

    def test_unregister_keeps_inflight_for_rehoming_child(self):
        agg, _ = self._agg()
        agg.register(1, lambda g, e: None)
        agg.deposit(1, [(0, b"p", [(0, 4)])])
        agg.unregister(1)  # child connection dropped mid-round
        # its rows survive: the child re-homes and re-ships, and upstream
        # replay dedupe absorbs the duplicate
        assert agg.inflight_merged() == [(0, b"p", [(0, 4)])]


class TestGroupedExchange:
    def test_tier_round_matches_flat_response_bytes(self):
        st = make_state(world=4, threshold=0)
        replies, deferred = st.exchange_tier(
            2, "t2.0", [(0, _req_payload(), [(0, 4)])])
        assert deferred == []
        assert [(s, r) for s, _, r in replies] == [(0, [(0, 4)])]
        # ONE grouped frame carried the whole round
        assert st.frames_in == 1
        flat = make_state(world=4, threshold=0)
        flat_replies, _ = flat.exchange_batch(
            [(r, 0, _req_payload()) for r in range(4)])
        assert {d for _, _, d in flat_replies} == {replies[0][1]}

    def test_shard_replay_is_idempotent(self):
        st = make_state(world=2, threshold=0)
        first, _ = st.exchange_tier(2, "t2.0",
                                    [(0, _req_payload(), [(0, 2)])])
        again, _ = st.exchange_tier(2, "t2.0",
                                    [(0, _req_payload(), [(0, 2)])])
        assert first == again  # answered from the subtree replay shard

    def test_tier_and_flat_interoperate(self):
        """Ranks 0-3 arrive as one group, ranks 4-5 flat: one barrier."""
        st = make_state(world=6, threshold=0)
        out = {}

        def flat(r):
            out[r] = st.exchange(r, 0, _req_payload())

        ts = [threading.Thread(target=flat, args=(r,)) for r in (4, 5)]
        for t in ts:
            t.start()
        replies, _ = st.exchange_tier(2, "t2.0",
                                      [(0, _req_payload(), [(0, 4)])])
        for t in ts:
            t.join(timeout=30)
        assert all(not t.is_alive() for t in ts)
        assert replies[0][1] == out[4] == out[5]

    def test_elastic_joiner_is_deferred_from_group(self):
        st = make_state(world=2, elastic=True)
        replies, deferred = st.exchange_tier(
            2, "t2.0", [(0, _req_payload(epoch=0), [(0, 3)])])
        # members answered as the narrowed run; the prospective joiner
        # comes back for a dedicated deferred-admission thread
        assert [(r, s) for r, s, _ in deferred] == [(2, 0)]
        assert [(s, r) for s, _, r in replies] == [(0, [(0, 2)])]

    def test_100k_ranks_reach_rank0_as_o_subtrees_frames(self):
        """Tentpole acceptance shape: 102400 fake ranks behind 4 top-tier
        subtrees negotiate with exactly 4 frames per round at rank 0 and
        O(groups) work (no per-rank structures on the static path)."""
        world, units = 102400, 4
        per = world // units
        st = make_state(world=world, threshold=0)
        payload = _req_payload()
        for rnd in range(3):
            before = st.frames_in
            datas = {}

            def unit(u, rnd=rnd):
                r, d = st.exchange_tier(
                    4, "t4.%d" % u,
                    [(rnd, payload, [(u * per, per)])])
                assert d == []
                datas[u] = r[0][1]

            ts = [threading.Thread(target=unit, args=(u,))
                  for u in range(units)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            assert all(not t.is_alive() for t in ts), "round deadlocked"
            assert st.frames_in - before == units
            assert len(set(datas.values())) == 1


class TestTierFailover:
    """Satellite 1: a sub-coordinator that loses its upstream probes the
    failover keys and re-homes, re-shipping its in-flight ledger."""

    def _kv(self, monkeypatch):
        from horovod_tpu.run import rendezvous

        secret = rendezvous.make_secret()
        kv = rendezvous.KVStoreServer(secret).start()
        monkeypatch.setenv("HVD_KV_ADDR", f"127.0.0.1:{kv.port}")
        monkeypatch.setenv("HVD_SECRET", secret)
        return kv, secret

    def _tier_round(self, sock, secret, seq, payload, runs, timeout=30):
        from horovod_tpu.runtime.coordinator import MSG_TBATCH
        from horovod_tpu.runtime.coordinator import MSG_TBATCH_RESP

        wire.send_frame(sock, secret, MSG_TBATCH, seq, 101,
                        wire.encode_tier_batch(1, 0, [(seq, payload,
                                                       runs)]))
        stop = threading.Event()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                mt, _, _, data = wire.recv_frame(sock, secret, stop)
            except socket.timeout:
                continue
            if mt == MSG_TBATCH_RESP:
                return wire.decode_tier_batch_resp(data)
        raise AssertionError("no tier response within %ss" % timeout)

    def test_subcoord_rehomes_via_failover_key(self, monkeypatch):
        from horovod_tpu.runtime.coordinator import (MSG_HELLO,
                                                     _publish_key)
        from horovod_tpu.runtime.hierarchy import SubCoordinator

        kv, secret = self._kv(monkeypatch)
        st = make_state(world=2, threshold=0)
        server = CoordinatorServer(st, secret)
        sub = None
        child = None
        server2 = None
        try:
            sub = SubCoordinator("127.0.0.1", server.port, secret,
                                 leader_rank=0, tier=2, index=0, tiers=2,
                                 up_fail_base="addr.901")
            child = socket.create_connection(("127.0.0.1", sub.port),
                                             timeout=5)
            child.settimeout(0.5)
            wire.send_frame(child, secret, MSG_HELLO, 0, 101)
            got = self._tier_round(child, secret, 0, _req_payload(),
                                   [(0, 2)])
            assert [(s, r) for s, _, r in got] == [(0, [(0, 2)])]

            # primary upstream dies abruptly; a replacement comes up under
            # the failover key the sub-coordinator probes on reconnect
            server.die()
            server2 = CoordinatorServer(make_state(world=2, threshold=0),
                                        secret)
            _publish_key("addr.901.f1", f"127.0.0.1:{server2.port}",
                         secret)
            got = self._tier_round(child, secret, 1, _req_payload(),
                                   [(0, 2)])
            assert [(s, r) for s, _, r in got] == [(1, [(0, 2)])]
            assert sub._up_addr == ("127.0.0.1", server2.port)
        finally:
            if child is not None:
                child.close()
            if sub is not None:
                sub.stop()
            if server2 is not None:
                server2.stop()
            server.stop()
            kv.stop()


class TestStormProofRendezvous:
    def test_join_storm_coalesces_to_one_epoch(self, monkeypatch):
        """64 simultaneous joiners -> exactly ONE membership epoch bump."""
        from horovod_tpu.metrics import instruments

        monkeypatch.setenv("HOROVOD_ADMISSION_BATCH_MS", "200")
        st = make_state(world=4, elastic=True)
        with st.cv:
            st.committed = set(st.members)  # commit boundary already open
        coalesced0 = instruments.epoch_coalesced_joins().value
        out = {}
        ts = [threading.Thread(target=lambda r=r: out.update(
            {r: st.exchange(r, 0, _req_payload(epoch=0))}))
            for r in range(100, 164)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert all(not t.is_alive() for t in ts)
        assert st.epoch == 1, "join storm must cost exactly one epoch bump"
        assert len(st.members) == 4 + 64
        for data in out.values():
            rflags, _, _, _, _ = wire.decode_response_list(data)[:5]
            assert rflags & wire.RESP_RANKS_CHANGED
        assert (instruments.epoch_coalesced_joins().value
                - coalesced0) == 63

    def test_loss_storm_coalesces_to_one_epoch(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_ADMISSION_BATCH_MS", "100")
        st = make_state(world=8, elastic=True)
        for r in (5, 6, 7):
            st.rank_lost(r, "test kill")
        assert st.epoch == 0  # coalescing window still open
        time.sleep(0.15)
        data = st.exchange(0, 0, _req_payload(epoch=0))  # triggers flush
        rflags, _, _, _, _ = wire.decode_response_list(data)[:5]
        assert rflags & wire.RESP_RANKS_CHANGED
        assert st.epoch == 1, "3 near-simultaneous losses -> ONE bump"
        assert st.members == {0, 1, 2, 3, 4}
        assert "workers lost: ranks [5, 6, 7]" in st.reset_reason
        assert "lost" in st.reset_reason  # keeps WorkerLostError mapping

    def test_admission_batch_off_keeps_historical_behavior(self):
        st = make_state(world=4, elastic=True)
        st.rank_lost(3, "a")
        st.rank_lost(2, "b")
        assert st.epoch == 2  # one bump per loss, exactly as before


class TestReconnectBackoff:
    def test_zero_jitter_matches_legacy_schedule(self):
        from horovod_tpu.runtime.coordinator import _backoff_schedule

        legacy = [0.05, 0.1, 0.2, 0.4, 0.8, 1.6, 2.0, 2.0]
        got = [_backoff_schedule(rank, a, 0.05, 2.0, 0.0)
               for rank in (0, 7, 511) for a in range(1, 9)]
        assert got == legacy * 3

    def test_jitter_envelope_and_dispersion(self):
        from horovod_tpu.runtime.coordinator import _backoff_schedule

        jitter = 0.5
        for attempt in (1, 3, 5):
            base = min(0.05 * 2 ** (attempt - 1), 2.0)
            delays = [_backoff_schedule(r, attempt, 0.05, 2.0, jitter)
                      for r in range(256)]
            # bounded-jitter envelope: [backoff, backoff * (1 + jitter)]
            assert all(base <= d <= base * (1 + jitter) + 1e-12
                       for d in delays)
            # a mass reconnect must actually disperse, not re-synchronize
            assert len(set(delays)) > 200
            spread = max(delays) - min(delays)
            assert spread > base * jitter * 0.8

    def test_jitter_is_deterministic(self):
        from horovod_tpu.runtime.coordinator import _backoff_schedule

        a = [_backoff_schedule(r, 2, 0.05, 2.0, 0.3) for r in range(32)]
        b = [_backoff_schedule(r, 2, 0.05, 2.0, 0.3) for r in range(32)]
        assert a == b


class TestFlatWireByteIdentity:
    """With the new knobs unset, every byte the flat path produces must be
    identical to the pre-hierarchy implementation. Pinned against golden
    hex captured from the wire codecs (any codec change that touches the
    legacy encodings fails here)."""

    GOLDEN_REQ = (
        "010200000003000000070000000100000006000000676f6c64656e000000000700"
        "0000666c6f617433320200000004000000000000000200000000000000ffffffff"
        "00000000000000f03f000000000000f03f0000000000010010000000000000000000"
        "000000e03fffffffff")
    GOLDEN_RESP = (
        "0000000000ffffffff01000000000000000100000006000000676f6c64656e0000"
        "000007000000666c6f617433320000000000000000000000f03f000000000000f0"
        "3fffffffff010000000200000004000000000000000200000000000000000000000"
        "1000000000000000000000000ffffffff0000000000000000")
    GOLDEN_FRAME = (
        "0700000002050000000100000016ba5246c103e036de847bf73707e118409b449c"
        "cf86f5682e731aebda8fed6e6cb24e177061796c6f6164")

    def test_request_list_bytes_pinned(self):
        m = wire.ReqMeta("golden", 0, "float32", (4, 2))
        req = wire.encode_request_list(1, [3, 7], [m], score=(4096, 0.5),
                                       epoch=-1)
        assert req.hex() == self.GOLDEN_REQ

    def test_response_list_bytes_pinned(self):
        st = make_state(world=2, threshold=0)
        m = wire.ReqMeta("golden", 0, "float32", (4, 2))
        out = st._negotiate({0: (0, [], [m]), 1: (0, [], [m])})
        assert out.hex() == self.GOLDEN_RESP

    def test_frame_bytes_pinned(self):
        a, b = socket.socketpair()
        try:
            wire.send_frame(a, "s3cret", 2, 5, 1, b"payload")
            b.settimeout(5)
            got = b.recv(65536)
        finally:
            a.close()
            b.close()
        assert got.hex() == self.GOLDEN_FRAME

    def test_flat_controllers_send_only_legacy_frame_types(
            self, monkeypatch, tmp_path):
        """Spy on send_frame across a real 2-rank exchange with the knobs
        unset: no frame type beyond the legacy 1-13 range may appear."""
        from horovod_tpu.run import rendezvous

        monkeypatch.delenv("HOROVOD_HIERARCHICAL_COORD", raising=False)
        monkeypatch.delenv("HOROVOD_STANDBY_COORD", raising=False)
        monkeypatch.delenv("HOROVOD_ADMISSION_BATCH_MS", raising=False)
        monkeypatch.delenv("HOROVOD_HIERARCHY_TIERS", raising=False)
        monkeypatch.delenv("HOROVOD_HIERARCHY_FANOUT", raising=False)
        sent_types = []
        real = wire.send_frame

        def spy(sock, secret, msg_type, seq, rank, payload=b"", fence=0):
            sent_types.append(msg_type)
            # knobs unset: the lease plane is off, so no frame may carry a
            # fencing epoch — epoch 0 keeps the wire byte-identical
            assert fence == 0, (
                f"flat path stamped fence={fence} on frame type {msg_type}")
            return real(sock, secret, msg_type, seq, rank, payload)

        monkeypatch.setattr(wire, "send_frame", spy)
        secret = rendezvous.make_secret()
        kv = rendezvous.KVStoreServer(secret).start()
        monkeypatch.setenv("HVD_KV_ADDR", f"127.0.0.1:{kv.port}")
        monkeypatch.setenv("HVD_SECRET", secret)
        common = dict(world=2, fusion_threshold=64 << 20,
                      stall_warning_s=60.0, stall_shutdown_s=0.0,
                      cache_capacity=64, fusion_enabled=True,
                      timeline_path=None, autotune=False, cycle_time_ms=5.0)
        c0 = CoordController(self_rank=0, **common)
        c1 = CoordController(self_rank=1, **common)
        try:
            from horovod_tpu.runtime.messages import TensorTableEntry
            from horovod_tpu.runtime.messages import RequestType as RT

            for c, r in ((c0, 0), (c1, 1)):
                c.submit(TensorTableEntry(
                    tensor_name="t", rank=r,
                    request_type=RT.ALLREDUCE,
                    array=np.zeros((4,), np.float32)))
            out = {}
            t = threading.Thread(target=lambda: out.update({0: c0.tick()}))
            t.start()
            out[1] = c1.tick()
            t.join(timeout=30)
            assert out[0] is not None and out[1] is not None
        finally:
            c0.shutdown()
            c1.shutdown()
            kv.stop()
        assert sent_types, "spy never saw a frame"
        assert max(sent_types) <= 13, (
            f"non-legacy frame types on the flat path: "
            f"{sorted(set(t for t in sent_types if t > 13))}")


class TestJournalReplication:
    def test_snapshot_and_journal_roundtrip(self):
        snap = wire.encode_coord_snapshot(9, 4, 128, True, [1, 2, 5], 77)
        assert wire.decode_coord_snapshot(snap) == (9, 4, 128, True,
                                                    [1, 2, 5], 77)
        rec = wire.encode_coord_journal(10, 5, [1, 2], "worker lost: x")
        assert wire.decode_coord_journal(rec) == (10, 5, [1, 2],
                                                  "worker lost: x")

    def test_attach_streams_snapshot_then_journal(self):
        import queue

        from horovod_tpu.runtime.coordinator import MSG_JOURNAL, MSG_SNAPSHOT

        st = make_state(world=3, elastic=True)
        q = queue.Queue()
        st.attach_journal(q)
        mt, payload = q.get(timeout=5)
        assert mt == MSG_SNAPSHOT
        jseq, epoch, world, elastic, members, ncid = \
            wire.decode_coord_snapshot(payload)
        assert (jseq, epoch, world, elastic) == (0, 0, 3, True)
        assert members == [0, 1, 2]
        st.rank_lost(2, "test")
        mt, payload = q.get(timeout=5)
        assert mt == MSG_JOURNAL
        jseq, epoch, members, reason = wire.decode_coord_journal(payload)
        assert (jseq, epoch, members) == (1, 1, [0, 1])
        assert "worker lost" in reason
        st.detach_journal(q)
        st.rank_lost(1, "test2")
        assert q.empty()


class TestCoordinatorFaultKinds:
    def test_slow_spec_parses_milliseconds(self):
        from horovod_tpu.faultinject.spec import parse_spec

        rules = parse_spec("slow@coordinator:50")
        assert len(rules) == 1
        assert rules[0].kind == "slow"
        assert rules[0].point == "coordinator"
        assert abs(rules[0].seconds - 0.05) < 1e-9

    def test_die_spec_parses(self):
        from horovod_tpu.faultinject.spec import parse_spec

        rules = parse_spec("die@coordinator#0")
        assert rules[0].kind == "die"
        assert rules[0].applies_to(0) and not rules[0].applies_to(1)

    def test_slow_coordinator_delays_negotiation(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_FAULT_SPEC", "slow@coordinator:80")
        st = make_state(world=1)
        server = CoordinatorServer(st, "")
        try:
            t0 = time.monotonic()
            st.exchange(0, 0, _req_payload())
            assert time.monotonic() - t0 >= 0.08
        finally:
            server.stop()

    def test_die_coordinator_severs_service(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_FAULT_SPEC", "die@coordinator")
        st = make_state(world=1)
        server = CoordinatorServer(st, "")
        port = server.port
        try:
            st.exchange(0, 0, _req_payload())  # first negotiation -> die
            deadline = time.monotonic() + 5
            refused = False
            while time.monotonic() < deadline:
                try:
                    s = socket.create_connection(("127.0.0.1", port),
                                                 timeout=0.5)
                    s.close()
                    time.sleep(0.05)
                except OSError:
                    refused = True
                    break
            assert refused, "die@coordinator left the service reachable"
        finally:
            server.stop()


class TestStandbyPromotion:
    def _kv(self, monkeypatch):
        from horovod_tpu.run import rendezvous

        secret = rendezvous.make_secret()
        kv = rendezvous.KVStoreServer(secret).start()
        monkeypatch.setenv("HVD_KV_ADDR", f"127.0.0.1:{kv.port}")
        monkeypatch.setenv("HVD_SECRET", secret)
        return kv, secret

    def test_promotes_on_abrupt_death_not_on_bye(self, monkeypatch):
        from horovod_tpu.metrics import instruments
        from horovod_tpu.runtime.coordinator import _resolve_key
        from horovod_tpu.runtime.standby import StandbyCoordinator

        kv, secret = self._kv(monkeypatch)
        st = make_state(world=3, elastic=True)
        server = CoordinatorServer(st, secret)
        failovers0 = instruments.coord_failovers().value
        sb = StandbyCoordinator(
            rank=1, gen=777, host="127.0.0.1", port=server.port,
            secret=secret,
            make_state=lambda: make_state(world=3, elastic=True),
            should_promote=lambda: True)
        sb.start()
        try:
            deadline = time.monotonic() + 10
            while not sb._have_snapshot and time.monotonic() < deadline:
                time.sleep(0.05)
            assert sb._have_snapshot, "standby never received the snapshot"
            # an epoch change replicates as one journal record
            st.rank_lost(2, "test kill")
            deadline = time.monotonic() + 10
            while sb._epoch != 1 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert sb._epoch == 1 and sb._members == [0, 1]
            # abrupt death (no BYE): the standby must promote
            server.die()
            deadline = time.monotonic() + 15
            while not sb.promoted and time.monotonic() < deadline:
                time.sleep(0.05)
            assert sb.promoted, "standby never promoted after die()"
            assert sb.server is not None
            # promotion itself is a membership reset losing rank 0
            assert sb.server.state.epoch == 2
            assert sb.server.state.members == {1}
            # _promote sets `promoted`, publishes its address (a KV round
            # trip), THEN counts the failover: wait for the count
            deadline = time.monotonic() + 10
            while (instruments.coord_failovers().value == failovers0
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert (instruments.coord_failovers().value
                    - failovers0) == 1
            # workers find the promoted address under the failover key
            addr, fsecret = _resolve_key("addr.777.f1", timeout=5)
            assert fsecret == secret
            host, port = addr.rsplit(":", 1)
            s = socket.create_connection((host, int(port)), timeout=5)
            s.close()
        finally:
            sb.stop()
            server.stop()
            kv.stop()

    def test_stands_down_on_clean_bye(self, monkeypatch):
        from horovod_tpu.runtime.standby import StandbyCoordinator

        kv, secret = self._kv(monkeypatch)
        st = make_state(world=2, elastic=True)
        server = CoordinatorServer(st, secret)
        sb = StandbyCoordinator(
            rank=1, gen=778, host="127.0.0.1", port=server.port,
            secret=secret,
            make_state=lambda: make_state(world=2, elastic=True),
            should_promote=lambda: True)
        sb.start()
        try:
            deadline = time.monotonic() + 10
            while not sb._have_snapshot and time.monotonic() < deadline:
                time.sleep(0.05)
            assert sb._have_snapshot
            st.set_bye()  # clean coordinated shutdown
            server.stop()
            sb._thread.join(timeout=10)
            assert not sb._thread.is_alive()
            assert not sb.promoted, "clean BYE must never trigger promotion"
        finally:
            sb.stop()
            kv.stop()


# --------------------------------------- integration: coordinator SIGKILL
def _failover_train_fn():
    """3 ranks; rank 0 (the coordinator) dies abruptly at step 5; the warm
    standby on rank 1 promotes and ranks 1+2 finish 12 steps. Per-rank
    gradients make the membership change observable in the parameter
    trajectory. Returns (step, w, epoch, members) rows."""
    import os

    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    state = hvd.elastic.ElasticState(w=np.array([4.0], np.float32), step=0)
    log = []
    target = np.float32(1.0)

    @hvd.elastic.run_fn
    def train(state):
        ctrl = hvd.basics._engine().controller
        while state.step < 12:
            if state.step == 5 and ctrl.epoch() == 0:
                # barrier before the kill: every rank has logged AND
                # committed step 4, so restore can never sync a survivor
                # past a step another survivor hasn't logged yet (rank 0
                # dying between serving two ranks' step-4 data otherwise
                # loses the slower rank's row to the rollback)
                hvd.allreduce(np.zeros(1, np.float32), name="prekill")
                if hvd.rank() == 0:
                    os._exit(23)  # SIGKILL-equivalent: no BYE, server dies
            g = np.float32(hvd.rank() + 1) * (np.asarray(state.w) - target)
            avg = hvd.allreduce(g, name=f"grad{state.step}",
                                op=hvd.Average)
            state.w = np.asarray(state.w) - np.float32(0.1) * \
                np.asarray(avg, np.float32)
            log.append((state.step, float(np.asarray(state.w)[0]),
                        ctrl.epoch(), list(ctrl.members())))
            state.step += 1
            state.commit()
        return log

    return train(state)


@pytest.mark.integration
def test_coordinator_sigkill_failover_bit_identical():
    """ISSUE acceptance: SIGKILL rank 0 mid-training with the standby
    enabled -> training resumes on the promoted coordinator with no lost
    or double-applied step, and both survivors hold bit-identical
    parameters matching the expected trajectory."""
    import cloudpickle

    from horovod_tpu.run import rendezvous

    here = os.path.dirname(os.path.abspath(__file__))
    secret = rendezvous.make_secret()
    kv = rendezvous.KVStoreServer(secret).start()
    addr = f"127.0.0.1:{kv.port}"
    client = rendezvous.KVStoreClient(addr, secret)
    client.put("runfunc", "fn",
               cloudpickle.dumps((_failover_train_fn, (), {})))

    procs = []
    try:
        for r in range(3):
            env = dict(os.environ)
            env.update({
                "HVD_NUM_PROCS": "3",
                "HVD_PROCESS_ID": str(r),
                "HVD_KV_ADDR": addr,
                "HVD_SECRET": secret,
                "HVD_ELASTIC": "1",
                "HOROVOD_STANDBY_COORD": "1",
                # failover never waits on the reconnect grace (promotion
                # declares rank 0 lost explicitly, standby.py); the grace
                # only shields LIVE ranks from load-induced connection
                # blips, so a tight value just makes a starved full-suite
                # run spuriously kill a survivor mid-test
                "HOROVOD_RECONNECT_GRACE": "15",
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": os.pathsep.join(
                    [os.path.dirname(here), here]),
            })
            env.pop("XLA_FLAGS", None)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "horovod_tpu.run.task"], env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))

        deadline = time.time() + 180
        blobs = {}
        while time.time() < deadline and len(blobs) < 2:
            for r in (1, 2):
                if r not in blobs:
                    blob = client.get("result", str(r))
                    if blob is not None:
                        blobs[r] = blob
            if len(blobs) < 2 and all(p.poll() is not None for p in procs):
                time.sleep(1.0)  # final PUTs may still be in flight
                for r in (1, 2):
                    blob = client.get("result", str(r))
                    if blob is not None:
                        blobs[r] = blob
                break
            time.sleep(0.25)
        assert len(blobs) == 2, (
            f"survivors produced no result (got ranks {sorted(blobs)}); "
            f"exit codes {[p.poll() for p in procs]}")
        logs = {}
        for r, blob in blobs.items():
            ok, log = pickle.loads(blob)
            assert ok, f"rank {r} raised:\n{log}"
            logs[r] = log
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        kv.stop()

    # rank 0 must have died with its marker code, not finished
    assert procs[0].wait(timeout=10) == 23

    for r in (1, 2):
        steps = [row[0] for row in logs[r]]
        # every step exactly once: none lost, none double-applied
        assert steps == list(range(12)), (r, steps)
        epochs = {s: e for s, _, e, _ in logs[r]}
        assert all(epochs[s] == 0 for s in range(5)), (r, epochs)
        # the failover reset bumps the epoch exactly once
        assert all(epochs[s] == 1 for s in range(5, 12)), (r, epochs)
        assert logs[r][4][3] == [0, 1, 2], (r, logs[r][4])
        assert logs[r][-1][3] == [1, 2], (r, logs[r][-1])

    # bit-identical across survivors at every step
    w1 = [row[1] for row in logs[1]]
    w2 = [row[1] for row in logs[2]]
    assert w1 == w2, "survivors diverged after failover"

    # and on the expected trajectory: mean(rank+1) is 2.0 with members
    # {0,1,2} (steps 0-4) and 2.5 with {1,2} (steps 5-11)
    w = 4.0
    for step in range(12):
        c = 2.0 if step < 5 else 2.5
        w = w - 0.1 * c * (w - 1.0)
        got = w1[step]
        assert abs(got - w) < 1e-4 * max(1.0, abs(w)), (
            f"step {step}: got {got}, expected ~{w} — a step was lost or "
            f"double-applied across the failover")


# ------------------------------------- integration: hierarchical mode e2e
def _hier_train_fn():
    """3 ranks on one simulated host with HOROVOD_HIERARCHICAL_COORD=1:
    ranks 1 and 2 negotiate through the host leader's sub-coordinator over
    real sockets; results must match the flat path exactly."""
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r = hvd.rank()
    out = []
    w = np.asarray(hvd.broadcast(np.ones(4, np.float32) * (r + 1),
                                 root_rank=0, name="w0"))
    out.append(w.tolist())
    for i in range(5):
        s = hvd.allreduce(np.ones(4, np.float32) * (r + 1),
                          name=f"h{i}", op=hvd.Sum)
        out.append(np.asarray(s).tolist())
    hvd.shutdown()
    return out


@pytest.mark.integration
def test_hierarchical_mode_end_to_end():
    """The sub-coordinator path over real processes and sockets: host
    leader aggregates its local ranks' frames, DATA-plane broadcast rides
    the direct rank-0 connection, and every collective result is exact."""
    import cloudpickle

    from horovod_tpu.run import rendezvous

    here = os.path.dirname(os.path.abspath(__file__))
    secret = rendezvous.make_secret()
    kv = rendezvous.KVStoreServer(secret).start()
    addr = f"127.0.0.1:{kv.port}"
    client = rendezvous.KVStoreClient(addr, secret)
    client.put("runfunc", "fn",
               cloudpickle.dumps((_hier_train_fn, (), {})))

    procs = []
    try:
        for r in range(3):
            env = dict(os.environ)
            env.update({
                "HVD_NUM_PROCS": "3",
                "HVD_PROCESS_ID": str(r),
                "HVD_KV_ADDR": addr,
                "HVD_SECRET": secret,
                "HVD_ELASTIC": "1",
                "HVD_LOCAL_RANK": str(r),
                "HVD_CROSS_RANK": "0",
                "HOROVOD_HIERARCHICAL_COORD": "1",
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": os.pathsep.join(
                    [os.path.dirname(here), here]),
            })
            env.pop("XLA_FLAGS", None)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "horovod_tpu.run.task"], env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))

        deadline = time.time() + 120
        blobs = {}
        while time.time() < deadline and len(blobs) < 3:
            for r in range(3):
                if r not in blobs:
                    blob = client.get("result", str(r))
                    if blob is not None:
                        blobs[r] = blob
            if len(blobs) < 3 and all(p.poll() is not None for p in procs):
                time.sleep(1.0)
                for r in range(3):
                    blob = client.get("result", str(r))
                    if blob is not None:
                        blobs[r] = blob
                break
            time.sleep(0.25)
        assert len(blobs) == 3, (
            f"hier job incomplete: results from {sorted(blobs)}, exit "
            f"codes {[p.poll() for p in procs]}")
        for r, blob in blobs.items():
            ok, out = pickle.loads(blob)
            assert ok, f"rank {r} raised:\n{out}"
            assert out[0] == [1.0] * 4          # broadcast from rank 0
            for row in out[1:]:
                assert row == [6.0] * 4         # 1+2+3 summed exactly
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        kv.stop()


# ------------------- integration: hierarchical x standby SIGKILL failover
@pytest.mark.integration
def test_hierarchical_standby_sigkill():
    """ISSUE acceptance: SIGKILL rank 0 with BOTH the hierarchical control
    plane and the warm standby enabled. Ranks 1+2 negotiate through their
    host's sub-coordinator; when rank 0 dies, the standby on rank 1
    promotes and the sub-coordinator re-homes upstream via the
    ``addr.{gen}.f1`` failover key, re-shipping its in-flight batch ledger
    — no step lost, none double-applied, survivors bit-identical."""
    import cloudpickle

    from horovod_tpu.run import rendezvous

    here = os.path.dirname(os.path.abspath(__file__))
    secret = rendezvous.make_secret()
    kv = rendezvous.KVStoreServer(secret).start()
    addr = f"127.0.0.1:{kv.port}"
    client = rendezvous.KVStoreClient(addr, secret)
    client.put("runfunc", "fn",
               cloudpickle.dumps((_failover_train_fn, (), {})))

    # two simulated hosts: rank 0 alone on host 0; ranks 1+2 on host 1
    # behind rank 1's sub-coordinator (rank 1 also runs the standby), so
    # the failover exercises the aggregator re-home, not just the direct
    # worker reconnect
    placement = {0: ("0", "0"), 1: ("0", "1"), 2: ("1", "1")}
    procs = []
    try:
        for r in range(3):
            local, cross = placement[r]
            env = dict(os.environ)
            env.update({
                "HVD_NUM_PROCS": "3",
                "HVD_PROCESS_ID": str(r),
                "HVD_KV_ADDR": addr,
                "HVD_SECRET": secret,
                "HVD_ELASTIC": "1",
                "HVD_LOCAL_RANK": local,
                "HVD_CROSS_RANK": cross,
                "HOROVOD_HIERARCHICAL_COORD": "1",
                "HOROVOD_STANDBY_COORD": "1",
                "HOROVOD_RECONNECT_GRACE": "15",
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": os.pathsep.join(
                    [os.path.dirname(here), here]),
            })
            env.pop("XLA_FLAGS", None)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "horovod_tpu.run.task"], env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))

        deadline = time.time() + 180
        blobs = {}
        while time.time() < deadline and len(blobs) < 2:
            for r in (1, 2):
                if r not in blobs:
                    blob = client.get("result", str(r))
                    if blob is not None:
                        blobs[r] = blob
            if len(blobs) < 2 and all(p.poll() is not None for p in procs):
                time.sleep(1.0)  # final PUTs may still be in flight
                for r in (1, 2):
                    blob = client.get("result", str(r))
                    if blob is not None:
                        blobs[r] = blob
                break
            time.sleep(0.25)
        assert len(blobs) == 2, (
            f"survivors produced no result (got ranks {sorted(blobs)}); "
            f"exit codes {[p.poll() for p in procs]}")
        logs = {}
        for r, blob in blobs.items():
            ok, log = pickle.loads(blob)
            assert ok, f"rank {r} raised:\n{log}"
            logs[r] = log
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        kv.stop()

    assert procs[0].wait(timeout=10) == 23

    for r in (1, 2):
        steps = [row[0] for row in logs[r]]
        # every step exactly once: none lost, none double-applied
        assert steps == list(range(12)), (r, steps)
        epochs = {s: e for s, _, e, _ in logs[r]}
        assert all(epochs[s] == 0 for s in range(5)), (r, epochs)
        assert all(epochs[s] == 1 for s in range(5, 12)), (r, epochs)
        assert logs[r][-1][3] == [1, 2], (r, logs[r][-1])

    # bit-identical across survivors at every step, on the expected
    # trajectory (mean gradient 2.0 with 3 members, 2.5 with 2)
    w1 = [row[1] for row in logs[1]]
    w2 = [row[1] for row in logs[2]]
    assert w1 == w2, "survivors diverged after failover"
    w = 4.0
    for step in range(12):
        c = 2.0 if step < 5 else 2.5
        w = w - 0.1 * c * (w - 1.0)
        assert abs(w1[step] - w) < 1e-4 * max(1.0, abs(w)), (
            f"step {step}: got {w1[step]}, expected ~{w} — a step was "
            f"lost or double-applied across the failover")


class TestTunedWireByteIdentity:
    """The joint tuner's 4th tuned field (collective algorithm) rides a new
    flag byte (3). Absent, the frame must stay byte-identical to the PR-10
    3-field bitwidth wire — pinned against golden hex — and old-style
    3-field frames must decode unchanged."""

    # encode_response_list(0, -1, [], [], [], tuned=(4096, 2.5, "int8"))
    GOLDEN_TUNED3 = (
        "0000000000ffffffff00000000000000000200100000000000000000000000000"
        "44004000000696e7438ffffffff0000000000000000")

    def test_three_field_frame_bytes_pinned(self):
        out = wire.encode_response_list(0, -1, [], [], [],
                                        tuned=(4096, 2.5, "int8"))
        assert out.hex() == self.GOLDEN_TUNED3

    def test_three_field_golden_decodes_unchanged(self):
        decoded = wire.decode_response_list(bytes.fromhex(
            self.GOLDEN_TUNED3))
        assert decoded[6] == (4096, 2.5, "int8")

    def test_empty_algorithm_keeps_old_bytes(self):
        # a JointTuner that has not settled an algorithm (or a plain
        # BitwidthTuner) must not grow the frame
        old = wire.encode_response_list(0, -1, [], [], [],
                                        tuned=(4096, 2.5, "int8"))
        new = wire.encode_response_list(0, -1, [], [], [],
                                        tuned=(4096, 2.5, "int8", ""))
        assert new == old

    def test_algorithm_field_roundtrip(self):
        for algo in ("ring", "tree", "hier"):
            buf = wire.encode_response_list(0, -1, [], [], [],
                                            tuned=(4096, 2.5, "int8", algo))
            assert wire.decode_response_list(buf)[6] \
                == (4096, 2.5, "int8", algo)
        # flag ladder stays monotone: each tier adds exactly one field
        for tuned, want in (((64, 5.0), (64, 5.0)),
                            ((64, 5.0, "bf16"), (64, 5.0, "bf16")),
                            (None, None)):
            buf = wire.encode_response_list(0, -1, [], [], [], tuned=tuned)
            assert wire.decode_response_list(buf)[6] == want
