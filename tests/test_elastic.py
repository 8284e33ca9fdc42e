"""Elastic training subsystem tests (docs/elastic.md).

Unit layer: CoordState membership epochs (stale-epoch rejection, worker-loss
resets releasing blocked barriers, commit-boundary admission), the host-wire
data plane, ElasticState commit/restore semantics, and the KV client's
transient-error retry. Integration layer: a real 2-process CPU job where one
worker dies mid-training — the survivor must renegotiate under a bumped
epoch, re-sync committed state, and keep the loss decreasing.

Parity model: reference `test/test_elastic.py` (state/commit/restore) and
`test/integration/test_elastic_torch.py` (kill-a-worker runs).
"""

import os
import pickle
import subprocess
import sys
import threading
import time
import urllib.error

import numpy as np
import pytest

from horovod_tpu.elastic import ElasticState
from horovod_tpu.runtime import wire
from horovod_tpu.runtime.coordinator import CoordState
from horovod_tpu.runtime.messages import RequestType

ALLREDUCE = int(RequestType.ALLREDUCE)
BROADCAST = int(RequestType.BROADCAST)


def meta(name, shape=(4,), rtype=ALLREDUCE, dtype="float32", **kw):
    return wire.ReqMeta(name, rtype, dtype, shape, **kw)


def make_estate(world=2):
    return CoordState(world, 64 << 20, cache_capacity=1024,
                      stall_warning_s=60.0, stall_shutdown_s=0.0,
                      elastic=True)


def req(metas, flags=0, epoch=0):
    return wire.encode_request_list(flags, [], metas, epoch=epoch)


# ----------------------------------------------------------- membership epochs
class TestMembershipEpochs:
    def test_stale_epoch_rejected_not_deadlocked(self):
        st = make_estate()
        st.rank_lost(1, "connection reset")  # epoch 0 -> 1
        # a frame negotiated under epoch 0 must fail fast, not enter a
        # barrier the current member set can never complete
        out = st.exchange(0, 0, req([meta("g")], epoch=0))
        (flags, _, _, _, _, reason, _, epoch,
         members, _, _) = wire.decode_response_list(out)
        assert flags & wire.RESP_RANKS_CHANGED
        assert epoch == 1
        assert members == [0]
        assert "worker lost" in reason and "rank 1" in reason

    def test_rank_lost_releases_blocked_barrier(self):
        st = make_estate()
        out = {}

        def blocked():
            out["r0"] = st.exchange(0, 0, req([meta("g")], epoch=0))

        t = threading.Thread(target=blocked)
        t.start()
        time.sleep(0.2)  # let rank 0 enter the barrier (waiting on rank 1)
        st.rank_lost(1, "killed")
        t.join(timeout=10)
        assert not t.is_alive(), "reset must release the blocked exchange"
        flags = wire.decode_response_list(out["r0"])[0]
        assert flags & wire.RESP_RANKS_CHANGED
        assert st.epoch == 1 and st.members == {0}

    def test_join_admitted_at_commit_boundary(self):
        st = make_estate()
        out = {}

        def joiner():
            out[2] = st.exchange(2, 0, req([], epoch=0))

        tj = threading.Thread(target=joiner)
        tj.start()
        time.sleep(0.2)
        # not yet a boundary: only rank 0 committed
        assert st.pending_joins == {2} and st.members == {0, 1}

        def commit(rank):
            out[rank] = st.exchange(
                rank, 0, req([], flags=wire.REQ_COMMIT, epoch=0))

        t0 = threading.Thread(target=commit, args=(0,))
        t0.start()
        time.sleep(0.1)
        commit(1)  # completes the boundary -> admission
        t0.join(timeout=10)
        tj.join(timeout=10)
        assert st.members == {0, 1, 2}
        assert st.epoch == 1
        for rank in (0, 1, 2):
            flags, _, _, _, _, _, _, epoch, members, _, _ = \
                wire.decode_response_list(out[rank])
            assert flags & wire.RESP_RANKS_CHANGED
            assert epoch == 1 and members == [0, 1, 2]

    def test_commit_boundary_without_joiners_is_noop(self):
        st = make_estate()
        out = {}
        t0 = threading.Thread(target=lambda: out.setdefault(0, st.exchange(
            0, 0, req([], flags=wire.REQ_COMMIT, epoch=0))))
        t0.start()
        st.exchange(1, 0, req([], flags=wire.REQ_COMMIT, epoch=0))
        t0.join(timeout=10)
        assert st.epoch == 0 and st.members == {0, 1}
        assert st.committed == set()

    def test_broadcast_root_validated_against_members(self):
        st = make_estate()
        st.rank_lost(1, "gone")
        out = st.exchange(
            0, 1, req([meta("b", rtype=BROADCAST, root_rank=1)], epoch=1))
        _, _, resps, _, _, _, _, _, _, _, _ = wire.decode_response_list(out)
        assert "Invalid root rank 1" in resps[0].error_message


# ----------------------------------------------------------- host-wire data
class TestDataExchange:
    def _dreq(self, epoch, dseq, arr, op=ALLREDUCE, root=-1):
        a = np.ascontiguousarray(arr)
        return wire.encode_data_request(epoch, dseq, op, root,
                                        str(a.dtype), a.shape, a.tobytes())

    def test_allreduce_sums_over_members(self):
        st = make_estate()
        out = {}

        def send(rank, arr):
            out[rank] = st.data_exchange(
                rank, self._dreq(0, 0, np.asarray(arr, np.float32)))

        t = threading.Thread(target=send, args=(0, [1.0, 2.0]))
        t.start()
        send(1, [3.0, 4.0])
        t.join(timeout=10)
        for rank in (0, 1):
            status, epoch, nparticipants, _, payload = \
                wire.decode_data_result(out[rank])
            assert status == wire.DATA_OK
            assert nparticipants == 2
            np.testing.assert_allclose(
                np.frombuffer(payload, np.float32), [4.0, 6.0])

    def test_broadcast_takes_root_payload(self):
        st = make_estate()
        out = {}
        t = threading.Thread(target=lambda: out.setdefault(0, st.data_exchange(
            0, self._dreq(0, 0, np.asarray([7.0], np.float32),
                          op=BROADCAST, root=0))))
        t.start()
        out[1] = st.data_exchange(
            1, self._dreq(0, 0, np.zeros(1, np.float32),
                          op=BROADCAST, root=0))
        t.join(timeout=10)
        for rank in (0, 1):
            _, _, _, _, payload = wire.decode_data_result(out[rank])
            np.testing.assert_allclose(
                np.frombuffer(payload, np.float32), [7.0])

    def test_stale_epoch_data_request_rejected(self):
        st = make_estate()
        st.rank_lost(1, "gone")
        out = st.data_exchange(
            0, self._dreq(0, 0, np.zeros(2, np.float32)))
        status, epoch, _, members, _ = wire.decode_data_result(out)
        assert status == wire.DATA_RANKS_CHANGED
        assert epoch == 1 and members == [0]

    def test_reset_releases_blocked_data_waiter(self):
        st = make_estate()
        out = {}
        t = threading.Thread(target=lambda: out.setdefault(0, st.data_exchange(
            0, self._dreq(0, 0, np.zeros(2, np.float32)))))
        t.start()
        time.sleep(0.2)
        st.rank_lost(1, "killed")
        t.join(timeout=10)
        assert not t.is_alive()
        status = wire.decode_data_result(out[0])[0]
        assert status == wire.DATA_RANKS_CHANGED


# ----------------------------------------------------------- ElasticState
class TestElasticState:
    def test_commit_restore_roundtrip(self):
        s = ElasticState(w=np.array([1.0, 2.0]), step=0)
        s.w = np.array([9.0, 9.0])
        s.step = 7
        s.commit()
        s.w[0] = -1.0  # in-place mutation must not corrupt the snapshot
        s.step = 8
        s.restore()
        np.testing.assert_allclose(s.w, [9.0, 9.0])
        assert s.step == 7

    def test_restore_before_commit_returns_ctor_values(self):
        s = ElasticState(x=np.array([3.0]))
        s.x = np.array([5.0])
        s.restore()
        np.testing.assert_allclose(s.x, [3.0])

    def test_attribute_protocol(self):
        s = ElasticState(a=1)
        s.b = "new slot"
        assert s.slots() == ["a", "b"]
        with pytest.raises(AttributeError):
            s.missing
        assert s.reset_count == 0

    def test_pytree_slots(self):
        tree = {"layer": {"w": np.ones((2, 2)), "b": np.zeros(2)}, "n": 3}
        s = ElasticState(params=tree)
        s.commit()
        s.params["layer"]["w"][:] = 9.0
        s.restore()
        np.testing.assert_allclose(s.params["layer"]["w"], np.ones((2, 2)))
        assert s.params["n"] == 3


# ----------------------------------------------------------- KV client retry
class TestKVRetry:
    def _client(self):
        from horovod_tpu.run.rendezvous import KVStoreClient

        c = KVStoreClient("127.0.0.1:1", "s")
        c.BACKOFF = 0.001  # keep the test fast
        return c

    def test_transient_errors_retried(self, monkeypatch):
        calls = []

        class FakeResp:
            def read(self):
                return b"ok"

        def fake_urlopen(req, timeout=None):
            calls.append(1)
            if len(calls) < 3:
                raise urllib.error.URLError("connection refused")
            return FakeResp()

        monkeypatch.setattr("urllib.request.urlopen", fake_urlopen)
        self._client().put("scope", "key", b"v")
        assert len(calls) == 3

    def test_retries_bounded(self, monkeypatch):
        calls = []

        def fake_urlopen(req, timeout=None):
            calls.append(1)
            raise ConnectionRefusedError("nope")

        monkeypatch.setattr("urllib.request.urlopen", fake_urlopen)
        with pytest.raises(ConnectionRefusedError):
            self._client().put("scope", "key", b"v")
        from horovod_tpu.run.rendezvous import KVStoreClient

        assert len(calls) == KVStoreClient.RETRIES

    def test_http_errors_not_retried(self, monkeypatch):
        calls = []

        def fake_urlopen(req, timeout=None):
            calls.append(1)
            raise urllib.error.HTTPError("u", 403, "forbidden", {}, None)

        monkeypatch.setattr("urllib.request.urlopen", fake_urlopen)
        with pytest.raises(urllib.error.HTTPError):
            self._client().put("scope", "key", b"v")
        assert len(calls) == 1

    def test_get_404_still_returns_none(self, monkeypatch):
        def fake_urlopen(req, timeout=None):
            raise urllib.error.HTTPError("u", 404, "not found", {}, None)

        monkeypatch.setattr("urllib.request.urlopen", fake_urlopen)
        assert self._client().get("scope", "key") is None


# ----------------------------------------------------------- integration (2p)
def _elastic_train_fn():
    """2 ranks; rank 1 dies at step 5; rank 0 finishes 12 steps. Returns
    rank 0's (step, loss, epoch, members) log."""
    import os

    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    state = hvd.elastic.ElasticState(w=np.array([4.0], np.float32), step=0)
    log = []
    target = 1.0

    @hvd.elastic.run_fn
    def train(state):
        ctrl = hvd.basics._engine().controller
        while state.step < 12:
            if hvd.rank() != 0 and state.step == 5:
                os._exit(17)  # hard kill: no BYE, no cleanup
            g = 2.0 * (np.asarray(state.w) - target)
            avg = hvd.allreduce(g, name=f"grad{state.step}", op=hvd.Average)
            state.w = np.asarray(state.w) - 0.1 * np.asarray(avg)
            loss = float((np.asarray(state.w)[0] - target) ** 2)
            log.append((state.step, loss, ctrl.epoch(),
                        list(ctrl.members())))
            state.step += 1
            state.commit()
        return log

    return train(state)


@pytest.mark.integration
def test_elastic_survives_worker_loss():
    """The acceptance scenario: kill one worker mid-training; the job
    continues — survivors renegotiate under a bumped epoch, sync() restores
    agreement, and the loss keeps decreasing. Uses its own Popen harness
    (not run()): the launcher's wait_all kills the job on first failure,
    which is exactly the behaviour elastic mode exists to avoid."""
    import cloudpickle

    from horovod_tpu.run import rendezvous

    here = os.path.dirname(os.path.abspath(__file__))
    secret = rendezvous.make_secret()
    kv = rendezvous.KVStoreServer(secret).start()
    addr = f"127.0.0.1:{kv.port}"
    client = rendezvous.KVStoreClient(addr, secret)
    client.put("runfunc", "fn",
               cloudpickle.dumps((_elastic_train_fn, (), {})))

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
                # the killed worker never reconnects; shrink the reconnect
                # grace window so rank_lost fires promptly instead of after
                # the 10 s production default
                "HOROVOD_RECONNECT_GRACE": "2",
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
            rc0 = procs[0].poll()
            if rc0 is not None:
                time.sleep(1.0)  # final result PUT may still be in flight
                blob = client.get("result", "0")
                break
            time.sleep(0.25)
        assert blob is not None, "rank 0 produced no result (deadlocked?)"
        ok, log = pickle.loads(blob)
        assert ok, f"rank 0 raised:\n{log}"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        kv.stop()

    # rank 1 must have died with its marker code, not finished
    assert procs[1].wait(timeout=10) == 17

    steps = [row[0] for row in log]
    assert steps == list(range(12)), steps
    epochs = {s: e for s, _, e, _ in log}
    # steps 0-4 under the initial epoch with both members; the loss of rank
    # 1 at step 5 bumps the epoch and the job continues with rank 0 alone
    assert all(epochs[s] == 0 for s in range(5)), epochs
    assert all(epochs[s] == 1 for s in range(5, 12)), epochs
    assert log[4][3] == [0, 1] and log[-1][3] == [0]
    losses = [row[1] for row in log]
    assert all(b < a for a, b in zip(losses, losses[1:])), \
        f"loss must keep decreasing through the reset: {losses}"
