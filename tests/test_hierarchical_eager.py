"""Two-level ("dcn","ici") eager collectives vs the flat rank mesh.

VERDICT r2 #3: the multiprocess/cluster executor gains the
NCCLHierarchicalAllreduce decomposition (reduce_scatter ICI → allreduce DCN
→ all_gather ICI, `nccl_operations.cc:150-346`) and the two-level allgather
(`mpi_operations.cc:168-310`'s node-leader gather), behind the reference's
HOROVOD_HIERARCHICAL_ALLREDUCE / _ALLGATHER env knobs. These tests assert
BIT-IDENTICAL results vs the flat path (inputs are small integers, so f32
addition is exact in any association order).
"""

import os

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import testing
from horovod_tpu.ops import collective_ops as C


def _allreduce_worker():
    r = hvd.rank()
    outs = []
    specs = [
        dict(op=hvd.Sum, arr=np.arange(17, dtype=np.float32) + r),
        dict(op=hvd.Average, arr=np.full((4, 3), float(r + 1), np.float32)),
        dict(op=hvd.Sum, arr=np.arange(8, dtype=np.int32) * (r + 1)),
    ]
    for i, s in enumerate(specs):
        h = C.allreduce_async(s["arr"], name=f"h{i}", op=s["op"])
        outs.append(np.asarray(C.synchronize(h)))
    # ragged allgather: rank r contributes r+1 rows
    rows = np.full((r + 1, 3), float(r), np.float32)
    hg = C.allgather_async(rows, name="hg")
    outs.append(np.asarray(C.synchronize(hg)))
    return outs


def _run_cluster_config(monkeypatch, hier: bool, np_ranks: int = 8,
                        worker=None):
    if hvd.is_initialized():
        hvd.shutdown()
    if hier:
        monkeypatch.setenv("HVD_LOCAL_SIZE", "4")
        monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
        monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLGATHER", "1")
    else:
        monkeypatch.delenv("HVD_LOCAL_SIZE", raising=False)
        monkeypatch.delenv("HOROVOD_HIERARCHICAL_ALLREDUCE", raising=False)
        monkeypatch.delenv("HOROVOD_HIERARCHICAL_ALLGATHER", raising=False)
    res = testing.run_cluster(worker or _allreduce_worker, np=np_ranks)
    hvd.shutdown()
    return res


def test_two_level_bitidentical_to_flat(monkeypatch):
    """8 ranks as a synthetic 2-host × 4-rank topology: every op's result is
    bitwise equal to the flat single-level mesh."""
    flat = _run_cluster_config(monkeypatch, hier=False)
    hier = _run_cluster_config(monkeypatch, hier=True)
    for rank, (f_outs, h_outs) in enumerate(zip(flat, hier)):
        assert len(f_outs) == len(h_outs) == 4
        for f, h in zip(f_outs, h_outs):
            np.testing.assert_array_equal(f, h)


def test_two_level_mesh_construction(monkeypatch):
    """The grouping honors HVD_LOCAL_SIZE and degenerates safely."""
    from horovod_tpu.runtime.executor import Executor

    monkeypatch.setenv("HVD_LOCAL_SIZE", "2")
    if hvd.is_initialized():
        hvd.shutdown()
    hvd.init(_cluster_size=8)
    try:
        ex = hvd.basics._engine()._executor
        assert ex._mesh2 is not None
        assert dict(ex._mesh2.shape) == {"dcn": 4, "ici": 2}
        # device order matches rank order when flattened
        assert list(ex._mesh2.devices.flat) == ex._rank_devices
    finally:
        hvd.shutdown()


def _mp_worker():
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.ops import collective_ops as C

    r = hvd.rank()
    outs = []
    h = C.allreduce_async(np.arange(33, dtype=np.float32) + 3 * r,
                          name="ar", op=hvd.Sum)
    outs.append(np.asarray(C.synchronize(h)).tolist())
    h = C.allreduce_async(np.full((5,), float(r + 1), np.float32),
                          name="avg", op=hvd.Average)
    outs.append(np.asarray(C.synchronize(h)).tolist())
    rows = np.full((r + 1, 2), float(r), np.float32)
    hg = C.allgather_async(rows, name="ag")
    outs.append(np.asarray(C.synchronize(hg)).tolist())
    return (r, outs)


@pytest.mark.integration
def test_mp_two_level_bitidentical_to_flat():
    """4 real processes as a synthetic 2-host × 2-rank topology: coordinated
    eager allreduce + ragged allgather produce bitwise-identical results on
    the two-level mesh and the flat mesh."""
    from horovod_tpu.run.api import run

    here = os.path.dirname(os.path.abspath(__file__))
    base = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": os.pathsep.join([os.path.dirname(here), here]),
    }
    hier = dict(base, HVD_UNIFORM_LOCAL_SIZE="2",
                HOROVOD_HIERARCHICAL_ALLREDUCE="1",
                HOROVOD_HIERARCHICAL_ALLGATHER="1")
    flat_res = dict(run(_mp_worker, np=4, env=base, start_timeout=240))
    hier_res = dict(run(_mp_worker, np=4, env=hier, start_timeout=240))
    assert set(flat_res) == set(hier_res) == {0, 1, 2, 3}
    for r in range(4):
        assert flat_res[r] == hier_res[r], f"rank {r} diverged"


def _mp_chain_worker():
    import numpy as np

    import horovod_tpu as hvd

    r = hvd.rank()
    # allgather result must be USABLE as input to a further collective in
    # multiprocess mode (fully addressable local copy, not a global array)
    g = hvd.allgather(np.full((r + 1, 2), float(r + 1), np.float32),
                      name="chain_g")
    s = hvd.allreduce(np.asarray(g) * 0 + np.asarray(g), name="chain_r",
                      op=hvd.Sum)
    # zero-width tail: gathered dim0 must come from negotiated sizes
    z = hvd.allgather(np.zeros((r + 2, 0), np.float32), name="chain_z")
    return (r, np.asarray(s).tolist(), list(np.asarray(z).shape))


@pytest.mark.integration
def test_mp_allgather_chains_and_zero_width():
    from horovod_tpu.run.api import run

    here = os.path.dirname(os.path.abspath(__file__))
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": os.pathsep.join([os.path.dirname(here), here]),
    }
    res = {r: (s, z) for r, s, z in
           run(_mp_chain_worker, np=2, env=env, start_timeout=240)}
    want = [[2.0, 2.0]] + [[4.0, 4.0]] * 2  # 2x the gathered rows
    for r in (0, 1):
        s, zshape = res[r]
        assert s == want, (r, s)
        assert zshape == [5, 0], (r, zshape)


def _fused_scaled_worker():
    r = hvd.rank()
    import ml_dtypes

    outs = []
    # several same-signature tensors in flight: the controller fuses them,
    # and the two-level kernel must unpack the fused buffer identically
    hs = [C.allreduce_async(np.full((64,), float(r + i), np.float32),
                            name=f"fz{i}", op=hvd.Sum) for i in range(4)]
    outs.append([float(np.asarray(C.synchronize(h))[0]) for h in hs])
    # prescale/postscale ride the decomposed path too
    h = C.allreduce_async(np.full((8,), float(r + 1), np.float32),
                          name="fz_scaled", op=hvd.Sum,
                          prescale_factor=2.0, postscale_factor=0.5)
    outs.append(float(np.asarray(C.synchronize(h))[0]))
    # bf16 wire dtype through pad/reduce_scatter/all_gather
    b = np.asarray([r + 1] * 24, ml_dtypes.bfloat16)
    h = C.allreduce_async(b, name="fz_bf16", op=hvd.Average)
    out = np.asarray(C.synchronize(h))
    outs.append((str(out.dtype), float(out.astype(np.float32)[0])))
    return outs


def test_two_level_fusion_scales_and_bf16(monkeypatch):
    """Fusion buckets, prescale/postscale and bf16 all flow through the
    hierarchical decomposition bit-identically to the flat mesh."""
    flat = _run_cluster_config(monkeypatch, hier=False,
                               worker=_fused_scaled_worker)
    hier = _run_cluster_config(monkeypatch, hier=True,
                               worker=_fused_scaled_worker)
    assert flat == hier
    # and the values are right: sum over ranks 0..7 of (r+i)
    for r_outs in hier:
        assert r_outs[0] == [28.0 + 8 * i for i in range(4)]
        assert r_outs[1] == 36.0  # 2.0 * sum(r+1) * 0.5
        dt, v = r_outs[2]
        assert dt == "bfloat16" and v == 4.5  # mean of 1..8
