"""Randomized control-plane fuzz: the negotiation machinery under chaotic
op mixes and per-rank timing skew.

The reference's race safety rests on design (single coordinator thread,
readiness counts); SURVEY §5 calls it "race detection by design". This fuzz
drives that design hard: every rank submits the same logical op sequence
(same seed) but with rank-dependent delays and interleaved async handles, so
arrival order at the controller is scrambled while program order stays
consistent. Every result is checked against numpy ground truth.
"""

import time

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import testing
from horovod_tpu.ops import collective_ops as C

WORLD = 4


_DTYPES = [np.float32, np.float64, np.int32]


def _gen_ops(seed, n_ops, world=WORLD):
    """Deterministic op schedule; identical on every rank."""
    rng = np.random.RandomState(seed)
    ops = []
    for i in range(n_ops):
        kind = rng.choice(["allreduce", "allgather", "broadcast",
                           "alltoall"])
        shape = tuple(int(x) for x in rng.randint(1, 5, rng.randint(1, 3)))
        if kind == "alltoall":
            # equal-split contract: dim0 divisible by world
            shape = (world * int(rng.randint(1, 3)),) + shape[1:]
        op = int(rng.choice([hvd.Sum, hvd.Average]))
        root = int(rng.randint(world))
        ragged = bool(rng.randint(2))
        dtype = _DTYPES[rng.randint(len(_DTYPES))]
        ops.append((i, kind, shape, op, root, ragged, dtype))
    return ops


def _a2av_splits(i, rank, world):
    """Deterministic uneven (incl. zero) splits for fuzz op i on `rank`."""
    return [(i + rank + d) % 3 for d in range(world)]


def _expected(ops, world):
    """Numpy ground truth for rank-dependent inputs full(shape, r+1+i)."""
    out = {}
    for i, kind, shape, op, root, ragged, dtype in ops:
        vals = [np.full(shape, r + 1 + i, dtype) for r in range(world)]
        if kind == "allreduce":
            s = np.sum(vals, axis=0)
            if op == hvd.Average:
                # integer Average floor-divides (engine int semantics)
                s = (s // world if np.issubdtype(dtype, np.integer)
                     else s / world)
            out[i] = s
        elif kind == "allgather":
            rows = [np.full(((r % 2 + 1) if ragged else shape[0],)
                            + shape[1:], r + 1 + i, dtype)
                    for r in range(world)]
            out[i] = np.concatenate(rows, axis=0)
        elif kind == "alltoall":
            if ragged:
                # alltoallv: src sends _a2av_splits(i, src)[dst] rows to dst
                out[i] = {dst: np.concatenate(
                    [np.full((_a2av_splits(i, src, world)[dst],)
                             + shape[1:], src + 1 + i, dtype)
                     for src in range(world)], axis=0)
                    for dst in range(world)}
            else:
                # each dst receives src's dst-th segment, concatenated by src
                seg = shape[0] // world
                out[i] = {dst: np.concatenate(
                    [vals[src][dst * seg:(dst + 1) * seg]
                     for src in range(world)], axis=0)
                    for dst in range(world)}
        else:
            out[i] = vals[root]
    return out


def _worker(seed, n_ops, world=WORLD):
    r = hvd.rank()
    ops = _gen_ops(seed, n_ops, world)
    delays = np.random.RandomState(seed * 1000 + r)
    handles = {}
    results = {}
    checked = 0
    for i, kind, shape, op, root, ragged, dtype in ops:
        if delays.rand() < 0.4:
            time.sleep(float(delays.rand()) * 0.01)
        x = np.full(shape, r + 1 + i, dtype)
        if kind == "allreduce":
            handles[i] = C.allreduce_async(x, name=f"fz{i}", op=op)
        elif kind == "allgather":
            rows = np.full(((r % 2 + 1) if ragged else shape[0],)
                           + shape[1:], r + 1 + i, dtype)
            handles[i] = C.allgather_async(rows, name=f"fz{i}")
        elif kind == "alltoall":
            if ragged:
                splits = _a2av_splits(i, r, world)
                xr = np.full((sum(splits),) + shape[1:], r + 1 + i, dtype)
                handles[i] = C.alltoall_async(xr, splits=splits,
                                              name=f"fz{i}")
            else:
                handles[i] = C.alltoall_async(x, name=f"fz{i}")
        else:
            handles[i] = C.broadcast_async(x, root, name=f"fz{i}")
        # randomly drain a pending handle mid-stream (its result is
        # validated like the rest)
        if handles and delays.rand() < 0.3:
            j = sorted(handles)[0]
            results[j] = _drain(C.synchronize(handles.pop(j)), j, r, world)
            checked += 1
    for i, h in handles.items():
        results[i] = _drain(C.synchronize(h), i, r, world)
    return (r, results, checked)


def _drain(res, i, r, world=WORLD):
    """Unwrap ragged alltoall results, asserting the negotiated
    received_splits are column r of the send matrix."""
    from horovod_tpu.runtime.messages import AlltoallvResult

    if isinstance(res, AlltoallvResult):
        assert list(res.received_splits) == \
            [_a2av_splits(i, src, world)[r] for src in range(world)], \
            f"op {i} rank {r}: wrong received_splits"
        return np.asarray(res.output)
    return np.asarray(res)


@pytest.mark.parametrize("seed", [7, 23, 91])
def test_fuzz_negotiation_under_timing_skew(seed):
    n_ops = 24
    res = testing.run_cluster(_worker, np=WORLD, args=(seed, n_ops))
    want = _expected(_gen_ops(seed, n_ops), WORLD)
    for r, results, _ in res:
        for i, got in results.items():
            w = want[i][r] if isinstance(want[i], dict) else want[i]
            np.testing.assert_allclose(
                got, w, rtol=1e-6,
                err_msg=f"seed {seed} rank {r} op {i}")


def _mp_fuzz_worker():
    return _worker(13, 18, world=2)


@pytest.mark.integration
def test_fuzz_coordinated_plane():
    """Same chaos through the RANK-0 coordinator (TCP exchange, wire codec,
    fusion, response cache) across 2 real processes."""
    import os

    from horovod_tpu.run.api import run

    here = os.path.dirname(os.path.abspath(__file__))
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "PYTHONPATH": os.pathsep.join([os.path.dirname(here), here]),
    }
    want = _expected(_gen_ops(13, 18, world=2), 2)
    res = run(_mp_fuzz_worker, np=2, env=env, start_timeout=240)
    for r, results, _ in res:
        assert len(results) == 18
        for i, got in results.items():
            w = want[i][r] if isinstance(want[i], dict) else want[i]
            np.testing.assert_allclose(got, w, rtol=1e-6,
                                       err_msg=f"rank {r} op {i}")
