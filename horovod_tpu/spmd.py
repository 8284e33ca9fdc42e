"""SPMD fast path: collectives *inside* a jitted step over the device mesh.

This is the performance path that replaces the reference's whole background
engine for training loops: where Horovod's `DistributedOptimizer` enqueues one
NCCL allreduce per gradient tensor with 64 MB fusion
(`horovod/torch/__init__.py:115-169`, `nccl_operations.cc:55-105`), here the
entire train step — forward, backward, gradient averaging, optimizer update —
is ONE compiled XLA program over the replica mesh. XLA schedules the gradient
all-reduces on ICI, overlaps them with the backward pass (latency-hiding
scheduler), and fuses the optimizer update; there is nothing left to negotiate
at runtime. This is the design stance from SURVEY.md §7: negotiation machinery
for the eager path, static scheduling for the hot path.

Two usage levels:

1. Collective primitives with the ``"hvd"`` axis for custom ``shard_map`` code:
   ``spmd.allreduce/allgather/alltoall/broadcast/...``
2. Whole-step builders: ``make_train_step(loss_fn, tx)`` returns a jitted
   data-parallel step with batch sharded over replicas and params replicated.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import basics
from .basics import MESH_AXIS, Adasum, Average, Sum


# --------------------------------------------------------- in-jit primitives
def allreduce(x, op: int = Average, axis: str = MESH_AXIS):
    """Collective reduce across the replica axis; call inside shard_map/pmap.

    TPU-native form of `EnqueueTensorAllreduce` (`operations.cc:783`) for code
    already running under SPMD.
    """
    if op == Adasum:
        return adasum(x, axis=axis)
    s = jax.lax.psum(x, axis)
    if op == Average:
        n = jax.lax.psum(jnp.ones((), jnp.int32), axis)
        if jnp.issubdtype(s.dtype, jnp.integer):
            s = s // n.astype(s.dtype)  # match eager engine int semantics
        else:
            s = s / n.astype(s.dtype)
    return s


def pmean(x, axis: str = MESH_AXIS):
    return jax.lax.pmean(x, axis)


def allgather(x, axis: str = MESH_AXIS):
    return jax.lax.all_gather(x, axis, tiled=True)


def alltoall(x, axis: str = MESH_AXIS, split_axis: int = 0, concat_axis: int = 0):
    return jax.lax.all_to_all(x, axis, split_axis, concat_axis, tiled=True)


def broadcast(x, root_rank: int, axis: str = MESH_AXIS):
    """Every replica receives replica ``root_rank``'s value."""
    idx = jax.lax.axis_index(axis)
    masked = jnp.where(idx == root_rank, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axis)


def reduce_scatter(x, axis: str = MESH_AXIS, scatter_axis: int = 0):
    return jax.lax.psum_scatter(x, axis, scatter_dimension=scatter_axis,
                                tiled=True)


def allreduce_sparse(values, indices, op: int = Average, axis: str = MESH_AXIS):
    """In-jit sparse allreduce (`tensorflow/__init__.py:75-91` rebuilt for
    SPMD): allgather rows + indices instead of reducing the dense tensor.

    Unlike the eager engine path (`ops.sparse.allreduce_sparse`, ragged dim0
    negotiated at runtime), XLA requires a static, equal per-device row count
    — pad with a sentinel row (e.g. index 0, zero values) to equalize.
    Returns ``(gathered_values [n*k, ...], gathered_indices [n*k])``; apply
    with scatter-add, duplicates accumulate.
    """
    if op == Adasum:
        raise NotImplementedError(
            "Adasum does not support sparse tensors; densify first")
    g_values = jax.lax.all_gather(values, axis, tiled=True)
    g_indices = jax.lax.all_gather(indices, axis, tiled=True)
    if op == Average:
        n = jax.lax.psum(jnp.ones((), jnp.int32), axis)
        if jnp.issubdtype(g_values.dtype, jnp.integer):
            g_values = g_values // n.astype(g_values.dtype)
        else:
            g_values = g_values / n.astype(g_values.dtype)
    return g_values, g_indices


def adasum(x, axis: str = MESH_AXIS):
    """Adasum combine across the replica axis inside SPMD code.

    Pairwise tree as in `adasum/adasum.h:185-331`: at level k, partners are
    distance 2^k apart; coefficients from psum'd dots/norms restricted to each
    pair. Implemented via all_gather + local tree (replica count is static).
    After the gather the tree is device-local math, so each pairwise combine
    runs as the fused Pallas dot+norm+apply kernel
    (`ops/pallas_kernels.adasum_combine`) when enabled — the TPU analogue of
    the reference's SSE/AVX fused loops (`adasum/adasum.h:98-131`) — with the
    vectorized-jnp tree as fallback (zero-padding to lane width is exact:
    zeros contribute nothing to dot or norms).
    """
    from .ops import pallas_kernels as _pk

    g = jax.lax.all_gather(x, axis)  # [n, ...]
    n = g.shape[0]
    if n & (n - 1):
        raise ValueError("Adasum requires a power-of-2 replica count "
                         "(parity: torch/mpi_ops.py:104-120)")
    flat = g.reshape(n, -1).astype(jnp.float32)
    pad = (-flat.shape[1]) % 128
    padded = jnp.pad(flat, ((0, 0), (0, pad))) if pad else flat
    if _pk.kernel_path("adasum_combine", padded, padded) == "pallas":
        while padded.shape[0] > 1:  # one batched launch per tree level
            padded = _pk.adasum_combine_pairs(padded[0::2], padded[1::2])
        return padded[0, :flat.shape[1]].reshape(x.shape).astype(x.dtype)
    while flat.shape[0] > 1:
        a, b = flat[0::2], flat[1::2]
        dot = jnp.sum(a * b, axis=1, keepdims=True)
        na = jnp.sum(a * a, axis=1, keepdims=True)
        nb = jnp.sum(b * b, axis=1, keepdims=True)
        ac = jnp.where(na == 0, 1.0, 1.0 - dot / (2 * jnp.where(na == 0, 1.0, na)))
        bc = jnp.where(nb == 0, 1.0, 1.0 - dot / (2 * jnp.where(nb == 0, 1.0, nb)))
        flat = ac * a + bc * b
    return flat[0].reshape(x.shape).astype(x.dtype)


# ------------------------------------------- quantized ring (GSPMD wire)
# The EQuARX move (PAPERS.md arXiv:2506.17615): quantized allreduce INSIDE
# the compiled program. The same ppermute ring as `matmul_reduce_scatter`
# above, but every hop ships the fused int8/int4 quantize+pack rows from
# `ops/pallas_kernels.py` instead of raw f32 — the PR 10 wire footprints
# (int4 = 50.8% of int8 bytes) finally applied to the GSPMD plane, which
# until now moved raw bf16/f32 while all the bandwidth wins sat on the
# coordinator path. See docs/gspmd.md.

_GSPMD_WIRES = ("int8", "int4")


def gspmd_wire(value: Optional[str] = None) -> str:
    """Resolve the compiled-path wire mode (``HOROVOD_GSPMD_WIRE``).

    Returns ``""`` (wire off — the exact GSPMD program), ``"int8"`` or
    ``"int4"``. ``value`` overrides the env var (the
    ``make_train_step(compression=...)`` argument). int4 must be admitted
    by the PR 10 ``ConvergenceGate`` first — a refused gate downgrades to
    int8 rather than risking the 4-bit grid on a model the deterministic
    A/B harness couldn't converge (`ops/adaptive.py`).
    """
    v = os.environ.get("HOROVOD_GSPMD_WIRE", "") if value is None else value
    v = (v or "").strip().lower()
    if v in ("", "0", "off", "none"):
        return ""
    if v not in _GSPMD_WIRES:
        raise ValueError(
            f"HOROVOD_GSPMD_WIRE must be int8|int4|off, got {v!r}")
    from .ops.adaptive import admit_wire

    return admit_wire(v)


def _wire_block(block: Optional[int]) -> int:
    from .ops import compression as comp

    return int(block or comp.block_size())


def _pack_fns(wire: str):
    from .ops import pallas_kernels as pk

    if wire == "int4":
        return pk.int4_quantize_pack, pk.int4_unpack
    return pk.int8_quantize_pack, pk.int8_unpack


def _ring_chunk(num_elements: int, world: int, block: int) -> int:
    """Per-rank chunk length: ceil(n/world) rounded up to whole blocks, so
    every hop's packed rows are [chunk//block, block+scale] with no ragged
    tail inside the ring."""
    per_rank = -(-num_elements // world)
    return -(-per_rank // block) * block


def _wire_eligible(num_elements: int, dtype, wire: str, block: int) -> bool:
    """Static (trace-time) gate for the quantized path: float payload, at
    least one quantization block (below that the scale overhead and ring
    latency beat the savings — the HOROVOD_COMPRESSION_MIN_SIZE rationale),
    and an even block for the int4 nibble split."""
    return (wire in _GSPMD_WIRES
            and jnp.issubdtype(dtype, jnp.floating)
            and num_elements >= block
            and not (wire == "int4" and block % 2))


def quantized_reduce_scatter(x, axis: str = MESH_AXIS, wire: str = "int8",
                             block: Optional[int] = None):
    """Ring reduce-scatter with a quantized wire; call inside shard_map.

    ``x`` is this rank's local contribution (any float shape; flattened and
    zero-padded to ``world * chunk`` with ``chunk = _ring_chunk(...)``).
    Returns the 1-D f32 chunk of the cross-rank sum this rank owns (global
    chunk ``p`` of the padded flat sum). Rank p seeds its accumulator with
    local chunk (p-1) mod m; each of the m-1 hops quantize+packs the
    accumulator ([rows, block] -> [rows, block+4] int8 rows, or the int4
    half-split nibble rows), rotates the packed bytes one rank forward via
    ppermute, dequantizes, and adds the local chunk (p-k-1) mod m — so
    after the last hop rank p holds chunk p summed over every rank, and
    every hop moved packed bytes instead of raw f32. ``wire`` values
    outside int8/int4 run the identical ring schedule with raw f32 hops
    (the exact-wire reference).
    """
    m = jax.lax.psum(1, axis)
    block = _wire_block(block)
    flat = jnp.ravel(x).astype(jnp.float32)
    num = flat.shape[0]
    if wire in _GSPMD_WIRES:
        chunk = _ring_chunk(num, m, block)
    else:
        chunk = -(-num // m)
    pad = m * chunk - num
    if pad:
        flat = jnp.pad(flat, (0, pad))
    if m == 1:
        return flat
    p = jax.lax.axis_index(axis)

    def local_chunk(k):
        idx = jnp.mod(p - k - 1, m)
        return jax.lax.dynamic_slice_in_dim(flat, idx * chunk, chunk)

    perm = [(j, (j + 1) % m) for j in range(m)]
    acc = local_chunk(0)
    if wire not in _GSPMD_WIRES:
        for k in range(1, m):
            acc = jax.lax.ppermute(acc, axis, perm) + local_chunk(k)
        return acc
    pack, unpack = _pack_fns(wire)
    for k in range(1, m):
        wired = jax.lax.ppermute(pack(acc.reshape(-1, block)), axis, perm)
        q, scales = unpack(wired)
        acc = (q.astype(jnp.float32) * scales).reshape(-1) + local_chunk(k)
    return acc


def quantized_all_gather(chunk, axis: str = MESH_AXIS, wire: str = "int8",
                         block: Optional[int] = None):
    """Ring all-gather of per-rank 1-D chunks with a quantized wire.

    Each rank quantize+packs its own chunk ONCE and the packed bytes make
    m-1 hops around the ring; every rank — including the owner —
    reconstructs each chunk from the same packed rows, so the gathered
    [m * chunk] result is bit-identical on every rank (the property the
    replicated-params invariant rests on). ``wire`` outside int8/int4
    falls back to the exact tiled all_gather.
    """
    m = jax.lax.psum(1, axis)
    flat = jnp.ravel(chunk).astype(jnp.float32)
    if m == 1:
        return flat
    if wire not in _GSPMD_WIRES:
        return jax.lax.all_gather(flat, axis, tiled=True)
    block = _wire_block(block)
    num = flat.shape[0]
    pad = (-num) % block
    padded = jnp.pad(flat, (0, pad)) if pad else flat
    pack, unpack = _pack_fns(wire)
    p = jax.lax.axis_index(axis)
    perm = [(j, (j + 1) % m) for j in range(m)]
    cur = pack(padded.reshape(-1, block))
    out = jnp.zeros((m * num,), jnp.float32)
    for k in range(m):
        q, scales = unpack(cur)
        val = (q.astype(jnp.float32) * scales).reshape(-1)[:num]
        idx = jnp.mod(p - k, m)
        out = jax.lax.dynamic_update_slice_in_dim(out, val, idx * num, 0)
        if k + 1 < m:
            cur = jax.lax.ppermute(cur, axis, perm)
    return out


def quantized_allreduce(x, op: int = Average, axis: str = MESH_AXIS,
                        wire: Optional[str] = None,
                        block: Optional[int] = None):
    """Allreduce whose wire rides the quantized ring; call inside shard_map.

    Composition of :func:`quantized_reduce_scatter` and
    :func:`quantized_all_gather`: every hop of both phases moves int8/int4
    packed rows, so the whole reduction costs the PR 10 wire footprints
    inside the compiled program. The result is bit-identical on every rank
    (averaging divides the identical gathered sum). Falls back to the
    exact :func:`allreduce` when the wire is off, the payload is not
    floating-point, or the flat size is under one quantization block
    (non-lane-aligned / tiny tensors — see ``_wire_eligible``).

    ``wire=None`` resolves ``HOROVOD_GSPMD_WIRE`` at trace time
    (:func:`gspmd_wire`, including the int4 convergence-gate admission).
    """
    wire = gspmd_wire(wire)
    if op == Adasum:
        raise NotImplementedError(
            "the quantized GSPMD wire does not support Adasum; use "
            "spmd.adasum (exact) instead")
    block = _wire_block(block)
    if not _wire_eligible(x.size, x.dtype, wire, block):
        return allreduce(x, op, axis)
    m = jax.lax.psum(1, axis)
    chunk = quantized_reduce_scatter(x, axis, wire, block)
    flat = quantized_all_gather(chunk, axis, wire, block)[:x.size]
    if op == Average:
        flat = flat / m
    return flat.reshape(x.shape).astype(x.dtype)


# ------------------------------------------------ algorithm zoo (autotune v3)
# The flat bidirectional ring above is bandwidth-optimal but pays world-1
# latency rounds; the MPI characterization study (PAPERS.md arXiv:1810.11112)
# and the reference's hierarchical allreduce (operations.cc:440-454) both
# show the winning algorithm is a function of payload size x world size x
# topology. The zoo: "ring" (above), "tree" (recursive halving/doubling,
# O(log w) rounds — latency-optimal for small payloads), "hier" (intra-host
# reduce-scatter -> cross-host allreduce -> intra-host all-gather over a
# (host, chip) factorization). Every member rides the same packed int8/int4
# rows, the same EF-residual convention and the same _wire_eligible exact
# fallbacks as the ring. See docs/autotune.md.

_GSPMD_ALGOS = ("ring", "tree", "hier", "auto")

#: payloads at or under this many f32 elements (256 KB) are latency-bound
#: on the flat ring — the "auto" tree/ring crossover before any tuner
#: measurement arrives
_TREE_AUTO_MAX = 1 << 16


def gspmd_algo(value: Optional[str] = None) -> str:
    """Resolve the compiled-path collective algorithm (``HOROVOD_GSPMD_ALGO``).

    Returns ``"ring"`` (the default — byte-identical to the pre-zoo
    program), ``"tree"``, ``"hier"`` or ``"auto"``. ``value`` overrides the
    env var (the ``make_train_step(algorithm=...)`` argument)."""
    v = os.environ.get("HOROVOD_GSPMD_ALGO", "") if value is None else value
    v = (v or "").strip().lower()
    if v in ("", "0", "off", "none"):
        return "ring"
    if v not in _GSPMD_ALGOS:
        raise ValueError(
            f"HOROVOD_GSPMD_ALGO must be ring|tree|hier|auto, got {v!r}")
    return v


def mesh_hosts(world: int) -> int:
    """``(host, chip)`` factorization for the hierarchical allreduce.

    ``HOROVOD_MESH_HOSTS`` pins the host count (it must divide the world
    size — the launcher's host-major rank numbering is assumed, rank =
    host * chips + chip, matching the executor's ("dcn","ici") mesh).
    Unset auto-factorizes: the largest divisor of ``world`` at most
    sqrt(world), so 8 -> 2x4, 16 -> 4x4; 1 (no factorization, ring
    fallback) when ``world`` is prime."""
    v = os.environ.get("HOROVOD_MESH_HOSTS", "").strip()
    if v:
        hosts = int(v)
        if hosts < 1 or world % hosts:
            raise ValueError(
                f"HOROVOD_MESH_HOSTS={hosts} does not divide the world "
                f"size {world} (host-major rank numbering needs "
                f"world = hosts * chips)")
        return hosts
    hosts, d = 1, 2
    while d * d <= world:
        if world % d == 0:
            hosts = d
        d += 1
    return hosts


def resolve_algorithm(total: int, world: int,
                      algorithm: Optional[str] = None) -> str:
    """Effective zoo member for one payload of ``total`` f32 elements.

    Explicit choices pass through; ``"auto"`` follows the coordinator's
    tuned broadcast when one has arrived
    (`ops/adaptive.set_autotuned_algorithm`, shipped as the fourth tuned
    ``ResponseList`` field) and otherwise the static heuristic: small
    payloads ride the tree when the world is a power of two, multi-host
    factorizations ride the hierarchical schedule, everything else the
    ring."""
    a = gspmd_algo(algorithm)
    if a != "auto":
        return a
    from .ops.adaptive import autotuned_algorithm

    tuned = autotuned_algorithm()
    if tuned:
        return tuned
    if total <= _TREE_AUTO_MAX and world & (world - 1) == 0 and world > 1:
        return "tree"
    if mesh_hosts(world) > 1:
        return "hier"
    return "ring"


def _ring_reduce_scatter(flat, axis: str, wire: str, block: int,
                         size: int, pos, perm):
    """Ring reduce-scatter over a sub-ring of ``size`` members embedded in
    ``axis``: ``pos`` is this rank's (traced) position on its ring and
    ``perm`` the global ppermute rotating every sub-ring one step forward
    in parallel. ``flat`` is the 1-D f32 local contribution, already
    padded to ``size * chunk``; returns the summed chunk position ``pos``
    owns — the same schedule as :func:`quantized_reduce_scatter`, just
    with ring geometry supplied by the caller."""
    chunk = flat.shape[0] // size
    if size == 1:
        return flat

    def local_chunk(k):
        idx = jnp.mod(pos - k - 1, size)
        return jax.lax.dynamic_slice_in_dim(flat, idx * chunk, chunk)

    acc = local_chunk(0)
    if wire not in _GSPMD_WIRES:
        for k in range(1, size):
            acc = jax.lax.ppermute(acc, axis, perm) + local_chunk(k)
        return acc
    pack, unpack = _pack_fns(wire)
    for k in range(1, size):
        wired = jax.lax.ppermute(pack(acc.reshape(-1, block)), axis, perm)
        q, scales = unpack(wired)
        acc = (q.astype(jnp.float32) * scales).reshape(-1) + local_chunk(k)
    return acc


def _ring_all_gather(chunk, axis: str, wire: str, block: int,
                     size: int, pos, perm):
    """Ring all-gather over a sub-ring (geometry as in
    :func:`_ring_reduce_scatter`). The owner packs its chunk once and the
    packed rows (raw f32 on an exact wire) make ``size - 1`` hops
    unchanged, so every ring member reconstructs each chunk from identical
    bytes — the bit-identity property of :func:`quantized_all_gather`."""
    num = chunk.shape[0]
    if size == 1:
        return chunk
    out = jnp.zeros((size * num,), jnp.float32)
    if wire not in _GSPMD_WIRES:
        cur = chunk
        for k in range(size):
            idx = jnp.mod(pos - k, size)
            out = jax.lax.dynamic_update_slice_in_dim(out, cur, idx * num, 0)
            if k + 1 < size:
                cur = jax.lax.ppermute(cur, axis, perm)
        return out
    pack, unpack = _pack_fns(wire)
    pad = (-num) % block
    padded = jnp.pad(chunk, (0, pad)) if pad else chunk
    cur = pack(padded.reshape(-1, block))
    for k in range(size):
        q, scales = unpack(cur)
        val = (q.astype(jnp.float32) * scales).reshape(-1)[:num]
        idx = jnp.mod(pos - k, size)
        out = jax.lax.dynamic_update_slice_in_dim(out, val, idx * num, 0)
        if k + 1 < size:
            cur = jax.lax.ppermute(cur, axis, perm)
    return out


def quantized_allreduce_tree(x, op: int = Average, axis: str = MESH_AXIS,
                             wire: Optional[str] = None,
                             block: Optional[int] = None):
    """Recursive-halving/doubling allreduce — O(log w) rounds, the
    latency-optimal zoo member for small payloads; call inside shard_map.

    Reduce phase: log2(w) recursive-halving exchanges at distances w/2,
    w/4, ..., 1. Each round partners ``p`` and ``p ^ d`` split the active
    window ("bit set keeps the upper half"), ship the half the partner
    keeps — packed int8/int4 rows on a quantized wire, raw f32 otherwise —
    and add; after the last round rank ``p`` owns the fully summed chunk
    ``p``, the same ownership convention as the ring. Gather phase: log2(w)
    recursive-doubling exchanges of *packed bytes*: each chunk is
    quantized once by its owner and forwarded verbatim, so every rank
    decodes identical bytes and the result is bit-identical everywhere
    (the :func:`quantized_all_gather` property).

    Falls back to the ring (:func:`quantized_allreduce`) on
    non-power-of-two worlds — the halving recursion needs 2^k members —
    and to the exact :func:`allreduce` for payloads the wire cannot carry
    (:func:`_wire_eligible`) or non-float dtypes.
    """
    wire = gspmd_wire(wire)
    if op == Adasum:
        raise NotImplementedError(
            "the GSPMD tree allreduce does not support Adasum; use "
            "spmd.adasum (exact) instead")
    block = _wire_block(block)
    m = jax.lax.psum(1, axis)
    if m & (m - 1) or m == 1:
        return quantized_allreduce(x, op, axis, wire, block)
    if wire in _GSPMD_WIRES and not _wire_eligible(x.size, x.dtype, wire,
                                                   block):
        return allreduce(x, op, axis)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return allreduce(x, op, axis)
    num = x.size
    quant = wire in _GSPMD_WIRES
    chunk = _ring_chunk(num, m, block) if quant else -(-num // m)
    flat = jnp.ravel(x).astype(jnp.float32)
    pad = m * chunk - num
    if pad:
        flat = jnp.pad(flat, (0, pad))
    p = jax.lax.axis_index(axis)
    rounds = int(m).bit_length() - 1
    if quant:
        pack, unpack = _pack_fns(wire)
    # recursive halving: every window half is a whole number of chunks,
    # hence (quantized) a whole number of blocks — no ragged rows
    win = flat
    for k in range(rounds):
        d = m >> (k + 1)
        half = win.shape[0] // 2
        bit = jnp.equal((p // d) % 2, 1)
        lower, upper = win[:half], win[half:]
        keep = jnp.where(bit, upper, lower)
        send = jnp.where(bit, lower, upper)
        perm = [(j, j ^ d) for j in range(m)]
        if quant:
            wired = jax.lax.ppermute(pack(send.reshape(-1, block)), axis,
                                     perm)
            q, scales = unpack(wired)
            recv = (q.astype(jnp.float32) * scales).reshape(-1)
        else:
            recv = jax.lax.ppermute(send, axis, perm)
        win = keep + recv
    # recursive doubling: forward the owner-packed rows verbatim so every
    # rank decodes the same bytes (bit-identity)
    if quant:
        rows = chunk // block
        packed = pack(win.reshape(-1, block))
        buf = jnp.zeros((m * rows, packed.shape[1]), packed.dtype)
        buf = jax.lax.dynamic_update_slice_in_dim(buf, packed, p * rows, 0)
        for k in range(rounds):
            d = 1 << k
            lo = (p // d) * d
            seg = jax.lax.dynamic_slice_in_dim(buf, lo * rows, d * rows)
            perm = [(j, j ^ d) for j in range(m)]
            recv = jax.lax.ppermute(seg, axis, perm)
            buf = jax.lax.dynamic_update_slice_in_dim(buf, recv,
                                                      (lo ^ d) * rows, 0)
        q, scales = unpack(buf)
        out = (q.astype(jnp.float32) * scales).reshape(-1)[:num]
    else:
        buf = jnp.zeros((m * chunk,), jnp.float32)
        buf = jax.lax.dynamic_update_slice_in_dim(buf, win, p * chunk, 0)
        for k in range(rounds):
            d = 1 << k
            lo = (p // d) * d
            seg = jax.lax.dynamic_slice_in_dim(buf, lo * chunk, d * chunk)
            perm = [(j, j ^ d) for j in range(m)]
            recv = jax.lax.ppermute(seg, axis, perm)
            buf = jax.lax.dynamic_update_slice_in_dim(buf, recv,
                                                      (lo ^ d) * chunk, 0)
        out = buf[:num]
    if op == Average:
        out = out / m
    return out.reshape(x.shape).astype(x.dtype)


def quantized_allreduce_hier(x, op: int = Average, axis: str = MESH_AXIS,
                             wire: Optional[str] = None,
                             block: Optional[int] = None,
                             hosts: Optional[int] = None):
    """2-level hierarchical allreduce over a ``(host, chip)`` factorization
    of the replica axis; call inside shard_map.

    The reference's NCCLHierarchicalAllreduce decomposition
    (`operations.cc:440-454`) on the packed wire: intra-host ring
    reduce-scatter (chips on one host talk over ICI), cross-host allreduce
    of each owned chunk — every chip is the representative for the chunk
    it owns, riding a host-ring reduce-scatter + all-gather that only
    crosses hosts — then intra-host ring all-gather. Both gather phases
    forward owner-packed bytes verbatim and the phase-2 result is
    bit-identical across hosts, so the final result is bit-identical on
    every rank. Cross-host traffic shrinks from the flat ring's
    ``2(w-1)`` chunk exchanges per boundary edge to the phase-2 rows alone
    (`ops/compression.gspmd_cross_host_footprint`).

    ``hosts`` defaults to :func:`mesh_hosts` (``HOROVOD_MESH_HOSTS`` or the
    auto factorization); rank numbering is host-major (rank = host * chips
    + chip), matching the executor's ("dcn","ici") mesh. Falls back to the
    flat ring when the factorization is degenerate (hosts <= 1, hosts ==
    world, or world % hosts != 0) and to the exact :func:`allreduce` for
    payloads the wire cannot carry.
    """
    wire = gspmd_wire(wire)
    if op == Adasum:
        raise NotImplementedError(
            "the GSPMD hierarchical allreduce does not support Adasum; "
            "use spmd.adasum (exact) instead")
    block = _wire_block(block)
    m = jax.lax.psum(1, axis)
    h = mesh_hosts(m) if hosts is None else int(hosts)
    if h <= 1 or h >= m or m % h:
        return quantized_allreduce(x, op, axis, wire, block)
    if wire in _GSPMD_WIRES and not _wire_eligible(x.size, x.dtype, wire,
                                                   block):
        return allreduce(x, op, axis)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return allreduce(x, op, axis)
    num = x.size
    c = m // h  # chips per host
    quant = wire in _GSPMD_WIRES
    chunk = _ring_chunk(num, c, block) if quant else -(-num // c)
    flat = jnp.ravel(x).astype(jnp.float32)
    pad = c * chunk - num
    if pad:
        flat = jnp.pad(flat, (0, pad))
    p = jax.lax.axis_index(axis)
    hp, l = p // c, p % c  # (host, chip) of this rank, host-major
    intra = [(j, (j // c) * c + ((j % c) + 1) % c) for j in range(m)]
    inter = [(j, (((j // c) + 1) % h) * c + (j % c)) for j in range(m)]
    # phase 1: intra-host reduce-scatter — chip l ends with chunk l of the
    # host-local sum
    chunk_l = _ring_reduce_scatter(flat, axis, wire, block, c, l, intra)
    # phase 2: cross-host allreduce of chunk l among the h chips sharing
    # local index l (RS + AG over the host ring — the only phase whose
    # bytes cross a host boundary)
    sub = _ring_chunk(chunk, h, block) if quant else -(-chunk // h)
    pad2 = h * sub - chunk
    if pad2:
        chunk_l = jnp.pad(chunk_l, (0, pad2))
    owned = _ring_reduce_scatter(chunk_l, axis, wire, block, h, hp, inter)
    chunk_g = _ring_all_gather(owned, axis, wire, block, h, hp,
                               inter)[:chunk]
    # phase 3: intra-host all-gather of the globally reduced chunks
    out = _ring_all_gather(chunk_g, axis, wire, block, c, l, intra)[:num]
    if op == Average:
        out = out / m
    return out.reshape(x.shape).astype(x.dtype)


def _wire_roundtrip(flat, wire: str, block: int):
    """The value one quantized hop delivers for a local contribution — the
    EF-SGD numerator, same absmax/qmax block math as
    ``ops/compression.py quantize_blocks`` (pure: no metric side effects,
    safe inside the traced step)."""
    from .ops import compression as comp

    num = flat.shape[0]
    pad = (-num) % block
    padded = jnp.pad(flat, (0, pad)) if pad else flat
    q, scales = comp.quantize_blocks(padded, block,
                                     bits=4 if wire == "int4" else 8)
    return comp.dequantize_blocks(q, scales, jnp.float32, block)[:num]


# --------------------------------------------------- quantized all_to_all
def _a2a_roundtrip(flat, wire: str, block: int):
    """EF numerator for one quantized all_to_all: the value the packed wire
    delivers for this rank's ``[m, per]`` payload, with the same per-peer
    padded block layout as the forward pack (each peer's segment pads to
    whole blocks independently, so no block ever mixes two peers' data).
    Pure ``comp.quantize_blocks`` math — safe inside the traced step."""
    from .ops import compression as comp

    m, per = flat.shape
    pad = (-per) % block
    padded = jnp.pad(flat, ((0, 0), (0, pad))) if pad else flat
    q, scales = comp.quantize_blocks(padded.reshape(-1), block,
                                     bits=4 if wire == "int4" else 8)
    out = comp.dequantize_blocks(q, scales, jnp.float32, block)
    return out.reshape(m, per + pad)[:, :per]


def _a2a_wired(x, axis: str, wire: str, block: int):
    """One quantized all_to_all exchange (forward value only): pad each
    destination peer's payload to whole blocks, quantize+pack through the
    fused kernels, move the packed int8 rows, unpack+dequantize on
    arrival. The packed rows keep their [rows, row_bytes] shape through
    the exchange because each peer's row count is identical."""
    m = jax.lax.psum(1, axis)
    per = x.size // m
    flat = x.reshape(m, per).astype(jnp.float32)
    pad = (-per) % block
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    pack, unpack = _pack_fns(wire)
    packed = pack(flat.reshape(-1, block))
    wired = jax.lax.all_to_all(packed, axis, 0, 0, tiled=True)
    q, scales = unpack(wired)
    vals = (q.astype(jnp.float32) * scales).reshape(m, per + pad)[:, :per]
    return vals.reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _st_all_to_all(x, axis, wire, block):
    return _a2a_wired(x, axis, wire, block)


def _st_fwd(x, axis, wire, block):
    return _a2a_wired(x, axis, wire, block), None


def _st_bwd(axis, wire, block, _res, g):
    # Straight-through: the quantizer is gradient-dead (jnp.round), so the
    # cotangent rides the exact wire. A dim-0 tiled all_to_all is its own
    # transpose, so this IS the true adjoint of the exchange itself — only
    # the quantization nonlinearity is bypassed.
    return (jax.lax.all_to_all(g, axis, 0, 0, tiled=True),)


_st_all_to_all.defvjp(_st_fwd, _st_bwd)


def quantized_all_to_all(x, axis: str = MESH_AXIS, wire: str = "int8",
                         block: Optional[int] = None, ef=None):
    """all_to_all over ``axis`` whose payload rides the packed wire; call
    inside shard_map (the MoE token exchange — docs/moe.md).

    ``x`` is the local ``[L, ...]`` operand with dim 0 indexing destination
    peers in ``L / world`` row groups (``jax.lax.all_to_all`` split/concat
    dim 0, tiled). Each peer's payload pads independently to whole
    quantization blocks and quantize+packs through the fused kernels into
    ``[payload | 4 f32-scale bytes]`` rows; only the packed int8 bytes
    cross the wire, and receivers dequantize. Eligibility mirrors the ring
    (:func:`_wire_eligible` on the per-peer element count): non-float
    payloads, payloads under one block, or an odd block under int4 ride
    the exact all_to_all instead.

    Gradients are straight-through: the backward pass ships the cotangent
    over an *exact* all_to_all, which is the true adjoint of the exchange
    (a dim-0 all_to_all is its own transpose); only the gradient-dead
    quantizer is bypassed.

    ``ef`` (f32, same shape as ``x``) engages EF-SGD error feedback: the
    residual from the previous exchange in this direction is added before
    quantization, and the new residual ``corrected - wire(corrected)``
    comes back to be banked — one leaf per exchange direction, like the
    PR 13 optimizer-state leaf. With ``ef`` given the return is
    ``(y, new_ef)``; otherwise just ``y``.
    """
    m = jax.lax.psum(1, axis)
    if x.shape[0] % m:
        raise ValueError(
            f"all_to_all dim 0 ({x.shape[0]}) not divisible by axis size "
            f"{m}")
    block = _wire_block(block)
    per = x.size // m
    if m == 1 or not _wire_eligible(per, x.dtype, wire, block):
        y = jax.lax.all_to_all(x, axis, 0, 0, tiled=True)
        return (y, jnp.zeros(x.shape, jnp.float32)) if ef is not None else y
    corrected = x.astype(jnp.float32)
    if ef is not None:
        corrected = corrected + jax.lax.stop_gradient(
            ef.astype(jnp.float32))
    y = _st_all_to_all(corrected, axis, wire, block).astype(x.dtype)
    if ef is None:
        return y
    flat = jax.lax.stop_gradient(corrected).reshape(m, per)
    new_ef = (flat - _a2a_roundtrip(flat, wire, block)).reshape(x.shape)
    return y, new_ef


# ------------------------------------------------------------ whole-step API
def replica_mesh() -> Mesh:
    return basics.mesh()


def batch_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    """Shard dim 0 (batch) across replicas."""
    return NamedSharding(mesh or basics.mesh(), P(MESH_AXIS))


def replicated_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    return NamedSharding(mesh or basics.mesh(), P())


def shard_batch(batch, mesh: Optional[Mesh] = None):
    """Place a host batch onto the mesh, sharded along dim 0."""
    sh = batch_sharding(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), batch)


def replicate(tree, mesh: Optional[Mesh] = None):
    """Replicate params/optimizer state across the mesh (the SPMD analogue of
    `broadcast_parameters`: every replica holds identical values)."""
    sh = replicated_sharding(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)


def make_train_step(loss_fn: Callable, tx, mesh: Optional[Mesh] = None,
                    donate: bool = True, zero1: bool = False,
                    example_opt_state=None,
                    compression: Optional[str] = None,
                    algorithm: Optional[str] = None) -> Callable:
    """Build the jitted data-parallel train step (the bench hot loop).

    ``loss_fn(params, batch) -> scalar loss`` computed on the *local* shard
    of the batch (dim 0 of every leaf is split over the replica axis); the
    step averages loss and gradients across replicas. ``tx`` is an optax
    GradientTransformation. Returns
    ``step(params, opt_state, batch) -> (params, opt_state, loss)``.

    Over more than one device the loss-and-gradient half runs as a
    ``shard_map`` with an explicit ``pmean``: a Pallas kernel inside
    ``loss_fn`` (the model's default flash attention) is a custom call the
    partitioner cannot split ("Mosaic kernels cannot be automatically
    partitioned"), while under ``shard_map`` every chip runs it on its own
    batch shard. The optimizer update stays under GSPMD, so ``zero1``'s
    output shardings apply as before. On a one-device mesh there is
    nothing to partition and the step is the plain jit it always was.

    ``zero1=True`` shards the optimizer state 1/N over the replica axis
    (`optim/zero.py`): pass ``example_opt_state`` (an abstract or concrete
    ``tx.init(params)`` pytree) so the per-leaf shardings can be derived,
    and place the live state with :func:`optim.zero.shard_opt_state` before
    the first call.

    ``compression`` selects the quantized GSPMD wire (``"int8"``/``"int4"``;
    ``None`` resolves ``HOROVOD_GSPMD_WIRE``, ``"off"`` forces the exact
    wire). When a wire engages, the step runs as an explicit shard_map
    program whose gradient reduction rides the quantized ppermute ring with
    an error-feedback residual carried as an extra optimizer-state leaf —
    build the state with :func:`quantized_opt_state`, and see docs/gspmd.md.
    With the wire off, the knob changes nothing in the compiled program
    (the cache-key pin tested in tests/test_gspmd.py).

    ``algorithm`` selects the collective schedule for the quantized wire
    (``"ring"``/``"tree"``/``"hier"``/``"auto"``; ``None`` resolves
    ``HOROVOD_GSPMD_ALGO``). Unset/``"ring"`` compiles the byte-identical
    pre-zoo ring program (pinned in tests); ``"auto"`` resolves per
    payload size and topology at trace time (:func:`resolve_algorithm`).
    With the wire off the partitioner inserts the psum itself and the
    algorithm knob is inert; ``zero1=True`` keeps the ring — its chunk
    layout IS the optimizer-state sharding.
    """
    import optax

    wire = gspmd_wire(compression)
    if wire:
        return _make_quantized_step(loss_fn, tx, mesh, donate, zero1, wire,
                                    algorithm=algorithm)

    mesh = mesh or basics.mesh()
    repl = NamedSharding(mesh, P())
    opt_sh: Any = repl
    if zero1:
        if example_opt_state is None:
            raise ValueError(
                "zero1=True needs example_opt_state (tx.init(params) or its "
                "jax.eval_shape) to derive per-leaf shardings")
        from .optim.zero import zero1_shardings

        opt_sh = zero1_shardings(example_opt_state, mesh)

    # The named scopes here, in lm_loss and around the flash kernels are how
    # a device trace tells the step's parts apart (docs/timeline.md):
    # compile-time metadata, no run-time cost.
    loss_and_grads = local = jax.value_and_grad(loss_fn)
    if mesh.shape[MESH_AXIS] > 1:
        def reduced(params, batch):
            out = local(params, batch)
            with jax.named_scope("grad_allreduce"):
                return jax.lax.pmean(out, MESH_AXIS)

        loss_and_grads = _shard_map(
            reduced, mesh, in_specs=(P(), P(MESH_AXIS)), out_specs=P())

    def step(params, opt_state, batch):
        loss, grads = loss_and_grads(params, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    donate_argnums = (0, 1) if donate else ()
    return jax.jit(
        step,
        donate_argnums=donate_argnums,
        out_shardings=(repl, opt_sh, repl),
    )


def _shard_map(f, mesh, in_specs, out_specs):
    """shard_map with the vma checker off, so the Pallas kernels stay
    eligible inside it (`pallas_kernels.vma_active`)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ------------------------------------------- quantized whole-step builder
def quantized_opt_state(tx, params, mesh: Optional[Mesh] = None,
                        zero1: bool = False, block: Optional[int] = None):
    """Initial ``(inner_state, ef_residual)`` for a quantized train step.

    The error-feedback residual — ``corrected - quantize_roundtrip(
    corrected)``, the same EF-SGD math the coordinator wire uses
    (`ops/compression.py`) — is a per-rank quantity, so it rides as ONE
    extra optimizer-state leaf of global shape ``[world, total_params]``
    sharded 1/N over the mesh axis: inside the shard_map step each rank
    sees exactly its own row. The update is deterministic (no RNG, fixed
    reduction order), so re-running a step reproduces the residual
    bit-for-bit and the replicated params stay bit-identical across ranks.

    ``zero1=True`` builds the flat-space ZeRO-1 state instead
    (`optim/zero.flat_zero1_state`): the optimizer runs on each rank's
    ring chunk of the flattened parameter vector — valid for elementwise
    transforms (sgd/momentum/adam/adamw), where flat-space update equals
    tree-space update.
    """
    mesh = mesh or basics.mesh()
    n = mesh.shape[MESH_AXIS]
    total = sum(int(np.prod(np.shape(l) or (1,)))
                for l in jax.tree_util.tree_leaves(params))
    ef = jax.device_put(jnp.zeros((n, total), jnp.float32),
                        NamedSharding(mesh, P(MESH_AXIS)))
    if zero1:
        from .optim.zero import flat_zero1_state

        inner = flat_zero1_state(tx, total, mesh, _wire_block(block))
    else:
        inner = replicate(tx.init(params), mesh)
    return inner, ef


#: Running (wire, exact) byte accumulators behind hvd_quantization_ratio
#: for the compiled path — the engine keeps its own pair for the
#: coordinator wire (runtime/engine.py).
_gspmd_bytes = {"wire": 0.0, "exact": 0.0}


#: last algorithm recorded per payload-size class — K_ALGO events fire on
#: change only, so hvddoctor's algorithm_thrash signature counts real flips
_algo_last: dict = {}


def _note_algorithm(algorithm: str, total: int) -> None:
    """Gauge + flight-recorder trail for the compiled plane's algorithm
    choice: ``hvd_collective_algorithm{class}`` tracks the member in play
    per payload-size class, and a blackbox ``K_ALGO`` event records each
    change (`blackbox/signatures.detect_algorithm_thrash`)."""
    from . import blackbox as _blackbox
    from .metrics import instruments
    from .ops.adaptive import ALGO_CODES, size_class

    cls = size_class(total * 4)
    instruments.collective_algorithm().labels(**{"class": cls}).set(
        ALGO_CODES.get(algorithm, 0))
    prev = _algo_last.get(cls)
    if prev != algorithm:
        _algo_last[cls] = algorithm
        if prev is not None:
            _blackbox.record(_blackbox.K_ALGO, cls, f"{prev}->{algorithm}")


def _record_gspmd_wire(total: int, wire: str, world: int, block: int,
                       algorithm: str = "ring"):
    """Truthful byte accounting for one quantized collective round (eagerly,
    per step call — counters cannot tick inside the compiled program).
    Bytes come from the same catalog the three-way bench reads
    (`ops/compression.gspmd_wire_footprint`), per the algorithm actually
    traced."""
    from .metrics import instruments
    from .ops import compression as comp

    hosts = mesh_hosts(world) if algorithm == "hier" else None
    wire_b = comp.gspmd_wire_footprint(total, wire, world, block,
                                       algorithm=algorithm, hosts=hosts)
    exact_b = comp.gspmd_wire_footprint(total, "none", world, block,
                                        algorithm=algorithm, hosts=hosts)
    instruments.wire_bytes().labels(compression=f"gspmd-{wire}").inc(wire_b)
    instruments.wire_bytes_exact().inc(exact_b)
    _note_algorithm(algorithm, total)
    _gspmd_bytes["wire"] += wire_b
    _gspmd_bytes["exact"] += exact_b
    if _gspmd_bytes["exact"]:
        instruments.quantization_ratio().set(
            _gspmd_bytes["wire"] / _gspmd_bytes["exact"])


def _make_quantized_step(loss_fn: Callable, tx, mesh: Optional[Mesh],
                         donate: bool, zero1: bool, wire: str,
                         block: Optional[int] = None,
                         algorithm: Optional[str] = None) -> Callable:
    """The explicit-collective variant of make_train_step: gradients ride
    the quantized ppermute ring instead of GSPMD's inserted psum.

    Dataflow (docs/gspmd.md): local grads -> flatten to one f32 vector ->
    add this rank's EF residual -> quantized ring. ``zero1=False`` runs a
    full quantized allreduce and the optimizer on the whole (replicated)
    tree; ``zero1=True`` reduce-scatters the corrected gradients so the
    elementwise optimizer math runs on this rank's 1/N chunk only, then
    all-gathers the param delta over the same quantized ring — the ZeRO-1
    schedule with every collective on the packed wire.

    ``algorithm`` swaps the allreduce schedule for a zoo member
    (docs/autotune.md); ``"ring"``/unset traces the identical pre-zoo
    program, and ``zero1=True`` always keeps the ring (its chunk layout is
    the optimizer-state sharding). The EF residual convention is
    algorithm-independent: every member delivers the same one-hop
    quantization of the corrected gradient (``_wire_roundtrip``).
    """
    import optax

    mesh = mesh or basics.mesh()
    n = mesh.shape[MESH_AXIS]
    block = _wire_block(block)
    algo = gspmd_algo(algorithm)

    def _flatten_f32(leaves):
        parts = [jnp.ravel(l).astype(jnp.float32) for l in leaves]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def _split_like(flat, leaves):
        out, off = [], 0
        for l in leaves:
            out.append(flat[off:off + l.size].reshape(l.shape)
                       .astype(l.dtype))
            off += l.size
        return out

    def local_step(params, inner, ef, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        g_leaves, treedef = jax.tree_util.tree_flatten(grads)
        flat = _flatten_f32(g_leaves)
        total = flat.shape[0]
        corrected = flat + ef[0]
        use_ring = zero1 or _wire_eligible(total, corrected.dtype, wire,
                                           block)
        if use_ring:
            new_ef = (corrected
                      - _wire_roundtrip(corrected, wire, block))[None]
        else:
            new_ef = jnp.zeros_like(ef)
        if zero1:
            g_chunk = quantized_reduce_scatter(
                corrected, MESH_AXIS, wire, block) / n
            chunk = g_chunk.shape[0]
            p_flat = _flatten_f32(jax.tree_util.tree_leaves(params))
            pad = n * chunk - total
            if pad:
                p_flat = jnp.pad(p_flat, (0, pad))
            p = jax.lax.axis_index(MESH_AXIS)
            p_chunk = jax.lax.dynamic_slice_in_dim(p_flat, p * chunk, chunk)
            with jax.named_scope("optimizer"):
                upd_chunk, inner = tx.update(g_chunk, inner, p_chunk)
            upd_flat = quantized_all_gather(
                upd_chunk, MESH_AXIS, wire, block)[:total]
            updates = jax.tree_util.tree_unflatten(
                treedef, _split_like(upd_flat, g_leaves))
            with jax.named_scope("optimizer"):
                params = optax.apply_updates(params, updates)
        else:
            a = resolve_algorithm(total, n, algo)
            if a == "tree":
                reduced = quantized_allreduce_tree(
                    corrected, Average, MESH_AXIS, wire, block)
            elif a == "hier":
                reduced = quantized_allreduce_hier(
                    corrected, Average, MESH_AXIS, wire, block)
            else:
                reduced = quantized_allreduce(
                    corrected, Average, MESH_AXIS, wire, block)
            grads = jax.tree_util.tree_unflatten(
                treedef, _split_like(reduced, g_leaves))
            with jax.named_scope("optimizer"):
                updates, inner = tx.update(grads, inner, params)
                params = optax.apply_updates(params, updates)
        loss = jax.lax.pmean(loss, MESH_AXIS)
        return params, inner, new_ef, loss

    def step(params, opt_state, batch):
        inner, ef = opt_state
        if zero1:
            inner_specs = jax.tree_util.tree_map(
                lambda l: P(MESH_AXIS) if (jnp.ndim(l) == 1 and l.shape[0]
                                           and l.shape[0] % n == 0) else P(),
                inner)
        else:
            inner_specs = jax.tree_util.tree_map(lambda l: P(), inner)
        fn = _shard_map(
            local_step, mesh,
            in_specs=(P(), inner_specs, P(MESH_AXIS), P(MESH_AXIS)),
            out_specs=(P(), inner_specs, P(MESH_AXIS), P()))
        params, inner, ef, loss = fn(params, inner, ef, batch)
        return params, (inner, ef), loss

    jitted = jax.jit(step, donate_argnums=(0, 1) if donate else ())

    # "auto" resolves at trace time (the first call); pin the same answer
    # for accounting so a later tuned broadcast can't make the byte
    # counters disagree with the program actually compiled
    resolved: dict = {}

    @functools.wraps(jitted)
    def instrumented(params, opt_state, batch):
        total = int(opt_state[1].shape[1])  # read before donation
        out = jitted(params, opt_state, batch)
        a = resolved.setdefault(
            total, "ring" if zero1 else resolve_algorithm(total, n, algo))
        _record_gspmd_wire(total, wire, n, block, a)
        return out

    instrumented.jitted = jitted  # .lower()/.compile() escape hatch
    return instrumented
