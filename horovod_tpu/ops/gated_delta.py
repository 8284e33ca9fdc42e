"""The gated delta rule: linear attention whose state is *corrected*
towards each new key's value, not only added to.

The layer is, per head, the recurrence over a ``[K, V]`` state

    S'  = exp(g_t) * S_{t-1}                                 (decay, g_t <= 0)
    S_t = S' + k_t (beta_t * (v_t - S'^T k_t))^T             (the delta rule)
    o_t = S_t^T q_t

(Yang, Kautz & Hatamizadeh, "Gated Delta Networks", 2024): what the state
already answers for ``k_t`` is taken off ``v_t`` before the rank-one write,
``beta_t`` in (0, 1) the write's strength, ``g_t`` one scalar a head and
position as Mamba-2's ``dt * A`` is (``ops/ssd.py``). With ``beta = 0`` it
is nothing, with the correction left out gated linear attention, with
``g = 0`` the plain delta rule.

:func:`gated_delta_chunked` computes it without a loop over positions.
Inside a chunk of ``L`` positions, with ``G`` the cumulative sum of ``g``,
the corrected values ``u_l = beta_l (v_l - S'_l^T k_l)`` depend on one
another through the keys: ``(I + A) U = diag(beta) (V - (K * exp(G)) S_0)``
with ``A = tril(diag(beta) (K K^T * exp(G_l - G_s)), -1)``, a unit lower
triangular system an ``L x L`` a head and chunk
(:func:`unit_lower_inverse`). So ``[W | U] = (I + A)^-1 diag(beta)
[K * exp(G) | V]`` is made for every chunk at once, and what crosses chunks
is the state alone, which each chunk *transforms* (``V' = U - W S``) and
not only decays: a ``lax.scan`` over chunks, not ``ssd_chunked``'s one
einsum over cumulative decays, and over that a scan over segments of
chunks that bounds what the gradient keeps. Batched matmuls and elementwise
ops that XLA lays out and autodiff takes back: the reference path, off the
chip, with kernels off, for a shape ``pallas_kernels.delta_route`` refuses
and for varying operands under ``shard_map(check_vma=True)``. On the chip
the same chunked form runs on a Pallas kernel pair
(``pallas_kernels.delta_rule``: ``delta_fwd``, and ``delta_bwd`` under a
hand-written backward pass) that walks a sequence's tiles in order with a
head's float32 state in VMEM and makes a chunk's decay matrix, system,
inverse and masked products there, none of which crosses HBM; the forward
that a backward pass follows saves each tile's entering state and each
chunk's inverse, which take the segments' place.
``pallas_kernels.kernel_path("gated_delta", q, k, v)`` says which path a
call takes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import pallas_kernels as pk


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + A)^-1`` for ``a`` ``[..., L, L]`` (``L`` a power of two), of
    which only the strictly lower triangle ``A`` is read; float32.

    Block recursion on ``[[M11, 0], [A21, M22]]^-1 = [[T11, 0], [-T22 A21
    T11, T22]]`` from blocks of one (whose inverse is 1) up, every block of
    a level at once: with ``T`` the inverse of the block diagonal at block
    size ``s`` and ``A_s`` the part of ``A`` inside the blocks of ``2 s``
    and outside those of ``s``, the next is ``T - T A_s T``. ``log2 L``
    levels of two ``L x L`` products, the matrices whole at every level
    (a chunk's two axes stay the minor ones, which is what the chip's
    tiles want). It is forward substitution in blocks: no power of ``A`` is
    formed, so keys that repeat (``A`` all ones, whose inverse is
    bidiagonal and whose powers are binomials) lose nothing.

    Its gradient is the inverse's own, ``dA = -T^T dT T^T`` under the
    strictly lower triangle, from ``T`` alone: two products, and no level
    of the recursion is kept for it."""
    size = a.shape[-1]
    if size & (size - 1):
        raise ValueError(f"unit_lower_inverse: L={size} is not a power of two")
    a = a.astype(jnp.float32)
    row = jnp.arange(size)[:, None]
    col = jnp.arange(size)[None, :]
    # blocks of one -> two: T_1 = I, so T_2 = I - A_1
    t = jnp.eye(size, dtype=jnp.float32) - jnp.where(
        (row // 2 == col // 2) & (row > col), a, 0.0)
    s = 2
    while s < size:
        inside = (row // (2 * s) == col // (2 * s)) & (row // s > col // s)
        step = jnp.where(inside, a, 0.0)
        t = t - jnp.einsum("...ij,...jk,...kl->...il", t, step, t,
                           precision="highest")
        s *= 2
    return t


def _inverse_fwd(a):
    t = unit_lower_inverse(a)
    return t, t


def _inverse_bwd(t, dt):
    da = -jnp.einsum("...ji,...jk,...lk->...il", t, dt.astype(jnp.float32),
                     t, precision="highest")
    return (jnp.tril(da, -1),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


#: positions a chunk (a power of two: one unit lower triangular system a
#: head; the kernels' own is ``pallas_kernels._DELTA_CHUNK``, the same 64),
#: and, on the reference path, positions a segment of chunks whose state the
#: gradient keeps (the kernel path keeps a tile's entering state and a
#: chunk's inverse, and walks no segments)
CHUNK = 64
SEGMENT = 4096


def gated_delta_chunked(q, k, v, g, beta):
    """The recurrence above for ``q``, ``k`` ``[b, T, H, K]`` and ``v``
    ``[b, T, H, V]``, from a state of zeros; ``g`` ``[b, T, H]`` the log
    decays (<= 0) and ``beta`` ``[b, T, H]`` the write strengths. ``q`` and
    ``k`` come as the caller made them (L2-normalised and scaled in
    ``models/hybrid.GatedDeltaMixer``). Returns ``o`` ``[b, T, H, V]`` in
    ``q.dtype``.

    Matmul operands are in ``q.dtype`` (bf16 in the model) with float32
    accumulation; ``g``, its cumulative sums, every ``exp``, the system
    ``A`` and its inverse, and the state between chunks are float32, and
    every exponent is <= 0: a decay is taken between two positions of one
    chunk or from a chunk's start or to its end, the mask going in before
    the ``exp``. That holds of both paths: the Pallas pair
    (``pallas_kernels.delta_rule``, where ``pallas_kernels.delta_route``
    admits the shape: heads of one lane width each way, 2- or 4-byte
    elements) rounds where this form rounds, and makes the inverse by the
    same recursion at full float32 precision.

    On the reference path the sequence is walked a segment of :data:`SEGMENT` positions at a time
    (of the whole chunks that hold ``T``, where that is less), the state
    handed on, and the backward pass makes a segment's chunks again from
    its operands and the state it started with: what the chunked form keeps
    for its gradient (a system, an inverse and five products a chunk, a
    state a chunk) is a segment's at a time and not the sequence's (at
    16,384 positions and 32 heads of 128, 3.9 GiB for 5.7 in a layer's
    gradient as the chip's compiler counts them), for one more forward pass
    of the rule. A ``T`` that the segment does not divide is padded with
    steps of ``g = 0, beta = 0``, which leave the state alone."""
    b, t, h, dk = q.shape
    if pk.kernel_path("gated_delta", q, k, v) == "pallas":
        with jax.named_scope("delta_rule"):
            return pk.delta_rule(q, k, v, g, beta)
    if SEGMENT % CHUNK:
        raise ValueError(f"SEGMENT={SEGMENT} is no multiple of CHUNK={CHUNK}")
    segment = min(SEGMENT, t + -t % CHUNK)
    pad = -t % segment
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    operands = (q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32))
    start = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)

    def segments(a):        # [b, T, ...] -> [T / segment, b, segment, ...]
        return jnp.moveaxis(a.reshape((b, -1, segment) + a.shape[2:]), 1, 0)

    with jax.named_scope("delta_rule"):
        _, out = jax.lax.scan(
            jax.checkpoint(lambda state, at: _segment(state, at, CHUNK)),
            start, tuple(segments(a) for a in operands))
    return jnp.moveaxis(out, 0, 1).reshape(v.shape)[:, :t]


def _segment(start, operands, chunk: int):
    """``(the state after, o)`` of :func:`gated_delta_chunked` over whole
    chunks of ``chunk`` positions, from the state ``start`` ``[b, H, K,
    V]``."""
    q, k, v, g, beta = operands
    b, t, h, _ = q.shape
    c, f32, dtype = t // chunk, jnp.float32, q.dtype

    def chunks(a):      # [b, T, H, ...] -> [c, b, H, L, ...]
        a = a.reshape((b, c, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    bc = chunks(beta)[..., None]                           # [c,b,H,L,1]
    cum = jnp.cumsum(chunks(g), axis=-1)                   # [c,b,H,L]
    # exp(G_l - G_s) for s <= l, 0 above the diagonal: the mask goes in
    # before the exp, where the exponent is positive and may overflow
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("...ld,...sd->...ls", kc, kc, preferred_element_type=f32)
    inverse = unit_lower_inverse(bc * kk * decay).astype(dtype)
    from_start = jnp.exp(cum)[..., None]                   # exp(G_l)
    to_end = jnp.exp(cum[..., -1:] - cum)[..., None]       # exp(G_L - G_l)
    k_in = bc * from_start * kc.astype(f32)
    w = jnp.einsum("...ls,...sd->...ld", inverse, k_in.astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    u = jnp.einsum("...ls,...sd->...ld", inverse,
                   (bc * vc.astype(f32)).astype(dtype),
                   preferred_element_type=f32)
    # what a chunk's queries read of its own keys, the diagonal included
    qk = (jnp.einsum("...ld,...sd->...ls", qc, kc,
                     preferred_element_type=f32) * decay).astype(dtype)
    q_in = (from_start * qc.astype(f32)).astype(dtype)
    k_out = (to_end * kc.astype(f32)).astype(dtype)
    whole = jnp.exp(cum[..., -1])[..., None, None]         # exp(G_L)

    def one_chunk(state, at):
        w, u, qk, q_in, k_out, whole = at
        held = state.astype(dtype)
        # the corrected values, given the state the chunk starts from
        new = (u - jnp.einsum("...ld,...de->...le", w, held,
                              preferred_element_type=f32)).astype(dtype)
        out = jnp.einsum("...ld,...de->...le", q_in, held,
                         preferred_element_type=f32) \
            + jnp.einsum("...ls,...se->...le", qk, new,
                         preferred_element_type=f32)
        state = whole * state + jnp.einsum(
            "...ld,...le->...de", k_out, new, preferred_element_type=f32)
        return state, out.astype(dtype)

    end, out = jax.lax.scan(one_chunk, start, (w, u, qk, q_in, k_out, whole))
    # [c, b, H, L, V] -> [b, T, H, V]
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 2), 1, 3)
    return end, out.reshape(b, t, h, -1)
