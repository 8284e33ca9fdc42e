"""Ring attention: exact attention over sequences sharded across devices.

No reference counterpart — Horovod 0.18.2 is data-parallel only (SURVEY §5
"Long-context: absent") — but long-context sequence parallelism is first-class
in this framework. Design follows the blockwise ring-attention construction
(Liu et al., "Ring Attention with Blockwise Transformers"; see PAPERS.md):

  * Q, K, V are sharded on the sequence axis across the ``sp`` mesh axis.
  * Each step computes a flash-style partial attention (running max ``m``,
    normalizer ``l``, accumulator ``o``) against the currently-held K/V block,
    then rotates K/V one hop around the ring with ``lax.ppermute`` — the
    collective rides ICI neighbor links, overlapping compute with transfer
    (XLA schedules the ppermute DMA alongside the matmuls).
  * After ``sp`` steps every query block has attended to every key block;
    memory per chip stays O(T/sp · T/sp) instead of O(T²).

Causal masking uses global positions derived from each block's ring origin, so
the result matches full causal attention exactly.

The per-hop block compute runs as a Pallas flash kernel
(`horovod_tpu/ops/pallas_kernels.py`) when shapes are MXU-tile-aligned on the
TPU backend (``HVD_PALLAS`` gates it), with this file's jnp flash step as the
always-available fallback — same (m, l, o) carry either way. The backward is
ring-structured too (`_ring_fa_vjp`): a second ring pass runs the Pallas
FlashAttention-2 dq/dkv kernels per hop and rotates the dk/dv accumulator
with its block, so residual memory stays O(T/sp) per chip instead of the
[T/sp, T/sp] score tensors a per-hop jnp VJP would materialize.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

def _block_attn(q, k, v, m, l, o, q_off, k_off, causal, scale):
    """One flash-accumulation step of q against the (k, v) block.

    q: [B, Tq, H, D]; k/v: [B, Tk, H, D]; m/l: [B, H, Tq]; o like q (f32).
    """
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)  # [B,H,Tq,Tk]
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        qpos = q_off + jnp.arange(tq)
        kpos = k_off + jnp.arange(tk)
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows: exp(-inf - -inf) etc.
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(jnp.where(jnp.isneginf(s), -jnp.inf, s - m_safe[..., None]))
    alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
    l_new = l * alpha + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _ring_fwd_stats(q, k, v, axis_name, step):
    """Forward ring pass: per-hop flash accumulation + K/V rotation.
    Returns the raw (m, l, o) statistics."""
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    m0 = jnp.full((b, h, t), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    o0 = jnp.zeros((b, t, h, d), jnp.float32)
    q_off = my * t
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, carry):
        m, l, o, kv_cur = carry
        # block currently held arrived from rank (my - i) mod n
        src = (my - i) % n
        m, l, o = step(q, kv_cur[0], kv_cur[1], m, l, o, q_off, src * t)
        # rotate K and V to the next rank as ONE stacked buffer: a single
        # collective launch per hop, one large DMA for XLA to overlap with
        # the block matmuls
        kv_nxt = lax.ppermute(kv_cur, axis_name, perm)
        return m, l, o, kv_nxt

    kv0 = jnp.stack([k, v])
    # blocks 0..n-2 rotate; the final block is processed outside the loop so
    # no wasted ppermute trails the last compute step
    m, l, o, kv_last = lax.fori_loop(0, n - 1, body, (m0, l0, o0, kv0))
    src = (my - (n - 1)) % n
    m, l, o = step(q, kv_last[0], kv_last[1], m, l, o, q_off, src * t)
    return m, l, o


@functools.lru_cache(maxsize=None)
def _ring_fa_vjp(axis_name: str, causal: bool, scale: float):
    """Ring attention with a ring-structured FlashAttention-2 backward.

    Forward: Pallas flash step per hop, saving only (q, k, v, out, LSE) —
    O(T/sp) residuals per chip. Backward: a SECOND ring pass — each hop
    runs the Pallas dq and dkv kernels against the visiting K/V block with
    the global row-LSE, accumulates dq locally, and rotates the (dk, dv)
    accumulator WITH the block so every block's gradient arrives back at
    its owner after n hops (the Liu et al. ring-attention backward). This
    replaces differentiating through the forward loop, whose per-hop jnp
    VJP materialized [T/sp, T/sp] score tensors in HBM.
    """
    from ..ops import pallas_kernels as pk

    def fwd_impl(q, k, v):
        def step(qq, kk, vv, m, l, o, q_off, k_off):
            return pk.flash_attention_step(qq, kk, vv, m, l, o, q_off, k_off,
                                           causal=causal, scale=scale)

        m, l, o = _ring_fwd_stats(q, k, v, axis_name, step)
        return pk.finalize_attention_stats(m, l, o, q.dtype)

    @jax.custom_vjp
    def fa(q, k, v):
        return fwd_impl(q, k, v)[0]

    def fwd(q, k, v):
        out, lse = fwd_impl(q, k, v)
        return out, (q, k, v, out, lse)

    def bwd(res, dout):
        q, k, v, out, lse = res
        n = lax.psum(1, axis_name)
        my = lax.axis_index(axis_name)
        t = q.shape[1]
        q_off = my * t
        perm = [(i, (i + 1) % n) for i in range(n)]

        def body(i, carry):
            dq, kv_cur, dkv_cur = carry
            src = (my - i) % n
            dq_i, dk_i, dv_i = pk._flash_bwd(
                q, kv_cur[0], kv_cur[1], out, lse, dout, q_off, src * t,
                causal=causal, scale=scale)
            dq = dq + dq_i
            dkv_cur = dkv_cur + jnp.stack([dk_i, dv_i])
            # n rotations total: the dk/dv accumulator travels with its
            # block and lands back on the block's owner after the loop.
            # Two launches per hop (not one stacked buffer like the
            # forward): the accumulator must stay f32 — n hops of bf16
            # accumulation would degrade the summed gradient — so the
            # dtypes differ; stacking everything in f32 would move MORE
            # bytes (16 vs 12 per element) than the extra launch costs.
            kv_nxt = lax.ppermute(kv_cur, axis_name, perm)
            dkv_nxt = lax.ppermute(dkv_cur, axis_name, perm)
            return dq, kv_nxt, dkv_nxt

        dq0 = jnp.zeros(q.shape, jnp.float32)
        dkv0 = jnp.zeros((2,) + k.shape, jnp.float32)
        dq, _, dkv = lax.fori_loop(0, n, body, (dq0, jnp.stack([k, v]), dkv0))
        return (dq.astype(q.dtype), dkv[0].astype(k.dtype),
                dkv[1].astype(v.dtype))

    fa.defvjp(fwd, bwd)
    return fa


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                   scale: Optional[float] = None, use_pallas: bool = True):
    """Exact (flash-accumulated) attention across a sequence-sharded ring.

    Call inside ``shard_map`` with q/k/v sharded on dim 1 (sequence) over
    ``axis_name``. Shapes per shard: ``[batch, seq/sp, heads, head_dim]``.
    Returns the attention output in the input dtype, same sharding.
    ``use_pallas=False`` forces the jnp block path — needed where a Pallas
    custom call cannot be partitioned (heads sharded over a GSPMD auto
    axis, `parallel/hybrid.py`).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5

    from ..ops import pallas_kernels

    if use_pallas and pallas_kernels.step_supported(q, k):
        # Pallas forward AND ring-structured Pallas backward (the blockwise
        # backward kernels cover any shard length — resident or streaming)
        return _ring_fa_vjp(axis_name, causal, float(scale))(q, k, v)

    def step(qq, kk, vv, m, l, o, q_off, k_off):
        return _block_attn(qq, kk, vv, m, l, o, q_off, k_off, causal,
                           scale)

    m, l, o = _ring_fwd_stats(q, k, v, axis_name, step)
    l_safe = jnp.where(l == 0, 1.0, l)
    out = o / l_safe.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def make_ring_attention(mesh, axis_name: str = "sp", causal: bool = False):
    """Jitted ring attention over ``mesh``: takes global [B, T, H, D] arrays
    sharded on T and returns the same."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name)

    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return jax.jit(fn)


def reference_attention(q, k, v, causal: bool = False,
                        scale: Optional[float] = None,
                        window: Optional[int] = None,
                        block_diffusion: Optional[int] = None):
    """Plain full attention (for tests / single-device fallback); with
    ``window`` (causal) a query sees its last ``window`` positions only;
    with ``block_diffusion`` (causal, ``2 T`` rows: a noised half then a
    clean one, in blocks of that length) ``flash_attention``'s mask of that
    name, its three clauses written out."""
    d = q.shape[-1]
    if scale is None:
        scale = d ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        delta = jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :]
        mask = delta >= 0 if window is None else (delta >= 0) & (delta < window)
        if block_diffusion is not None:
            half = tq // 2
            clean = jnp.arange(tq) >= half
            block = jnp.arange(tq) % half // block_diffusion
            (cq, ck), (bq, bk) = ((a[:, None], a[None, :])
                                  for a in (clean, block))
            mask = (ck & ((bk < bq) | ((bk == bq) & cq))) \
                | (~ck & ~cq & (bk == bq))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
