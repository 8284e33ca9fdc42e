"""Mamba-2's sequence mixer: the state-space scan in its dual (SSD) form,
and the two small ops on either side of it.

The layer is, per head, the recurrence

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t B_t^T        S: [P, N]
    y_t = S_t C_t + D * x_t

(Dao & Gu, "Transformers are SSMs", 2024). :func:`ssd_chunked` computes it
without a loop over time: inside a chunk of ``L`` positions the recurrence
unrolls to the masked product ``(C B^T * decay) x`` — attention with a decay
in place of the softmax — and across chunks only the ``[P, N]`` state at each
chunk's end is carried. That dual form, batched matmuls and elementwise ops
that XLA lays out and autodiff takes back, is the reference path: off the
chip, with kernels off, and for a shape ``pallas_kernels.ssd_route``
refuses. On the chip the scan runs on a Pallas kernel pair
(``pallas_kernels.ssd_scan``: ``ssd_fwd``, and ``ssd_bwd`` under a
hand-written backward pass) that walks a sequence's tiles in order with
every head's state in VMEM, at its own tile edge, the groups of ``B`` and
``C`` an index map; ``pallas_kernels.kernel_path("ssd_scan", x, B)`` says
which path a call takes.

``causal_conv1d`` and ``gated_rms_norm`` are the depthwise convolution before
the scan and the gated normalisation after it (``models/hybrid.py``); the
gated delta rule's mixer (``ops/gated_delta.py``) uses both, the norm in
its other order, and the gated short convolution the conv alone. The conv
has two paths as the scan has: on the chip, for whole lane widths of
channels, a Pallas kernel pair under a hand-written backward pass
(``pallas_kernels.causal_conv``: ``conv_fwd``, ``conv_bwd``; residuals ``x``
and the taps, no padded or float32 copy of ``[T, C]`` in HBM); off the
chip, with kernels off, for a shape ``pallas_kernels.conv_route`` refuses
and for varying operands under ``shard_map``, K shifted multiply-adds in
jnp that autodiff takes back, which is also what the tests hold the
kernels to. ``pallas_kernels.kernel_path("causal_conv", x, kernel)`` says
which path a call takes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import pallas_kernels as pk


def ssd_chunked(x, dt, A, B, C, D, chunk: int = 256):
    """The scan above for ``x`` ``[b, T, H, P]`` (H heads of width P).

    ``dt`` ``[b, T, H]``: positive step sizes (after the softplus); ``A``
    ``[H]``: negative decay rates; ``B``, ``C``: the input and output
    projections of the N-wide state, ``[b, T, N]`` for one group shared by
    every head or ``[b, T, G, N]`` for ``G`` groups, head ``h`` reading
    group ``h // (H / G)``; ``D`` ``[H]``: the skip. Returns ``y``
    ``[b, T, H, P]`` in ``x.dtype``.

    Matmul operands are in ``x.dtype`` (bf16 in the model) with float32
    accumulation; ``dt``, ``A``, the cumulative sums of ``dt * A`` and every
    ``exp`` are float32, and every exponent is <= 0: a decay is taken
    between two positions of one chunk, or over whole chunks, never as a
    ratio of two large cumulative products. A ``T`` that ``chunk`` does not
    divide is padded with steps of ``dt = 0``, which leave the state alone.
    On the kernel path ``chunk`` decides nothing: the tile is the kernel's
    own, and so is the padding.
    """
    b, t, h, p = x.shape
    if pk.kernel_path("ssd_scan", x, B) == "pallas":
        with jax.named_scope("ssd"):
            return pk.ssd_scan(x, dt, A, B, C, D)
    if B.ndim == 4:
        # G groups are G scans, each of H / G heads over one group
        g = B.shape[2]
        with jax.named_scope("ssd"):
            return jax.vmap(partial(ssd_chunked, chunk=chunk),
                            in_axes=(2, 2, 0, 2, 2, 0), out_axes=2)(
                x.reshape(b, t, g, h // g, p), dt.reshape(b, t, g, h // g),
                A.reshape(g, h // g), B, C,
                D.reshape(g, h // g)).reshape(x.shape)
    pad = -t % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    c, f32 = (t + pad) // chunk, jnp.float32
    with jax.named_scope("ssd"):
        xc = x.reshape(b, c, chunk, h, p)
        Bc = B.reshape(b, c, chunk, -1).astype(x.dtype)
        Cc = C.reshape(b, c, chunk, -1).astype(x.dtype)
        # heads before positions: [b, c, H, L], the decay's layout
        dtc = dt.astype(f32).reshape(b, c, chunk, h).transpose(0, 1, 3, 2)
        cum = jnp.cumsum(dtc * A.astype(f32)[:, None], axis=-1)

        # inside a chunk: y_l += sum_{s <= l} (C_l . B_s) exp(cum_l - cum_s)
        # dt_s x_s. The mask goes in before the exp: above the diagonal the
        # exponent is positive and may overflow.
        seg = cum[..., :, None] - cum[..., None, :]
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))      # [b,c,H,L,L]
        cb = jnp.einsum("bcln,bcsn->bcls", Cc, Bc, preferred_element_type=f32)
        scores = cb[:, :, None] * decay * dtc[..., None, :]
        y = jnp.einsum("bchls,bcshp->bclhp", scores.astype(x.dtype), xc,
                       preferred_element_type=f32)

        # a chunk's own end state: sum_s exp(cum_end - cum_s) dt_s x_s B_s^T
        to_end = jnp.exp(cum[..., -1:] - cum) * dtc            # [b,c,H,L]
        xw = xc * to_end.transpose(0, 1, 3, 2)[..., None].astype(x.dtype)
        states = jnp.einsum("bclhp,bcln->bchpn", xw, Bc,
                            preferred_element_type=f32)

        # the state entering chunk z: sum_{k < z} states_k decayed over the
        # whole chunks k+1 .. z-1
        total = cum[..., -1]                                   # [b,c,H]
        before = jnp.cumsum(total, axis=1) - total             # exclusive
        over = before[:, :, None] - before[:, None, :] - total[:, None, :]
        earlier = jnp.tril(jnp.ones((c, c), bool), -1)[None, :, :, None]
        carry = jnp.exp(jnp.where(earlier, over, -jnp.inf))    # [b,z,k,H]
        # (c x c a head: nothing to the MXU, so the float32 decays and states
        # are multiplied as float32)
        entering = jnp.einsum("bzkh,bkhpn->bzhpn", carry, states,
                              precision="highest")

        # ... read by every position of the chunk through its own decay
        y_in = jnp.einsum("bcln,bchpn->bclhp", Cc, entering.astype(x.dtype),
                          preferred_element_type=f32)
        y = y + y_in * jnp.exp(cum).transpose(0, 1, 3, 2)[..., None]
        y = y + D.astype(f32)[:, None] * xc.astype(f32)
        return y.reshape(b, t + pad, h, p)[:, :t].astype(x.dtype)


def causal_conv1d(x, kernel, bias=None, *, activation=None):
    """Depthwise causal convolution over time: ``y_t = act(bias + sum_k
    kernel[k] * x_{t-(K-1)+k})`` for ``x`` ``[b, T, C]``, ``kernel``
    ``[K, C]``, positions before the sequence zero; no ``bias`` is a bias
    of zero; ``activation`` is ``None`` or ``"silu"``. K shifted
    multiply-adds accumulated in float32 (K is 3 or 4: not worth a
    convolution's layout), the bias first and the taps in order, the
    activation on the float32 sum, one cast to ``x.dtype``.

    Where ``pallas_kernels.conv_route`` admits the shape (on the chip:
    whole lane widths of channels, at most 8 taps) that is a Pallas kernel
    pair under a backward pass of its own, whose residuals are ``x`` and
    the taps (``pallas_kernels.causal_conv``); everywhere else the sums
    below, which autodiff takes back."""
    if activation not in (None, "silu"):
        raise ValueError(f"activation {activation!r}: None or 'silu'")
    if pk.kernel_path("causal_conv", x, kernel) == "pallas":
        return pk.causal_conv(x, kernel, bias, activation=activation)
    k, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    y = 0.0 if bias is None else bias.astype(jnp.float32)
    for i in range(k):
        y = y + padded[:, i:i + t] * kernel[i].astype(jnp.float32)
    if activation == "silu":
        y = jax.nn.silu(y)
    return y.astype(x.dtype)


def gated_rms_norm(y, gate, scale, eps: float, groups: int = 1,
                   norm_first: bool = False):
    """``RMSNorm(y * silu(gate)) * scale``, the mean square taken over each
    of ``groups`` equal runs of the last axis, in float32, returned in
    ``y.dtype``. With ``norm_first`` the other order, ``RMSNorm(y) * scale
    * silu(gate)``: the norm over the last axis itself (a head's width),
    under a ``scale`` that wide."""
    h, gate = y.astype(jnp.float32), jax.nn.silu(gate.astype(jnp.float32))
    if not norm_first:
        h = h * gate
    if groups > 1:
        h = h.reshape(h.shape[:-1] + (groups, -1))
    h = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), axis=-1, keepdims=True) + eps)
    h = h.reshape(y.shape) * scale.astype(jnp.float32)
    return (h * gate if norm_first else h).astype(y.dtype)
