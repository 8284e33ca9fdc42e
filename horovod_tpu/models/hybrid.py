"""Decoder-only LM whose blocks are described by data: a hybrid of sequence
mixers (Mamba-2, gated delta rule, attention, gated short convolution) and
feed-forwards (SwiGLU, routed experts).

``TransformerLM`` is one recipe. Here a model is a tuple of per-layer mixer
kinds (``"mamba"`` | ``"gated_delta"`` | ``"attention"`` |
``"latent_attention"`` | ``"short_conv"`` | ``"none"``), a
tuple of per-layer feed-forward kinds (``"swiglu"`` | ``"moe"`` |
``"none"``) and the widths of each; every block is

    x = x + r * mixer(RMSNorm(x));    x = x + r * ffn(RMSNorm(x))

or, where one of its two kinds is ``"none"``, the other sublayer alone
under its own norm (Nemotron-H: every block is one sublayer), and the model
is ``tok_emb[t] * e`` -> blocks -> RMSNorm -> head ``/ s``, the head the
table itself or, untied, a matrix of its own (``r``, ``e``, ``s`` and the
attention scale are Granite 4.0-H's four multipliers; each is 1, and the
scale ``head_dim ** -0.5``, unless given).

* The attention mixer has grouped KV heads and runs the same flash kernels
  as ``TransformerLM``. Its position kind is ``"none"`` (Granite 4.0-H: the
  state-space layers carry the order) or ``"rope"`` (``ops/rope.py``), and
  it can normalise q and k per head before that (LFM2). A model may have
  attention layers of several kinds (``attn_kinds``: a further mixer kind
  by name, and what of the attention mixer differs in it): Laguna's window
  layers have more query heads than its full ones, see their last 512
  positions only (``window``, a band the flash kernels skip by) and turn
  by another rotary scheme (a base over the whole head against given
  frequencies over half of it, scaled), and every layer gates each head's
  output (``gate``: one scalar a head, or one a head and channel). A model
  trained by denoising blocks (``denoise_blocks``: SDAR's) runs on a
  sequence's noised copy and then its clean one, both at the sequence's
  own positions, under the block-diffusion mask the flash kernels skip by
  (``block_diffusion``), and hands its head the noised half.
* The latent-attention mixer (MLA, DeepSeek-V2/V3's, without a query
  latent) projects its input to one narrow normed latent, from which every
  head's keys and values are made, and to one rotary key that all heads
  share; a head's keys are wider than its values, and the flash kernels
  take each at its own width.
* The Mamba-2 mixer is ``ops/ssd.py``, with one group of ``B`` and ``C``
  or several; the gated short convolution (LFM2's
  ``conv`` layer) is ``W_out (C * conv(B * u))`` over ``[B, C, u] = W_in h``
  with the same depthwise causal conv.
* The gated delta-rule mixer (Gated DeltaNet) is ``ops/gated_delta.py``:
  a ``[K, V]`` state a value head that decays by one scalar a position and
  is corrected towards each new key's value, fewer key heads than value
  heads, the same conv before it and the gated norm after it in its other
  order.
* The routed feed-forward is ``ops/moe.py``: top-k routing over sigmoid or
  softmax scores that drops no token, told which experts it holds. Its experts are SwiGLU or
  squared-ReLU, read the block's input or a latent of it (projected down
  before them and up after their sum), and may stand beside a shared
  expert that every token passes, whole or under a sigmoid gate a token.

bf16 compute and f32 parameters, ``remat=`` with ``TransformerLM``'s three
names and policies, and the module names the trace's scope classes read
(``block_<i>``, ``tok_emb``).
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import moe, ssd
from ..ops.gated_delta import gated_delta_chunked
from ..ops.pallas_kernels import flash_attention
from ..ops.rope import apply_rope
from .transformer import REMAT_POLICIES

def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=jnp.float32,
                    kernel_init=nn.initializers.normal(0.02), name=name)


class GatedRMSNorm(nn.Module):
    eps: float
    groups: int = 1
    norm_first: bool = False

    @nn.compact
    def __call__(self, y, gate):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],),
                           jnp.float32)
        return ssd.gated_rms_norm(y, gate, scale, self.eps, self.groups,
                                  self.norm_first)


def _conv_kernel_init(width: int):
    """As torch leaves a depthwise ``Conv1d`` of this width,
    U(+-1/sqrt(width)), which is how the Mamba-2 authors' code starts it."""
    bound = width ** -0.5

    def uniform(key, shape, dtype):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return uniform


class CausalConv(nn.Module):
    """Depthwise causal convolution over time, with bias (which starts at
    0; the kernel: :func:`_conv_kernel_init`) unless ``use_bias`` is off,
    and ``ops/ssd.causal_conv1d``'s ``activation``. Called with several
    runs of channels (the parts the mixer splits its projection into) it
    is one ``[width, channels]`` kernel over them side by side and returns
    as many: a channel's conv reads no other channel, so each run goes
    through on its own, from the projection's columns to the consumer's
    operand, and nothing is split out of one ``[b, T, channels]`` result."""
    width: int
    use_bias: bool = True
    activation: Optional[str] = None

    @nn.compact
    def __call__(self, *parts):
        channels = [p.shape[-1] for p in parts]
        cuts = np.cumsum(channels)[:-1]
        kernel = self.param("kernel", _conv_kernel_init(self.width),
                            (self.width, sum(channels)), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (sum(channels),),
                          jnp.float32) if self.use_bias else None
        kernels = jnp.split(kernel, cuts, axis=1)
        biases = [None] * len(parts) if bias is None \
            else jnp.split(bias, cuts)
        out = tuple(ssd.causal_conv1d(p, k, b, activation=self.activation)
                    for p, k, b in zip(parts, kernels, biases))
        return out if len(out) > 1 else out[0]


class MambaMixer(nn.Module):
    """Mamba-2: one projection to gate ``z``, ``[x, B, C]`` and ``dt``; a
    causal conv and SiLU over ``[x, B, C]``; the scan; the gated norm; the
    output projection. ``groups`` groups of ``B`` and ``C``, each shared by
    ``heads / groups`` consecutive heads, and the gated norm over as many
    groups of channels."""
    heads: int
    head_dim: int
    state: int
    conv_width: int
    chunk: int
    eps: float
    dtype: Any
    groups: int = 1

    @nn.compact
    def __call__(self, h):
        b, t, d_model = h.shape
        inner, bc = self.heads * self.head_dim, self.groups * self.state
        z, x, B, C, dt = jnp.split(
            _dense(2 * inner + 2 * bc + self.heads, self.dtype, "in_proj")(h),
            [inner, 2 * inner, 2 * inner + bc, 2 * inner + 2 * bc], axis=-1)
        x, B, C = CausalConv(self.conv_width, activation="silu",
                             name="conv")(x, B, C)
        if self.groups > 1:
            B, C = (a.reshape(b, t, self.groups, self.state) for a in (B, C))

        def per_head(name, init):
            return self.param(name, lambda *_: init, (self.heads,))

        ones = jnp.ones((self.heads,), jnp.float32)
        a_log = per_head("A_log", jnp.log(jnp.arange(1, self.heads + 1,
                                                     dtype=jnp.float32)))
        dt = jax.nn.softplus(dt.astype(jnp.float32) + per_head("dt_bias", ones))
        y = ssd.ssd_chunked(x.reshape(b, t, self.heads, self.head_dim), dt,
                            -jnp.exp(a_log), B, C, per_head("D", ones),
                            chunk=self.chunk)
        y = GatedRMSNorm(self.eps, self.groups, name="gate_norm")(
            y.reshape(b, t, inner), z)
        return _dense(d_model, self.dtype, "out_proj")(y)


class GatedDeltaMixer(nn.Module):
    """Gated DeltaNet: one projection to ``[q | k | v | z]`` (``key_heads``
    heads of ``key_dim`` for q and for k, ``value_heads`` of ``value_dim``
    for v and for the gate ``z``) and one to ``[b | a]`` (a write strength
    and a decay a value head); a causal conv (no bias) and SiLU over ``[q |
    k | v]``; ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a +
    dt_bias)`` in float32; q and k L2-normalised a head (``x * rsqrt(sum
    x^2 + 1e-6)``), q times ``key_dim ** -0.5``, each key head handed to
    its ``value_heads // key_heads`` consecutive value heads (all of that
    under the scope ``prep``); the delta rule
    (``ops/gated_delta.gated_delta_chunked``, scope ``delta_rule``); the
    gated norm a head, norm first, under one ``[value_dim]`` weight; the
    output projection. ``A_log`` and the conv's kernel start as
    ``MambaMixer``'s; ``dt_bias`` where Mamba-2's authors start theirs, at
    the inverse softplus of steps between 0.001 and 0.1 (here spaced evenly
    in the logarithm over the heads, not drawn), so that ``g`` runs from
    -0.001 to -0.1 x heads a position and the state remembers: at a bias
    of 1 every head forgets within a position or two and the rule has
    nothing to correct."""
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_width: int
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, h):
        b, t, d_model = h.shape
        heads, rep = self.value_heads, self.value_heads // self.key_heads
        keys, values = self.key_heads * self.key_dim, heads * self.value_dim
        q, k, v, z = jnp.split(
            _dense(2 * keys + 2 * values, self.dtype, "in_proj")(h),
            [keys, 2 * keys, 2 * keys + values], axis=-1)
        write, decay = jnp.split(
            _dense(2 * heads, self.dtype, "in_gates")(h), 2, axis=-1)

        def per_head(name, init):
            return self.param(name, lambda *_: init, (heads,))

        a_log = per_head("A_log", jnp.log(jnp.arange(1, heads + 1,
                                                     dtype=jnp.float32)))
        steps = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), heads))
        dt_bias = per_head("dt_bias", steps + jnp.log(-jnp.expm1(-steps)))
        conv = CausalConv(self.conv_width, use_bias=False, activation="silu",
                          name="conv")
        with jax.named_scope("prep"):
            q, k, v = conv(q, k, v)
            beta = nn.sigmoid(write.astype(jnp.float32))
            g = -jnp.exp(a_log) * jax.nn.softplus(
                decay.astype(jnp.float32) + dt_bias)

            def unit(x, scale=1.0):    # L2-normalised a head, in float32
                x = x.reshape(b, t, self.key_heads,
                              self.key_dim).astype(jnp.float32)
                x = x * (scale * jax.lax.rsqrt(
                    jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6))
                return jnp.repeat(x.astype(self.dtype), rep, axis=2)

            q, k = unit(q, self.key_dim ** -0.5), unit(k)
        o = gated_delta_chunked(q, k, v.reshape(b, t, heads, self.value_dim),
                                g, beta)
        y = GatedRMSNorm(self.eps, norm_first=True, name="gate_norm")(
            o, z.reshape(o.shape))
        return _dense(d_model, self.dtype, "out_proj")(
            y.reshape(b, t, values))


class GatedShortConv(nn.Module):
    """``c * conv(b * u)``: the depthwise causal conv (no bias) between the
    short-conv mixer's two gates."""
    width: int

    @nn.compact
    def __call__(self, b, c, u):
        kernel = self.param("kernel", _conv_kernel_init(self.width),
                            (self.width, u.shape[-1]), jnp.float32)
        return c * ssd.causal_conv1d(b * u, kernel)


class ShortConvMixer(nn.Module):
    """LFM2's gated short convolution: ``[B, C, u] = W_in h`` (each as wide
    as the model, in that order), ``W_out (C * conv(B * u))``."""
    conv_width: int
    dtype: Any

    @nn.compact
    def __call__(self, h):
        d_model = h.shape[-1]
        b, c, u = jnp.split(_dense(3 * d_model, self.dtype, "in_proj")(h), 3,
                            axis=-1)
        y = GatedShortConv(self.conv_width, name="short_conv")(b, c, u)
        return _dense(d_model, self.dtype, "out_proj")(y)


def _head_rms_norm(x, scale, eps: float):
    """RMSNorm over each head's last axis, in float32."""
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), axis=-1, keepdims=True) + eps)
    return (h * scale).astype(x.dtype)


class AttentionMixer(nn.Module):
    """Causal attention with ``kv_heads`` key/value heads, each serving
    ``heads // kv_heads`` consecutive query heads; no bias, the caller's
    softmax scale. ``position`` is ``"none"`` or ``"rope"`` (rotary at base
    ``rope_theta``, on q and k); with ``qk_norm_eps`` q and k are RMS-normed
    per head first, each under its own ``[head_dim]`` weight. The flash
    kernels take as many KV heads as query heads, so each is handed to them
    ``heads // kv_heads`` times. The rotary scheme's further fields are
    ``ops/rope.apply_rope``'s: ``rotary_dim`` elements of a head turn (all
    unless given), by ``rope_inv_freq`` where given and not by the base,
    cos and sin times ``rope_factor``. With ``window`` a query sees its
    last ``window`` positions only (``flash_attention``'s band, under the
    scope ``window``); with ``gate`` each head's output is multiplied by
    the sigmoid of a projection of the mixer's input (``gate``) before
    ``o``: ``"head"`` (or ``True``) one scalar a head and position,
    ``"channel"`` one a head, channel and position. With
    ``q_norm_init`` is where ``q_norm``'s weight starts (1 unless given:
    at ``g`` a fresh model's scores spread ``g`` times as wide, and its
    softmax tells its keys apart as a trained one's does). With
    ``block_diffusion`` (a block length ``B``) the input is a sequence's
    noised copy and then its clean one, ``[noised | clean]`` on the time
    axis: both halves turn by positions ``0..T-1``, and a query sees what
    ``flash_attention``'s mask of that name lets it see (under the scope
    ``block_diffusion``)."""
    heads: int
    kv_heads: int
    head_dim: int
    scale: float
    dtype: Any
    position: str = "none"
    rope_theta: float = 1e4
    qk_norm_eps: Optional[float] = None
    rotary_dim: Optional[int] = None
    rope_inv_freq: Optional[Tuple[float, ...]] = None
    rope_factor: float = 1.0
    window: Optional[int] = None
    gate: Union[bool, str] = False      # | "head" (True) | "channel"
    block_diffusion: Optional[int] = None
    q_norm_init: float = 1.0

    @nn.compact
    def __call__(self, h):
        b, t, d_model = h.shape
        if self.gate not in (False, True, "head", "channel"):
            raise ValueError(f"gate={self.gate!r}; expected False, 'head' "
                             f"(or True) or 'channel'")
        if self.block_diffusion is not None and self.window is not None:
            raise ValueError("block_diffusion with a window: the mask takes "
                             "the triangle's place, not a band's")

        def project(name, heads):
            return _dense(heads * self.head_dim, self.dtype, name)(h).reshape(
                b, t, heads, self.head_dim)

        q = project("q", self.heads)
        k, v = project("k", self.kv_heads), project("v", self.kv_heads)
        if self.qk_norm_eps is not None:
            def weight(name, start=1.0):
                return self.param(
                    name, nn.initializers.ones if start == 1.0
                    else nn.initializers.constant(start), (self.head_dim,),
                    jnp.float32)

            with jax.named_scope("qk_norm"):
                q = _head_rms_norm(q, weight("q_norm", self.q_norm_init),
                                   self.qk_norm_eps)
                k = _head_rms_norm(k, weight("k_norm"), self.qk_norm_eps)
        if self.position == "rope":
            # each half of [noised | clean] at its own positions 0..T-1
            positions = None if self.block_diffusion is None \
                else jnp.arange(t) % (t // 2)
            turn = partial(apply_rope, theta=self.rope_theta,
                           positions=positions, rotary_dim=self.rotary_dim,
                           inv_freq=self.rope_inv_freq,
                           factor=self.rope_factor)
            with jax.named_scope("rope"):
                q, k = turn(q), turn(k)
        k, v = (jnp.repeat(a, self.heads // self.kv_heads, axis=2)
                for a in (k, v))
        scope = "window" if self.window is not None else \
            "block_diffusion" if self.block_diffusion is not None else None
        with jax.named_scope(scope) if scope else contextlib.nullcontext():
            out = flash_attention(q, k, v, causal=True, scale=self.scale,
                                  window=self.window,
                                  block_diffusion=self.block_diffusion)
        out = out.astype(self.dtype)
        if self.gate == "channel":
            out = out * nn.sigmoid(_dense(self.heads * self.head_dim,
                                          self.dtype, "gate")(h)).reshape(
                out.shape)
        elif self.gate:
            out = out * nn.sigmoid(
                _dense(self.heads, self.dtype, "gate")(h))[..., None]
        return _dense(d_model, self.dtype, "o")(
            out.reshape(b, t, self.heads * self.head_dim))


class LatentAttentionMixer(nn.Module):
    """Multi-head latent attention in its training form (K and V made for
    every head; no query latent): ``q = h Wq``, a head ``[nope | rope]``;
    ``[c | k_r] = h Wkv_a`` with ``c`` ``kv_rank`` wide; ``[k_nope | v] =
    RMSNorm(c) Wkv_b``, a head ``nope_dim + v_dim``; each head's rotary
    part of q and the one ``k_r`` turn by position (``ops/rope.apply_rope``
    at ``rope_theta``, pairs of neighbouring elements), and every head's
    key is ``[k_nope | k_r]``. Causal softmax at the caller's ``scale``
    through the flash kernels, keys ``nope_dim + rope_dim`` and values
    ``v_dim`` wide; no bias. The scopes a device trace reads:
    ``latent`` (the two latent products and the norm between them),
    ``rope``, ``assemble`` (the splits, ``k_r`` handed to every head, the
    concatenations)."""
    heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    scale: float
    eps: float
    dtype: Any
    rope_theta: float

    @nn.compact
    def __call__(self, h):
        b, t, d_model = h.shape
        heads, nope = self.heads, self.nope_dim
        q = _dense(heads * (nope + self.rope_dim), self.dtype, "q")(
            h).reshape(b, t, heads, nope + self.rope_dim)
        with jax.named_scope("latent"):
            c, k_rope = jnp.split(_dense(self.kv_rank + self.rope_dim,
                                         self.dtype, "kv_a")(h),
                                  [self.kv_rank], axis=-1)
            c = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype,
                           param_dtype=jnp.float32, name="kv_norm")(c)
            kv = _dense(heads * (nope + self.v_dim), self.dtype, "kv_b")(
                c).reshape(b, t, heads, nope + self.v_dim)
        with jax.named_scope("assemble"):
            q_nope, q_rope = jnp.split(q, [nope], axis=-1)
            k_nope, v = jnp.split(kv, [nope], axis=-1)
        with jax.named_scope("rope"):
            turn = partial(apply_rope, theta=self.rope_theta,
                           interleaved=True)
            q_rope, k_rope = turn(q_rope), turn(k_rope[:, :, None, :])
        with jax.named_scope("assemble"):
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_rope, (b, t, heads, self.rope_dim))], axis=-1)
        out = flash_attention(q, k, v, causal=True, scale=self.scale)
        return _dense(d_model, self.dtype, "o")(
            out.astype(self.dtype).reshape(b, t, heads * self.v_dim))


def _activate(kind: str, pre):
    """``"swiglu"``: ``silu(a) * b`` of ``pre = [a, b]``; ``"relu2"``:
    ``relu(pre) ** 2``."""
    if kind == "relu2":
        return jnp.square(nn.relu(pre))
    gate, up = jnp.split(pre, 2, axis=-1)
    return nn.silu(gate) * up


class RoutedFeedForward(nn.Module):
    """``ops/moe.routed_ffn`` with its parameters: the router over all
    ``experts`` and its selection bias (float32), and the weights of the
    experts ``held`` here, ``width`` wide, of kind ``activation``
    (``"swiglu"`` | ``"relu2"``: ``W2 relu(W1 u) ** 2``, no gate). With
    ``latent`` the experts read ``latent_in(h)``, that wide, and their
    weighted sum goes through ``latent_out`` back to the model's width; the
    router reads ``h`` all the same. With ``shared_width`` a shared expert
    of that width and the same kind (``shared_in``, ``shared_out``), which
    every token passes, is added, with ``shared_gate`` under the sigmoid of
    one more projection of ``h`` (``shared_gate``: a scalar a token). A
    routed token's weights sum to
    ``scale``, their sum taking ``norm_eps`` before it divides; the scores
    they are made of are ``scoring`` of the router's logits (``"sigmoid"``
    | ``"softmax"`` over all the experts). With ``aux_loss`` the layer's
    auxiliary balancing loss times that coefficient rides the backward
    pass (``ops/moe.balancing``). Sows the
    experts each token chose, their scores and the tokens an expert
    (``intermediates``: free unless asked for), and hands the layer its
    module path (``block_<i>/ffn``) as the ``label`` of what it reports
    under ``HOROVOD_MOE_REPORT`` (``ops/moe.report_load``)."""
    experts: int
    held: Tuple[int, ...]
    top_k: int
    width: int
    dtype: Any
    activation: str = "swiglu"
    latent: int = 0
    shared_width: int = 0
    scale: float = 1.0
    norm_eps: float = moe.NORM_EPS
    scoring: str = "sigmoid"
    shared_gate: bool = False
    aux_loss: float = 0.0

    @nn.compact
    def __call__(self, h):
        b, t, d = h.shape
        init, n_held = nn.initializers.normal(0.02), len(self.held)
        sides = 2 if self.activation == "swiglu" else 1
        inner = self.latent or d
        x = _dense(inner, self.dtype, "latent_in")(h).reshape(b * t, inner) \
            if self.latent else None
        y, chosen, scores, load = moe.routed_ffn(
            h.reshape(b * t, d),
            self.param("router", init, (d, self.experts), jnp.float32),
            self.param("expert_bias", nn.initializers.zeros, (self.experts,),
                       jnp.float32),
            self.param("w_in", init, (n_held, inner, sides * self.width),
                       jnp.float32),
            self.param("w_out", init, (n_held, self.width, inner),
                       jnp.float32),
            held=self.held, top_k=self.top_k, x=x,
            activation=self.activation, scale=self.scale,
            norm_eps=self.norm_eps, scoring=self.scoring,
            aux_loss=self.aux_loss, label="/".join(self.path))
        self.sow("intermediates", "chosen", chosen.reshape(b, t, self.top_k))
        self.sow("intermediates", "scores", scores.reshape(b, t, self.experts))
        self.sow("intermediates", "load", load)
        y = y.reshape(b, t, inner)
        if self.latent:
            y = _dense(d, self.dtype, "latent_out")(y)
        if self.shared_width:
            shared = _dense(d, self.dtype, "shared_out")(_activate(
                self.activation, _dense(sides * self.shared_width, self.dtype,
                                        "shared_in")(h)))
            if self.shared_gate:
                shared = shared * nn.sigmoid(
                    _dense(1, self.dtype, "shared_gate")(h))
            y = y + shared
        return y


class HybridBlock(nn.Module):
    """A mixer and a feed-forward, each a residual sublayer under its own
    norm, or one of the two alone."""
    mixer: Optional[Callable[..., nn.Module]]   # makes it, given name=
    ffn: str                            # "swiglu" | "moe" | "none"
    ffn_width: int                      # of the SwiGLU feed-forward
    residual_multiplier: float
    eps: float
    dtype: Any
    routed_ffn: Optional[Callable[..., nn.Module]] = None   # makes the "moe"

    @nn.compact
    def __call__(self, x):
        norm = partial(nn.RMSNorm, epsilon=self.eps, dtype=self.dtype,
                       param_dtype=jnp.float32)
        if self.mixer is not None:
            h = self.mixer(name="mixer")(norm(name="norm_mixer")(x))
            x = x + self.residual_multiplier * h
        if self.ffn == "none":
            return x
        h = norm(name="norm_ffn")(x)
        if self.ffn == "moe":
            h = self.routed_ffn(name="ffn")(h)
        else:
            gate, up = jnp.split(
                _dense(2 * self.ffn_width, self.dtype, "ffn_in")(h), 2, axis=-1)
            h = _dense(x.shape[-1], self.dtype, "ffn_out")(nn.silu(gate) * up)
        return x + self.residual_multiplier * h


class HybridLM(nn.Module):
    """Tokens ``[B, T]`` -> float32 logits ``[B, T, vocab_size]``. A model
    built to denoise by blocks (``denoise_blocks``, the block length) takes
    ``(noised, clean)``, both ``[B, T]``, and gives the logits of the
    noised half."""
    vocab_size: int
    layer_kinds: Tuple[str, ...]        # "mamba" | "attention" | "short_conv"
                                        # | "latent_attention" | "gated_delta"
                                        # | "none": a feed-forward block
                                        # | a name of ``attn_kinds``
    d_model: int
    ffn_width: int                      # of a "swiglu" feed-forward
    attn_heads: int
    attn_kv_heads: int
    attn_head_dim: int
    ssm_heads: int = 0                  # the "mamba" layers' sizes
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv_width: int = 4
    ssm_chunk: int = 256
    attention_multiplier: Optional[float] = None    # the softmax scale
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: str = "none"                 # as TransformerLM: REMAT_POLICIES
    attn_position: str = "none"         # "none" | "rope"
    attn_rope_theta: float = 1e4
    attn_qk_norm: bool = False          # RMSNorm on q and k, per head
    conv_width: int = 3                 # the "short_conv" layers' kernel
    ffn_kinds: Tuple[str, ...] = ()     # "swiglu" | "moe" | "none" (a mixer
                                        # block); () = all "swiglu"
    moe_experts: int = 0                # the router's width
    moe_held: Tuple[int, ...] = ()      # ids of the experts held here
    moe_top_k: int = 0
    moe_width: int = 0                  # of one expert
    ssm_groups: int = 1                 # groups of B and C, and of the norm
    moe_activation: str = "swiglu"      # | "relu2": W2 relu(W1 u)^2
    moe_latent: int = 0                 # the experts' input width; 0: d_model
    moe_shared_width: int = 0           # of the shared expert; 0: none
    moe_scale: float = 1.0              # what a token's weights sum to
    moe_norm_eps: float = moe.NORM_EPS  # added to their sum before it divides
    tied_head: bool = True              # the head is the table
    moe_scoring: str = "sigmoid"        # | "softmax", over all the experts
    # a sigmoid gate on each head's output: True / "head", or "channel"
    attn_gate: Union[bool, str] = False
    # further attention kinds by name, for ``layer_kinds``: what of
    # ``AttentionMixer``'s fields differs from the "attention" kind's
    attn_kinds: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)
    # the "latent_attention" layers (``attn_heads`` heads; the softmax scale
    # is (nope + rope) ** -0.5 unless ``attention_multiplier`` gives it)
    mla_kv_rank: int = 0                # the KV latent's width
    mla_nope_dim: int = 0               # a head's keys and queries: the part
    mla_rope_dim: int = 0               # that does not turn, and the one that
    mla_v_dim: int = 0                  # does; a head's values
    mla_rope_theta: float = 0.0         # the rotary base: to be stated
    moe_shared_gate: bool = False       # a sigmoid gate on the shared expert
    # the "gated_delta" layers' sizes (the conv is ``ssm_conv_width`` wide)
    delta_key_heads: int = 0            # heads of q and k, each serving
    delta_value_heads: int = 0          # value_heads / key_heads value heads
    delta_key_dim: int = 0
    delta_value_dim: int = 0
    # hold the residual stream as it stands at every block's entry and after
    # the last (``jax.lax.optimization_barrier``): XLA then fuses nothing
    # across a block's boundary, and the bf16 stream is rounded where the
    # blocks say, whatever else a program made from the model hands out
    # (``capture_intermediates``) or keeps (``remat``)
    pin_stream: bool = False
    # denoising by blocks of this length (0: next-token prediction): the
    # blocks run on ``2 T`` rows, ``[noised | clean]`` on the time axis
    # (laid side by side and cut apart under the scope ``denoise_io``);
    # every attention layer takes the block-diffusion mask and turns both
    # halves by positions ``0..T-1``; the final norm and the head run on the
    # noised half alone. Attention is the one mixer that knows the layout
    denoise_blocks: int = 0
    # the coefficient of each routed layer's auxiliary balancing loss, which
    # rides the backward pass (``ops/moe.balancing``); 0: none
    moe_aux_loss: float = 0.0
    # where the attention layers' ``q_norm`` weight starts
    attn_q_norm_init: float = 1.0

    @nn.compact
    def __call__(self, tokens, clean=None):
        if (clean is None) != (self.denoise_blocks == 0):
            raise ValueError(
                f"denoise_blocks={self.denoise_blocks}: a model built to "
                f"denoise by blocks takes (noised, clean), any other model "
                f"one token array")
        if self.denoise_blocks and set(self.layer_kinds) - {
                "attention", "none", *self.attn_kinds}:
            raise ValueError(
                f"denoise_blocks with layer_kinds={self.layer_kinds!r}: "
                f"only attention layers take [noised | clean]")
        if self.remat not in REMAT_POLICIES:
            raise ValueError(f"remat={self.remat!r}; expected one of "
                             f"{sorted(REMAT_POLICIES)}")
        if self.attn_position not in ("none", "rope"):
            raise ValueError(f"attn_position={self.attn_position!r}; "
                             f"expected 'none' or 'rope'")
        scale = self.attn_head_dim ** -0.5 \
            if self.attention_multiplier is None else self.attention_multiplier
        mla_scale = self.attention_multiplier
        if "latent_attention" in self.layer_kinds:
            mla = (self.mla_kv_rank, self.mla_nope_dim, self.mla_rope_dim,
                   self.mla_v_dim, self.mla_rope_theta)
            if not all(x > 0 for x in mla):
                raise ValueError(
                    f"latent_attention layers need mla_kv_rank, mla_nope_dim, "
                    f"mla_rope_dim, mla_v_dim and mla_rope_theta; got {mla}")
            if mla_scale is None:
                mla_scale = (self.mla_nope_dim + self.mla_rope_dim) ** -0.5
        if "gated_delta" in self.layer_kinds:
            delta = (self.delta_key_heads, self.delta_value_heads,
                     self.delta_key_dim, self.delta_value_dim)
            if not all(x > 0 for x in delta) \
                    or self.delta_value_heads % self.delta_key_heads:
                raise ValueError(
                    f"gated_delta layers need delta_key_heads, "
                    f"delta_value_heads (a multiple of them), delta_key_dim "
                    f"and delta_value_dim; got {delta}")
        mixers = {
            "mamba": partial(MambaMixer, self.ssm_heads, self.ssm_head_dim,
                             self.ssm_state, self.ssm_conv_width,
                             self.ssm_chunk, self.norm_eps, self.dtype,
                             self.ssm_groups),
            "attention": partial(
                AttentionMixer, heads=self.attn_heads,
                kv_heads=self.attn_kv_heads, head_dim=self.attn_head_dim,
                scale=scale, dtype=self.dtype, position=self.attn_position,
                rope_theta=self.attn_rope_theta,
                qk_norm_eps=self.norm_eps if self.attn_qk_norm else None,
                gate=self.attn_gate, q_norm_init=self.attn_q_norm_init,
                block_diffusion=self.denoise_blocks or None),
            "short_conv": partial(ShortConvMixer, self.conv_width,
                                  self.dtype),
            "gated_delta": partial(
                GatedDeltaMixer, self.delta_key_heads, self.delta_value_heads,
                self.delta_key_dim, self.delta_value_dim, self.ssm_conv_width,
                self.norm_eps, self.dtype),
            "latent_attention": partial(
                LatentAttentionMixer, heads=self.attn_heads,
                kv_rank=self.mla_kv_rank, nope_dim=self.mla_nope_dim,
                rope_dim=self.mla_rope_dim, v_dim=self.mla_v_dim,
                scale=mla_scale, eps=self.norm_eps, dtype=self.dtype,
                rope_theta=self.mla_rope_theta),
            "none": None}
        fields = {f.name for f in dataclasses.fields(AttentionMixer)} - {
            "parent", "name"}
        for kind, differs in self.attn_kinds.items():
            if kind in mixers or set(differs) - fields:
                raise ValueError(
                    f"attn_kinds[{kind!r}]={dict(differs)!r}; expected a "
                    f"new kind's name and fields of AttentionMixer")
            mixers[kind] = partial(mixers["attention"], **differs)
        unknown = set(self.layer_kinds) - set(mixers)
        if unknown:
            raise ValueError(f"layer_kinds has {sorted(unknown)}; expected "
                             f"each of {sorted(mixers)}")
        ffn_kinds = self.ffn_kinds or ("swiglu",) * len(self.layer_kinds)
        if len(ffn_kinds) != len(self.layer_kinds) \
                or set(ffn_kinds) - {"swiglu", "moe", "none"} \
                or ("none", "none") in zip(self.layer_kinds, ffn_kinds):
            raise ValueError(f"ffn_kinds={ffn_kinds!r}; expected 'swiglu', "
                             f"'moe' or (beside a mixer) 'none' for each of "
                             f"{len(self.layer_kinds)} layers")
        routed = None
        if "moe" in ffn_kinds:
            held = tuple(self.moe_held)
            if not (held and len(set(held)) == len(held)
                    and all(0 <= e < self.moe_experts for e in held)
                    and 0 < self.moe_top_k <= self.moe_experts):
                raise ValueError(
                    f"moe_held={held!r} must be distinct ids below "
                    f"moe_experts={self.moe_experts}, and moe_top_k="
                    f"{self.moe_top_k} at most that")
            if self.moe_activation not in ("swiglu", "relu2"):
                raise ValueError(f"moe_activation={self.moe_activation!r}; "
                                 f"expected 'swiglu' or 'relu2'")
            routed = partial(RoutedFeedForward, self.moe_experts, held,
                             self.moe_top_k, self.moe_width, self.dtype,
                             self.moe_activation, self.moe_latent,
                             self.moe_shared_width, self.moe_scale,
                             self.moe_norm_eps, self.moe_scoring,
                             self.moe_shared_gate, self.moe_aux_loss)
        use_remat, policy = REMAT_POLICIES[self.remat]
        block_cls = nn.remat(HybridBlock, policy=policy) if use_remat \
            else HybridBlock

        emb = nn.Embed(self.vocab_size, self.d_model,
                       embedding_init=nn.initializers.normal(0.02),
                       param_dtype=jnp.float32, dtype=self.dtype,
                       name="tok_emb")
        if self.denoise_blocks:
            with jax.named_scope("denoise_io"):
                tokens = jnp.concatenate([tokens, clean], axis=1)
        x = emb(tokens) * self.embedding_multiplier
        pin = jax.lax.optimization_barrier if self.pin_stream else (
            lambda x: x)
        for i, (kind, ffn) in enumerate(zip(self.layer_kinds, ffn_kinds)):
            x = block_cls(mixers[kind], ffn, self.ffn_width,
                          self.residual_multiplier, self.norm_eps, self.dtype,
                          routed if ffn == "moe" else None,
                          name=f"block_{i}")(pin(x))
        x = pin(x)
        if self.denoise_blocks:
            with jax.named_scope("denoise_io"):
                x = x[:, :clean.shape[1]]
        x = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                       param_dtype=jnp.float32, name="norm_f")(x)
        # the head in the model's dtype as TransformerLM's: the table, or a
        # matrix of its own
        logits = emb.attend(x) if self.tied_head else _dense(
            self.vocab_size, self.dtype, "lm_head")(x)
        return logits.astype(jnp.float32) / self.logits_scaling
