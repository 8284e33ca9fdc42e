"""Decoder-only LM whose blocks are described by data: a hybrid of sequence
mixers (Mamba-2, attention, gated short convolution) and feed-forwards
(SwiGLU, routed experts).

``TransformerLM`` is one recipe. Here a model is a tuple of per-layer mixer
kinds (``"mamba"`` | ``"attention"`` | ``"short_conv"``), a tuple of
per-layer feed-forward kinds (``"swiglu"`` | ``"moe"``) and the widths of
each; every block is

    x = x + r * mixer(RMSNorm(x));    x = x + r * ffn(RMSNorm(x))

and the model is ``tok_emb[t] * e`` -> blocks -> RMSNorm -> tied head ``/ s``
(``r``, ``e``, ``s`` and the attention scale are Granite 4.0-H's four
multipliers; each is 1, and the scale ``head_dim ** -0.5``, unless given).

* The attention mixer has grouped KV heads and runs the same flash kernels
  as ``TransformerLM``. Its position kind is ``"none"`` (Granite 4.0-H: the
  state-space layers carry the order) or ``"rope"`` (``ops/rope.py``), and
  it can normalise q and k per head before that (LFM2).
* The Mamba-2 mixer is ``ops/ssd.py``; the gated short convolution (LFM2's
  ``conv`` layer) is ``W_out (C * conv(B * u))`` over ``[B, C, u] = W_in h``
  with the same depthwise causal conv.
* The routed feed-forward is ``ops/moe.py``: sigmoid top-k routing that
  drops no token, told which experts it holds.

bf16 compute and f32 parameters, ``remat=`` with ``TransformerLM``'s three
names and policies, and the module names the trace's scope classes read
(``block_<i>``, ``tok_emb``).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import moe, ssd
from ..ops.pallas_kernels import flash_attention
from ..ops.rope import apply_rope
from .transformer import REMAT_POLICIES

def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=jnp.float32,
                    kernel_init=nn.initializers.normal(0.02), name=name)


class GatedRMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, y, gate):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],),
                           jnp.float32)
        return ssd.gated_rms_norm(y, gate, scale, self.eps)


def _conv_kernel_init(width: int):
    """As torch leaves a depthwise ``Conv1d`` of this width,
    U(+-1/sqrt(width)), which is how the Mamba-2 authors' code starts it."""
    bound = width ** -0.5

    def uniform(key, shape, dtype):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return uniform


class CausalConv(nn.Module):
    """Depthwise causal convolution over time, with bias (which starts at
    0; the kernel: :func:`_conv_kernel_init`)."""
    width: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", _conv_kernel_init(self.width),
                            (self.width, x.shape[-1]), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],),
                          jnp.float32)
        return ssd.causal_conv1d(x, kernel, bias)


class MambaMixer(nn.Module):
    """Mamba-2: one projection to gate ``z``, ``[x, B, C]`` and ``dt``; a
    causal conv and SiLU over ``[x, B, C]``; the scan; the gated norm; the
    output projection. One group of ``B`` and ``C``, shared by the heads."""
    heads: int
    head_dim: int
    state: int
    conv_width: int
    chunk: int
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, h):
        b, t, d_model = h.shape
        inner = self.heads * self.head_dim
        z, xbc, dt = jnp.split(
            _dense(2 * inner + 2 * self.state + self.heads, self.dtype,
                   "in_proj")(h),
            [inner, 2 * inner + 2 * self.state], axis=-1)
        xbc = nn.silu(CausalConv(self.conv_width, name="conv")(xbc))
        x, B, C = jnp.split(xbc, [inner, inner + self.state], axis=-1)

        def per_head(name, init):
            return self.param(name, lambda *_: init, (self.heads,))

        ones = jnp.ones((self.heads,), jnp.float32)
        a_log = per_head("A_log", jnp.log(jnp.arange(1, self.heads + 1,
                                                     dtype=jnp.float32)))
        dt = jax.nn.softplus(dt.astype(jnp.float32) + per_head("dt_bias", ones))
        y = ssd.ssd_chunked(x.reshape(b, t, self.heads, self.head_dim), dt,
                            -jnp.exp(a_log), B, C, per_head("D", ones),
                            chunk=self.chunk)
        y = GatedRMSNorm(self.eps, name="gate_norm")(y.reshape(b, t, inner), z)
        return _dense(d_model, self.dtype, "out_proj")(y)


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
    ``heads // kv_heads`` times."""
    heads: int
    kv_heads: int
    head_dim: int
    scale: float
    dtype: Any
    position: str = "none"
    rope_theta: float = 1e4
    qk_norm_eps: Optional[float] = None

    @nn.compact
    def __call__(self, h):
        b, t, d_model = h.shape

        def project(name, heads):
            return _dense(heads * self.head_dim, self.dtype, name)(h).reshape(
                b, t, heads, self.head_dim)

        q = project("q", self.heads)
        k, v = project("k", self.kv_heads), project("v", self.kv_heads)
        if self.qk_norm_eps is not None:
            def weight(name):
                return self.param(name, nn.initializers.ones,
                                  (self.head_dim,), jnp.float32)

            with jax.named_scope("qk_norm"):
                q = _head_rms_norm(q, weight("q_norm"), self.qk_norm_eps)
                k = _head_rms_norm(k, weight("k_norm"), self.qk_norm_eps)
        if self.position == "rope":
            with jax.named_scope("rope"):
                q, k = apply_rope(q, self.rope_theta), \
                    apply_rope(k, self.rope_theta)
        k, v = (jnp.repeat(a, self.heads // self.kv_heads, axis=2)
                for a in (k, v))
        out = flash_attention(q, k, v, causal=True, scale=self.scale)
        return _dense(d_model, self.dtype, "o")(
            out.astype(self.dtype).reshape(b, t, self.heads * self.head_dim))


class RoutedFeedForward(nn.Module):
    """``ops/moe.routed_ffn`` with its parameters: the router over all
    ``experts`` and its selection bias (float32), and the SwiGLU weights of
    the experts ``held`` here, ``width`` wide. Sows the experts each token
    chose, their scores and the tokens an expert (``intermediates``: free
    unless asked for)."""
    experts: int
    held: Tuple[int, ...]
    top_k: int
    width: int
    dtype: Any

    @nn.compact
    def __call__(self, h):
        b, t, d = h.shape
        init, n_held = nn.initializers.normal(0.02), len(self.held)
        y, chosen, scores, load = moe.routed_ffn(
            h.reshape(b * t, d),
            self.param("router", init, (d, self.experts), jnp.float32),
            self.param("expert_bias", nn.initializers.zeros, (self.experts,),
                       jnp.float32),
            self.param("w_in", init, (n_held, d, 2 * self.width), jnp.float32),
            self.param("w_out", init, (n_held, self.width, d), jnp.float32),
            held=self.held, top_k=self.top_k)
        self.sow("intermediates", "chosen", chosen.reshape(b, t, self.top_k))
        self.sow("intermediates", "scores", scores.reshape(b, t, self.experts))
        self.sow("intermediates", "load", load)
        return y.reshape(b, t, d)


class HybridBlock(nn.Module):
    mixer: Callable[..., nn.Module]     # makes the block's mixer, given name=
    ffn_width: int                      # of the SwiGLU feed-forward
    residual_multiplier: float
    eps: float
    dtype: Any
    routed_ffn: Optional[Callable[..., nn.Module]] = None   # in its place

    @nn.compact
    def __call__(self, x):
        norm = partial(nn.RMSNorm, epsilon=self.eps, dtype=self.dtype,
                       param_dtype=jnp.float32)
        h = self.mixer(name="mixer")(norm(name="norm_mixer")(x))
        x = x + self.residual_multiplier * h
        h = norm(name="norm_ffn")(x)
        if self.routed_ffn is not None:
            h = self.routed_ffn(name="ffn")(h)
        else:
            gate, up = jnp.split(
                _dense(2 * self.ffn_width, self.dtype, "ffn_in")(h), 2, axis=-1)
            h = _dense(x.shape[-1], self.dtype, "ffn_out")(nn.silu(gate) * up)
        return x + self.residual_multiplier * h


class HybridLM(nn.Module):
    """Tokens ``[B, T]`` -> float32 logits ``[B, T, vocab_size]``."""
    vocab_size: int
    layer_kinds: Tuple[str, ...]        # "mamba" | "attention" | "short_conv"
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
    ffn_kinds: Tuple[str, ...] = ()     # "swiglu" | "moe"; () = all "swiglu"
    moe_experts: int = 0                # the router's width
    moe_held: Tuple[int, ...] = ()      # ids of the experts held here
    moe_top_k: int = 0
    moe_width: int = 0                  # of one expert's SwiGLU

    @nn.compact
    def __call__(self, tokens):
        if self.remat not in REMAT_POLICIES:
            raise ValueError(f"remat={self.remat!r}; expected one of "
                             f"{sorted(REMAT_POLICIES)}")
        if self.attn_position not in ("none", "rope"):
            raise ValueError(f"attn_position={self.attn_position!r}; "
                             f"expected 'none' or 'rope'")
        scale = self.attn_head_dim ** -0.5 \
            if self.attention_multiplier is None else self.attention_multiplier
        mixers = {
            "mamba": partial(MambaMixer, self.ssm_heads, self.ssm_head_dim,
                             self.ssm_state, self.ssm_conv_width,
                             self.ssm_chunk, self.norm_eps, self.dtype),
            "attention": partial(
                AttentionMixer, self.attn_heads, self.attn_kv_heads,
                self.attn_head_dim, scale, self.dtype, self.attn_position,
                self.attn_rope_theta,
                self.norm_eps if self.attn_qk_norm else None),
            "short_conv": partial(ShortConvMixer, self.conv_width,
                                  self.dtype)}
        unknown = set(self.layer_kinds) - set(mixers)
        if unknown:
            raise ValueError(f"layer_kinds has {sorted(unknown)}; expected "
                             f"each of {sorted(mixers)}")
        ffn_kinds = self.ffn_kinds or ("swiglu",) * len(self.layer_kinds)
        if len(ffn_kinds) != len(self.layer_kinds) \
                or set(ffn_kinds) - {"swiglu", "moe"}:
            raise ValueError(f"ffn_kinds={ffn_kinds!r}; expected 'swiglu' or "
                             f"'moe' for each of {len(self.layer_kinds)} layers")
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
            routed = partial(RoutedFeedForward, self.moe_experts, held,
                             self.moe_top_k, self.moe_width, self.dtype)
        use_remat, policy = REMAT_POLICIES[self.remat]
        block_cls = nn.remat(HybridBlock, policy=policy) if use_remat \
            else HybridBlock

        emb = nn.Embed(self.vocab_size, self.d_model,
                       embedding_init=nn.initializers.normal(0.02),
                       param_dtype=jnp.float32, dtype=self.dtype,
                       name="tok_emb")
        x = emb(tokens) * self.embedding_multiplier
        for i, (kind, ffn) in enumerate(zip(self.layer_kinds, ffn_kinds)):
            x = block_cls(mixers[kind], self.ffn_width,
                          self.residual_multiplier, self.norm_eps, self.dtype,
                          routed if ffn == "moe" else None,
                          name=f"block_{i}")(x)
        x = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                       param_dtype=jnp.float32, name="norm_f")(x)
        # weight-tied head, in the model's dtype as TransformerLM's
        return emb.attend(x).astype(jnp.float32) / self.logits_scaling
