"""Decoder-only LM whose blocks are described by data: a hybrid of Mamba-2
(state-space) and attention layers.

``TransformerLM`` is one recipe. Here a model is a tuple of per-layer mixer
kinds (``"mamba"`` | ``"attention"``) and the widths of each; every block is

    x = x + r * mixer(RMSNorm(x));    x = x + r * ffn(RMSNorm(x))

with a SwiGLU feed-forward, and the model is ``tok_emb[t] * e`` -> blocks ->
RMSNorm -> tied head ``/ s`` (the Granite 4.0-H layout: ``r``, ``e``, ``s`` and
the attention scale are its four multipliers). The attention mixer has grouped
KV heads and no position encoding — the state-space layers carry the order —
and runs the same flash kernels as ``TransformerLM``; the Mamba-2 mixer is
``ops/ssd.py``. bf16 compute and f32 parameters, ``remat=`` with
``TransformerLM``'s three names and policies, and the module names the
trace's scope classes read (``block_<i>``, ``tok_emb``).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import ssd
from ..ops.pallas_kernels import flash_attention
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


class CausalConv(nn.Module):
    """Depthwise causal convolution over time, with bias. The kernel starts
    as torch leaves a depthwise ``Conv1d`` of this width, U(+-1/sqrt(width)),
    which is how the Mamba-2 authors' code starts it; the bias starts at 0."""
    width: int

    @nn.compact
    def __call__(self, x):
        bound = self.width ** -0.5

        def uniform(key, shape, dtype):
            return jax.random.uniform(key, shape, dtype, -bound, bound)

        kernel = self.param("kernel", uniform, (self.width, x.shape[-1]),
                            jnp.float32)
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


class AttentionMixer(nn.Module):
    """Causal attention with ``kv_heads`` key/value heads, each serving
    ``heads // kv_heads`` consecutive query heads; no bias, no position
    encoding, the caller's softmax scale. The flash kernels take as many KV
    heads as query heads, so each is handed to them ``heads // kv_heads``
    times."""
    heads: int
    kv_heads: int
    head_dim: int
    scale: float
    dtype: Any

    @nn.compact
    def __call__(self, h):
        b, t, d_model = h.shape

        def project(name, heads):
            return _dense(heads * self.head_dim, self.dtype, name)(h).reshape(
                b, t, heads, self.head_dim)

        q = project("q", self.heads)
        k, v = (jnp.repeat(project(name, self.kv_heads),
                           self.heads // self.kv_heads, axis=2)
                for name in ("k", "v"))
        out = flash_attention(q, k, v, causal=True, scale=self.scale)
        return _dense(d_model, self.dtype, "o")(
            out.astype(self.dtype).reshape(b, t, self.heads * self.head_dim))


class HybridBlock(nn.Module):
    mixer: Callable[..., nn.Module]     # makes the block's mixer, given name=
    ffn_width: int
    residual_multiplier: float
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        norm = partial(nn.RMSNorm, epsilon=self.eps, dtype=self.dtype,
                       param_dtype=jnp.float32)
        h = self.mixer(name="mixer")(norm(name="norm_mixer")(x))
        x = x + self.residual_multiplier * h
        gate, up = jnp.split(_dense(2 * self.ffn_width, self.dtype, "ffn_in")(
            norm(name="norm_ffn")(x)), 2, axis=-1)
        h = _dense(x.shape[-1], self.dtype, "ffn_out")(nn.silu(gate) * up)
        return x + self.residual_multiplier * h


class HybridLM(nn.Module):
    """Tokens ``[B, T]`` -> float32 logits ``[B, T, vocab_size]``."""
    vocab_size: int
    layer_kinds: Tuple[str, ...]        # "mamba" | "attention", one a layer
    d_model: int
    ffn_width: int
    attn_heads: int
    attn_kv_heads: int
    attn_head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_conv_width: int
    ssm_chunk: int
    attention_multiplier: float         # the softmax scale
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    norm_eps: float
    dtype: Any = jnp.bfloat16
    remat: str = "none"                 # as TransformerLM: REMAT_POLICIES

    @nn.compact
    def __call__(self, tokens):
        if self.remat not in REMAT_POLICIES:
            raise ValueError(f"remat={self.remat!r}; expected one of "
                             f"{sorted(REMAT_POLICIES)}")
        mixers = {
            "mamba": partial(MambaMixer, self.ssm_heads, self.ssm_head_dim,
                             self.ssm_state, self.ssm_conv_width,
                             self.ssm_chunk, self.norm_eps, self.dtype),
            "attention": partial(
                AttentionMixer, self.attn_heads, self.attn_kv_heads,
                self.attn_head_dim, self.attention_multiplier, self.dtype)}
        unknown = set(self.layer_kinds) - set(mixers)
        if unknown:
            raise ValueError(f"layer_kinds has {sorted(unknown)}; expected "
                             f"each of {sorted(mixers)}")
        use_remat, policy = REMAT_POLICIES[self.remat]
        block_cls = nn.remat(HybridBlock, policy=policy) if use_remat \
            else HybridBlock

        emb = nn.Embed(self.vocab_size, self.d_model,
                       embedding_init=nn.initializers.normal(0.02),
                       param_dtype=jnp.float32, dtype=self.dtype,
                       name="tok_emb")
        x = emb(tokens) * self.embedding_multiplier
        for i, kind in enumerate(self.layer_kinds):
            x = block_cls(mixers[kind], self.ffn_width,
                          self.residual_multiplier, self.norm_eps, self.dtype,
                          name=f"block_{i}")(x)
        x = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                       param_dtype=jnp.float32, name="norm_f")(x)
        # weight-tied head, in the model's dtype as TransformerLM's
        return emb.attend(x).astype(jnp.float32) / self.logits_scaling
