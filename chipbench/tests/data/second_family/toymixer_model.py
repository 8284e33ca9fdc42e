"""Stands for a program's model of a second architecture (in a real PR: a
module under ``horovod_tpu/models``): a two-layer LM that is not
``TransformerLM`` and has no attention layer. Each block mixes tokens with a
causal running mean and positions with a SiLU MLP; RMSNorm, tied head. It
keeps the names the scope classes read (``block_<i>``, ``tok_emb``), and
computes in float32: the test it serves is of the seam, not of a precision,
and a toy's bf16 error swings with the steps a one-second window fits."""

import flax.linen as nn
import jax.numpy as jnp


class MixerBlock(nn.Module):
    width: int
    inner: int
    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        h = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name="norm")(x)
        seen = jnp.arange(1, x.shape[1] + 1, dtype=jnp.float32)[None, :, None]
        mean = jnp.cumsum(h.astype(jnp.float32), axis=1) / seen
        h = jnp.concatenate([h, mean.astype(self.dtype)], axis=-1)
        h = nn.Dense(self.inner, dtype=self.dtype, name="up")(h)
        return x + nn.Dense(self.width, dtype=self.dtype, name="down")(
            nn.silu(h))


class ToyMixerLM(nn.Module):
    vocab_rows: int
    width: int
    inner: int
    layers: int
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        emb = nn.Embed(self.vocab_rows, self.width, dtype=self.dtype,
                       embedding_init=nn.initializers.normal(
                           self.width ** -0.5), name="tok_emb")
        x = emb(tokens)
        for i in range(self.layers):
            x = MixerBlock(self.width, self.inner, self.eps, self.dtype,
                           name=f"block_{i}")(x)
        x = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name="norm_f")(x)
        return emb.attend(x)
