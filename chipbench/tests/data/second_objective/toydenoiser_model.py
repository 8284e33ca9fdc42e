"""Stands for a program's model of a second training objective (in a real
PR: a module under ``horovod_tpu/models`` and a loss beside ``lm_loss``): a
two-layer LM that takes two token arrays, a noised copy of a sequence and the
clean one, and gives logits on the noised copy's positions; and the loss
that goes with it, a weighted cross entropy against the token at the same
position. A position sees its own noised token and the mean of the clean
tokens before it; the blocks are a SiLU MLP a position; RMSNorm, tied head.
It keeps the names the scope classes read (``block_<i>``, ``tok_emb``,
``loss``), and computes in float32, as ``second_family``'s toy does."""

import flax.linen as nn
import jax
import jax.numpy as jnp


class DenoiserBlock(nn.Module):
    width: int
    inner: int
    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        h = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name="norm")(x)
        h = nn.Dense(self.inner, dtype=self.dtype, name="up")(h)
        return x + nn.Dense(self.width, dtype=self.dtype, name="down")(
            nn.silu(h))


class ToyDenoiserLM(nn.Module):
    vocab_rows: int
    width: int
    inner: int
    layers: int
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, noised, clean):
        emb = nn.Embed(self.vocab_rows, self.width, dtype=self.dtype,
                       embedding_init=nn.initializers.normal(
                           self.width ** -0.5), name="tok_emb")
        seen = emb(clean).astype(jnp.float32)
        before = jnp.arange(clean.shape[1], dtype=jnp.float32)[None, :, None]
        context = (jnp.cumsum(seen, axis=1) - seen) / jnp.maximum(before, 1.0)
        x = emb(noised) + context.astype(self.dtype)
        for i in range(self.layers):
            x = DenoiserBlock(self.width, self.inner, self.eps, self.dtype,
                              name=f"block_{i}")(x)
        x = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name="norm_f")(x)
        return emb.attend(x)


@jax.named_scope("loss")
def weighted_loss(logits, clean, weights):
    """``sum_i weights_i CE(logits_i, clean_i)`` over the tokens of the
    batch: no shift, a position of weight nought is not trained on."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, clean[..., None], axis=-1)[..., 0]
    return jnp.sum(weights * nll) / nll.size
