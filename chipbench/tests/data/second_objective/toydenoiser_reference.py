"""The toy denoiser's plain reference: float32 ``jax.numpy`` at ``highest``
matmul precision, written from the description in ``toydenoiser_model.py``
and importing nothing of it. Reads the parameter tree
(``tok_emb.embedding``, ``block_<i>.{norm,up,down}``, ``norm_f``) and
nothing else."""

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


@jax.jit
def _forward(params, noised, clean, eps):
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), params)
        table = p["tok_emb"]["embedding"]
        seen = table[clean]
        before = jnp.maximum(
            jnp.arange(clean.shape[1], dtype=jnp.float32), 1.0)
        x = table[noised] + (jnp.cumsum(seen, axis=1) - seen) \
            / before[None, :, None]
        for i in range(sum(1 for k in p if k.startswith("block_"))):
            b = p[f"block_{i}"]
            h = _rms_norm(x, b["norm"]["scale"], eps)
            h = jax.nn.silu(h @ b["up"]["kernel"] + b["up"]["bias"])
            x = x + h @ b["down"]["kernel"] + b["down"]["bias"]
        return _rms_norm(x, p["norm_f"]["scale"], eps) @ table.T


def forward(params, noised, clean, eps: float):
    """Logits ``[B, T, vocab_rows]`` in float32 on the noised copy's
    positions, for int ``noised`` and ``clean`` ``[B, T]``."""
    return _forward(params, noised, clean, eps)
