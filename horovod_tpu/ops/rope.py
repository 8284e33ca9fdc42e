"""Rotary position embedding in the rotate-half layout (Su et al.,
"RoFormer", 2021, as the GPT-NeoX / Hugging Face modelling code lays it
out): pair ``i`` of a head is elements ``i`` and ``i + D/2``, turned by the
angle ``position * theta ** (-2 i / D)``."""

from __future__ import annotations

import jax.numpy as jnp


def apply_rope(x, theta: float, positions=None):
    """``x`` ``[B, T, H, D]`` (D even) turned by its positions (``arange(T)``
    unless given, ``[T]``). Angles, sines and the rotation are float32; the
    result is in ``x.dtype``."""
    half = x.shape[-1] // 2
    if positions is None:
        positions = jnp.arange(x.shape[1])
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq    # [T, D/2]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)
