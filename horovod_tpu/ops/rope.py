"""Rotary position embedding in the rotate-half layout (Su et al.,
"RoFormer", 2021, as the GPT-NeoX / Hugging Face modelling code lays it
out): pair ``i`` of a head is elements ``i`` and ``i + D/2``, turned by the
angle ``position * theta ** (-2 i / D)``. A head may turn only its first
``rotary_dim`` elements (pair ``i`` is then ``i`` and ``i + rotary_dim/2``;
the rest pass through), by frequencies that are given and not derived
(:func:`yarn_inv_freq`), with cos and sin scaled by a factor. The other
layout of the same turn pairs neighbouring elements (pair ``i`` is ``2 i``
and ``2 i + 1``, the paper's own and DeepSeek-V3's ``rope_interleave``):
``interleaved=True``."""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np


def yarn_inv_freq(theta: float, rotary_dim: int, factor: float,
                  original_max_position: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> Tuple[float, ...]:
    """The ``rotary_dim / 2`` frequencies of YaRN (Peng et al., 2023) as
    ``transformers``' ``_compute_yarn_parameters`` lays them out: pair
    ``i`` turns at ``theta ** (-2 i / rotary_dim)`` where it makes more than
    ``beta_fast`` rotations over ``original_max_position`` positions, at
    that over ``factor`` where it makes fewer than ``beta_slow``, and at a
    linear blend of the two between the (whole) pairs at which the two
    counts are reached. They do not depend on a sequence's length. Python
    floats, so that they can be a model's data."""
    def pair_at(rotations):
        return rotary_dim * math.log(original_max_position / (
            rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_at(beta_fast)), 0)
    high = min(math.ceil(pair_at(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    half = rotary_dim // 2
    plain = theta ** (-np.arange(half, dtype=np.float64) / half)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    return tuple(float(f) for f in plain / factor * ramp + plain * (1 - ramp))


def apply_rope(x, theta: float, positions=None, *,
               rotary_dim: Optional[int] = None,
               inv_freq: Optional[Sequence[float]] = None,
               factor: float = 1.0, interleaved: bool = False):
    """``x`` ``[B, T, H, D]`` (D even) turned by its positions (``arange(T)``
    unless given, ``[T]``). Angles, sines and the rotation are float32; the
    result is in ``x.dtype``. ``rotary_dim`` (even, at most D; D unless
    given) elements of a head turn and the rest pass through; ``inv_freq``
    (``rotary_dim / 2`` of them) takes the place of ``theta``'s; ``factor``
    multiplies cos and sin. ``interleaved``: pair ``i`` is elements ``2 i``
    and ``2 i + 1``, turned in place, and not ``i`` and ``i + width / 2``."""
    width = x.shape[-1] if rotary_dim is None else rotary_dim
    half = width // 2
    if positions is None:
        positions = jnp.arange(x.shape[1])
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq    # [T, D/2]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    if factor != 1.0:
        cos, sin = factor * cos, factor * sin
    xf = x.astype(jnp.float32)
    turned, rest = (xf, None) if width == x.shape[-1] else (
        xf[..., :width], xf[..., width:])
    if interleaved:
        x1, x2 = turned[..., 0::2], turned[..., 1::2]
        parts = [jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).reshape(turned.shape)]
    else:
        x1, x2 = jnp.split(turned, 2, axis=-1)
        parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if rest is not None:
        parts.append(rest)
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)
