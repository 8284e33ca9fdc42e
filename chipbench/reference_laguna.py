"""The plain reference of the Laguna family (``"model_type": "laguna"``):
its forward pass in float32 ``jax.numpy``, every matrix product under
``jax.default_matmul_precision("highest")``.

No kernel, no cache, no sort, no grouped product, no bf16. Block ``l``,
pre-norm, RMSNorm (weight, ``rms_norm_eps``), no bias anywhere:
``h = x + Attn_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``; table ->
blocks -> RMSNorm -> the untied head ``logits = x W_head``.

* ``Attn_l``, ``u`` the normed input, ``H_l`` =
  ``num_attention_heads_per_layer[l]`` query heads over
  ``num_key_value_heads`` KV heads of ``head_dim``: ``q = u Wq``,
  ``k = u Wk``, ``v = u Wv``; the layer kind's rotary turn on q and k;
  query head ``j`` reads KV head ``j // (H_l / kv)``; a masked softmax of
  ``q k^T / sqrt(head_dim)`` over the whole ``[T, T]`` scores of a head, a
  block of queries at a time so that 8192 positions fit: the query at ``i``
  sees keys ``j <= i`` and, where ``layer_types[l]`` is
  ``sliding_attention``, only ``i - sliding_window < j`` (its last
  ``sliding_window`` positions, itself among them); ``a = P v``; the gate
  ``g = sigmoid(u Wg)``, one scalar a head and position; ``concat_j(g_j
  a_j) Wo``.
* The rotary turn, rotate-half layout, its tables written out
  (:func:`rotary_tables`): ``rope_type`` ``default`` is the base over the
  rotary width; ``yarn`` blends ``base^(-2i/r)`` with that over ``factor``
  by the linear ramp between the correction pairs of ``beta_fast`` and
  ``beta_slow`` at ``original_max_position_embeddings``, and multiplies cos
  and sin by ``attention_factor``; ``partial_rotary_factor`` of a head's
  width turns (pair ``i`` is elements ``i`` and ``i + r/2``), the rest
  passes through.
* ``mlp_layer_types[l]`` ``dense``: ``W_2 (silu(a) * b)``, ``[a, b] = W_1
  u``. ``sparse``: ``p = softmax(u W_r)`` over **all** experts; a token's
  experts are the ``num_experts_per_tok`` with the largest ``p`` (plus a
  bias that is zero); ``w_e = moe_routed_scaling_factor * p_e / (sum over
  the chosen of p)``; ``sum over e chosen and held of w_e SwiGLU_e(u)``
  plus the shared SwiGLU expert, whole. **The same share** as the program
  is given (``held_experts``, the sliced table and head), each held expert
  computed over every token and masked by ``w``. What the experts held
  elsewhere would add is left out, here as there.

**Ties and block by block**: as ``reference_lfm2_moe`` (its docstring and
its :func:`choose`): :func:`forward_from_program` gives every reference
block the program's own input to it, takes the program's choice of experts
only where every expert in which it differs lies within ``tau`` of the
reference's own boundary ``(10th + 11th) / 2``, and measures the program's
*update* against the reference's. :func:`forward` is the free-running pass.

It reads the program's parameter tree by its flax names
(``models/hybrid.py``) and nothing else of the program; the departures from
the published model are in the configuration file's ``assumed``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .reference_lfm2_moe import choose

#: queries a block of the masked softmax takes
QUERY_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def rotary_tables(rope: dict, head_dim: int, t: int):
    """``(cos, sin)``, each ``[t, r / 2]`` float32 with ``r`` the rotary
    width, of one entry of ``rope_parameters``, computed in float64."""
    r = int(head_dim * rope.get("partial_rotary_factor", 1))
    base = float(rope["rope_theta"])
    i = np.arange(r // 2, dtype=np.float64)
    freq = base ** (-2.0 * i / r)
    scale = 1.0
    if rope["rope_type"] == "yarn":
        def pair_at(rotations):
            return r * math.log(rope["original_max_position_embeddings"]
                                / (rotations * 2 * math.pi)) \
                / (2 * math.log(base))

        low = max(math.floor(pair_at(rope["beta_fast"])), 0)
        high = min(math.ceil(pair_at(rope["beta_slow"])), r - 1)
        ramp = np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
        freq = freq / rope["factor"] * ramp + freq * (1.0 - ramp)
        scale = float(rope["attention_factor"])
    elif rope["rope_type"] != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    angle = np.arange(t, dtype=np.float64)[:, None] * freq
    return (jnp.asarray(scale * np.cos(angle), jnp.float32),
            jnp.asarray(scale * np.sin(angle), jnp.float32))


def _rotate(x, cos, sin):
    """``x`` ``[b, t, heads, hd]``: its first ``2 * cos.shape[-1]`` elements
    turned, the rest as they are."""
    half = cos.shape[-1]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(p, h, heads, kv_heads, window, cos, sin):
    b, t, _ = h.shape
    q = (h @ p["q"]["kernel"]).reshape(b, t, heads, -1)
    k = (h @ p["k"]["kernel"]).reshape(b, t, kv_heads, -1)
    v = (h @ p["v"]["kernel"]).reshape(b, t, kv_heads, -1)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    scale = q.shape[-1] ** -0.5
    rows = min(QUERY_BLOCK, t)
    blocks = t // rows

    def one(at):           # one sequence, one query head, one query block
        seq, i, block = at // (heads * blocks), at // blocks % heads, \
            at % blocks
        kv = i // (heads // kv_heads)
        first = block * rows
        qs = jax.lax.dynamic_slice_in_dim(q[seq, :, i], first, rows)
        s = scale * qs @ k[seq, :, kv].T                       # [rows, t]
        delta = (first + jnp.arange(rows))[:, None] - jnp.arange(t)[None, :]
        seen = delta >= 0
        if window:
            seen = seen & (delta < window)
        s = jnp.where(seen, s, -jnp.inf)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        return (e / jnp.sum(e, axis=-1, keepdims=True)) @ v[seq, :, kv]

    out = jax.lax.map(one, jnp.arange(b * heads * blocks))
    out = out.reshape(b, heads, t, -1).transpose(0, 2, 1, 3)  # [b,t,H,hd]
    gate = _sigmoid(h @ p["gate"]["kernel"])                   # [b, t, H]
    return (out * gate[..., None]).reshape(b, t, -1) @ p["o"]["kernel"]


def _swiglu(h, w_in, w_out):
    a, b_ = jnp.split(h @ w_in, 2, axis=-1)
    return (_silu(a) * b_) @ w_out


def _routed(p, h, held, top_k, scale, program, tau):
    logits = h @ p["router"]                               # [b, t, E]
    e = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    scores = e / jnp.sum(e, axis=-1, keepdims=True)
    use, stats = choose(scores + p["expert_bias"], top_k,
                        None if program is None else program["chosen"], tau)
    if program is not None:
        stats["score_rms"] = jnp.sqrt(jnp.mean(
            (program["scores"] - scores) ** 2))
    picked = jnp.where(use, scores, 0.0)
    weights = scale * picked / jnp.sum(picked, axis=-1, keepdims=True)

    def expert(y, at):                 # one held expert over every token
        w_in, w_out, e = at
        w = jnp.take(weights, e, axis=-1)[..., None]
        return y + w * _swiglu(h, w_in, w_out), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (p["w_in"], p["w_out"], jnp.asarray(held)))
    return y + _swiglu(h, p["shared_in"]["kernel"],
                       p["shared_out"]["kernel"]), stats


@partial(jax.jit, static_argnames=("heads", "kv_heads", "window", "eps",
                                   "held", "top_k", "scale"))
def block(p, x, cos, sin, program, tau, heads, kv_heads, window, eps, held,
          top_k, scale):
    """One block, ``x`` ``[b, t, d]`` float32: attention over ``heads``
    query heads (``window`` 0: every earlier position), and the routed
    feed-forward where the block has one (``"ffn"`` in ``p``), else the
    dense one. Returns ``(x, stats)``."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), p)
        x = x + _attention(p["mixer"], _rms_norm(
            x, p["norm_mixer"]["scale"], eps), heads, kv_heads, window, cos,
            sin)
        h = _rms_norm(x, p["norm_ffn"]["scale"], eps)
        if "ffn" in p:
            y, stats = _routed(p["ffn"], h, held, top_k, scale, program, tau)
            return x + y, stats
        return x + _swiglu(h, p["ffn_in"]["kernel"],
                           p["ffn_out"]["kernel"]), {}


@partial(jax.jit, static_argnames=("eps",))
def head(params, x, eps):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, params["norm_f"]["scale"].astype(jnp.float32), eps)
        return x @ params["lm_head"]["kernel"].astype(jnp.float32)


def _block_of(params, config: dict, i: int, x, program, tau):
    """``block_<i>`` of the configuration on ``x``: ``(output, stats)``."""
    c, kind = config, config["layer_types"][i]
    cos, sin = rotary_tables(c["rope_parameters"][kind], c["head_dim"],
                             x.shape[1])
    return block(params[f"block_{i}"], x, cos, sin, program,
                 jnp.float32(tau), c["num_attention_heads_per_layer"][i],
                 c["num_key_value_heads"],
                 c["sliding_window"] if kind == "sliding_attention" else 0,
                 float(c["rms_norm_eps"]), tuple(c["held_experts"]),
                 c["num_experts_per_tok"],
                 float(c["moe_routed_scaling_factor"]))


def forward(params, tokens, config: dict):
    """Logits ``[B, T, rows]`` in float32 for int tokens ``[B, T]``, from
    the configuration's keys: the free-running forward pass, every choice
    the reference's own."""
    x = params["tok_emb"]["embedding"].astype(jnp.float32)[tokens]
    for i in range(config["num_hidden_layers"]):
        x, _ = _block_of(params, config, i, x, None, 0.0)
    return head(params, x, float(config["rms_norm_eps"]))


@jax.jit
def _update_error(got_out, want_out, x_in):
    """The program's update of the stream against the reference's, as the
    rms of the difference over the rms of the reference's update."""
    got_out = got_out.astype(jnp.float32)
    return jnp.sqrt(jnp.mean((got_out - want_out) ** 2)
                    / jnp.mean((want_out - x_in) ** 2))


def forward_from_program(params, tokens, config: dict, program_outputs,
                         routing: Dict[str, dict],
                         tau: float) -> Tuple[jax.Array, List[dict]]:
    """``(logits, one stats dict a block)`` with every block computed from
    the **program's** input to it, as ``reference_lfm2_moe``'s function of
    this name: ``program_outputs[i]`` is the program's output of
    ``block_<i>``, ``routing`` maps ``"block_<i>"`` to that layer's
    ``{"chosen", "scores"}`` as the program computed them, for the tie rule
    at width ``tau``. The logits are the head over the last reference
    block's output."""
    x = params["tok_emb"]["embedding"].astype(jnp.float32)[tokens]
    stats = []
    for i in range(config["num_hidden_layers"]):
        name = f"block_{i}"
        out, layer = _block_of(params, config, i, x, routing.get(name), tau)
        stats.append({"layer": name, **layer, "update_error": _update_error(
            program_outputs[i], out, x)})
        x = program_outputs[i].astype(jnp.float32)
    return head(params, out, float(config["rms_norm_eps"])), stats
