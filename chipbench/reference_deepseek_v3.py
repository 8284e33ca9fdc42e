"""The plain reference of the DeepSeek-V3 family as kanana-2 configures it
(``"model_type": "deepseek_v3"``, ``q_lora_rank: null``): forward pass,
loss and gradients in float32 ``jax.numpy``, every matrix product under
``jax.default_matmul_precision("highest")``.

No kernel, no cache, no sort, no grouped product, no bf16. Block ``l``,
pre-norm, RMSNorm (weight, ``rms_norm_eps``), no bias anywhere:
``h = x + MLA(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``; table ->
blocks -> RMSNorm -> the untied head ``logits = x W_head``.

* ``MLA``, ``u`` the normed input, ``H = num_attention_heads``: ``q = u
  W_q``, ``[T, H, qk_nope_head_dim + qk_rope_head_dim]``, a head ``[q_nope
  | q_rope]``; ``[c | k_r] = u W_kva``, ``[T, kv_lora_rank +
  qk_rope_head_dim]``; ``c' = RMSNorm(c)`` (its own weight, the same eps);
  ``[k_nope | v] = c' W_kvb``, ``[T, H, qk_nope_head_dim + v_head_dim]``.
  ``q_rope`` and the one ``k_r``, which every head shares, turn by
  position: pair ``i`` is elements ``2 i`` and ``2 i + 1``
  (``rope_interleave``), angle ``t rope_theta^(-2 i / qk_rope_head_dim)``,
  tables in float64, no scaling. ``k_h = [k_nope_h | k_r]``; a causal
  softmax of ``q_h k_h^T / sqrt(qk_head_dim)`` over the whole ``[T, T]``
  scores of a head, a block of queries at a time so that 16,384 positions
  fit; ``o_h = P v_h``; ``concat_h(o_h) W_o``.
* Layers below ``first_k_dense_replace``: ``W_2 (silu(a) * b)``, ``[a, b]
  = W_1 u``. The others: ``s = sigmoid(u W_r)`` over **all** experts; a
  token's experts are the ``num_experts_per_tok`` with the largest ``s +
  bias`` (``noaux_tc``; one group, so no group limit); ``w_e =
  routed_scaling_factor s_e / (sum over the chosen of s + 1e-20)``; ``sum
  over e chosen and held of w_e SwiGLU_e(u)`` plus the shared SwiGLU expert
  (``n_shared_experts moe_intermediate_size`` wide), whole. **The same
  share** as the program is given (``held_experts``, the sliced table and
  head), each held expert computed over every token and masked by ``w``.
  What the experts held elsewhere would add is left out, here as there.

**Departures from the published modelling code**: it permutes ``q_rope``
and ``k_r`` into the rotate-half layout before turning them (the same
permutation on both, so every score is the same); here the pairs turn in
place. It computes the router's scores from float32 logits, as here.

**Ties and block by block**: as ``reference_lfm2_moe`` (its docstring and
its :func:`choose`): :func:`forward_from_program` gives every reference
block the program's own input to it, takes the program's choice of experts
only where every expert in which it differs lies within ``tau`` of the
reference's own boundary ``(6th + 7th) / 2``, and measures the program's
*update* against the reference's. :func:`forward` is the free-running pass
(:func:`forward_following` the same pass under the tie rule against a
program's routing), :func:`loss` the job's loss over it,
:func:`loss_and_grads` its gradients.

It reads the program's parameter tree by its flax names
(``models/hybrid.py``) and nothing else of the program; what was assumed is
in the configuration file's ``assumed``.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .reference_lfm2_moe import choose

#: queries a block of the masked softmax takes
QUERY_BLOCK = 1024
#: added to the sum of a token's chosen scores before it divides
NORM_EPS = 1e-20


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def rotary_tables(theta: float, width: int, t: int):
    """``(cos, sin)``, each ``[t, width / 2]`` float32, computed in
    float64: pair ``i`` turns by ``position theta^(-2 i / width)``."""
    freq = float(theta) ** (-2.0 * np.arange(width // 2, dtype=np.float64)
                            / width)
    angle = np.arange(t, dtype=np.float64)[:, None] * freq
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def _rotate_pairs(x, cos, sin):
    """``x`` ``[b, t, heads, width]``: elements ``2 i`` and ``2 i + 1``
    turned by the angle of pair ``i``, in place."""
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _latent_attention(p, h, heads, rank, nope, v_dim, eps, cos, sin):
    b, t, _ = h.shape
    q = (h @ p["q"]["kernel"]).reshape(b, t, heads, -1)
    latent = h @ p["kv_a"]["kernel"]
    c, k_r = latent[..., :rank], latent[..., rank:]
    kv = (_rms_norm(c, p["kv_norm"]["scale"], eps)
          @ p["kv_b"]["kernel"]).reshape(b, t, heads, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope],
                         _rotate_pairs(q[..., nope:], cos, sin)], axis=-1)
    k_r = _rotate_pairs(k_r[:, :, None, :], cos, sin)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_r, (b, t, heads, k_r.shape[-1]))], axis=-1)
    scale = q.shape[-1] ** -0.5
    rows = min(QUERY_BLOCK, t)
    blocks = t // rows

    def one(at):           # one sequence, one head, one block of queries
        seq, i, block = at // (heads * blocks), at // blocks % heads, \
            at % blocks
        first = block * rows
        qs = jax.lax.dynamic_slice_in_dim(q[seq, :, i], first, rows)
        s = scale * qs @ k[seq, :, i].T                        # [rows, t]
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(seen, s, -jnp.inf)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        return (e / jnp.sum(e, axis=-1, keepdims=True)) @ v[seq, :, i]

    out = jax.lax.map(one, jnp.arange(b * heads * blocks))
    out = out.reshape(b, heads, t, v_dim).transpose(0, 2, 1, 3)
    return out.reshape(b, t, heads * v_dim) @ p["o"]["kernel"]


def _swiglu(h, w_in, w_out):
    a, b_ = jnp.split(h @ w_in, 2, axis=-1)
    return (_silu(a) * b_) @ w_out


def _routed(p, h, held, top_k, scale, program, tau):
    scores = _sigmoid(h @ p["router"])                     # [b, t, E]
    use, stats = choose(scores + p["expert_bias"], top_k,
                        None if program is None else program["chosen"], tau)
    if program is not None:
        stats["score_rms"] = jnp.sqrt(jnp.mean(
            (program["scores"] - scores) ** 2))
    picked = jnp.where(use, scores, 0.0)
    weights = scale * picked / (jnp.sum(picked, axis=-1, keepdims=True)
                                + NORM_EPS)

    def expert(y, at):                 # one held expert over every token
        w_in, w_out, e = at
        w = jnp.take(weights, e, axis=-1)[..., None]
        return y + w * _swiglu(h, w_in, w_out), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (p["w_in"], p["w_out"], jnp.asarray(held)))
    return y + _swiglu(h, p["shared_in"]["kernel"],
                       p["shared_out"]["kernel"]), stats


@partial(jax.jit, static_argnames=("heads", "rank", "nope", "v_dim", "eps",
                                   "held", "top_k", "scale"))
def block(p, x, cos, sin, program, tau, heads, rank, nope, v_dim, eps, held,
          top_k, scale):
    """One block, ``x`` ``[b, t, d]`` float32: latent attention, and the
    routed feed-forward where the block has one (``"ffn"`` in ``p``), else
    the dense one. Returns ``(x, stats)``."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), p)
        x = x + _latent_attention(
            p["mixer"], _rms_norm(x, p["norm_mixer"]["scale"], eps), heads,
            rank, nope, v_dim, eps, cos, sin)
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
    c = config
    cos, sin = rotary_tables(c["rope_theta"], c["qk_rope_head_dim"],
                             x.shape[1])
    return block(params[f"block_{i}"], x, cos, sin, program,
                 jnp.float32(tau), c["num_attention_heads"],
                 c["kv_lora_rank"], c["qk_nope_head_dim"], c["v_head_dim"],
                 float(c["rms_norm_eps"]), tuple(c["held_experts"]),
                 c["num_experts_per_tok"], float(c["routed_scaling_factor"]))


def forward_following(params, tokens, config: dict,
                      routing: Dict[str, dict],
                      tau: float) -> Tuple[jax.Array, List[dict]]:
    """``(logits, one stats dict a routed block)``: the free-running pass
    on the reference's own stream, under the tie rule at width ``tau``
    against ``routing`` (``"block_<i>"`` to that layer's ``{"chosen",
    "scores"}`` as a program computed them): a layer takes the program's
    choice only where every expert in which it differs lies within ``tau``
    of the reference's own boundary there. With no routing every choice is
    the reference's own."""
    x = params["tok_emb"]["embedding"].astype(jnp.float32)[tokens]
    stats = []
    for i in range(config["num_hidden_layers"]):
        name = f"block_{i}"
        x, layer = _block_of(params, config, i, x, routing.get(name), tau)
        if layer:
            stats.append({"layer": name, **layer})
    return head(params, x, float(config["rms_norm_eps"])), stats


def forward(params, tokens, config: dict):
    """Logits ``[B, T, rows]`` in float32 for int tokens ``[B, T]``, from
    the configuration's keys: the free-running forward pass, every choice
    the reference's own."""
    return forward_following(params, tokens, config, {}, 0.0)[0]


def loss(params, tokens, targets, config: dict):
    """The job's loss over :func:`forward`: the mean over every position of
    the cross-entropy of the float32 logits against ``targets``."""
    logits = forward(params, tokens, config)
    top = jnp.max(logits, axis=-1, keepdims=True)
    log_z = top[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1))
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(log_z - picked)


def loss_and_grads(params, tokens, targets, config: dict):
    """``(loss, its gradient in every parameter)``, by ``jax.grad`` of the
    forward pass above (the selection bias steers a choice and has none)."""
    return jax.value_and_grad(loss)(params, tokens, targets, config)


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
