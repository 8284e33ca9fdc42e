"""The plain reference of the SDAR expert family (``"model_type":
"sdar_moe"``), a model trained by denoising blocks: its forward pass, its
loss and (at test size) its gradients in float32 ``jax.numpy``, every matrix
product under ``jax.default_matmul_precision("highest")``.

No kernel, no tile, no table of live tiles, no sort, no grouped product, no
bf16. **The input**: a clean sequence ``x`` of ``T`` ids and a noised copy
``x~`` of it (``chipbench/objectives/block_denoise.py`` makes both). The
model runs on ``2 T`` rows, ``[x~ | x]``: rows ``0..T-1`` noised, ``T..2T-1``
clean. Row ``i`` has half ``h_i`` (clean or noised), position ``p_i = i mod
T`` (both halves carry positions ``0..T-1``) and block ``b_i = p_i // B``
with ``B`` the configuration's ``assumed.block_length``.

Block ``l``, pre-norm, RMSNorm (weight, ``rms_norm_eps``), no bias anywhere,
every block alike: ``x += Attn(RMSNorm(x))``, ``x += MoE(RMSNorm(x))``; table
-> blocks -> **the noised half's rows** -> RMSNorm -> the untied head.

* ``Attn``, ``u`` the normed input, ``H`` = ``num_attention_heads`` query
  heads over ``num_key_value_heads`` KV heads of ``head_dim``: ``q = u Wq``,
  ``k = u Wk``, ``v = u Wv``; q and k RMS-normed over a head (a
  ``[head_dim]`` weight each); the rotary turn over the whole head,
  rotate-half layout (pair ``i`` is elements ``i`` and ``i + head_dim / 2``;
  ``reference_laguna._rotate`` over this module's tables), **row ``i`` by
  the angle ``p_i rope_theta^(-2i/head_dim)``**, its own position and not
  its row (:func:`rotary_rows`, float64 tables); query head
  ``j`` reads KV head ``j // (H / kv)``; scores ``q k^T head_dim^-0.5``; a
  softmax over the keys **the mask allows**, a block of query rows at a time
  against every key so that a head's ``[2T, 2T]`` scores never exist at
  once; ``concat_j(P_j v) Wo``.
* **The mask** (:func:`seen`), its three clauses written out: query row
  ``i`` sees key row ``j`` iff ``j`` is clean and ``b_j < b_i``; or ``j`` is
  clean, ``b_j = b_i`` and ``i`` is clean; or ``j`` and ``i`` are both
  noised and ``b_j = b_i``. Clean rows attend block-causally among
  themselves; a noised row sees the clean blocks strictly before its own
  and the noised rows of its own block; nothing else sees a noised row.
* ``MoE``: ``p = softmax(u W_r)`` over **all** experts; a token's experts
  are the ``num_experts_per_tok`` with the largest ``p`` (plus a bias that
  is zero); ``w_e = p_e / (sum over the chosen of p)`` (``norm_topk_prob``);
  ``sum over e chosen and held of w_e W2_e (silu(W1_e u) * W3_e u)``; no
  shared expert. **The same share** as the program is given
  (``held_experts``, the sliced table and head), each held expert computed
  over every row and masked by ``w``. What the experts held elsewhere would
  add is left out, here as there.
* **The loss** (:func:`loss`): ``(1 / (batch T)) sum_i weights_i
  CE(logits_i, x_i)`` over the noised half's logits at the same position (no
  shift), ``weights`` nought where a position is not masked and ``1 / t`` of
  its block where it is.

**Ties and block by block**: as ``reference_lfm2_moe`` (its docstring and its
:func:`choose`): :func:`forward_from_program` gives every reference block the
program's own input to it (all ``2 T`` rows), takes the program's choice of
experts only where every expert in which it differs lies within ``tau`` of
the reference's own boundary ``(8th + 9th) / 2``, and measures the program's
*update* against the reference's and, by itself, the program's attention
output against the reference's. :func:`forward` is the free-running pass.

It reads the program's parameter tree by its flax names
(``models/hybrid.py``) and nothing else of the program; the departures from
the published model are in the configuration file's ``assumed``.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .reference_laguna import (_rms_norm, _rotate, _swiglu, _update_error,
                               head)
from .reference_lfm2_moe import choose

#: query rows a block of the masked softmax takes
QUERY_BLOCK = 1024


def rotary_rows(theta: float, head_dim: int, positions) -> tuple:
    """``(cos, sin)``, each ``[rows, head_dim / 2]`` float32: row ``i``'s
    angles ``positions[i] * theta^(-2 pair / head_dim)``, in float64."""
    pair = np.arange(head_dim // 2, dtype=np.float64)
    angle = np.asarray(positions, np.float64)[:, None] \
        * float(theta) ** (-2.0 * pair / head_dim)
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def seen(query_rows, key_rows, half: int, block: int):
    """``[queries, keys]`` bool: the block-diffusion mask between the given
    rows of ``[noised | clean]`` (``half`` rows each, blocks of ``block``),
    clause by clause."""
    def of(rows):
        return rows >= half, rows % half // block

    (q_clean, q_block), (k_clean, k_block) = of(query_rows), of(key_rows)
    q_clean, q_block = q_clean[:, None], q_block[:, None]
    k_clean, k_block = k_clean[None, :], k_block[None, :]
    earlier_clean = k_clean & (k_block < q_block)
    own_clean = k_clean & (k_block == q_block) & q_clean
    own_noised = ~k_clean & ~q_clean & (k_block == q_block)
    return earlier_clean | own_clean | own_noised


def _attention(p, h, heads, kv_heads, eps, cos, sin, block):
    b, rows, _ = h.shape
    half = rows // 2
    q = (h @ p["q"]["kernel"]).reshape(b, rows, heads, -1)
    k = (h @ p["k"]["kernel"]).reshape(b, rows, kv_heads, -1)
    v = (h @ p["v"]["kernel"]).reshape(b, rows, kv_heads, -1)
    q = _rotate(_rms_norm(q, p["q_norm"], eps), cos, sin)
    k = _rotate(_rms_norm(k, p["k_norm"], eps), cos, sin)
    scale = q.shape[-1] ** -0.5
    step = min(QUERY_BLOCK, rows)
    steps = rows // step

    def one(at):           # one sequence, one query head, one query block
        seq, i, part = at // (heads * steps), at // steps % heads, at % steps
        kv = i // (heads // kv_heads)
        first = part * step
        qs = jax.lax.dynamic_slice_in_dim(q[seq, :, i], first, step)
        s = scale * qs @ k[seq, :, kv].T                   # [step, rows]
        s = jnp.where(seen(first + jnp.arange(step), jnp.arange(rows), half,
                           block), s, -jnp.inf)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        return (e / jnp.sum(e, axis=-1, keepdims=True)) @ v[seq, :, kv]

    out = jax.lax.map(one, jnp.arange(b * heads * steps))
    out = out.reshape(b, heads, rows, -1).transpose(0, 2, 1, 3)
    return out.reshape(b, rows, -1) @ p["o"]["kernel"]


def _routed(p, h, held, top_k, program, tau):
    logits = h @ p["router"]                               # [b, rows, E]
    e = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    scores = e / jnp.sum(e, axis=-1, keepdims=True)
    use, stats = choose(scores + p["expert_bias"], top_k,
                        None if program is None else program["chosen"], tau)
    if program is not None:
        stats["score_rms"] = jnp.sqrt(jnp.mean(
            (program["scores"] - scores) ** 2))
    # the layer's auxiliary balancing loss: E sum_e f_e P_e, f_e the share
    # of the rows that chose e (a count) and P_e the mean of their scores
    rows = use.shape[0] * use.shape[1]
    share = jax.lax.stop_gradient(jnp.sum(use, axis=(0, 1)) / rows)
    stats["aux"] = scores.shape[-1] * jnp.sum(
        share * jnp.sum(scores, axis=(0, 1)) / rows)
    picked = jnp.where(use, scores, 0.0)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)

    def expert(y, at):                 # one held expert over every row
        w_in, w_out, e = at
        w = jnp.take(weights, e, axis=-1)[..., None]
        return y + w * _swiglu(h, w_in, w_out), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (p["w_in"], p["w_out"], jnp.asarray(held)))
    return y, stats


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "held",
                                   "top_k", "block"))
def block(p, x, cos, sin, program, tau, heads, kv_heads, eps, held, top_k,
          block):
    """One block, ``x`` ``[b, 2 T, d]`` float32, ``[noised | clean]``.
    Returns ``(x, stats)``."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), p)
        mixed = _attention(p["mixer"], _rms_norm(
            x, p["norm_mixer"]["scale"], eps), heads, kv_heads, eps, cos,
            sin, block)
        x = x + mixed
        y, stats = _routed(p["ffn"], _rms_norm(x, p["norm_ffn"]["scale"],
                                               eps), held, top_k, program,
                           tau)
        if program is not None:
            # the program's attention output on the same input, by itself:
            # a fault of the mask or of the positions is all here, whatever
            # the routed experts beside it add to the block's update
            stats["mixer_error"] = jnp.sqrt(
                jnp.mean((program["mixer"].astype(jnp.float32) - mixed) ** 2)
                / jnp.mean(mixed ** 2))
        return x + y, stats


def block_length(config: dict) -> int:
    return int(config["assumed"]["block_length"]["value"])


def _block_of(params, config: dict, i: int, x, program, tau):
    """``block_<i>`` of the configuration on ``x``: ``(output, stats)``."""
    c, half = config, x.shape[1] // 2
    cos, sin = rotary_rows(c["rope_theta"], c["head_dim"],
                           np.arange(2 * half) % half)
    return block(params[f"block_{i}"], x, cos, sin, program,
                 jnp.float32(tau), c["num_attention_heads"],
                 c["num_key_value_heads"], float(c["rms_norm_eps"]),
                 tuple(c["held_experts"]), c["num_experts_per_tok"],
                 block_length(c))


def _rows(params, noised, clean):
    """The table's rows of ``[noised | clean]``: ``[b, 2 T, d]``."""
    return params["tok_emb"]["embedding"].astype(jnp.float32)[
        jnp.concatenate([noised, clean], axis=1)]


def forward(params, noised, clean, config: dict):
    """Logits ``[B, T, rows]`` in float32 of the noised half, for int
    ``noised`` and ``clean`` ``[B, T]``: the free-running forward pass,
    every choice the reference's own."""
    return _free_running(params, noised, clean, config)[0]


def _free_running(params, noised, clean, config: dict):
    """``(logits, the sum of the blocks' auxiliary balancing losses)``."""
    x, aux = _rows(params, noised, clean), 0.0
    for i in range(config["num_hidden_layers"]):
        x, stats = _block_of(params, config, i, x, None, 0.0)
        aux = aux + stats["aux"]
    return head(params, x[:, :clean.shape[1]],
                float(config["rms_norm_eps"])), aux


def loss(logits, clean, weights):
    """``sum_i weights_i CE(logits_i, clean_i) / (batch T)``, written out."""
    top = jnp.max(logits, axis=-1, keepdims=True)
    log_z = top[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1))
    picked = jnp.take_along_axis(logits, clean[..., None], axis=-1)[..., 0]
    return jnp.sum(weights * (log_z - picked)) / weights.size


def loss_and_grads(params, noised, clean, weights, config: dict):
    """``(loss, what the optimizer is handed for every parameter)``:
    :func:`loss` over :func:`forward`, and ``jax.grad`` of it plus
    ``assumed.auxiliary_loss.coefficient`` times every block's auxiliary
    balancing loss (``E sum_e f_e P_e``: :func:`_routed`). The value is the
    objective's loss alone, as the program's step reports it. (The
    selection bias steers a choice and has no gradient.)"""
    coefficient = float(config["assumed"]["auxiliary_loss"]["coefficient"])

    def fn(p):
        logits, aux = _free_running(p, noised, clean, config)
        value = loss(logits, clean, weights)
        return value + coefficient * aux, value

    (_, value), grads = jax.value_and_grad(fn, has_aux=True)(params)
    return value, grads


def forward_from_program(params, inputs, config: dict, program_outputs,
                         routing: Dict[str, dict],
                         tau: float) -> Tuple[jax.Array, List[dict]]:
    """``(logits, one stats dict a block)`` with every block computed from
    the **program's** input to it, as ``reference_laguna``'s function of
    this name: ``inputs`` is ``(noised, clean)``, ``program_outputs[i]`` the
    program's output of ``block_<i>`` (``[B, 2 T, d]``), ``routing`` maps
    ``"block_<i>"`` to that layer's ``{"chosen", "scores"}`` as the program
    computed them and its ``"mixer"``, the program's attention output in that
    block (``[B, 2 T, d]``), for the tie rule at width ``tau`` and for
    ``mixer_error``, that output against the reference's on the same input,
    rms over rms. The logits are the head over the noised half of the last
    reference block's output."""
    noised, clean = inputs
    x = _rows(params, noised, clean)
    stats = []
    for i in range(config["num_hidden_layers"]):
        name = f"block_{i}"
        out, layer = _block_of(params, config, i, x, routing.get(name), tau)
        stats.append({"layer": name, **layer, "update_error": _update_error(
            program_outputs[i], out, x)})
        x = program_outputs[i].astype(jnp.float32)
    return head(params, out[:, :clean.shape[1]],
                float(config["rms_norm_eps"])), stats
