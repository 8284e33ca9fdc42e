"""The plain reference of the Qwen3-Next family (``"model_type":
"qwen3_next"``): its forward pass in float32 ``jax.numpy``, every matrix
product under ``jax.default_matmul_precision("highest")``.

No kernel, no chunked form, no sort, no grouped product, no bf16. Block
``i``, pre-norm, RMSNorm (weight, ``rms_norm_eps``), no bias anywhere:
``x += mixer_i(RMSNorm(x))``, ``x += moe(RMSNorm(x))``; the mixer is
``full_attention`` where ``(i + 1) % full_attention_interval == 0``, else
``linear_attention``; every block routes. Table -> blocks -> RMSNorm -> the untied head ``logits = x W_head``.

* ``linear_attention`` (Gated DeltaNet), ``h`` the normed input, ``Hk`` =
  ``linear_num_key_heads`` key heads and ``Hv`` = ``linear_num_value_heads``
  value heads of ``linear_key_head_dim`` / ``linear_value_head_dim``, value
  head ``j`` reading key head ``j // (Hv / Hk)``:
  ``[q | k | v | z] = h W_qkvz`` (``Hk K``, ``Hk K``, ``Hv V``, ``Hv V``
  columns), ``[b | a] = h W_ba`` (``Hv`` each);
  ``[q | k | v] <- silu(conv([q | k | v]))``, the depthwise causal
  convolution of ``linear_conv_kernel_dim`` taps without bias as shifted
  multiply-adds; ``beta = sigmoid(b)``; ``g = -exp(A_log) * softplus(a +
  dt_bias)``; ``q`` and ``k`` L2-normalised over a head (``x / sqrt(sum
  x^2 + 1e-6)``), ``q`` then times ``K ** -0.5``; a value head's state
  ``S`` ``[K, V]`` from zeros, **the literal recurrence, position by
  position** (:func:`delta_rule`):
  ``S' = exp(g_t) S_{t-1}``; ``S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T``;
  ``o_t = S_t^T q_t``;
  ``y = RMSNorm_V(o) * w_norm * silu(z)`` a head (the norm first, then the
  gate; one ``[V]`` weight shared by the heads; eps ``rms_norm_eps``);
  ``y.reshape(T, Hv V) W_out``.
* ``full_attention`` (gated attention), ``H`` = ``num_attention_heads``
  over ``num_key_value_heads`` KV heads of ``head_dim``: ``q = h Wq``,
  ``gate = h Wg`` (each ``H x head_dim`` wide), ``k = h Wk``, ``v = h
  Wv``; q and k RMS-normed a head (a weight each); the rotary turn on the
  first ``partial_rotary_factor * head_dim`` elements of a head
  (rotate-half: pair ``i`` is elements ``i`` and ``i + r / 2``, angle ``t
  rope_theta^(-2i/r)``), the rest passing through; query head ``j`` reads
  KV head ``j // (H / kv)``; a masked softmax of ``q k^T / sqrt(head_dim)``,
  a block of queries at a time so that 16,384 positions fit; ``(P v *
  sigmoid(gate)).reshape(T, H head_dim) Wo``: a gate a head **and
  channel**.
* the routed feed-forward: ``p = softmax(h W_r)`` over **all** experts; a
  token's experts are the ``num_experts_per_tok`` with the largest ``p``
  (plus a bias that is zero); ``w_e = p_e / (sum over the chosen of p)``
  (``norm_topk_prob``); ``sum over e chosen and held of w_e SwiGLU_e(h)``
  plus ``sigmoid(h w_s) * SwiGLU_shared(h)``, the shared expert under one
  scalar gate a token. **The same share** as the program is given
  (``held_experts``, the sliced table and head), each held expert computed
  over every token and masked by ``w``. What the experts held elsewhere
  would add is left out, here as there.

**Departures from the published model**, each in the configuration file's
``assumed``: every norm's weight is the plain ``x_hat * w`` with ``w``
starting at 1 where the published one is ``x_hat * (1 + w)`` with ``w``
starting at 0 (the gated norm after the delta rule is plain there too);
``W_qkvz`` and ``W_ba`` hold their parts side by side where the published
ones interleave them by key head, and ``Wq`` / ``Wg`` are two matrices
where the published ``q_proj`` holds a head's query then its gate (column
permutations, free with random weights); no multi-token-prediction module.

**Ties and block by block**: as ``reference_lfm2_moe`` (its docstring and
its :func:`choose`): :func:`forward_from_program` gives every reference
block the program's own input to it, takes the program's choice of experts
only where every expert in which it differs lies within ``tau`` of the
reference's own boundary ``(10th + 11th) / 2``, and measures the program's
*update* against the reference's (the stream is the job's program's own:
``families/qwen3_next.program_trace``).
:func:`forward` is the free-running pass.

It reads the program's parameter tree by its flax names
(``models/hybrid.py``) and nothing else of the program.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from .reference_laguna import (QUERY_BLOCK, _rms_norm, _rotate, _sigmoid,
                               _silu, _swiglu, _update_error, head,
                               rotary_tables)
from .reference_lfm2_moe import choose

#: positions a block of the recurrence takes, the state carried from one
#: block to the next
POSITION_BLOCK = 1024

L2_EPS = 1e-6


def delta_rule(q, k, v, g, beta, block: int = POSITION_BLOCK):
    """The gated delta rule as its recurrence, one position at a time:
    ``q``, ``k`` ``[b, T, Hk, K]``, ``v`` ``[b, T, Hv, V]``, ``g`` and
    ``beta`` ``[b, T, Hv]``; value head ``j`` reads key head ``j // (Hv /
    Hk)`` (no operand is repeated: the state is ``[b, Hk, Hv / Hk, K, V]``).
    Products and sums written out elementwise, so float32 whatever the
    matmul precision. ``T`` positions in blocks of ``block`` (where it
    divides ``T``), the casts and the layout made a block at a time and the
    state carried, so that 16,384 positions fit. Returns ``o`` ``[b, T, Hv,
    V]``."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    rep = hv // hk
    if t % block:
        block = t

    def position(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        k_t, q_t = k_t[:, :, None, :, None], q_t[:, :, None, :, None]
        state = jnp.exp(g_t)[..., None, None] * state
        read = jnp.sum(state * k_t, axis=-2)               # S'^T k: [.., V]
        write = beta_t[..., None] * (v_t - read)
        state = state + k_t * write[..., None, :]
        return state, jnp.sum(state * q_t, axis=-2)

    def positions(state, at):          # one block, time first
        q_b, k_b, v_b, g_b, beta_b = (
            jnp.moveaxis(a.astype(jnp.float32), 1, 0) for a in at)
        state, out = jax.lax.scan(position, state, (
            q_b, k_b, v_b.reshape(block, b, hk, rep, dv),
            g_b.reshape(block, b, hk, rep),
            beta_b.reshape(block, b, hk, rep)))
        return state, jnp.moveaxis(out, 0, 1)              # [b, block, ...]

    def blocks(a):                     # [b, T, ...] -> [T / block, b, block, ...]
        return jnp.moveaxis(
            a.reshape((b, t // block, block) + a.shape[2:]), 1, 0)

    start = jnp.zeros((b, hk, rep, dk, dv), jnp.float32)
    _, out = jax.lax.scan(positions, start,
                          tuple(blocks(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, hv, dv)


def _l2_norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _causal_conv(x, kernel):
    """``y_t = sum_j kernel[j] x_{t - (width - 1) + j}``, zeros before the
    sequence; ``x`` ``[b, t, c]``, ``kernel`` ``[width, c]``."""
    b, t, c = x.shape
    width = kernel.shape[0]
    z = jnp.concatenate([jnp.zeros((b, width - 1, c), x.dtype), x], axis=1)
    return sum(z[:, j:j + t] * kernel[j] for j in range(width))


def _gated_delta(p, h, key_heads, value_heads, key_dim, value_dim, eps):
    b, t, _ = h.shape
    keys, values = key_heads * key_dim, value_heads * value_dim
    qkvz = h @ p["in_proj"]["kernel"]
    qkv, z = qkvz[..., :2 * keys + values], qkvz[..., 2 * keys + values:]
    gates = h @ p["in_gates"]["kernel"]
    beta = _sigmoid(gates[..., :value_heads])
    g = -jnp.exp(p["A_log"]) * _softplus(gates[..., value_heads:]
                                         + p["dt_bias"])
    qkv = _silu(_causal_conv(qkv, p["conv"]["kernel"]))
    q = _l2_norm(qkv[..., :keys].reshape(b, t, key_heads, key_dim)) \
        * key_dim ** -0.5
    k = _l2_norm(qkv[..., keys:2 * keys].reshape(b, t, key_heads, key_dim))
    v = qkv[..., 2 * keys:].reshape(b, t, value_heads, value_dim)
    o = delta_rule(q, k, v, g, beta)
    y = _rms_norm(o, p["gate_norm"]["scale"], eps) \
        * _silu(z.reshape(b, t, value_heads, value_dim))
    return y.reshape(b, t, values) @ p["out_proj"]["kernel"]


def _gated_attention(p, h, heads, kv_heads, eps, cos, sin):
    b, t, _ = h.shape
    q = (h @ p["q"]["kernel"]).reshape(b, t, heads, -1)
    k = (h @ p["k"]["kernel"]).reshape(b, t, kv_heads, -1)
    v = (h @ p["v"]["kernel"]).reshape(b, t, kv_heads, -1)
    q = _rotate(_rms_norm(q, p["q_norm"], eps), cos, sin)
    k = _rotate(_rms_norm(k, p["k_norm"], eps), cos, sin)
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
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(seen, s, -jnp.inf)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        return (e / jnp.sum(e, axis=-1, keepdims=True)) @ v[seq, :, kv]

    out = jax.lax.map(one, jnp.arange(b * heads * blocks))
    out = out.reshape(b, heads, t, -1).transpose(0, 2, 1, 3)  # [b,t,H,hd]
    gate = _sigmoid(h @ p["gate"]["kernel"]).reshape(out.shape)
    return (out * gate).reshape(b, t, -1) @ p["o"]["kernel"]


def _routed(p, h, held, top_k, program, tau):
    logits = h @ p["router"]                               # [b, t, E]
    e = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    scores = e / jnp.sum(e, axis=-1, keepdims=True)
    use, stats = choose(scores + p["expert_bias"], top_k,
                        None if program is None else program["chosen"], tau)
    if program is not None:
        stats["score_rms"] = jnp.sqrt(jnp.mean(
            (program["scores"] - scores) ** 2))
    picked = jnp.where(use, scores, 0.0)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)

    def expert(y, at):                 # one held expert over every token
        w_in, w_out, e = at
        w = jnp.take(weights, e, axis=-1)[..., None]
        return y + w * _swiglu(h, w_in, w_out), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (p["w_in"], p["w_out"], jnp.asarray(held)))
    shared = _swiglu(h, p["shared_in"]["kernel"], p["shared_out"]["kernel"])
    return y + _sigmoid(h @ p["shared_gate"]["kernel"]) * shared, stats


@partial(jax.jit, static_argnames=("kind", "heads", "kv_heads", "delta",
                                   "eps", "held", "top_k"))
def block(p, x, cos, sin, program, tau, kind, heads, kv_heads, delta, eps,
          held, top_k):
    """One block, ``x`` ``[b, t, d]`` float32: the mixer of ``kind``
    (``"linear_attention"`` with ``delta`` = (key heads, value heads, key
    width, value width), or ``"full_attention"``) and the routed
    feed-forward. Returns ``(x, stats)``."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), p)
        h = _rms_norm(x, p["norm_mixer"]["scale"], eps)
        if kind == "full_attention":
            x = x + _gated_attention(p["mixer"], h, heads, kv_heads, eps,
                                     cos, sin)
        else:
            x = x + _gated_delta(p["mixer"], h, *delta, eps)
        y, stats = _routed(p["ffn"], _rms_norm(x, p["norm_ffn"]["scale"],
                                               eps), held, top_k, program,
                           tau)
        return x + y, stats


def _block_of(params, config: dict, i: int, x, program, tau):
    """``block_<i>`` of the configuration on ``x``: ``(output, stats)``."""
    c = config
    kind = "linear_attention" if (i + 1) % c["full_attention_interval"] \
        else "full_attention"
    cos, sin = rotary_tables(
        {"rope_type": "default", "rope_theta": c["rope_theta"],
         "partial_rotary_factor": c["partial_rotary_factor"]},
        c["head_dim"], x.shape[1])
    return block(params[f"block_{i}"], x, cos, sin, program,
                 jnp.float32(tau), kind,
                 c["num_attention_heads"], c["num_key_value_heads"],
                 (c["linear_num_key_heads"], c["linear_num_value_heads"],
                  c["linear_key_head_dim"], c["linear_value_head_dim"]),
                 float(c["rms_norm_eps"]), tuple(c["held_experts"]),
                 c["num_experts_per_tok"])


def forward(params, tokens, config: dict):
    """Logits ``[B, T, rows]`` in float32 for int tokens ``[B, T]``, from
    the configuration's keys: the free-running forward pass, every choice
    the reference's own."""
    x = params["tok_emb"]["embedding"].astype(jnp.float32)[tokens]
    for i in range(config["num_hidden_layers"]):
        x, _ = _block_of(params, config, i, x, None, 0.0)
    return head(params, x, float(config["rms_norm_eps"]))


def loss_and_grads(params, tokens, targets, config: dict):
    """``(loss, its gradient in every parameter)``: the job's loss (the mean
    over every position of the cross-entropy of the float32 logits against
    ``targets``, written out) over :func:`forward`, and ``jax.grad`` of it
    (the selection bias steers a choice and has none)."""
    def loss(params):
        logits = forward(params, tokens, config)
        top = jnp.max(logits, axis=-1, keepdims=True)
        log_z = top[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1))
        picked = jnp.take_along_axis(logits, targets[..., None],
                                     axis=-1)[..., 0]
        return jnp.mean(log_z - picked)

    return jax.value_and_grad(loss)(params)


def forward_from_program(params, tokens, config: dict, program_outputs,
                         routing: Dict[str, dict],
                         tau: float) -> Tuple[jax.Array, List[dict]]:
    """``(logits, one stats dict a block)`` with every block computed from
    the **program's** input to it, as ``reference_laguna``'s function of
    this name: ``program_outputs[i]`` is the program's output of
    ``block_<i>`` (its residual stream, ``[B, T, d]``; the first block's
    input is the table's rows), ``routing`` maps ``"block_<i>"`` to that
    layer's ``{"chosen", "scores"}`` as the program computed them, for the
    tie rule at width ``tau``. A block's stats hold the tie rule's shares,
    the rms of program-minus-reference scores and ``update_error``: the
    program's update (its output minus its input) against the reference's,
    the last block's too. The logits are the head over the last reference
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
