"""The plain reference of the LFM2 mixture-of-experts family (``"model_type":
"lfm2_moe"``): its forward pass in float32 ``jax.numpy``, every matrix
product under ``jax.default_matmul_precision("highest")``.

No kernel, no sort, no grouped product, no bf16. With ``h = RMSNorm(x)``
(weight, eps) before each sub-layer and plain residual adds:

* model: ``x = tok_emb[t]``; the blocks; RMSNorm; ``logits = x @ tok_emb^T``.
* ``conv`` mixer: ``[B, C, u] = W_in h`` (each as wide as the model, in that
  order); ``W_out (C * conv3(B * u))`` with the depthwise causal convolution
  without bias as shifted multiply-adds, ``conv3(z)_t = sum_j k_j z_{t-2+j}``.
* ``full_attention`` mixer: ``q, k, v, o`` without bias; RMSNorm over each
  head's 64 on q and on k (a weight each); RoPE in the rotate-half layout
  (pair ``i`` is elements ``i`` and ``i + 32``, angle ``t theta^(-i/32)``) on
  q and k; a masked softmax of ``q k^T / sqrt(64)``, one sequence and one
  head at a time, query head ``i`` reading KV head ``i // (heads //
  kv_heads)``.
* dense feed-forward (the first ``num_dense_layers``): ``W_2 (silu(a) * b)``,
  ``[a, b] = W_1 h``.
* routed feed-forward: ``s = sigmoid(W_r h)`` over **all** experts; a token's
  experts are the ``num_experts_per_tok`` with the largest ``s + bias``; ``w_e
  = s_e / (sum over the chosen of s + 1e-6)``; ``y = sum over e chosen and
  held of w_e * expert_e(h)``: **the same share** as the program is given
  (``held_experts``, the sliced table), each held expert computed over every
  token and masked by ``w``. What the experts held elsewhere would add is
  left out, here as there.

**Ties.** A top-4-of-32 choice turns a rounding of the router's input into
a different expert wherever the 4th and 5th values nearly tie; both answers
are then correct to within rounding. Given the program's own choices and a
width ``tau``, a (token, layer) is *tied* when an expert lies within ``tau``
of the boundary ``(4th + 5th) / 2`` of this reference's own ``s + bias``.
Only where every expert in which the program's choice differs from the
reference's is within ``tau`` of that boundary does the reference take the
program's choice; everywhere else it keeps its own. A program whose scores
are wrong by more than ``tau`` differs *outside* it, is counted, and is not
followed.

**Block by block.** Every mixer and feed-forward here multiplies
projections of its input with one another (``C * conv(B * u)`` is cubic in
``h``, SwiGLU quadratic), so a block passes a rounding of the residual
stream on *amplified*: on the chip the bf16 program's stream leaves the
float32 one by 1.0% after the first block and 2.3% after the ninth, flips
followed (PERF.md, PR 32), while each block computed from the same input
agrees to 1% of its own update. :func:`forward_from_program` therefore
checks the program one block at a time: each reference block is given the
program's own input to that block, the error of the program's *update*
(output minus input) is measured against the reference's, and the logits
are the head over the last reference block. :func:`forward` is the
free-running forward pass, every choice its own.

It reads the program's parameter tree by its flax names
(``models/hybrid.py``) and nothing else of the program; the departures from
the published model are in the configuration file's ``assumed``.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _rope(x, theta):
    """``x`` ``[b, t, heads, hd]``, rotate-half layout."""
    t, half = x.shape[1], x.shape[-1] // 2
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)   # [t, hd/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _attention(p, h, heads, kv_heads, theta, eps):
    b, t, _ = h.shape
    q = (h @ p["q"]["kernel"]).reshape(b, t, heads, -1)
    k = (h @ p["k"]["kernel"]).reshape(b, t, kv_heads, -1)
    v = (h @ p["v"]["kernel"]).reshape(b, t, kv_heads, -1)
    q = _rope(_rms_norm(q, p["q_norm"], eps), theta)
    k = _rope(_rms_norm(k, p["k_norm"], eps), theta)
    scale = q.shape[-1] ** -0.5
    mask = jnp.tril(jnp.ones((t, t), bool))

    def one(at):                       # one sequence, one query head
        seq, i = at // heads, at % heads
        kv = i // (heads // kv_heads)
        s = scale * q[seq, :, i] @ k[seq, :, kv].T             # [t, t]
        s = jnp.where(mask, s, -jnp.inf)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        return (e / jnp.sum(e, axis=-1, keepdims=True)) @ v[seq, :, kv]

    out = jax.lax.map(one, jnp.arange(b * heads))          # [b*heads, t, hd]
    out = out.reshape(b, heads, t, -1).transpose(0, 2, 1, 3)
    return out.reshape(b, t, -1) @ p["o"]["kernel"]


def _short_conv(p, h):
    b, t, d = h.shape
    bcu = h @ p["in_proj"]["kernel"]
    B, C, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    w = p["short_conv"]["kernel"]                          # [width, d]
    width = w.shape[0]
    z = jnp.concatenate([jnp.zeros((b, width - 1, d), h.dtype), B * u], axis=1)
    conv = sum(z[:, j:j + t] * w[j] for j in range(width))
    return (C * conv) @ p["out_proj"]["kernel"]


def choose(values, top_k, program_chosen=None, tau=0.0):
    """Which experts each token's result is summed over: ``[..., E]`` bool.

    ``values`` ``[..., E]`` is this reference's ``s + bias``. Without
    ``program_chosen`` it is the ``top_k`` largest. With it (``[..., top_k]``
    expert ids) the tie rule of the module's docstring applies, and the
    second result counts, as float32 means over the tokens: ``tied`` (an
    expert within ``tau`` of the boundary), ``followed`` (the program's
    different choice taken) and ``outside`` (the program differed in an
    expert farther than ``tau`` from the boundary: not taken)."""
    experts = values.shape[-1]
    top, index = jax.lax.top_k(values, top_k + 1)
    own = jnp.sum(jax.nn.one_hot(index[..., :top_k], experts), axis=-2) > 0
    if program_chosen is None:
        return own, {}
    boundary = 0.5 * (top[..., top_k - 1] + top[..., top_k])
    near = jnp.abs(values - boundary[..., None]) <= tau
    theirs = jnp.sum(jax.nn.one_hot(program_chosen, experts), axis=-2) > 0
    differs = own != theirs
    differed = jnp.any(differs, axis=-1)
    inside = jnp.all(~differs | near, axis=-1)
    follow = differed & inside
    share = partial(jnp.mean, dtype=jnp.float32)
    return jnp.where(follow[..., None], theirs, own), {
        "tied": share(jnp.any(near, axis=-1)), "followed": share(follow),
        "outside": share(differed & ~inside)}


def _routed(p, h, held, top_k, program, tau):
    scores = _sigmoid(h @ p["router"])                     # [b, t, E]
    use, stats = choose(scores + p["expert_bias"], top_k,
                        None if program is None else program["chosen"], tau)
    if program is not None:
        stats["score_rms"] = jnp.sqrt(jnp.mean(
            (program["scores"] - scores) ** 2))
    picked = jnp.where(use, scores, 0.0)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)

    def expert(y, at):                 # one held expert over every token
        w_in, w_out, e = at
        a, b_ = jnp.split(h @ w_in, 2, axis=-1)
        w = jnp.take(weights, e, axis=-1)[..., None]
        return y + w * ((_silu(a) * b_) @ w_out), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (p["w_in"], p["w_out"], jnp.asarray(held)))
    return y, stats


@partial(jax.jit, static_argnames=("kind", "heads", "kv_heads", "theta",
                                   "eps", "held", "top_k"))
def block(p, x, program, tau, kind, heads, kv_heads, theta, eps, held, top_k):
    """One block, ``x`` ``[b, t, d]`` float32: mixer ``kind`` (``"conv"`` |
    ``"full_attention"``), and the routed feed-forward where the block has
    one (``"ffn"`` in ``p``), else the dense one. Returns ``(x, stats)``."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), p)
        h = _rms_norm(x, p["norm_mixer"]["scale"], eps)
        if kind == "full_attention":
            x = x + _attention(p["mixer"], h, heads, kv_heads, theta, eps)
        else:
            x = x + _short_conv(p["mixer"], h)
        h = _rms_norm(x, p["norm_ffn"]["scale"], eps)
        if "ffn" in p:
            y, stats = _routed(p["ffn"], h, held, top_k, program, tau)
            return x + y, stats
        a, b_ = jnp.split(h @ p["ffn_in"]["kernel"], 2, axis=-1)
        return x + (_silu(a) * b_) @ p["ffn_out"]["kernel"], {}


@partial(jax.jit, static_argnames=("eps",))
def head(params, x, eps):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, params["norm_f"]["scale"].astype(jnp.float32), eps)
        return x @ params["tok_emb"]["embedding"].astype(jnp.float32).T


def _block_of(params, config: dict, i: int, x, program, tau):
    """``block_<i>`` of the configuration on ``x``: ``(output, stats)``."""
    c = config
    return block(params[f"block_{i}"], x, program, jnp.float32(tau),
                 c["layer_types"][i], c["num_attention_heads"],
                 c["num_key_value_heads"], float(c["rope_theta"]),
                 float(c["norm_eps"]), tuple(c["held_experts"]),
                 c["num_experts_per_tok"])


def forward(params, tokens, config: dict):
    """Logits ``[B, T, rows]`` in float32 for int tokens ``[B, T]``, from
    the configuration's keys: the free-running forward pass, every choice
    the reference's own. Called outside a jit, the layers of one kind run
    one compiled ``block`` program."""
    x = params["tok_emb"]["embedding"].astype(jnp.float32)[tokens]
    for i in range(config["num_hidden_layers"]):
        x, _ = _block_of(params, config, i, x, None, 0.0)
    return head(params, x, float(config["norm_eps"]))


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
    the **program's** input to it: ``program_outputs[i]`` is the program's
    output of ``block_<i>`` (its residual stream, ``[B, T, d]``), and
    ``routing`` maps ``"block_<i>"`` to that layer's ``{"chosen": [B, T, k]
    ids, "scores": [B, T, E]}`` as the program computed them, for the tie
    rule at width ``tau``. A block's stats hold ``update_error``
    (:func:`_update_error`) and, where it routes, the tie rule's shares and
    the rms of program-minus-reference scores. The logits are the head over
    the last reference block's output."""
    x = params["tok_emb"]["embedding"].astype(jnp.float32)[tokens]
    stats = []
    for i in range(config["num_hidden_layers"]):
        name = f"block_{i}"
        out, layer = _block_of(params, config, i, x, routing.get(name), tau)
        stats.append({"layer": name, **layer, "update_error": _update_error(
            program_outputs[i], out, x)})
        x = program_outputs[i].astype(jnp.float32)
    return head(params, out, float(config["norm_eps"])), stats
