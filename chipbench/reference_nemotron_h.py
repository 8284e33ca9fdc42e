"""The plain reference of the Nemotron-H family (``"model_type":
"nemotron_h"``): its forward pass in float32 ``jax.numpy``, every matrix
product under ``jax.default_matmul_precision("highest")``.

No kernel, no chunking, no sort, no grouped product, no bf16. A block is
**one sublayer**, ``x <- x + f(RMSNorm(x))``, its kind a character of
``hybrid_override_pattern``; after the last block RMSNorm, then the untied
head ``logits = h W_head^T``. RMSNorm eps ``layer_norm_epsilon``, no bias
but the conv's.

* ``M`` (Mamba-2): ``[z, xBC, dt] = W_in h``; ``xBC = silu(conv4(xBC) +
  bias)`` with the depthwise causal conv as shifted multiply-adds;
  ``x, B, C = split(xBC)`` with ``n_groups`` groups of ``B`` and of ``C``;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the state-space
  model as its **recurrence**, one position at a time (``lax.scan``), head
  ``h`` reading group ``h // (heads / n_groups)``:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``;
  ``y = RMSNorm_w(y * silu(z))`` with the mean square over each of the
  ``n_groups`` runs of channels; ``W_out y``.
* ``*`` (attention): ``q, k, v, o`` without bias or position encoding; a
  masked softmax of ``q k^T / sqrt(head_dim)``, one head at a time, query
  head ``i`` reading KV head ``i // (heads // kv_heads)``.
* ``E`` (LatentMoE): ``s = sigmoid(W_r h)`` over **all** experts, the router
  reading the full hidden vector; a token's experts are the
  ``num_experts_per_tok`` with the largest ``s + bias``; ``w_e =
  routed_scaling_factor * s_e / (sum over the chosen of s + 1e-20)``; ``u =
  W_down h``; ``r = sum over e chosen and held of w_e * W2_e relu(W1_e
  u)^2``; ``y = W_up r + S2 relu(S1 h)^2``. **The same share** as the program
  is given (``held_experts``, the sliced table and head), each held expert
  computed over every token and masked by ``w``. What the experts held
  elsewhere would add is left out, here as there; the shared expert is
  whole.

**Ties and block by block**: as ``reference_lfm2_moe`` (its docstring and
its :func:`choose`). A top-22-of-512 choice has an expert near the boundary
``(22nd + 23rd) / 2`` in most tokens, and both answers are then correct to
within rounding; :func:`forward_from_program` gives every reference block
the program's own input to it, takes the program's choice only where every
expert in which it differs lies within ``tau`` of the reference's own
boundary, and measures the program's *update* against the reference's.
:func:`forward` is the free-running pass, every choice its own.

It reads the program's parameter tree by its flax names
(``models/hybrid.py``) and nothing else of the program; the departures from
the published model are in the configuration file's ``assumed``.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from .reference_lfm2_moe import choose


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _relu2(x):
    return jnp.maximum(x, 0.0) ** 2


def _attention(p, h, heads, kv_heads):
    b, t, _ = h.shape
    q = (h @ p["q"]["kernel"]).reshape(b, t, heads, -1)
    k = (h @ p["k"]["kernel"]).reshape(b, t, kv_heads, -1)
    v = (h @ p["v"]["kernel"]).reshape(b, t, kv_heads, -1)
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


def recurrence(x, dt, A, B, C, D):
    """The state-space model one position at a time: ``x`` ``[b, t, H, P]``,
    ``dt`` ``[b, t, H]``, ``A`` and ``D`` ``[H]``, ``B`` and ``C``
    ``[b, t, G, N]``, head ``h`` reading group ``h // (H / G)``; the state
    ``S`` is ``[b, H, P, N]`` and starts at zero."""
    per_group = x.shape[2] // B.shape[2]

    def step(S, at):
        x_t, dt_t, B_t, C_t = at
        B_t, C_t = (jnp.repeat(a, per_group, axis=1) for a in (B_t, C_t))
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return S, jnp.sum(S * C_t[:, :, None, :], axis=-1)

    S0 = jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:], x.dtype)
    _, y = jax.lax.scan(step, S0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1) + D[:, None] * x


def _mamba(p, h, heads, groups, state, eps):
    b, t, _ = h.shape
    inner = p["out_proj"]["kernel"].shape[0]
    zxbcdt = h @ p["in_proj"]["kernel"]
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:-heads],
                  zxbcdt[..., -heads:])
    w = p["conv"]["kernel"]                                 # [width, channels]
    width = w.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((b, width - 1, xbc.shape[-1]), xbc.dtype), xbc], axis=1)
    xbc = _silu(sum(padded[:, i:i + t] * w[i] for i in range(width))
                + p["conv"]["bias"])
    x = xbc[..., :inner].reshape(b, t, heads, -1)
    B, C = (xbc[..., at:at + groups * state].reshape(b, t, groups, state)
            for at in (inner, inner + groups * state))
    dt = jnp.log1p(jnp.exp(dt + p["dt_bias"]))             # softplus, [b,t,H]
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), B, C, p["D"])
    y = (y.reshape(b, t, inner) * _silu(z)).reshape(b, t, groups, -1)
    y = _rms_norm(y, 1.0, eps).reshape(b, t, inner) * p["gate_norm"]["scale"]
    return y @ p["out_proj"]["kernel"]


def _latent_moe(p, h, held, top_k, scale, program, tau):
    scores = _sigmoid(h @ p["router"])                     # [b, t, E]
    use, stats = choose(scores + p["expert_bias"], top_k,
                        None if program is None else program["chosen"], tau)
    if program is not None:
        stats["score_rms"] = jnp.sqrt(jnp.mean(
            (program["scores"] - scores) ** 2))
    picked = jnp.where(use, scores, 0.0)
    weights = scale * picked / (jnp.sum(picked, axis=-1, keepdims=True)
                                + 1e-20)
    u = h @ p["latent_in"]["kernel"]

    def expert(r, at):                 # one held expert over every token
        w_in, w_out, e = at
        w = jnp.take(weights, e, axis=-1)[..., None]
        return r + w * (_relu2(u @ w_in) @ w_out), None

    r, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                        (p["w_in"], p["w_out"], jnp.asarray(held)))
    shared = _relu2(h @ p["shared_in"]["kernel"]) @ p["shared_out"]["kernel"]
    return r @ p["latent_out"]["kernel"] + shared, stats


@partial(jax.jit, static_argnames=("kind", "heads", "kv_heads", "ssm_heads",
                                   "groups", "state", "eps", "held", "top_k",
                                   "scale"))
def block(p, x, program, tau, kind, heads, kv_heads, ssm_heads, groups, state,
          eps, held, top_k, scale):
    """One block of ``kind`` (``"M"`` | ``"E"`` | ``"*"``), ``x``
    ``[b, t, d]`` float32. Returns ``(x, stats)``."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), p)
        if kind == "E":
            y, stats = _latent_moe(
                p["ffn"], _rms_norm(x, p["norm_ffn"]["scale"], eps), held,
                top_k, scale, program, tau)
            return x + y, stats
        h = _rms_norm(x, p["norm_mixer"]["scale"], eps)
        if kind == "*":
            return x + _attention(p["mixer"], h, heads, kv_heads), {}
        return x + _mamba(p["mixer"], h, ssm_heads, groups, state, eps), {}


@partial(jax.jit, static_argnames=("eps",))
def head(params, x, eps):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, params["norm_f"]["scale"].astype(jnp.float32), eps)
        return x @ params["lm_head"]["kernel"].astype(jnp.float32)


def _block_of(params, config: dict, i: int, x, program, tau):
    """``block_<i>`` of the configuration on ``x``: ``(output, stats)``."""
    c = config
    return block(params[f"block_{i}"], x, program, jnp.float32(tau),
                 c["hybrid_override_pattern"][i], c["num_attention_heads"],
                 c["num_key_value_heads"], c["mamba_num_heads"],
                 c["n_groups"], c["ssm_state_size"],
                 float(c["layer_norm_epsilon"]), tuple(c["held_experts"]),
                 c["num_experts_per_tok"], float(c["routed_scaling_factor"]))


def forward(params, tokens, config: dict):
    """Logits ``[B, T, rows]`` in float32 for int tokens ``[B, T]``, from
    the configuration's keys: the free-running forward pass, every choice
    the reference's own. Called outside a jit, the layers of one kind run
    one compiled ``block`` program."""
    x = params["tok_emb"]["embedding"].astype(jnp.float32)[tokens]
    for i in range(config["num_hidden_layers"]):
        x, _ = _block_of(params, config, i, x, None, 0.0)
    return head(params, x, float(config["layer_norm_epsilon"]))


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
    return head(params, out, float(config["layer_norm_epsilon"])), stats
