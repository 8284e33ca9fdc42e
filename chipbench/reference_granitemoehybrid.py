"""The plain reference of the Granite 4.0-H family (``"model_type":
"granitemoehybrid"`` with no experts): its forward pass in float32
``jax.numpy``, every matrix product under
``jax.default_matmul_precision("highest")``.

No kernel, no chunking, no bf16. It follows the published modelling code
(Hugging Face ``GraniteMoeHybrid``, the torch path):

* model: ``x = tok_emb[t] * embedding_multiplier``; the blocks; RMSNorm;
  ``logits = x @ tok_emb^T / logits_scaling``.
* block: ``x = x + residual_multiplier * mixer(RMSNorm(x))``, then the same
  with ``ffn(h) = W_out (silu(a) * b)``, ``[a, b] = W_in h``.
* attention mixer: ``q, k, v, o`` without bias or position encoding; a
  masked softmax of ``attention_multiplier * q k^T``, one head at a time,
  query head ``i`` reading KV head ``i // (heads // kv_heads)``.
* Mamba-2 mixer: ``[z, xBC, dt] = W_in h``; ``xBC = silu(conv(xBC) +
  bias)`` with the depthwise causal conv as shifted multiply-adds; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the state-space model as
  its **recurrence**, one position at a time (``lax.scan``):
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``;
  ``y = RMSNorm_w(y * silu(z))`` over the whole inner width (one group);
  ``W_out y``.

It reads the program's parameter tree by its flax names
(``models/hybrid.py``) and nothing else of the program; the departures from
the published model are in the configuration file's ``changed`` and
``assumed``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _attention(p, h, heads, kv_heads, scale):
    b, t, _ = h.shape
    q = (h @ p["q"]["kernel"]).reshape(b, t, heads, -1)
    k = (h @ p["k"]["kernel"]).reshape(b, t, kv_heads, -1)
    v = (h @ p["v"]["kernel"]).reshape(b, t, kv_heads, -1)
    mask = jnp.tril(jnp.ones((t, t), bool))

    def one_head(i):
        kv = i // (heads // kv_heads)
        s = scale * q[:, :, i] @ jnp.swapaxes(k[:, :, kv], 1, 2)   # [b, t, t]
        s = jnp.where(mask, s, -jnp.inf)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        return (e / jnp.sum(e, axis=-1, keepdims=True)) @ v[:, :, kv]

    out = jax.lax.map(one_head, jnp.arange(heads))          # [heads, b, t, hd]
    return out.transpose(1, 2, 0, 3).reshape(b, t, -1) @ p["o"]["kernel"]


def recurrence(x, dt, A, B, C, D):
    """The state-space model one position at a time: ``x`` ``[b, t, H, P]``,
    ``dt`` ``[b, t, H]``, ``A`` and ``D`` ``[H]``, ``B`` and ``C``
    ``[b, t, N]``; the state ``S`` is ``[b, H, P, N]`` and starts at zero."""
    def step(S, at):
        x_t, dt_t, B_t, C_t = at
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :])
        return S, jnp.sum(S * C_t[:, None, None, :], axis=-1)

    S0 = jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:], x.dtype)
    _, y = jax.lax.scan(step, S0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1) + D[:, None] * x


def _mamba(p, h, heads, state, eps):
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
    B, C = xbc[..., inner:inner + state], xbc[..., inner + state:]
    dt = jnp.log1p(jnp.exp(dt + p["dt_bias"]))             # softplus, [b,t,H]
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), B, C, p["D"])
    y = _rms_norm(y.reshape(b, t, inner) * _silu(z), p["gate_norm"]["scale"],
                  eps)
    return y @ p["out_proj"]["kernel"]


@partial(jax.jit, static_argnames=("kind", "heads", "kv_heads", "ssm_heads",
                                   "state", "scale", "residual", "eps"))
def block(p, x, kind, heads, kv_heads, ssm_heads, state, scale, residual, eps):
    """One block of either ``kind``, ``x`` ``[b, t, d]`` float32."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), p)
        h = _rms_norm(x, p["norm_mixer"]["scale"], eps)
        if kind == "attention":
            h = _attention(p["mixer"], h, heads, kv_heads, scale)
        else:
            h = _mamba(p["mixer"], h, ssm_heads, state, eps)
        x = x + residual * h
        ab = _rms_norm(x, p["norm_ffn"]["scale"], eps) @ p["ffn_in"]["kernel"]
        a, b_ = jnp.split(ab, 2, axis=-1)
        return x + residual * ((_silu(a) * b_) @ p["ffn_out"]["kernel"])


@partial(jax.jit, static_argnames=("eps", "scaling"))
def head(params, x, eps, scaling):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, params["norm_f"]["scale"].astype(jnp.float32), eps)
        return x @ params["tok_emb"]["embedding"].astype(jnp.float32).T \
            / scaling


def forward(params, tokens, config: dict):
    """Logits ``[B, T, rows]`` in float32 for int tokens ``[B, T]``, from
    the configuration's published keys. Called outside a jit, the layers of
    one kind run one compiled ``block`` program."""
    c = config
    x = params["tok_emb"]["embedding"].astype(jnp.float32)[tokens] \
        * float(c["embedding_multiplier"])
    for i, kind in enumerate(c["layer_types"][:c["num_hidden_layers"]]):
        x = block(params[f"block_{i}"], x, kind, c["num_attention_heads"],
                  c["num_key_value_heads"], c["mamba_n_heads"],
                  c["mamba_d_state"], float(c["attention_multiplier"]),
                  float(c["residual_multiplier"]), float(c["rms_norm_eps"]))
    return head(params, x, float(c["rms_norm_eps"]),
                float(c["logits_scaling"]))
