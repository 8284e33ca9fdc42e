"""The plain reference: GPT-2's forward pass and loss in float32 jax.numpy.

No kernels, no cache, no batching tricks, no bf16: every matrix product
runs under ``jax.default_matmul_precision("highest")`` (on a TPU a float32
product otherwise runs in bf16 passes). It follows the published model
(Radford et al. 2019; pre-LN blocks, learned positions, fused QKV
multi-head causal attention, 4x tanh-GELU MLP, tied output head) with the
departures the configuration files list under ``changed`` and ``assumed``:
LayerNorm eps is the program's 1e-6, there is no dropout, and the fused
QKV columns are laid out head-major ``[head][q,k,v][head_dim]`` as
``models/transformer.py`` lays them out (a fixed permutation of the
published ``[q,k,v][head][head_dim]``).

It reads the program's parameter tree (flax names: ``tok_emb.embedding``,
``pos_emb``, ``block_<i>.{ln_attn,qkv,proj,ln_mlp,mlp_in,mlp_out}``,
``ln_f``) and nothing else of the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


@jax.jit
def embed(params, tokens):
    """Token plus learned position embeddings, ``[B, T, d]`` float32."""
    t = tokens.shape[1]
    return (params["tok_emb"]["embedding"].astype(jnp.float32)[tokens]
            + params["pos_emb"].astype(jnp.float32)[:t])


@partial(jax.jit, static_argnames=("n_head", "eps"))
def block(p, x, n_head: int, eps: float):
    """One pre-LN block: causal multi-head attention, then the MLP."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), p)
        b, t, d = x.shape
        hd = d // n_head
        h = _layer_norm(x, p["ln_attn"], eps)
        qkv = _dense(h, p["qkv"]).reshape(b, t, n_head, 3, hd)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + _dense(a.reshape(b, t, d), p["proj"])
        h = _layer_norm(x, p["ln_mlp"], eps)
        return x + _dense(_gelu_new(_dense(h, p["mlp_in"])), p["mlp_out"])


@partial(jax.jit, static_argnames=("eps",))
def head(params, x, eps: float):
    """Final LayerNorm and the tied output head: logits in float32."""
    with jax.default_matmul_precision("highest"):
        x = _layer_norm(x, jax.tree_util.tree_map(
            lambda l: l.astype(jnp.float32), params["ln_f"]), eps)
        return x @ params["tok_emb"]["embedding"].astype(jnp.float32).T


def forward(params, tokens, n_head: int, eps: float):
    """Logits ``[B, T, vocab_rows]`` in float32 for int tokens ``[B, T]``.

    Called outside a jit, every block runs the one compiled ``block``
    program (the layers share their shapes), so a 36-layer reference costs
    one small compilation and one small cache entry, not a 36-layer one."""
    x = embed(params, tokens)
    n_layer = sum(1 for k in params if k.startswith("block_"))
    for i in range(n_layer):
        x = block(params[f"block_{i}"], x, n_head, eps)
    return head(params, x, eps)


def loss(logits, targets):
    """Mean next-token cross entropy over every position."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
