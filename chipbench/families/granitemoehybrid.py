"""The Granite 4.0-H family (``"model_type": "granitemoehybrid"``, here
without experts: ``num_local_experts`` 0): Mamba-2 layers with an attention
layer every tenth, a SwiGLU feed-forward in each, RMSNorm, a tied head and
four multipliers. The program's model is ``models/hybrid.HybridLM``; the
plain reference is ``chipbench/reference_granitemoehybrid.py``.

The six names of a family (``PERF.md`` section 3), and ``ssd_train_costs``
for the family's own per-layer metric, ``ssd_roofline``.
"""

from __future__ import annotations

import math
from typing import Dict, List

from .. import flops, ssd_cost

#: the toy period of ``--rehearse``: every kind of layer, and a chunk that
#: divides the mixes' rehearsal ``seq``
REHEARSAL = {"num_hidden_layers": 4,
             "layer_types": ["mamba", "mamba", "attention", "mamba"],
             "hidden_size": 128, "shared_intermediate_size": 256,
             "num_attention_heads": 2, "num_key_value_heads": 1,
             "mamba_n_heads": 4, "mamba_d_head": 64, "mamba_d_state": 16,
             "mamba_chunk_size": 16, "vocab_size": 500}


def _layer_kinds(config: dict) -> List[str]:
    return config["layer_types"][:config["num_hidden_layers"]]


def build_model(config: dict, vocab_rows: int, mix: dict):
    """The program's model through the library's public constructor."""
    from horovod_tpu.models.hybrid import HybridLM

    c = config
    return HybridLM(
        vocab_size=vocab_rows, layer_kinds=tuple(_layer_kinds(c)),
        d_model=c["hidden_size"], ffn_width=c["shared_intermediate_size"],
        attn_heads=c["num_attention_heads"],
        attn_kv_heads=c["num_key_value_heads"],
        attn_head_dim=c["hidden_size"] // c["num_attention_heads"],
        ssm_heads=c["mamba_n_heads"], ssm_head_dim=c["mamba_d_head"],
        ssm_state=c["mamba_d_state"], ssm_conv_width=c["mamba_d_conv"],
        ssm_chunk=c["mamba_chunk_size"],
        attention_multiplier=c["attention_multiplier"],
        embedding_multiplier=c["embedding_multiplier"],
        residual_multiplier=c["residual_multiplier"],
        logits_scaling=c["logits_scaling"], norm_eps=c["rms_norm_eps"],
        remat=mix.get("remat", "none"))


def reference_forward(params, tokens, config: dict):
    """Float32 logits ``[B, T, vocab_rows]`` from the plain reference."""
    from .. import reference_granitemoehybrid

    return reference_granitemoehybrid.forward(params, tokens, config)


def train_flops_per_token(config: dict, vocab_rows: int, seq: int) -> float:
    """Forward plus backward operations one trained token requires.

    6 x the matrix elements a token touches (a multiply-add is two
    operations, the backward pass costs twice the forward): in every layer
    the feed-forward's 3 d F; in a Mamba-2 layer in_proj d (2 I + 2 N + H)
    and out_proj I d, with I = H P the inner width; in an attention layer
    q and o, d d each, and k and v, d (d kv/heads) each; the head's d V.
    An attention layer adds 6 s d for QK^T and PV (causal: half the
    sequence on average, as the GPT-2 family counts it); a Mamba-2 layer
    adds the recurrence's own 15 H P N (``ssd_cost.ssd_train_cost``).
    Recomputation, the KV heads' broadcast and the dual form's extra
    matmuls are not required work."""
    c, kinds = config, _layer_kinds(config)
    d, f = c["hidden_size"], c["shared_intermediate_size"]
    h, p, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    inner = h * p
    mamba = d * (2 * inner + 2 * n + h) + inner * d
    attention = 2 * d * d + 2 * d * d * c["num_key_value_heads"] \
        // c["num_attention_heads"]
    n_mamba, n_attn = kinds.count("mamba"), kinds.count("attention")
    matrices = len(kinds) * 3 * d * f + n_mamba * mamba + n_attn * attention \
        + d * vocab_rows
    return 6.0 * matrices + n_attn * 6.0 * seq * d + n_mamba * 15.0 * h * p * n


def attention_train_costs(config: dict, per_chip_batch: int,
                          seq: int) -> List[Dict[str, float]]:
    """One ``{"flops", "bytes"}`` for each attention layer, over the
    published KV heads: handing the kernels each KV head four times is the
    program's cost, not required work."""
    heads = config["num_attention_heads"]
    cost = flops.flash_attention_train_cost(
        per_chip_batch, heads, seq, config["hidden_size"] // heads,
        kv_heads=config["num_key_value_heads"])
    return [cost] * _layer_kinds(config).count("attention")


def ssd_train_costs(config: dict, per_chip_batch: int,
                    seq: int) -> List[Dict[str, float]]:
    """One ``{"flops", "bytes"}`` for each Mamba-2 layer's scan."""
    cost = ssd_cost.ssd_train_cost(
        per_chip_batch, seq, config["mamba_n_heads"], config["mamba_d_head"],
        config["mamba_d_state"])
    return [cost] * _layer_kinds(config).count("mamba")


def expected_first_loss(config: dict, vocab_rows: int) -> float:
    """ln(rows) + sigma^2/2: the final RMSNorm (weight 1) hands the tied
    head a vector of mean square 1, so a logit over N(0, 0.02^2) embeddings
    has variance d 0.02^2, divided by ``logits_scaling`` squared. (The one
    logit of the input token itself is large, since the residual stream
    starts as 12 times its embedding; among 100,352 it moves the loss by
    0.002.)"""
    sigma2 = config["hidden_size"] * 0.02 ** 2 / config["logits_scaling"] ** 2
    return math.log(vocab_rows) + sigma2 / 2
