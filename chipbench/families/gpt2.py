"""The GPT-2 family (``"model_type": "gpt2"``): what of a training cell is
this architecture's and not the job's.

The recipe is the one ``models/transformer.py`` implements (pre-LN blocks,
learned positions, fused-QKV multi-head attention, 4x tanh-GELU MLP, tied
head); its plain reference is ``chipbench/reference.py``. A family gives
the six names below and nothing else (``PERF.md`` section 3); ``jax`` and
the program are imported inside the functions, as the jobs do.
"""

from __future__ import annotations

import math
from typing import Dict, List

from .. import flops

#: the configuration keys of ``--rehearse`` (a CPU walk through the harness;
#: its numbers carry ``rehearsal_`` names and mean nothing)
REHEARSAL = {"n_layer": 2, "n_embd": 128, "n_head": 2,
             "n_positions": 256, "vocab_size": 500}


def build_model(config: dict, vocab_rows: int, mix: dict):
    """The program's model through the library's public constructor, with
    its defaults (bf16 compute, f32 params, flash attention)."""
    from horovod_tpu.models.transformer import TransformerLM

    return TransformerLM(
        vocab_size=vocab_rows, num_layers=config["n_layer"],
        num_heads=config["n_head"], d_model=config["n_embd"],
        max_seq_len=config["n_positions"], remat=mix["remat"])


def reference_forward(params, tokens, config: dict):
    """Float32 logits ``[B, T, vocab_rows]`` from the plain reference."""
    from .. import reference

    return reference.forward(params, tokens, config["n_head"],
                             config["layer_norm_epsilon"])


def train_flops_per_token(config: dict, vocab_rows: int, seq: int) -> float:
    """Forward plus backward operations one trained token requires.

    6 x (block matrices 12 L d^2 + the head's d V): a multiply-add is two
    operations, the backward pass costs twice the forward. Attention adds
    QK^T and PV, 4 s d a token and layer in the forward pass, halved
    because a causal row sees half the sequence on average, times three
    for forward plus backward: 6 L s d. The head is counted (it is 11% of
    gpt2-medium's operations at 50304 rows); recomputation is not."""
    n_layer, d = config["n_layer"], config["n_embd"]
    return 6.0 * (12 * n_layer * d * d + d * vocab_rows) \
        + 6.0 * n_layer * seq * d


def attention_train_costs(config: dict, per_chip_batch: int,
                          seq: int) -> List[Dict[str, float]]:
    """One ``{"flops", "bytes"}`` for each attention layer: every layer is
    one, with as many KV heads as query heads."""
    cost = flops.flash_attention_train_cost(
        per_chip_batch, config["n_head"], seq,
        config["n_embd"] // config["n_head"])
    return [cost] * config["n_layer"]


def expected_first_loss(config: dict, vocab_rows: int) -> float:
    """ln(rows) + sigma^2/2 with sigma^2 = d * 0.02^2 the variance of a
    tied-head logit over N(0, 0.02^2) embeddings on a unit-variance final
    LayerNorm (chip_smoke.py's reasoning)."""
    return math.log(vocab_rows) + config["n_embd"] * 0.02 ** 2 / 2
