"""The LFM2 mixture-of-experts family (``"model_type": "lfm2_moe"``): gated
short-convolution layers with a grouped-head attention layer (RoPE, RMSNorm
on q and k) every fourth, a dense SwiGLU feed-forward in the leading
layers and a top-k sigmoid-routed expert feed-forward in the others,
RMSNorm, a tied head. The program's model is ``models/hybrid.HybridLM``;
the plain reference is ``chipbench/reference_lfm2_moe.py``.

A configuration of this family states the chip's share of its deployment:
``num_experts`` experts held here (ids ``held_experts``) of the
``num_experts_published`` the router scores, and ``vocab_size`` rows of the
table. Program and reference are given the same share.

The six names of a family (``PERF.md`` section 3), and ``moe_train_costs``
for the family's own per-layer metric, ``moe_experts_roofline``.
"""

from __future__ import annotations

import math
from typing import Dict, List

from .. import flops, harness, moe_cost

#: the toy of ``--rehearse``: the leading dense layer and one period, every
#: kind of layer; 2 of 8 experts held, two a token
REHEARSAL = {"num_hidden_layers": 5,
             "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
             "num_dense_layers": 1, "hidden_size": 128,
             "intermediate_size": 256, "moe_intermediate_size": 64,
             "num_attention_heads": 2, "num_key_value_heads": 1,
             "num_experts": 2, "num_experts_published": 8,
             "held_experts": [0, 1], "num_experts_per_tok": 2,
             "vocab_size": 512,
             # the toy's own tau: its program-minus-reference scores differ
             # by 1.3e-4 to 2.8e-4 rms (CPU rehearsal), four times that
             "assumed": {"tie_tau": {"value": 1e-3}}}

MIXER_KINDS = {"conv": "short_conv", "full_attention": "attention"}

#: The reference's verdict on the program, block by block on the program's
#: own residual stream (``reference_forward``). Each limit lies between two
#: readings on the chip (PERF.md section 6, PR 32: fresh weights and the
#: weights 55 steps leave): the sound bf16 program's, and those of the same
#: program with every matrix rounded to 4 bits of mantissa (e4m3's), which
#: must fail.
#:
#: * a block's update (output minus input) against the reference's, rms over
#:   rms: 0.8-1.0% in a conv block and 1.7-2.2% in an attention block; the
#:   control 6.5-16%.
#: * the share of a layer's (token, layer) pairs in which the program chose
#:   an expert farther than tau from the reference's boundary: 0 of 131,072
#:   in every layer at tau 0.002 (0.018% at half that); the control 4.2-7.4%.
#: * the share of pairs within tau of the boundary, which is the reference's
#:   alone and says whether tau still decides anything: 14-15.3%.
UPDATE_LIMIT, OUTSIDE_LIMIT, TIED_LIMIT = 0.04, 1e-3, 0.25


def _layer_kinds(config: dict) -> List[str]:
    return config["layer_types"][:config["num_hidden_layers"]]


def _routed_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["num_dense_layers"]


def build_model(config: dict, vocab_rows: int, mix: dict):
    """The program's model through the library's public constructor."""
    from horovod_tpu.models.hybrid import HybridLM

    if "ffn_kinds" not in HybridLM.__dataclass_fields__:
        raise harness.BenchmarkError(
            "this checkout's models/hybrid.HybridLM has no feed-forward "
            "kinds: it cannot build an lfm2_moe model")
    c, kinds = config, _layer_kinds(config)
    dense = c["num_dense_layers"]
    return HybridLM(
        vocab_size=vocab_rows,
        layer_kinds=tuple(MIXER_KINDS[k] for k in kinds),
        d_model=c["hidden_size"], ffn_width=c["intermediate_size"],
        attn_heads=c["num_attention_heads"],
        attn_kv_heads=c["num_key_value_heads"],
        attn_head_dim=c["hidden_size"] // c["num_attention_heads"],
        norm_eps=c["norm_eps"], remat=mix.get("remat", "none"),
        attn_position="rope", attn_rope_theta=float(c["rope_theta"]),
        attn_qk_norm=True, conv_width=c["conv_L_cache"],
        ffn_kinds=("swiglu",) * dense + ("moe",) * (len(kinds) - dense),
        moe_experts=c["num_experts_published"],
        moe_held=tuple(c["held_experts"]),
        moe_top_k=c["num_experts_per_tok"],
        moe_width=c["moe_intermediate_size"])


def program_trace(params, tokens, config: dict):
    """``(logits, [each block's output], {"block_<i>": {"chosen", "scores",
    "load"}})`` as the program's own model computes them on ``params`` (its
    bf16 path, no recomputation)."""
    import jax

    from horovod_tpu.models.hybrid import HybridBlock

    rows = params["tok_emb"]["embedding"].shape[0]
    model = build_model(config, rows, {"remat": "none"})
    logits, state = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, mutable=["intermediates"],
        capture_intermediates=lambda module, _: isinstance(
            module, HybridBlock)))(params, tokens)
    blocks = state["intermediates"]
    outputs = [blocks[f"block_{i}"]["__call__"][0]
               for i in range(config["num_hidden_layers"])]
    routing = {name: {key: layer["ffn"][key][0] for key in
                      ("chosen", "scores", "load")}
               for name, layer in blocks.items() if "ffn" in layer}
    return logits, outputs, routing


def reference_forward(params, tokens, config: dict):
    """Float32 logits ``[B, T, vocab_rows]`` from the plain reference run
    block by block on the program's own residual stream, under its tie rule
    at the width ``assumed.tie_tau`` of the configuration
    (``reference_lfm2_moe``'s docstring). Says what it found, and returns
    NaN logits, which no comparison passes, where a block's update or the
    program's routing differs from the reference's by more than rounding
    (:data:`UPDATE_LIMIT`, :data:`OUTSIDE_LIMIT`, :data:`TIED_LIMIT`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import reference_lfm2_moe as reference

    tau = float(config["assumed"]["tie_tau"]["value"])
    held = list(config["held_experts"])
    program_logits, outputs, routing = program_trace(params, tokens, config)
    # one fused pass: op by op it would hold two more copies of the logits
    relative = jax.jit(lambda got, want: jnp.sqrt(
        jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))
    free = float(relative(program_logits,
                          reference.forward(params, tokens, config)))
    del program_logits
    harness.say(f"  lfm2_moe reference, free-running and every choice its "
                f"own: the program's logits leave it by {free:.5f} of its rms")
    logits, stats = reference.forward_from_program(params, tokens, config,
                                                   outputs, routing, tau)
    harness.say(f"  block by block on the program's stream, tau {tau}:")
    sound = True
    for layer in stats:
        update = float(layer["update_error"])
        line = f"  {layer['layer']}: update error {update:.5f}"
        sound = sound and update <= UPDATE_LIMIT
        if "tied" in layer:
            tied, followed, outside, score_rms = (
                float(layer[k]) for k in ("tied", "followed", "outside",
                                          "score_rms"))
            load = np.asarray(routing[layer["layer"]]["load"])[held]
            line += (
                f"; tied {100 * tied:.3f}% of pairs, program's choice taken "
                f"{100 * followed:.3f}%, program differed outside tau "
                f"{100 * outside:.4f}%; program-minus-reference score rms "
                f"{score_rms:.2e}; held experts' load max/mean "
                f"{load.max() / max(load.mean(), 1e-9):.3f}, rows here "
                f"{int(load.sum())}")
            sound = sound and outside <= OUTSIDE_LIMIT and tied <= TIED_LIMIT
        harness.say(line)
    if not sound:
        harness.say(f"  lfm2_moe reference: a block's update is over "
                    f"{UPDATE_LIMIT} of the reference's, the program's "
                    f"routing differs outside tau in over "
                    f"{100 * OUTSIDE_LIMIT}% of a layer's pairs, or over "
                    f"{100 * TIED_LIMIT}% are tied: no match")
        return jnp.full_like(logits, jnp.nan)
    return logits


def train_flops_per_token(config: dict, vocab_rows: int, seq: int) -> float:
    """Forward plus backward operations one trained token requires **on
    this chip**.

    6 x the matrix elements a token touches: a ``conv`` mixer's in_proj
    d 3d and out_proj d d; an attention mixer's q and o, d d each, and k
    and v, d (d kv/heads) each; the dense feed-forward's 3 d F; in a routed
    layer the router's d E and, of the token's ``top_k`` experts of 3 d f,
    the share held here (``top_k held / E`` experts on average); the
    head's d V over the rows held. An attention layer adds 6 s d for QK^T
    and PV (causal: half the sequence on average). Recomputation, the KV
    heads' broadcast, the worst-case dispatch buffer, and rows the router
    sends here beyond that average are not required work."""
    c, kinds = config, _layer_kinds(config)
    d = c["hidden_size"]
    conv = 3 * d * d + d * d
    attention = 2 * d * d + 2 * d * d * c["num_key_value_heads"] \
        // c["num_attention_heads"]
    experts_here = c["num_experts_per_tok"] * len(c["held_experts"]) \
        / c["num_experts_published"]
    routed = d * c["num_experts_published"] \
        + experts_here * 3 * d * c["moe_intermediate_size"]
    n_attn = kinds.count("full_attention")
    matrices = kinds.count("conv") * conv + n_attn * attention \
        + c["num_dense_layers"] * 3 * d * c["intermediate_size"] \
        + _routed_layers(c) * routed + d * vocab_rows
    return 6.0 * matrices + n_attn * 6.0 * seq * d


def attention_train_costs(config: dict, per_chip_batch: int,
                          seq: int) -> List[Dict[str, float]]:
    """One ``{"flops", "bytes"}`` for each attention layer, over the
    published KV heads."""
    heads = config["num_attention_heads"]
    cost = flops.flash_attention_train_cost(
        per_chip_batch, heads, seq, config["hidden_size"] // heads,
        kv_heads=config["num_key_value_heads"])
    return [cost] * _layer_kinds(config).count("full_attention")


def moe_train_costs(config: dict, per_chip_batch: int,
                    seq: int) -> List[Dict[str, float]]:
    """One ``{"flops", "bytes"}`` for each routed layer's grouped products,
    over the rows a balanced router sends to the experts held here."""
    c, held = config, len(config["held_experts"])
    rows = moe_cost.expected_rows(per_chip_batch * seq,
                                  c["num_experts_per_tok"], held,
                                  c["num_experts_published"])
    cost = moe_cost.moe_train_cost(rows, c["hidden_size"],
                                   c["moe_intermediate_size"], held)
    return [cost] * _routed_layers(c)


def expected_first_loss(config: dict, vocab_rows: int) -> float:
    """ln(rows) + sigma^2/2: the final RMSNorm (weight 1) hands the tied
    head a vector of mean square 1, so a logit over N(0, 0.02^2) embeddings
    has variance d 0.02^2."""
    return math.log(vocab_rows) + config["hidden_size"] * 0.02 ** 2 / 2
