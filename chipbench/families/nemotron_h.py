"""The Nemotron-H family (``"model_type": "nemotron_h"``): blocks of **one
sublayer** each, its kind a character of ``hybrid_override_pattern``: ``M``
a Mamba-2 layer with ``n_groups`` groups of B and C, ``*`` a grouped-head
attention layer without position encoding, ``E`` a LatentMoE layer (sigmoid
top-k routing over the full hidden vector, squared-ReLU experts inside a
latent, a shared expert beside them); RMSNorm, an untied head. The
program's model is ``models/hybrid.HybridLM``; the plain reference is
``chipbench/reference_nemotron_h.py``.

A configuration of this family states the chip's share of its deployment:
``n_routed_experts`` experts held here (ids ``held_experts``) of the
``n_routed_experts_published`` the router scores, and ``vocab_size`` rows of
the table and of the head. Program and reference are given the same share.

The six names of a family (``PERF.md`` section 3), and ``ssd_train_costs``
and ``moe_train_costs`` for ``ssd_roofline`` and ``moe_experts_roofline``.
"""

from __future__ import annotations

import math
from typing import Dict, List

from .. import flops, harness, moe_cost, moe_relu2_cost, ssd_grouped_cost

#: the toy of ``--rehearse``: every kind of layer; 2 of 8 experts held,
#: three a token; 2 state groups; 2 KV heads under 4 query heads; a latent
#: narrower than the model; a chunk that divides the mixes' rehearsal ``seq``
REHEARSAL = {"num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
             "hidden_size": 256, "mamba_num_heads": 4, "mamba_head_dim": 64,
             "n_groups": 2, "ssm_state_size": 16, "chunk_size": 16,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 64, "n_routed_experts": 2,
             "n_routed_experts_published": 8, "held_experts": [0, 1],
             "num_experts_per_tok": 3, "moe_intermediate_size": 64,
             "moe_latent_size": 64, "moe_shared_expert_intermediate_size": 512,
             "vocab_size": 512,
             # the toy's own tau: its program-minus-reference scores differ
             # by 1.2e-4 to 1.7e-4 rms (CPU rehearsal), six times that
             "assumed": {"tie_tau": {"value": 1e-3}}}

#: a block's (mixer, feed-forward) kinds in ``HybridLM``'s words
BLOCK_KINDS = {"M": ("mamba", "none"), "E": ("none", "moe"),
               "*": ("attention", "none")}

#: added to the sum of a token's chosen scores before it divides them (the
#: modelling code's)
ROUTE_NORM_EPS = 1e-20

#: The reference's verdict on the program, block by block on the program's
#: own residual stream (``reference_forward``), as ``families/lfm2_moe``'s.
#: Each limit lies between two readings on the chip (PERF.md section 6, PR
#: 34: fresh weights and the weights 100 steps leave): the sound bf16
#: program's, and those of the same program with every matrix rounded to 4
#: bits of mantissa (e4m3's), which must fail.
#:
#: * a block's update (output minus input) against the reference's, rms over
#:   rms: 0.6-1.6% in a Mamba-2 block, 0.5-0.9% in a LatentMoE block,
#:   0.9% in the attention block (2.4% on fresh weights, where its update is
#:   small beside the stream the program rounds to bf16); the control
#:   5.8-10.7%, 3.5-4.1% and 1.9-4.7%: the Mamba-2 blocks fail it.
#: * the share of a layer's (token, layer) pairs in which the program chose
#:   an expert farther than tau from the reference's boundary: 0 of 4,096 in
#:   every layer at tau 0.004; the control 8.4-15.1%.
#:
#: The share of pairs *within* tau of the boundary is printed and not
#: limited: with 512 experts the 22nd and 23rd scores lie closer than tau in
#: most tokens, so 92-97% of pairs are tied whatever the program does.
UPDATE_LIMIT, OUTSIDE_LIMIT = 0.04, 5e-3


def _pattern(config: dict) -> str:
    return config["hybrid_override_pattern"][:config["num_hidden_layers"]]


def build_model(config: dict, vocab_rows: int, mix: dict):
    """The program's model through the library's public constructor."""
    from horovod_tpu.models.hybrid import HybridLM

    if "tied_head" not in HybridLM.__dataclass_fields__:
        raise harness.BenchmarkError(
            "this checkout's models/hybrid.HybridLM has no one-sublayer "
            "blocks, latent experts or untied head: it cannot build a "
            "nemotron_h model")
    c = config
    mixers, ffns = zip(*(BLOCK_KINDS[k] for k in _pattern(c)))
    return HybridLM(
        vocab_size=vocab_rows, layer_kinds=mixers, ffn_kinds=ffns,
        d_model=c["hidden_size"], ffn_width=0,
        attn_heads=c["num_attention_heads"],
        attn_kv_heads=c["num_key_value_heads"], attn_head_dim=c["head_dim"],
        ssm_heads=c["mamba_num_heads"], ssm_head_dim=c["mamba_head_dim"],
        ssm_state=c["ssm_state_size"], ssm_conv_width=c["conv_kernel"],
        ssm_chunk=c["chunk_size"], ssm_groups=c["n_groups"],
        norm_eps=c["layer_norm_epsilon"], remat=mix.get("remat", "none"),
        moe_experts=c["n_routed_experts_published"],
        moe_held=tuple(c["held_experts"]),
        moe_top_k=c["num_experts_per_tok"],
        moe_width=c["moe_intermediate_size"], moe_activation="relu2",
        moe_latent=c["moe_latent_size"],
        moe_shared_width=c["moe_shared_expert_intermediate_size"],
        moe_scale=float(c["routed_scaling_factor"]),
        moe_norm_eps=ROUTE_NORM_EPS, tied_head=False)


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
    (``reference_nemotron_h``'s docstring). Says what it found, the rows
    routed to the experts held here among it, and returns NaN logits, which
    no comparison passes, where a block's update or the program's routing
    differs from the reference's by more than rounding
    (:data:`UPDATE_LIMIT`, :data:`OUTSIDE_LIMIT`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import reference_nemotron_h as reference

    tau = float(config["assumed"]["tie_tau"]["value"])
    held = list(config["held_experts"])
    program_logits, outputs, routing = program_trace(params, tokens, config)
    # one fused pass: op by op it would hold two more copies of the logits
    relative = jax.jit(lambda got, want: jnp.sqrt(
        jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))
    free = float(relative(program_logits,
                          reference.forward(params, tokens, config)))
    del program_logits
    harness.say(f"  nemotron_h reference, free-running and every choice its "
                f"own: the program's logits leave it by {free:.5f} of its rms")
    logits, stats = reference.forward_from_program(params, tokens, config,
                                                   outputs, routing, tau)
    harness.say(f"  block by block on the program's stream, tau {tau}:")
    sound = True
    for kind, layer in zip(_pattern(config), stats):
        update = float(layer["update_error"])
        line = f"  {layer['layer']} ({kind}): update error {update:.5f}"
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
            sound = sound and outside <= OUTSIDE_LIMIT
        harness.say(line)
    if not sound:
        harness.say(f"  nemotron_h reference: a block's update is over "
                    f"{UPDATE_LIMIT} of the reference's, or the program's "
                    f"routing differs outside tau in over "
                    f"{100 * OUTSIDE_LIMIT}% of a layer's pairs: no match")
        return jnp.full_like(logits, jnp.nan)
    return logits


def _experts_here(config: dict) -> float:
    """Of a token's experts, those held here under a balanced router."""
    return config["num_experts_per_tok"] * len(config["held_experts"]) \
        / config["n_routed_experts_published"]


def train_flops_per_token(config: dict, vocab_rows: int, seq: int) -> float:
    """Forward plus backward operations one trained token requires **on
    this chip**.

    6 x the matrix elements a token touches: an ``M`` layer's in_proj
    d (2 I + 2 G N + H) and out_proj I d, with I = H P the inner width; a
    ``*`` layer's q and o, d A each with A = heads x head_dim, and k and v,
    d (kv_heads x head_dim) each; in an ``E`` layer the router's d E, the
    latent's down- and up-projection d l each, the shared expert's 2 d S
    and, of the token's ``top_k`` experts of 2 l f, the share held here
    (``top_k held / E`` experts on average); the untied head's d V over
    the rows held (the table is a lookup). A ``*`` layer adds 6 s A for
    QK^T and PV (causal: half the sequence on average); an ``M`` layer the
    recurrence's own 15 H P N (``ssd_grouped_cost``). Recomputation, the KV
    heads' broadcast, the dual form's extra matmuls, the worst-case
    dispatch buffer, and rows the router sends here beyond that average
    are not required work."""
    c, pattern = config, _pattern(config)
    d, l = c["hidden_size"], c["moe_latent_size"]
    h, p, n = c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"]
    inner = h * p
    mamba = d * (2 * inner + 2 * c["n_groups"] * n + h) + inner * d
    attn = c["num_attention_heads"] * c["head_dim"]
    attention = 2 * d * attn \
        + 2 * d * c["num_key_value_heads"] * c["head_dim"]
    routed = d * c["n_routed_experts_published"] + 2 * d * l \
        + 2 * d * c["moe_shared_expert_intermediate_size"] \
        + _experts_here(c) * 2 * l * c["moe_intermediate_size"]
    n_mamba, n_attn = pattern.count("M"), pattern.count("*")
    matrices = n_mamba * mamba + n_attn * attention \
        + pattern.count("E") * routed + d * vocab_rows
    return 6.0 * matrices + n_attn * 6.0 * seq * attn \
        + n_mamba * 15.0 * h * p * n


def attention_train_costs(config: dict, per_chip_batch: int,
                          seq: int) -> List[Dict[str, float]]:
    """One ``{"flops", "bytes"}`` for each attention layer, over the
    published KV heads: handing the kernels each KV head sixteen times is
    the program's cost, not required work."""
    cost = flops.flash_attention_train_cost(
        per_chip_batch, config["num_attention_heads"], seq,
        config["head_dim"], kv_heads=config["num_key_value_heads"])
    return [cost] * _pattern(config).count("*")


def ssd_train_costs(config: dict, per_chip_batch: int,
                    seq: int) -> List[Dict[str, float]]:
    """One ``{"flops", "bytes"}`` for each Mamba-2 layer's scan."""
    cost = ssd_grouped_cost.ssd_train_cost(
        per_chip_batch, seq, config["mamba_num_heads"],
        config["mamba_head_dim"], config["ssm_state_size"],
        config["n_groups"])
    return [cost] * _pattern(config).count("M")


def moe_train_costs(config: dict, per_chip_batch: int,
                    seq: int) -> List[Dict[str, float]]:
    """One ``{"flops", "bytes"}`` for each routed layer's grouped products
    (squared-ReLU experts inside the latent), over the rows a balanced
    router sends to the experts held here."""
    c, held = config, len(config["held_experts"])
    rows = moe_cost.expected_rows(per_chip_batch * seq,
                                  c["num_experts_per_tok"], held,
                                  c["n_routed_experts_published"])
    cost = moe_relu2_cost.moe_train_cost(rows, c["moe_latent_size"],
                                         c["moe_intermediate_size"], held)
    return [cost] * _pattern(c).count("E")


def expected_first_loss(config: dict, vocab_rows: int) -> float:
    """ln(rows) + sigma^2/2: the final RMSNorm (weight 1) hands the head a
    vector of mean square 1, so a logit over an N(0, 0.02^2) head has
    variance d 0.02^2."""
    return math.log(vocab_rows) + config["hidden_size"] * 0.02 ** 2 / 2
