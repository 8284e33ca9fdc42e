"""The Qwen3-Next family (``"model_type": "qwen3_next"``): gated
delta-rule linear attention (Gated DeltaNet: fewer key heads than value
heads over a ``[K, V]`` state a value head, a scalar decay and a write
strength a head and position, a causal conv before it and a gated norm
after it) in every layer but each ``full_attention_interval``-th, which is
gated softmax attention (grouped KV heads, q and k normed a head, a partial
rotary turn, a sigmoid gate a head and channel); every block routes to the
top-k of softmax scores beside a shared expert under a sigmoid gate a
token; RMSNorm, an untied head. The program's model is
``models/hybrid.HybridLM``; the plain reference is
``chipbench/reference_qwen3_next.py``.

A configuration of this family states the chip's share of its deployment:
``num_experts`` experts held here (ids ``held_experts``) of the
``num_experts_published`` the router scores, and ``vocab_size`` rows of the
table and of the head. Program and reference are given the same share.

The six names of a family (``PERF.md`` section 3) and ``gdn_train_costs``
for ``delta_rule_roofline`` (``chipbench/gated_delta_cost.py``). **No
``moe_train_costs``, so no ``moe_experts_roofline`` in this family's
cell**: that share divides the products' least time over the rows a
balanced router sends here (16,384 x 10 x 16 / 512 = 5,120) by the time
taken over the rows that came, and a run's steps of Adam move the 512-wide
router far from balance within a window (PERF.md section 6, PR 46: the
rows here, which ``reference_forward`` prints a layer); as
``families/deepseek_v3.py`` says of its own, it comes back when something
holds the routing near balance.
"""

from __future__ import annotations

import math
from typing import Dict, List

from .. import flops, gated_delta_cost, harness

#: the toy of ``--rehearse``: one period (three delta-rule layers of 2 key
#: and 4 value heads of 32, then a gated attention layer of 4 heads over 2
#: KV heads of 64, a width the flash kernels take, a quarter of it rotary);
#: 2 of 8 experts held, three a token, a gated shared expert
REHEARSAL = {"num_hidden_layers": 4, "hidden_size": 128,
             "linear_num_key_heads": 2, "linear_num_value_heads": 4,
             "linear_key_head_dim": 32, "linear_value_head_dim": 32,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 64, "num_experts": 2, "num_experts_published": 8,
             "held_experts": [0, 1], "num_experts_per_tok": 3,
             "moe_intermediate_size": 64,
             "shared_expert_intermediate_size": 64, "vocab_size": 512,
             # the toy's own tau: eight softmax scores around 1/8, as
             # Laguna's toy
             "assumed": {"tie_tau": {"value": 5e-4}}}

MIXER_KINDS = {"linear_attention": "gated_delta",
               "full_attention": "full_attention"}

#: The reference's verdict on the program, block by block on the program's
#: own residual stream (``reference_forward``, on the stream
#: ``program_trace`` hands out), as ``families/laguna``'s.
#: Each limit lies between two readings on the chip (PERF.md section 6, PR
#: 46; 1 x 16,384 tokens; fresh weights, the weights 8 and 22 steps leave
#: and the weights a 30 s window leaves): the sound bf16 program's, and
#: those of controls that must fail.
#:
#: * a block's update (output minus input) against the reference's, rms over
#:   rms: 0.84-1.41% in a delta-rule block and **1.76-2.58% in an attention
#:   block**, whose update is a quarter as large (0.13-0.22 rms on a stream
#:   of 1.0-1.7, against 0.59-0.71), so that the bf16 stream's own rounding
#:   at the two residual adds is most of what is read. The controls: every
#:   matrix rounded to 4 bits of mantissa (e4m3's) 4.89-5.26% in an
#:   attention block and 6.5-9.3% in a delta-rule block; the rule's
#:   correction dropped (``S_t = S' + k_t (beta_t v_t)^T``) 38-46% in every
#:   delta-rule block; its decay dropped (``g = 0``) 114-118%; the
#:   attention's gate dropped 53-64% in both attention blocks. The limit is
#:   1.4 times the sound program's worst and 1.36 times under the controls'
#:   least: there is no more room between a block whose update is small and
#:   4 bits of mantissa.
#: * the share of a layer's (token, layer) pairs in which the program chose
#:   an expert farther than tau from the reference's boundary: 0-0.41% of
#:   16,384 at the configuration's tau of 1e-4 (0.04-1.09% at 8e-5, 0-0.1%
#:   at 1.5e-4); the 4-bit control 10.7-66% at 1.5e-4, the dropped
#:   correction 88-99.8%, the dropped decay 99.9%, the dropped gate 46-55%
#:   in the attention blocks.
#:
#: The share of pairs *within* tau of the boundary is printed and not
#: limited (40% at 8e-5, 49% at 1e-4, 61% at 1.5e-4): the tenth and
#: eleventh of 512 softmax scores lie close in most tokens whatever the
#: program does.
UPDATE_LIMIT, OUTSIDE_LIMIT = 0.036, 0.03


def _layers(config: dict) -> int:
    return config["num_hidden_layers"]


def layer_types(config: dict) -> List[str]:
    """``linear_attention`` but every ``full_attention_interval``-th layer,
    which is ``full_attention``: the published rule."""
    every = config["full_attention_interval"]
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(_layers(config))]


def _check(config: dict) -> None:
    """What of the family this file does not build is refused, not
    ignored."""
    wrong = [key for key, want in (
        ("decoder_sparse_step", 1), ("mlp_only_layers", []),
        ("norm_topk_prob", True), ("rope_scaling", None),
        ("use_sliding_window", False), ("tie_word_embeddings", False),
        ("hidden_act", "silu")) if config.get(key) != want]
    if wrong:
        raise harness.BenchmarkError(
            f"qwen3_next: {wrong} of the configuration is not what this "
            f"family builds (every block routes, weights renormalised, no "
            f"rope scaling, no window, an untied head, SiLU)")


def build_model(config: dict, vocab_rows: int, mix: dict):
    """The program's model through the library's public constructor."""
    from horovod_tpu.models.hybrid import HybridLM

    if "delta_key_heads" not in HybridLM.__dataclass_fields__:
        raise harness.BenchmarkError(
            "this checkout's models/hybrid.HybridLM has no gated delta-rule "
            "mixer: it cannot build a qwen3_next model")
    _check(config)
    c = config
    rotary = int(c["head_dim"] * c["partial_rotary_factor"])
    return HybridLM(
        vocab_size=vocab_rows,
        layer_kinds=tuple(MIXER_KINDS[k] for k in layer_types(c)),
        ffn_kinds=("moe",) * _layers(c), d_model=c["hidden_size"],
        ffn_width=c["intermediate_size"],
        attn_heads=c["num_attention_heads"],
        attn_kv_heads=c["num_key_value_heads"], attn_head_dim=c["head_dim"],
        attn_position="rope", attn_rope_theta=float(c["rope_theta"]),
        attn_qk_norm=True, attn_gate="channel",
        attn_kinds={"full_attention": {"rotary_dim": rotary}},
        delta_key_heads=c["linear_num_key_heads"],
        delta_value_heads=c["linear_num_value_heads"],
        delta_key_dim=c["linear_key_head_dim"],
        delta_value_dim=c["linear_value_head_dim"],
        ssm_conv_width=c["linear_conv_kernel_dim"],
        norm_eps=c["rms_norm_eps"], remat=mix.get("remat", "none"),
        moe_experts=c["num_experts_published"],
        moe_held=tuple(c["held_experts"]),
        moe_top_k=c["num_experts_per_tok"],
        moe_width=c["moe_intermediate_size"],
        moe_shared_width=c["shared_expert_intermediate_size"],
        moe_shared_gate=True, moe_norm_eps=0.0, moe_scoring="softmax",
        tied_head=False, pin_stream=True)


def program_trace(params, tokens, config: dict):
    """``(logits, [each block's output], {"block_<i>": {"chosen", "scores",
    "load"}})`` as the program's own model computes them on ``params``
    (its bf16 path, no recomputation), the outputs through Flax's capture
    as the other routed families read them.

    **Why this family's model holds its stream** (``HybridLM(pin_stream=
    True)``, :func:`build_model`; PERF.md section 6, PR 46). A block's
    update here is as large as the stream it is added to (0.6 rms on 1.0
    to 1.5; the table's rows are 0.02), so a rounding of the stream grows
    five times on its way to the logits (float32 reference, a 0.1%
    perturbation of the first block's output). XLA fuses a block's last
    add into the next block's first operations and rounds what it fuses
    otherwise than what it writes out, so a compilation that hands out the
    stream computed other logits than one that does not, by 2.5-3.3% of
    their rms on the chip, where the job allows 2%. With the stream held at
    every block's boundary the two are 8e-6 apart, and the stream handed
    out here is the one under the logits the job compares."""
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
               for i in range(_layers(config))]
    routing = {name: {key: layer["ffn"][key][0] for key in
                      ("chosen", "scores", "load")}
               for name, layer in blocks.items()}
    return logits, outputs, routing


def flash_route(config: dict, seq: int) -> dict:
    """Which flash kernels a head of ``seq`` positions takes at this
    family's head width (``pallas_kernels.flash_route``, bf16)."""
    from horovod_tpu.ops import pallas_kernels as pk

    return pk.flash_route(seq, seq, config["head_dim"], 2)


def reference_forward(params, tokens, config: dict):
    """Float32 logits ``[B, T, vocab_rows]`` from the plain reference run
    block by block on the program's own residual stream
    (:func:`program_trace`), under its tie rule at the width
    ``assumed.tie_tau`` of the configuration (``reference_qwen3_next``'s
    docstring): the head over the last reference block on the program's
    input to it. Says what it found, each routed layer's rows here and the
    path the flash kernels took among it, and returns NaN logits, which no
    comparison passes, where a block's update (every block's, the last
    one's too) or the program's routing differs from the reference's by
    more than rounding (:data:`UPDATE_LIMIT`, :data:`OUTSIDE_LIMIT`)."""
    import jax.numpy as jnp
    import numpy as np

    from .. import reference_qwen3_next as reference

    tau = float(config["assumed"]["tie_tau"]["value"])
    held = list(config["held_experts"])
    _, outputs, routing = program_trace(params, tokens, config)
    seq = tokens.shape[1]
    harness.say(f"  full_attention: flash_route({seq}, {seq}, "
                f"{config['head_dim']}, 2) = {flash_route(config, seq)}")
    logits, stats = reference.forward_from_program(params, tokens, config,
                                                   outputs, routing, tau)
    del outputs
    harness.say(f"  block by block on the program's stream, tau {tau}:")
    sound = True
    for kind, layer in zip(layer_types(config), stats):
        update, tied, followed, outside, score_rms = (
            float(layer[k]) for k in (
                "update_error", "tied", "followed", "outside", "score_rms"))
        load = np.asarray(routing[layer["layer"]]["load"])[held]
        harness.say(
            f"  {layer['layer']} ({kind}): update error {update:.5f}; tied "
            f"{100 * tied:.3f}% of pairs, program's choice taken "
            f"{100 * followed:.3f}%, program differed outside tau "
            f"{100 * outside:.4f}%; program-minus-reference score rms "
            f"{score_rms:.2e}; held experts' load max/mean "
            f"{load.max() / max(load.mean(), 1e-9):.3f}, rows here "
            f"{int(load.sum())}")
        sound = sound and update <= UPDATE_LIMIT and outside <= OUTSIDE_LIMIT
    if not sound:
        harness.say(f"  qwen3_next reference: a block's update is over "
                    f"{UPDATE_LIMIT} of the reference's, or the program's "
                    f"routing differs outside tau in over "
                    f"{100 * OUTSIDE_LIMIT}% of a layer's pairs: no match")
        return jnp.full_like(logits, jnp.nan)
    return logits


def layer_parameters(config: dict) -> Dict[str, int]:
    """The matrix elements of each part of a layer (norm weights, the
    conv's taps and the per-head decays left out): what
    ``train_flops_per_token`` multiplies and the tests count."""
    c, d = config, config["hidden_size"]
    keys = c["linear_num_key_heads"] * c["linear_key_head_dim"]
    values = c["linear_num_value_heads"] * c["linear_value_head_dim"]
    heads, kv = (n * c["head_dim"] for n in (c["num_attention_heads"],
                                             c["num_key_value_heads"]))
    return {"delta_in": d * (2 * keys + 2 * values),
            "delta_gates": d * 2 * c["linear_num_value_heads"],
            "delta_out": values * d,
            "attn_q_gate": 2 * d * heads, "attn_kv": 2 * d * kv,
            "attn_o": heads * d,
            "router": d * c["num_experts_published"],
            "shared": 3 * d * c["shared_expert_intermediate_size"],
            "shared_gate": d, "expert": 3 * d * c["moe_intermediate_size"]}


def _experts_here(config: dict) -> float:
    """Of a token's experts, those held here under a balanced router."""
    return config["num_experts_per_tok"] * len(config["held_experts"]) \
        / config["num_experts_published"]


def train_flops_per_token(config: dict, vocab_rows: int, seq: int) -> float:
    """Forward plus backward operations one trained token requires **on
    this chip**.

    6 x the matrix elements a token touches (:func:`layer_parameters`): a
    delta-rule layer's ``W_qkvz``, ``W_ba`` and ``W_out``; a full layer's
    ``Wq``, ``Wg``, ``Wk``, ``Wv`` and ``Wo``; in every layer the router's
    d E, the shared expert's 3 d S and its gate's d and, of the token's
    ``top_k`` experts of 3 d f, the share held here (``top_k held / E``
    experts on average); the untied head's d V over the rows held (the
    table is a lookup). A full layer adds 6 s H head_dim for QK^T and PV
    and their gradients (causal: half the sequence on average); a
    delta-rule layer the recurrence's own 21 Hv K V
    (``gated_delta_cost.gated_delta_train_cost``). Recomputation, the key
    heads' and KV heads' broadcast, the chunked form's system, inverse and
    masked products, the worst-case dispatch buffer, and rows the router
    sends here beyond that average are not required work."""
    c, p, kinds = config, layer_parameters(config), layer_types(config)
    linear, full = (kinds.count(k) for k in ("linear_attention",
                                             "full_attention"))
    routed = p["router"] + p["shared"] + p["shared_gate"] \
        + _experts_here(c) * p["expert"]
    matrices = linear * (p["delta_in"] + p["delta_gates"] + p["delta_out"]) \
        + full * (p["attn_q_gate"] + p["attn_kv"] + p["attn_o"]) \
        + _layers(c) * routed + c["hidden_size"] * vocab_rows
    return 6.0 * matrices \
        + full * 6.0 * seq * c["num_attention_heads"] * c["head_dim"] \
        + linear * 21.0 * c["linear_num_value_heads"] \
        * c["linear_key_head_dim"] * c["linear_value_head_dim"]


def attention_train_costs(config: dict, per_chip_batch: int,
                          seq: int) -> List[Dict[str, float]]:
    """One ``{"flops", "bytes"}`` for each full-attention layer, over the
    published KV heads: handing the kernels each KV head eight times is the
    program's cost, not required work."""
    c = config
    cost = flops.flash_attention_train_cost(
        per_chip_batch, c["num_attention_heads"], seq, c["head_dim"],
        kv_heads=c["num_key_value_heads"])
    return [cost] * layer_types(c).count("full_attention")


def gdn_train_costs(config: dict, per_chip_batch: int,
                    seq: int) -> List[Dict[str, float]]:
    """One ``{"flops", "bytes"}`` for each delta-rule layer's recurrence."""
    c = config
    cost = gated_delta_cost.gated_delta_train_cost(
        per_chip_batch, seq, c["linear_num_key_heads"],
        c["linear_num_value_heads"], c["linear_key_head_dim"],
        c["linear_value_head_dim"])
    return [cost] * layer_types(c).count("linear_attention")


def expected_first_loss(config: dict, vocab_rows: int) -> float:
    """ln(rows) + sigma^2/2: the final RMSNorm (weight 1) hands the head a
    vector of mean square 1, so a logit over an N(0, 0.02^2) head has
    variance d 0.02^2."""
    return math.log(vocab_rows) + config["hidden_size"] * 0.02 ** 2 / 2
