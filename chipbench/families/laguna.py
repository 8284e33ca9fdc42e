"""The Laguna family (``"model_type": "laguna"``): window and full
attention in one model, each kind of layer with its own number of query
heads over the same grouped KV heads and its own rotary scheme (a base over
the whole head in the ``sliding_attention`` layers; YaRN frequencies over
part of it, scaled, in the ``full_attention`` ones), a sigmoid gate on every
head's output; a dense SwiGLU feed-forward in the ``dense`` layers and in
the ``sparse`` ones a top-k softmax-routed expert feed-forward beside a
shared expert; RMSNorm, an untied head. The program's model is
``models/hybrid.HybridLM``; the plain reference is
``chipbench/reference_laguna.py``.

A configuration of this family states the chip's share of its deployment:
``num_experts`` experts held here (ids ``held_experts``) of the
``num_experts_published`` the router scores, and ``vocab_size`` rows of the
table and of the head. Program and reference are given the same share.

The six names of a family (``PERF.md`` section 3), ``moe_train_costs`` for
``moe_experts_roofline`` and ``window_train_costs`` for
``attn_window_roofline``.
"""

from __future__ import annotations

import inspect
import math
from typing import Dict, List

from .. import flops, harness, moe_cost, window_attention_cost

#: the toy of ``--rehearse``: the leading dense layer and one period, both
#: kinds of attention layer (6 and 4 query heads over 2 KV heads of 64, a
#: window of 16 under the mixes' rehearsal ``seq`` so that the band binds,
#: both rotary schemes); 2 of 8 experts held, three a token, a shared one
REHEARSAL = {"num_hidden_layers": 5, "hidden_size": 128,
             "intermediate_size": 256, "num_attention_heads": 4,
             "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
             "num_key_value_heads": 2, "head_dim": 64, "sliding_window": 16,
             "num_experts": 2, "num_experts_published": 8,
             "held_experts": [0, 1], "num_experts_per_tok": 3,
             "moe_intermediate_size": 64,
             "shared_expert_intermediate_size": 64, "vocab_size": 512,
             # the toy's own tau: its program-minus-reference scores differ
             # by 7e-5 to 9e-5 rms around 1/8 (CPU rehearsal), six times that
             "assumed": {"tie_tau": {"value": 5e-4}}}

MIXER_KINDS = ("full_attention", "sliding_attention")
FFN_KINDS = {"dense": "swiglu", "sparse": "moe"}

#: The reference's verdict on the program, block by block on the program's
#: own residual stream (``reference_forward``), as ``families/lfm2_moe``'s.
#: Each limit lies between two readings on the chip (PERF.md section 6, PR
#: 39): the sound bf16 program's, and those of the same program with every
#: matrix rounded to 4 bits of mantissa (e4m3's), which must fail.
#:
#: * a block's update (output minus input) against the reference's, rms over
#:   rms: 1.0-1.5% in every block, fresh weights and after a window; the
#:   control 13-16% in the dense block and 5.1-5.7% in the routed ones. A
#:   window layer run without its window reads 21-22%.
#: * the share of a layer's (token, layer) pairs in which the program chose
#:   an expert farther than tau from the reference's boundary: 0.04-0.61% of
#:   8,192 at tau 1e-4 (2.7 x the scores' rms error); the control 20-21%.
#:
#: The share of pairs *within* tau of the boundary is printed and not
#: limited (15% at 6e-5, 23% at 1e-4): the tenth and eleventh of 256
#: softmax scores lie close in many tokens whatever the program does.
UPDATE_LIMIT, OUTSIDE_LIMIT = 0.03, 0.03


def _layers(config: dict) -> int:
    return config["num_hidden_layers"]


def _heads_of(config: dict) -> Dict[str, int]:
    """``{layer kind: its query heads}``: one count a kind."""
    c, heads = config, {}
    for kind, n in zip(c["layer_types"][:_layers(c)],
                       c["num_attention_heads_per_layer"]):
        if kind not in MIXER_KINDS or heads.setdefault(kind, n) != n:
            raise harness.BenchmarkError(
                f"laguna: layer kind {kind!r} with {n} heads; expected one "
                f"head count for each of {MIXER_KINDS}")
    if set(c.get("gating_types", ["per_head"])[:_layers(c)]) != {"per_head"}:
        raise harness.BenchmarkError("laguna: a gating type but per_head")
    return heads


def _rotary(config: dict, kind: str) -> dict:
    """``AttentionMixer``'s rotary fields for one entry of
    ``rope_parameters``."""
    from horovod_tpu.ops import rope

    r = config["rope_parameters"][kind]
    width = int(config["head_dim"] * r.get("partial_rotary_factor", 1))
    fields = {"rope_theta": float(r["rope_theta"])}
    if width != config["head_dim"]:
        fields["rotary_dim"] = width
    if r["rope_type"] == "yarn":
        fields["rope_inv_freq"] = rope.yarn_inv_freq(
            float(r["rope_theta"]), width, float(r["factor"]),
            int(r["original_max_position_embeddings"]),
            float(r["beta_fast"]), float(r["beta_slow"]))
        fields["rope_factor"] = float(r["attention_factor"])
    return fields


def build_model(config: dict, vocab_rows: int, mix: dict):
    """The program's model through the library's public constructor."""
    from horovod_tpu.models.hybrid import HybridLM
    from horovod_tpu.ops.pallas_kernels import flash_attention

    if "attn_kinds" not in HybridLM.__dataclass_fields__ or "window" \
            not in inspect.signature(flash_attention).parameters:
        raise harness.BenchmarkError(
            "this checkout's models/hybrid.HybridLM has no attention kinds, "
            "or its flash_attention no window: it cannot build a laguna "
            "model")
    c, heads = config, _heads_of(config)
    kinds = {kind: {"heads": n, **_rotary(c, kind)}
             for kind, n in heads.items()}
    kinds.get("sliding_attention", {})["window"] = c["sliding_window"]
    return HybridLM(
        vocab_size=vocab_rows, layer_kinds=tuple(c["layer_types"][:_layers(c)]),
        ffn_kinds=tuple(FFN_KINDS[k] for k in
                        c["mlp_layer_types"][:_layers(c)]),
        d_model=c["hidden_size"], ffn_width=c["intermediate_size"],
        attn_heads=c["num_attention_heads"],
        attn_kv_heads=c["num_key_value_heads"], attn_head_dim=c["head_dim"],
        attn_position="rope", attn_gate=True, attn_kinds=kinds,
        norm_eps=c["rms_norm_eps"], remat=mix.get("remat", "none"),
        moe_experts=c["num_experts_published"],
        moe_held=tuple(c["held_experts"]),
        moe_top_k=c["num_experts_per_tok"],
        moe_width=c["moe_intermediate_size"],
        moe_shared_width=c["shared_expert_intermediate_size"],
        moe_scale=float(c["moe_routed_scaling_factor"]), moe_norm_eps=0.0,
        moe_scoring="softmax", tied_head=False)


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
               for i in range(_layers(config))]
    routing = {name: {key: layer["ffn"][key][0] for key in
                      ("chosen", "scores", "load")}
               for name, layer in blocks.items() if "ffn" in layer}
    return logits, outputs, routing


def plan_shares(config: dict, seq: int) -> Dict[str, tuple]:
    """``{layer kind: (forward, backward)}``: ``flash_plan``'s scores
    computed over needed for one head of ``seq`` positions, at the tiles
    the kernels take."""
    from horovod_tpu.ops import pallas_kernels as pk

    shares = {}
    for kind in _heads_of(config):
        window = config["sliding_window"] if kind == "sliding_attention" \
            and config["sliding_window"] < seq else None
        block_q, block_k = pk.flash_tiles(seq, seq, window)
        if block_q is None or block_k is None:
            continue
        plans = [pk.flash_plan(True, seq, seq, 0, 0, block_k, *sub, window)
                 for sub in ((block_q, block_k),
                             pk._pick_sub_tile(True, block_q, block_k))]
        shares[kind] = tuple(p["scores"] / p["needed"] for p in plans)
    return shares


def reference_forward(params, tokens, config: dict):
    """Float32 logits ``[B, T, vocab_rows]`` from the plain reference run
    block by block on the program's own residual stream, under its tie rule
    at the width ``assumed.tie_tau`` of the configuration
    (``reference_laguna``'s docstring). Says what it found, the rows routed
    to the experts held here and the flash kernels' scores computed over
    needed among it, and returns NaN logits, which no comparison passes,
    where a block's update or the program's routing differs from the
    reference's by more than rounding (:data:`UPDATE_LIMIT`,
    :data:`OUTSIDE_LIMIT`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import reference_laguna as reference

    tau = float(config["assumed"]["tie_tau"]["value"])
    held = list(config["held_experts"])
    program_logits, outputs, routing = program_trace(params, tokens, config)
    # one fused pass: op by op it would hold two more copies of the logits
    relative = jax.jit(lambda got, want: jnp.sqrt(
        jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))
    free = float(relative(program_logits,
                          reference.forward(params, tokens, config)))
    del program_logits
    harness.say(f"  laguna reference, free-running and every choice its "
                f"own: the program's logits leave it by {free:.5f} of its rms")
    for kind, (fwd, bwd) in plan_shares(config, tokens.shape[1]).items():
        harness.say(f"  {kind}: flash_plan computes {fwd:.3f} (forward) and "
                    f"{bwd:.3f} (backward) times the needed scores")
    logits, stats = reference.forward_from_program(params, tokens, config,
                                                   outputs, routing, tau)
    harness.say(f"  block by block on the program's stream, tau {tau}:")
    sound = True
    for kind, layer in zip(config["layer_types"], stats):
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
        harness.say(f"  laguna reference: a block's update is over "
                    f"{UPDATE_LIMIT} of the reference's, or the program's "
                    f"routing differs outside tau in over "
                    f"{100 * OUTSIDE_LIMIT}% of a layer's pairs: no match")
        return jnp.full_like(logits, jnp.nan)
    return logits


def _experts_here(config: dict) -> float:
    """Of a token's experts, those held here under a balanced router."""
    return config["num_experts_per_tok"] * len(config["held_experts"]) \
        / config["num_experts_published"]


def train_flops_per_token(config: dict, vocab_rows: int, seq: int) -> float:
    """Forward plus backward operations one trained token requires **on
    this chip**.

    6 x the matrix elements a token touches: an attention layer's q and o,
    d A each with A = its heads x head_dim, k and v, d (kv_heads x
    head_dim) each, and the gate's d x heads; the dense feed-forward's
    3 d F; in a routed layer the router's d E, the shared expert's 3 d S
    and, of the token's ``top_k`` experts of 3 d f, the share held here
    (``top_k held / E`` experts on average); the untied head's d V over
    the rows held (the table is a lookup). A full-attention layer adds
    6 s A for QK^T and PV (causal: half the sequence on average); a window
    layer 12 A times the band's scores a query (``window_attention_cost``:
    the window, less what the first queries lack), not the triangle's.
    Recomputation, the KV heads' broadcast, scores a tile computes outside
    the band, the worst-case dispatch buffer, and rows the router sends
    here beyond that average are not required work."""
    c = config
    d, hd, total = c["hidden_size"], c["head_dim"], 0.0
    kv = c["num_key_value_heads"] * hd
    for kind, heads, ffn in zip(c["layer_types"][:_layers(c)],
                                c["num_attention_heads_per_layer"],
                                c["mlp_layer_types"]):
        total += 6.0 * (2 * d * heads * hd + 2 * d * kv + d * heads)
        if kind == "sliding_attention":
            total += 12.0 * heads * hd * window_attention_cost.needed_scores(
                seq, c["sliding_window"]) / seq
        else:
            total += 6.0 * seq * heads * hd
        if ffn == "dense":
            total += 6.0 * 3 * d * c["intermediate_size"]
        else:
            total += 6.0 * (
                d * c["num_experts_published"]
                + 3 * d * c["shared_expert_intermediate_size"]
                + _experts_here(c) * 3 * d * c["moe_intermediate_size"])
    return total + 6.0 * d * vocab_rows


def attention_train_costs(config: dict, per_chip_batch: int,
                          seq: int) -> List[Dict[str, float]]:
    """One ``{"flops", "bytes"}`` for each attention layer, in the model's
    order, over the published KV heads: a full layer's by
    ``flops.flash_attention_train_cost``, a window layer's by
    ``window_attention_cost`` (the band, not the triangle)."""
    c = config
    return [
        window_attention_cost.window_attention_train_cost(
            per_chip_batch, heads, seq, c["head_dim"], c["sliding_window"],
            kv_heads=c["num_key_value_heads"])
        if kind == "sliding_attention" else flops.flash_attention_train_cost(
            per_chip_batch, heads, seq, c["head_dim"],
            kv_heads=c["num_key_value_heads"])
        for kind, heads in zip(c["layer_types"][:_layers(c)],
                               c["num_attention_heads_per_layer"])]


def window_train_costs(config: dict, per_chip_batch: int,
                       seq: int) -> List[Dict[str, float]]:
    """The window layers' part of :func:`attention_train_costs`."""
    return [cost for kind, cost in zip(
        config["layer_types"], attention_train_costs(
            config, per_chip_batch, seq)) if kind == "sliding_attention"]


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
    return [cost] * c["mlp_layer_types"][:_layers(c)].count("sparse")


def expected_first_loss(config: dict, vocab_rows: int) -> float:
    """ln(rows) + sigma^2/2: the final RMSNorm (weight 1) hands the head a
    vector of mean square 1, so a logit over an N(0, 0.02^2) head has
    variance d 0.02^2."""
    return math.log(vocab_rows) + config["hidden_size"] * 0.02 ** 2 / 2
