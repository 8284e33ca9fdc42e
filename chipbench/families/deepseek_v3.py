"""The DeepSeek-V3 family as kanana-2 configures it (``"model_type":
"deepseek_v3"``, no query latent): multi-head latent attention in every
layer (one normed KV latent from which each head's keys and values are
made, one rotary key that all heads share, keys wider than values), a
dense SwiGLU feed-forward in the first ``first_k_dense_replace`` layers and
in the others a top-k sigmoid-routed expert feed-forward (a selection bias,
weights renormalised and scaled) beside a shared expert; RMSNorm, an untied
head. The program's model is ``models/hybrid.HybridLM``; the plain
reference is ``chipbench/reference_deepseek_v3.py``.

A configuration of this family states the chip's share of its deployment:
``n_routed_experts`` experts held here (ids ``held_experts``) of the
``n_routed_experts_published`` the router scores, and ``vocab_size`` rows of
the table and of the head. Program and reference are given the same share.

The six names of a family (``PERF.md`` section 3);
``attention_train_costs`` counts the two widths
(``chipbench/mla_attention_cost.py``). **No ``moe_train_costs``, so no
``moe_experts_roofline`` in this family's cell**: that share divides the
products' least time over the rows a balanced router sends here by the time
taken over the rows that came, and in a run's 23 steps of Adam the 16 held
experts see 10k to 132k rows a step for the balanced 86k (PERF.md sections
6 and 7, PR 42): it read 35.5% at 100,725 rows and 61.7% at 50,456, and
under 30k rows (3 runs of 18) it would pass 100%. It comes back when the
selection bias's balancing update holds the routing near balance.
"""

from __future__ import annotations

import math
from typing import Dict, List

from .. import harness, mla_attention_cost

#: the toy of ``--rehearse``: the leading dense layer and two routed ones,
#: 2 heads with keys 64 + 64 and values 64 wide (two widths the flash
#: kernels take, so that the interpreter runs them) over a latent of 32;
#: 2 of 8 experts held, three a token, two shared
REHEARSAL = {"num_hidden_layers": 3, "first_k_dense_replace": 1,
             "hidden_size": 128, "intermediate_size": 256,
             "num_attention_heads": 2, "num_key_value_heads": 2,
             "kv_lora_rank": 32, "qk_nope_head_dim": 64,
             "qk_rope_head_dim": 64, "qk_head_dim": 128, "head_dim": 64,
             "v_head_dim": 64, "n_routed_experts": 2,
             "n_routed_experts_published": 8, "held_experts": [0, 1],
             "num_experts_per_tok": 3, "moe_intermediate_size": 64,
             "n_shared_experts": 2, "vocab_size": 512,
             # the toy's own tau: its program-minus-reference scores differ
             # by 2e-4 to 3e-4 rms (CPU rehearsal), four times that
             "assumed": {"tie_tau": {"value": 1e-3}}}

#: The reference's verdict on the program, block by block on the residual
#: stream of the compilation that keeps it (``program_trace``, in
#: ``reference_forward``), as ``families/lfm2_moe``'s. Each limit lies
#: between two readings on the chip (PERF.md section 6, PR 42; 1 x 16,384
#: tokens, fresh weights and the weights a 30 s window leaves): the sound
#: bf16 program's, and those of the same program with every matrix rounded
#: to 4 bits of mantissa (e4m3's), which must fail.
#:
#: * a block's update (output minus input) against the reference's, rms over
#:   rms: 0.59-1.08% in every block; the control 5.6-7.7%. A program that
#:   leaves the shared rotary key unturned reads 39% in the dense block and
#:   3.0-3.4% in the routed ones, one that scales by 128 ** -0.5 13.8% and
#:   1.3-1.4%. The limit is 2.3 times the sound reading and 2.2 times under
#:   the control's.
#: * the share of a layer's (token, layer) pairs in which the program chose
#:   an expert farther than tau from the reference's boundary: 0-0.024% of
#:   16,384 at tau 0.002; the control 6.7-7.8% (the unturned key 0.4-1.1%).
#:
#: The share of pairs *within* tau of the boundary is printed and not
#: limited (27-33% at 0.002, 8-9% at 0.0005): 128 sigmoid scores of an
#: N(0, 0.02) router lie within 0.2 of 1/2, so the sixth and seventh are
#: close in many tokens whatever the program does.
UPDATE_LIMIT, OUTSIDE_LIMIT = 0.025, 0.01


def _layers(config: dict) -> int:
    return config["num_hidden_layers"]


def _routed_layers(config: dict) -> int:
    return _layers(config) - config["first_k_dense_replace"]


def _check(config: dict) -> None:
    """What of the family this file does not build is refused, not
    ignored."""
    c = config
    wrong = [key for key, want in (
        ("q_lora_rank", None), ("rope_scaling", None), ("n_group", 1),
        ("topk_group", 1), ("moe_layer_freq", 1), ("scoring_func", "sigmoid"),
        ("norm_topk_prob", True), ("rope_interleave", True))
        if c.get(key) != want]
    if wrong or c["qk_head_dim"] != c["qk_nope_head_dim"] \
            + c["qk_rope_head_dim"]:
        raise harness.BenchmarkError(
            f"deepseek_v3: {wrong or ['qk_head_dim']} of the configuration "
            f"is not what this family builds (no query latent, no rope "
            f"scaling, one expert group, sigmoid scores renormalised, "
            f"neighbouring rotary pairs)")


def build_model(config: dict, vocab_rows: int, mix: dict):
    """The program's model through the library's public constructor."""
    from horovod_tpu.models.hybrid import HybridLM

    if "mla_kv_rank" not in HybridLM.__dataclass_fields__:
        raise harness.BenchmarkError(
            "this checkout's models/hybrid.HybridLM has no latent-attention "
            "mixer: it cannot build a deepseek_v3 model")
    _check(config)
    c, dense = config, config["first_k_dense_replace"]
    return HybridLM(
        vocab_size=vocab_rows, layer_kinds=("latent_attention",) * _layers(c),
        ffn_kinds=("swiglu",) * dense + ("moe",) * _routed_layers(c),
        d_model=c["hidden_size"], ffn_width=c["intermediate_size"],
        attn_heads=c["num_attention_heads"],
        attn_kv_heads=c["num_key_value_heads"],
        attn_head_dim=c["qk_head_dim"],
        mla_kv_rank=c["kv_lora_rank"], mla_nope_dim=c["qk_nope_head_dim"],
        mla_rope_dim=c["qk_rope_head_dim"], mla_v_dim=c["v_head_dim"],
        mla_rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], remat=mix.get("remat", "none"),
        moe_experts=c["n_routed_experts_published"],
        moe_held=tuple(c["held_experts"]),
        moe_top_k=c["num_experts_per_tok"],
        moe_width=c["moe_intermediate_size"],
        moe_shared_width=c["n_shared_experts"] * c["moe_intermediate_size"],
        moe_scale=float(c["routed_scaling_factor"]), moe_norm_eps=1e-20,
        moe_scoring="sigmoid", tied_head=False)


def _applied(params, tokens, config: dict, keep_blocks: bool):
    """The program's forward pass (its bf16 path, no recomputation) with
    what its routed layers sow kept and, with ``keep_blocks``, each block's
    output: ``(logits, intermediates)``."""
    import jax

    from horovod_tpu.models.hybrid import HybridBlock

    rows = params["tok_emb"]["embedding"].shape[0]
    model = build_model(config, rows, {"remat": "none"})
    logits, state = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, mutable=["intermediates"],
        capture_intermediates=(lambda module, _: isinstance(
            module, HybridBlock)) if keep_blocks else False))(params, tokens)
    return logits, state["intermediates"]


def _routing(blocks) -> Dict[str, dict]:
    return {name: {key: layer["ffn"][key][0] for key in
                   ("chosen", "scores", "load")}
            for name, layer in blocks.items() if "ffn" in layer}


def program_routing(params, tokens, config: dict):
    """``(logits, {"block_<i>": {"chosen", "scores", "load"}})`` from the
    compilation that keeps the routing and no block's output. On the chip
    its logits are the job's ``program_forward``'s bit for bit (compiled for
    a v5e it has that program's fused computations, a copy scheduled
    otherwise in one branch aside, and adds the sown values' own: PERF.md
    section 6, PR 42), so this is the routing of the program the job
    compares."""
    logits, blocks = _applied(params, tokens, config, False)
    return logits, _routing(blocks)


def program_trace(params, tokens, config: dict):
    """``(logits, [each block's output], {"block_<i>": {"chosen", "scores",
    "load"}})`` from one compilation that keeps them all. It rounds the
    stream otherwise than the job's compilation does (XLA carries a bf16
    value at more than its precision inside a fusion, so what is rounded
    depends on what is handed out) and, with 27-33% of a layer's pairs
    within ``tau`` of the boundary, chooses other experts than the job's in
    3-7% of a layer's tokens: a compilation of the program with routing and
    stream of its own, which is what a block's check needs."""
    logits, blocks = _applied(params, tokens, config, True)
    outputs = [blocks[f"block_{i}"]["__call__"][0]
               for i in range(_layers(config))]
    return logits, outputs, _routing(blocks)


def kernel_plan(config: dict, seq: int) -> dict:
    """Which flash kernels a head of ``seq`` positions takes at the two
    widths, and ``flash_plan``'s scores computed over needed at their
    tiles: ``{"route", "forward", "backward"}``; ``{}`` where no tile
    divides ``seq``."""
    from horovod_tpu.ops import pallas_kernels as pk

    block_q, block_k = pk.flash_tiles(seq, seq)
    if block_q is None or block_k is None:
        return {}
    route = pk.flash_route(seq, seq, config["qk_head_dim"], 2,
                           dv=config["v_head_dim"])
    whole = (block_q, block_k)
    cut = whole if route["backward"] == "streaming" \
        else pk._pick_sub_tile(True, block_q, block_k)
    shares = [p["scores"] / p["needed"] for p in (
        pk.flash_plan(True, seq, seq, 0, 0, block_k, *sub)
        for sub in (whole, cut))]
    return {"route": route, "forward": shares[0], "backward": shares[1]}


def _say_routing(layer: dict, load) -> str:
    tied, followed, outside, score_rms = (
        float(layer[k]) for k in ("tied", "followed", "outside", "score_rms"))
    return (f"tied {100 * tied:.3f}% of pairs, program's choice taken "
            f"{100 * followed:.3f}%, program differed outside tau "
            f"{100 * outside:.4f}%; program-minus-reference score rms "
            f"{score_rms:.2e}; held experts' load max/mean "
            f"{load.max() / max(load.mean(), 1e-9):.3f}, rows here "
            f"{int(load.sum())}")


def reference_forward(params, tokens, config: dict):
    """Float32 logits ``[B, T, vocab_rows]`` of the plain reference's
    free-running pass, under its tie rule at the width ``assumed.tie_tau``
    of the configuration against the routing of the program the job
    compares (:func:`program_routing`; ``reference_deepseek_v3``'s
    docstring): the reference's own stream and scores, and of the program
    only which of the experts that the reference finds within ``tau`` of
    its boundary were taken. Nothing of the program's logits is in them.

    Before that, every block is held to the reference on the stream of a
    compilation that keeps it (:func:`program_trace`, with that
    compilation's own routing), and NaN logits, which no comparison passes,
    are returned where a block's update or the program's routing differs
    from the reference's by more than rounding (:data:`UPDATE_LIMIT`,
    :data:`OUTSIDE_LIMIT`). Says what it found, the rows routed to the
    experts held here and the flash kernels' route among it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import reference_deepseek_v3 as reference

    tau = float(config["assumed"]["tie_tau"]["value"])
    held = list(config["held_experts"])
    job_logits, job_routing = program_routing(params, tokens, config)
    traced_logits, outputs, routing = program_trace(params, tokens, config)
    # one fused pass: op by op it would hold two more copies of the logits
    apart = float(jax.jit(lambda a, b: jnp.sqrt(jnp.mean(
        (a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2) / jnp.mean(
        b.astype(jnp.float32) ** 2)))(traced_logits, job_logits))
    del job_logits, traced_logits
    harness.say(f"  the program compiled with its routing kept (the job's "
                f"program) and with its blocks' outputs kept too: "
                f"{apart:.5f} of the logits' rms apart")
    plan = kernel_plan(config, tokens.shape[1])
    if plan:
        harness.say(f"  latent attention, keys {config['qk_head_dim']} / "
                    f"values {config['v_head_dim']}: {plan['route']}; "
                    f"flash_plan computes {plan['forward']:.3f} (forward) "
                    f"and {plan['backward']:.3f} (backward) times the "
                    f"needed scores")
    _, stats = reference.forward_from_program(params, tokens, config,
                                              outputs, routing, tau)
    del outputs
    harness.say(f"  block by block on the traced program's stream, tau "
                f"{tau}:")
    sound = True
    for layer in stats:
        update = float(layer["update_error"])
        line = f"  {layer['layer']}: update error {update:.5f}"
        sound = sound and update <= UPDATE_LIMIT
        if "tied" in layer:
            load = np.asarray(routing[layer["layer"]]["load"])[held]
            line += "; " + _say_routing(layer, load)
            sound = sound and float(layer["outside"]) <= OUTSIDE_LIMIT
        harness.say(line)
    if not sound:
        harness.say(f"  deepseek_v3 reference: a block's update is over "
                    f"{UPDATE_LIMIT} of the reference's, or the program's "
                    f"routing differs outside tau in over "
                    f"{100 * OUTSIDE_LIMIT}% of a layer's pairs: no match")
        return jnp.full(tokens.shape + params["lm_head"]["kernel"].shape[-1:],
                        jnp.nan, jnp.float32)
    logits, stats = reference.forward_following(params, tokens, config,
                                                job_routing, tau)
    harness.say(f"  free-running on the reference's own stream, the job's "
                f"program's routing, tau {tau}:")
    for layer in stats:
        load = np.asarray(job_routing[layer["layer"]]["load"])[held]
        harness.say(f"  {layer['layer']}: " + _say_routing(layer, load))
    return logits


def _experts_here(config: dict) -> float:
    """Of a token's experts, those held here under a balanced router."""
    return config["num_experts_per_tok"] * len(config["held_experts"]) \
        / config["n_routed_experts_published"]


def layer_parameters(config: dict) -> Dict[str, int]:
    """The matrix elements of each part of a layer (norm weights left
    out): what ``train_flops_per_token`` multiplies and the tests count."""
    c, d, heads = config, config["hidden_size"], config["num_attention_heads"]
    f = c["moe_intermediate_size"]
    return {"q": d * heads * c["qk_head_dim"],
            "kv_a": d * (c["kv_lora_rank"] + c["qk_rope_head_dim"]),
            "kv_b": c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                                 + c["v_head_dim"]),
            "o": heads * c["v_head_dim"] * d,
            "dense": 3 * d * c["intermediate_size"],
            "router": d * c["n_routed_experts_published"],
            "shared": 3 * d * c["n_shared_experts"] * f,
            "expert": 3 * d * f}


def train_flops_per_token(config: dict, vocab_rows: int, seq: int) -> float:
    """Forward plus backward operations one trained token requires **on
    this chip**.

    6 x the matrix elements a token touches (:func:`layer_parameters`):
    every layer's ``W_q``, ``W_kva``, ``W_kvb`` and ``W_o``; the dense
    feed-forward's 3 d F; in a routed layer the router's d E, the shared
    expert's 3 d S and, of the token's ``top_k`` experts of 3 d f, the
    share held here (``top_k held / E`` experts on average); the untied
    head's d V over the rows held (the table is a lookup). Every layer
    adds 3 s H (key width + value width) for QK^T and PV and their
    gradients (causal: half the sequence on average). Recomputation, the
    shared key's broadcast, scores a tile computes above the diagonal, the
    worst-case dispatch buffer, and rows the router sends here beyond that
    average are not required work."""
    c, p = config, layer_parameters(config)
    mixer = p["q"] + p["kv_a"] + p["kv_b"] + p["o"]
    routed = p["router"] + p["shared"] + _experts_here(c) * p["expert"]
    scores = 3.0 * seq * c["num_attention_heads"] * (c["qk_head_dim"]
                                                     + c["v_head_dim"])
    return 6.0 * (_layers(c) * mixer
                  + c["first_k_dense_replace"] * p["dense"]
                  + _routed_layers(c) * routed
                  + c["hidden_size"] * vocab_rows) + _layers(c) * scores


def attention_train_costs(config: dict, per_chip_batch: int,
                          seq: int) -> List[Dict[str, float]]:
    """One ``{"flops", "bytes"}`` for each layer (all attend), at the true
    widths of keys and values whatever a kernel pads."""
    c = config
    return [mla_attention_cost.mla_attention_train_cost(
        per_chip_batch, c["num_attention_heads"], seq, c["qk_head_dim"],
        c["v_head_dim"])] * _layers(c)


def expected_first_loss(config: dict, vocab_rows: int) -> float:
    """ln(rows) + sigma^2/2: the final RMSNorm (weight 1) hands the head a
    vector of mean square 1, so a logit over an N(0, 0.02^2) head has
    variance d 0.02^2."""
    return math.log(vocab_rows) + config["hidden_size"] * 0.02 ** 2 / 2
