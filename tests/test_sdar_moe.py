"""The block-diffusion mask of ``ops/pallas_kernels.flash_attention`` against
the dense mask written out, and ``models/hybrid.HybridLM`` as the SDAR family
builds it (a model trained by denoising blocks: ``2 T`` rows ``[noised |
clean]`` under that mask, both halves at positions ``0..T-1``, a
softmax-routed expert feed-forward without a shared expert in every block,
the head on the noised half) against its plain reference,
``chipbench/reference_sdar_moe.py``.

Small size, seeded weights with the norm weights randomised and the matrices
scaled so that each part of a block is as large as what it stands beside.
The model holds 2 of 8 experts (ids 1 and 6: not a prefix), three a row; 4
heads over 2 KV heads of 64; blocks of 4 positions.
"""

import contextlib
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from chipbench import block_diffusion_attention_cost, harness
from chipbench import reference_sdar_moe as reference
from chipbench import sdar_moe_controls as controls
from chipbench.families import sdar_moe as family
from chipbench.objectives import block_denoise
from horovod_tpu import spmd
from horovod_tpu.models import hybrid
from horovod_tpu.models.transformer import denoise_loss, lm_loss
from horovod_tpu.ops import moe, rope
from horovod_tpu.ops import pallas_kernels as pk
from chipbench.sdar_moe_controls import four_bits, leak, rows_for_positions
from tests.test_laguna import relative, worst_leaf

#: the configuration keys the family, the objective and the reference read
CONFIG = {"num_hidden_layers": 2, "hidden_size": 128,
          "intermediate_size": 320, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 64, "rope_theta": 1000000,
          "rope_scaling": None, "rms_norm_eps": 1e-6, "num_experts": 2,
          "num_experts_published": 8, "held_experts": [1, 6],
          "num_experts_per_tok": 3, "moe_intermediate_size": 64,
          "norm_topk_prob": True, "decoder_sparse_step": 1,
          "mlp_only_layers": [], "use_sliding_window": False,
          "tie_word_embeddings": False, "attention_bias": False,
          "hidden_act": "silu", "vocab_size": 512,
          "assumed": {
              # four times the rms of the bf16 program's scores minus the
              # reference's at this size (1.0e-3 to 1.5e-3 around 1/8)
              "tie_tau": {"value": 6e-3},
              "block_length": {"value": 4},
              # every block's auxiliary balancing loss rides the backward
              # pass; q_norm starts at 1 here (the weights are shaken and
              # scaled below: ``randomised_params``)
              "auxiliary_loss": {"coefficient": 0.1},
              "q_norm_init": {"value": 1.0},
              "noise_schedule": {"low": 0.45, "high": 0.95},
              "mask_token_id": {"value": 511}}}
ROWS = 512

#: float32 program against float32 reference: both round at 2^-24 and
#: differ in the order of their sums (grouped rows against masked experts,
#: one softmax over the kept keys against a block of queries at a time).
#: Measured 1e-6 in the logits and 2e-5 in the worst gradient leaf; bf16
#: operands read 1e-2 and 0.1.
F32_TOL = 2e-4


# ------------------------------------------------- the mask, op by op
def dense_mask(t, b):
    """``[2 t, 2 t]`` bool, the three clauses row by row, in Python."""
    m = np.zeros((2 * t, 2 * t), bool)
    for i in range(2 * t):
        for j in range(2 * t):
            clean_i, clean_j = i >= t, j >= t
            block_i, block_j = i % t // b, j % t // b
            m[i, j] = (clean_j and block_j < block_i) \
                or (clean_j and block_j == block_i and clean_i) \
                or (not clean_j and not clean_i and block_j == block_i)
    return m


def dense_attention(q, k, v, mask):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def qkv(t, d=64, heads=2, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed + t), 4)
    q, k, v, do = (jax.random.normal(key, (1, 2 * t, heads, d), dtype)
                   for key in keys)
    return q, k, v, do


def with_grads(attn, q, k, v, do):
    out, vjp = jax.vjp(attn, q, k, v)
    return (out,) + vjp(do)


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("HVD_PALLAS", "interpret")


#: T, B: one grid tile; a B that the tiles' edges cut (2 T = 192 takes
#: tiles of 64, T = 96 lies inside the second, blocks of 12 cross every
#: edge); two q tiles of 512 a half and one key tile a half; a straddling
#: key tile (2 T = 384: tiles of 128)
MASK_CASES = [(64, 4), (96, 12), (192, 4), (512, 4), (512, 32), (256, 1)]


@pytest.mark.parametrize("t,b", MASK_CASES)
def test_the_kernels_are_the_dense_mask_values_and_gradients(t, b,
                                                             interpreted):
    q, k, v, do = qkv(t)
    assert pk.kernel_path("flash_attention", q, k, v) == "pallas"
    got = with_grads(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=True, block_diffusion=b), q, k, v, do)
    mask = jnp.asarray(dense_mask(t, b))
    want = with_grads(lambda q, k, v: dense_attention(q, k, v, mask),
                      q, k, v, do)
    # (float32 in both: 2e-6 measured, the order of the sums)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        assert relative(g, w) <= 2e-5, name


@pytest.mark.parametrize("t,b", MASK_CASES[:3])
def test_the_jnp_path_is_the_dense_mask(t, b):
    """Kernels off (the CPU's default): ``reference_attention``."""
    q, k, v, do = qkv(t)
    assert pk.kernel_path("flash_attention", q, k, v) == "reference"
    got = with_grads(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=True, block_diffusion=b), q, k, v, do)
    mask = jnp.asarray(dense_mask(t, b))
    want = with_grads(lambda q, k, v: dense_attention(q, k, v, mask),
                      q, k, v, do)
    for g, w in zip(got, want):
        assert relative(g, w) <= 1e-6
    np.testing.assert_array_equal(
        np.asarray(reference.seen(jnp.arange(2 * t), jnp.arange(2 * t), t,
                                  b)), dense_mask(t, b))


@pytest.mark.parametrize("t,b", MASK_CASES + [(8192, 4)])
def test_flash_plan_counts_the_masks_tiles(t, b):
    rows, blocks = 2 * t, (b, t)
    block_q, block_k = pk.flash_tiles(rows, rows)
    sub = pk._pick_sub_tile(True, block_q, block_k)
    fwd = pk.flash_plan(True, rows, rows, 0, 0, block_k, block_q, block_k,
                        blocks=blocks)
    bwd = pk.flash_plan(True, rows, rows, 0, 0, block_k, *sub, blocks=blocks)
    for plan, (sq, sk) in ((fwd, (block_q, block_k)), (bwd, sub)):
        assert plan["needed"] == t * t + t * b
        assert plan["computed"] + plan["skipped"] == (rows // sq) * (
            rows // sk)
        assert plan["scores"] == plan["computed"] * sq * sk >= plan["needed"]
        # every tile pair that holds a kept score is computed
        live = pk._blockdiff_live(rows, sq, sk, blocks)
        assert plan["computed"] >= live.sum()
        if t <= 512:
            mask = dense_mask(t, b)
            held = mask.reshape(rows // sq, sq, rows // sk, sk).any((1, 3))
            np.testing.assert_array_equal(live, held)
            assert int(mask.sum()) == plan["needed"]
    # the clean-to-noised quadrant: no key-loop trip forward, no grid cell
    # backward, wherever a tile lies wholly inside it
    spans = pk._blockdiff_spans(rows, block_q, block_k, blocks)
    cells = pk._blockdiff_cells(rows, block_q, block_k, *sub, blocks)
    for iq, (a0, a1, b0, b1) in enumerate(spans):
        if iq * block_q >= t:
            walked = list(range(a0, a1)) + list(range(b0, b1))
            assert all((jk + 1) * block_k > t for jk in walked), iq
    for jk, iq in cells[:, :2]:
        assert not (iq * block_q >= t and (jk + 1) * block_k <= t), (jk, iq)
    # each key tile's cells are consecutive, opened and closed once
    assert list(cells[:, 0]) == sorted(cells[:, 0])
    assert cells[:, 2].sum() == cells[:, 3].sum() == rows // block_k
    if (t, b) == (8192, 4):
        # the cell's shape: 160 key-loop trips of 512 x 1024 forward, 160
        # grid cells and 288 strips of 512 x 512 backward, of 512 and 1,024
        assert (fwd["computed"], bwd["computed"], len(cells)) == (160, 288,
                                                                  160)
        assert fwd["scores"] / fwd["needed"] == pytest.approx(1.2494, abs=1e-4)
        assert bwd["scores"] / bwd["needed"] == pytest.approx(1.1245, abs=1e-4)
        assert fwd["scores"] / fwd["needed"] <= 1.35
        # a causal call over the 2 T rows that skipped by the triangle
        # alone would compute twice that
        causal = pk.flash_plan(True, rows, rows, 0, 0, block_k, block_q,
                               block_k)
        assert causal["scores"] / fwd["needed"] > 2.0


def test_the_clean_half_attends_block_causally_by_itself(interpreted):
    """The clean quadrant alone: the clean rows' output is dense attention
    over the clean rows under ``b_j <= b_i``, whatever the noised rows hold;
    at B = 1 that is the causal call."""
    t, b = 128, 4
    q, k, v, _ = qkv(t)
    out = pk.flash_attention(q, k, v, causal=True, block_diffusion=b)
    block = np.arange(t) // b
    want = dense_attention(q[:, t:], k[:, t:], v[:, t:],
                           jnp.asarray(block[None, :] <= block[:, None]))
    assert relative(out[:, t:], want) <= 2e-5
    other = qkv(t, seed=9)
    mixed = [jnp.concatenate([o[:, :t], x[:, t:]], axis=1)
             for o, x in zip(other, (q, k, v))]
    again = pk.flash_attention(*mixed, causal=True, block_diffusion=b)
    np.testing.assert_array_equal(again[:, t:], out[:, t:])
    single = pk.flash_attention(q, k, v, causal=True, block_diffusion=1)
    causal = pk.flash_attention(q[:, t:], k[:, t:], v[:, t:], causal=True)
    assert relative(single[:, t:], causal) <= 2e-5


# ------------------------------------------- the model against its reference
def model(dtype=jnp.float32, remat="none", config=CONFIG, **changes):
    return family.build_model(config, ROWS, {"remat": remat}).clone(
        dtype=dtype, **changes)


def batch(seq, sequences=2, seed=0):
    """``(clean, noised, weights)`` as the objective makes them."""
    return block_denoise.make_batches(seed, 1, sequences, seq, CONFIG,
                                      None)[0]


def inputs(seq, sequences=2, seed=0):
    return block_denoise.model_inputs(batch(seq, sequences, seed), sequences)


@functools.lru_cache(maxsize=None)
def randomised_params(seed=1):
    params = model().init(jax.random.PRNGKey(seed), *inputs(32))["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))

    def shake(path, leaf, key):
        name = jax.tree_util.keystr(path)
        # at width 128 an N(0, 0.02) matrix shrinks what it maps to a
        # quarter: scaled so that the attention's scores spread, a mixer's
        # update and the routed experts' are as large as what they stand
        # beside, and the router is not flat
        if any(k in name for k in ("['q']", "['k']", "['v']", "['o']",
                                   "router", "w_in", "w_out")):
            return 4.0 * leaf
        if leaf.ndim >= 2 or "expert_bias" in name:
            return leaf
        return leaf + 0.5 * jax.random.normal(key, leaf.shape, leaf.dtype)

    return jax.tree_util.tree_unflatten(treedef, [
        shake(path, leaf, key) for (path, leaf), key in zip(leaves, keys)])


def _logits_loss_grads(forward, loss_of, seq):
    params, (clean, noised, weights) = randomised_params(), batch(seq)

    def fn(p):
        logits = forward(p, noised, clean)
        return loss_of(logits, clean, weights), logits

    (loss, logits), grads = jax.jit(
        jax.value_and_grad(fn, has_aux=True))(params)
    return logits, loss, grads


def program_side(seq, dtype):
    m = model(dtype)
    return _logits_loss_grads(lambda p, *t: m.apply({"params": p}, *t),
                              denoise_loss, seq)


@functools.lru_cache(maxsize=None)
def reference_side(seq):
    return _logits_loss_grads(
        lambda p, *t: reference.forward(p, *t, CONFIG), reference.loss, seq)


def test_logits_loss_and_every_gradient_match_the_reference(seq=64):
    logits, loss, grads = program_side(seq, jnp.float32)
    ref_logits, ref_loss, ref_grads = reference_side(seq)
    assert logits.dtype == jnp.float32 and logits.shape == (2, seq, ROWS)
    assert relative(logits, ref_logits) <= F32_TOL
    assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss)
    # the program's step is handed the gradient of the loss plus 0.1 times
    # every block's auxiliary balancing loss, and reports the loss alone:
    # reference.loss_and_grads
    clean, noised, weights = batch(seq)
    handed_loss, handed = jax.jit(functools.partial(
        reference.loss_and_grads, config=CONFIG))(
            randomised_params(), noised, clean, weights)
    assert float(handed_loss) == float(ref_loss)
    path, error = worst_leaf(grads, handed)
    assert error <= F32_TOL, (jax.tree_util.keystr(path), error)
    # which is another gradient than the loss's own, in the routers most
    assert relative(handed["block_1"]["ffn"]["router"],
                    ref_grads["block_1"]["ffn"]["router"]) > 100 * F32_TOL
    # every kind of parameter took a gradient, but the selection bias, which
    # steers a choice and has none, in the program and in the reference
    for path, leaf in jax.tree_util.tree_leaves_with_path(handed):
        moved = float(jnp.max(jnp.abs(leaf))) > 0
        assert moved != ("expert_bias" in jax.tree_util.keystr(path)), path


def test_the_loss_is_the_weighted_cross_entropy():
    """``denoise_loss`` against the sum written out; with weights of one it
    is ``lm_loss``; an unmasked position has no say."""
    clean, _, weights = batch(64)
    logits = jax.random.normal(jax.random.PRNGKey(3), (2, 64, ROWS))
    logp = np.asarray(jax.nn.log_softmax(logits), np.float64)
    picked = np.take_along_axis(logp, np.asarray(clean)[..., None], -1)[..., 0]
    want = -(np.asarray(weights, np.float64) * picked).sum() / weights.size
    assert float(denoise_loss(logits, clean, weights)) == pytest.approx(
        want, rel=1e-6)
    assert float(reference.loss(logits, clean, weights)) == pytest.approx(
        want, rel=1e-6)
    assert float(denoise_loss(logits, clean, jnp.ones_like(weights))) \
        == pytest.approx(float(lm_loss(logits, clean)), rel=1e-6)
    moved = logits.at[:, :, 0].add(jnp.where(weights == 0, 5.0, 0.0))
    assert float(denoise_loss(moved, clean, weights)) == pytest.approx(
        want, rel=1e-6)


def test_the_objectives_batch_is_the_assumed_schedule():
    clean, noised, weights = (np.asarray(a) for a in batch(4096, 4, seed=2))
    masked = noised != clean
    assert clean.max() <= 510 and clean.min() == 0
    assert set(np.unique(noised[masked])) == {511}
    # one rate a block of four: a masked position's weight is 1 / t with t
    # on [0.45, 0.95], the same for its block; the weights are 1 a token
    rate = 1 / weights[masked]
    assert 0.45 <= rate.min() and rate.max() <= 0.95
    blocks = weights.reshape(4, -1, 4)
    for row in blocks.reshape(-1, 4)[:512]:
        assert len(set(row[row > 0])) <= 1
    assert weights[~masked].max() == 0
    assert weights.mean() == pytest.approx(1.0, abs=0.03)
    assert masked.mean() == pytest.approx(0.70, abs=0.02)
    assert block_denoise.first_loss(9.9) == 9.9
    shapes = block_denoise.abstract_batch(4, 4096, None)
    assert [(s.shape, s.dtype) for s in shapes] == [
        (a.shape, a.dtype) for a in (clean, noised, weights)]


def stream(m, params, noised, clean):
    """``(logits, [each block's output])`` through Flax's capture."""
    logits, state = m.apply(
        {"params": params}, noised, clean, mutable=["intermediates"],
        capture_intermediates=lambda module, _: isinstance(
            module, hybrid.HybridBlock))
    return logits, [state["intermediates"][f"block_{i}"]["__call__"][0]
                    for i in range(CONFIG["num_hidden_layers"])]


def test_the_clean_half_is_a_plain_model_on_the_clean_ids():
    """Two invariants that tie the model to code the benchmark already
    guards: nothing of the noised ids reaches the clean half's stream, and
    at B = 1 that stream is the causal model's on the clean ids alone (its
    logits the causal path's)."""
    params, (noised, clean) = randomised_params(), inputs(64)
    _, outs = stream(model(), params, noised, clean)
    _, other = stream(model(), params, inputs(64, seed=5)[0], clean)
    for a, b in zip(outs, other):
        np.testing.assert_array_equal(a[:, 64:], b[:, 64:])
        assert relative(a[:, :64], b[:, :64]) > 0.1
    _, single = stream(model(denoise_blocks=1), params, noised, clean)
    plain = model(denoise_blocks=0)
    logits, state = plain.apply(
        {"params": params}, clean, mutable=["intermediates"],
        capture_intermediates=lambda module, _: isinstance(
            module, hybrid.HybridBlock))
    for i, out in enumerate(single):
        want = state["intermediates"][f"block_{i}"]["__call__"][0]
        assert relative(out[:, 64:], want) <= 1e-5, i
    with jax.default_matmul_precision("highest"):
        head = reference.head(params, single[-1][:, 64:], 1e-6)
        assert relative(head, logits) <= 1e-5


def test_a_noised_row_sees_no_clean_token_of_its_own_or_a_later_block():
    """Change the clean token at position 21 (block 5 of blocks of 4): the
    noised rows of blocks 0 to 5 keep their logits to the bit, those of
    block 6 on see the change."""
    params, (noised, clean) = randomised_params(), inputs(64)
    m = model()
    before = m.apply({"params": params}, noised, clean)
    after = m.apply({"params": params}, noised,
                    clean.at[:, 21].set((clean[:, 21] + 7) % 511))
    np.testing.assert_array_equal(before[:, :24], after[:, :24])
    assert relative(after[:, 24:], before[:, 24:]) > 1e-3
    # and the noised token at 21 reaches its own block's noised rows only
    moved = m.apply({"params": params},
                    noised.at[:, 21].set((noised[:, 21] + 7) % 511), clean)
    np.testing.assert_array_equal(before[:, :20], moved[:, :20])
    np.testing.assert_array_equal(before[:, 24:], moved[:, 24:])
    assert relative(moved[:, 20:24], before[:, 20:24]) > 1e-3


def test_the_rotary_angles_are_those_of_a_rows_position():
    """Both halves turn by ``0..T-1``: the mixer's q and k are those of
    ``apply_rope`` at ``i mod T``, and the reference's tables are the same
    angles."""
    t = 32
    cos, sin = reference.rotary_rows(1e6, 64, np.arange(2 * t) % t)
    np.testing.assert_array_equal(cos[:t], cos[t:])
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2 * t, 2, 64))
    turned = rope.apply_rope(x, 1e6, jnp.arange(2 * t) % t)
    assert relative(turned, reference._rotate(x, cos, sin)) <= 1e-6
    assert relative(rope.apply_rope(x, 1e6), turned) > 0.1


def test_the_parameter_tree_and_the_scopes():
    """No parameter but the plain model's; the three scopes are in the
    compiled program, the loss under ``loss``; the head and the final norm
    run on T rows (no ``[2 T, rows]`` array anywhere in the step)."""
    params = randomised_params()
    plain = model(denoise_blocks=0).init(jax.random.PRNGKey(0),
                                         inputs(32)[1])["params"]
    assert jax.tree_util.tree_structure(params) \
        == jax.tree_util.tree_structure(plain)
    assert set(params["block_0"]["ffn"]) == {"router", "expert_bias", "w_in",
                                             "w_out"}
    m, (clean, noised, weights) = model(remat="full"), batch(64)

    def step(p):
        return denoise_loss(m.apply({"params": p}, noised, clean), clean,
                            weights)

    lowered = jax.jit(jax.value_and_grad(step)).lower(params)
    text = lowered.as_text(debug_info=True)
    for scope in ("mixer/block_diffusion", "denoise_io", "loss"):
        assert scope in text, scope
    assert "tensor<2x64x512xf32>" in text
    assert "x128x512xf32>" not in text and "x128x512xbf16>" not in text


def test_what_the_model_and_the_kernels_do_not_build_is_refused():
    params, (noised, clean) = randomised_params(), inputs(32)
    with pytest.raises(ValueError, match="takes .noised, clean."):
        model().apply({"params": params}, noised)
    with pytest.raises(ValueError, match="takes .noised, clean."):
        model(denoise_blocks=0).apply({"params": params}, noised, clean)
    with pytest.raises(ValueError, match="only attention layers"):
        model(layer_kinds=("attention", "short_conv")).init(
            jax.random.PRNGKey(0), noised, clean)
    with pytest.raises(ValueError, match="block_diffusion with a window"):
        model(attn_kinds={"banded": {"window": 8}},
              layer_kinds=("attention", "banded")).init(
            jax.random.PRNGKey(0), noised, clean)
    with pytest.raises(harness.BenchmarkError, match="sdar_moe"):
        family.build_model({**CONFIG, "norm_topk_prob": False}, ROWS, {})


def test_a_recomputed_model_agrees_on_the_kernel_path(interpreted):
    """``remat="full"`` through the kernels (the interpreter's) against the
    reference's loss and what it hands the optimizer."""
    params, (clean, noised, weights) = randomised_params(), batch(64)

    def grads(m):
        return jax.jit(jax.value_and_grad(lambda p: denoise_loss(
            m.apply({"params": p}, noised, clean), clean, weights)))(params)

    loss, got = grads(model(remat="full"))
    ref_loss, want = jax.jit(functools.partial(
        reference.loss_and_grads, config=CONFIG))(params, noised, clean,
                                                  weights)
    assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss)
    assert worst_leaf(got, want)[1] <= F32_TOL


def test_three_train_steps_on_the_mesh_reproduce_the_reference_losses():
    """``spmd.make_train_step`` + the objective's loss + the job's AdamW as
    ``chipbench/jobs/train_lm.build`` calls them, batch 8 over the 8-device
    mesh, against the reference's own loss and gradients under the same
    optimizer. (A chip's auxiliary balancing loss is over the rows it
    holds: the reference's is taken a sequence at a time here, and the
    eight averaged as the mesh averages its chips' gradients.)"""
    hvd.init()
    mesh = hvd.mesh()
    m = model()
    params, data = randomised_params(), batch(64, 8, seed=7)
    clean, noised, weights = data
    tx = optax.adamw(3e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16)
    step = spmd.make_train_step(block_denoise.loss(m), tx, mesh=mesh,
                                donate=False)
    p, o = spmd.replicate(params, mesh), spmd.replicate(tx.init(params), mesh)
    sharded = spmd.shard_batch(data, mesh)
    rp, ro = params, tx.init(params)
    one = jax.jit(functools.partial(reference.loss_and_grads, config=CONFIG))

    def plain(p, noised, clean, weights):
        each = [one(p, noised[i:i + 1], clean[i:i + 1], weights[i:i + 1])
                for i in range(noised.shape[0])]
        return jax.tree_util.tree_map(lambda *l: sum(l) / len(l), *each)

    for i in range(3):
        p, o, loss = step(p, o, sharded)
        ref_loss, grads = plain(rp, noised, clean, weights)
        updates, ro = tx.update(grads, ro, rp)
        rp = optax.apply_updates(rp, updates)
        # the mesh's loss is the mean of eight shards' means, each over its
        # own sequence's weights: the same sum
        assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss), i
    assert float(loss) < float(plain(params, noised, clean, weights)[0])
    assert worst_leaf(jax.tree_util.tree_map(np.asarray, p), rp)[1] <= F32_TOL


# ----------------------------------------------------- the eight shares
def layer_params(seed=3, d=32, f=24, experts=128):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    normal = jax.random.normal
    return {"router": 0.5 * normal(keys[0], (d, experts)),
            "expert_bias": jnp.zeros((experts,)),
            "w_in": 0.3 * normal(keys[1], (experts, d, 2 * f)),
            "w_out": 0.3 * normal(keys[2], (experts, f, d))}


def share_of(params, held):
    index = jnp.asarray(held)
    return {**params, "w_in": params["w_in"][index],
            "w_out": params["w_out"][index]}


def layer(params, h, held):
    """``RoutedFeedForward`` as the family builds it, holding ``held``."""
    module = hybrid.RoutedFeedForward(
        128, tuple(held), 8, 24, jnp.float32, norm_eps=0.0,
        scoring="softmax")
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, h: module.apply({"params": p}, h))(
            share_of(params, held), h)


def reference_layer(params, h, held):
    with jax.default_matmul_precision("highest"):
        return reference._routed(share_of(params, held), h, tuple(held), 8,
                                 None, 0.0)[0]


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Each of 8 chips holds sixteen of the 128 experts (the deployment's
    share): their routed parts are what the reference gives holding every
    expert; there is no shared expert to count once."""
    params = layer_params()
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 48, 32))
    shares = [tuple(range(16 * i, 16 * i + 16)) for i in range(8)]
    whole = reference_layer(params, h, range(128))
    parts = [layer(params, h, held) for held in shares]
    # (8 float32 differences summed: 1e-6 measured)
    assert relative(sum(parts), whole) <= 1e-5
    assert relative(sum(reference_layer(params, h, held)
                        for held in shares[:3]) + sum(parts[3:]),
                    whole) <= 1e-5
    # a row's eight weights sum to 1 over all the shares
    weights = moe.route(h[0] @ params["router"], params["expert_bias"], 8,
                        1.0, 0.0, "softmax")[1]
    np.testing.assert_allclose(jnp.sum(weights, -1), 1.0, rtol=1e-6)
    # one share alone is not the layer, nor are seven
    assert relative(parts[0], whole) > 0.1
    assert relative(sum(parts[:7]), whole) > 0.05


# ------------------------------------------------------------- balancing
def aux_loss_of(scores, chosen):
    """``E sum_e f_e P_e``, written out."""
    n, e = scores.shape
    share = np.bincount(np.asarray(chosen).reshape(-1), minlength=e) / n
    return e * jnp.sum(share * jnp.mean(scores, axis=0))


def test_the_attached_gradient_is_the_auxiliary_losss():
    """``ops/moe.balancing``: the scores as they are; their cotangent as it
    comes plus the coefficient times the gradient of ``E sum_e f_e P_e``;
    the loss is 'top_k' where the choice is even and more where it is
    not."""
    scores = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0), (6, 4)))
    _, chosen = jax.lax.top_k(scores, 2)
    out, pull = jax.vjp(lambda s: moe.balancing(s, chosen, 0.25), scores)
    np.testing.assert_array_equal(out, scores)
    seed = jax.random.normal(jax.random.PRNGKey(1), scores.shape)
    want = seed + 0.25 * jax.grad(aux_loss_of)(scores, chosen)
    np.testing.assert_allclose(pull(seed)[0], want, rtol=1e-6)
    even = jnp.asarray([[0, 1], [2, 3], [0, 2], [1, 3]])
    flat = jnp.full((4, 4), 0.25)
    assert abs(float(aux_loss_of(flat, even)) - 2.0) < 1e-6
    narrow = jnp.asarray([[0, 1]] * 4)
    leaning = jnp.asarray([[0.4, 0.4, 0.1, 0.1]] * 4)
    assert float(aux_loss_of(leaning, narrow)) > 3.0


def test_route_hands_out_the_same_choice_and_weights_with_the_loss_attached():
    logits = jax.random.normal(jax.random.PRNGKey(2), (16, 8))
    plain = moe.route(logits, jnp.zeros(8), 3, scoring="softmax")
    attached = moe.route(logits, jnp.zeros(8), 3, scoring="softmax",
                         aux_loss=0.1)
    for got, want in zip(attached, plain):
        np.testing.assert_array_equal(got, want)


def test_without_the_auxiliary_loss_the_routed_layer_is_the_program_it_was():
    """``aux_loss`` 0 adds no operation: the other cells' programs."""
    args = (jnp.ones((8, 16)), jnp.ones((16, 4)), jnp.zeros(4),
            jnp.ones((2, 16, 8)), jnp.ones((2, 4, 16)))

    def text(**kw):
        return str(jax.make_jaxpr(jax.grad(lambda router: jnp.sum(
            moe.routed_ffn(args[0], router, *args[2:], held=(0, 1),
                           top_k=2, **kw)[0])))(args[1]))

    assert text() == text(aux_loss=0.0) != text(aux_loss=0.1)


def skewed(params, by=2.0):
    """Every router's first four columns raised: each row's scores lean to
    experts 0-3."""
    def lean(path, leaf):
        if "router" not in jax.tree_util.keystr(path):
            return leaf
        return leaf.at[:, :4].add(by * jnp.mean(jnp.abs(leaf)))
    return jax.tree_util.tree_map_with_path(lean, params)


def held_load(m, params, ins):
    _, state = m.apply({"params": params}, *ins, mutable=["intermediates"])
    return [np.asarray(layer["ffn"]["load"][0])
            for _, layer in sorted(state["intermediates"].items())]


@pytest.mark.parametrize("coefficient", [0.0, 0.1])
def test_the_auxiliary_loss_evens_a_skewed_routers_load(coefficient):
    """Forty of the job's AdamW steps on routers that lean to four of their
    eight experts: with the loss attached the busiest expert's load comes
    down; without it the load stays as skewed as it was, or worse."""
    m = model(moe_aux_loss=coefficient)
    params, data = skewed(randomised_params()), batch(64, 2, seed=3)
    ins = block_denoise.model_inputs(data, 2)

    def worst(p):
        return max(float(load.max() / load.mean())
                   for load in held_load(m, p, ins))

    before = worst(params)
    assert before > 1.6
    tx = optax.adamw(3e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16)
    loss_fn = block_denoise.loss(m)

    @jax.jit
    def step(p, o):
        grads = jax.grad(loss_fn)(p, data)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o

    opt = tx.init(params)
    for _ in range(40):
        params, opt = step(params, opt)
    after = worst(params)
    print(f"coefficient {coefficient}: busiest over mean {before:.3f} -> "
          f"{after:.3f}")
    # read 2.333 -> 1.094 with the loss and -> 1.917 without
    assert after < 1.25 if coefficient else after > 1.6


def test_the_family_starts_q_norm_where_the_configuration_says():
    config = {**CONFIG, "assumed": {**CONFIG["assumed"],
                                    "q_norm_init": {"value": 6.0}}}
    params = model(config=config).init(jax.random.PRNGKey(0),
                                       *inputs(32))["params"]
    for i in range(config["num_hidden_layers"]):
        mixer = params[f"block_{i}"]["mixer"]
        np.testing.assert_array_equal(mixer["q_norm"], np.full(64, 6.0))
        np.testing.assert_array_equal(mixer["k_norm"], np.ones(64))


# -------------------------------------------------------------- controls
def test_control_bf16_operands_fail_the_float32_tolerance():
    """The same comparison one precision lower: over F32_TOL by far."""
    logits, _, grads = program_side(64, jnp.bfloat16)
    ref_logits, _, ref_grads = reference_side(64)
    assert relative(logits, ref_logits) > 10 * F32_TOL
    assert worst_leaf(grads, ref_grads)[1] > 10 * F32_TOL


def chip_check(params, ins, program_params=None):
    """What ``jobs/train_lm.check_logits`` computes for this family: the
    program's bf16 logits (one jitted program, as the job's) against
    ``family.reference_forward``; NaN where the reference refuses the
    program."""
    program_params = params if program_params is None else program_params
    got = jax.jit(lambda p, *t: model(jnp.bfloat16).apply(
        {"params": p}, *t))(program_params, *ins)
    want = controls.reference_on(params, program_params, ins, CONFIG)
    return relative(got, want) if bool(jnp.all(jnp.isfinite(want))) \
        else float("nan")


def test_the_chips_check_passes_a_sound_bf16_program(interpreted):
    """Under the job's 2% (chipbench/jobs/train_lm.LOGIT_RMS_TOL), through
    the kernels."""
    assert chip_check(randomised_params(), inputs(64)) <= 0.02


def test_control_four_bit_operands_fail_the_chips_check(interpreted):
    params = randomised_params()
    assert not chip_check(params, inputs(64),
                          program_params=four_bits(params)) <= 0.02


def test_control_the_leak_fails_the_chips_check(interpreted):
    """A noised query that sees its own clean block: the family's verdict
    is no match (a block's update leaves the reference's by far more than
    rounding), where the sound kernels pass."""
    with leak():
        assert not chip_check(randomised_params(), inputs(64)) <= 0.02
    assert chip_check(randomised_params(), inputs(64)) <= 0.02


def test_control_positions_counted_over_both_halves_fail_the_chips_check(
        interpreted):
    with rows_for_positions():
        assert not chip_check(randomised_params(), inputs(64)) <= 0.02


# ------------------------------------------------------------- the cell
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


def test_the_cell_is_declared():
    """The configuration's published keys are the catalog row's, ``reduced``
    and the floors hold, the parameter count is the file's, the family
    counts what the issue counted, and the cell lists its metrics."""
    config = harness.load_json("configs", "SDAR-30B-A3B-Chat.json")
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    differs = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == set(config["reduced"])
    assert (config["num_hidden_layers_published"],
            config["num_experts_published"],
            config["vocab_size_published"]) == (48, 128, 151936)
    held, layers = len(config["held_experts"]), config["num_hidden_layers"]
    assert layers in (8, 6) and layers >= 4
    assert held == config["num_experts"] == 16
    assert config["held_experts"] == list(range(16))
    assert config["vocab_size"] == 151936 // 8
    assumed = config["assumed"]
    assert {"block_length", "noise_schedule", "mask_token_id", "halves",
            "tie_tau", "padded_vocab_size"} <= set(assumed)
    assert (assumed["block_length"]["value"],
            assumed["mask_token_id"]["value"],
            assumed["noise_schedule"]["low"],
            assumed["noise_schedule"]["high"]) == (4, 18991, 0.45, 0.95)
    assert "same position" in assumed["halves"]
    rows = assumed["padded_vocab_size"]["value"]
    assert rows == 149 * 128 >= config["vocab_size"] > rows - 128

    m = family.build_model(config, rows, {"remat": "full"})
    assert (m.attn_heads, m.attn_kv_heads, m.attn_head_dim, m.attn_rope_theta,
            m.attn_qk_norm, m.denoise_blocks) == (32, 4, 128, 1e6, True, 4)
    assert (m.moe_experts, m.moe_top_k, m.moe_width, m.moe_shared_width,
            m.moe_scoring, m.tied_head) == (128, 8, 768, 0, "softmax", False)
    assert m.layer_kinds == ("attention",) * layers
    ids = jnp.zeros((1, 128), jnp.int32)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0), ids,
                            ids)["params"]

    def count(tree):
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(tree))

    parts = family.layer_parameters(config)
    assert parts == {"attn_q": 8_388_608, "attn_kv": 2_097_152,
                     "attn_o": 8_388_608, "router": 262_144,
                     "expert": 4_718_592}
    attention = parts["attn_q"] + parts["attn_kv"] + parts["attn_o"]
    assert count(shapes["block_0"]["mixer"]) == attention + 2 * 128
    assert count(shapes["block_1"]["ffn"]) == parts["router"] + 128 \
        + held * parts["expert"]
    assert shapes["block_1"]["ffn"]["w_in"].shape == (held, 2048, 1536)
    assert shapes["lm_head"]["kernel"].shape == (2048, rows)
    total = count(shapes)
    assert total == layers * (attention + 256 + parts["router"] + 128
                              + held * parts["expert"] + 2 * 2048) \
        + 2 * rows * 2048 + 2048
    assert round(total / 1e6, 1) == {8: 835.2, 6: 646.0}[layers]
    assert round(total * 10 / 2 ** 30, 2) == {8: 7.78, 6: 6.02}[layers]
    # an eighth of the experts is held: the balanced rows and the worst case
    assert moe.capacities(16384 * 8, 16, 128)[1] == 131072

    # a data token passes the blocks twice and the head once
    here = 8 * held / 128
    layer = attention + parts["router"] + here * parts["expert"]
    needed = block_diffusion_attention_cost.needed_scores(8192, 4)
    assert needed == 8192 ** 2 + 8192 * 4
    assert family.train_flops_per_token(config, rows, 8192) == pytest.approx(
        layers * (12 * layer + 12 * 4096 * needed / 8192)
        + 6 * 2048 * rows, rel=1e-9)
    costs = family.attention_train_costs(config, 1, 8192)
    assert costs == [{"flops": 12.0 * 32 * 128 * needed,
                      "bytes": 6.0 * 2 * 16384 * 128 * (32 + 4)}] * layers
    assert family.expected_first_loss(config, rows) == pytest.approx(
        math.log(rows) + 2048 * 0.02 ** 2 / 2)
    assert not hasattr(family, "moe_train_costs")
    route = family.flash_route(config, 8192)
    assert (route["forward"], route["backward"]) == ("once", "fused")
    assert pk._kv_vmem(16384, 128, 128, 2) == 16 * 2 ** 20

    cell = harness.load_cell("sdarmoe-train-s8192")
    assert (cell.chips, cell.job, cell.vocab_rows) == (1, "train_lm", rows)
    assert (cell.mix["objective"], cell.mix["global_batch"], cell.mix["seq"],
            cell.mix["remat"], cell.mix["chunk_steps"],
            cell.mix["batches"]) == ("block_denoise", 1, 8192, "full", 2, 4)
    step = cell.spec["sizing"]["programs"]["train_step"]
    assert 0.25 * 16 < step["peak_estimate_gib"] < 14.6
    declared = harness.declared_metrics(cell.name)
    names = {m["name"] for m in declared["per_layer"]}
    assert {"attn_blockdiff_relayout_ms", "denoise_io_ms",
            "flash_attention_roofline", "attn_kernel_ms", "train_mfu",
            "moe_experts_ms", "moe_route_ms", "moe_dispatch_ms",
            "moe_worst_case_ms", "moe_fit_ms", "moe_router_ms",
            "lm_head_ms", "blocks_recompute_ms"} <= names
    assert not {"ssd_ms", "short_conv_ms", "moe_shared_ms", "attn_gate_ms",
                "attn_window_kernel_ms", "delta_rule_ms", "allreduce_ms",
                "moe_experts_roofline"} & names
    assert {m["name"] for m in declared["end_to_end"]} == {
        "train_tokens_per_s_chip", "setup_s"}
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = [m for m in bench["per_layer"]
           if m["name"] in ("attn_blockdiff_relayout_ms", "denoise_io_ms")]
    assert [m["workloads"] for m in new] == [[cell.name]] * 2
    # side by side where PR 50 appended them (later metrics come after)
    at = bench["per_layer"].index(new[0])
    assert bench["per_layer"][at:at + 2] == new
    assert bench["workloads"][-1]["name"] == cell.name
    assert bench["configs"][-1]["name"] == "SDAR-30B-A3B-Chat"
