"""``models/hybrid.HybridLM`` as the Laguna family builds it (window and
full attention layers with their own head counts and rotary schemes, a gate
on every head's output, a softmax-routed expert feed-forward beside a shared
expert, an untied head) against its plain reference,
``chipbench/reference_laguna.py``; and what ``ops/pallas_kernels.py`` (a
window on the flash kernels), ``ops/rope.py`` and ``ops/moe.py`` gained for
it against loops and tables.

Small size, seeded weights with the norm weights randomised and the
matrices scaled so that each part of a block is as large as what it stands
beside. The model holds 2 of 8 experts (ids 1 and 6: not a prefix), three a
token; its window is 16 positions.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from chipbench import harness, reference_laguna as reference
from chipbench import window_attention_cost
from chipbench.families import laguna as family
from horovod_tpu import spmd
from horovod_tpu.models import hybrid
from horovod_tpu.models.transformer import lm_loss
from horovod_tpu.ops import moe, pallas_kernels as pk, rope
from horovod_tpu.parallel.ring_attention import reference_attention

ROPE = {"full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
#: the configuration keys the family and the reference read, small: the
#: leading dense layer and one period, 6 and 4 query heads over 2 KV heads
CONFIG = {"num_hidden_layers": 5, "hidden_size": 128,
          "intermediate_size": 192, "num_attention_heads": 4,
          "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
          "num_key_value_heads": 2, "head_dim": 32, "sliding_window": 16,
          "layer_types": ["full_attention"] + ["sliding_attention"] * 3
          + ["full_attention"],
          "mlp_layer_types": ["dense"] + ["sparse"] * 4,
          "gating_types": ["per_head"] * 5, "rope_parameters": ROPE,
          "num_experts": 2, "num_experts_published": 8,
          "held_experts": [1, 6], "num_experts_per_tok": 3,
          "moe_intermediate_size": 64, "shared_expert_intermediate_size": 64,
          "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
          "vocab_size": 512, "assumed": {"tie_tau": {"value": 5e-4}}}
ROWS = 512

#: float32 program against float32 reference: both round at 2^-24 and
#: differ in the order of their sums (grouped rows against masked experts,
#: one softmax against blocks of queries). Measured 3e-7 in the logits and
#: 2e-6 in the worst gradient leaf; bf16 operands read 1e-2 and 0.1.
F32_TOL = 2e-4


def model(dtype=jnp.float32, remat="none", config=CONFIG, **changes):
    return family.build_model(config, ROWS, {"remat": remat}).clone(
        dtype=dtype, **changes)


def tokens(seq, batch=2, seed=0):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                              ROWS, dtype=jnp.int32)
    return toks[:, :-1], toks[:, 1:]


@functools.lru_cache(maxsize=None)
def randomised_params(seed=1):
    params = model().init(jax.random.PRNGKey(seed), tokens(32)[0])["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))

    def shake(path, leaf, key):
        name = jax.tree_util.keystr(path)
        # at width 128 an N(0, 0.02) matrix shrinks what it maps to a
        # quarter: scaled so that attention's scores spread, its update and
        # the routed experts' are as large as what they stand beside, and
        # the gate and the router are not flat
        if any(k in name for k in ("['q']", "['k']", "['v']", "['o']",
                                   "['gate']", "router", "w_in", "w_out")):
            return 3.0 * leaf
        if leaf.ndim >= 2:
            return leaf                 # the matrices: N(0, 0.02) already
        if "expert_bias" in name:       # zeros that nothing moves
            return leaf
        return leaf + 0.5 * jax.random.normal(key, leaf.shape, leaf.dtype)

    return jax.tree_util.tree_unflatten(treedef, [
        shake(path, leaf, key) for (path, leaf), key in zip(leaves, keys)])


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not np.any(want) and not np.any(got):       # the selection bias's
        return 0.0
    return np.sqrt(np.sum((got - want) ** 2) / np.sum(want ** 2))


def _logits_loss_grads(forward, seq):
    params, (toks, targets) = randomised_params(), tokens(seq)

    def fn(p):
        logits = forward(p, toks)
        return lm_loss(logits, targets), logits

    (loss, logits), grads = jax.jit(
        jax.value_and_grad(fn, has_aux=True))(params)
    return logits, loss, grads


def program_side(seq, dtype):
    m = model(dtype)
    return _logits_loss_grads(lambda p, t: m.apply({"params": p}, t), seq)


@functools.lru_cache(maxsize=None)
def reference_side(seq):
    return _logits_loss_grads(
        lambda p, t: reference.forward(p, t, CONFIG), seq)


def worst_leaf(got, want, skip="expert_bias"):
    errors = jax.tree_util.tree_map(relative, got, want)
    return max((kv for kv in jax.tree_util.tree_leaves_with_path(errors)
                if skip not in jax.tree_util.keystr(kv[0])),
               key=lambda kv: kv[1])


@pytest.mark.parametrize("seq", [12, 40])
def test_logits_loss_and_every_gradient_match_the_reference(seq):
    """12 positions are under the window of 16 (the band never binds), 40
    over it."""
    logits, loss, grads = program_side(seq, jnp.float32)
    ref_logits, ref_loss, ref_grads = reference_side(seq)
    assert logits.dtype == jnp.float32 and logits.shape == (2, seq, ROWS)
    assert relative(logits, ref_logits) <= F32_TOL
    assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss)
    path, error = worst_leaf(grads, ref_grads)
    assert error <= F32_TOL, (jax.tree_util.keystr(path), error)
    # every kind of parameter took a gradient, but the selection bias, which
    # steers a choice and has none, in the program and in the reference
    for path, leaf in jax.tree_util.tree_leaves_with_path(ref_grads):
        moved = float(jnp.max(jnp.abs(leaf))) > 0
        assert moved != ("expert_bias" in jax.tree_util.keystr(path)), path


def test_the_parameter_tree_is_the_published_layers():
    """Six heads in a window layer and four in a full one over two KV
    heads, a gate of one column a head, no norm on q or k; a dense
    feed-forward in layer 0 and router, held experts and shared expert in
    the others; an untied head beside the table."""
    shapes = jax.tree_util.tree_map(lambda l: l.shape, randomised_params())
    assert set(shapes) == {"tok_emb", "norm_f", "lm_head"} | {
        f"block_{i}" for i in range(5)}

    def mixer(heads):
        return {"q": {"kernel": (128, heads * 32)}, "k": {"kernel": (128, 64)},
                "v": {"kernel": (128, 64)}, "gate": {"kernel": (128, heads)},
                "o": {"kernel": (heads * 32, 128)}}

    norms = {"norm_mixer": {"scale": (128,)}, "norm_ffn": {"scale": (128,)}}
    routed = {"router": (128, 8), "expert_bias": (8,), "w_in": (2, 128, 128),
              "w_out": (2, 64, 128), "shared_in": {"kernel": (128, 128)},
              "shared_out": {"kernel": (64, 128)}}
    assert shapes["block_0"] == {
        **norms, "mixer": mixer(4), "ffn_in": {"kernel": (128, 384)},
        "ffn_out": {"kernel": (192, 128)}}
    for i in (1, 2, 3):
        assert shapes[f"block_{i}"] == {**norms, "mixer": mixer(6),
                                        "ffn": routed}
    assert shapes["block_4"] == {**norms, "mixer": mixer(4), "ffn": routed}
    assert shapes["lm_head"] == {"kernel": (128, 512)}


def test_the_new_scopes_are_in_the_compiled_program():
    m = model(remat="full")
    toks = tokens(32)[0]
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0), toks)["params"]
    text = jax.jit(jax.grad(lambda p: jnp.sum(m.apply({"params": p}, toks)))
                   ).lower(params).as_text(debug_info=True)
    for scope in ("block_1/mixer/window", "block_3/mixer/window",
                  "block_0/mixer/gate", "block_1/mixer/gate",
                  "block_0/mixer/rope", "block_1/mixer/rope",
                  "block_1/ffn/shared_in", "block_1/ffn/moe/router",
                  "lm_head", "rematted_computation/block_4"):
        assert scope in text, scope
    for scope in ("block_0/mixer/window", "block_4/mixer/window", "qk_norm",
                  "tok_emb.attend", "latent_in"):
        assert scope not in text, scope


# ------------------------------------------------- the band in the kernels
def _qkv(t, h=2, d=64, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed + t), 4)
    return tuple(jax.random.normal(k, (1, t, h, d), jnp.float32)
                 for k in keys)


def _with_grads(fn, q, k, v, weight):
    """``(out, dq, dk, dv)`` under the loss ``sum(out * weight)``."""
    grads = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * weight),
                     argnums=(0, 1, 2))(q, k, v)
    return (fn(q, k, v), *grads)


_FLASH_DISPATCHERS = ("_flash_fwd_once_call", "_flash_step_call_resident",
                      "_flash_step_call_streaming", "_flash_bwd_fused",
                      "_flash_bwd_streaming")


@pytest.fixture
def small_tiles(monkeypatch):
    """The kernels through the interpreter at grid tiles of 32 x 32 (a
    windowed call's key tile: the power of two under its window, up to
    32), sub-tiles of 16; the dispatchers' traces forgotten around it."""
    def forget():
        for name in _FLASH_DISPATCHERS:
            getattr(pk, name).clear_cache()
        pk._flash_fullattn_vjp.cache_clear()

    monkeypatch.setenv("HVD_PALLAS", "interpret")
    monkeypatch.setattr(pk, "_BLOCK_Q", 32)
    monkeypatch.setattr(pk, "_BLOCK_K", 32)
    monkeypatch.setattr(pk, "_SUB_TILE", 16)
    forget()
    yield monkeypatch
    forget()


#: route -> the caps that send a head of 128 positions to it
ROUTES = {"resident_fused": {},
          "streaming_fused": {"_KV_VMEM_CAP": 1},
          "streaming_split": {"_KV_VMEM_CAP": 1, "_DQ_SCRATCH_CAP": 1}}


@pytest.mark.parametrize("route,window", [
    (route, window) for route in sorted(ROUTES) for window in (24, 32, 40)
] + [("resident_fused", 1), ("streaming_split", 1)])
def test_the_windowed_kernels_are_the_masked_softmax(route, window,
                                                     small_tiles):
    """The output and all three gradients of ``flash_attention(window=W)``
    through the Pallas interpreter against the masked reference, for a
    window under, equal to and over a tile edge of 32 (24 and 40 are no
    multiples of a tile or of a sub-tile) and of one position, through the
    resident and the streaming forward and the fused and the split
    backward."""
    for cap, value in ROUTES[route].items():
        small_tiles.setattr(pk, cap, value)
    t = 128
    q, k, v, weight = _qkv(t)
    taken = []
    real = pk._named_call
    small_tiles.setattr(pk, "_named_call", lambda name, kernel, **kw: (
        taken.append((name, kw["grid_spec"].grid)), real(name, kernel, **kw)
    )[1])
    got = _with_grads(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=True, window=window), q, k, v, weight)
    want = _with_grads(lambda q, k, v: reference_attention(
        q, k, v, causal=True, window=window), q, k, v, weight)
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=name)
    names = [name for name, _ in taken]
    forward, backward = route.split("_")
    assert ("flash_fwd" in names) == (forward == "resident")
    assert ("flash_step" in names) == (forward == "streaming")
    assert ("flash_bwd" in names) == (backward == "fused")
    assert ("flash_bwd_dq" in names) == (backward == "split")
    # a streaming grid's innermost extent is the band's, not the length's
    block_k = pk.flash_tiles(t, t, window)[1]
    spans = pk._band_spans(window, 32, block_k, t // 32, t // block_k)
    for name, grid in taken:
        if name in ("flash_step", "flash_bwd_dq"):
            assert grid[1:] == (t // 32, spans[0]) and spans[0] < t // block_k
        if name in ("flash_bwd", "flash_bwd_dkv"):
            assert grid[1:] == (t // block_k, spans[1]) and spans[1] < t // 32


def test_a_window_off_by_one_is_seen(small_tiles):
    """The control: the same comparison against a reference one position
    wider is far outside the tolerance."""
    q, k, v, weight = _qkv(128)
    got = _with_grads(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=True, window=32), q, k, v, weight)
    near = _with_grads(lambda q, k, v: reference_attention(
        q, k, v, causal=True, window=33), q, k, v, weight)
    for a, b in zip(got, near):
        assert np.max(np.abs(np.asarray(a) - np.asarray(b))) > 1e-2


def test_a_window_as_long_as_the_sequence_is_the_causal_call_to_the_bit(
        small_tiles):
    q, k, v, weight = _qkv(128)
    causal = _with_grads(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=True), q, k, v, weight)
    for window in (128, 1000):
        same = _with_grads(lambda q, k, v: pk.flash_attention(
            q, k, v, causal=True, window=window), q, k, v, weight)
        for a, b in zip(same, causal):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_window_needs_a_causal_self_attention_and_no_ring_hop():
    q, k, v, _ = _qkv(64)
    with pytest.raises(ValueError, match="window"):
        pk.flash_attention(q, k, v, causal=False, window=16)
    with pytest.raises(ValueError, match="window"):
        pk.flash_attention(q, k[:, :32], v[:, :32], causal=True, window=16)
    stat = jnp.zeros((1, 2, 64))
    with pytest.raises(ValueError, match="window"):
        pk.flash_attention_step(q, k, v, stat, stat, q, 0, 0, causal=True,
                                window=16)


def _brute_force_plan(t, block_k, sub_q, sub_k, window):
    """Sub-tiles that hold a score of the band, and the band's scores, one
    by one."""
    q, k = np.arange(t)[:, None], np.arange(t)[None, :]
    band = (k <= q) & (q - k < window)
    live = band.reshape(t // sub_q, sub_q, t // sub_k, sub_k).any(axis=(1, 3))
    return int(live.sum()), int(band.sum())


@pytest.mark.parametrize("t,block_k,sub,window", [
    (256, 64, (32, 64), 64), (256, 64, (32, 32), 40), (256, 32, (64, 16), 24),
    (512, 128, (128, 64), 100), (256, 64, (64, 64), 1)])
def test_flash_plan_with_a_window_counts_the_live_sub_tiles(t, block_k, sub,
                                                            window):
    plan = pk.flash_plan(True, t, t, 0, 0, block_k, *sub, window)
    computed, needed = _brute_force_plan(t, block_k, *sub, window)
    assert (plan["computed"], plan["needed"]) == (computed, needed)
    assert plan["masked"] == computed
    assert plan["computed"] + plan["skipped"] == (t // sub[0]) * (t // sub[1])
    assert plan["scores"] == computed * sub[0] * sub[1] >= needed
    assert needed == window_attention_cost.needed_scores(t, window)
    # without a window: the triangle, and today's count
    causal = pk.flash_plan(True, t, t, 0, 0, block_k, *sub)
    assert causal["needed"] == t * (t + 1) // 2
    assert causal["computed"] > plan["computed"]


def test_the_cells_band_is_skipped_and_not_only_masked():
    """8192 positions under a window of 512: the kernels' tiles compute at
    most 2.1 times the band's scores forward and backward (the causal
    tiles would compute 8.3 times as many), and a streaming grid walks two
    key blocks a q tile of the sixteen."""
    t, window = 8192, 512
    block_q, block_k = pk.flash_tiles(t, t, window)
    assert (block_q, block_k) == (512, 512)
    assert pk.flash_tiles(t, t) == (512, 1024)
    assert pk.flash_tiles(t, t, 300)[1] == 256       # no wider than the window
    forward = pk.flash_plan(True, t, t, 0, 0, block_k, block_q, block_k,
                            window)
    backward = pk.flash_plan(True, t, t, 0, 0, block_k, *pk._pick_sub_tile(
        True, block_q, block_k), window)
    needed = window * t - window * (window - 1) // 2
    for plan in (forward, backward):
        assert plan["needed"] == needed
        assert plan["scores"] / needed <= 2.1
    masked_only = pk.flash_plan(True, t, t, 0, 0, 1024, 512, 1024)
    assert masked_only["scores"] / needed > 8
    assert pk._band_spans(window, block_q, block_k, 16, 16) == (2, 2)
    shares = family.plan_shares(harness.load_json(
        "configs", "Laguna-S-2.1.json"), t)
    assert shares["sliding_attention"] == (
        forward["scores"] / needed, backward["scores"] / needed)
    assert max(shares["full_attention"]) < 1.15
    # the route is the full layers': a head's 2 MiB of K and 2 of V stay in
    # VMEM (8 MiB with the pipeline's second buffers, of the cap's 64)
    assert pk._kv_vmem(t, 128, 128, 2) == 8 * 2 ** 20
    assert pk.flash_route(t, t, 128, 2, window) == pk.flash_route(
        t, t, 128, 2) == {"forward": "once", "step": "step",
                          "backward": "fused", "backward_vmem": None}


# --------------------------------------------------------------- the rotary
def test_yarn_frequencies_and_the_partial_rotation_against_a_table():
    """The full layers' scheme at the published numbers, written out by
    hand: 32 pairs over the first 64 elements of a head of 128."""
    r = ROPE["full_attention"]
    inv_freq = rope.yarn_inv_freq(r["rope_theta"], 64, r["factor"],
                                  r["original_max_position_embeddings"],
                                  r["beta_fast"], r["beta_slow"])
    # the correction pairs: 32 rotations over 8192 positions at pair 9.0...,
    # one rotation at pair 17.5...; truncated to 9 and 18
    low = 64 * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(5e5))
    high = 64 * math.log(8192 / (1 * 2 * math.pi)) / (2 * math.log(5e5))
    assert (math.floor(low), math.ceil(high)) == (9, 18)
    want = []
    for i in range(32):
        plain = 5e5 ** (-2 * i / 64)
        ramp = min(max((i - 9) / (18 - 9), 0.0), 1.0)
        want.append(plain / 128 * ramp + plain * (1 - ramp))
    np.testing.assert_allclose(inv_freq, want, rtol=1e-12)
    assert inv_freq[:10] == tuple(5e5 ** (-2 * i / 64) for i in range(10))
    assert inv_freq[18:] == pytest.approx(
        [5e5 ** (-2 * i / 64) / 128 for i in range(18, 32)], rel=1e-12)
    cos, sin = reference.rotary_tables(r, 128, 40)
    np.testing.assert_allclose(
        cos, r["attention_factor"] * np.cos(np.arange(40)[:, None]
                                            * np.asarray(want)), rtol=1e-6)

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, 128), jnp.float32)
    got = rope.apply_rope(x, r["rope_theta"], rotary_dim=64,
                          inv_freq=inv_freq, factor=r["attention_factor"])
    table = np.asarray(x, np.float64).copy()
    for t in range(40):
        for i in range(32):
            c = r["attention_factor"] * math.cos(t * want[i])
            s = r["attention_factor"] * math.sin(t * want[i])
            a, b = np.asarray(x[:, t, :, i], np.float64), \
                np.asarray(x[:, t, :, i + 32], np.float64)
            table[:, t, :, i], table[:, t, :, i + 32] = a * c - b * s, \
                b * c + a * s
    np.testing.assert_allclose(got, table, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    np.testing.assert_allclose(reference._rotate(x, cos, sin), table,
                               rtol=2e-5, atol=2e-5)


def test_the_whole_head_at_a_base_is_todays_apply_rope_to_the_bit():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 3, 64), jnp.float32)
    half = 32
    angles = jnp.arange(24, dtype=jnp.float32)[:, None] * 1e4 ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    cos, sin = (f(angles)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    x1, x2 = jnp.split(x, 2, axis=-1)
    before = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    for call in (lambda: rope.apply_rope(x, 1e4),
                 lambda: rope.apply_rope(x, 1e4, rotary_dim=64, factor=1.0)):
        np.testing.assert_array_equal(np.asarray(call()), np.asarray(before))


# ------------------------------------------------------------- the routing
def test_softmax_routing_sums_to_the_scale_and_sigmoid_is_todays():
    logits = 2.0 * jax.random.normal(jax.random.PRNGKey(0), (64, 8),
                                     jnp.float32)
    zeros = jnp.zeros(8)
    chosen, weights, scores = moe.route(logits, zeros, 3, 2.5, 0.0,
                                        scoring="softmax")
    np.testing.assert_allclose(scores, jax.nn.softmax(logits, -1), rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(scores, -1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(weights, -1), 2.5, rtol=1e-6)
    np.testing.assert_array_equal(
        np.sort(chosen, -1), np.sort(np.argsort(-np.asarray(logits))[:, :3]))
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(
        weights, 2.5 * picked / jnp.sum(picked, -1, keepdims=True), rtol=1e-6)
    # a bias steers the choice and is in no weight; the model's is zeros
    steered, biased, _ = moe.route(logits, zeros.at[2].set(10.0), 3, 2.5,
                                   0.0, scoring="softmax")
    assert bool(jnp.all(jnp.any(steered == 2, axis=-1)))
    np.testing.assert_allclose(jnp.sum(biased, -1), 2.5, rtol=1e-6)
    # the kind is data, and the one there was is untouched
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (8,))
    for got, want in zip(moe.route(logits, bias, 3, scoring="sigmoid"),
                         moe.route(logits, bias, 3)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    s = jax.nn.sigmoid(logits)
    np.testing.assert_array_equal(np.asarray(moe.route(logits, bias, 3)[2]),
                                  np.asarray(s))
    with pytest.raises(ValueError, match="scoring"):
        moe.route(logits, bias, 3, scoring="tanh")


def layer_params(seed=3, d=32, f=24, shared=24, experts=32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = jax.random.normal
    return {"router": 0.5 * normal(keys[0], (d, experts)),
            "expert_bias": jnp.zeros((experts,)),
            "w_in": 0.3 * normal(keys[1], (experts, d, 2 * f)),
            "w_out": 0.3 * normal(keys[2], (experts, f, d)),
            "shared_in": {"kernel": 0.2 * normal(keys[3], (d, 2 * shared))},
            "shared_out": {"kernel": 0.2 * normal(keys[4], (shared, d))}}


def share_of(params, held):
    index = jnp.asarray(held)
    return {**params, "w_in": params["w_in"][index],
            "w_out": params["w_out"][index]}


def layer(params, h, held):
    """``RoutedFeedForward`` as the family builds it, holding ``held``."""
    module = hybrid.RoutedFeedForward(
        32, tuple(held), 4, 24, jnp.float32, shared_width=24, scale=2.5,
        norm_eps=0.0, scoring="softmax")
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, h: module.apply({"params": p}, h))(
            share_of(params, held), h)


def reference_layer(params, h, held):
    with jax.default_matmul_precision("highest"):
        return reference._routed(share_of(params, held), h, tuple(held), 4,
                                 2.5, None, 0.0)[0]


def test_the_sixteen_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """Each of sixteen chips holds two of the thirty-two experts and
    computes the shared expert whole; the routed parts of the sixteen, with
    the shared expert counted once, are what the reference gives holding
    every expert."""
    params = layer_params()
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 48, 32))
    shares = [(2 * i, 2 * i + 1) for i in range(16)]
    with jax.default_matmul_precision("highest"):
        shared = reference._swiglu(h, params["shared_in"]["kernel"],
                                   params["shared_out"]["kernel"])
    whole = reference_layer(params, h, range(32))
    parts = [layer(params, h, held) for held in shares]
    assert relative(sum(p - shared for p in parts) + shared, whole) <= 1e-6
    assert relative(sum(reference_layer(params, h, held) - shared
                        for held in shares[:4])
                    + sum(p - shared for p in parts[4:]) + shared,
                    whole) <= 1e-6
    # one share alone is not the layer, nor are the sixteen with the shared
    # expert counted sixteen times
    assert relative(parts[0], whole) > 0.1
    assert relative(sum(parts), whole) > 0.1
    norm = jnp.linalg.norm
    assert norm(shared) > 0.05 * norm(whole) < norm(whole - shared)


def test_the_layer_and_its_gradients_match_the_reference_at_each_capacity():
    """A bias under the held experts' scores (the reference adds the same)
    pushes the routing to no row here, to a balanced share and to every
    token: both row capacities and none."""
    params = layer_params()
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 96, 32))
    held = (1, 4, 6, 9)
    sizes = moe.capacities(96 * 4, 4, 32)
    for bias, size in ((-10.0, 0), (0.0, 0), (10.0, 1)):
        p = {**params, "expert_bias": params["expert_bias"].at[
            jnp.asarray(held)].add(bias)}
        picked = np.argsort(-np.asarray(
            jax.nn.softmax(h[0] @ p["router"]) + p["expert_bias"]))[:, :4]
        rows = int(np.isin(picked, held).sum())
        assert sum(rows > s for s in sizes[:-1]) == size, (rows, sizes)

        def loss(fn):
            return lambda p, h: jnp.sum(jnp.sin(0.5 * fn(p, h, held)))

        assert relative(layer(p, h, held), reference_layer(p, h, held)) <= 1e-6
        got = jax.grad(loss(layer), (0, 1))(p, h)
        want = jax.grad(loss(reference_layer), (0, 1))(p, h)
        path, error = worst_leaf(got, want)
        assert error <= 1e-5, (bias, jax.tree_util.keystr(path), error)
        assert not np.any(np.asarray(got[0]["expert_bias"]))


# -------------------------------------------------------------- controls
def test_control_bf16_operands_fail_the_float32_tolerance():
    """The same comparison one precision lower: over F32_TOL by far."""
    logits, _, grads = program_side(40, jnp.bfloat16)
    ref_logits, _, ref_grads = reference_side(40)
    assert relative(logits, ref_logits) > 10 * F32_TOL
    assert worst_leaf(grads, ref_grads)[1] > 10 * F32_TOL


def low(x, bits):
    """``x`` rounded to ``bits`` bits of mantissa."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 2.0 ** bits) / 2.0 ** bits, e)


def chip_check(params, toks, program=None, program_params=None):
    """What ``jobs/train_lm.check_logits`` computes for this family: the
    program's bf16 logits against ``family.reference_forward``; NaN where
    the reference refuses the program."""
    m = model(jnp.bfloat16) if program is None else program
    got = m.apply({"params": params if program_params is None
                   else program_params}, toks)
    want = family.reference_forward(params, toks, CONFIG)
    return relative(got, want) if bool(jnp.all(jnp.isfinite(want))) \
        else float("nan")


def test_the_chips_check_passes_a_sound_bf16_program():
    """Under the job's 2% (chipbench/jobs/train_lm.LOGIT_RMS_TOL)."""
    assert chip_check(randomised_params(), tokens(64)[0]) <= 0.02


def test_control_four_bit_operands_fail_the_chips_check():
    """Every matrix rounded to 4 bits of mantissa (e4m3's) in the program
    only: the blocks' updates leave their limit."""
    params = randomised_params()
    coarse = jax.tree_util.tree_map(
        lambda l: low(l, 4) if l.ndim >= 2 else l, params)
    assert not chip_check(params, tokens(64)[0], program_params=coarse) <= 0.02


def _kind(differs):
    """The sound model with one attention kind's fields changed."""
    sound = family.build_model(CONFIG, ROWS, {})
    kinds = {kind: {**fields, **differs.get(kind, {})}
             for kind, fields in sound.attn_kinds.items()}
    return {"attn_kinds": kinds, **differs.get("model", {})}


#: a program that differs from the model in one thing the reference holds
#: it to; ``family.program_trace`` runs the program, so it is patched too
WRONG = {
    "a_missing_gate": {"model": {"attn_gate": False}},
    "a_window_off_by_one": {"sliding_attention": {"window": 17}},
    "no_window": {"sliding_attention": {"window": None}},
    "a_missing_attention_factor": {"full_attention": {"rope_factor": 1.0}},
    "the_whole_head_turned": {"full_attention": {"rotary_dim": None,
                                                 "rope_inv_freq": None}},
    "sigmoid_scores": {"model": {"moe_scoring": "sigmoid"}},
}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_control_a_wrong_program_fails_the_chips_check(name, monkeypatch):
    sound = family.build_model
    changes = _kind(WRONG[name])
    monkeypatch.setattr(family, "build_model",
                        lambda *a: sound(*a).clone(**changes))
    params = randomised_params()
    wrong = family.build_model(CONFIG, ROWS, {}).clone(dtype=jnp.bfloat16)
    assert not chip_check(params, tokens(64)[0], program=wrong) <= 0.02, name


# ---------------------------------------------------- remat, training, count
def test_a_recomputed_model_agrees():
    params, (toks, targets) = randomised_params(), tokens(40)

    def loss_and_grads(remat):
        m = model(remat=remat)
        return jax.jit(jax.value_and_grad(lambda p: lm_loss(
            m.apply({"params": p}, toks), targets)))(params)

    base_loss, base_grads = loss_and_grads("none")
    loss, grads = loss_and_grads("full")     # the cell's
    assert abs(float(loss) - float(base_loss)) <= 1e-6 * float(base_loss)
    assert worst_leaf(grads, base_grads)[1] <= 1e-5


def test_three_train_steps_on_the_mesh_reproduce_the_reference_losses():
    """``spmd.make_train_step`` + ``lm_loss`` + the job's AdamW as
    ``chipbench/jobs/train_lm.build`` calls them, batch 8 over the 8-device
    mesh, against ``jax.value_and_grad`` of ``lm_loss`` over the reference
    under the same optimizer."""
    hvd.init()
    mesh = hvd.mesh()
    m = model()
    params, batch = randomised_params(), tokens(40, batch=8, seed=7)

    def loss_fn(p, b):
        return lm_loss(m.apply({"params": p}, b[0]), b[1])

    def plain_loss(p, b):
        return lm_loss(reference.forward(p, b[0], CONFIG), b[1])

    tx = optax.adamw(3e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16)
    step = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False)
    p, o = spmd.replicate(params, mesh), spmd.replicate(tx.init(params), mesh)
    sharded = spmd.shard_batch(batch, mesh)
    rp, ro = params, tx.init(params)
    plain = jax.jit(jax.value_and_grad(plain_loss))
    for i in range(3):
        p, o, loss = step(p, o, sharded)
        ref_loss, grads = plain(rp, batch)
        updates, ro = tx.update(grads, ro, rp)
        rp = optax.apply_updates(rp, updates)
        assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss), i
    assert float(loss) < float(plain(params, batch)[0])


def test_unknown_attention_kinds_are_refused():
    toks = tokens(8)[0]
    for change, match in (
            ({"attn_kinds": {"attention": {"heads": 6}}}, "attn_kinds"),
            ({"attn_kinds": {"wide": {"head_count": 6}}}, "attn_kinds"),
            ({"layer_kinds": ("full_attention",) * 4 + ("local",)},
             "layer_kinds"),
            ({"moe_scoring": "tanh"}, "scoring")):
        with pytest.raises(ValueError, match=match):
            jax.eval_shape(model().clone(**change).init,
                           jax.random.PRNGKey(0), toks)


def test_the_family_counts_what_the_issue_counted():
    """The published widths: 1,113 M parameters here, the matrix elements a
    token touches, the rooflines' operations."""
    config = harness.load_json("configs", "Laguna-S-2.1.json")
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "gating_types", "num_experts",
        "vocab_size"]
    held = len(config["held_experts"])
    assert held == config["num_experts"]
    m = family.build_model(config, 12544, {"remat": "full"})
    assert m.attn_kinds["sliding_attention"]["window"] == 512
    assert m.attn_kinds["sliding_attention"]["heads"] == 72
    assert m.attn_kinds["full_attention"]["rotary_dim"] == 64
    assert len(m.attn_kinds["full_attention"]["rope_inv_freq"]) == 32
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))["params"]

    def count(tree):
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(tree))

    full = 3072 * (48 + 8 + 8) * 128 + 48 * 128 * 3072 + 3072 * 48
    window = 3072 * (72 + 16) * 128 + 72 * 128 * 3072 + 3072 * 72
    assert (full, window) == (44_187_648, 63_135_744)
    assert count(shapes["block_0"]["mixer"]) == full
    assert count(shapes["block_1"]["mixer"]) == window
    expert = 3 * 3072 * 1024
    assert count(shapes["block_1"]["ffn"]) == (
        3072 * 256 + 256 + (held + 1) * expert)
    assert shapes["block_1"]["ffn"]["w_in"].shape == (held, 3072, 2048)
    assert count(shapes["block_0"]) == full + 3 * 3072 * 12288 + 2 * 3072
    total = count(shapes)
    assert total == (2 * full + 3 * window + 3 * 3072 * 12288
                     + 4 * (3072 * 256 + 256 + (held + 1) * expert)
                     + 2 * 12544 * 3072 + 11 * 3072)
    assert total == {16: 1_113_008_128, 8: 811_018_240}[held]
    # four times the balanced 5,120 rows: a 16th of the experts is held
    assert moe.capacities(8192 * 10, held, 256) == {
        16: (20480, 81920), 8: (12800, 81920)}[held]
    # the other routed cells' are what they were
    assert moe.capacities(16384 * 4, 8, 32) == (32768, 65536)
    assert moe.capacities(4096 * 22, 8, 512) == (11264, 90112)

    # 6 x the matrix elements a token touches, and the attention's scores
    here = 10 * held / 256
    elements = (2 * (full - 0) + 3 * window + 3 * 3072 * 12288
                + 4 * (3072 * 256 + 3 * 3072 * 1024 + here * expert)
                + 12544 * 3072)
    band = 12 * 72 * 128 * (512 * 8192 - 512 * 511 / 2) / 8192
    assert family.train_flops_per_token(config, 12544, 8192) == pytest.approx(
        6 * elements + 2 * 6 * 8192 * 48 * 128 + 3 * band, rel=1e-9)
    assert family.expected_first_loss(config, 12544) == pytest.approx(
        math.log(12544) + 3072 * 0.02 ** 2 / 2)
    costs = family.attention_train_costs(config, 1, 8192)
    assert [c["flops"] for c in costs] == [
        6.0 * 48 * 8192 * 8192 * 128,
        *[12.0 * 72 * 128 * (512 * 8192 - 512 * 511 / 2)] * 3,
        6.0 * 48 * 8192 * 8192 * 128]
    assert family.window_train_costs(config, 1, 8192) == costs[1:4]
    # a window layer at the bf16 peak: 2.28 ms, compute-bound
    assert costs[1]["flops"] / 197e12 == pytest.approx(2.281e-3, rel=1e-3)
    assert costs[1]["bytes"] / 819e9 < costs[1]["flops"] / 197e12
    moe_costs = family.moe_train_costs(config, 1, 8192)
    assert len(moe_costs) == 4
    assert moe_costs[0]["flops"] == 18.0 * (8192 * 10 * held / 256) \
        * 3072 * 1024


# ---------------------------- the three earlier hybrids: nothing of them moved
def _tree_and_text(family_module, config_file, rows=512):
    config = {**harness.load_json("configs", config_file),
              **family_module.REHEARSAL}
    m = family_module.build_model(config, rows, {"remat": "full"})
    toks = jnp.zeros((1, 32), jnp.int32)
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0), toks)["params"]
    text = jax.jit(jax.grad(lambda p: jnp.sum(m.apply({"params": p}, toks)))
                   ).lower(params).as_text(debug_info=True)
    return m, jax.tree_util.tree_map(lambda l: l.shape, params), text


@pytest.mark.parametrize("name", ["granitemoehybrid", "lfm2_moe",
                                  "nemotron_h"])
def test_the_earlier_hybrids_trees_and_scope_paths_are_unchanged(name):
    """Built as their families build them: no gate, no window scope, the
    attention mixer's parameters as they were, the new fields at defaults
    that are the model there was."""
    import importlib

    module = importlib.import_module(f"chipbench.families.{name}")
    config_file = {"granitemoehybrid": "granite-4.0-h-micro.json",
                   "lfm2_moe": "LFM2-8B-A1B.json",
                   "nemotron_h": "NVIDIA-Nemotron-3-Super-120B-A12B-BF16.json"
                   }[name]
    m, shapes, text = _tree_and_text(module, config_file)
    assert (m.attn_gate, dict(m.attn_kinds), m.moe_scoring) == (
        False, {}, "sigmoid")
    attention = [i for i, kind in enumerate(m.layer_kinds)
                 if kind == "attention"]
    assert attention
    for i in attention:
        mixer = shapes[f"block_{i}"]["mixer"]
        want = {"q", "k", "v", "o"} | (
            {"q_norm", "k_norm"} if name == "lfm2_moe" else set())
        assert set(mixer) == want
        assert f"block_{i}/mixer/q" in text
        assert (f"block_{i}/mixer/rope" in text) == (name == "lfm2_moe")
    for scope in ("mixer/window", "mixer/gate/"):
        assert scope not in text, scope
    fields = hybrid.AttentionMixer.__dataclass_fields__
    assert (fields["window"].default, fields["gate"].default,
            fields["rotary_dim"].default, fields["rope_inv_freq"].default,
            fields["rope_factor"].default) == (None, False, None, None, 1.0)
