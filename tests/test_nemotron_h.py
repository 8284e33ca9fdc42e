"""``models/hybrid.HybridLM`` as the Nemotron-H family builds it (blocks of
one sublayer: Mamba-2 with groups of B and C, attention without position
encoding, a LatentMoE feed-forward with a shared expert; an untied head)
against its plain reference, ``chipbench/reference_nemotron_h.py``, and what
``ops/ssd.py`` and ``ops/moe.py`` gained for it against loops.

Small size, seeded weights with the norm weights, ``A_log``, ``dt_bias``,
``D``, the conv and the routers' selection bias randomised: at their
initial values (every norm weight 1, ``D`` 1, bias 0) a dropped weight or
a wrong decay hides. The model holds 2 of 8 experts (ids 1 and 6: not a
prefix, so a mix-up of ids and positions shows), three a token.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from chipbench import reference_nemotron_h as reference
from chipbench.families import nemotron_h as family
from horovod_tpu import spmd
from horovod_tpu.models import hybrid
from horovod_tpu.models.transformer import lm_loss
from horovod_tpu.ops import moe, pallas_kernels as pk, ssd

#: the configuration keys the family and the reference read, small: every
#: kind of block, 2 state groups, 2 KV heads under 4 query heads, a latent
#: narrower than the model
CONFIG = {"num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
          "hidden_size": 128, "mamba_num_heads": 4, "mamba_head_dim": 32,
          "n_groups": 2, "ssm_state_size": 16, "chunk_size": 8,
          "conv_kernel": 4, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 32, "n_routed_experts": 2,
          "n_routed_experts_published": 8, "held_experts": [1, 6],
          "num_experts_per_tok": 3, "moe_intermediate_size": 64,
          "moe_latent_size": 64, "moe_shared_expert_intermediate_size": 96,
          "routed_scaling_factor": 5, "layer_norm_epsilon": 1e-5,
          "vocab_size": 512, "assumed": {"tie_tau": {"value": 1e-3}}}
ROWS = 512

#: float32 program against float32 reference: both round at 2^-24 and
#: differ in the order of their sums (chunks of 8 against one position at a
#: time, grouped rows against masked experts, flash tiles against one
#: softmax). Measured 6e-7 in the logits, and in the worst gradient leaf
#: (always an ``A_log``, four numbers a layer whose gradient sums a decay's
#: exponents over every position) 5e-6 to 6.4e-5 over the lengths and the
#: weights tried. 2e-4 is three times the worst; bf16 operands read 7e-2 in
#: the logits and 0.3 in a leaf, a thousand times over: the control.
F32_TOL = 2e-4


def model(dtype=jnp.float32, remat="none", config=CONFIG, **changes):
    return family.build_model(config, ROWS, {"remat": remat}).clone(
        dtype=dtype, **changes)


def tokens(seq, batch=2, seed=0):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                              ROWS, dtype=jnp.int32)
    return toks[:, :-1], toks[:, 1:]


def randomised_params(seed=1):
    params = model().init(jax.random.PRNGKey(seed), tokens(32)[0])["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))

    def shake(path, leaf, key):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:       # half the scores' spread
            return 0.03 * jax.random.normal(key, leaf.shape, leaf.dtype)
        # at width 128 an N(0, 0.02) matrix shrinks what it maps to a quarter:
        # an attention or feed-forward block's update is a twentieth of the
        # stream, which the program rounds to bf16, and through four matrices
        # and a square the routed part is a thousandth of the shared
        # expert's. Scaled so that each is as large as what it stands beside
        if any(k in name for k in ("latent_in", "w_in", "w_out", "latent_out")):
            return 4.0 * leaf
        if any(k in name for k in ("['v']", "['o']", "shared_")):
            return 2.5 * leaf
        if leaf.ndim >= 2 and "conv" not in name:
            return leaf                 # the matrices: N(0, 0.02) already
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


@pytest.mark.parametrize("seq", [32, 52])
def test_logits_loss_and_every_gradient_match_the_reference(seq):
    """52 is no multiple of the chunk: the scan pads it."""
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
    for block in ("block_1", "block_4"):
        assert not np.any(np.asarray(grads[block]["ffn"]["expert_bias"]))


def test_the_parameter_tree_is_the_published_layers():
    """One norm and one sublayer a block: no feed-forward in ``M`` and
    ``*``, no mixer in ``E``; an untied head beside the table."""
    shapes = jax.tree_util.tree_map(lambda l: l.shape, randomised_params())
    assert set(shapes) == {"tok_emb", "norm_f", "lm_head"} | {
        f"block_{i}" for i in range(5)}
    assert shapes["block_0"] == {
        "norm_mixer": {"scale": (128,)},
        "mixer": {"in_proj": {"kernel": (128, 2 * 128 + 2 * 2 * 16 + 4)},
                  "conv": {"kernel": (4, 128 + 2 * 2 * 16),
                           "bias": (128 + 2 * 2 * 16,)},
                  "A_log": (4,), "dt_bias": (4,), "D": (4,),
                  "gate_norm": {"scale": (128,)},
                  "out_proj": {"kernel": (128, 128)}}}
    assert shapes["block_2"] == shapes["block_0"]
    assert shapes["block_3"] == {
        "norm_mixer": {"scale": (128,)},
        "mixer": {"q": {"kernel": (128, 128)}, "k": {"kernel": (128, 64)},
                  "v": {"kernel": (128, 64)}, "o": {"kernel": (128, 128)}}}
    assert shapes["block_1"] == {
        "norm_ffn": {"scale": (128,)},
        "ffn": {"router": (128, 8), "expert_bias": (8,),
                "latent_in": {"kernel": (128, 64)},
                "w_in": (2, 64, 64), "w_out": (2, 64, 64),
                "latent_out": {"kernel": (64, 128)},
                "shared_in": {"kernel": (128, 96)},
                "shared_out": {"kernel": (96, 128)}}}
    assert shapes["block_4"] == shapes["block_1"]
    assert shapes["tok_emb"] == {"embedding": (512, 128)}
    assert shapes["lm_head"] == {"kernel": (128, 512)}


def test_the_new_scopes_are_in_the_compiled_program():
    m = model(remat="full")
    toks = tokens(32)[0]
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0), toks)["params"]
    text = jax.jit(jax.grad(lambda p: jnp.sum(m.apply({"params": p}, toks)))
                   ).lower(params).as_text(debug_info=True)
    for scope in ("block_0/mixer/ssd", "block_0/mixer/gate_norm",
                  "block_3/mixer/q", "block_1/ffn/latent_in",
                  "block_1/ffn/latent_out", "block_1/ffn/shared_in",
                  "block_1/ffn/shared_out", "block_1/ffn/moe/router",
                  "block_1/ffn/moe/dispatch", "experts", "combine",
                  "block_1/norm_ffn", "block_0/norm_mixer", "lm_head",
                  "tok_emb", "norm_f", "rematted_computation/block_4"):
        assert scope in text, scope
    for scope in ("block_0/norm_ffn", "block_1/norm_mixer", "block_1/mixer",
                  "tok_emb.attend", "rope"):
        assert scope not in text, scope


# ------------------------------------------------------------ the shares
def layer_params(seed=3, d=32, latent=16, f=24, shared=40, experts=8):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = jax.random.normal
    return {"router": 0.5 * normal(keys[0], (d, experts)),
            "expert_bias": 0.1 * normal(keys[1], (experts,)),
            "latent_in": {"kernel": 0.3 * normal(keys[2], (d, latent))},
            "w_in": 0.3 * normal(keys[3], (experts, latent, f)),
            "w_out": 0.3 * normal(keys[4], (experts, f, latent)),
            "latent_out": {"kernel": 0.3 * normal(keys[5], (latent, d))},
            "shared_in": {"kernel": 0.2 * normal(keys[6], (d, shared))},
            "shared_out": {"kernel": 0.2 * normal(keys[7], (shared, d))}}


def share_of(params, held):
    index = jnp.asarray(held)
    return {**params, "w_in": params["w_in"][index],
            "w_out": params["w_out"][index]}


def layer(params, h, held, **changes):
    """``RoutedFeedForward`` as the family builds it, holding ``held``."""
    module = hybrid.RoutedFeedForward(
        8, tuple(held), 3, 24, jnp.float32, "relu2", 16, 40, 5.0,
        family.ROUTE_NORM_EPS).clone(**changes)
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, h: module.apply({"params": p}, h))(
            share_of(params, held), h)


def reference_layer(params, h, held):
    with jax.default_matmul_precision("highest"):
        return reference._latent_moe(share_of(params, held), h, tuple(held),
                                     3, 5.0, None, 0.0)[0]


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """Each of four chips holds two of the eight experts and computes the
    shared expert whole; the routed parts of the four, with the shared
    expert counted once, are what the reference gives holding all eight
    (the up-projection is linear, so it may be applied share by share)."""
    params = layer_params()
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 96, 32))
    shares = [(0, 1), (2, 3), (4, 5), (6, 7)]
    with jax.default_matmul_precision("highest"):
        shared = reference._relu2(h @ params["shared_in"]["kernel"]) \
            @ params["shared_out"]["kernel"]
    whole = reference_layer(params, h, range(8))
    parts = [layer(params, h, held) for held in shares]
    assert relative(sum(p - shared for p in parts) + shared, whole) <= 1e-6
    assert relative(sum(reference_layer(params, h, held) - shared
                        for held in shares) + shared, whole) <= 1e-6
    # one share alone is not the layer, nor are the four with the shared
    # expert counted four times
    assert relative(parts[0], whole) > 0.1
    assert relative(sum(parts), whole) > 0.1
    # neither the shared expert nor the routed part is a rounding of it
    norm = jnp.linalg.norm
    assert norm(shared) > 0.05 * norm(whole) < norm(whole - shared)


def test_the_layer_and_its_gradients_match_the_reference_at_each_capacity():
    """The held experts' bias pushes the routing to no row here, to a
    balanced share and to every token: both row capacities and none."""
    params = layer_params()
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 96, 32))
    held = (1, 4, 6)
    sizes = moe.capacities(96 * 3, 3, 8)
    for bias, size in ((-10.0, 0), (0.0, 0), (10.0, 1)):
        p = {**params, "expert_bias": params["expert_bias"].at[
            jnp.asarray(held)].add(bias)}
        picked = np.argsort(-np.asarray(
            jax.nn.sigmoid(h[0] @ p["router"]) + p["expert_bias"]))[:, :3]
        rows = int(np.isin(picked, held).sum())
        assert sum(rows > s for s in sizes[:-1]) == size, (rows, sizes)

        def loss(fn):       # (the layer's values reach 50: sin(y) would
            # turn a rounding of y into one of the gradient)
            return lambda p, h: jnp.sum(jnp.sin(0.02 * fn(p, h, held)))

        assert relative(layer(p, h, held), reference_layer(p, h, held)) <= 1e-6
        got = jax.grad(loss(layer), (0, 1))(p, h)
        want = jax.grad(loss(reference_layer), (0, 1))(p, h)
        path, error = worst_leaf(got, want)
        assert error <= 1e-5, (bias, jax.tree_util.keystr(path), error)
        assert not np.any(np.asarray(got[0]["expert_bias"]))


def test_the_weights_sum_to_the_scale_and_the_bias_only_steers():
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
    bias = jnp.zeros(8).at[2].set(10.0)
    chosen, weights, scores = moe.route(logits, bias, 3, 5.0, 1e-20)
    assert bool(jnp.all(jnp.any(chosen == 2, axis=-1)))
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 5.0, rtol=1e-6)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(
        weights, 5.0 * picked / jnp.sum(picked, -1, keepdims=True), rtol=1e-6)
    # the defaults are LFM2's: a unit sum but for its 1e-6
    _, unit, _ = moe.route(logits, bias, 3)
    np.testing.assert_allclose(
        unit, picked / (jnp.sum(picked, -1, keepdims=True) + 1e-6), rtol=1e-6)


# --------------------------------------------------- the scan with groups
def scan_operands(groups, seed=0, b=2, t=40, h=8, p=4, n=6):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    bc = (b, t, n) if groups is None else (b, t, groups, n)
    return (jax.random.normal(keys[0], (b, t, h, p)),
            jax.nn.softplus(jax.random.normal(keys[1], (b, t, h))),
            -jnp.exp(0.3 * jax.random.normal(keys[2], (h,))),
            jax.random.normal(keys[3], bc), jax.random.normal(keys[4], bc),
            jax.random.normal(keys[5], (h,)))


@pytest.mark.parametrize("groups", [1, 2, 4, 8])
def test_the_chunked_scan_with_groups_is_the_recurrence(groups):
    """Head ``h`` reads group ``h // (8 / groups)``; 40 positions in chunks
    of 16 are padded. Float32: the chunked form's sums differ in order from
    the recurrence's, 2e-6 measured."""
    operands = scan_operands(groups)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(functools.partial(ssd.ssd_chunked, chunk=16))(*operands)
        want = reference.recurrence(*operands)
        assert got.shape == want.shape == (2, 40, 8, 4)
        assert relative(got, want) <= 1e-5
        # and its gradients, in every operand
        weight = jnp.cos(jnp.arange(want.size, dtype=jnp.float32)).reshape(
            want.shape)
        g_got = jax.grad(lambda *a: jnp.sum(ssd.ssd_chunked(
            *a, chunk=16) * weight), range(6))(*operands)
        g_want = jax.grad(lambda *a: jnp.sum(reference.recurrence(*a)
                                             * weight), range(6))(*operands)
    for name, a, b in zip("x dt A B C D".split(), g_got, g_want):
        assert relative(a, b) <= 2e-5, (name, relative(a, b))
    # groups matter: the heads of the second half read other B and C
    if groups > 1:
        first = tuple(a[:, :, :1] if i in (3, 4) else a
                      for i, a in enumerate(operands))
        assert relative(ssd.ssd_chunked(*first, chunk=16), want) > 0.1


def kernel_operands(groups, h, p, t, seed=0, b=1, n=128, dtype=jnp.float32):
    """Operands the scan's Pallas kernels take (``pk.ssd_route``): a group
    of at least 8 heads, a state of one lane width."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    bc = (b, t, n) if groups is None else (b, t, groups, n)

    def normal(key, shape):
        return jax.random.normal(key, shape, jnp.float32)

    return (normal(keys[0], (b, t, h, p)).astype(dtype),
            0.3 * jax.nn.softplus(normal(keys[1], (b, t, h))),
            -jnp.exp(0.3 * normal(keys[2], (h,))),
            (0.3 * normal(keys[3], bc)).astype(dtype),
            (0.3 * normal(keys[4], bc)).astype(dtype), normal(keys[5], (h,)))


#: groups, heads, head width, positions, chunk: the published head width
#: (two heads a lane width) under one, two and no axis of groups (16 heads
#: a grid cell, 8 and 8); eight groups of eight narrow heads (a lane width
#: is a cell); a head a lane width. The kernel's tile is 256 (128 for a
#: sequence no longer): 300 and 400 positions fill the second tile in
#: part; a chunk of 512 is two kernel tiles, 256 one, 64 a quarter of one.
KERNEL_CASES = {"one_group": (1, 16, 64, 512, 256),
                "two_groups": (2, 16, 64, 300, 512),
                "no_group_axis": (None, 8, 64, 128, 64),
                "eight_groups": (8, 64, 16, 400, 256),
                "a_head_a_lane_width": (2, 16, 128, 300, 512)}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_scan_kernels_with_groups_are_the_recurrence(case, monkeypatch):
    """``y`` and all six gradients of the kernel path (``ssd_fwd`` and the
    hand-written ``ssd_bwd`` through the Pallas interpreter, the groups an
    index map) against the float32 recurrence, at the dual form's
    tolerances: measured 2e-7 and, in A's gradient, 7e-6."""
    monkeypatch.setenv("HVD_PALLAS", "interpret")
    groups, h, p, t, chunk = KERNEL_CASES[case]
    operands = kernel_operands(groups, h, p, t)
    assert pk.kernel_path("ssd_scan", operands[0], operands[3]) == "pallas"
    with jax.default_matmul_precision("highest"):
        got = ssd.ssd_chunked(*operands, chunk=chunk)
        want = reference.recurrence(*(
            a[:, :, None] if groups is None and i in (3, 4) else a
            for i, a in enumerate(operands)))
        assert got.shape == want.shape == (1, t, h, p)
        assert relative(got, want) <= 1e-5
        weight = jnp.cos(jnp.arange(want.size, dtype=jnp.float32)).reshape(
            want.shape)
        g_got = jax.grad(lambda *a: jnp.sum(ssd.ssd_chunked(
            *a, chunk=chunk) * weight), range(6))(*operands)
        g_want = jax.grad(lambda *a: jnp.sum(reference.recurrence(*(
            x[:, :, None] if groups is None and i in (3, 4) else x
            for i, x in enumerate(a))) * weight), range(6))(*operands)
    for name, a, b in zip("x dt A B C D".split(), g_got, g_want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert relative(a, b) <= 2e-5, (name, relative(a, b))
    if groups and groups > 1:
        first = tuple(a[:, :, :1] if i in (3, 4) else a
                      for i, a in enumerate(operands))
        assert relative(ssd.ssd_chunked(*first, chunk=chunk), want) > 0.1


#: bf16 operands, either path against the float32 recurrence and the two
#: against each other: measured 2.4e-3 to 3.4e-3 in ``y`` and up to 6e-3 in
#: a gradient (each path rounds its scores and its state once to bf16,
#: 2^-9, at different places: the kernels fold dt into the scores' columns
#: forward and into x backward); 2e-2 is what the chip's check allows a
#: bf16 program (``test_hybrid_lm``'s control)
BF16_SCAN_TOL = 2e-2


@pytest.mark.parametrize("case", ["one_group", "two_groups"])
def test_the_scan_kernels_at_bf16_stay_by_the_dual_form(case, monkeypatch):
    groups, h, p, t, chunk = KERNEL_CASES[case]
    operands = kernel_operands(groups, h, p, t, dtype=jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in operands)
    weight = jnp.cos(jnp.arange(operands[0].size, dtype=jnp.float32)
                     ).reshape(operands[0].shape)

    def both(*a):
        def loss(*a):
            y = ssd.ssd_chunked(*a, chunk=chunk)
            return jnp.sum(y.astype(jnp.float32) * weight), y
        grads, y = jax.grad(loss, range(6), has_aux=True)(*a)
        return (y,) + grads

    assert pk.kernel_path("ssd_scan", operands[0], operands[3]) == "reference"
    dual = both(*operands)
    monkeypatch.setenv("HVD_PALLAS", "interpret")
    assert pk.kernel_path("ssd_scan", operands[0], operands[3]) == "pallas"
    kernels = both(*operands)
    want = (reference.recurrence(*exact),) + jax.grad(
        lambda *a: jnp.sum(reference.recurrence(*a) * weight),
        range(6))(*exact)
    for name, k, d, w in zip("y x dt A B C D".split(), kernels, dual, want):
        assert k.dtype == d.dtype and k.shape == d.shape, name
        assert relative(k, d) <= BF16_SCAN_TOL, (name, relative(k, d))
        assert relative(k, w) <= BF16_SCAN_TOL, (name, relative(k, w))
        assert relative(k, w) <= 1.5 * relative(d, w) + 1e-3, name


def scan_shapes(h, p, n, groups, t=4096, dtype=jnp.bfloat16):
    bc = (1, t, n) if groups is None else (1, t, groups, n)
    return (jax.ShapeDtypeStruct((1, t, h, p), dtype),
            jax.ShapeDtypeStruct(bc, dtype))


#: name -> heads, head width, state, groups, HVD_PALLAS, the path: the two
#: published shapes (granite-4.0-h-micro, Nemotron-3-Super) on and off the
#: chip's mode, and what the gate refuses
PATH_CASES = {
    "granite": (64, 64, 128, None, "on", "pallas"),
    "nemotron_h": (128, 64, 128, 8, "on", "pallas"),
    "eight_heads_a_group": (64, 64, 128, 8, "on", "pallas"),
    "granite_interpreted": (64, 64, 128, None, "interpret", "pallas"),
    "granite_kernels_off": (64, 64, 128, None, "0", "reference"),
    "nemotron_h_kernels_off": (128, 64, 128, 8, "0", "reference"),
    "granite_off_the_chip": (64, 64, 128, None, "", "reference"),
    "four_heads_a_group": (32, 64, 128, 8, "on", "reference"),
    "a_state_of_16": (64, 64, 16, None, "on", "reference"),
    "a_head_of_48": (64, 48, 128, None, "on", "reference"),
    "a_head_of_8": (64, 8, 128, None, "on", "reference"),
    "heads_the_groups_do_not_divide": (64, 64, 128, 3, "on", "reference"),
}


@pytest.mark.parametrize("case", sorted(PATH_CASES))
def test_kernel_path_says_which_scan_runs(case, monkeypatch):
    h, p, n, groups, env, path = PATH_CASES[case]
    monkeypatch.setenv("HVD_PALLAS", env)
    assert pk.kernel_path("ssd_scan", *scan_shapes(h, p, n, groups)) == path
    route = pk.ssd_route(4096, h, p, n, groups or 1)
    assert (route["path"] == "pallas") == (path == "pallas" or env in ("0", ""))
    if route["path"] == "pallas":
        assert route["tile"] == 256
        assert route["heads"] == (8 if case == "eight_heads_a_group" else 16)
    # float64 operands (the suite enables x64) are nobody's kernel
    assert pk.kernel_path("ssd_scan", *scan_shapes(
        h, p, n, groups, dtype=jnp.float64)) == "reference"


def test_one_group_is_todays_scan_to_the_bit():
    """``B`` and ``C`` ``[b, T, N]`` take the path they always took, whose
    code did not move; one group given as ``[b, T, 1, N]`` is the same
    numbers."""
    x, dt, A, B, C, D = scan_operands(None)
    flat = ssd.ssd_chunked(x, dt, A, B, C, D, chunk=8)
    one = ssd.ssd_chunked(x, dt, A, B[:, :, None], C[:, :, None], D, chunk=8)
    assert np.array_equal(np.asarray(flat), np.asarray(one))
    with jax.default_matmul_precision("highest"):
        assert relative(ssd.ssd_chunked(x, dt, A, B, C, D, chunk=8),
                        reference.recurrence(x, dt, A, B[:, :, None],
                                             C[:, :, None], D)) <= 1e-5


def test_the_gated_norm_over_groups_is_a_norm_a_group():
    y, gate = (jax.random.normal(k, (2, 5, 24))
               for k in jax.random.split(jax.random.PRNGKey(0)))
    scale = jnp.linspace(0.5, 1.5, 24)
    got = ssd.gated_rms_norm(y, gate, scale, 1e-5, groups=3)
    want = jnp.concatenate([ssd.gated_rms_norm(
        y[..., i:i + 8], gate[..., i:i + 8], scale[i:i + 8], 1e-5)
        for i in (0, 8, 16)], axis=-1)
    assert relative(got, want) <= 1e-6
    assert relative(ssd.gated_rms_norm(y, gate, scale, 1e-5), want) > 0.05


# -------------------------- the squared-ReLU stage's hand-written backward
def _stage_operands(held, n=256, d=128, f=128, top_k=2, seed=11):
    """The operands of ``moe._experts`` for 256 tokens over 8 squared-ReLU
    experts, at widths the kernels' route takes (whole lanes; both
    capacities whole row tiles), routed as the layer routes them."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    f32 = jnp.float32       # the suite's default is 64 bits: no kernel's
    h = jax.random.normal(keys[0], (n, d), f32)
    logits = h @ (0.5 * jax.random.normal(keys[1], (d, 8), f32))
    # one expert held, and every token sent to it: past the smaller capacity
    bias = jnp.zeros((8,)).at[jnp.asarray(held)].set(
        10.0 if len(held) == 1 else 0.0)
    chosen, weights, _ = moe.route(logits, bias, top_k, 5.0, 1e-20)
    return (h, 0.2 * jax.random.normal(keys[2], (len(held), d, f), f32),
            0.2 * jax.random.normal(keys[3], (len(held), f, d), f32), weights,
            *moe.dispatch(chosen, held))


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("held,index", [((1, 4), 0), ((4,), 1)],
                         ids=["smaller", "worst-case"])
def test_the_squared_relu_backward_pass_is_autodiff_of_the_ragged_dot_path(
        held, index, mode, monkeypatch):
    """``_experts``' gradients for ``W2 relu(W1 u)^2`` (written out: five
    grouped products, ``d a = 2 relu(a) d act``) in ``h``, ``w_in``,
    ``w_out`` and the routing weights against ``jax.vjp`` of the forward
    stage on the ``ragged_dot`` path, at each of the two capacities, off
    the chip and through the kernels."""
    operands = _stage_operands(held)
    sizes = moe.capacities(256 * 2, len(held), 8)
    here = int(jnp.sum(operands[-1]))
    assert sum(here > s for s in sizes[:-1]) == index, (here, sizes)
    rows = sizes[index]
    dy = jnp.cos(jnp.arange(256 * 128, dtype=jnp.float32).reshape(256, 128))
    with jax.default_matmul_precision("highest"):
        want_y, vjp = jax.vjp(lambda *diff: moe._experts_at(
            rows, *diff, *operands[4:], activation="relu2"), *operands[:4])
        want = vjp(dy)
        monkeypatch.setenv("HVD_PALLAS", mode)
        assert pk.kernel_path("grouped_matmul", operands[0][:1].repeat(
            rows, 0), operands[1]) == ("pallas" if mode == "interpret"
                                       else "reference")
        got_y, vjp = jax.vjp(lambda *diff: moe._experts(
            sizes, *diff, *operands[4:], "relu2"), *operands[:4])
        got = vjp(dy)
    assert relative(got_y, want_y) <= 1e-6
    for name, a, b in zip(("h", "w_in", "w_out", "weights"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert float(jnp.max(jnp.abs(b))) > 0, name
        assert relative(a, b) <= 2e-6, (name, relative(a, b))


@pytest.mark.parametrize("rows,k,n", [(11264, 1024, 2688),
                                      (11264, 2688, 1024),
                                      (90112, 1024, 2688)])
def test_the_published_widths_take_the_kernels(rows, k, n):
    """The cell's products (a latent of 1024, experts 2688 wide; both row
    capacities of ``capacities(4096 * 22, 8, 512)``: an eighth of the worst
    case, not twice the balanced 1,408, which a window's routing crosses)
    are whole lanes and whole row tiles."""
    assert moe.capacities(4096 * 22, 8, 512) == (11264, 90112)
    # where a quarter of the experts is held, twice the balanced rows as ever
    assert moe.capacities(16384 * 4, 8, 32) == (32768, 65536)
    route = pk.grouped_route(rows, k, n, 2)
    assert route["path"] == "pallas", route
    for tile, whole in zip(route["tiling"], (rows, k, n)):
        assert whole % tile == 0


# -------------------------------------------------------------- controls
def test_control_bf16_operands_fail_the_float32_tolerance():
    """The same comparison one precision lower: over F32_TOL by far."""
    logits, _, grads = program_side(32, jnp.bfloat16)
    ref_logits, _, ref_grads = reference_side(32)
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


def test_control_eight_bit_operands_fail_the_chips_check():
    """Every matrix rounded to 4 bits of mantissa (e4m3's) in the program
    only: the blocks' updates leave their limit."""
    params = randomised_params()
    coarse = jax.tree_util.tree_map(
        lambda l: low(l, 4) if l.ndim >= 2 else l, params)
    assert not chip_check(params, tokens(64)[0], program_params=coarse) <= 0.02


def test_control_a_missing_routing_scale_fails_the_chips_check(monkeypatch):
    """A program whose routing weights sum to 1, not to 5: what
    ``family.program_trace`` runs is the program, so it is patched too."""
    sound = family.build_model
    monkeypatch.setattr(family, "build_model", lambda *a: sound(*a).clone(
        moe_scale=1.0))
    unscaled = family.build_model(CONFIG, ROWS, {}).clone(dtype=jnp.bfloat16)
    assert not chip_check(randomised_params(), tokens(64)[0],
                          program=unscaled) <= 0.02


def test_control_a_gated_norm_over_one_group_fails_the_chips_check(
        monkeypatch):
    """A program whose gated norm takes one mean square over the whole
    inner width where the model has one a group."""
    sound = ssd.gated_rms_norm
    monkeypatch.setattr(ssd, "gated_rms_norm",
                        lambda y, gate, scale, eps, groups=1, norm_first=False:
                        sound(y, gate, scale, eps))
    assert not chip_check(randomised_params(), tokens(64)[0]) <= 0.02


def test_the_reference_refuses_a_program_that_routes_outside_tau(monkeypatch):
    """``family.reference_forward`` returns NaN logits, which no comparison
    passes, for a program whose selection bias is not the reference's."""
    params, toks = randomised_params(), tokens(32)[0]
    assert bool(jnp.all(jnp.isfinite(
        family.reference_forward(params, toks, CONFIG))))
    sound = family.program_trace

    def without_the_bias(p, t, c):
        return sound(jax.tree_util.tree_map_with_path(
            lambda path, l: jnp.zeros_like(l) if "expert_bias" in
            jax.tree_util.keystr(path) else l, p), t, c)

    monkeypatch.setattr(family, "program_trace", without_the_bias)
    assert bool(jnp.all(jnp.isnan(
        family.reference_forward(params, toks, CONFIG))))


# ---------------------------------------------------- remat, training, load
def test_remat_modes_agree():
    params, (toks, targets) = randomised_params(), tokens(32)

    def loss_and_grads(remat):
        m = model(remat=remat)
        return jax.jit(jax.value_and_grad(lambda p: lm_loss(
            m.apply({"params": p}, toks), targets)))(params)

    base_loss, base_grads = loss_and_grads("none")
    for remat in ("full", "dots"):
        loss, grads = loss_and_grads(remat)
        assert abs(float(loss) - float(base_loss)) <= 1e-6 * float(base_loss)
        assert worst_leaf(grads, base_grads)[1] <= 1e-5, remat


def test_three_train_steps_on_the_mesh_reproduce_the_reference_losses():
    """``spmd.make_train_step`` + ``lm_loss`` + the job's AdamW as
    ``chipbench/jobs/train_lm.build`` calls them, batch 8 over the 8-device
    mesh, against ``jax.value_and_grad`` of ``lm_loss`` over the reference
    under the same optimizer."""
    hvd.init()
    mesh = hvd.mesh()
    m = model()
    params, batch = randomised_params(), tokens(32, batch=8, seed=7)

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


def test_the_sown_load_sets_the_moe_gauges():
    from horovod_tpu.metrics import instruments

    m, params = model(), randomised_params()
    _, state = m.apply({"params": params}, tokens(32)[0],
                       mutable=["intermediates"])
    sown = state["intermediates"]["block_4"]["ffn"]
    assert sown["chosen"][0].shape == (2, 32, 3)
    assert sown["scores"][0].shape == (2, 32, 8)
    load = np.asarray(sown["load"][0])
    assert load.sum() == 2 * 32 * 3
    imbalance = moe.report_load(load, CONFIG["held_experts"])
    here = load[CONFIG["held_experts"]]
    assert imbalance == pytest.approx(here.max() / here.mean())
    assert instruments.moe_load_imbalance().value == pytest.approx(imbalance)
    assert instruments.expert_load().labels(expert="6").value == load[6]


def test_unknown_kinds_and_a_block_of_nothing_are_refused():
    toks = tokens(8)[0]
    for change, match in (
            ({"ffn_kinds": ("none",) * 5}, "ffn_kinds"),     # block_1: nothing
            ({"ffn_kinds": ("none", "moe", "none", "none", "dense")},
             "ffn_kinds"),
            ({"layer_kinds": ("mamba", "none", "mamba", "rwkv", "none")},
             "layer_kinds"),
            ({"moe_activation": "gelu"}, "moe_activation")):
        with pytest.raises(ValueError, match=match):
            model().clone(**change).init(jax.random.PRNGKey(0), toks)


def test_the_family_counts_what_the_issue_counted():
    """The published widths: 1,211 M parameters here, 0.93 G matrix
    elements a token, the rooflines' operations."""
    from chipbench import harness

    config = harness.load_json(
        "configs", "NVIDIA-Nemotron-3-Super-120B-A12B-BF16.json")
    m = family.build_model(config, 16384, {"remat": "full"})
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))["params"]
    count = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    assert count == 1_210_931_584
    assert shapes["block_1"]["ffn"]["w_in"].shape == (8, 1024, 2688)
    assert shapes["block_0"]["mixer"]["in_proj"]["kernel"].shape == (4096, 18560)
    flops = family.train_flops_per_token(config, 16384, 4096)
    assert flops == pytest.approx(6 * 0.9335e9 + 6 * 4096 * 4096
                                  + 5 * 15 * 128 * 64 * 128, rel=1e-3)
    assert family.expected_first_loss(config, 16384) == pytest.approx(10.523,
                                                                      abs=1e-3)
    ssd_cost, = set(map(lambda c: tuple(sorted(c.items())),
                        family.ssd_train_costs(config, 1, 4096)))
    assert dict(ssd_cost)["flops"] == 15.0 * 4096 * 128 * 64 * 128
    moe_costs = family.moe_train_costs(config, 1, 4096)
    assert len(moe_costs) == 5
    assert moe_costs[0]["flops"] == 12.0 * 1408 * 1024 * 2688
    assert len(family.attention_train_costs(config, 1, 4096)) == 1


# ------------------------------------------------ LFM2: nothing of it moved
def test_lfm2s_parameter_tree_and_scope_paths_are_unchanged():
    """The routed layer's other user, built as its family builds it: the
    same parameter tree (names and shapes) and the same scope paths in the
    compiled program as before the new kinds (PR 32's and 33's)."""
    from chipbench import harness
    from chipbench.families import lfm2_moe

    config = {**harness.load_json("configs", "LFM2-8B-A1B.json"),
              **lfm2_moe.REHEARSAL}
    m = lfm2_moe.build_model(config, 512, {"remat": "full"})
    toks = jnp.zeros((1, 32), jnp.int32)
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0), toks)["params"]
    shapes = jax.tree_util.tree_map(lambda l: l.shape, params)
    assert set(shapes) == {"tok_emb", "norm_f"} | {f"block_{i}"
                                                   for i in range(5)}
    assert shapes["block_0"] == {
        "norm_mixer": {"scale": (128,)}, "norm_ffn": {"scale": (128,)},
        "ffn_in": {"kernel": (128, 512)}, "ffn_out": {"kernel": (256, 128)},
        "mixer": {"in_proj": {"kernel": (128, 384)},
                  "short_conv": {"kernel": (3, 128)},
                  "out_proj": {"kernel": (128, 128)}}}
    assert shapes["block_1"] == {
        "norm_mixer": {"scale": (128,)}, "norm_ffn": {"scale": (128,)},
        "mixer": {"q": {"kernel": (128, 128)}, "k": {"kernel": (128, 64)},
                  "v": {"kernel": (128, 64)}, "o": {"kernel": (128, 128)},
                  "q_norm": (64,), "k_norm": (64,)},
        "ffn": {"router": (128, 8), "expert_bias": (8,),
                "w_in": (2, 128, 128), "w_out": (2, 64, 128)}}
    text = jax.jit(jax.grad(lambda p: jnp.sum(m.apply({"params": p}, toks)))
                   ).lower(params).as_text(debug_info=True)
    for scope in ("block_0/mixer/in_proj", "block_0/mixer/short_conv",
                  "block_0/mixer/out_proj", "block_0/ffn_in",
                  "block_0/ffn_out", "block_1/mixer/q", "block_1/mixer/qk_norm",
                  "block_1/mixer/rope", "block_1/ffn/moe/router",
                  "block_1/ffn/moe/dispatch", "experts", "combine",
                  "block_1/norm_mixer", "block_1/norm_ffn", "tok_emb.attend",
                  "norm_f", "rematted_computation/block_4"):
        assert scope in text, scope
    for scope in ("mixer/ssd", "ffn/latent_in", "ffn/latent_out",
                  "ffn/shared_in", "ffn/shared_out", "lm_head"):
        assert scope not in text, scope
    fields = hybrid.HybridLM.__dataclass_fields__
    assert (fields["tied_head"].default, fields["ssm_groups"].default,
            fields["moe_activation"].default, fields["moe_latent"].default,
            fields["moe_shared_width"].default, fields["moe_scale"].default
            ) == (True, 1, "swiglu", 0, 0, 1.0)
