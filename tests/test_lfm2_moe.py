"""``models/hybrid.HybridLM`` as the LFM2 mixture-of-experts family builds it
(gated short-conv and RoPE attention mixers, a dense and a routed
feed-forward) against its plain reference,
``chipbench/reference_lfm2_moe.py``, and ``ops/moe.py`` against loops.

Small size, seeded weights with the norm weights, the conv kernels and the
routers' selection bias randomised: at their initial values (every norm
weight 1, bias 0) a dropped weight hides. The model holds 3 of 8 experts
(ids 1, 4, 6: not a prefix, so a mix-up of ids and positions shows).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from chipbench import reference_lfm2_moe as reference
from chipbench.families import lfm2_moe as family
from horovod_tpu import spmd
from horovod_tpu.models import hybrid
from horovod_tpu.models.transformer import lm_loss
from horovod_tpu.ops import moe, pallas_kernels as pk

#: the configuration keys the family and the reference read, small
CONFIG = {"num_hidden_layers": 5,
          "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
          "num_dense_layers": 1, "hidden_size": 128, "intermediate_size": 256,
          "moe_intermediate_size": 64, "num_attention_heads": 4,
          "num_key_value_heads": 2, "num_experts": 3,
          "num_experts_published": 8, "held_experts": [1, 4, 6],
          "num_experts_per_tok": 2, "conv_L_cache": 3, "norm_eps": 1e-5,
          "rope_theta": 1000000, "vocab_size": 512,
          "assumed": {"tie_tau": {"value": 1e-3}}}
ROWS = 512

#: float32 program against float32 reference: both round at 2^-24 and
#: differ in the order of their sums (grouped rows against masked experts,
#: flash tiles against one softmax). Measured 4e-7 in the logits and 6e-6
#: in the worst gradient leaf; bf16 operands read 5e-3 and more: the control.
F32_TOL = 2e-5


def model(dtype=jnp.float32, remat="none", config=CONFIG):
    return family.build_model(config, ROWS, {"remat": remat}).clone(dtype=dtype)


def tokens(seq, batch=2, seed=0):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                              ROWS, dtype=jnp.int32)
    return toks[:, :-1], toks[:, 1:]


def randomised_params(seed=1, config=CONFIG):
    params = model(config=config).init(jax.random.PRNGKey(seed),
                                       tokens(32)[0])["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))

    def shake(path, leaf, key):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:       # half the scores' spread (0.05)
            return 0.03 * jax.random.normal(key, leaf.shape, leaf.dtype)
        if leaf.ndim >= 2 and "short_conv" not in name:
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


@pytest.mark.parametrize("seq", [32, 64])
def test_logits_loss_and_every_gradient_match_the_reference(seq):
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
    for block in (f"block_{i}" for i in range(1, 5)):
        assert not np.any(np.asarray(grads[block]["ffn"]["expert_bias"]))


def test_the_parameter_tree_is_the_published_layers():
    shapes = jax.tree_util.tree_map(lambda l: l.shape, randomised_params())
    assert set(shapes["block_0"]) == {"norm_mixer", "mixer", "norm_ffn",
                                      "ffn_in", "ffn_out"}
    assert shapes["block_0"]["mixer"] == {
        "in_proj": {"kernel": (128, 384)}, "short_conv": {"kernel": (3, 128)},
        "out_proj": {"kernel": (128, 128)}}
    assert shapes["block_1"]["mixer"] == {
        "q": {"kernel": (128, 128)}, "k": {"kernel": (128, 64)},
        "v": {"kernel": (128, 64)}, "o": {"kernel": (128, 128)},
        "q_norm": (32,), "k_norm": (32,)}
    assert shapes["block_1"]["ffn"] == {
        "router": (128, 8), "expert_bias": (8,), "w_in": (3, 128, 128),
        "w_out": (3, 64, 128)}
    assert "pos_emb" not in shapes and shapes["tok_emb"] == {
        "embedding": (512, 128)}


# ------------------------------------------------------------ the shares
def layer_operands(seed=3, n=96, d=32, f=16, experts=8):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"h": jax.random.normal(keys[0], (n, d)),
            "router": 0.5 * jax.random.normal(keys[1], (d, experts)),
            "bias": 0.1 * jax.random.normal(keys[2], (experts,)),
            "w_in": 0.2 * jax.random.normal(keys[3], (experts, d, 2 * f)),
            "w_out": 0.2 * jax.random.normal(keys[4], (experts, f, d))}


def routed(ops, held, top_k=2):
    held = tuple(held)
    index = jnp.asarray(held)
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda o: moe.routed_ffn(
            o["h"], o["router"], o["bias"], o["w_in"][index],
            o["w_out"][index], held=held, top_k=top_k))(ops)


def reference_layer(ops, held, top_k=2):
    p = {"router": ops["router"], "expert_bias": ops["bias"],
         "w_in": ops["w_in"][jnp.asarray(held)],
         "w_out": ops["w_out"][jnp.asarray(held)]}
    with jax.default_matmul_precision("highest"):
        return reference._routed(p, ops["h"][None], tuple(held), top_k, None,
                                 0.0)[0][0]


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Each of four chips holds two of the eight experts; what the four
    compute, summed, is what the reference gives holding all eight (there is
    nothing every chip computes alike: no shared expert)."""
    ops = layer_operands()
    shares = [(0, 1), (2, 3), (4, 5), (6, 7)]
    parts = [routed(ops, held) for held in shares]
    whole = reference_layer(ops, range(8))
    assert relative(sum(p[0] for p in parts), whole) <= 1e-6
    # every share routes over all eight alike, and the reference's shares
    # add up too
    for part in parts[1:]:
        assert np.array_equal(part[1], parts[0][1])
        assert np.array_equal(part[3], parts[0][3])
    assert int(parts[0][3].sum()) == 96 * 2
    assert relative(sum(reference_layer(ops, held) for held in shares),
                    whole) <= 1e-6
    # and one share alone is not the layer
    assert relative(parts[0][0], whole) > 0.1


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    """All 96 tokens choose experts 1 and 4, both held: twice the balanced
    load of the whole layer on two experts, the worst-case capacity."""
    ops = layer_operands()
    ops["bias"] = jnp.zeros(8).at[jnp.asarray([1, 4])].set(10.0)
    y, chosen, _, load = routed(ops, (1, 4, 6))
    assert np.array_equal(np.sort(chosen, axis=-1),
                          np.tile([1, 4], (96, 1)))
    assert list(np.asarray(load)) == [0, 96, 0, 0, 96, 0, 0, 0]
    want = reference_layer(ops, (1, 4, 6))
    assert relative(y, want) <= 1e-6
    assert float(jnp.min(jnp.linalg.norm(want, axis=-1))) > 0
    # the rows here (192) are past the smaller capacity
    assert moe.capacities(192, 3, 8) == (144, 192)


@pytest.mark.parametrize("bias,size", [(-10.0, 0), (0.0, 0), (10.0, 1)],
                         ids=["none-here", "balanced", "all-here"])
def test_every_capacity_gives_the_reference_and_its_gradients(bias, size):
    """The expert stage is compiled at two row counts and a step runs the
    smaller if it holds its rows: the held experts' bias pushes the routing
    into each, and to no row at all."""
    ops, held = layer_operands(), (1, 4, 6)
    ops["bias"] = ops["bias"].at[jnp.asarray(held)].add(bias)
    sizes = moe.capacities(96 * 2, 3, 8)
    rows = int(np.asarray(routed(ops, held)[3])[list(held)].sum())
    assert sum(rows > s for s in sizes[:-1]) == size, (rows, sizes)

    def loss(layer):
        return lambda o: jnp.sum(jnp.sin(layer(o, held)))

    got = jax.grad(loss(lambda o, h: routed(o, h)[0]))(ops)
    want = jax.grad(loss(reference_layer))(ops)
    assert relative(routed(ops, held)[0], reference_layer(ops, held)) <= 1e-6 \
        or rows == 0
    for name in ("h", "router", "w_in", "w_out"):
        scale = max(float(jnp.max(jnp.abs(want[name]))), 1e-30)
        assert float(jnp.max(jnp.abs(got[name] - want[name]))) <= 1e-5 * max(
            scale, 1e-3), name
    assert not np.any(np.asarray(got["bias"]))


def test_rows_of_no_group_reach_no_result_and_no_gradient(monkeypatch):
    """On the chip a grouped product leaves the rows past its groups as they
    were (uninitialised memory), in its result and in the gradient of its
    row operand; here they are NaN, at a capacity with rows to spare."""
    sound = moe.grouped_matmul

    def spoil(out, sizes):
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(sizes), out, jnp.nan)

    @jax.custom_vjp
    def spoiled(lhs, rhs, sizes):
        return spoil(sound(lhs, rhs, sizes), sizes)

    def forward(lhs, rhs, sizes):
        return spoiled(lhs, rhs, sizes), (lhs, rhs, sizes)

    def backward(res, g):
        lhs, rhs, sizes = res
        d_lhs, d_rhs = jax.vjp(lambda a, b: sound(a, b, sizes), lhs, rhs)[1](
            jnp.where(jnp.isnan(spoil(g, sizes)), 0, g))
        return spoil(d_lhs, sizes), d_rhs, None

    spoiled.defvjp(forward, backward)
    monkeypatch.setattr(moe, "grouped_matmul", spoiled)
    ops, held = layer_operands(), (1, 4, 6)
    rows = int(np.asarray(routed(ops, held)[3])[list(held)].sum())
    runs_at = min(c for c in moe.capacities(96 * 2, 3, 8) if c >= rows)
    assert 0 < rows < runs_at

    def loss(layer):
        return lambda o: jnp.sum(jnp.sin(layer(o, held)))

    assert relative(routed(ops, held)[0], reference_layer(ops, held)) <= 1e-6
    got = jax.grad(loss(lambda o, h: routed(o, h)[0]))(ops)
    monkeypatch.setattr(moe, "grouped_matmul", sound)
    want = jax.grad(loss(reference_layer))(ops)
    for name in ("h", "router", "w_in", "w_out", "bias"):
        assert bool(jnp.all(jnp.isfinite(got[name]))), name
        assert float(jnp.max(jnp.abs(got[name] - want[name]))) <= 1e-5, name


def test_grouped_matmul_is_a_loop_over_the_experts_with_an_empty_group():
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    lhs = jax.random.normal(keys[0], (40, 16))
    rhs = jax.random.normal(keys[1], (4, 16, 24))
    sizes = jnp.asarray([7, 0, 20, 5], jnp.int32)      # 32 of 40 rows
    bounds = np.concatenate([[0], np.cumsum(sizes)])

    def loop(lhs, rhs):
        return jnp.concatenate([lhs[bounds[g]:bounds[g + 1]] @ rhs[g]
                                for g in range(4)])

    with jax.default_matmul_precision("highest"):
        got = moe.grouped_matmul(lhs, rhs, sizes)
        assert got.shape == (40, 24)
        assert relative(got[:32], loop(lhs, rhs)) <= 1e-6
        weight = jnp.cos(jnp.arange(32 * 24.0).reshape(32, 24))
        g_got = jax.grad(lambda a, b: jnp.sum(
            moe.grouped_matmul(a, b, sizes)[:32] * weight), (0, 1))(lhs, rhs)
        g_want = jax.grad(lambda a, b: jnp.sum(loop(a, b) * weight),
                          (0, 1))(lhs, rhs)
    assert relative(g_got[0][:32], g_want[0][:32]) <= 1e-6
    assert relative(g_got[1], g_want[1]) <= 1e-6
    assert not np.any(np.asarray(g_got[1][1]))          # the empty group's


# ------------------------------------- the Pallas grouped products (PR 33)
#: rows x K x N tiles: one K step and two, one visit a row tile and edges
_TILINGS = [(128, 256, 128), (256, 128, 384), (512, 128, 128)]
#: 384 of 512 rows in the groups; an empty group first, in the middle (at a
#: tile's edge and inside one) and last
_SIZES = [[0, 100, 0, 156, 28, 0, 100, 0], [128, 0, 0, 256, 0, 0, 0, 0]]


def _grouped_operands(sizes, k=256, n=384):
    """``lhs [512, k]``, the groups' matrices both ways round, a second row
    operand, and the groups' bounds: the rows of no group are NaN."""
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    here = sum(sizes)
    poison = (jnp.arange(512) >= here)[:, None]
    f32 = jnp.float32       # the suite's default is 64 bits: no kernel's
    lhs = jnp.where(poison, jnp.nan, jax.random.normal(keys[0], (512, k), f32))
    other = jnp.where(poison, jnp.nan,
                      jax.random.normal(keys[1], (512, n), f32))
    return (lhs, jax.random.normal(keys[2], (len(sizes), k, n), f32),
            jax.random.normal(keys[3], (len(sizes), n, k), f32), other,
            np.concatenate([[0], np.cumsum(sizes)]))


@pytest.mark.parametrize("sizes", _SIZES, ids=["edges", "aligned"])
@pytest.mark.parametrize("tiling", _TILINGS, ids=str)
@pytest.mark.parametrize("product", ["gmm", "gmm_transposed", "tgmm"])
def test_the_grouped_kernels_are_loops_over_the_experts(product, tiling,
                                                        sizes, monkeypatch):
    """``pallas_kernels.gmm`` / ``tgmm`` through the interpreter against the
    loop over the groups, with empty groups and the rows past the groups
    NaN: none reaches a row of a group or a group's matrix."""
    monkeypatch.setenv("HVD_PALLAS", "interpret")
    lhs, rhs, rhs_t, other, bounds = _grouped_operands(sizes)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    spans = list(zip(bounds[:-1], bounds[1:]))
    with jax.default_matmul_precision("highest"):
        if product == "tgmm":
            got = pk.tgmm(lhs, other, group_sizes, tiling=tiling)
            want = jnp.stack([lhs[a:b].T @ other[a:b] for a, b in spans])
            assert not np.any(np.asarray(got[2]))       # an empty group's
        else:
            flip = product == "gmm_transposed"
            got = pk.gmm(lhs, rhs_t if flip else rhs, group_sizes,
                         tiling=tiling, transpose_rhs=flip)[:bounds[-1]]
            want = jnp.concatenate([
                lhs[a:b] @ (rhs_t[g].T if flip else rhs[g])
                for g, (a, b) in enumerate(spans)])
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.all(jnp.isfinite(got)))
    assert relative(got, want) <= 1e-6


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_the_three_dispatchers_agree_with_the_loop_on_either_path(
        mode, monkeypatch):
    """``grouped_matmul``, ``grouped_matmul_t`` and ``grouped_outer`` off
    the chip (``ragged_dot``) and through the kernels, at a shape the route
    takes."""
    monkeypatch.setenv("HVD_PALLAS", mode)
    sizes = _SIZES[0]
    lhs, rhs, rhs_t, other, bounds = _grouped_operands(sizes)
    assert pk.kernel_path("grouped_matmul", lhs, rhs) == (
        "pallas" if mode == "interpret" else "reference")
    lhs, other = jnp.nan_to_num(lhs), jnp.nan_to_num(other)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    spans = list(zip(bounds[:-1], bounds[1:]))
    with jax.default_matmul_precision("highest"):
        for got, want in [
                (moe.grouped_matmul(lhs, rhs, group_sizes)[:bounds[-1]],
                 jnp.concatenate([lhs[a:b] @ rhs[g]
                                  for g, (a, b) in enumerate(spans)])),
                (moe.grouped_matmul_t(lhs, rhs_t, group_sizes)[:bounds[-1]],
                 jnp.concatenate([lhs[a:b] @ rhs_t[g].T
                                  for g, (a, b) in enumerate(spans)])),
                (moe.grouped_outer(lhs, other, group_sizes),
                 jnp.stack([lhs[a:b].T @ other[a:b] for a, b in spans]))]:
            assert relative(got, want) <= 1e-6


@pytest.mark.parametrize("rows,k,n,itemsize,path,tiling", [
    (32768, 2048, 3584, 2, "pallas", (256, 2048, 896)),    # x W1, the cell's
    (65536, 3584, 2048, 2, "pallas", (256, 3584, 1024)),   # dGU W1^T
    (384, 256, 128, 4, "pallas", (128, 256, 128)),
    (144, 256, 128, 4, "reference", None),      # rows no row tile divides
    (512, 64, 128, 2, "reference", None),       # K not whole lanes
    (512, 128, 192, 2, "reference", None),      # N not whole lanes
    (512, 128, 128, 1, "reference", None),      # 8-bit operands
])
def test_grouped_route_is_what_the_dispatchers_follow(rows, k, n, itemsize,
                                                      path, tiling,
                                                      monkeypatch):
    route = pk.grouped_route(rows, k, n, itemsize)
    assert (route["path"], route["tiling"]) == (path, tiling)
    if path == "pallas":
        for tile, whole in zip(route["outer_tiling"], (rows, k, n)):
            assert whole % tile == 0
    dtype = {1: jnp.int8, 2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    lhs = jax.ShapeDtypeStruct((rows, k), dtype)
    operands = {"grouped_matmul": jax.ShapeDtypeStruct((8, k, n), dtype),
                "grouped_matmul_t": jax.ShapeDtypeStruct((8, n, k), dtype),
                "grouped_outer": jax.ShapeDtypeStruct((rows, n), dtype)}
    for name, rhs in operands.items():
        # off the chip (this run) the reference whatever the shape; with the
        # kernels on, what the route says
        assert pk.kernel_path(name, lhs, rhs) == "reference"
        monkeypatch.setenv("HVD_PALLAS", "interpret")
        assert pk.kernel_path(name, lhs, rhs) == path
        monkeypatch.delenv("HVD_PALLAS")
    # operands of two dtypes: the reference, which promotes them
    assert pk.kernel_path(
        "grouped_matmul", jax.ShapeDtypeStruct((512, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)) == "reference"


def _stage_operands(held, n=256, d=128, f=128, top_k=2, seed=11):
    """The operands of ``moe._experts`` for 256 tokens over 8 experts, at
    widths the kernels' route takes (whole lanes; both capacities whole row
    tiles), routed as the layer routes them."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    f32 = jnp.float32       # the suite's default is 64 bits: no kernel's
    h = jax.random.normal(keys[0], (n, d), f32)
    logits = h @ (0.5 * jax.random.normal(keys[1], (d, 8), f32))
    # one expert held, and every token sent to it: past the smaller capacity
    bias = jnp.zeros((8,)).at[jnp.asarray(held)].set(
        10.0 if len(held) == 1 else 0.0)
    chosen, weights, _ = moe.route(logits, bias, top_k)
    return (h, 0.2 * jax.random.normal(keys[2], (len(held), d, 2 * f), f32),
            0.2 * jax.random.normal(keys[3], (len(held), f, d), f32), weights,
            *moe.dispatch(chosen, held))


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("held,index", [((1, 4), 0), ((4,), 1)],
                         ids=["smaller", "worst-case"])
def test_the_hand_written_backward_pass_is_autodiff_of_the_ragged_dot_path(
        held, index, mode, monkeypatch):
    """``_experts``' gradients (the layer's ``custom_vjp``: five grouped
    products, the second forward product not run again) in ``h``, ``w_in``,
    ``w_out`` and the routing weights against ``jax.vjp`` of the forward
    stage on the ``ragged_dot`` path, at each of the two capacities, off the
    chip and through the kernels."""
    operands = _stage_operands(held)
    sizes = moe.capacities(256 * 2, len(held), 8)
    # an eighth held: log2(8) = 3 times the balanced 64 rows (capacities)
    assert sizes == ((256, 512), (192, 512))[index]
    here = int(jnp.sum(operands[-1]))
    assert sum(here > s for s in sizes[:-1]) == index, (here, sizes)
    rows = sizes[index]
    dy = jnp.cos(jnp.arange(256 * 128, dtype=jnp.float32).reshape(256, 128))
    with jax.default_matmul_precision("highest"):
        want_y, vjp = jax.vjp(lambda *diff: moe._experts_at(
            rows, *diff, *operands[4:]), *operands[:4])
        want = vjp(dy)
        monkeypatch.setenv("HVD_PALLAS", mode)
        assert pk.kernel_path("grouped_matmul", operands[0][:1].repeat(
            rows, 0), operands[1]) == ("pallas" if mode == "interpret"
                                       else "reference")
        got_y, vjp = jax.vjp(lambda *diff: moe._experts(
            sizes, *diff, *operands[4:]), *operands[:4])
        got = vjp(dy)
    assert relative(got_y, want_y) <= 1e-6
    for name, a, b in zip(("h", "w_in", "w_out", "weights"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert float(jnp.max(jnp.abs(b))) > 0, name
        assert relative(a, b) <= 2e-6, (name, relative(a, b))


def test_the_backward_pass_reads_no_row_of_no_group(monkeypatch):
    """Every one of the stage's seven products leaves the rows past its
    groups as they were; here each returns them NaN (and the two
    weight-gradient products are handed NaN there), at a capacity with rows
    to spare: no gradient sees one."""
    sound = {name: getattr(moe, name) for name in
             ("grouped_matmul", "grouped_matmul_t", "grouped_outer")}

    def spoil(out, sizes):
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(sizes), out, jnp.nan)

    ops, held = layer_operands(), (1, 4, 6)
    want = jax.grad(lambda o: jnp.sum(jnp.sin(reference_layer(o, held))))(ops)
    for name in ("grouped_matmul", "grouped_matmul_t"):
        monkeypatch.setattr(moe, name, lambda lhs, rhs, sizes, name=name:
                            spoil(sound[name](lhs, rhs, sizes), sizes))
    monkeypatch.setattr(moe, "grouped_outer", lambda lhs, rhs, sizes: sound[
        "grouped_outer"](spoil(lhs, sizes), spoil(rhs, sizes), sizes))
    got = jax.grad(lambda o: jnp.sum(jnp.sin(routed(o, held)[0])))(ops)
    for name in ("h", "router", "w_in", "w_out", "bias"):
        assert bool(jnp.all(jnp.isfinite(got[name]))), name
        assert float(jnp.max(jnp.abs(got[name] - want[name]))) <= 1e-5, name


def _chosen(tokens, experts, top_k, seed=0):
    """``[tokens, top_k]`` distinct experts a token, drawn evenly."""
    rs = np.random.RandomState(seed)
    return np.stack([rs.permutation(experts)[:top_k] for _ in range(tokens)])


def _every_token_elsewhere_but(tokens, experts, top_k, held, here):
    """Tokens ``here`` choose ``held`` first (all ``top_k`` of theirs where
    there are that many), every other token none of them."""
    away = [e for e in range(experts) if e not in held]
    chosen = np.stack([np.random.RandomState(t).permutation(away)[:top_k]
                       for t in range(tokens)])
    for t in here:
        chosen[t, :min(top_k, len(held))] = held[:top_k]
    return chosen


# name -> (chosen [N, k], experts, held, row capacity or None for N k)
_ROWS_CASES = {
    "k1": (_chosen(300, 8, 1), 8, (1, 4), 128),
    "k4": (_chosen(300, 32, 4), 32, tuple(range(8)), 512),
    "k10": (_chosen(256, 64, 10), 64, (0, 1, 2, 3), 256),
    "k22": (_chosen(128, 64, 22), 64, tuple(range(8)), 512),
    # tokens 5-9 have no row here, token 3 all four of its
    "a_token_with_none_and_one_with_all_k": (
        _every_token_elsewhere_but(40, 16, 4, (2, 3, 5, 7), [3, 11, 12]),
        16, (2, 3, 5, 7), 16),
    # 384 tokens are three tiles of 128: every row in the second; the
    # first and the third are empty
    "every_row_in_one_token_tile": (
        _every_token_elsewhere_but(384, 16, 2, (0, 9), range(130, 250)),
        16, (0, 9), 256),
    "an_empty_token_tile": (
        _every_token_elsewhere_but(
            512, 16, 2, (4,), [*range(0, 128, 3), *range(300, 512, 2)]),
        16, (4,), 256),
    "no_row_at_all": (
        _every_token_elsewhere_but(256, 16, 2, (4,), []), 16, (4,), 128),
    # the layer held whole: R = N k, every row in a group
    "layer_held_whole": (_chosen(256, 8, 2), 8, tuple(range(8)), None),
    "worst_case_capacity_with_rows_to_spare": (
        _chosen(200, 16, 4), 16, (1, 2), None),
    # a token count no tile divides, and a capacity no row tile divides
    "tokens_no_tile_divides": (_chosen(203, 16, 3), 16, (0, 5, 6), 200),
}


@pytest.mark.parametrize("mode", ["off", "interpret"])
@pytest.mark.parametrize("case", sorted(_ROWS_CASES))
def test_take_and_put_rows_are_each_others_transposes(case, mode,
                                                      monkeypatch):
    """``put_rows`` (the segment sum over the rows in token order) against
    its plain definition, ``sum_j padded[slots[:, j]]`` in float32, with the
    rows past the groups NaN; ``take_rows`` against ``x[token]``; each the
    other's transpose, and their gradients autodiff's of the plain
    definitions. Off the chip (``ragged_dot_general``) and through the
    kernel where the route takes the shape."""
    monkeypatch.setenv("HVD_PALLAS", mode)
    chosen, experts, held, capacity = _ROWS_CASES[case]
    (tokens, top_k), width = chosen.shape, 128
    order, per_token, group_sizes = moe.dispatch(
        jnp.asarray(chosen, jnp.int32), held)
    rows = capacity or tokens * top_k
    here = int(jnp.sum(group_sizes))
    assert here <= rows
    _, token, valid, back = moe._rows_at(rows, top_k, order, per_token,
                                         group_sizes)
    # the sorted row of each of a token's assignments, ``rows`` where it has
    # none here: the plain definition's index, which the stage never builds
    inverse = np.empty(tokens * top_k, np.int64)
    inverse[np.asarray(order)] = np.arange(tokens * top_k)
    slots = np.where(inverse < here, inverse, rows).reshape(tokens, top_k)
    held_by = np.asarray(back[1])
    assert held_by.sum() == here and held_by.shape == (tokens,)
    if case == "a_token_with_none_and_one_with_all_k":
        assert held_by[3] == top_k and not held_by[5:10].any()
    if case in ("every_row_in_one_token_tile", "an_empty_token_tile"):
        assert 0 in held_by.reshape(-1, 128).sum(axis=1)
    if case == "layer_held_whole":
        assert here == rows == tokens * top_k
    x = jax.random.normal(jax.random.PRNGKey(0), (tokens, width), jnp.float32)
    r = jax.random.normal(jax.random.PRNGKey(1), (rows, width), jnp.float32)
    path = pk.kernel_path("grouped_outer", jnp.zeros((rows, 128), r.dtype), r)
    assert path == ("pallas" if mode == "interpret" and rows % 128 == 0
                    else "reference")

    def plain_put(r):
        padded = jnp.concatenate([jnp.where(valid, r, 0),
                                  jnp.zeros((1, width), r.dtype)])
        return sum(padded[slots[:, j]].astype(jnp.float32)
                   for j in range(top_k)).astype(r.dtype)

    def plain_take(x):
        return jnp.where(valid, x[token], 0)

    poisoned = jnp.where(valid, r, jnp.nan)
    taken = moe.take_rows(x, token, back)
    put = moe.put_rows(poisoned, token, back)
    assert np.array_equal(taken, x[token])
    assert put.shape == x.shape and put.dtype == r.dtype
    assert bool(jnp.all(jnp.isfinite(put)))
    want = plain_put(r)
    assert float(jnp.max(jnp.abs(put - want))) <= 1e-5
    assert not np.any(np.asarray(put)[held_by == 0])
    # <take(x), r> = <x, put(r)> over the rows here, and autodiff of the
    # plain definitions gives the same as the pair's own rules
    wide = [np.asarray(a, np.float64) for a in (plain_take(x), r, x, put)]
    assert abs(np.vdot(*wide[:2]) - np.vdot(*wide[2:])) <= 1e-4
    d_x = jax.grad(lambda x: jnp.vdot(moe.take_rows(x, token, back),
                                      poisoned))(x)
    assert float(jnp.max(jnp.abs(d_x - jax.grad(
        lambda x: jnp.vdot(plain_take(x), r))(x)))) <= 1e-5
    d_r = jax.grad(lambda r: jnp.vdot(moe.put_rows(
        jnp.where(valid, r, 0), token, back), x))(r)
    assert float(jnp.max(jnp.abs(d_r - jax.grad(
        lambda r: jnp.vdot(plain_put(r), x))(r)))) <= 1e-5
    # in bfloat16: float32 sums rounded once, as the plain definition's
    low = moe.put_rows(poisoned.astype(jnp.bfloat16), token, back)
    assert low.dtype == jnp.bfloat16
    assert np.array_equal(low, plain_put(r.astype(jnp.bfloat16)))


# -------------------------------------------------- the two controls
def test_control_bf16_operands_fail_the_float32_tolerance():
    """The same comparison one precision lower: over F32_TOL by far, and
    (its ties followed) inside what the chip's check allows a bf16 program
    (chipbench/jobs/train_lm.LOGIT_RMS_TOL)."""
    logits, _, grads = program_side(32, jnp.bfloat16)
    ref_logits, _, ref_grads = reference_side(32)
    assert relative(logits, ref_logits) > 10 * F32_TOL
    assert worst_leaf(grads, ref_grads)[1] > 10 * F32_TOL


def low(x, bits=8):
    """``x`` rounded to ``bits`` bits of mantissa."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 2.0 ** bits) / 2.0 ** bits, e)


def chip_check(params, toks, config=CONFIG, program_params=None):
    """What ``jobs/train_lm.check_logits`` computes for this family: the
    program's bf16 logits against ``family.reference_forward``."""
    m = model(jnp.bfloat16, config=config)
    got = m.apply({"params": params if program_params is None
                   else program_params}, toks)
    want = family.reference_forward(params, toks, config)
    return relative(got, want) if bool(jnp.all(jnp.isfinite(want))) \
        else float("nan")


def test_the_chips_check_passes_a_sound_bf16_program():
    assert chip_check(randomised_params(), tokens(64)[0]) <= 0.02


def test_control_eight_bit_operands_fail_the_chips_check():
    """Every matrix rounded to 8 bits of mantissa in the program only: the
    routing leaves tau and the logits leave the 2%."""
    params = randomised_params()
    coarse = jax.tree_util.tree_map(
        lambda l: low(l, 4) if l.ndim >= 2 else l, params)
    error = chip_check(params, tokens(64)[0], program_params=coarse)
    assert not error <= 0.02


def test_control_weights_not_renormalised_fail_the_chips_check(monkeypatch):
    """A program whose combine weights are the raw scores, not divided by
    the chosen four's sum."""
    sound = moe.route

    def unnormalised(logits, bias, top_k):
        chosen, _, scores = sound(logits, bias, top_k)
        return chosen, jnp.take_along_axis(scores, chosen, axis=-1), scores

    monkeypatch.setattr(moe, "route", unnormalised)
    error = chip_check(randomised_params(), tokens(64)[0])
    assert not error <= 0.02


# ------------------------------------------------------------ the tie rule
def test_the_tie_rule_accepts_a_swap_inside_tau_and_refuses_one_outside():
    tau = 0.01
    #            0     1     2      3      4     5
    values = jnp.asarray([[0.9, 0.8, 0.502, 0.498, 0.3, 0.1]] * 4)
    own = [True, True, True, False, False, False]
    program = jnp.asarray([[0, 1, 2],      # the reference's own choice
                           [0, 1, 3],      # 2 <-> 3: both within tau
                           [0, 1, 4],      # 2 <-> 4: expert 4 is far outside
                           [0, 3, 2]])     # 1 <-> 3: expert 1 is far outside
    use, stats = reference.choose(values, 3, program, tau)
    assert use.tolist() == [own, [True, True, False, True, False, False],
                            own, own]
    assert float(stats["tied"]) == 1.0
    assert float(stats["followed"]) == 0.25 and float(stats["outside"]) == 0.5
    # no tie, no following: the same swap where 2 and 3 are far apart
    wide = values.at[:, 3].set(0.4)
    use, stats = reference.choose(wide, 3, program, tau)
    assert use.tolist() == [own] * 4
    assert float(stats["tied"]) == 0.0 and float(stats["outside"]) == 0.75
    # without the program's choices: the plain top k
    assert reference.choose(values, 3)[0].tolist() == [own] * 4


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
    sown = state["intermediates"]["block_2"]["ffn"]
    assert sown["chosen"][0].shape == (2, 32, 2)
    assert sown["scores"][0].shape == (2, 32, 8)
    load = np.asarray(sown["load"][0])
    assert load.sum() == 2 * 32 * 2
    imbalance = moe.report_load(load, CONFIG["held_experts"])
    here = load[CONFIG["held_experts"]]
    assert imbalance == pytest.approx(here.max() / here.mean())
    assert instruments.moe_load_imbalance().value == pytest.approx(imbalance)
    assert instruments.expert_load().labels(expert="4").value == load[4]


def test_unknown_kinds_and_a_bad_share_are_refused():
    toks = tokens(8)[0]
    for change, match in (({"ffn_kinds": ("swiglu", "dense") + ("moe",) * 3},
                           "ffn_kinds"),
                          ({"ffn_kinds": ("moe",) * 4}, "ffn_kinds"),
                          ({"moe_held": (1, 1, 4)}, "moe_held"),
                          ({"moe_held": (1, 8)}, "moe_held"),
                          ({"moe_top_k": 9}, "moe_top_k"),
                          ({"attn_position": "alibi"}, "attn_position"),
                          ({"layer_kinds": ("short_conv", "rwkv") * 2
                            + ("attention",)}, "layer_kinds")):
        with pytest.raises(ValueError, match=match):
            model().clone(**change).init(jax.random.PRNGKey(0), toks)


# ------------------------------------------- granite: nothing of it moved
def test_granites_parameter_tree_and_scope_paths_are_unchanged():
    """The other user of ``HybridLM``, built as its family builds it: the
    same parameter tree (names and shapes) and the same scope paths in the
    compiled program as before the new kinds (PR 27's)."""
    from chipbench.families import granitemoehybrid as granite

    from chipbench import harness

    config = {**harness.load_json("configs", "granite-4.0-h-micro.json"),
              **granite.REHEARSAL}
    m = granite.build_model(config, 512, {"remat": "full"})
    toks = jnp.zeros((1, 32), jnp.int32)
    shapes = jax.tree_util.tree_map(
        lambda l: l.shape, jax.eval_shape(m.init, jax.random.PRNGKey(0),
                                          toks)["params"])
    assert set(shapes) == {"tok_emb", "norm_f"} | {f"block_{i}"
                                                   for i in range(4)}
    assert shapes["block_0"] == {
        "norm_mixer": {"scale": (128,)}, "norm_ffn": {"scale": (128,)},
        "ffn_in": {"kernel": (128, 512)}, "ffn_out": {"kernel": (256, 128)},
        "mixer": {"in_proj": {"kernel": (128, 548)},
                  "conv": {"kernel": (4, 288), "bias": (288,)},
                  "A_log": (4,), "dt_bias": (4,), "D": (4,),
                  "gate_norm": {"scale": (256,)},
                  "out_proj": {"kernel": (256, 128)}}}
    assert shapes["block_2"]["mixer"] == {
        "q": {"kernel": (128, 128)}, "k": {"kernel": (128, 64)},
        "v": {"kernel": (128, 64)}, "o": {"kernel": (128, 128)}}
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0), toks)["params"]
    text = jax.jit(jax.grad(lambda p: jnp.sum(m.apply({"params": p}, toks)))
                   ).lower(params).as_text(debug_info=True)
    for scope in ("block_0/mixer/in_proj", "block_0/mixer/conv",
                  "block_0/mixer/ssd", "block_0/mixer/gate_norm",
                  "block_0/mixer/out_proj", "block_2/mixer/q",
                  "block_2/mixer/o", "block_0/ffn_in", "block_0/ffn_out",
                  "block_0/norm_mixer", "block_0/norm_ffn", "tok_emb",
                  "norm_f", "rematted_computation/block_3"):
        assert scope in text, scope
    for scope in ("moe", "short_conv", "rope", "qk_norm"):
        assert f"/{scope}" not in text, scope
    assert hybrid.HybridLM.__dataclass_fields__["ffn_kinds"].default == ()
