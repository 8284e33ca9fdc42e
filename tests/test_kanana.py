"""``models/hybrid.HybridLM`` as the DeepSeek-V3 family builds kanana-2
(latent attention in every layer: a normed KV latent, one rotary key all
heads share, keys wider than values; a sigmoid-routed expert feed-forward
beside a shared expert behind one dense layer; an untied head) against its
plain reference, ``chipbench/reference_deepseek_v3.py``; and what
``ops/pallas_kernels.py`` (a value width of its own on the flash kernels)
and ``ops/rope.py`` (neighbouring pairs) gained for it against
``reference_attention`` and a complex rotation.

Small size, seeded weights with the norm weights randomised and the
matrices scaled so that each part of a block is as large as what it stands
beside. The model holds 2 of 8 experts (ids 1 and 6: not a prefix), three a
token; a head's keys are 32 + 16 wide, its values 32.
"""

import functools
import importlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from chipbench import flops, harness, mla_attention_cost
from chipbench import reference_deepseek_v3 as reference
from chipbench.families import deepseek_v3 as family
from horovod_tpu import spmd
from horovod_tpu.models import hybrid
from horovod_tpu.models.transformer import lm_loss
from horovod_tpu.ops import moe, pallas_kernels as pk, rope
from horovod_tpu.parallel.ring_attention import reference_attention
from tests.test_laguna import low, relative, worst_leaf

#: the configuration keys the family and the reference read, small: the
#: leading dense layer and two routed ones
CONFIG = {"num_hidden_layers": 3, "first_k_dense_replace": 1,
          "hidden_size": 128, "intermediate_size": 192,
          "num_attention_heads": 4, "num_key_value_heads": 4,
          "kv_lora_rank": 48, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
          "qk_head_dim": 48, "v_head_dim": 32, "head_dim": 16,
          "q_lora_rank": None, "rope_scaling": None, "rope_theta": 1000000,
          "rope_interleave": True, "n_group": 1, "topk_group": 1,
          "moe_layer_freq": 1, "scoring_func": "sigmoid",
          "norm_topk_prob": True, "topk_method": "noaux_tc",
          "n_routed_experts": 2, "n_routed_experts_published": 8,
          "held_experts": [1, 6], "num_experts_per_tok": 3,
          "moe_intermediate_size": 64, "n_shared_experts": 2,
          "routed_scaling_factor": 2.448, "rms_norm_eps": 1e-6,
          "vocab_size": 512, "assumed": {"tie_tau": {"value": 1e-3}}}
ROWS = 512

#: float32 program against float32 reference: both round at 2^-24 and
#: differ in the order of their sums (grouped rows against masked experts,
#: one softmax against blocks of queries). Measured 3e-7 in the logits and
#: 3e-6 in the worst gradient leaf; bf16 operands read 1e-2 and 0.1.
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
        # quarter: scaled so that attention's scores spread (through the
        # latent the keys pass two matrices), its update and the routed
        # experts' are as large as what they stand beside, and the router
        # is not flat
        if any(k in name for k in ("['q']", "['kv_a']", "['kv_b']", "['o']",
                                   "router", "w_in", "w_out")):
            return 4.0 * leaf
        if leaf.ndim >= 2:
            return leaf                 # the matrices: N(0, 0.02) already
        if "expert_bias" in name:       # zeros that nothing moves
            return leaf
        return leaf + 0.5 * jax.random.normal(key, leaf.shape, leaf.dtype)

    return jax.tree_util.tree_unflatten(treedef, [
        shake(path, leaf, key) for (path, leaf), key in zip(leaves, keys)])


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


# ---------------------------------------------------- model against reference
def test_logits_loss_and_every_gradient_match_the_reference(seq=40):
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


def test_the_references_own_loss_and_gradients_are_the_jobs():
    """``reference.loss`` / ``loss_and_grads`` (the cross-entropy written
    out) against ``lm_loss`` over ``reference.forward`` and its
    ``jax.grad``."""
    params, (toks, targets) = randomised_params(), tokens(40)
    _, want_loss, want_grads = reference_side(40)
    loss, grads = jax.jit(functools.partial(
        reference.loss_and_grads, config=CONFIG))(params, toks, targets)
    assert abs(float(loss) - float(want_loss)) <= 1e-6 * float(want_loss)
    assert worst_leaf(grads, want_grads)[1] <= 1e-5


def _mixer_and_inputs(dtype=jnp.float32):
    params = randomised_params()["block_1"]["mixer"]
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 128), jnp.float32)
    mixer = hybrid.LatentAttentionMixer(
        heads=4, kv_rank=48, nope_dim=32, rope_dim=16, v_dim=32,
        scale=48 ** -0.5, eps=1e-6, dtype=dtype, rope_theta=1e6)
    return mixer, params, h


def _reference_mixer(p, h):
    cos, sin = reference.rotary_tables(1e6, 16, h.shape[1])
    with jax.default_matmul_precision("highest"):
        return reference._latent_attention(p, h, 4, 48, 32, 32, 1e-6, cos,
                                           sin)


def test_the_mixer_alone_and_its_gradients_match_the_reference():
    """The latent-attention mixer outside any block, its output and its
    gradients in every matrix, the latent's norm and its input."""
    mixer, params, h = _mixer_and_inputs()

    def loss(fn):
        return lambda p, h: jnp.sum(jnp.sin(fn(p, h)))

    def program(p, h):
        return mixer.apply({"params": p}, h)

    assert relative(program(params, h), _reference_mixer(params, h)) <= 1e-6
    got = jax.grad(loss(program), (0, 1))(params, h)
    want = jax.grad(loss(_reference_mixer), (0, 1))(params, h)
    path, error = worst_leaf(got, want)
    assert error <= 1e-5, (jax.tree_util.keystr(path), error)
    assert set(got[0]) == {"q", "kv_a", "kv_norm", "kv_b", "o"}


def test_the_parameter_tree_is_the_published_layers():
    """Every layer: q of 4 x (32 + 16), the latent projection 48 + 16 wide,
    its norm, the up-projection to 4 x (32 + 32), o from 4 x 32; a dense
    feed-forward in layer 0 and router, held experts and the two shared
    experts as one in the others; an untied head beside the table."""
    shapes = jax.tree_util.tree_map(lambda l: l.shape, randomised_params())
    assert set(shapes) == {"tok_emb", "norm_f", "lm_head"} | {
        f"block_{i}" for i in range(3)}
    mixer = {"q": {"kernel": (128, 4 * 48)}, "kv_a": {"kernel": (128, 64)},
             "kv_norm": {"scale": (48,)}, "kv_b": {"kernel": (48, 4 * 64)},
             "o": {"kernel": (4 * 32, 128)}}
    norms = {"norm_mixer": {"scale": (128,)}, "norm_ffn": {"scale": (128,)}}
    routed = {"router": (128, 8), "expert_bias": (8,), "w_in": (2, 128, 128),
              "w_out": (2, 64, 128), "shared_in": {"kernel": (128, 256)},
              "shared_out": {"kernel": (128, 128)}}
    assert shapes["block_0"] == {
        **norms, "mixer": mixer, "ffn_in": {"kernel": (128, 384)},
        "ffn_out": {"kernel": (192, 128)}}
    for i in (1, 2):
        assert shapes[f"block_{i}"] == {**norms, "mixer": mixer,
                                        "ffn": routed}
    assert shapes["lm_head"] == {"kernel": (128, 512)}
    built = model()
    assert (built.moe_scoring, built.moe_top_k, built.moe_scale,
            built.moe_norm_eps, built.moe_shared_width, built.tied_head) == (
        "sigmoid", 3, 2.448, 1e-20, 128, False)


def test_the_new_scopes_are_in_the_compiled_program():
    m = model(remat="full")
    toks = tokens(32)[0]
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0), toks)["params"]
    text = jax.jit(jax.grad(lambda p: jnp.sum(m.apply({"params": p}, toks)))
                   ).lower(params).as_text(debug_info=True)
    for scope in ("block_0/mixer/latent/kv_a", "block_2/mixer/latent/kv_b",
                  "block_1/mixer/latent/kv_norm", "block_0/mixer/rope",
                  "block_2/mixer/assemble", "block_1/mixer/q",
                  "block_1/ffn/shared_in", "block_1/ffn/moe/router",
                  "lm_head", "rematted_computation/block_2"):
        assert scope in text, scope
    for scope in ("mixer/window", "mixer/gate", "qk_norm", "tok_emb.attend",
                  "latent_in"):
        assert scope not in text, scope
    # what the two readers match, and nothing of the feed-forward's latent
    for reader, part in (("mla_latent_ms", "latent"),
                         ("mla_assemble_ms", "assemble")):
        pattern = importlib.import_module(
            f"chipbench.layer_metrics.{reader}").PATTERN
        assert re.search(pattern, f"jit(f)/block_1/mixer/{part}/kv_a/dot")
        assert re.search(pattern, f"transpose(jvp(block_1))/mixer/{part}")
        assert not re.search(pattern, "jit(f)/block_1/ffn/latent_in/dot")
        assert not re.search(pattern, f"jit(f)/block_1/mixer/{part}_x/dot")


# ------------------------------------- the flash kernels at two widths
def _qkv(t, h=2, d=192, dv=128, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    normal = jax.random.normal
    return (normal(keys[0], (1, t, h, d), dtype),
            normal(keys[1], (1, t, h, d), dtype),
            normal(keys[2], (1, t, h, dv), dtype),
            normal(keys[3], (1, t, h, dv), dtype))


def _with_grads(fn, q, k, v, weight):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(weight)


_FLASH_DISPATCHERS = ("_flash_fwd_once_call", "_flash_step_call_resident",
                      "_flash_step_call_streaming", "_flash_bwd_fused",
                      "_flash_bwd_streaming")


def _forget_traces():
    pk._flash_fullattn_vjp.cache_clear()
    for name in _FLASH_DISPATCHERS:
        getattr(pk, name).clear_cache()


@pytest.fixture
def small_tiles(monkeypatch):
    """The kernels through the Pallas interpreter on tiles of 64, so that
    256 positions are 4 x 4 grid tiles; the dispatchers' traces forgotten
    before and after (they read the tile edges and the caps when traced)."""
    monkeypatch.setenv("HVD_PALLAS", "interpret")
    monkeypatch.setattr(pk, "_BLOCK_Q", 64)
    monkeypatch.setattr(pk, "_BLOCK_K", 64)
    monkeypatch.setattr(pk, "_SUB_TILE", 32)
    _forget_traces()
    yield monkeypatch
    _forget_traces()


def _spy_kernels(monkeypatch):
    taken, real = [], pk._named_call

    def spy(name, kernel, **kw):
        taken.append(name)
        return real(name, kernel, **kw)

    monkeypatch.setattr(pk, "_named_call", spy)
    return taken


#: route -> the caps that force it at 256 positions, and the kernels taken
ROUTES = {
    "once_fused": ({}, ["flash_fwd", "flash_bwd"]),
    "streaming_fused": ({"_KV_VMEM_CAP": 1}, ["flash_step", "flash_bwd"]),
    "streaming_streaming": ({"_KV_VMEM_CAP": 1, "_DQ_SCRATCH_CAP": 1},
                            ["flash_step", "flash_bwd_dq", "flash_bwd_dkv"]),
    "once_streaming": ({"_DQ_SCRATCH_CAP": 1},
                       ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
}


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_the_two_width_kernels_are_reference_attention(route, causal,
                                                       small_tiles):
    """Keys 192 and values 128 wide, as the cell's: the output and dv are
    as wide as v, dq and dk as wide as q, on every route, forward and
    backward, in the interpreter."""
    caps, kernels = ROUTES[route]
    for name, value in caps.items():
        small_tiles.setattr(pk, name, value)
    taken = _spy_kernels(small_tiles)
    q, k, v, weight = _qkv(256)
    assert pk.kernel_path("flash_attention", q, k, v) == "pallas"
    scale = 192 ** -0.5
    got = _with_grads(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=causal, scale=scale), q, k, v, weight)
    assert taken == kernels
    want = _with_grads(lambda q, k, v: reference_attention(
        q, k, v, causal=causal, scale=scale), q, k, v, weight)
    assert [a.shape[-1] for a in got] == [128, 192, 192, 128]
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=f"{route} {name}")


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("against", ["reference", "pair"])
def test_a_scratch_over_the_default_is_the_same_one_pass_backward(
        against, causal, small_tiles):
    """The cell's rule at a size the interpreter runs: a dq scratch over
    ``_DQ_SCRATCH_DEFAULT`` and within ``_DQ_SCRATCH_CAP`` takes the
    multi-sweep fused kernel (4 x 4 tiles here) with the VMEM limit the
    route reckons for it, and its dq, dk and dv are the f32 reference's and
    the streaming pair's, which a scratch over the cap takes."""
    t, scratch = 256, 256 * 192 * 4
    small_tiles.setattr(pk, "_KV_VMEM_CAP", 1)
    small_tiles.setattr(pk, "_DQ_SCRATCH_DEFAULT", scratch - 1)
    route = pk.flash_route(t, t, 192, 4, dv=128)
    assert route["backward"] == "fused"
    assert route["backward_vmem"] == pk._flash_bwd_vmem(
        t, 192, 128, 4, 64, 64) > scratch
    q, k, v, weight = _qkv(t)
    scale = 192 ** -0.5

    def grads(q, k, v):
        return _with_grads(lambda q, k, v: pk.flash_attention(
            q, k, v, causal=causal, scale=scale), q, k, v, weight)[1:]

    _forget_traces()
    params = _pallas_params(lambda *a: grads(*a), q, k, v)
    assert set(params) == {"flash_step", "flash_bwd"}
    bwd = params["flash_bwd"]
    assert bwd["compiler_params"]["mosaic_tpu"].vmem_limit_bytes \
        == route["backward_vmem"]
    assert bwd["grid_mapping"].grid == (2, 4, 4)     # four key sweeps
    got = grads(q, k, v)
    if against == "reference":
        want = _with_grads(lambda q, k, v: reference_attention(
            q, k, v, causal=causal, scale=scale), q, k, v, weight)[1:]
    else:
        small_tiles.setattr(pk, "_DQ_SCRATCH_CAP", scratch - 1)
        _forget_traces()
        assert pk.flash_route(t, t, 192, 4, dv=128)["backward"] \
            == "streaming"
        taken = _spy_kernels(small_tiles)
        want = grads(q, k, v)
        assert taken == ["flash_step", "flash_bwd_dq", "flash_bwd_dkv"]
    assert [a.shape[-1] for a in got] == [192, 192, 128]
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=f"{against} {name}")


def test_the_ring_hops_step_takes_a_value_width_too(small_tiles):
    """``flash_attention_step`` + ``finalize_attention_stats`` and the ring
    path's ``_flash_bwd`` at two widths, resident and streaming."""
    q, k, v, weight = _qkv(128)
    scale = 192 ** -0.5
    want = _with_grads(lambda q, k, v: reference_attention(
        q, k, v, causal=True, scale=scale), q, k, v, weight)
    for cap in (2 ** 20, 1):
        small_tiles.setattr(pk, "_KV_VMEM_CAP", cap)
        small_tiles.setattr(pk, "_DQ_SCRATCH_CAP", 4 * 2 ** 20 * cap)
        _forget_traces()
        m = jnp.full((1, 2, 128), -jnp.inf, jnp.float32)
        m, l, o = pk.flash_attention_step(
            q, k, v, m, jnp.zeros((1, 2, 128), jnp.float32),
            jnp.zeros((1, 128, 2, 128), jnp.float32), 0, 0, causal=True,
            scale=scale)
        out, lse = pk.finalize_attention_stats(m, l, o, jnp.float32)
        got = (out,) + pk._flash_bwd(q, k, v, out, lse, weight, 0, 0,
                                     causal=True, scale=scale)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


def _pallas_params(fn, *args):
    """``{kernel name: its pallas_call's params}`` in ``fn``'s jaxpr."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = eqn.params
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


# cell -> (batch, positions, heads, key width, value width, window) of its
# flash calls, and the VMEM limit its backward call names, in MiB: 96 for a
# single key sweep (the resident budget, as it has been), ``None`` (Mosaic's
# default) for a multi-sweep call whose dq scratch is at most 4 MiB, and for
# kanana2-train-s16384's 16,384 x 192 and qwen3next-train-s16384's 16,384 x
# 256 what the shape is reckoned to hold
_CELL_BACKWARD_VMEM = {
    "gpt2m-train-s1024_and_dp4": ((8, 1024, 16, 64, 64, None), 96),
    "gpt2l-train-s1024": ((4, 1024, 20, 64, 64, None), 96),
    "granite4hm-train-s4096": ((1, 4096, 32, 64, 64, None), None),
    "lfm2moe-train-s8192": ((2, 8192, 32, 64, 64, None), None),
    "nemotron3s-train-s4096": ((1, 4096, 32, 128, 128, None), None),
    "lagunas-train-s8192_full": ((1, 8192, 48, 128, 128, None), None),
    "lagunas-train-s8192_window": ((1, 8192, 72, 128, 128, 512), None),
    "kanana2-train-s16384": ((1, 16384, 32, 192, 128, None), 35),
    # a 16 MiB dq scratch at 256 lanes; K + V resident are 32 MiB
    "qwen3next-train-s16384": ((1, 16384, 16, 256, 256, None), 37),
}


@pytest.mark.parametrize("cell", sorted(_CELL_BACKWARD_VMEM))
def test_a_cells_backward_names_the_vmem_its_shape_needs(cell, monkeypatch):
    """Every cell but one compiles the backward call it compiled before the
    dq scratch's budget moved, with the VMEM limit it had; the one whose
    scratch is over 4 MiB takes the same kernel and names its own, what
    ``_flash_bwd_vmem`` reckons, under the chip's 128 MiB."""
    monkeypatch.setenv("HVD_PALLAS", "interpret")
    (b, t, h, d, dv, window), mib = _CELL_BACKWARD_VMEM[cell]
    route = pk.flash_route(t, t, d, 2, window, dv=dv)
    assert route["backward"] == "fused"
    assert (route["backward_vmem"] is None) == (mib in (None, 96))
    qk = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, t, h, dv), jnp.bfloat16)
    _forget_traces()
    calls = _pallas_params(jax.grad(
        lambda q, k, v: jnp.sum(pk.flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32)),
        argnums=(0, 1, 2)), qk, qk, v)
    _forget_traces()
    assert "flash_bwd" in calls and "flash_bwd_dq" not in calls
    # and every cell's forward keeps a head's K and V in VMEM: the
    # single-shot kernel under the resident calls' limit, no carried m, l, o
    assert route["forward"] == "once"
    assert "flash_fwd" in calls and "flash_step" not in calls
    assert calls["flash_fwd"]["compiler_params"][
        "mosaic_tpu"].vmem_limit_bytes == pk._VMEM_LIMIT > pk._KV_VMEM_CAP
    params = calls["flash_bwd"]["compiler_params"]["mosaic_tpu"]
    assert params.vmem_limit_bytes == (mib and mib * 2 ** 20)
    if route["backward_vmem"] is not None:
        tiles = pk.flash_tiles(t, t, window)
        # the scratch as VMEM lays it out (192 in 256 lanes), and no more
        # than the chip has
        assert 4 * t * 256 < params.vmem_limit_bytes == pk._flash_bwd_vmem(
            t, d, dv, 2, *tiles) <= pk._VMEM_LIMIT < 128 * 2 ** 20


def test_equal_widths_are_the_kernels_there_were(small_tiles):
    """With values as wide as keys nothing of a call moved: the route and
    the kernels are those of a call that names no value width, the result
    is that call's to the bit whichever way the width is said, and each
    kernel's cost estimate is the one-width formula's number."""
    q, k, _, _ = _qkv(256, d=128)
    v = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)
    for t, d in ((1024, 64), (8192, 128), (16384, 128), (32768, 64)):
        assert pk.flash_route(t, t, d, 2) == pk.flash_route(t, t, d, 2, dv=d)
    assert pk.step_supported(q, k) and pk.step_supported(q, k, v)

    def grads(q, k, v):
        return _with_grads(lambda q, k, v: pk.flash_attention(
            q, k, v, causal=True), q, k, v, v)

    bh, t, d = 2, 256, 128
    scores = {}
    shipped = {name: getattr(pk, name)
               for name in ("_KV_VMEM_CAP", "_DQ_SCRATCH_CAP")}
    for route, (caps, _) in sorted(ROUTES.items()):
        for name in shipped:
            small_tiles.setattr(pk, name, caps.get(name, shipped[name]))
        _forget_traces()
        # a fresh function: make_jaxpr keeps the trace of one it has seen
        for name, params in _pallas_params(
                lambda *a: grads(*a), q, k, v).items():
            cost = params["cost_estimate"]
            n = scores[name] = cost.transcendentals
            per_score, tq_rows, tk_rows = {
                "flash_fwd": (4, 2, 2), "flash_step": (4, 2, 2),
                "flash_bwd": (10, 5, 4), "flash_bwd_dq": (6, 4, 2),
                "flash_bwd_dkv": (8, 4, 3)}[name]
            assert cost.flops == per_score * n * d, (route, name)
            stats = {"flash_fwd": 4 * bh * t, "flash_step": 16 * bh * t}.get(
                name, 4 * bh * t)
            item = 2 if name == "flash_fwd" else 4
            assert cost.bytes_accessed == item * bh * (
                tq_rows * t * d + tk_rows * t * d) + stats, (route, name)
    assert set(scores) == {"flash_fwd", "flash_step", "flash_bwd",
                           "flash_bwd_dq", "flash_bwd_dkv"}
    # a streaming call counts the whole square, the resident forward and
    # the fused backward their plans
    assert scores["flash_step"] == scores["flash_bwd_dq"] == bh * t * t


def test_widths_the_kernels_do_not_take_go_to_the_reference(monkeypatch):
    monkeypatch.setenv("HVD_PALLAS", "interpret")
    q, k, v, _ = _qkv(64)
    assert pk.kernel_path("flash_attention", q, k, v) == "pallas"
    assert pk.kernel_path("flash_attention", q, k, v[..., :96]) == "reference"
    assert pk.kernel_path("flash_attention", q[..., :160], k[..., :160],
                          v) == "reference"
    # K and V are held to the resident cap together, each at its own width
    # as VMEM lays it out (192 in 256 lanes), and the dq scratch is as wide
    # as q: the cell's head keeps its 24 MiB of K and V in VMEM forward, and
    # its one-pass backward names the 35 MiB its 16 MiB scratch needs
    assert pk._kv_vmem(16384, 192, 128, 2) == 24 * 2 ** 20
    assert pk.flash_route(16384, 16384, 192, 2, dv=128) == {
        "forward": "once", "step": "step",
        "backward": "fused", "backward_vmem": 35 * 2 ** 20}
    # whichever of the two is the wider: 1.5 KiB a position, so 64 MiB are
    # 42 key blocks of 1024 (and 32,768 the last power of two)
    for d, dv in ((192, 128), (128, 192)):
        assert pk.flash_route(42 * 1024, 42 * 1024, d, 2,
                              dv=dv)["forward"] == "once"
        assert pk.flash_route(43 * 1024, 43 * 1024, d, 2,
                              dv=dv)["forward"] == "step_streaming"
    assert pk.flash_route(4096, 4096, 192, 2, dv=128)["backward"] == "fused"
    out = pk.flash_attention(q, k, v[..., :96], causal=True)
    assert out.shape == (1, 64, 2, 96)


def test_the_cost_of_two_widths_is_one_widths_where_they_are_equal():
    for b, h, t, d in ((8, 16, 1024, 64), (1, 32, 4096, 128)):
        assert mla_attention_cost.mla_attention_train_cost(b, h, t, d, d) \
            == flops.flash_attention_train_cost(b, h, t, d)
    cost = mla_attention_cost.mla_attention_train_cost(1, 32, 16384, 192, 128)
    # (6 x 192 + 6 x 128) operations a score of the triangle a head
    assert cost["flops"] == 32 * (16384 ** 2 / 2) * (6 * 192 + 6 * 128)
    assert cost["bytes"] == 2 * 32 * 16384 * (6 * 192 + 6 * 128)
    # 2.75 TFLOP a layer forward; compute-bound, 41.9 ms at the bf16 peak
    assert cost["flops"] / 3 == pytest.approx(2.749e12, rel=1e-3)
    assert cost["flops"] / 197e12 == pytest.approx(41.86e-3, rel=1e-3)
    assert cost["bytes"] / 819e9 < 0.1 * cost["flops"] / 197e12


# ------------------------------------------------------------------ the rope
def test_the_pair_layout_is_a_complex_rotation_and_rotate_half_is_todays():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 3, 16), jnp.float32)
    positions = np.arange(24, dtype=np.float64)
    freq = 1e6 ** (-2.0 * np.arange(8, dtype=np.float64) / 16)
    turn = np.exp(1j * positions[:, None] * freq)[None, :, None, :]
    xs = np.asarray(x, np.float64)
    pairs = (xs[..., 0::2] + 1j * xs[..., 1::2]) * turn
    want = np.stack([pairs.real, pairs.imag], axis=-1).reshape(xs.shape)
    got = rope.apply_rope(x, 1e6, interleaved=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    # rotate-half, the default: pair i is elements i and i + 8
    halves = (xs[..., :8] + 1j * xs[..., 8:]) * turn
    want_half = np.concatenate([halves.real, halves.imag], axis=-1)
    default = rope.apply_rope(x, 1e6)
    np.testing.assert_allclose(np.asarray(default), want_half, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(default), np.asarray(rope.apply_rope(x, 1e6,
                                                        interleaved=False)))
    assert relative(got, default) > 0.1
    # the two layouts are one turn under the permutation between them, so
    # a score q . k is the same in both
    perm = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])
    np.testing.assert_allclose(
        np.asarray(got)[..., perm],
        np.asarray(rope.apply_rope(x[..., perm], 1e6)), rtol=1e-5, atol=1e-5)
    # positions given, a partial width, in the model's dtype
    part = rope.apply_rope(x.astype(jnp.bfloat16), 1e6, jnp.arange(24) + 5,
                           rotary_dim=8, interleaved=True)
    assert part.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(part[..., 8:]),
                                  np.asarray(x.astype(jnp.bfloat16)[..., 8:]))


# ------------------------------------------------------------- the routing
def layer_params(seed=3, d=32, f=24, shared=48, experts=32):
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
        32, tuple(held), 6, 24, jnp.float32, shared_width=48, scale=2.448,
        norm_eps=1e-20, scoring="sigmoid")
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, h: module.apply({"params": p}, h))(
            share_of(params, held), h)


def reference_layer(params, h, held):
    with jax.default_matmul_precision("highest"):
        return reference._routed(share_of(params, held), h, tuple(held), 6,
                                 2.448, None, 0.0)[0]


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """Each of eight chips holds four of the thirty-two experts and
    computes the shared expert whole; the routed parts of the eight, with
    the shared expert counted once, are what the reference gives holding
    every expert."""
    params = layer_params()
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 48, 32))
    shares = [tuple(range(4 * i, 4 * i + 4)) for i in range(8)]
    with jax.default_matmul_precision("highest"):
        shared = reference._swiglu(h, params["shared_in"]["kernel"],
                                   params["shared_out"]["kernel"])
    whole = reference_layer(params, h, range(32))
    parts = [layer(params, h, held) for held in shares]
    assert relative(sum(p - shared for p in parts) + shared, whole) <= 1e-6
    assert relative(sum(reference_layer(params, h, held) - shared
                        for held in shares[:3])
                    + sum(p - shared for p in parts[3:]) + shared,
                    whole) <= 1e-6
    # a token's six weights sum to the scale over all the shares
    weights = moe.route(h[0] @ params["router"], params["expert_bias"], 6,
                        2.448, 1e-20)[1]
    np.testing.assert_allclose(jnp.sum(weights, -1), 2.448, rtol=1e-6)
    # one share alone is not the layer, nor are the eight with the shared
    # expert counted eight times
    assert relative(parts[0], whole) > 0.1
    assert relative(sum(parts), whole) > 0.1
    norm = jnp.linalg.norm
    assert norm(shared) > 0.05 * norm(whole) < norm(whole - shared)


# -------------------------------------------------------------- controls
def test_control_bf16_operands_fail_the_float32_tolerance():
    """The same comparison one precision lower: over F32_TOL by far."""
    logits, _, grads = program_side(40, jnp.bfloat16)
    ref_logits, _, ref_grads = reference_side(40)
    assert relative(logits, ref_logits) > 10 * F32_TOL
    assert worst_leaf(grads, ref_grads)[1] > 10 * F32_TOL


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


def _shared_key_unturned(x, theta, positions=None, **kw):
    """``apply_rope`` that leaves a one-head operand (the shared rotary key)
    as it is."""
    return x if x.shape[2] == 1 else rope.apply_rope(x, theta, positions,
                                                     **kw)


def _rotate_half(x, theta, positions=None, **kw):
    """``apply_rope`` in the other layout: pair ``i`` is elements ``i`` and
    ``i + width / 2``."""
    return rope.apply_rope(x, theta, positions, **{**kw, "interleaved": False})


#: a program that differs from the model in one thing the reference holds
#: it to: fields of the model, or a patch of ``models/hybrid``
WRONG = {
    "a_scale_of_the_nope_width": {"attention_multiplier": 32 ** -0.5},
    "rotate_half_pairs": {"apply_rope": _rotate_half},
    "another_rotary_base": {"mla_rope_theta": 1e4},
    "the_shared_key_unturned": {"apply_rope": _shared_key_unturned},
    "unscaled_expert_weights": {"moe_scale": 1.0},
    "softmax_scores": {"moe_scoring": "softmax"},
}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_control_a_wrong_program_fails_the_chips_check(name, monkeypatch):
    """``family.program_trace`` runs the program, so the family's builder
    is patched too."""
    sound = family.build_model
    changes = dict(WRONG[name])
    if "apply_rope" in changes:
        monkeypatch.setattr(hybrid, "apply_rope", changes.pop("apply_rope"))
    monkeypatch.setattr(family, "build_model",
                        lambda *a: sound(*a).clone(**changes))
    params = randomised_params()
    wrong = family.build_model(CONFIG, ROWS, {}).clone(dtype=jnp.bfloat16)
    assert not chip_check(params, tokens(64)[0], program=wrong) <= 0.02, name


def test_the_reference_returns_its_own_logits_whatever_the_programs_are(
        monkeypatch):
    """What ``reference_forward`` returns is the plain reference's
    free-running pass under the tie rule against the job's program's
    routing, and nothing of the program's own logits: the job's comparison
    is what holds those."""
    params, toks = randomised_params(), tokens(64)[0]
    logits, routing = family.program_routing(params, toks, CONFIG)
    plain, stats = reference.forward_following(
        params, toks, CONFIG, routing, CONFIG["assumed"]["tie_tau"]["value"])
    assert [layer["layer"] for layer in stats] == ["block_1", "block_2"]
    assert all(0 < float(layer["tied"]) < 1 and float(layer["outside"]) < 0.01
               for layer in stats)
    want = family.reference_forward(params, toks, CONFIG)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(plain))
    monkeypatch.setattr(family, "program_routing",
                        lambda *a: (logits + 1.0, routing))
    moved = family.reference_forward(params, toks, CONFIG)
    np.testing.assert_array_equal(np.asarray(moved), np.asarray(plain))
    assert relative(logits + 1.0, moved) > 0.02
    # no routing to follow: every choice the reference's own
    own, none = reference.forward_following(params, toks, CONFIG, {}, 0.0)
    assert none == []
    np.testing.assert_array_equal(
        np.asarray(own), np.asarray(reference.forward(params, toks, CONFIG)))


def test_the_familys_two_compilations_are_one_program():
    """Routing kept alone (``program_routing``: the job's program on the
    chip) and kept with the blocks' outputs (``program_trace``): on the CPU
    one stream, so the same logits and choices, and the last output under
    the final norm and the head is those logits to the bf16 head's
    rounding."""
    params, toks = randomised_params(), tokens(64)[0]
    logits, routing = family.program_routing(params, toks, CONFIG)
    traced, outputs, traced_routing = family.program_trace(params, toks,
                                                           CONFIG)
    assert len(outputs) == CONFIG["num_hidden_layers"]
    assert sorted(routing) == sorted(traced_routing) == ["block_1", "block_2"]
    assert routing["block_1"]["chosen"].shape == toks.shape + (3,)
    assert relative(traced, logits) < 1e-3
    for name in routing:
        assert np.mean(np.sort(np.asarray(routing[name]["chosen"]), -1)
                       != np.sort(np.asarray(traced_routing[name]["chosen"]),
                                  -1)) < 0.01
    again = reference.head(params, outputs[-1].astype(jnp.float32), 1e-6)
    assert relative(again, logits) < 0.01


def test_what_the_family_does_not_build_is_refused():
    toks = tokens(8)[0]
    for key, value in (("q_lora_rank", 1536), ("n_group", 8),
                       ("rope_scaling", {"type": "yarn"}),
                       ("scoring_func", "softmax"), ("qk_head_dim", 64)):
        with pytest.raises(harness.BenchmarkError, match="deepseek_v3"):
            family.build_model({**CONFIG, key: value}, ROWS, {})
    with pytest.raises(ValueError, match="layer_kinds"):
        jax.eval_shape(model().clone(layer_kinds=("latent",) * 3).init,
                       jax.random.PRNGKey(0), toks)


@pytest.mark.parametrize("field", ["mla_kv_rank", "mla_nope_dim",
                                   "mla_rope_dim", "mla_v_dim",
                                   "mla_rope_theta"])
def test_latent_attention_needs_every_field_stated(field):
    with pytest.raises(ValueError, match="latent_attention layers need"):
        jax.eval_shape(model().clone(**{field: 0}).init,
                       jax.random.PRNGKey(0), tokens(8)[0])


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
    mesh, against the reference's own loss and gradients under the same
    optimizer."""
    hvd.init()
    mesh = hvd.mesh()
    m = model()
    params, batch = randomised_params(), tokens(24, batch=8, seed=7)

    def loss_fn(p, b):
        return lm_loss(m.apply({"params": p}, b[0]), b[1])

    tx = optax.adamw(3e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16)
    step = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False)
    p, o = spmd.replicate(params, mesh), spmd.replicate(tx.init(params), mesh)
    sharded = spmd.shard_batch(batch, mesh)
    rp, ro = params, tx.init(params)
    plain = jax.jit(functools.partial(reference.loss_and_grads,
                                      config=CONFIG))
    for i in range(3):
        p, o, loss = step(p, o, sharded)
        ref_loss, grads = plain(rp, *batch)
        updates, ro = tx.update(grads, ro, rp)
        rp = optax.apply_updates(rp, updates)
        assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss), i
    assert float(loss) < float(plain(params, *batch)[0])


def test_the_family_counts_what_the_issue_counted():
    """The published widths: 911.0 M parameters here (687.9 M at the
    fallback's six layers), the matrix elements a token touches, the
    rooflines' operations; the file against the catalog's row."""
    config = harness.load_json("configs",
                               "kanana-2-30b-a3b-instruct-2601.json")
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"])
    assert (config["num_hidden_layers_published"],
            config["n_routed_experts_published"],
            config["vocab_size_published"]) == (48, 128, 128256)
    held, layers = len(config["held_experts"]), config["num_hidden_layers"]
    assert held == config["n_routed_experts"] == 16
    assert config["held_experts"] == list(range(16))
    assert layers in (8, 6) and config["vocab_size"] == 128256 // 8
    rows = config["assumed"]["padded_vocab_size"]["value"]
    assert rows == 126 * 128 >= config["vocab_size"] > rows - 128
    m = family.build_model(config, rows, {"remat": "full"})
    assert (m.mla_kv_rank, m.mla_nope_dim, m.mla_rope_dim, m.mla_v_dim,
            m.mla_rope_theta, m.attn_heads) == (512, 128, 64, 128, 1e6, 32)
    assert m.layer_kinds == ("latent_attention",) * layers
    assert m.ffn_kinds == ("swiglu",) + ("moe",) * (layers - 1)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))["params"]

    def count(tree):
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(tree))

    parts = family.layer_parameters(config)
    assert parts == {"q": 12_582_912, "kv_a": 1_179_648, "kv_b": 4_194_304,
                     "o": 8_388_608, "dense": 37_748_736, "router": 262_144,
                     "shared": 9_437_184, "expert": 4_718_592}
    mla = parts["q"] + parts["kv_a"] + parts["kv_b"] + parts["o"]
    assert mla == 26_345_472
    assert count(shapes["block_0"]["mixer"]) == mla + 512
    assert count(shapes["block_0"]) == mla + 512 + parts["dense"] + 2 * 2048
    routed = parts["router"] + 128 + parts["shared"] + held * parts["expert"]
    assert count(shapes["block_1"]["ffn"]) == routed
    assert shapes["block_1"]["ffn"]["w_in"].shape == (held, 2048, 1536)
    assert shapes["lm_head"]["kernel"].shape == (2048, rows)
    total = count(shapes)
    assert total == (layers * (mla + 512 + 2 * 2048) + parts["dense"]
                     + (layers - 1) * routed + 2 * rows * 2048 + 2048)
    assert total == {8: 910_990_208, 6: 687_896_192}[layers]
    assert round(total * 10 / 2 ** 30, 2) == {8: 8.48, 6: 6.41}[layers]
    # three times the balanced 12,288 rows: an eighth of the experts is held
    assert moe.capacities(16384 * 6, held, 128) == (36864, 98304)
    # the other routed cells' are what they were
    assert moe.capacities(16384 * 4, 8, 32) == (32768, 65536)
    assert moe.capacities(4096 * 22, 8, 512) == (11264, 90112)
    assert moe.capacities(8192 * 10, 16, 256) == (20480, 81920)

    # 6 x the matrix elements a token touches, and the attention's scores
    here = 6 * held / 128
    elements = (layers * mla + parts["dense"] + (layers - 1) * (
        parts["router"] + parts["shared"] + here * parts["expert"])
        + rows * 2048)
    assert family.train_flops_per_token(config, rows, 16384) == pytest.approx(
        6 * elements + layers * 3 * 16384 * 32 * (192 + 128), rel=1e-9)
    assert family.expected_first_loss(config, rows) == pytest.approx(
        math.log(rows) + 2048 * 0.02 ** 2 / 2)
    costs = family.attention_train_costs(config, 1, 16384)
    assert costs == [mla_attention_cost.mla_attention_train_cost(
        1, 32, 16384, 192, 128)] * layers
    # no share of the experts' roofline while a run's routing is far from
    # the balanced rows that share divides by (the family's docstring)
    assert not hasattr(family, "moe_train_costs")
    plan = family.kernel_plan(config, 16384)
    assert plan["route"] == {
        "forward": "once", "step": "step",
        "backward": "fused", "backward_vmem": 35 * 2 ** 20}
    # whole 512 x 1024 tiles forward, 512 x 512 strips in the fused backward
    assert (plan["forward"], plan["backward"]) == pytest.approx(
        (1.0625, 1.03125), rel=1e-3)


def test_the_cell_is_sized_and_declared():
    cell = harness.load_cell("kanana2-train-s16384")
    assert (cell.chips, cell.job, cell.vocab_rows) == (1, "train_lm", 16128)
    assert (cell.mix["global_batch"], cell.mix["seq"], cell.mix["remat"],
            cell.mix["chunk_steps"], cell.mix["batches"]) == (
        1, 16384, "full", 2, 4)
    step = cell.spec["sizing"]["programs"]["train_step"]
    # over a quarter of the chip and under what a run can hold
    assert 0.25 * 16 < step["peak_estimate_gib"] < 14.6
    layers = cell.config["num_hidden_layers"]
    # three flash kernels a layer (flash_step, whose output remat full
    # keeps, dq, dkv); nine grouped products a routed layer and capacity
    assert step["pallas_calls"] == 3 * layers + (layers - 1) * 2 * 9
    declared = harness.declared_metrics(cell.name)
    names = {m["name"] for m in declared["per_layer"]}
    assert {"mla_latent_ms", "mla_assemble_ms", "flash_attention_roofline",
            "moe_experts_ms", "moe_route_ms", "moe_shared_ms",
            "lm_head_ms", "blocks_recompute_ms"} <= names
    assert not {"ssd_ms", "short_conv_ms", "moe_latent_ms", "attn_gate_ms",
                "attn_window_kernel_ms", "allreduce_ms",
                "moe_experts_roofline"} & names
    assert {m["name"] for m in declared["end_to_end"]} == {
        "train_tokens_per_s_chip", "setup_s"}


# ------------------------------ the four earlier hybrids: nothing of them moved
@pytest.mark.parametrize("name", ["granitemoehybrid", "lfm2_moe",
                                  "nemotron_h", "laguna"])
def test_the_earlier_hybrids_have_no_latent_attention(name):
    """Built as their families build them: the new fields at defaults that
    are the model there was, no parameter and no scope of the new mixer."""
    module = importlib.import_module(f"chipbench.families.{name}")
    config_file = {"granitemoehybrid": "granite-4.0-h-micro.json",
                   "lfm2_moe": "LFM2-8B-A1B.json",
                   "nemotron_h": "NVIDIA-Nemotron-3-Super-120B-A12B-BF16.json",
                   "laguna": "Laguna-S-2.1.json"}[name]
    config = {**harness.load_json("configs", config_file),
              **module.REHEARSAL}
    m = module.build_model(config, 512, {"remat": "full"})
    assert (m.mla_kv_rank, m.mla_nope_dim, m.mla_rope_dim, m.mla_v_dim,
            m.mla_rope_theta) == (0, 0, 0, 0, 0.0)
    assert "latent_attention" not in m.layer_kinds
    toks = jnp.zeros((1, 32), jnp.int32)
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0), toks)["params"]
    leaves = {jax.tree_util.keystr(path)
              for path, _ in jax.tree_util.tree_leaves_with_path(params)}
    assert not [l for l in leaves if "kv_a" in l or "kv_b" in l
                or "kv_norm" in l]
    text = jax.jit(jax.grad(lambda p: jnp.sum(m.apply({"params": p}, toks)))
                   ).lower(params).as_text(debug_info=True)
    for scope in ("mixer/latent", "mixer/assemble"):
        assert scope not in text, scope
