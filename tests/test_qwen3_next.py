"""``ops/gated_delta.gated_delta_chunked`` against the literal recurrence,
and ``models/hybrid.HybridLM`` as the Qwen3-Next family builds it (gated
delta-rule linear attention three layers in four, gated softmax attention
with a partial rotary turn the fourth, a softmax-routed expert feed-forward
beside a gated shared expert in every block; an untied head) against its
plain reference, ``chipbench/reference_qwen3_next.py``.

Small size, seeded weights with the norm weights randomised and the
matrices scaled so that each part of a block is as large as what it stands
beside. The model holds 2 of 8 experts (ids 1 and 6: not a prefix), three a
token; 2 key heads and 4 value heads of 32 in a delta-rule layer, 4 heads
over 2 KV heads of 64 (16 of them rotary) in an attention layer.
"""

import functools
import importlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from chipbench import flops, gated_delta_cost, harness
from chipbench import reference_qwen3_next as reference
from chipbench.families import qwen3_next as family
from horovod_tpu import spmd
from horovod_tpu.models import hybrid
from horovod_tpu.models.transformer import lm_loss
from horovod_tpu.ops import gated_delta, moe, ssd
from horovod_tpu.ops import pallas_kernels as pk
from tests.test_laguna import low, relative, worst_leaf

#: the configuration keys the family and the reference read, small: one
#: period
CONFIG = {"num_hidden_layers": 4, "full_attention_interval": 4,
          "hidden_size": 128, "intermediate_size": 320,
          "linear_num_key_heads": 2, "linear_num_value_heads": 4,
          "linear_key_head_dim": 32, "linear_value_head_dim": 32,
          "linear_conv_kernel_dim": 4, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 64,
          "partial_rotary_factor": 0.25, "rope_theta": 10000000,
          "rope_scaling": None, "rms_norm_eps": 1e-6, "num_experts": 2,
          "num_experts_published": 8, "held_experts": [1, 6],
          "num_experts_per_tok": 3, "moe_intermediate_size": 64,
          "shared_expert_intermediate_size": 64, "norm_topk_prob": True,
          "decoder_sparse_step": 1, "mlp_only_layers": [],
          "use_sliding_window": False, "tie_word_embeddings": False,
          "hidden_act": "silu", "vocab_size": 512,
          # four times the rms of the bf16 program's scores minus the
          # reference's at this size (1.2e-3 to 1.7e-3 around 1/8)
          "assumed": {"tie_tau": {"value": 6e-3}}}
ROWS = 512

#: float32 program against float32 reference: both round at 2^-24 and
#: differ in the order of their sums (chunks and a triangular inverse
#: against one position at a time, grouped rows against masked experts).
#: Measured 1e-6 in the logits and 2e-5 in the worst gradient leaf; bf16
#: operands read 1e-2 and 0.1.
F32_TOL = 2e-4


# ------------------------------------------- the op against the recurrence
def operands(t, b=2, heads=4, key_dim=16, value_dim=24, seed=0,
             dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (b, t, heads, key_dim))
    k = jax.random.normal(keys[1], (b, t, heads, key_dim))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (b, t, heads, value_dim))
    g = -2.0 * jax.nn.softplus(jax.random.normal(keys[3], (b, t, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, heads)))
    return tuple(a.astype(dtype) for a in (q, k, v)) + (g, beta)


def chunked(*ops, chunk=16, segment=4096):
    """The op at a chunk and a segment of the test's (the module's two
    sizes, put back after)."""
    sizes = gated_delta.CHUNK, gated_delta.SEGMENT
    gated_delta.CHUNK, gated_delta.SEGMENT = chunk, segment
    try:
        with jax.default_matmul_precision("highest"):
            return gated_delta.gated_delta_chunked(*ops)
    finally:
        gated_delta.CHUNK, gated_delta.SEGMENT = sizes


def _with_grads(fn, ops):
    weight = jnp.cos(jnp.arange(ops[2].size, dtype=jnp.float32)).reshape(
        ops[2].shape)
    return jax.value_and_grad(lambda *a: jnp.sum(weight * fn(*a)),
                              argnums=range(5))(*ops)


@pytest.mark.parametrize("t,chunk,segment", [
    (100, 16, 4096), (100, 16, 32), (64, 16, 4096), (64, 16, 16),
    (40, 64, 4096), (7, 8, 4096)])
def test_the_chunked_rule_is_the_recurrence_values_and_gradients(t, chunk,
                                                                 segment):
    """A ``T`` the chunk does not divide (100 over 16, 40 and 7 under one
    chunk) and one it does; six chunk boundaries in the first; in one
    segment, and in four whose gradient makes each again from the state it
    started with (100 over 32: the last padded; 64 over 16: a chunk each).
    The gradients of all five operands."""
    ops = operands(t)
    chunked_ = functools.partial(chunked, chunk=chunk, segment=segment)
    got = chunked_(*ops)
    want = reference.delta_rule(*ops)
    assert got.shape == want.shape == (2, t, 4, 24) and got.dtype == ops[0].dtype
    assert relative(got, want) <= 2e-6
    (_, grads), (_, ref_grads) = (
        _with_grads(chunked_, ops),
        _with_grads(reference.delta_rule, ops))
    for name, a, b in zip("qkvgb", grads, ref_grads):
        assert relative(a, b) <= 1e-5, name


def test_the_state_crosses_chunk_boundaries():
    """What a late position reads of the first chunk's writes: with no
    decay and a first key that nothing later overlaps, the value written
    at position 0 is read back whole at position 99, six chunks on."""
    q, k, v, g, beta = operands(100)
    first = jnp.zeros_like(k[:, 0]).at[..., 0].set(1.0)
    k = k.at[..., 0].set(0.0)
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).at[:, 0].set(first)
    q = q.at[:, 99].set(first)
    out = chunked(q, k, v, 0.0 * g, jnp.ones_like(beta))
    np.testing.assert_allclose(out[:, 99], v[:, 0], rtol=1e-5, atol=1e-5)
    assert relative(out, reference.delta_rule(q, k, v, 0.0 * g,
                                              jnp.ones_like(beta))) <= 2e-6


def test_a_strongly_negative_decay_neither_overflows_nor_loses_the_rule():
    """``g`` down to -4,000 a position (a cumulative sum of -60,000 over a
    chunk): every exponent the op takes is <= 0, so nothing overflows, no
    NaN in value or gradient, and the state is forgotten as the recurrence
    forgets it."""
    q, k, v, g, beta = operands(48)
    g = 1000.0 * g
    got, want = chunked(q, k, v, g, beta), reference.delta_rule(q, k, v, g,
                                                                beta)
    assert bool(jnp.all(jnp.isfinite(got))) and relative(got, want) <= 2e-6
    _, grads = _with_grads(chunked, (q, k, v, g, beta))
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in grads)


def _linear_attention(q, k, v, g, beta):
    """Gated linear attention, the recurrence with the read left out
    (``S_t = exp(g_t) S_{t-1} + k_t (beta_t v_t)^T``), as its sum."""
    cum = jnp.cumsum(g, axis=1)                                # [b, t, h]
    decay = jnp.exp(cum[:, :, None] - cum[:, None])           # [b, l, s, h]
    seen = jnp.tril(jnp.ones((q.shape[1],) * 2, bool))[None, :, :, None]
    scores = jnp.einsum("blhd,bshd->blsh", q, k) * jnp.where(seen, decay, 0.0)
    return jnp.einsum("blsh,bshe->blhe", scores, beta[..., None] * v)


def test_special_cases_no_write_no_decay_and_no_overlap():
    """``beta = 0`` writes nothing; ``g = 0`` is the plain delta rule (its
    recurrence, and with one repeated key and ``beta = 1`` the last value
    written is what is read); keys that never overlap make the correction
    vanish, which leaves gated linear attention."""
    q, k, v, g, beta = operands(40)
    with jax.default_matmul_precision("highest"):
        assert not np.any(np.asarray(chunked(q, k, v, g, 0.0 * beta)))
        plain = chunked(q, k, v, 0.0 * g, beta)
        assert relative(plain, reference.delta_rule(q, k, v, 0.0 * g,
                                                    beta)) <= 2e-6
        same = jnp.broadcast_to(k[:, :1], k.shape)
        read_back = chunked(same, same, v, 0.0 * g, jnp.ones_like(beta),
                            chunk=64)
        np.testing.assert_allclose(read_back, v, rtol=1e-4, atol=1e-4)
        # 16 positions, 16 orthogonal keys a head
        eye = jnp.broadcast_to(jnp.eye(16)[None, :, None, :], (2, 16, 4, 16))
        q16, _, v16, g16, beta16 = operands(16)
        assert relative(chunked(q16, eye, v16, g16, beta16),
                        _linear_attention(q16, eye, v16, g16, beta16)) <= 2e-6
        # with keys that do overlap the correction is the rule
        assert relative(chunked(q, k, v, 0.1 * g, beta),
                        _linear_attention(q, k, v, 0.1 * g, beta)) > 0.1


def test_the_inverse_is_forward_substitution_whatever_the_keys():
    """All ones under the diagonal (one key repeated, ``beta`` 1): the
    inverse is bidiagonal, and the recursion finds it exactly where powers
    of ``A`` (binomials up to 9e17 at 64) would not; a random system
    against numpy's inverse; a size that is no power of two refused."""
    ones = jnp.tril(jnp.ones((64, 64)), -1)
    want = np.eye(64) - np.eye(64, k=-1)
    np.testing.assert_array_equal(gated_delta.unit_lower_inverse(ones), want)
    a = jax.random.normal(jax.random.PRNGKey(0), (3, 32, 32))
    got = gated_delta.unit_lower_inverse(a)
    want = np.linalg.inv(np.eye(32) + np.tril(np.asarray(a, np.float64), -1))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    with pytest.raises(ValueError, match="power of two"):
        gated_delta.unit_lower_inverse(jnp.zeros((48, 48)))
    with pytest.raises(ValueError, match="no multiple of CHUNK"):
        chunked(*operands(8), chunk=16, segment=40)


def test_bf16_operands_accumulate_in_float32_and_return_bf16():
    ops = operands(100, dtype=jnp.bfloat16)
    got = gated_delta.gated_delta_chunked(*ops)
    assert got.dtype == jnp.bfloat16
    want = reference.delta_rule(*ops)
    assert 1e-4 < relative(got.astype(jnp.float32), want) < 2e-2


# --------------------------------- the kernel pair against the recurrence
@pytest.fixture
def interpreted(monkeypatch):
    """The Pallas pair through the interpreter: ``delta_fwd`` and
    ``delta_bwd``'s own bodies on the CPU."""
    monkeypatch.setenv("HVD_PALLAS", "interpret")


def wide(t, heads=2, **kw):
    """Operands the route admits: heads a lane width each way."""
    return operands(t, b=1, heads=heads, key_dim=128, value_dim=128, **kw)


def kernels(*ops):
    assert pk.kernel_path("gated_delta", *ops[:3]) == "pallas"
    return gated_delta.gated_delta_chunked(*ops)


#: t, heads, and whether a key head serves two value heads
PAIR_CASES = {"one_tile_of_one_chunk": (40, 2, False),
              "one_tile_of_a_pair": (128, 1, False),
              "several_tiles": (600, 4, False),
              "a_padded_length": (300, 3, False),
              "two_value_heads_to_a_key_head": (200, 4, True)}


@pytest.mark.parametrize("case", sorted(PAIR_CASES))
def test_the_kernel_pair_is_the_recurrence_values_and_gradients(
        case, interpreted):
    """``delta_fwd`` (and the variant that saves the tiles' entering states
    and the chunks' inverses) and ``delta_bwd`` against the literal
    recurrence, float32 operands: ``o`` and the gradients of all five
    operands. Under one chunk (the pair's second is padding), one pair
    exactly, three tiles of 256 (the last padded, four heads a cell), 300
    over 256 at three heads (one a cell), and q and k handed to two
    consecutive value heads each as the mixer hands them, whose gradients
    leave at the heads they came in at and are summed by the repeat's."""
    t, heads, shared = PAIR_CASES[case]
    route = pk.delta_route(t, heads, 128, 128, 4)
    assert route == {"path": "pallas", "tile": 128 if t <= 128 else 256,
                     "heads": {1: 1, 2: 2, 3: 1, 4: 4}[heads]}
    ops = wide(t, heads)
    if shared:
        ops = tuple(a[:, :, ::2] for a in ops[:2]) + ops[2:]

    def through(fn):
        def run(q, k, v, g, beta):
            if shared:
                q, k = (jnp.repeat(a, 2, axis=2) for a in (q, k))
            return fn(q, k, v, g, beta)
        return run

    got, want = through(kernels)(*ops), through(reference.delta_rule)(*ops)
    assert got.shape == want.shape == (1, t, heads, 128)
    assert got.dtype == ops[0].dtype and relative(got, want) <= 2e-6
    (_, grads), (_, ref_grads) = (_with_grads(through(kernels), ops),
                                  _with_grads(through(reference.delta_rule),
                                              ops))
    for name, a, b in zip("qkvgb", grads, ref_grads):
        assert a.shape == b.shape and relative(a, b) <= 1e-5, name


def test_the_state_crosses_tile_boundaries_in_vmem(interpreted):
    """The kernels' state from one tile to the next: with no decay and a
    first key that nothing later overlaps, the value written at position 0
    is read back whole at position 599, two tiles and nine chunks on; and
    its gradient comes back to ``v`` at position 0 through the backward
    kernel's state."""
    q, k, v, g, beta = wide(600)
    first = jnp.zeros_like(k[:, 0]).at[..., 0].set(1.0)
    k = k.at[..., 0].set(0.0)
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).at[:, 0].set(first)
    q = q.at[:, 599].set(first)
    g, beta = 0.0 * g, jnp.ones_like(beta)
    out = kernels(q, k, v, g, beta)
    np.testing.assert_allclose(out[:, 599], v[:, 0], rtol=1e-5, atol=1e-5)
    assert relative(out, reference.delta_rule(q, k, v, g, beta)) <= 2e-6
    dv = jax.grad(lambda v: jnp.sum(kernels(q, k, v, g, beta)[:, 599]))(v)
    np.testing.assert_allclose(dv[:, 0], jnp.ones_like(dv[:, 0]),
                               rtol=1e-5, atol=1e-5)


def test_the_kernels_neither_overflow_nor_lose_the_rule_under_a_strong_decay(
        interpreted):
    """``g`` down to -4,000 a position: every exponent the kernels take is
    <= 0 (the mask goes in before the ``exp``, a decay runs from a chunk's
    start or to its end), so nothing overflows, no NaN in value or
    gradient, and the state is forgotten as the recurrence forgets it."""
    q, k, v, g, beta = wide(200)
    g = 1000.0 * g
    got, want = kernels(q, k, v, g, beta), reference.delta_rule(q, k, v, g,
                                                                beta)
    assert bool(jnp.all(jnp.isfinite(got))) and relative(got, want) <= 2e-6
    _, grads = _with_grads(kernels, (q, k, v, g, beta))
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in grads)


def test_the_kernels_special_cases_and_repeated_keys(interpreted):
    """``beta = 0`` writes nothing; ``g = 0`` is the plain delta rule;
    one key repeated with ``beta = 1`` (the system all ones under the
    diagonal, whose inverse is bidiagonal: forward substitution whatever
    the keys) reads back the last value written; keys that never overlap
    leave gated linear attention."""
    q, k, v, g, beta = wide(140)
    assert not np.any(np.asarray(kernels(q, k, v, g, 0.0 * beta)))
    assert relative(kernels(q, k, v, 0.0 * g, beta),
                    reference.delta_rule(q, k, v, 0.0 * g, beta)) <= 2e-6
    same = jnp.broadcast_to(k[:, :1], k.shape)
    read_back = kernels(same, same, v, 0.0 * g, jnp.ones_like(beta))
    np.testing.assert_allclose(read_back, v, rtol=1e-4, atol=1e-4)
    eye = jnp.broadcast_to(jnp.eye(128, dtype=q.dtype)[None, :, None, :],
                           (1, 128, 2, 128))
    q128, _, v128, g128, beta128 = wide(128)
    assert relative(kernels(q128, eye, v128, g128, beta128),
                    _linear_attention(q128, eye, v128, g128, beta128)) <= 2e-6
    # (random keys of 128 overlap by 0.09, those of 16 by 0.25: 0.08 here)
    assert relative(kernels(q, k, v, 0.1 * g, beta),
                    _linear_attention(q, k, v, 0.1 * g, beta)) > 0.05


def test_the_kernels_bf16_operands_stay_by_the_chunked_form(interpreted,
                                                            monkeypatch):
    """bf16 operands: the kernels round where the XLA form rounds (the
    inverse, ``W``, the corrected values and the masked scores cast at the
    MXU's operand, the state float32), so ``o`` is the chunked form's to a
    bf16 rounding or two, and as far from the float32 recurrence as it
    is; the gradients, whose cotangents the kernel keeps float32 for
    longer, within a bf16 rounding's reach of the XLA form's."""
    ops = wide(300, dtype=jnp.bfloat16)
    got = kernels(*ops)
    (_, grads) = _with_grads(kernels, ops)
    monkeypatch.setenv("HVD_PALLAS", "0")
    assert pk.kernel_path("gated_delta", *ops[:3]) == "reference"
    form = gated_delta.gated_delta_chunked(*ops)
    (_, form_grads) = _with_grads(gated_delta.gated_delta_chunked, ops)
    assert got.dtype == form.dtype == jnp.bfloat16
    f32 = jnp.float32
    assert relative(got.astype(f32), form.astype(f32)) <= 3e-3
    want = reference.delta_rule(*ops)
    assert 1e-4 < relative(got.astype(f32), want) < 2e-2
    for name, a, b in zip("qkvgb", grads, form_grads):
        assert a.dtype == b.dtype
        assert relative(a.astype(f32), b.astype(f32)) <= 2e-2, name


# ------------------------------------------------ which path a call takes
#: key width, value width, dtype, HVD_PALLAS -> the path
ROUTE_CASES = {
    "published_on": (128, 128, jnp.bfloat16, "on", "pallas"),
    "published_interpreted": (128, 128, jnp.bfloat16, "interpret", "pallas"),
    "published_float32": (128, 128, jnp.float32, "on", "pallas"),
    "published_off": (128, 128, jnp.bfloat16, "0", "reference"),
    "published_off_the_chip": (128, 128, jnp.bfloat16, "", "reference"),
    "no_lane_width_of_keys": (64, 128, jnp.bfloat16, "on", "reference"),
    "no_lane_width_of_values": (128, 96, jnp.bfloat16, "on", "reference"),
    "two_lane_widths": (256, 256, jnp.bfloat16, "on", "reference"),
    "eight_byte_elements": (128, 128, jnp.float64, "on", "reference")}


@pytest.mark.parametrize("case", sorted(ROUTE_CASES))
def test_kernel_path_says_which_rule_runs(case, monkeypatch):
    """The published shape (32 heads of 128 / 128 over 16,384 positions,
    bf16) on the chip, interpreted, switched off and off the chip; what the
    route refuses: a head that is not one lane width each way, 8-byte
    elements."""
    dk, dv, dtype, env, path = ROUTE_CASES[case]
    monkeypatch.setenv("HVD_PALLAS", env)
    q, k, v = (jax.ShapeDtypeStruct((1, 16384, 32, d), dtype)
               for d in (dk, dk, dv))
    assert pk.kernel_path("gated_delta", q, k, v) == path
    route = pk.delta_route(16384, 32, dk, dv, jnp.dtype(dtype).itemsize)
    assert (route["path"] == "pallas") == (path == "pallas"
                                           or env in ("0", ""))
    if route["path"] == "pallas":
        assert route == {"path": "pallas", "tile": 256, "heads": 8}
    else:
        assert route == {"path": "reference", "tile": None, "heads": None}


def test_varying_operands_are_not_the_kernels(monkeypatch):
    """Under ``shard_map(check_vma=True)`` a ``pallas_call`` cannot meet
    the checker's rules, so ``kernel_path`` hands varying operands to the
    XLA form; the same call with the check off keeps the kernels."""
    from jax.sharding import PartitionSpec as P

    monkeypatch.setenv("HVD_PALLAS", "interpret")
    mesh = jax.make_mesh((2,), ("x",), devices=jax.devices()[:2])
    ops = operands(16, b=2, heads=1, key_dim=128, value_dim=128)
    seen = []

    def body(*a):
        seen.append(pk.kernel_path("gated_delta", *a[:3]))
        return kernels(*a) if seen[-1] == "pallas" else a[2]

    for check, path in ((True, "reference"), (False, "pallas")):
        out = jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                            out_specs=P("x"), check_vma=check)(*ops)
        assert seen[-1] == path
    assert relative(out, reference.delta_rule(*ops)) <= 2e-6


def test_the_gated_norm_in_its_other_order():
    y = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 4, 32))
    gate = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 4, 32))
    scale = 1.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(2), (32,))
    got = ssd.gated_rms_norm(y, gate, scale, 1e-6, norm_first=True)
    want = reference._rms_norm(y, scale, 1e-6) * reference._silu(gate)
    assert relative(got, want) <= 1e-6
    # the order there was is the default, and another function
    first = ssd.gated_rms_norm(y, gate, scale, 1e-6)
    assert relative(first, reference._rms_norm(y * reference._silu(gate),
                                               scale, 1e-6)) <= 1e-6
    assert relative(first, want) > 0.1


# ---------------------------------------------------- model against reference
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
        # quarter: scaled so that the gates and the decay leave 1/2, the
        # attention's scores spread, a mixer's update and the routed
        # experts' are as large as what they stand beside, and the router
        # is not flat
        if any(k in name for k in ("in_proj", "in_gates", "out_proj", "['q']",
                                   "['k']", "['v']", "['o']", "['gate']",
                                   "shared_", "router", "w_in", "w_out")):
            return 4.0 * leaf
        if leaf.ndim >= 2 or "expert_bias" in name:
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


def test_logits_loss_and_every_gradient_match_the_reference(seq=100):
    """100 positions: a chunk and a part of one in every delta-rule layer."""
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


def test_the_mixer_alone_and_its_gradients_match_the_reference():
    """The delta-rule mixer outside any block, its output and its
    gradients in every parameter and its input."""
    params = randomised_params()["block_1"]["mixer"]
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 70, 128), jnp.float32)
    mixer = hybrid.GatedDeltaMixer(2, 4, 32, 32, 4, 1e-6, jnp.float32)

    def program(p, h):
        return mixer.apply({"params": p}, h)

    def plain(p, h):
        with jax.default_matmul_precision("highest"):
            return reference._gated_delta(p, h, 2, 4, 32, 32, 1e-6)

    def loss(fn):
        return lambda p, h: jnp.sum(jnp.sin(fn(p, h)))

    assert relative(program(params, h), plain(params, h)) <= 2e-6
    got = jax.grad(loss(program), (0, 1))(params, h)
    want = jax.grad(loss(plain), (0, 1))(params, h)
    path, error = worst_leaf(got, want)
    assert error <= 2e-5, (jax.tree_util.keystr(path), error)
    assert set(got[0]) == {"in_proj", "in_gates", "conv", "A_log", "dt_bias",
                           "gate_norm", "out_proj"}


def test_the_parameter_tree_is_the_published_layers():
    """A delta-rule layer: ``[q | k | v | z]`` of 2 x 32, 2 x 32, 4 x 32,
    4 x 32, ``[b | a]`` of 4 + 4, the conv's 4 taps over q, k and v with no
    bias, a decay rate and a step bias a value head, one ``[32]`` norm
    weight, out from 4 x 32. An attention layer: q and the gate 4 x 64
    each, k and v 2 x 64, the two head norms, o. Every block: the router
    over 8, the held experts, the shared expert and its gate of width 1. An
    untied head beside the table."""
    shapes = jax.tree_util.tree_map(lambda l: l.shape, randomised_params())
    assert set(shapes) == {"tok_emb", "norm_f", "lm_head"} | {
        f"block_{i}" for i in range(4)}
    delta = {"in_proj": {"kernel": (128, 64 + 64 + 128 + 128)},
             "in_gates": {"kernel": (128, 8)},
             "conv": {"kernel": (4, 256)}, "A_log": (4,), "dt_bias": (4,),
             "gate_norm": {"scale": (32,)},
             "out_proj": {"kernel": (128, 128)}}
    attention = {"q": {"kernel": (128, 256)}, "gate": {"kernel": (128, 256)},
                 "k": {"kernel": (128, 128)}, "v": {"kernel": (128, 128)},
                 "q_norm": (64,), "k_norm": (64,),
                 "o": {"kernel": (256, 128)}}
    rest = {"norm_mixer": {"scale": (128,)}, "norm_ffn": {"scale": (128,)},
            "ffn": {"router": (128, 8), "expert_bias": (8,),
                    "w_in": (2, 128, 128), "w_out": (2, 64, 128),
                    "shared_in": {"kernel": (128, 128)},
                    "shared_out": {"kernel": (64, 128)},
                    "shared_gate": {"kernel": (128, 1)}}}
    for i in range(4):
        assert shapes[f"block_{i}"] == {
            **rest, "mixer": attention if i == 3 else delta}, i
    assert shapes["lm_head"] == {"kernel": (128, 512)}
    built = model()
    assert built.layer_kinds == ("gated_delta",) * 3 + ("full_attention",)
    assert (built.moe_scoring, built.moe_top_k, built.moe_scale,
            built.moe_norm_eps, built.moe_shared_gate, built.attn_gate,
            built.tied_head, dict(built.attn_kinds)) == (
        "softmax", 3, 1.0, 0.0, True, "channel", False,
        {"full_attention": {"rotary_dim": 16}})
    # the decay rates start as MambaMixer's, A = 1..heads; the steps
    # between 0.001 and 0.1, evenly in the logarithm: a state that remembers
    fresh = model().init(jax.random.PRNGKey(0), tokens(8)[0])["params"]
    np.testing.assert_allclose(np.exp(fresh["block_0"]["mixer"]["A_log"]),
                               [1, 2, 3, 4], rtol=1e-6)
    np.testing.assert_allclose(
        jax.nn.softplus(fresh["block_0"]["mixer"]["dt_bias"]),
        [1e-3, 10 ** -(7 / 3), 10 ** -(5 / 3), 1e-1], rtol=1e-5)


def test_the_new_scopes_are_in_the_compiled_program():
    m = model(remat="full")
    toks = tokens(32)[0]
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0), toks)["params"]
    text = jax.jit(jax.grad(lambda p: jnp.sum(m.apply({"params": p}, toks)))
                   ).lower(params).as_text(debug_info=True)
    for scope in ("block_0/mixer/delta_rule", "block_2/mixer/delta_rule",
                  "block_1/mixer/prep/conv", "block_0/mixer/in_proj",
                  "block_0/mixer/in_gates", "block_2/mixer/gate_norm",
                  "block_1/mixer/out_proj", "block_3/mixer/gate",
                  "block_3/mixer/qk_norm", "block_3/mixer/rope",
                  "block_0/ffn/shared_gate", "block_3/ffn/shared_in",
                  "block_1/ffn/moe/router", "lm_head",
                  "rematted_computation/block_2"):
        assert scope in text, scope
    for scope in ("block_3/mixer/delta_rule", "block_3/mixer/prep",
                  "block_0/mixer/gate/", "mixer/window", "mixer/latent",
                  "mixer/ssd", "tok_emb.attend"):
        assert scope not in text, scope
    # what the readers match
    for reader, part in (("delta_rule_ms", "delta_rule"),
                         ("delta_rule_prep_ms", "prep"),
                         ("attn_gate_ms", "gate")):
        pattern = importlib.import_module(
            f"chipbench.layer_metrics.{reader}").PATTERN
        assert re.search(pattern, f"jit(f)/block_1/mixer/{part}/dot_general")
        assert re.search(pattern, f"transpose(jvp(block_1))/mixer/{part}")
        assert not re.search(pattern, f"jit(f)/block_1/mixer/{part}_norm/mul")
        assert not re.search(pattern, f"jit(f)/block_1/ffn/{part}/dot")


# ------------------------------------------------------------- the routing
def layer_params(seed=3, d=32, f=24, shared=24, experts=32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = jax.random.normal
    return {"router": 0.5 * normal(keys[0], (d, experts)),
            "expert_bias": jnp.zeros((experts,)),
            "w_in": 0.3 * normal(keys[1], (experts, d, 2 * f)),
            "w_out": 0.3 * normal(keys[2], (experts, f, d)),
            "shared_in": {"kernel": 0.2 * normal(keys[3], (d, 2 * shared))},
            "shared_out": {"kernel": 0.2 * normal(keys[4], (shared, d))},
            "shared_gate": {"kernel": 0.5 * normal(keys[5], (d, 1))}}


def share_of(params, held):
    index = jnp.asarray(held)
    return {**params, "w_in": params["w_in"][index],
            "w_out": params["w_out"][index]}


def layer(params, h, held):
    """``RoutedFeedForward`` as the family builds it, holding ``held``."""
    module = hybrid.RoutedFeedForward(
        32, tuple(held), 10, 24, jnp.float32, shared_width=24, norm_eps=0.0,
        scoring="softmax", shared_gate=True)
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, h: module.apply({"params": p}, h))(
            share_of(params, held), h)


def reference_layer(params, h, held):
    with jax.default_matmul_precision("highest"):
        return reference._routed(share_of(params, held), h, tuple(held), 10,
                                 None, 0.0)[0]


def test_the_32_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """Each of 32 chips holds one of the thirty-two experts and computes
    the gated shared expert whole; the routed parts of the 32, with the
    shared expert counted once, are what the reference gives holding every
    expert."""
    params = layer_params()
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 48, 32))
    shares = [(i,) for i in range(32)]
    with jax.default_matmul_precision("highest"):
        shared = reference._sigmoid(h @ params["shared_gate"]["kernel"]) \
            * reference._swiglu(h, params["shared_in"]["kernel"],
                                params["shared_out"]["kernel"])
    whole = reference_layer(params, h, range(32))
    parts = [layer(params, h, held) for held in shares]
    # (32 float32 differences summed: 2e-6 measured)
    assert relative(sum(p - shared for p in parts) + shared, whole) <= 1e-5
    assert relative(sum(reference_layer(params, h, held) - shared
                        for held in shares[:3])
                    + sum(p - shared for p in parts[3:]) + shared,
                    whole) <= 1e-5
    # a token's ten weights sum to 1 over all the shares
    weights = moe.route(h[0] @ params["router"], params["expert_bias"], 10,
                        1.0, 0.0, "softmax")[1]
    np.testing.assert_allclose(jnp.sum(weights, -1), 1.0, rtol=1e-6)
    # one share alone is not the layer, nor are the 32 with the shared
    # expert counted 32 times, nor the layer with the shared expert ungated
    assert relative(parts[0], whole) > 0.1
    assert relative(sum(parts), whole) > 0.1
    ungated = hybrid.RoutedFeedForward(
        32, tuple(range(32)), 10, 24, jnp.float32, shared_width=24,
        norm_eps=0.0, scoring="softmax")
    with jax.default_matmul_precision("highest"):
        plain = ungated.apply({"params": {k: v for k, v in params.items()
                                          if k != "shared_gate"}}, h)
    assert relative(plain, whole) > 0.05


# -------------------------------------------------------------- controls
def test_control_bf16_operands_fail_the_float32_tolerance():
    """The same comparison one precision lower: over F32_TOL by far."""
    logits, _, grads = program_side(100, jnp.bfloat16)
    ref_logits, _, ref_grads = reference_side(100)
    assert relative(logits, ref_logits) > 10 * F32_TOL
    assert worst_leaf(grads, ref_grads)[1] > 10 * F32_TOL


def chip_check(params, toks, program=None, program_params=None):
    """What ``jobs/train_lm.check_logits`` computes for this family: the
    program's bf16 logits (one jitted program, as the job's) against
    ``family.reference_forward``; NaN where the reference refuses the
    program."""
    m = model(jnp.bfloat16) if program is None else program
    got = jax.jit(lambda p, t: m.apply({"params": p}, t))(
        params if program_params is None else program_params, toks)
    want = family.reference_forward(params, toks, CONFIG)
    return relative(got, want) if bool(jnp.all(jnp.isfinite(want))) \
        else float("nan")


def test_the_chips_check_passes_a_sound_bf16_program():
    """Under the job's 2% (chipbench/jobs/train_lm.LOGIT_RMS_TOL)."""
    assert chip_check(randomised_params(), tokens(100)[0]) <= 0.02


def test_control_four_bit_operands_fail_the_chips_check():
    """Every matrix rounded to 4 bits of mantissa (e4m3's) in the program
    only: the blocks' updates leave their limit."""
    params = randomised_params()
    coarse = jax.tree_util.tree_map(
        lambda l: low(l, 4) if l.ndim >= 2 else l, params)
    assert not chip_check(params, tokens(100)[0],
                          program_params=coarse) <= 0.02


def test_the_stream_checked_is_the_one_under_the_jobs_logits():
    """``program_trace`` hands out every block's output through Flax's
    capture beside the routing, from the family's model, whose stream is
    held at every block's boundary (``pin_stream``): the program that hands
    the stream out computes the plain program's logits to the bit, every
    block's update is held to the reference's, the last block's too, and a
    model built without the hold has no barrier in its program (the held
    model differentiated, with and without recomputation:
    ``test_a_recomputed_model_agrees``)."""
    params, toks = randomised_params(), tokens(100)[0]
    logits, outputs, routing = family.program_trace(params, toks, CONFIG)
    assert len(outputs) == 4 and sorted(routing) == [
        f"block_{i}" for i in range(4)]
    assert all(x.dtype == jnp.bfloat16 and x.shape == toks.shape + (128,)
               for x in outputs)
    assert routing["block_3"]["chosen"].shape == toks.shape + (3,)
    held = model(jnp.bfloat16)
    assert held.pin_stream
    plain = jax.jit(lambda p, t: held.apply({"params": p}, t))(params, toks)
    np.testing.assert_array_equal(logits, plain)
    want, stats = reference.forward_from_program(
        params, toks, CONFIG, outputs, routing,
        CONFIG["assumed"]["tie_tau"]["value"])
    assert all(float(layer["update_error"]) < 0.03 for layer in stats)
    assert relative(plain, want) < 0.02

    def barriers(m):
        return jax.jit(lambda p, t: m.apply({"params": p}, t)).lower(
            params, toks).as_text().count("optimization_barrier")

    assert barriers(held) == 5 and barriers(held.clone(pin_stream=False)) == 0


def uncorrected(q, k, v, g, beta):
    """The delta rule with its correction dropped: ``S_t = exp(g_t)
    S_{t-1} + k_t (beta_t v_t)^T``, gated linear attention, position by
    position (so that the chip's control can run it at 16,384)."""
    def position(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[..., None, None] * state \
            + k_t[..., :, None] * (beta_t[..., None] * v_t)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    start = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[3:], jnp.float32)
    _, out = jax.lax.scan(position, start, tuple(
        jnp.moveaxis(a.astype(jnp.float32), 1, 0)
        for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1).astype(q.dtype)


def undecayed(q, k, v, g, beta):
    """The delta rule with its decay dropped: ``g = 0``."""
    return gated_delta.gated_delta_chunked(q, k, v, 0.0 * g, beta)


#: a program that differs from the model in one thing the reference holds
#: it to: fields of the model, or a patch of ``models/hybrid``'s op (what
#: the chip's controls run: PERF.md section 6, PR 46)
WRONG = {
    "the_correction_dropped": {"gated_delta_chunked": uncorrected},
    "the_decay_dropped": {"gated_delta_chunked": undecayed},
    "the_attention_gate_dropped": {"attn_gate": False},
    "the_whole_head_turned": {"attn_kinds": {"full_attention": {
        "rope_factor": 1.0}}},
    "an_ungated_shared_expert": {"moe_shared_gate": False},
    "sigmoid_scores": {"moe_scoring": "sigmoid"},
}


def wrong_program(name, monkeypatch):
    """The family's builder patched to build the program ``WRONG[name]``
    describes (``family.program_trace`` runs the program, so it has to be
    the family's)."""
    sound = family.build_model
    changes = dict(WRONG[name])
    if "gated_delta_chunked" in changes:
        monkeypatch.setattr(hybrid, "gated_delta_chunked",
                            changes.pop("gated_delta_chunked"))
    monkeypatch.setattr(family, "build_model",
                        lambda *a: sound(*a).clone(**changes))


@pytest.mark.parametrize("name", sorted(WRONG))
def test_control_a_wrong_program_fails_the_chips_check(name, monkeypatch):
    """(A model without a gate leaves the gate's parameters unread.)"""
    wrong_program(name, monkeypatch)
    wrong = family.build_model(CONFIG, ROWS, {}).clone(dtype=jnp.bfloat16)
    assert not chip_check(randomised_params(), tokens(100)[0],
                          program=wrong) <= 0.02, name


def test_what_the_family_and_the_model_do_not_build_is_refused():
    toks = tokens(8)[0]
    for key, value in (("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
                       ("norm_topk_prob", False),
                       ("rope_scaling", {"type": "yarn"}),
                       ("use_sliding_window", True),
                       ("tie_word_embeddings", True)):
        with pytest.raises(harness.BenchmarkError, match="qwen3_next"):
            family.build_model({**CONFIG, key: value}, ROWS, {})

    def init(**changes):
        return jax.eval_shape(model().clone(**changes).init,
                              jax.random.PRNGKey(0), toks)

    with pytest.raises(ValueError, match="layer_kinds"):
        init(layer_kinds=("delta",) * 4)
    with pytest.raises(ValueError, match="gate="):
        init(attn_gate="element")
    for field, value in (("delta_key_heads", 0), ("delta_value_heads", 3),
                         ("delta_key_dim", 0), ("delta_value_dim", 0)):
        with pytest.raises(ValueError, match="gated_delta layers need"):
            init(**{field: value})


# ---------------------------------------------------- remat, training, count
def test_a_recomputed_model_agrees():
    params, (toks, targets) = randomised_params(), tokens(72)

    def loss_and_grads(remat):
        m = model(remat=remat)
        return jax.jit(jax.value_and_grad(lambda p: lm_loss(
            m.apply({"params": p}, toks), targets)))(params)

    base_loss, base_grads = loss_and_grads("none")
    loss, grads = loss_and_grads("full")     # the cell's
    assert abs(float(loss) - float(base_loss)) <= 1e-6 * float(base_loss)
    # (float32 sums in another order where a block is run again: 3e-5)
    assert worst_leaf(grads, base_grads)[1] <= 1e-4


def test_a_recomputed_model_agrees_on_the_kernel_path(interpreted,
                                                      monkeypatch):
    """The same with heads the route admits (two of 128 / 128 to a key
    head; one delta-rule layer and one of attention) and the kernels through the interpreter:
    under ``remat="full"`` the recomputed block runs the forward that saves
    and the backward kernel reads what it saved; and the model with the
    kernels is the model without."""
    wide_config = dict(CONFIG, num_hidden_layers=2, full_attention_interval=2,
                       linear_num_key_heads=1, linear_num_value_heads=2,
                       linear_key_head_dim=128, linear_value_head_dim=128)
    toks, targets = tokens(72, batch=1)
    params = model(config=wide_config).init(jax.random.PRNGKey(1),
                                            toks)["params"]

    def loss_and_grads(remat):
        m = model(remat=remat, config=wide_config)
        return jax.jit(jax.value_and_grad(lambda p: lm_loss(
            m.apply({"params": p}, toks), targets)))(params)

    base_loss, base_grads = loss_and_grads("none")
    loss, grads = loss_and_grads("full")
    assert abs(float(loss) - float(base_loss)) <= 1e-6 * float(base_loss)
    assert worst_leaf(grads, base_grads)[1] <= 1e-4
    monkeypatch.setenv("HVD_PALLAS", "0")
    jax.clear_caches()
    form_loss, form_grads = loss_and_grads("full")
    assert abs(float(loss) - float(form_loss)) <= 1e-5 * float(form_loss)
    assert worst_leaf(grads, form_grads)[1] <= 2e-4


def test_three_train_steps_on_the_mesh_reproduce_the_reference_losses():
    """``spmd.make_train_step`` + ``lm_loss`` + the job's AdamW as
    ``chipbench/jobs/train_lm.build`` calls them, batch 8 over the 8-device
    mesh, against the reference's own loss and gradients under the same
    optimizer."""
    hvd.init()
    mesh = hvd.mesh()
    m = model()
    params, batch = randomised_params(), tokens(72, batch=8, seed=7)

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
    # the parameters the three steps left are the reference's
    assert worst_leaf(jax.tree_util.tree_map(np.asarray, p), rp)[1] <= F32_TOL


def test_the_family_counts_what_the_issue_counted():
    """The published widths: 771.2 M parameters here (626.0 M at the
    fallback's one period with 32 held), the matrix elements a token
    touches, the rooflines' operations; the file against the catalog's
    row."""
    config = harness.load_json("configs", "Qwen3-Next-80B-A3B-Instruct.json")
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == set(config["reduced"])
    assert (config["num_hidden_layers_published"],
            config["num_experts_published"],
            config["vocab_size_published"]) == (48, 512, 151936)
    assert "multi_token_prediction" in config["changed"]
    held, layers = len(config["held_experts"]), config["num_hidden_layers"]
    assert (layers, held) in ((8, 16), (4, 32))
    assert held == config["num_experts"]
    assert config["held_experts"] == list(range(held))
    assert config["vocab_size"] == 151936 // 8
    rows = config["assumed"]["padded_vocab_size"]["value"]
    assert rows == 149 * 128 >= config["vocab_size"] > rows - 128
    m = family.build_model(config, rows, {"remat": "full"})
    assert (m.delta_key_heads, m.delta_value_heads, m.delta_key_dim,
            m.delta_value_dim, m.ssm_conv_width) == (16, 32, 128, 128, 4)
    assert (m.attn_heads, m.attn_kv_heads, m.attn_head_dim,
            m.attn_rope_theta, dict(m.attn_kinds)) == (
        16, 2, 256, 1e7, {"full_attention": {"rotary_dim": 64}})
    assert m.layer_kinds == (("gated_delta",) * 3 + ("full_attention",)) \
        * (layers // 4)
    assert m.ffn_kinds == ("moe",) * layers
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))["params"]

    def count(tree):
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(tree))

    parts = family.layer_parameters(config)
    assert parts == {
        "delta_in": 25_165_824, "delta_gates": 131_072,
        "delta_out": 8_388_608, "attn_q_gate": 16_777_216,
        "attn_kv": 2_097_152, "attn_o": 8_388_608, "router": 1_048_576,
        "shared": 3_145_728, "shared_gate": 2048, "expert": 3_145_728}
    delta = parts["delta_in"] + parts["delta_gates"] + parts["delta_out"]
    attention = parts["attn_q_gate"] + parts["attn_kv"] + parts["attn_o"]
    assert (delta, attention) == (33_685_504, 27_262_976)
    # beside the matrices: the conv's 4 x 8192 taps, a decay rate and a
    # step bias a value head, the gated norm's [128]; the two head norms
    assert count(shapes["block_0"]["mixer"]) == delta + 4 * 8192 + 64 + 128
    assert count(shapes["block_3"]["mixer"]) == attention + 2 * 256
    routed = parts["router"] + 512 + parts["shared"] + parts["shared_gate"] \
        + held * parts["expert"]
    assert count(shapes["block_1"]["ffn"]) == routed
    assert shapes["block_1"]["ffn"]["w_in"].shape == (held, 2048, 1024)
    assert shapes["lm_head"]["kernel"].shape == (2048, rows)
    total = count(shapes)
    assert total == (
        layers * 3 // 4 * (delta + 4 * 8192 + 64 + 128)
        + layers // 4 * (attention + 512) + layers * (routed + 2 * 2048)
        + 2 * rows * 2048 + 2048)
    assert round(total / 1e6, 1) == {8: 771.2, 4: 626.0}[layers]
    assert round(total * 10 / 2 ** 30, 2) == {8: 7.18, 4: 5.83}[layers]
    # five times the balanced 5,120 rows: a 32nd of the experts is held
    assert moe.capacities(16384 * 10, 16, 512) == (25600, 163840)

    # 6 x the matrix elements a token touches, the attention's scores and
    # the recurrence's own operations
    here = 10 * held / 512
    elements = (layers * 3 // 4 * delta + layers // 4 * attention
                + layers * (parts["router"] + parts["shared"]
                            + parts["shared_gate"] + here * parts["expert"])
                + rows * 2048)
    assert family.train_flops_per_token(config, rows, 16384) == pytest.approx(
        6 * elements + layers // 4 * 6 * 16384 * 16 * 256
        + layers * 3 // 4 * 21 * 32 * 128 * 128, rel=1e-9)
    assert family.expected_first_loss(config, rows) == pytest.approx(
        math.log(rows) + 2048 * 0.02 ** 2 / 2)
    costs = family.attention_train_costs(config, 1, 16384)
    assert costs == [flops.flash_attention_train_cost(
        1, 16, 16384, 256, kv_heads=2)] * (layers // 4)
    rule = family.gdn_train_costs(config, 1, 16384)
    assert rule == [gated_delta_cost.gated_delta_train_cost(
        1, 16384, 16, 32, 128, 128)] * (layers * 3 // 4)
    # 7 operations an element of the state forward, twice that backward;
    # q, k at their 16 heads, v and o at 32, g and beta float32: 24,832
    # bytes a token forward, 41,472 backward; memory-bound on a v5e
    assert rule[0]["flops"] == 21.0 * 16384 * 32 * 128 * 128
    assert rule[0]["bytes"] == 16384 * (24_832 + 41_472)
    assert rule[0]["bytes"] / 819e9 > rule[0]["flops"] / 197e12
    # no share of the experts' roofline (the family's docstring)
    assert not hasattr(family, "moe_train_costs")
    assert family.flash_route(config, 16384) == {
        "forward": "once", "step": "step", "backward": "fused",
        "backward_vmem": 37 * 2 ** 20}
    assert pk._kv_vmem(16384, 256, 256, 2) == 32 * 2 ** 20


def test_the_cell_is_sized_and_declared():
    cell = harness.load_cell("qwen3next-train-s16384")
    assert (cell.chips, cell.job, cell.vocab_rows) == (1, "train_lm", 19072)
    assert (cell.mix["global_batch"], cell.mix["seq"], cell.mix["remat"],
            cell.mix["chunk_steps"], cell.mix["batches"]) == (
        1, 16384, "full", 2, 4)
    step = cell.spec["sizing"]["programs"]["train_step"]
    # over a quarter of the chip and under what a run can hold
    assert 0.25 * 16 < step["peak_estimate_gib"] < 14.6
    layers = cell.config["num_hidden_layers"]
    # two flash kernels a full layer (flash_fwd, whose output remat full
    # keeps, and flash_bwd); nine grouped products a routed layer and
    # capacity: the benchmark file's reading, PR 46's, when the delta rule
    # had no kernel (166 with PR 47's three a delta-rule layer, which
    # tests/test_tpu_lowering.py counts; the file is the benchmark's)
    assert step["pallas_calls"] == 2 * (layers // 4) + layers * 2 * 9
    declared = harness.declared_metrics(cell.name)
    names = {m["name"] for m in declared["per_layer"]}
    assert {"delta_rule_ms", "delta_rule_roofline", "delta_rule_prep_ms",
            "flash_attention_roofline", "attn_gate_ms", "moe_experts_ms",
            "moe_route_ms", "moe_shared_ms", "lm_head_ms",
            "blocks_recompute_ms"} <= names
    assert not {"ssd_ms", "short_conv_ms", "moe_latent_ms", "mla_latent_ms",
                "attn_window_kernel_ms", "allreduce_ms",
                "moe_experts_roofline"} & names
    assert {m["name"] for m in declared["end_to_end"]} == {
        "train_tokens_per_s_chip", "setup_s"}
    # the delta rule's three metrics are this cell's alone
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        new = [m for m in json.load(f)["per_layer"]
               if m["name"].startswith("delta_rule_")]
    assert len(new) == 3
    for metric in new:
        assert metric["workloads"] == [cell.name], metric["name"]
        assert metric["layer"] == "gated delta rule " \
            "(ops/gated_delta.gated_delta_chunked)"


# ------------------------------- the five earlier hybrids: nothing of them moved
@pytest.mark.parametrize("name", ["granitemoehybrid", "lfm2_moe",
                                  "nemotron_h", "laguna", "deepseek_v3"])
def test_the_earlier_hybrids_trees_and_scope_paths_are_unchanged(name):
    """Built as their families build them: the new fields at defaults that
    are the model there was (a head's gate where there was one, no gate on a
    shared expert, no delta-rule layer), no parameter and no scope of the
    new parts, and every leaf of the tree where it was."""
    module = importlib.import_module(f"chipbench.families.{name}")
    config_file = {
        "granitemoehybrid": "granite-4.0-h-micro.json",
        "lfm2_moe": "LFM2-8B-A1B.json",
        "nemotron_h": "NVIDIA-Nemotron-3-Super-120B-A12B-BF16.json",
        "laguna": "Laguna-S-2.1.json",
        "deepseek_v3": "kanana-2-30b-a3b-instruct-2601.json"}[name]
    config = {**harness.load_json("configs", config_file),
              **module.REHEARSAL}
    m = module.build_model(config, 512, {"remat": "full"})
    assert (m.delta_key_heads, m.delta_value_heads, m.delta_key_dim,
            m.delta_value_dim, m.moe_shared_gate) == (0, 0, 0, 0, False)
    assert m.attn_gate is (name == "laguna")
    assert "gated_delta" not in m.layer_kinds
    toks = jnp.zeros((1, 32), jnp.int32)
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0), toks)["params"]
    leaves = {jax.tree_util.keystr(path): leaf.shape for path, leaf
              in jax.tree_util.tree_leaves_with_path(params)}
    assert not [l for l in leaves if "shared_gate" in l or "in_gates" in l]
    # a head's gate is one column a head, where there is one
    gates = {l: s for l, s in leaves.items() if "['gate']" in l}
    assert bool(gates) == (name == "laguna")
    assert all(s[-1] in (4, 6) for s in gates.values()), gates
    # a Mamba-2 layer's conv keeps its bias, its gated norm its order
    for leaf in leaves:
        if "['conv']['kernel']" in leaf:
            assert leaf.replace("kernel", "bias") in leaves
    text = jax.jit(jax.grad(lambda p: jnp.sum(m.apply({"params": p}, toks)))
                   ).lower(params).as_text(debug_info=True)
    for scope in ("mixer/delta_rule", "mixer/prep", "ffn/shared_gate"):
        assert scope not in text, scope
    fields = hybrid.AttentionMixer.__dataclass_fields__
    assert fields["gate"].default is False
    routed = hybrid.RoutedFeedForward.__dataclass_fields__
    assert routed["shared_gate"].default is False
    assert hybrid.GatedRMSNorm.__dataclass_fields__[
        "norm_first"].default is False
    assert hybrid.CausalConv.__dataclass_fields__["use_bias"].default is True
    assert m.pin_stream is False
