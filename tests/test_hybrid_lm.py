"""``models/hybrid.HybridLM`` (Mamba-2 / attention hybrid) against its plain
reference, ``chipbench/reference_granitemoehybrid.py``: the forward pass in
float32 with the state-space layer as a recurrence over time. The reference
shares no code with ``horovod_tpu/``.

Small size, seeded weights with ``A_log``, ``dt_bias``, ``D``, the conv and
the norm weights randomised: at their initial values (``D`` = 1, conv bias
0, every norm weight 1) a wrong decay or a dropped weight hides.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from chipbench import reference_granitemoehybrid as reference
from horovod_tpu import spmd
from horovod_tpu.models.hybrid import HybridLM
from horovod_tpu.models.transformer import lm_loss
from horovod_tpu.ops.ssd import ssd_chunked

#: the configuration keys the reference reads, at the small size
CONFIG = {"num_hidden_layers": 4,
          "layer_types": ["mamba", "mamba", "attention", "mamba"],
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "mamba_n_heads": 4, "mamba_d_state": 16,
          "attention_multiplier": 1 / 32, "residual_multiplier": 0.22,
          "embedding_multiplier": 12, "logits_scaling": 8,
          "rms_norm_eps": 1e-5}
ROWS, HIDDEN = 512, 128

#: float32 program against float32 reference. Both round at 2^-24 = 6e-8;
#: they differ in the order of the sums (chunks of 8 against one position
#: at a time, 64 positions, four blocks), and measured 2.0e-7 in the logits
#: and 4.8e-6 in the worst gradient leaf (relative to the reference's norm).
#: 2e-5 is four times the worst; bf16 operands (2^-9 a rounding) read 6e-3
#: in the logits and 3e-2 in a leaf, three hundred times over: the control.
F32_TOL = 2e-5


def model(dtype=jnp.float32, remat="none", chunk=8):
    return HybridLM(
        vocab_size=ROWS, layer_kinds=tuple(CONFIG["layer_types"]),
        d_model=HIDDEN, ffn_width=256, attn_heads=4, attn_kv_heads=2,
        attn_head_dim=32, ssm_heads=4, ssm_head_dim=32, ssm_state=16,
        ssm_conv_width=4, ssm_chunk=chunk, attention_multiplier=1 / 32,
        embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
        norm_eps=1e-5, dtype=dtype, remat=remat)


def tokens(seq, batch=2, seed=0):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                              ROWS, dtype=jnp.int32)
    return toks[:, :-1], toks[:, 1:]


def randomised_params(seed=1):
    params = model().init(jax.random.PRNGKey(seed), tokens(32)[0])["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))

    def shake(path, leaf, key):
        if leaf.ndim == 2 and "conv" not in jax.tree_util.keystr(path):
            return leaf                     # the matrices: N(0, 0.02) already
        return leaf + 0.5 * jax.random.normal(key, leaf.shape, leaf.dtype)

    return jax.tree_util.tree_unflatten(treedef, [
        shake(path, leaf, key) for (path, leaf), key in zip(leaves, keys)])


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
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
    """(logits, loss, gradients) of the reference on the seeded batch and
    the randomised weights; read-only, shared by the tests of one ``seq``."""
    return _logits_loss_grads(
        lambda p, t: reference.forward(p, t, CONFIG), seq)


def worst_leaf(got, want):
    errors = jax.tree_util.tree_map(relative, got, want)
    return max(jax.tree_util.tree_leaves_with_path(errors),
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
    # every kind of parameter took a gradient
    for leaf in jax.tree_util.tree_leaves(ref_grads):
        assert float(jnp.max(jnp.abs(leaf))) > 0


def test_control_bf16_operands_fail_the_float32_tolerance():
    """The same comparison with the matmul operands one precision lower."""
    logits, _, grads = program_side(32, jnp.bfloat16)
    ref_logits, _, ref_grads = reference_side(32)
    assert relative(logits, ref_logits) > 10 * F32_TOL
    assert worst_leaf(grads, ref_grads)[1] > 10 * F32_TOL
    # ... and stay inside what the chip's check allows a bf16 program
    # (chipbench/jobs/train_lm.LOGIT_RMS_TOL)
    assert relative(logits, ref_logits) <= 0.02


def scan_operands(seq, seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    b, h, p, n = 2, 4, 32, 16
    x = jax.random.normal(keys[0], (b, seq, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, seq, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(keys[2], (h,), jnp.float32))
    B = jax.random.normal(keys[3], (b, seq, n), jnp.float32)
    C = jax.random.normal(keys[4], (b, seq, n), jnp.float32)
    D = jax.random.normal(keys[5], (h,), jnp.float32)
    return x, dt, A, B, C, D


#: chunked scan against the recurrence, float32 both, one layer: measured
#: 1.7e-7, and 1.5e-6 in the worst gradient (A's), which gets ten times the
#: bound (the decay between two positions is one exp of a difference of
#: cumulative sums, the recurrence's a product of up to 32 exps). With dt
#: and A rounded to bf16 the output is 1.7e-3 off: the control.
SCAN_TOL = 5e-6


@pytest.mark.parametrize("seq,chunk", [(32, 8), (32, 16), (20, 8)],
                         ids=["chunk8", "chunk16", "padded"])
def test_ssd_chunked_is_the_recurrence(seq, chunk):
    ops = scan_operands(seq)
    want = jax.jit(reference.recurrence)(*ops)
    got = jax.jit(ssd_chunked, static_argnames="chunk")(*ops, chunk=chunk)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert relative(got, want) <= SCAN_TOL

    def loss(fn, **kw):
        return lambda *a: jnp.sum(fn(*a, **kw) * jnp.cos(want))

    g_got = jax.jit(jax.grad(loss(ssd_chunked, chunk=chunk),
                             argnums=range(6)))(*ops)
    g_want = jax.jit(jax.grad(loss(reference.recurrence),
                              argnums=range(6)))(*ops)
    for name, a, b in zip("x dt A B C D".split(), g_got, g_want):
        assert relative(a, b) <= 10 * SCAN_TOL, name


def kernel_operands(seq, seed=5):
    """Operands the scan's Pallas kernels take (``pallas_kernels.ssd_route``:
    8 heads a grid cell, a head width that divides a lane width, a state of
    whole lane widths), small: four heads share a lane width."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    b, h, p, n = 2, 8, 32, 128
    x = jax.random.normal(keys[0], (b, seq, h, p), jnp.float32)
    dt = 0.2 * jax.nn.softplus(
        jax.random.normal(keys[1], (b, seq, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(keys[2], (h,), jnp.float32))
    B = 0.3 * jax.random.normal(keys[3], (b, seq, n), jnp.float32)
    C = 0.3 * jax.random.normal(keys[4], (b, seq, n), jnp.float32)
    D = jax.random.normal(keys[5], (h,), jnp.float32)
    return x, dt, A, B, C, D


@pytest.mark.parametrize("seq,chunk", [(512, 256), (512, 512), (300, 64)],
                         ids=["a_tile_a_chunk", "two_tiles_a_chunk", "padded"])
def test_the_scan_kernels_are_the_recurrence(seq, chunk, monkeypatch):
    """The kernel path (``ssd_fwd`` and the hand-written ``ssd_bwd``, through
    the Pallas interpreter) against the recurrence at the tolerance the
    dual form is held to: measured 2e-7 in ``y`` and 7e-6 in the worst
    gradient (A's). Its tile is its own, 256, whatever ``chunk`` says: two
    tiles, of which 300 positions fill the second to 44."""
    from horovod_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("HVD_PALLAS", "interpret")
    ops = kernel_operands(seq)
    assert pk.kernel_path("ssd_scan", ops[0], ops[3]) == "pallas"
    assert pk.ssd_route(seq, 8, 32, 128, 1) == {
        "path": "pallas", "tile": 256, "heads": 8}
    want = jax.jit(reference.recurrence)(*ops)
    got = ssd_chunked(*ops, chunk=chunk)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert relative(got, want) <= SCAN_TOL

    def loss(fn, **kw):
        return lambda *a: jnp.sum(fn(*a, **kw) * jnp.cos(want))

    g_got = jax.grad(loss(ssd_chunked, chunk=chunk), argnums=range(6))(*ops)
    g_want = jax.jit(jax.grad(loss(reference.recurrence),
                              argnums=range(6)))(*ops)
    for name, a, b in zip("x dt A B C D".split(), g_got, g_want):
        assert relative(a, b) <= 10 * SCAN_TOL, name


@pytest.mark.parametrize("path", ["reference", "pallas"])
def test_control_bf16_decay_exponents_fail_the_scan_tolerance(path,
                                                              monkeypatch):
    """``dt`` and ``A`` rounded to bf16 before the scan: every decay
    exponent one precision lower, everything else float32. On the dual
    form, and on the kernels."""
    from horovod_tpu.ops import pallas_kernels as pk

    if path == "pallas":
        monkeypatch.setenv("HVD_PALLAS", "interpret")
    x, dt, A, B, C, D = scan_operands(32) if path == "reference" \
        else kernel_operands(300)
    assert pk.kernel_path("ssd_scan", x, B) == path
    want = reference.recurrence(x, dt, A, B, C, D)

    def low(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    got = ssd_chunked(x, low(dt), low(A), B, C, D, chunk=8)
    assert relative(got, want) > 100 * SCAN_TOL
    assert relative(ssd_chunked(x, dt, A, B, C, D, chunk=8), want) <= SCAN_TOL


def test_remat_modes_agree():
    params, (toks, targets) = randomised_params(), tokens(32)

    def loss_and_grads(remat):
        m = model(remat=remat)
        return jax.jit(jax.value_and_grad(lambda p: lm_loss(
            m.apply({"params": p}, toks), targets)))(params)

    base_loss, base_grads = loss_and_grads("none")
    for remat in ("full", "dots"):
        loss, grads = loss_and_grads(remat)
        # the same float32 operations, which XLA fuses differently around
        # the recomputation's barriers: measured 1e-6 in the worst leaf
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
        # float32 both; the first moment is kept in bf16 on both sides, and
        # a gradient 3e-6 apart can round it to the neighbouring bf16 value,
        # which moves a parameter by lr * 2^-8 = 1e-6: later losses agree
        # to that and not to F32_TOL squared
        assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss), i
    assert float(loss) < float(plain(params, batch)[0])


def test_unknown_layer_kind_and_remat_mode_are_refused():
    toks = tokens(8)[0]
    with pytest.raises(ValueError, match="layer_kinds"):
        model().clone(layer_kinds=("mamba", "rwkv")).init(
            jax.random.PRNGKey(0), toks)
    with pytest.raises(ValueError, match="remat"):
        model(remat="some").init(jax.random.PRNGKey(0), toks)
