"""Pallas kernel correctness (interpreter mode on the CPU test platform).

The kernels themselves target TPU (`ops/pallas_kernels.py`); here they run
through the Pallas interpreter (`HVD_PALLAS=interpret`) so the exact kernel
code paths — tiling, scalar prefetch, SMEM accumulation — execute on the
8-device CPU platform. Numerics are checked against the plain-jnp reference
implementations, mirroring how the reference validates its hand kernels
against NumPy (`test/test_adasum_tensorflow.py:104`).
"""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from horovod_tpu.ops import pallas_kernels as pk
from horovod_tpu.parallel.ring_attention import (
    make_ring_attention, reference_attention)
from tests.tests_adasum_ref import numpy_adasum_pair


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("HVD_PALLAS", "interpret")
    yield


# the flash kernels' jitted dispatchers (the rule below ``pk._named_call``)
_FLASH_DISPATCHERS = ("_flash_fwd_once_call", "_flash_step_call_resident",
                      "_flash_step_call_streaming", "_flash_bwd_fused",
                      "_flash_bwd_streaming")


def _forget_flash_traces():
    """A dispatcher's body runs once a (shapes, static arguments): what a
    test patches inside it (``_named_call``, ``_SUB_TILE``,
    ``_live_sub_tiles``) is seen by the next trace only."""
    for name in _FLASH_DISPATCHERS:
        getattr(pk, name).clear_cache()


@pytest.fixture(autouse=True)
def _fresh_flash_traces():
    _forget_flash_traces()
    yield
    _forget_flash_traces()


def _at_every_call_site(monkeypatch):
    """The dispatchers without their ``jax.jit``: each call site traces the
    kernel's body itself, which is the program before the boundary."""
    for name in _FLASH_DISPATCHERS:
        monkeypatch.setattr(pk, name, getattr(pk, name).__wrapped__)


def _rand_qkv(rng, b, t, h, d, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


# ---------------------------------------------------------- flash attention
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, 128, 2, 64)
    out = pk.flash_attention(q, k, v, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_step_chained_blocks():
    """Accumulating two k/v blocks through the kernel == full attention."""
    b, t, h, d = 1, 64, 2, 64
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), b, 2 * t, h, d)
    q1 = q[:, :t]  # query shard 0 of a 2-way ring
    m = jnp.full((b, h, t), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, t), jnp.float32)
    o = jnp.zeros((b, t, h, d), jnp.float32)
    for hop, k_off in enumerate((0, t)):
        m, l, o = pk.flash_attention_step(
            q1, k[:, k_off:k_off + t], v[:, k_off:k_off + t], m, l, o,
            0, k_off, causal=True, scale=d ** -0.5)
    out = (o / jnp.where(l == 0, 1.0, l).transpose(0, 2, 1)[..., None])
    ref = reference_attention(q, k, v, causal=True)[:, :t]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_uses_pallas_step(causal):
    """End-to-end ring attention with the Pallas inner step (4-device ring)."""
    from jax.sharding import Mesh

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("sp",))
    b, t, h, d = 1, 4 * 64, 2, 64  # per-shard t=64: tile-aligned
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), b, t, h, d)
    assert pk.step_supported(q[:, :64], k[:, :64])
    fn = make_ring_attention(mesh, causal=causal)
    out = fn(q, k, v)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_bf16():
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, 128, 2, 64, jnp.bfloat16)
    out = pk.flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_gating(monkeypatch):
    q = jnp.zeros((1, 128, 1, 64))
    monkeypatch.setenv("HVD_PALLAS", "0")
    assert pk.mode() == "off"
    assert not pk.step_supported(q, q)
    monkeypatch.setenv("HVD_PALLAS", "interpret")
    assert pk.mode() == "interpret"
    assert pk.step_supported(q, q)
    # ragged seq len -> kernel declines, caller falls back
    assert not pk.step_supported(jnp.zeros((1, 100, 1, 64)), q)


# ------------------------------------------------------------------- adasum
def test_adasum_combine_matches_numpy():
    rng = np.random.RandomState(0)
    a = rng.randn(4, 512).astype(np.float32)
    b = rng.randn(4, 512).astype(np.float32)
    out = pk.adasum_combine(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), numpy_adasum_pair(a, b),
                               rtol=1e-5, atol=1e-5)


def test_adasum_combine_zero_norm_guard():
    a = jnp.zeros((8, 128), jnp.float32)
    b = jnp.ones((8, 128), jnp.float32)
    out = pk.adasum_combine(a, b)
    np.testing.assert_allclose(np.asarray(out),
                               numpy_adasum_pair(np.zeros((8, 128)),
                                                 np.ones((8, 128))))


def test_adasum_combine_bf16():
    rng = np.random.RandomState(1)
    a = rng.randn(2, 256).astype(np.float32)
    b = rng.randn(2, 256).astype(np.float32)
    out = pk.adasum_combine(jnp.asarray(a, jnp.bfloat16),
                            jnp.asarray(b, jnp.bfloat16))
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               numpy_adasum_pair(a, b), rtol=5e-2, atol=5e-2)


def test_adasum_combine_rejects_ragged():
    with pytest.raises(ValueError):
        pk.adasum_combine(jnp.zeros(100), jnp.zeros(100))


def test_spmd_adasum_pallas_path_matches_numpy():
    """spmd.adasum routes pairwise combines through the Pallas kernel when
    enabled; ragged sizes are zero-padded (exact for dot/norms)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import spmd
    from tests.tests_adasum_ref import numpy_adasum

    hvd.init()
    mesh = hvd.mesh()
    n = hvd.num_replicas()
    rng = np.random.RandomState(2)
    data = rng.randn(n, 37).astype(np.float32)  # 37: not lane-aligned
    gx = jax.device_put(jnp.asarray(data).reshape(n, 1, 37),
                        NamedSharding(mesh, P("hvd")))

    # check_vma=False: with vma checking on, spmd.adasum falls back to jnp
    # (pallas kernels and the vma checker don't compose); this test pins the
    # kernel path
    fn = jax.shard_map(lambda v: spmd.adasum(v[0])[None], mesh=mesh,
                       in_specs=P("hvd"), out_specs=P("hvd"), check_vma=False)
    out = jax.jit(fn)(gx)
    ref = numpy_adasum([data[i] for i in range(n)])
    for row in np.asarray(out).reshape(n, 37):
        np.testing.assert_allclose(row, ref, rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------- differentiation
def test_flash_attention_grad_matches_reference():
    """The Pallas step must stay differentiable (custom VJP, remat backward):
    grads of the kernel path == grads of plain jnp attention."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 1, 128, 2, 64)

    def loss_pk(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_pk = jax.grad(loss_pk, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pk, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_fullattn_bwd_multiblock(causal):
    """The Pallas FlashAttention-2 backward (dq + dkv kernels) across
    multiple q/k blocks: grads == autodiff of plain jnp attention. Weighted
    loss makes the incoming cotangent row-dependent, exercising the D/LSE
    recompute."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), 2, 256, 2, 64)
    w = jax.random.normal(jax.random.PRNGKey(8), q.shape, q.dtype)

    def loss_pk(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, causal=causal) * w)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) * w)

    g_pk = jax.grad(loss_pk, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pk, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_ring_attention_grad_with_pallas_step():
    from jax.sharding import Mesh

    devs = jax.devices()[:2]
    mesh = Mesh(np.array(devs), ("sp",))
    b, t, h, d = 1, 2 * 64, 2, 64
    q, k, v = _rand_qkv(jax.random.PRNGKey(6), b, t, h, d)
    fn = make_ring_attention(mesh, causal=True)

    g = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2),
                 argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(reference_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_streaming_forward_variant(causal, monkeypatch):
    """Force the streaming FORWARD layout (k/v too long to keep resident):
    output and grads must match exact attention."""
    monkeypatch.setattr(pk, "_KV_VMEM_CAP", 1)
    pk._flash_fullattn_vjp.cache_clear()
    q, k, v = _rand_qkv(jax.random.PRNGKey(13), 1, 256, 2, 64)
    w = jax.random.normal(jax.random.PRNGKey(14), q.shape, q.dtype)

    out = pk.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(reference_attention(q, k, v, causal=causal)),
        rtol=2e-5, atol=2e-5)
    g_pk = jax.grad(
        lambda q, k, v: jnp.sum(pk.flash_attention(q, k, v, causal=causal)
                                * w), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(reference_attention(q, k, v, causal=causal)
                                * w), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pk, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# name -> (key width, value width, causal, window): one head count, 256
# positions over 4 x 4 tiles of 64
_FORWARD_PAIR_CASES = {
    "causal_64": (64, 64, True, None),
    "full_64": (64, 64, False, None),
    "causal_128": (128, 128, True, None),
    "window_96_128": (128, 128, True, 96),
    "window_64_64": (64, 64, True, 64),
    "causal_keys_192_values_128": (192, 128, True, None),
    "full_keys_192_values_128": (192, 128, False, None),
}


@pytest.mark.parametrize("name", sorted(_FORWARD_PAIR_CASES))
def test_resident_and_streaming_forward_are_the_same_sums(name, monkeypatch):
    """What ``_KV_VMEM_CAP`` decides is a layout, not a result: the
    single-shot forward (K and V of a head in VMEM, m, l, o in registers,
    normalised in the kernel) and the streaming one (a grid step a key
    tile, m, l and an f32 o carried through the revisited output tiles, an
    XLA epilogue) accumulate the same key blocks in the same order a q
    tile, so ``out`` and the LSE the backward reads agree far inside the
    tolerance each holds against plain attention, causal, windowed and at
    two widths, and so do the gradients taken through either's residuals."""
    d, dv, causal, window = _FORWARD_PAIR_CASES[name]
    monkeypatch.setattr(pk, "_BLOCK_Q", 64)
    monkeypatch.setattr(pk, "_BLOCK_K", 64)
    monkeypatch.setattr(pk, "_SUB_TILE", 32)
    _at_every_call_site(monkeypatch)     # eager: a kernel's results are seen
    ks = jax.random.split(jax.random.PRNGKey(d + dv), 4)
    b, t, h = 1, 256, 2
    q, k = (jax.random.normal(kk, (b, t, h, d)) for kk in ks[:2])
    v, w = (jax.random.normal(kk, (b, t, h, dv)) for kk in ks[2:])
    scale = d ** -0.5
    results = {}
    real = pk._named_call

    def spy(kernel_name, kernel, **kw):
        call = real(kernel_name, kernel, **kw)

        def recorded(*operands):
            results[kernel_name] = call(*operands)
            return results[kernel_name]

        return recorded

    monkeypatch.setattr(pk, "_named_call", spy)

    def attend(q, k, v):
        return pk.flash_attention(q, k, v, causal=causal, scale=scale,
                                  window=window)

    def run():
        results.clear()
        out = attend(q, k, v)
        forward = dict(results)
        grads = jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v) * w),
                         argnums=(0, 1, 2))(q, k, v)
        return out, forward, grads

    out_r, resident, grads_r = run()
    monkeypatch.setattr(pk, "_KV_VMEM_CAP", 1)
    out_s, streaming, grads_s = run()
    assert list(resident) == ["flash_fwd"]
    assert list(streaming) == ["flash_step"]
    lse_r = resident["flash_fwd"][1]
    lse_s = pk._masked_row_stats(*streaming["flash_step"][:2])[1]
    assert lse_r.shape == lse_s.shape == (b * h, 1, t)
    np.testing.assert_allclose(np.asarray(lse_r), np.asarray(lse_s),
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_s),
                               rtol=2e-6, atol=2e-6)
    for a, b_, nm in zip(grads_r, grads_s, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-5,
                                   atol=2e-5, err_msg=nm)
    ref = reference_attention(q, k, v, causal=causal, scale=scale,
                              window=window)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _spy_kernels(monkeypatch):
    """The kernel functions handed to ``pk._named_call`` from here on, by
    name: which dispatch a call took."""
    taken = []
    real = pk._named_call

    def spy(name, kernel, **kw):
        taken.append(kernel.func.__name__)
        return real(name, kernel, **kw)

    monkeypatch.setattr(pk, "_named_call", spy)
    return taken


@pytest.mark.parametrize("causal", [True, False])
def test_flash_bwd_streaming_pair(causal, monkeypatch):
    """A dq scratch over ``_DQ_SCRATCH_CAP`` (a head longer than 16,384
    positions at d = 64) takes the streaming pair, the 3D-grid dq and dkv
    kernels, here over 4 x 4 tiles: grads must match the reference."""
    monkeypatch.setattr(pk, "_DQ_SCRATCH_CAP", 1)
    monkeypatch.setattr(pk, "_BLOCK_Q", 64)
    monkeypatch.setattr(pk, "_BLOCK_K", 64)
    pk._flash_fullattn_vjp.cache_clear()
    taken = _spy_kernels(monkeypatch)
    q, k, v = _rand_qkv(jax.random.PRNGKey(11), 1, 256, 2, 64)
    w = jax.random.normal(jax.random.PRNGKey(12), q.shape, q.dtype)

    g_pk = jax.grad(
        lambda q, k, v: jnp.sum(pk.flash_attention(q, k, v, causal=causal)
                                * w), argnums=(0, 1, 2))(q, k, v)
    pk._flash_fullattn_vjp.cache_clear()
    assert taken == ["_flash_fwd_once_kernel", "_flash_bwd_dq_kernel",
                     "_flash_bwd_dkv_kernel"]
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(reference_attention(q, k, v, causal=causal)
                                * w), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pk, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_fa2_backward_4dev(causal):
    """The ring-structured FlashAttention-2 backward (second ring pass: dq
    local, dk/dv rotating home with their blocks) across 4 devices, with a
    row-dependent cotangent — grads == autodiff of exact attention."""
    from jax.sharding import Mesh

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("sp",))
    b, t, h, d = 1, 4 * 128, 2, 64
    q, k, v = _rand_qkv(jax.random.PRNGKey(9), b, t, h, d)
    w = jax.random.normal(jax.random.PRNGKey(10), q.shape, q.dtype)
    fn = make_ring_attention(mesh, causal=causal)

    g = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * w),
                 argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(
            reference_attention(q, k, v, causal=causal) * w),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_bwd_fused_multi_ksweep(causal, monkeypatch):
    """The fused backward's SCRATCH path (nk > 1: dq accumulates across k
    sweeps in the persistent VMEM scratch) — small test shapes otherwise
    take the single-sweep fast path that skips the scratch entirely."""
    monkeypatch.setattr(pk, "_BLOCK_K", 64)              # 256/64 -> nk=4
    monkeypatch.setattr(pk, "_BLOCK_Q", 64)
    pk._flash_fullattn_vjp.cache_clear()
    q, k, v = _rand_qkv(jax.random.PRNGKey(31), 1, 256, 2, 64)
    w = jax.random.normal(jax.random.PRNGKey(32), q.shape, q.dtype)

    g_pk = jax.grad(
        lambda q, k, v: jnp.sum(pk.flash_attention(q, k, v, causal=causal)
                                * w), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(reference_attention(q, k, v, causal=causal)
                                * w), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pk, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)

def _differential_trials():
    """Six seeded configurations ``(tq, tk, causal, q_off, k_off)``. Offsets
    are drawn so the q and k blocks OVERLAP, keeping causal trials on a real
    mask boundary instead of degenerate all-masked/all-unmasked corners."""
    rng = np.random.RandomState(17)
    trials = []
    for _ in range(6):
        tq = int(rng.choice([64, 128, 256]))
        tk = int(rng.choice([64, 128, 256]))
        causal = bool(rng.randint(2))
        # overlapping ring-style block origins: k block starts inside
        # [q_off, q_off + tq) so a causal mask boundary crosses the tiles
        q_off = int(rng.choice([0, 64]))
        k_off = q_off + int(rng.randint(0, tq // 64)) * 64
        trials.append((tq, tk, causal, q_off, k_off))
    return trials


@pytest.mark.parametrize("trial", range(6))
def test_flash_bwd_fused_vs_streaming_differential(trial, monkeypatch):
    """The ONE-pass fused backward against the streaming pair, the two
    backwards ``flash_route`` chooses between, through the production
    ``_flash_bwd`` packing at f32 rtol; odd trials on grid tiles of 64, so
    that both run over several tiles. f32-only by design: shared-math bugs
    are covered by the reference-attention comparisons in the tests above;
    this test's job is a divergence between the two."""
    tq, tk, causal, q_off, k_off = _differential_trials()[trial]
    if trial % 2:
        monkeypatch.setattr(pk, "_BLOCK_Q", 64)
        monkeypatch.setattr(pk, "_BLOCK_K", 64)
    b, h, d = 1, 2, 64
    keys = jax.random.split(jax.random.PRNGKey(trial), 4)
    q = jax.random.normal(keys[0], (b, tq, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, tk, h, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, tk, h, d), jnp.float32)
    dout = jax.random.normal(keys[3], (b, tq, h, d), jnp.float32)
    # forward statistics from the step kernel (what ring hops carry)
    m = jnp.full((b, h, tq), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, tq), jnp.float32)
    o = jnp.zeros((b, tq, h, d), jnp.float32)
    m, l, o = pk.flash_attention_step(q, k, v, m, l, o, q_off, k_off,
                                      causal=causal, scale=d ** -0.5)
    out, lse = pk.finalize_attention_stats(m, l, o, jnp.float32)
    taken = _spy_kernels(monkeypatch)

    def bwd():
        return pk._flash_bwd(q, k, v, out, lse, dout, q_off, k_off,
                             causal=causal, scale=d ** -0.5)

    fused = bwd()
    monkeypatch.setattr(pk, "_DQ_SCRATCH_CAP", 1)
    for a, b_, nm in zip(fused, bwd(), ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=1e-5, atol=1e-5,
            err_msg=f"{nm} fused != streaming ({tq=}, {tk=}, {causal=}, "
                    f"{q_off=}, {k_off=})")
    assert taken == ["_flash_bwd_fused_kernel", "_flash_bwd_dq_kernel",
                     "_flash_bwd_dkv_kernel"]


def test_vmem_policy_and_input_fusion(monkeypatch):
    """The two VMEM policies, and input fusion: on for a relayout that is a
    4-D transpose, off where ``HVD_PALLAS_INPUT_FUSION=0`` says so, read
    when the compiler params are BUILT, not at module import."""
    # resident kernels get 96 MiB, streaming ones Mosaic's default
    assert pk._sem_par2_res().vmem_limit_bytes == 96 * 2 ** 20
    assert pk._cparams("parallel", "arbitrary", "arbitrary",
                       resident=True).vmem_limit_bytes == 96 * 2 ** 20
    for streaming in (pk._sem_par2(), pk._sem_par_arb(), pk._sem_par2_arb()):
        assert streaming.vmem_limit_bytes is None
    # but for the one call that names its own (``flash_route``'s
    # ``backward_vmem``), and ``None`` there is the default again
    assert pk._cparams("parallel", "arbitrary", "arbitrary",
                       vmem_limit=35 * 2 ** 20).vmem_limit_bytes == 35 * 2 ** 20
    assert pk._cparams("parallel", "arbitrary", "arbitrary",
                       vmem_limit=None) == pk._cparams(
                           "parallel", "arbitrary", "arbitrary")

    # input fusion: default on, disabled per-call by the env
    monkeypatch.delenv("HVD_PALLAS_INPUT_FUSION", raising=False)
    p = pk._input_fusion(pk._sem_par2_res(), "tttttt",
                         pk._relayout_fusable(8, 16))
    assert list(p.allow_input_fusion) == [False] + [True] * 6
    # the row statistics stay out of it: with a producer folded in, the
    # call is an XLA fusion and a device trace no longer names the kernel
    p = pk._input_fusion(pk._sem_par2_res(), "tttsst", True)
    assert list(p.allow_input_fusion) == [False] + [True] * 3 + [
        False, False, True]
    # one batch row or one head: the relayout is no 4-D transpose, and
    # fusing it crashes the TPU compiler (AOT-probed, libtpu 0.0.34)
    for b, h in ((1, 16), (8, 1)):
        p = pk._input_fusion(pk._sem_par2_res(), "tttttt",
                             pk._relayout_fusable(b, h))
        assert p.allow_input_fusion is None
    monkeypatch.setenv("HVD_PALLAS_INPUT_FUSION", "0")
    p = pk._input_fusion(pk._sem_par2_res(), "tttttt", True)
    assert p.allow_input_fusion is None


@pytest.mark.parametrize("causal", [True, False])
def test_flash_fwd_oneshot_vs_step_path(causal, monkeypatch):
    """The single-shot forward (``_flash_fwd_once_call``, what a head with
    resident k/v takes) against the ring-step kernel + finalize it is the
    carry-free form of: the same normalized output and the same row LSE,
    which is all the backward reads of a forward. Two q tiles a slice."""
    monkeypatch.setattr(pk, "_BLOCK_Q", 128)
    bh, t, d = 4, 256, 64
    qt, kt, vt = (jax.random.normal(kk, (bh, t, d), jnp.float32)
                  for kk in jax.random.split(jax.random.PRNGKey(31), 3))
    offs = jnp.zeros((2,), jnp.int32)
    kw = dict(causal=causal, scale=d ** -0.5,
              block_q=pk._pick_block(t, side="q"),
              block_k=pk._pick_block(t, side="k"), interpret=True,
              fusable=True)
    taken = _spy_kernels(monkeypatch)
    out_once, lse_once = pk._flash_fwd_once_call(qt, kt, vt, offs, **kw)
    mt, lt, ot = pk._flash_step_call(
        qt, kt, vt, jnp.full((bh, 1, t), -jnp.inf, jnp.float32),
        jnp.zeros((bh, 1, t), jnp.float32),
        jnp.zeros((bh, t, d), jnp.float32), offs, **kw)
    assert taken == ["_flash_fwd_once_kernel", "_flash_step_kernel"]
    assert lse_once.shape == mt.shape == lt.shape == (bh, 1, t)
    l_safe, lse_step = pk._masked_row_stats(mt, lt)
    np.testing.assert_allclose(np.asarray(out_once),
                               np.asarray(ot / l_safe[:, 0, :, None]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(lse_once), np.asarray(lse_step),
                               rtol=1e-6, atol=1e-6)


# name -> the call that shares nothing with ``causal=True, scale=0.125``
_OTHER_STATIC = {"causal": dict(causal=False, scale=0.125),
                 "scale": dict(causal=True, scale=0.25)}


@pytest.mark.parametrize("differs", sorted(_OTHER_STATIC))
def test_flash_kernel_is_traced_once_a_shape_not_once_a_call_site(
        differs, monkeypatch):
    """Three attention layers of one shape under one ``jax.grad`` trace the
    forward and the backward kernel once each (six times before the
    dispatchers were jitted), a second program of the same shape traces
    nothing, and a call that differs only in ``causal`` or in ``scale``
    shares no trace with it. One batch row, as a ring shard's or granite's
    call has: its gradients match a kernel traced at every site to the
    bit."""
    taken = _spy_kernels(monkeypatch)
    q, k, v = _rand_qkv(jax.random.PRNGKey(41), 1, 128, 2, 64)

    def grads(**kw):
        def loss(q, k, v):
            x = q
            for _ in range(3):
                x = pk.flash_attention(x, k, v, **kw)
            return jnp.sum(x ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    pair = ["_flash_fwd_once_kernel", "_flash_bwd_fused_kernel"]
    first = grads(causal=True, scale=0.125)
    assert taken == pair
    grads(causal=True, scale=0.125)
    assert taken == pair
    grads(**_OTHER_STATIC[differs])
    assert taken == pair + pair

    del taken[:]
    _at_every_call_site(monkeypatch)
    for a, b in zip(first, grads(causal=True, scale=0.125)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert sorted(taken) == sorted(pair * 3)


# ------------------------------------------------ which kernels a shape takes
# name -> (tq, tk, d, itemsize), then what ``flash_route`` must say: the
# forward, the step (a ring hop), the backward and the MiB of VMEM the fused
# backward names (``None``: Mosaic's default, the call there has always
# been). The cells' calls, and both sides of each boundary: 64 MiB of a
# head's k + v as VMEM holds them, 128 lanes to a head of 64 and both of the
# pipeline's buffers (``_KV_VMEM_CAP``; the two sides of the 1 MiB an operand
# it was until PR 44 are both resident now), a dq scratch of 4 MiB
# (``_DQ_SCRATCH_DEFAULT``: the last call with no limit of its own) and one
# of 32 MiB (``_DQ_SCRATCH_CAP``: the last fused call).
_ROUTE_CASES = {
    "cells_gpt2_1024x64_bf16": (
        (1024, 1024, 64, 2), ("once", "step", "fused", None)),
    "cell_granite_4096x64_bf16": (
        (4096, 4096, 64, 2), ("once", "step", "fused", None)),
    "cell_laguna_8192x128_bf16": (
        (8192, 8192, 128, 2), ("once", "step", "fused", None)),
    "kv_8192x64_bf16_the_last_under_the_old_cap": (
        (1024, 8192, 64, 2), ("once", "step", "fused", None)),
    "kv_16384x64_bf16_the_first_over_the_old_cap": (
        (1024, 16384, 64, 2), ("once", "step", "fused", None)),
    "kv_65536x64_bf16_the_last_resident": (
        (1024, 65536, 64, 2), ("once", "step", "fused", None)),
    "kv_131072x64_bf16_the_first_streamed": (
        (1024, 131072, 64, 2),
        ("step_streaming", "step_streaming", "fused", None)),
    "kv_4096x64_f32_the_last_under_the_old_cap": (
        (4096, 4096, 64, 4), ("once", "step", "fused", None)),
    "kv_8192x64_f32_the_first_over_the_old_cap": (
        (8192, 8192, 64, 4), ("once", "step", "fused", None)),
    "kv_32768x64_f32_the_last_resident": (
        (1024, 32768, 64, 4), ("once", "step", "fused", None)),
    "kv_65536x64_f32_the_first_streamed": (
        (1024, 65536, 64, 4),
        ("step_streaming", "step_streaming", "fused", None)),
    "kv_65536x128_bf16_the_last_resident": (
        (1024, 65536, 128, 2), ("once", "step", "fused", None)),
    "kv_131072x128_bf16_the_first_streamed": (
        (1024, 131072, 128, 2),
        ("step_streaming", "step_streaming", "fused", None)),
    "dq_16384x64_the_last_at_the_default": (
        (16384, 1024, 64, 2), ("once", "step", "fused", None)),
    "dq_32768x64_the_first_with_a_limit": (
        (32768, 1024, 64, 2), ("once", "step", "fused", 32)),
    "dq_131072x64_the_last_fused": (
        (131072, 1024, 64, 2), ("once", "step", "fused", 80)),
    "dq_262144x64_the_first_streamed": (
        (262144, 1024, 64, 2), ("once", "step", "streaming", None)),
    "dq_8192x128_the_last_at_the_default": (
        (8192, 8192, 128, 2), ("once", "step", "fused", None)),
    "dq_16384x128_the_first_with_a_limit": (
        (16384, 16384, 128, 2), ("once", "step", "fused", 24)),
    "dq_65536x128_the_last_fused": (
        (65536, 65536, 128, 2), ("once", "step", "fused", 48)),
    "dq_131072x128_the_first_streamed": (
        (131072, 131072, 128, 2),
        ("step_streaming", "step_streaming", "streaming", None)),
}
_ROUTE_KERNELS = {
    "once": ["_flash_fwd_once_kernel"],
    "step": ["_flash_step_kernel"],
    "step_streaming": ["_flash_step_stream_kernel"],
    "fused": ["_flash_bwd_fused_kernel"],
    "streaming": ["_flash_bwd_dq_kernel", "_flash_bwd_dkv_kernel"],
}


@pytest.mark.parametrize("name", sorted(_ROUTE_CASES))
def test_flash_route(name, monkeypatch):
    """``flash_route`` is the one table of which kernels a head's shape
    takes, and the dispatchers follow it: the shape is traced at its real
    size (nothing runs) through ``flash_attention``'s forward and backward
    and through ``flash_attention_step``, and a spy names the kernels."""
    (tq, tk, d, itemsize), (forward, step, backward, mib) = _ROUTE_CASES[name]
    assert pk.flash_route(tq, tk, d, itemsize) == {
        "forward": forward, "step": step, "backward": backward,
        "backward_vmem": mib and mib * 2 ** 20}

    dtype = {2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    q = jax.ShapeDtypeStruct((2, tq, 2, d), dtype)
    kv = jax.ShapeDtypeStruct((2, tk, 2, d), dtype)
    taken = _spy_kernels(monkeypatch)
    pk._flash_fullattn_vjp.cache_clear()
    jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(pk.flash_attention(
        q, k, v, causal=True).astype(jnp.float32)), argnums=(0, 1, 2)),
        q, kv, kv)
    pk._flash_fullattn_vjp.cache_clear()
    assert taken == _ROUTE_KERNELS[forward] + _ROUTE_KERNELS[backward]

    del taken[:]
    _forget_flash_traces()    # the streamed forward above is this step's call
    stat = jax.ShapeDtypeStruct((2, 2, tq), jnp.float32)
    jax.eval_shape(
        lambda q, k, v, m, l, o: pk.flash_attention_step(
            q, k, v, m, l, o, 0, 0, causal=True, scale=d ** -0.5),
        q, kv, kv, stat, stat, jax.ShapeDtypeStruct(q.shape, jnp.float32))
    assert taken == _ROUTE_KERNELS[step]


def test_only_flash_route_compares_a_shape_with_the_caps():
    """Under ``horovod_tpu/`` the three budgets are read in one function."""
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(pk))
    readers = {
        fn.name for fn in ast.walk(tree) if isinstance(fn, ast.FunctionDef)
        for node in ast.walk(fn) if isinstance(node, ast.Name)
        and node.id in ("_KV_VMEM_CAP", "_DQ_SCRATCH_DEFAULT",
                        "_DQ_SCRATCH_CAP")}
    assert readers == {"flash_route"}


@pytest.mark.parametrize("t,side,block", [
    (1024, "q", 512), (1024, "k", 1024), (4096, "k", 1024), (256, "k", 256),
    (192, "q", 64), (100, "q", None)])
def test_pick_block_grid_tile_edges(t, side, block):
    """The flash kernels' grid tile: the largest power of two up to 512
    (q side) or 1024 (k side) that divides the length, none under 8."""
    assert pk._pick_block(t, side=side) == block


def test_pallas_variables_are_the_two_the_docs_list():
    """What is left of the kernels' environment: the platform switch and
    the way round a compiler fault, in the code and in ``docs/knobs.md``."""
    import os
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    name = re.compile(r"\b(?:HVD_PALLAS|HVD_FUSED)[A-Z0-9_]*")
    read = set()
    for folder, _, files in os.walk(os.path.join(root, "horovod_tpu")):
        for f in files:
            if f.endswith((".py", ".cc", ".h")):
                with open(os.path.join(folder, f), errors="replace") as fh:
                    read |= set(name.findall(fh.read()))
    assert read == {"HVD_PALLAS", "HVD_PALLAS_INPUT_FUSION"}
    with open(os.path.join(root, "docs", "knobs.md")) as fh:
        assert set(name.findall(fh.read())) == read


# ------------------------------------- causal sub-tiles inside a grid cell
def _masked_reference(q, k, v, dout, q_off, k_off, causal):
    """Plain attention of q rows from ``q_off`` against keys from ``k_off``
    and its three gradients; a row with every key masked gives zeros."""
    d = q.shape[-1]

    def attn(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q * d ** -0.5, k)
        mask = jnp.ones(s.shape[-2:], bool)
        if causal:
            mask = ((q_off + jnp.arange(q.shape[1]))[:, None]
                    >= (k_off + jnp.arange(k.shape[1]))[None, :])
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1) * mask
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out, vjp = jax.vjp(attn, q, k, v)
    return (out,) + vjp(dout)


def _flash_with_grads(q, k, v, dout, q_off, k_off, causal):
    """(out, dq, dk, dv) of the kernels: through ``flash_attention`` where
    the offsets are ``None`` (the single-shot forward, static zero offsets),
    else the ring hop's pair, the step kernel and ``_flash_bwd``, with the
    offsets TRACED as a ring passes them."""
    if q_off is None:
        out, vjp = jax.vjp(
            lambda q, k, v: pk.flash_attention(q, k, v, causal=causal),
            q, k, v)
        return (out,) + vjp(dout)
    b, tq, h, d = q.shape

    @jax.jit
    def hop(q_off, k_off):
        m, l, o = pk.flash_attention_step(
            q, k, v, jnp.full((b, h, tq), -jnp.inf, jnp.float32),
            jnp.zeros((b, h, tq), jnp.float32),
            jnp.zeros(q.shape, jnp.float32), q_off, k_off, causal=causal,
            scale=d ** -0.5)
        out, lse = pk.finalize_attention_stats(m, l, o, jnp.float32)
        return (out,) + pk._flash_bwd(q, k, v, out, lse, dout, q_off, k_off,
                                      causal=causal, scale=d ** -0.5)

    return hop(jnp.int32(q_off), jnp.int32(k_off))


# name -> (tq, tk, q_off, k_off, causal, block_q, block_k, sub_tile)
# The cells' tiles are 512 x 1024 cut at 512: here 64 x 128 cut at 32, two
# row sub-tiles a q tile and four widths (sub_tile None: the edge as shipped).
_SUB_TILE_CASES = {
    "two_q_tiles_one_k_tile": (128, 128, None, None, True, 64, 128, 32),
    "eight_q_tiles_four_k_tiles": (512, 512, None, None, True, 64, 128, 32),
    "hop_wholly_live": (128, 128, 256, 0, True, 64, 128, 32),
    "hop_wholly_dead": (128, 128, 0, 256, True, 64, 128, 32),
    "hop_crossed": (128, 128, 64, 32, True, 64, 128, 32),
    "hop_crossed_off_the_sub_tile_grid": (128, 128, 48, 16, True, 64, 128,
                                          32),
    "hop_tq_below_tk": (64, 256, 128, 0, True, 64, 128, 32),
    "hop_tq_above_tk": (256, 64, 0, 96, True, 64, 64, 32),
    "rectangular_sub_tiles": (256, 256, 64, 0, True, 64, 128, 32),
    "non_causal": (128, 128, None, None, False, 64, 128, 32),
    "non_causal_hop": (128, 256, 64, 0, False, 64, 128, 32),
    "edge_over_the_tile": (192, 192, None, None, True, 512, 1024, 128),
    "a_cell_at_its_real_edges": (1024, 1024, None, None, True, 512, 1024,
                                 None),
}


def _sub_tile_case(name, monkeypatch):
    """The case's grid tiles and sub-tile edge set, its operands
    ``(q, k, v, dout)``, and its offsets as the kernels get them (``None``:
    through ``flash_attention``) and as the reference does."""
    tq, tk, q_off, k_off, causal, bq, bk, sub = _SUB_TILE_CASES[name]
    monkeypatch.setattr(pk, "_BLOCK_Q", bq)
    monkeypatch.setattr(pk, "_BLOCK_K", bk)
    if sub is not None:
        monkeypatch.setattr(pk, "_SUB_TILE", sub)
    pk._flash_fullattn_vjp.cache_clear()
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 4)
    h = 1 if tq == 1024 else 2
    q, dout = (jax.random.normal(kk, (1, tq, h, 64)) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (1, tk, h, 64)) for kk in ks[2:])
    return (q, k, v, dout), (q_off, k_off), (q_off or 0, k_off or 0)


@pytest.mark.parametrize("name", sorted(_SUB_TILE_CASES))
def test_flash_causal_sub_tiles_match_reference(name, monkeypatch):
    """A causal backward cell computes, a row sub-tile at a time, only the
    strip of sub-tiles that hold an unmasked score, and the forward runs a
    lone key block as straight-line code: forward and the three gradients
    against plain attention, over the geometries they meet (two
    rectangular q tiles over one k tile as at 1024 positions, eight over
    four with the dq scratch and the forward's loop as at 4096, ring hops
    with traced offsets, whole-tile fall-backs)."""
    operands, offs, ref_offs = _sub_tile_case(name, monkeypatch)
    tq, tk, _, _, causal, _, _, _ = _SUB_TILE_CASES[name]
    block_q, block_k = pk._pick_block(tq, side="q"), pk._pick_block(
        tk, side="k")
    sub = pk._SUB_TILE
    want = ((min(block_q, sub), min(block_k, sub)) if causal
            else (block_q, block_k))
    assert pk._pick_sub_tile(causal, block_q, block_k) == want
    got = _flash_with_grads(*operands, *offs, causal)
    ref = _masked_reference(*operands, *ref_offs, causal)
    for a, b, nm in zip(got, ref, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=f"{name}: {nm}")


@pytest.mark.parametrize("name", ["two_q_tiles_one_k_tile", "hop_crossed",
                                  "eight_q_tiles_four_k_tiles"])
def test_flash_causal_sub_tiles_control_one_live_sub_tile_dropped(
        name, monkeypatch):
    """The control that must fail: the same comparison with the bound one
    sub-tile short for the rows from position 64 (the backward's row
    sub-tile there loses the sub-tile the diagonal crosses; where the
    forward loops over key blocks, its q tile there loses a block) is far
    outside the tolerance in every gradient, and in the output where the
    forward asks the bound — so the test above would see a strip that
    stops early."""
    operands, offs, ref_offs = _sub_tile_case(name, monkeypatch)
    tk, block_k = _SUB_TILE_CASES[name][1], _SUB_TILE_CASES[name][6]
    real = pk._live_sub_tiles

    def one_short(q_lo, k_lo, sub_q, sub_k, n):
        w = real(q_lo, k_lo, sub_q, sub_k, n)
        if isinstance(q_lo, int):                    # the plan's own call
            return w
        return jnp.where(q_lo == ref_offs[0] + 64, jnp.maximum(w - 1, 0), w)

    monkeypatch.setattr(pk, "_live_sub_tiles", one_short)
    got = _flash_with_grads(*operands, *offs, True)
    ref = _masked_reference(*operands, *ref_offs, True)
    for a, b, nm in zip(got, ref, ("out", "dq", "dk", "dv")):
        if nm == "out" and tk == block_k:     # one key block: no bound asked
            continue
        err = np.max(np.abs(np.asarray(a) - np.asarray(b)))
        assert err > 1e-2, f"{name}: {nm} moved only {err}"


# kernel -> the caps that route a 128-position head to it (``None``: as
# shipped) and which of (out, lse, dq, dk, dv) it produces
_MASKED_ROW_KERNELS = {
    "_flash_fwd_once_kernel": ({}, ("out", "lse")),
    "_flash_step_kernel": ({}, ("out", "lse")),
    "_flash_step_stream_kernel": ({"_KV_VMEM_CAP": 1}, ("out", "lse")),
    "_flash_bwd_fused_kernel": ({}, ("dq", "dk", "dv")),
    "_flash_bwd_dq_kernel": ({"_DQ_SCRATCH_CAP": 1}, ("dq",)),
    "_flash_bwd_dkv_kernel": ({"_DQ_SCRATCH_CAP": 1}, ("dk", "dv")),
}


@pytest.mark.parametrize("k_off,dead", [(256, 128), (64, 64)],
                         ids=["hop_wholly_dead", "first_64_rows_dead"])
@pytest.mark.parametrize("kernel", sorted(_MASKED_ROW_KERNELS))
def test_flash_fully_masked_rows_in_each_kernel(kernel, k_off, dead,
                                                monkeypatch):
    """The fully-masked-row convention (``_masked_row_stats``) with the
    statistics as rows, in each of the six kernels, reached through
    ``flash_route``'s caps: 128 queries from position 0 against 128 keys
    from ``k_off``, so the first ``dead`` query rows see no key. Their
    output is exactly zero and their LSE the sentinel 0; their dq is
    exactly zero, and where every row is dead so are dk and dv. What is
    live matches plain attention."""
    caps, produces = _MASKED_ROW_KERNELS[kernel]
    for cap, value in caps.items():
        monkeypatch.setattr(pk, cap, value)
    monkeypatch.setattr(pk, "_BLOCK_Q", 64)
    monkeypatch.setattr(pk, "_BLOCK_K", 64)
    monkeypatch.setattr(pk, "_SUB_TILE", 32)
    b, t, h, d = 1, 128, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(k_off), 4)
    q, k, v, dout = (jax.random.normal(kk, (b, t, h, d)) for kk in ks)
    scale = d ** -0.5
    taken = _spy_kernels(monkeypatch)

    def run():
        if kernel == "_flash_fwd_once_kernel":
            # the single-shot forward takes offsets too, though its one
            # caller passes zeros: heads-major operands, lse as it leaves
            # the kernel
            hm = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
            out_t, lse_t = pk._flash_fwd_once_call(
                hm(q), hm(k), hm(v), jnp.array([0, k_off], jnp.int32),
                causal=True, scale=scale, block_q=64, block_k=64,
                interpret=True, fusable=True)
            assert lse_t.shape == (b * h, 1, t)
            assert lse_t.dtype == jnp.float32
            out = pk._heads_minor(out_t, b, h, t, d)
            lse = lse_t.reshape(b, h, t)
        else:
            m, l, o = pk.flash_attention_step(
                q, k, v, jnp.full((b, h, t), -jnp.inf, jnp.float32),
                jnp.zeros((b, h, t), jnp.float32), jnp.zeros(q.shape),
                jnp.int32(0), jnp.int32(k_off), causal=True, scale=scale)
            out, lse = pk.finalize_attention_stats(m, l, o, jnp.float32)
        got = {"out": out, "lse": lse}
        if "dq" in produces or "dk" in produces:
            got.update(zip(("dq", "dk", "dv"), pk._flash_bwd(
                q, k, v, out, lse, dout, jnp.int32(0), jnp.int32(k_off),
                causal=True, scale=scale)))
        return got

    got = run()
    assert kernel in taken
    # through the dispatchers' jax.jit, the bits of a kernel traced at its
    # call site (a ring hop's run-time offsets are operands of both)
    _at_every_call_site(monkeypatch)
    for name, x in run().items():
        np.testing.assert_array_equal(np.asarray(got[name]), np.asarray(x),
                                      err_msg=name)

    ref = dict(zip(("out", "dq", "dk", "dv"),
                   _masked_reference(q, k, v, dout, 0, k_off, True)))
    for name in produces:
        x = np.asarray(got[name])
        if name == "lse":
            assert not x[:, :, :dead].any()           # the sentinel
            assert np.isfinite(x).all()
            continue
        if name in ("out", "dq"):
            assert not x[:, :dead].any(), f"{name}: dead rows not exact 0"
        elif dead == t:
            assert not x.any(), f"{name}: not exact 0"
        np.testing.assert_allclose(x, np.asarray(ref[name]), rtol=2e-4,
                                   atol=2e-4, err_msg=name)


# cell -> (per-chip batch, heads, positions): its attention layers' calls
_CELL_ATTENTION = {
    "gpt2m-train-s1024": (8, 16, 1024),
    "gpt2m-train-dp4": (8, 16, 1024),
    "gpt2l-train-s1024": (4, 20, 1024),
    "granite4hm-train-s4096": (1, 32, 4096),
}


@pytest.mark.parametrize("cell", sorted(_CELL_ATTENTION))
def test_flash_plan_at_the_cells_shapes(cell, monkeypatch):
    """The plan of a cell's causal calls (sub-tiles computed, masked,
    skipped), and the kernels' cost estimates made of it. The 512 x 1024
    tiles alone compute the whole square at 1024 positions and five eighths
    of it at 4096: the forward still does (whole key blocks), the backward
    computes 3 of 4 and 36 of 64 sub-tiles of 512 x 512 (10 of 16 and 136
    of 256 were the edge 256)."""
    b, h, t = _CELL_ATTENTION[cell]
    block_q, block_k = pk._pick_block(t, side="q"), pk._pick_block(
        t, side="k")
    assert (block_q, block_k) == (512, 1024)
    sub_q, sub_k = pk._pick_sub_tile(True, block_q, block_k)
    tiles = pk.flash_plan(True, t, t, 0, 0, block_k, block_q, block_k)
    plan = pk.flash_plan(True, t, t, 0, 0, block_k, sub_q, sub_k)
    assert tiles["scores"] == {1024: 1.0, 4096: 0.625}[t] * t * t
    assert plan["scores"] <= {1024: 0.75, 4096: 0.5625}[t] * t * t
    assert plan["scores"] >= t * (t + 1) // 2        # every needed score
    assert plan["computed"] + plan["skipped"] == (t // sub_q) * (t // sub_k)
    assert plan["masked"] == plan["computed"]
    assert plan["computed"] == {512: {1024: 3, 4096: 36},
                                256: {1024: 10, 4096: 136}}[sub_q][t]
    whole = pk.flash_plan(False, t, t, 0, 0, block_k, *pk._pick_sub_tile(
        False, block_q, block_k))
    assert (whole["scores"], whole["masked"], whole["skipped"]) == (
        t * t, 0, 0)
    # a ring hop: wholly live, wholly dead, crossed off the sub-tile grid
    hop = functools.partial(pk.flash_plan, True, 256, 256, block_k=256,
                            sub_q=64, sub_k=64)
    assert hop(512, 0)["skipped"] == 0 and hop(0, 512)["computed"] == 0
    assert hop(96, 32)["computed"] == 2 + 3 + 4 + 4

    costs = {}
    real = pk._named_call

    def spy(name, kernel, **kw):
        costs[name] = kw["cost_estimate"]
        return real(name, kernel, **kw)

    monkeypatch.setattr(pk, "_named_call", spy)
    pk._flash_fullattn_vjp.cache_clear()
    x = jax.ShapeDtypeStruct((b, t, h, 64), jnp.bfloat16)
    jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(pk.flash_attention(
        q, k, v, causal=True).astype(jnp.float32)), argnums=(0, 1, 2)),
        x, x, x)
    pk._flash_fullattn_vjp.cache_clear()
    fwd, bwd = b * h * tiles["scores"], b * h * plan["scores"]
    assert costs["flash_fwd"].flops == 4 * fwd * 64
    assert costs["flash_fwd"].transcendentals == fwd
    assert costs["flash_bwd"].flops == 10 * bwd * 64
    assert costs["flash_bwd"].transcendentals == bwd
    # the row statistics at their true bytes, 4 a position: the forward
    # writes lse beside q, k, v, out in bf16; the backward reads lse beside
    # its nine [T, 64] operands and results (out among them: D is formed in
    # the kernel), priced at 4 bytes
    bh = b * h
    assert costs["flash_fwd"].bytes_accessed == 2 * 4 * bh * t * 64 + 4 * bh * t
    assert costs["flash_bwd"].bytes_accessed == (
        4 * 9 * bh * t * 64 + 4 * bh * t)


# ------------------------------------------- fused quantize + pack (wire)
def test_int8_quantize_pack_matches_unfused_pair():
    """Packed rows carry exactly the payload + scales of the unfused
    two-buffer kernel: unpacking reproduces int8_quantize_2d bit-for-bit."""
    rng = np.random.RandomState(3)
    x = rng.randn(16, 256).astype(np.float32)
    x[0, :] = 0.0  # all-zero block exercises the scale>0 guard
    packed = pk.int8_quantize_pack_2d(jnp.asarray(x))
    assert packed.shape == (16, 256 + pk.PACK_SCALE_BYTES)
    assert packed.dtype == jnp.int8
    q, s = pk.int8_quantize_2d(jnp.asarray(x))
    uq, us = pk.int8_unpack(packed)
    np.testing.assert_array_equal(np.asarray(uq), np.asarray(q))
    np.testing.assert_array_equal(np.asarray(us), np.asarray(s))


def test_int8_quantize_pack_kernel_vs_ref_bits():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(32, 128).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(pk.int8_quantize_pack_2d(x)),
        np.asarray(pk.int8_quantize_pack_ref(x)))


def test_int8_quantize_pack_fallback_non_lane_aligned():
    """Shapes the kernel can't tile (rows=5, block=100) dispatch to the jnp
    reference — same bits, and the dequantized roundtrip stays within the
    per-row quantization bound."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(5, 100).astype(np.float32))
    assert not pk.int8_supported(5, 100)
    packed = pk.int8_quantize_pack(x)
    np.testing.assert_array_equal(
        np.asarray(packed), np.asarray(pk.int8_quantize_pack_ref(x)))
    q, s = pk.int8_unpack(packed)
    deq = np.asarray(q, np.float32) * np.asarray(s)
    bound = np.max(np.abs(np.asarray(x)), axis=1, keepdims=True) / 127 * 0.51
    assert np.all(np.abs(deq - np.asarray(x)) <= bound + 1e-7)


def test_int8_quantize_pack_gating(monkeypatch):
    x = jnp.asarray(np.random.RandomState(6).randn(16, 128)
                    .astype(np.float32))
    ref = np.asarray(pk.int8_quantize_pack_ref(x))
    monkeypatch.setenv("HVD_PALLAS", "0")
    np.testing.assert_array_equal(np.asarray(pk.int8_quantize_pack(x)), ref)
    monkeypatch.setenv("HVD_PALLAS", "interpret")
    np.testing.assert_array_equal(np.asarray(pk.int8_quantize_pack(x)), ref)


# ------------------------------------------- fused matmul + reduce-scatter
def test_matmul_2d_matches_jnp():
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(64, 256).astype(np.float32))
    w = jnp.asarray(rng.randn(256, 128).astype(np.float32))
    assert pk.matmul_tiles(64, 256, 128) is not None
    np.testing.assert_allclose(np.asarray(pk.matmul_2d(x, w)),
                               np.asarray(x @ w), rtol=1e-5, atol=1e-4)


def test_matmul_tiles_gating(monkeypatch):
    assert pk.matmul_tiles(64, 256, 128) is not None
    assert pk.matmul_tiles(64, 250, 128) is None   # k not lane-aligned
    assert pk.matmul_tiles(64, 256, 100) is None   # n not lane-aligned
    assert pk.matmul_tiles(5, 256, 128) is None    # m has no block
    monkeypatch.setenv("HVD_PALLAS", "0")
    assert pk.matmul_tiles(64, 256, 128) is None


def _ring_mm_run(fn, x, w, m):
    """shard_map ``fn(x_shard, w_shard)`` over the hvd mesh axis; x/w are
    [m, ...] with one leading slice per rank."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    hvd.init()
    mesh = hvd.mesh()
    gx = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("hvd")))
    gw = jax.device_put(jnp.asarray(w), NamedSharding(mesh, P("hvd")))
    # check_vma=False pins the ring/kernel path (vma checking would
    # dispatch the fallback, same as spmd.adasum above)
    sm = jax.shard_map(lambda a, b: fn(a[0], b[0], "hvd")[None], mesh=mesh,
                       in_specs=P("hvd"), out_specs=P("hvd"),
                       check_vma=False)
    return np.asarray(jax.jit(sm)(gx, gw))


def test_matmul_reduce_scatter_matches_reference():
    """The compute/permute ring == psum_scatter(x @ w) up to f32 addition
    order, and both equal the dense cross-rank sum."""
    import horovod_tpu as hvd

    hvd.init()
    m = hvd.num_replicas()
    rows, kl, n = 8 * m, 128, 128
    rng = np.random.RandomState(8)
    x = rng.randn(m, rows, kl).astype(np.float32)
    w = rng.randn(m, kl, n).astype(np.float32)

    out = _ring_mm_run(pk.matmul_reduce_scatter, x, w, m)
    ref = _ring_mm_run(pk.matmul_reduce_scatter_reference, x, w, m)
    assert out.shape == ref.shape == (m, rows // m, n)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-3)
    dense = np.sum([x[i] @ w[i] for i in range(m)], axis=0)
    np.testing.assert_allclose(out.reshape(rows, n), dense,
                               rtol=1e-4, atol=1e-3)


def test_matmul_reduce_scatter_non_aligned_chunks():
    """Chunk shapes the MXU kernel can't tile (n=96 not lane-aligned) keep
    the ring but ride jnp.dot partials — same contraction."""
    import horovod_tpu as hvd

    hvd.init()
    m = hvd.num_replicas()
    rows, kl, n = 2 * m, 64, 96
    assert pk.matmul_tiles(rows // m, kl, n) is None
    rng = np.random.RandomState(9)
    x = rng.randn(m, rows, kl).astype(np.float32)
    w = rng.randn(m, kl, n).astype(np.float32)
    out = _ring_mm_run(pk.matmul_reduce_scatter, x, w, m)
    dense = np.sum([x[i] @ w[i] for i in range(m)], axis=0)
    np.testing.assert_allclose(out.reshape(rows, n), dense,
                               rtol=1e-4, atol=1e-3)


def test_matmul_reduce_scatter_fallback_when_off(monkeypatch):
    """HVD_PALLAS=0 routes straight to the unfused reference (bitwise —
    it IS the reference call)."""
    import horovod_tpu as hvd

    hvd.init()
    m = hvd.num_replicas()
    rng = np.random.RandomState(10)
    x = rng.randn(m, 4 * m, 64).astype(np.float32)
    w = rng.randn(m, 64, 128).astype(np.float32)
    ref = _ring_mm_run(pk.matmul_reduce_scatter_reference, x, w, m)
    monkeypatch.setenv("HVD_PALLAS", "0")
    out = _ring_mm_run(pk.matmul_reduce_scatter, x, w, m)
    np.testing.assert_array_equal(out, ref)


# ------------------------------- pack kernels at the shapes the ring produces
@pytest.mark.parametrize("rows", [1, 5, 33, 300, 1285])
@pytest.mark.parametrize("wire", ["int8", "int4"])
def test_quantize_pack_any_row_count_bit_equal(wire, rows):
    """A ring chunk has ceil(n / world / block) rows — any count, not a
    multiple of 8 — so the kernels run a cdiv grid with a partial last
    tile. Bits must equal the reference's on every row, scale bytes with
    the high bit set included (a large-magnitude row's f32 scale)."""
    pack, ref = {
        "int8": (pk.int8_quantize_pack, pk.int8_quantize_pack_ref),
        "int4": (pk.int4_quantize_pack, pk.int4_quantize_pack_ref)}[wire]
    x = np.random.RandomState(rows).randn(rows, 256).astype(np.float32)
    x[-1] *= 3.0e38 / np.abs(x[-1]).max()   # scale near f32 max
    x[0] = 0.0                       # the scale > 0 guard
    x = jnp.asarray(x)
    assert pk.kernel_path(f"{wire}_quantize_pack", x) == "pallas"
    np.testing.assert_array_equal(np.asarray(pack(x)), np.asarray(ref(x)))


def test_kernel_path_reports_each_hand_over(monkeypatch):
    x = jnp.zeros((16, 256), jnp.float32)
    assert pk.kernel_path("int8_quantize_pack", x) == "pallas"
    # a gate the shape fails
    assert pk.kernel_path("int8_quantize_pack", x[:, :100]) == "reference"
    assert pk.kernel_path("int4_quantize_pack", x[:, :128]) == "reference"
    q = jnp.zeros((1, 100, 2, 64), jnp.float32)
    assert pk.kernel_path("flash_attention", q, q, q) == "reference"
    assert pk.kernel_path("matmul_reduce_scatter", x, x.T, 1) == "reference"
    assert pk.kernel_path("matmul_reduce_scatter", x, x.T, 4) == "pallas"
    # varying operands under shard_map(check_vma=True)
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    paths = []
    jax.shard_map(lambda v: (paths.append(
        pk.kernel_path("int8_quantize_pack", v)), v)[1],
        mesh=mesh, in_specs=P("x"), out_specs=P("x"))(
        jnp.zeros((32, 256), jnp.float32))
    assert paths == ["reference"]
    # kernels off
    monkeypatch.setenv("HVD_PALLAS", "0")
    assert pk.kernel_path("int8_quantize_pack", x) == "reference"


@pytest.mark.parametrize("wire,digest,scale_hex", [
    ("int8", "f66fdb1369b85c3df72856db23267f41695f08bbb72ff23eb50478ecc559db65",
     "552a153fdd846d3a"),
    ("int4", "863f28f808d53edb19df5b8ed68d0dd284d4be91625459487ecc67cf1e3e7bf4",
     "9324294130aa863c"),
])
def test_packed_wire_rows_golden_bytes(wire, digest, scale_hex):
    """The wire format is a contract between ranks and between versions:
    these bytes were produced by the pre-PR-21 reference (whose kernels
    never compiled for the TPU); kernel and reference must still emit
    them."""
    import hashlib

    x = (np.arange(2 * 256, dtype=np.float32).reshape(2, 256)
         - 200.0) * np.float32(0.37)
    x[1] *= -1e-3
    for fn in (f"{wire}_quantize_pack", f"{wire}_quantize_pack_ref"):
        p = np.asarray(getattr(pk, fn)(jnp.asarray(x)))
        assert p[:, -4:].astype(np.uint8).tobytes().hex() == scale_hex
        assert hashlib.sha256(p.tobytes()).hexdigest() == digest


@pytest.mark.parametrize("why,shape,kw", [
    ("a window with it", (1, 128, 2, 64), dict(causal=True, window=16)),
    ("no causal flag", (1, 128, 2, 64), dict(causal=False)),
    ("T no multiple of B", (1, 136, 2, 64), dict(causal=True)),
    ("an odd number of rows", (1, 129, 2, 64), dict(causal=True)),
    ("a block length of nought", (1, 128, 2, 64),
     dict(causal=True, block_diffusion=0)),
])
def test_block_diffusion_mask_refuses_what_it_is_not_written_for(why, shape,
                                                                 kw):
    """``flash_attention(block_diffusion=B)``: ``window`` together with the
    mask is refused and not ignored, as is a call that is not ``2 T`` rows
    of queries and keys with ``T`` a multiple of ``B``; on either path."""
    x = jnp.zeros(shape, jnp.float32)
    kw = {"block_diffusion": 8, **kw}
    with pytest.raises(ValueError, match="block_diffusion"):
        pk.flash_attention(x, x, x, **kw)
    with pytest.raises(ValueError, match="block_diffusion"):
        pk.flash_attention(x, x[:, :64], x[:, :64], causal=True,
                           block_diffusion=8)


def test_block_diffusion_mask_refuses_the_streaming_kernels(monkeypatch):
    """A head whose K and V are not resident, or whose dq scratch is over
    the cap, takes kernels the mask is not written for: refused where the
    kernels are on, by shape alone."""
    monkeypatch.setenv("HVD_PALLAS", "interpret")
    monkeypatch.setattr(pk, "_KV_VMEM_CAP", 1)
    x = jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="streaming kernels"):
        jax.eval_shape(lambda q: pk.flash_attention(
            q, q, q, causal=True, block_diffusion=4), x)
    with pytest.raises(ValueError, match="takes no window"):
        pk.flash_attention_step(None, None, None, None, None, None, 0, 0,
                                window=4)
