"""Quantized GSPMD fast-path tests (docs/gspmd.md): the int8/int4 ppermute
ring inside the compiled step — parity against eager mirrors and the plain
GSPMD collectives, the error-feedback residual, the ``HOROVOD_GSPMD_WIRE``
knob, the footprint catalog, and the knob-unset cache-key pin.

Runs on the 8-device virtual CPU platform like the rest of the suite.
"""

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import spmd
from horovod_tpu.ops import compression as comp

BLOCK = 256  # pin the block so HOROVOD_INT8_BLOCK in the env can't skew


def _shard_map(fn, mesh, in_specs, out_specs):
    import jax

    return jax.jit(spmd._shard_map(fn, mesh, in_specs, out_specs))


def _roundtrip(vec, wire, block=BLOCK):
    """Eager mirror of one quantized hop: the same block math the ring's
    pack/unpack kernels implement (comp.quantize_blocks is bit-identical
    to the fused kernels — tests/test_pallas.py)."""
    import jax.numpy as jnp

    flat = jnp.asarray(vec, jnp.float32)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    q, s = comp.quantize_blocks(flat, block, bits=4 if wire == "int4" else 8)
    return np.asarray(comp.dequantize_blocks(q, s, jnp.float32, block)
                      )[:np.size(vec)]


def _mirror_allreduce(xs, wire, block=BLOCK):
    """Numpy mirror of the full quantized ring (RS then AG), same hop
    schedule and quantization points as spmd.quantized_allreduce."""
    m, num = len(xs), xs[0].size
    chunk = spmd._ring_chunk(num, m, block)
    padded = [np.pad(np.asarray(x, np.float32).ravel(),
                     (0, m * chunk - num)) for x in xs]

    def local(p, k):
        i = (p - k - 1) % m
        return padded[p][i * chunk:(i + 1) * chunk]

    acc = [local(p, 0).copy() for p in range(m)]
    for k in range(1, m):
        wired = [_roundtrip(acc[p], wire, block) for p in range(m)]
        acc = [wired[(p - 1) % m] + local(p, k) for p in range(m)]
    # all-gather: every rank (owner included) dequantizes the same packed
    # bytes, so the mirror is one roundtrip per owned chunk
    gathered = np.concatenate([_roundtrip(acc[p], wire, block)
                               for p in range(m)])
    return gathered[:num] / m


# ------------------------------------------------------------ knob parsing
def test_gspmd_wire_env_parsing(monkeypatch):
    monkeypatch.delenv("HOROVOD_GSPMD_WIRE", raising=False)
    assert spmd.gspmd_wire() == ""
    for off in ("", "0", "off", "none", "OFF"):
        monkeypatch.setenv("HOROVOD_GSPMD_WIRE", off)
        assert spmd.gspmd_wire() == ""
    monkeypatch.setenv("HOROVOD_GSPMD_WIRE", "int8")
    assert spmd.gspmd_wire() == "int8"
    assert spmd.gspmd_wire("int8") == "int8"
    monkeypatch.setenv("HOROVOD_GSPMD_WIRE", "fp8")
    with pytest.raises(ValueError, match="int8|int4|off"):
        spmd.gspmd_wire()
    with pytest.raises(ValueError):
        spmd.gspmd_wire("bf16")


def test_gspmd_wire_int4_needs_gate_admission(monkeypatch):
    from horovod_tpu.ops.adaptive import ConvergenceGate

    # Other tests may have left an instance-level `allows` shadow on the
    # shared singleton (monkeypatch's inherited-attr undo); force a fresh
    # singleton so the class-level patches below are what shared() sees.
    monkeypatch.setattr(ConvergenceGate, "_shared", None)
    monkeypatch.setattr(ConvergenceGate, "allows", lambda self, mode: False)
    assert spmd.gspmd_wire("int4") == "int8"  # refused -> downgrade
    monkeypatch.setattr(ConvergenceGate, "allows", lambda self, mode: True)
    assert spmd.gspmd_wire("int4") == "int4"


# ------------------------------------------------------------ ring parity
@pytest.mark.parametrize("wire", ["int8", "int4"])
def test_quantized_allreduce_matches_eager_mirror(wire):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    hvd.init()
    mesh, n = hvd.mesh(), hvd.num_replicas()
    num = 700  # not a block multiple: exercises the ring padding
    xs = np.random.RandomState(0).randn(n, num).astype(np.float32)
    gx = jax.device_put(jnp.asarray(xs), NamedSharding(mesh, P("hvd")))

    out = _shard_map(
        lambda v: spmd.quantized_allreduce(v[0], wire=wire, block=BLOCK)[None],
        mesh, P("hvd"), P("hvd"))(gx)
    out = np.asarray(out)

    mirror = _mirror_allreduce(list(xs), wire)
    exact = xs.mean(axis=0)
    # tight vs the mirror (same schedule, FMA reassociation is the only
    # slack) but only loosely vs the exact mean — proves the ring follows
    # the quantized schedule rather than accidentally computing exactly
    for row in out:
        np.testing.assert_allclose(row, mirror, rtol=1e-4, atol=1e-5)
    q_err = np.max(np.abs(mirror - exact))
    assert q_err > 1e-4  # quantization really happened
    np.testing.assert_allclose(out[0], exact, atol=4 * q_err + 1e-5)


def test_quantized_allreduce_bit_identical_across_ranks():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    hvd.init()
    mesh, n = hvd.mesh(), hvd.num_replicas()
    xs = np.random.RandomState(1).randn(n, 513).astype(np.float32)
    gx = jax.device_put(jnp.asarray(xs), NamedSharding(mesh, P("hvd")))
    out = np.asarray(_shard_map(
        lambda v: spmd.quantized_allreduce(v[0], wire="int8",
                                           block=BLOCK)[None],
        mesh, P("hvd"), P("hvd"))(gx))
    # the replicated-params invariant: every rank dequantizes the same
    # packed bytes, so the gathered result is BIT-identical everywhere
    for p in range(1, n):
        assert np.array_equal(out[0], out[p])


def test_exact_wire_ring_matches_plain_collectives():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    hvd.init()
    mesh, n = hvd.mesh(), hvd.num_replicas()
    num = 96
    xs = np.random.RandomState(2).randn(n, num).astype(np.float32)
    gx = jax.device_put(jnp.asarray(xs), NamedSharding(mesh, P("hvd")))

    # wire values outside int8/int4 run the identical ring schedule on raw
    # f32 — the exact-wire reference arm
    chunks = np.asarray(_shard_map(
        lambda v: spmd.quantized_reduce_scatter(v[0], wire="fp32")[None],
        mesh, P("hvd"), P("hvd"))(gx))
    chunk = -(-num // n)
    total = np.pad(xs.sum(axis=0), (0, n * chunk - num))
    for p in range(n):
        np.testing.assert_allclose(chunks[p], total[p * chunk:(p + 1) * chunk],
                                   rtol=1e-5, atol=1e-5)

    plain = np.asarray(_shard_map(
        lambda v: spmd.allreduce(v[0], op=hvd.Average)[None],
        mesh, P("hvd"), P("hvd"))(gx))
    ring = np.asarray(_shard_map(
        lambda v: spmd.quantized_all_gather(
            spmd.quantized_reduce_scatter(v[0], wire="fp32"),
            wire="fp32")[None],
        mesh, P("hvd"), P("hvd"))(gx))[:, :num] / n
    np.testing.assert_allclose(ring, plain, rtol=1e-5, atol=1e-5)


def test_small_and_nonaligned_payloads_fall_back_exact():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    hvd.init()
    mesh, n = hvd.mesh(), hvd.num_replicas()

    def both(xs, **kw):
        gx = jax.device_put(jnp.asarray(xs), NamedSharding(mesh, P("hvd")))
        q = _shard_map(
            lambda v: spmd.quantized_allreduce(v[0], **kw)[None],
            mesh, P("hvd"), P("hvd"))(gx)
        plain = _shard_map(
            lambda v: spmd.allreduce(v[0])[None],
            mesh, P("hvd"), P("hvd"))(gx)
        return np.asarray(q), np.asarray(plain)

    # under one quantization block -> exact fallback, bit-equal
    tiny = np.random.RandomState(3).randn(n, 10).astype(np.float32)
    q, plain = both(tiny, wire="int8", block=BLOCK)
    assert np.array_equal(q, plain)

    # int4 with an odd block cannot nibble-split -> exact fallback
    odd = np.random.RandomState(4).randn(n, 300).astype(np.float32)
    q, plain = both(odd, wire="int4", block=255)
    assert np.array_equal(q, plain)

    # integer payloads never ride the quantized wire
    ints = np.arange(n * 512, dtype=np.int64).reshape(n, 512)
    gx = jax.device_put(jnp.asarray(ints), NamedSharding(mesh, P("hvd")))
    q = np.asarray(_shard_map(
        lambda v: spmd.quantized_allreduce(v[0], op=hvd.Sum,
                                           wire="int8")[None],
        mesh, P("hvd"), P("hvd"))(gx))
    assert np.array_equal(q[0], ints.sum(axis=0))


def test_quantized_allreduce_rejects_adasum():
    import jax.numpy as jnp

    hvd.init()
    with pytest.raises(NotImplementedError, match="Adasum"):
        spmd.quantized_allreduce(jnp.zeros(512), op=hvd.Adasum, wire="int8")


# ------------------------------------------------------- whole-step parity
def _linreg(n, elements=520, batch_per=2, seed=0):
    """Tiny linear-regression problem: multi-leaf params (tests the flat
    pack/split), non-block-aligned total, batch sharded n ways."""
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    batch = batch_per * n
    x = rng.randn(batch, elements).astype(np.float32) / np.sqrt(elements)
    w = rng.randn(elements).astype(np.float32)
    y = (x @ w + 0.1).astype(np.float32)
    params = {"w": jnp.zeros((elements,), jnp.float32),
              "b": jnp.zeros((), jnp.float32)}

    def loss_fn(p, b):
        xb, yb = b
        return jnp.mean((xb @ p["w"] + p["b"] - yb) ** 2)

    return params, loss_fn, (jnp.asarray(x), jnp.asarray(y))


@pytest.mark.parametrize("zero1", [False, True])
def test_quantized_step_converges(zero1):
    import jax
    import optax

    hvd.init()
    mesh, n = hvd.mesh(), hvd.num_replicas()
    params, loss_fn, batch = _linreg(n)
    tx = optax.adam(0.05)
    step = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False,
                                zero1=zero1, compression="int8")
    p = spmd.replicate(params, mesh)
    o = spmd.quantized_opt_state(tx, params, mesh, zero1=zero1)
    data = spmd.shard_batch(batch, mesh)
    losses = []
    for _ in range(20):
        p, o, loss = step(p, o, data)
        losses.append(float(loss))
    assert losses[-1] < 0.2 * losses[0], losses
    assert np.isfinite(losses).all()


def test_zero1_quantized_state_is_sharded():
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    hvd.init()
    mesh, n = hvd.mesh(), hvd.num_replicas()
    params, loss_fn, batch = _linreg(n)
    tx = optax.adam(0.05)
    o = spmd.quantized_opt_state(tx, params, mesh, zero1=True)
    inner, ef = o
    total = sum(int(np.prod(np.shape(l) or (1,)))
                for l in jax.tree_util.tree_leaves(params))
    from horovod_tpu.optim.zero import ring_chunk

    padded = n * ring_chunk(total, n, comp.block_size())
    sharded = [l for l in jax.tree_util.tree_leaves(inner)
               if np.shape(l) == (padded,)]
    assert sharded, "flat zero1 state should carry full-length leaves"
    for leaf in sharded:
        assert leaf.sharding.spec == P("hvd")  # 1/N per rank: the memory win
    assert ef.shape == (n, total) and ef.sharding.spec == P("hvd")

    # state sharding survives the step itself
    step = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False,
                                zero1=True, compression="int8")
    p = spmd.replicate(params, mesh)
    p, o, _ = step(p, o, spmd.shard_batch(batch, mesh))
    for leaf in jax.tree_util.tree_leaves(o[0]):
        if np.shape(leaf) == (padded,):
            assert leaf.sharding.spec == P("hvd")


def test_error_feedback_residual_math_and_replay():
    import jax
    import optax

    hvd.init()
    mesh, n = hvd.mesh(), hvd.num_replicas()
    params, loss_fn, batch = _linreg(n)
    tx = optax.sgd(0.05)
    step = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False,
                                compression="int8")
    p0 = spmd.replicate(params, mesh)
    o0 = spmd.quantized_opt_state(tx, params, mesh)
    data = spmd.shard_batch(batch, mesh)

    p1, o1, _ = step(p0, o0, data)
    ef = np.asarray(o1[1])
    block = comp.block_size()

    # after the first step (EF starts at zero) rank p's residual row is
    # exactly grad_p - roundtrip(grad_p) on its local batch shard
    per = batch[0].shape[0] // n
    for p in range(n):
        local = (batch[0][p * per:(p + 1) * per],
                 batch[1][p * per:(p + 1) * per])
        g = jax.grad(loss_fn)(params, local)
        flat = np.concatenate(  # tree-flatten order: b then w
            [np.ravel(np.asarray(l, np.float32))
             for l in jax.tree_util.tree_leaves(g)])
        expect = flat - _roundtrip(flat, "int8", block)
        np.testing.assert_allclose(ef[p], expect, rtol=1e-5, atol=1e-6)
        assert np.abs(ef[p]).max() > 0  # the wire really dropped something

    # deterministic: replaying the same step reproduces every output
    # BIT-for-bit (the "bit-deterministic across replicas" contract)
    p1b, o1b, _ = step(p0, o0, data)
    assert np.array_equal(np.asarray(o1b[1]), ef)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p1b)):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    # and the residual feeds the NEXT step: step 2 from o1 differs from a
    # hypothetical step 2 with the residual zeroed out
    p2, o2, _ = step(p1, o1, data)
    o1_zero = (o1[0], o1[1] * 0)
    p2z, _, _ = step(p1, o1_zero, data)
    assert not np.array_equal(np.asarray(p2["w"]), np.asarray(p2z["w"]))


# ---------------------------------------------------------- byte catalog
def test_gspmd_wire_footprint_catalog():
    f = comp.gspmd_wire_footprint
    # world of 1 never touches the wire
    for mode in ("none", "int8", "int4", "bf16"):
        assert f(1024, mode, 1, block=256) == 0
    # dim 1024 on 8 ranks, block 256: per-rank chunk 128 -> one packed row
    # per hop; 2*(world-1) hops across RS+AG
    assert f(1024, "none", 8) == 14 * 128 * 4 == 7168
    assert f(1024, "bf16", 8) == 14 * 128 * 2 == 3584
    assert f(1024, "int8", 8, block=256) == 14 * (256 + 4) == 3640
    assert f(1024, "int4", 8, block=256) == 14 * (128 + 4) == 1848
    # the acceptance ratios the three-way bench asserts — at a size whose
    # per-rank chunk is block-aligned (16k/8 = 2048 = 8 blocks); at 1024
    # above the 128-element chunk pads to a whole 256 block and the
    # per-element ratio is dominated by padding, which is why the bench
    # defaults to --elements 262144
    assert f(16384, "int4", 8, block=256) / f(16384, "none", 8) < 0.6
    assert 4.0 * f(16384, "int8", 8, block=256) / f(16384, "none", 8) <= 1.05
    with pytest.raises(ValueError):
        f(1024, "fp8", 8)


def test_instruments_cover_gspmd_ring():
    import jax
    import optax

    hvd.init()
    from horovod_tpu.metrics import instruments

    mesh, n = hvd.mesh(), hvd.num_replicas()
    params, loss_fn, batch = _linreg(n)
    tx = optax.sgd(0.05)
    step = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False,
                                compression="int8")
    p = spmd.replicate(params, mesh)
    o = spmd.quantized_opt_state(tx, params, mesh)
    data = spmd.shard_batch(batch, mesh)

    total = int(o[1].shape[1])
    block = comp.block_size()
    wire_c = instruments.wire_bytes().labels(compression="gspmd-int8")
    exact_c = instruments.wire_bytes_exact()
    w0, e0 = wire_c.value, exact_c.value
    for _ in range(3):
        p, o, _ = step(p, o, data)
    # truthful accounting: the counters advance by exactly the catalog
    # footprint per step — the same numbers the three-way bench reads
    assert wire_c.value - w0 == pytest.approx(
        3 * comp.gspmd_wire_footprint(total, "int8", n, block))
    assert exact_c.value - e0 == pytest.approx(
        3 * comp.gspmd_wire_footprint(total, "none", n, block))
    # the ratio gauge is a RUNNING wire/exact quotient over every quantized
    # step this process ran; at this tiny model the per-step ratio is
    # honestly ~0.98 (the 66-element chunk pads to one whole 256 block), so
    # only its bounds are stable here — the counter deltas above are the
    # precise accounting check
    ratio = instruments.quantization_ratio().value
    assert 0.0 < ratio <= 1.05


# ------------------------------------------------------------ cache-key pin
def _golden_plain_step(loss_fn, tx, mesh):
    """Verbatim copy of make_train_step's wire-off body on a multi-device
    mesh (zero1 off; since PR 21 loss and gradients run under shard_map so
    that Pallas kernels partition): the golden the pin compares against. If spmd.make_train_step's exact path
    drifts, update BOTH on purpose — the test exists to make that drift
    loud, because an accidental change to the wire-off program invalidates
    every user's jit cache."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    repl = NamedSharding(mesh, P())
    local = jax.value_and_grad(loss_fn)
    loss_and_grads = jax.shard_map(
        lambda params, batch: jax.lax.pmean(local(params, batch), "hvd"),
        mesh=mesh, in_specs=(P(), P("hvd")), out_specs=P(),
        check_vma=False)

    def step(params, opt_state, batch):
        loss, grads = loss_and_grads(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1),
                   out_shardings=(repl, repl, repl))


def test_knob_unset_compiles_identical_program(monkeypatch):
    import jax
    import optax

    hvd.init()
    monkeypatch.delenv("HOROVOD_GSPMD_WIRE", raising=False)
    mesh, n = hvd.mesh(), hvd.num_replicas()
    params, loss_fn, batch = _linreg(n)
    tx = optax.sgd(0.05)
    p = spmd.replicate(params, mesh)
    o = spmd.replicate(tx.init(params), mesh)
    data = spmd.shard_batch(batch, mesh)

    golden = _golden_plain_step(loss_fn, tx, mesh
                                ).lower(p, o, data).as_text()
    unset = spmd.make_train_step(loss_fn, tx, mesh=mesh
                                 ).lower(p, o, data).as_text()
    # byte-identical StableHLO: same program, same jit cache key — adding
    # the knob did not perturb the wire-off path
    assert unset == golden
    off = spmd.make_train_step(loss_fn, tx, mesh=mesh, compression="off"
                               ).lower(p, o, data).as_text()
    assert off == golden

    # and flipping the knob on really changes the program shape
    monkeypatch.setenv("HOROVOD_GSPMD_WIRE", "int8")
    quant = spmd.make_train_step(loss_fn, tx, mesh=mesh)
    assert hasattr(quant, "jitted")  # the instrumented quantized wrapper


def _golden_quantized_ring_step(loss_fn, tx, mesh, wire, block):
    """Verbatim copy of _make_quantized_step's pre-algorithm-zoo body
    (zero1 off, donate off): the golden the HOROVOD_GSPMD_ALGO pin
    compares against. If the exact ring trace drifts, update BOTH on
    purpose — an accidental change invalidates every user's jit cache."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[spmd.MESH_AXIS]

    def _flatten_f32(leaves):
        parts = [jnp.ravel(l).astype(jnp.float32) for l in leaves]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def _split_like(flat, leaves):
        out, off = [], 0
        for l in leaves:
            out.append(flat[off:off + l.size].reshape(l.shape)
                       .astype(l.dtype))
            off += l.size
        return out

    def local_step(params, inner, ef, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        g_leaves, treedef = jax.tree_util.tree_flatten(grads)
        flat = _flatten_f32(g_leaves)
        total = flat.shape[0]
        corrected = flat + ef[0]
        use_ring = spmd._wire_eligible(total, corrected.dtype, wire, block)
        if use_ring:
            new_ef = (corrected
                      - spmd._wire_roundtrip(corrected, wire, block))[None]
        else:
            new_ef = jnp.zeros_like(ef)
        reduced = spmd.quantized_allreduce(
            corrected, hvd.Average, spmd.MESH_AXIS, wire, block)
        grads = jax.tree_util.tree_unflatten(
            treedef, _split_like(reduced, g_leaves))
        updates, inner = tx.update(grads, inner, params)
        params = optax.apply_updates(params, updates)
        loss = jax.lax.pmean(loss, spmd.MESH_AXIS)
        return params, inner, new_ef, loss

    def step(params, opt_state, batch):
        inner, ef = opt_state
        inner_specs = jax.tree_util.tree_map(lambda l: P(), inner)
        fn = spmd._shard_map(
            local_step, mesh,
            in_specs=(P(), inner_specs, P(spmd.MESH_AXIS),
                      P(spmd.MESH_AXIS)),
            out_specs=(P(), inner_specs, P(spmd.MESH_AXIS), P()))
        params, inner, ef, loss = fn(params, inner, ef, batch)
        return params, (inner, ef), loss

    return jax.jit(step)


def test_algo_unset_compiles_identical_quantized_program(monkeypatch):
    """HOROVOD_GSPMD_ALGO unset/"ring" pins: the quantized fast path must
    lower to byte-identical StableHLO as the pre-zoo ring builder — the
    algorithm axis is free until someone actually flips it."""
    import optax

    hvd.init()
    monkeypatch.setenv("HOROVOD_GSPMD_WIRE", "int8")
    monkeypatch.setenv("HOROVOD_INT8_BLOCK", str(BLOCK))
    monkeypatch.delenv("HOROVOD_GSPMD_ALGO", raising=False)
    mesh, n = hvd.mesh(), hvd.num_replicas()
    params, loss_fn, batch = _linreg(n)
    tx = optax.sgd(0.05)
    p = spmd.replicate(params, mesh)
    o = spmd.quantized_opt_state(tx, params, mesh)
    data = spmd.shard_batch(batch, mesh)

    golden = _golden_quantized_ring_step(loss_fn, tx, mesh, "int8", BLOCK
                                         ).lower(p, o, data).as_text()
    unset = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False
                                 ).jitted.lower(p, o, data).as_text()
    assert unset == golden
    ring = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False,
                                algorithm="ring"
                                ).jitted.lower(p, o, data).as_text()
    assert ring == golden

    # and a zoo member really changes the traced program
    tree = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False,
                                algorithm="tree"
                                ).jitted.lower(p, o, data).as_text()
    assert tree != golden
