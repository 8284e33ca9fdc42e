"""SPMD fast-path tests: in-jit collectives + whole-step training over the
replica mesh (the performance path replacing the reference's NCCL engine)."""

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import spmd


def _shard_map(fn, mesh, in_specs, out_specs):
    import jax

    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def test_spmd_allreduce_ops():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    hvd.init()
    mesh = hvd.mesh()
    n = hvd.num_replicas()
    x = jnp.arange(n * 2, dtype=jnp.float32).reshape(n, 2)
    gx = jax.device_put(x, NamedSharding(mesh, P("hvd")))

    out = _shard_map(lambda v: spmd.allreduce(v, op=hvd.Sum),
                     mesh, P("hvd"), P("hvd"))(gx)
    expected = x.sum(axis=0)
    for row in np.asarray(out):
        np.testing.assert_allclose(row, expected)

    out = _shard_map(lambda v: spmd.allreduce(v, op=hvd.Average),
                     mesh, P("hvd"), P("hvd"))(gx)
    for row in np.asarray(out):
        np.testing.assert_allclose(row, expected / n)


def test_spmd_broadcast():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    hvd.init()
    mesh = hvd.mesh()
    n = hvd.num_replicas()
    x = jnp.arange(n, dtype=jnp.float32).reshape(n, 1)
    gx = jax.device_put(x, NamedSharding(mesh, P("hvd")))
    out = _shard_map(lambda v: spmd.broadcast(v, root_rank=3),
                     mesh, P("hvd"), P("hvd"))(gx)
    np.testing.assert_allclose(np.asarray(out), np.full((n, 1), 3.0))


def test_spmd_adasum_matches_numpy():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tests_adasum_ref import numpy_adasum

    hvd.init()
    mesh = hvd.mesh()
    n = hvd.num_replicas()
    rng = np.random.RandomState(1)
    data = rng.randn(n, 17).astype(np.float32)
    gx = jax.device_put(jnp.asarray(data).reshape(n, 1, 17),
                        NamedSharding(mesh, P("hvd")))

    out = _shard_map(lambda v: spmd.adasum(v[0])[None],
                     mesh, P("hvd"), P("hvd"))(gx)
    expected = numpy_adasum([data[i] for i in range(n)])
    for row in np.asarray(out).reshape(n, 17):
        np.testing.assert_allclose(row, expected, rtol=3e-5, atol=3e-5)


def test_make_train_step_converges_and_averages():
    """Whole-step DP training: loss decreases and the result equals the
    single-device run on the concatenated batch (gradient averaging works)."""
    import jax
    import jax.numpy as jnp
    import optax

    hvd.init()
    mesh = hvd.mesh()
    n = hvd.num_replicas()

    rng = np.random.RandomState(0)
    W_true = rng.randn(4, 3).astype(np.float32)
    X = rng.randn(16 * n, 4).astype(np.float32)
    Y = X @ W_true

    def loss_fn(params, batch):
        x, y = batch
        pred = x @ params["w"]
        return jnp.mean((pred - y) ** 2)

    tx = optax.sgd(0.05)
    params = {"w": jnp.zeros((4, 3), jnp.float32)}
    opt_state = tx.init(params)
    params = spmd.replicate(params, mesh)
    opt_state = spmd.replicate(opt_state, mesh)
    batch = spmd.shard_batch((jnp.asarray(X), jnp.asarray(Y)), mesh)

    step = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False)
    losses = []
    for _ in range(50):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.05

    # compare against pure single-device training on the full batch
    p2 = {"w": jnp.zeros((4, 3), jnp.float32)}
    s2 = tx.init(p2)
    gf = jax.jit(jax.value_and_grad(loss_fn))
    for _ in range(50):
        l2, g2 = gf(p2, (jnp.asarray(X), jnp.asarray(Y)))
        up, s2 = tx.update(g2, s2, p2)
        p2 = optax.apply_updates(p2, up)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(p2["w"]),
                               rtol=1e-4, atol=1e-5)


def test_spmd_reduce_scatter_allgather_roundtrip():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    hvd.init()
    mesh = hvd.mesh()
    n = hvd.num_replicas()
    x = jnp.ones((n, n * 2), jnp.float32)
    gx = jax.device_put(x, NamedSharding(mesh, P("hvd")))

    def fn(v):
        rs = spmd.reduce_scatter(v[0])        # [2] chunk, summed
        return spmd.allgather(rs)[None]       # [n*2] reassembled

    out = _shard_map(fn, mesh, P("hvd"), P("hvd"))(gx)
    np.testing.assert_allclose(np.asarray(out),
                               np.full((n, n * 2), float(n)))


# ------------------------------------------------------------------- ZeRO-1
def test_zero1_state_sharded_and_math_identical():
    """optim/zero.py: optimizer state shards 1/N over the replica axis; the
    training math matches the replicated step's (GSPMD only changes
    placement)."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import spmd
    from horovod_tpu.optim.zero import shard_opt_state, zero1_shardings

    hvd.init()
    mesh = hvd.mesh()
    n = mesh.shape["hvd"]
    rng = np.random.RandomState(0)
    dim = 8 * n
    xs = jnp.asarray(rng.randn(4 * n, dim).astype(np.float32))
    w_true = jnp.asarray(rng.randn(dim).astype(np.float32))
    ys = xs @ w_true

    def loss_fn(params, batch):
        xb, yb = batch
        return jnp.mean((xb @ params["w"] - yb) ** 2)

    tx = optax.adamw(1e-2)
    params0 = {"w": jnp.zeros(dim)}
    opt0 = tx.init(params0)

    step_r = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False)
    p_r = spmd.replicate(params0, mesh)
    o_r = spmd.replicate(opt0, mesh)
    batch = (spmd.shard_batch(xs, mesh), spmd.shard_batch(ys, mesh))
    for _ in range(5):
        p_r, o_r, loss_r = step_r(p_r, o_r, batch)

    step_z = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False,
                                  zero1=True, example_opt_state=opt0)
    p_z = spmd.replicate(params0, mesh)
    o_z = shard_opt_state(opt0, mesh)
    mu_leaf = o_z[0].mu["w"]
    assert mu_leaf.addressable_shards[0].data.shape == (dim // n,)
    for _ in range(5):
        p_z, o_z, loss_z = step_z(p_z, o_z, batch)

    np.testing.assert_allclose(np.asarray(p_r["w"]), np.asarray(p_z["w"]),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(float(loss_r), float(loss_z), rtol=1e-6)
    abstract = jax.eval_shape(tx.init, params0)
    sh = zero1_shardings(abstract, mesh)
    assert sh[0].mu["w"].spec == jax.sharding.PartitionSpec("hvd")


def test_zero1_odd_shapes_replicate():
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.optim.zero import zero1_shardings

    hvd.init()
    mesh = hvd.mesh()
    n = mesh.shape["hvd"]
    params = {"odd": jnp.zeros((n + 1,)), "scalar": jnp.zeros(()),
              "mat": jnp.zeros((3, 2 * n))}
    tx = optax.adam(1e-3)
    sh = zero1_shardings(tx.init(params), mesh)
    P = jax.sharding.PartitionSpec
    assert sh[0].mu["odd"].spec == P()
    assert sh[0].mu["scalar"].spec == P()
    assert sh[0].mu["mat"].spec == P(None, "hvd")


def test_multi_device_step_with_pallas_attention_matches_one_device(
        monkeypatch):
    """The model's default attention is a Pallas call; over 8 devices the
    step runs it per shard under shard_map with an explicit pmean. Same
    global batch, same init: the one-device plain jit and the 8-device step
    must walk the same losses to the same params."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from horovod_tpu.models.transformer import TransformerLM, lm_loss
    from horovod_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("HVD_PALLAS", "interpret")
    hvd.init()
    model = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                          d_model=128, max_seq_len=32, dtype=jnp.float32)
    toks = np.random.RandomState(0).randint(0, 64, (8, 33)).astype(np.int32)
    batch = (jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:]))
    params0 = jax.jit(lambda key: model.init(key, batch[0][:1])["params"])(
        jax.random.PRNGKey(0))
    q = jnp.zeros((1, 32, 2, 64), jnp.float32)
    assert pk.kernel_path("flash_attention", q, q, q) == "pallas"

    def loss_fn(p, b):
        return lm_loss(model.apply({"params": p}, b[0]), b[1])

    # plain SGD: the update is linear in the averaged gradient, so a wrong
    # average cannot hide behind an optimizer that normalizes its scale
    tx = optax.sgd(0.5)

    def run(mesh):
        step = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False)
        p = spmd.replicate(params0, mesh)
        o = spmd.replicate(tx.init(params0), mesh)
        data = spmd.shard_batch(batch, mesh)
        losses = []
        for _ in range(2):
            p, o, loss = step(p, o, data)
            losses.append(float(loss))
        return losses, p

    devices = jax.devices()
    one_l, one_p = run(Mesh(np.asarray(devices[:1]), ("hvd",)))
    all_l, all_p = run(hvd.mesh())
    assert one_l[-1] < one_l[0]
    np.testing.assert_allclose(all_l, one_l, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(all_p),
                    jax.tree_util.tree_leaves(one_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    # and the replicas really are replicas
    leaf = jax.tree_util.tree_leaves(all_p)[0]
    first = np.asarray(leaf.addressable_shards[0].data)
    for s in leaf.addressable_shards[1:]:
        np.testing.assert_array_equal(first, np.asarray(s.data))


@pytest.fixture(scope="module")
def step_hlo():
    """The compiled HLO of a small LM step on one device and on two, and the
    TPU lowering of flash attention forward and forward + backward."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from horovod_tpu.models.transformer import TransformerLM, lm_loss
    from horovod_tpu.ops import pallas_kernels as pk

    model = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                          d_model=128, max_seq_len=32, dtype=jnp.float32)
    batch = (jnp.zeros((2, 32), jnp.int32), jnp.zeros((2, 32), jnp.int32))
    params = jax.eval_shape(
        lambda key: model.init(key, batch[0][:1])["params"],
        jax.random.PRNGKey(0))
    tx = optax.adamw(1e-3)

    def loss_fn(p, b):
        return lm_loss(model.apply({"params": p}, b[0]), b[1])

    def attention(q, k, v):
        return pk.flash_attention(q, k, v, causal=True)

    def attention_grads(q, k, v):
        return jax.grad(lambda *qkv: attention(*qkv).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    q = jax.ShapeDtypeStruct((2, 256, 2, 64), jnp.float32)
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("HVD_PALLAS", "interpret")
        for name, n in (("one", 1), ("two", 2)):
            mesh = Mesh(np.asarray(jax.devices()[:n]), ("hvd",))
            step = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False)
            out[name] = step.lower(
                params, jax.eval_shape(tx.init, params),
                batch).compile().as_text()
        mp.setenv("HVD_PALLAS", "on")
        with jax.enable_x64(False):   # Mosaic has no 64-bit types
            for name, fn in (("fwd", attention), ("bwd", attention_grads)):
                out[name] = jax.jit(fn).trace(q, q, q).lower(
                    lowering_platforms=("tpu",)).as_text(debug_info=True)
    return out


@pytest.mark.parametrize("program,pattern", [
    ("one", r'op_name="jit\(step\)/optimizer/'),
    ("one", r'op_name="jit\(step\)/jvp\(loss\)/'),
    ("one", r'op_name="jit\(step\)/transpose\(jvp\(loss\)\)/'),
    ("one", r'op_name="jit\(step\)/jvp\(TransformerLM\)/block_0/qkv/'),
    ("one", r'op_name="jit\(step\)/transpose\(jvp\(TransformerLM\)\)'
            r'/block_0/qkv/'),
    ("one", r'op_name="jit\(step\)/jvp\(TransformerLM\)/tok_emb\.attend/'),
    # a kernel's dispatcher is jitted (the rule below
    # ``pallas_kernels._named_call``): XLA inlines the call and joins the
    # site's path with the kernel's own
    ("one", r'op_name="jit\(step\)/jvp\(TransformerLM\)/block_0'
            r'/jit\(_flash_fwd_once_call\)/flash_fwd/'),
    ("one", r'op_name="jit\(step\)/transpose\(jvp\(TransformerLM\)\)'
            r'/block_0/jit\(_flash_bwd_fused\)/flash_bwd/'),
    ("two", r'op_name="jit\(step\)/shard_map/grad_allreduce/psum'),
    ("two", r'op_name="jit\(step\)/optimizer/'),
    ("two", r'op_name="jit\(step\)/shard_map/jvp\(loss\)/'),
    ("fwd", r'kernel_name = "flash_fwd"'),
    # in the lowered module the kernel sits once, in its dispatcher's
    # function under a path of its own, and a site is a ``call`` of it
    ("fwd", r'loc\("flash_fwd/flash_fwd/pallas_call"'),
    ("fwd", r'loc\("jit\(attention\)/jit\(_flash_fwd_once_call\)"'),
    ("bwd", r'kernel_name = "flash_bwd"'),
    ("bwd", r'loc\("flash_bwd/flash_bwd/pallas_call"'),
    ("bwd", r'loc\("jit\(attention_grads\)/transpose\([^"]*'
            r'jit\(_flash_bwd_fused\)\)*"'),
])
def test_compiled_step_names_its_parts(step_hlo, program, pattern):
    """The scopes a device trace is read by (docs/timeline.md): optimizer,
    loss, grad_allreduce from make_train_step and lm_loss, Flax's module
    names with jvp( / transpose( for the two passes, and the flash kernels'
    names on the custom calls the TPU would run."""
    import re

    assert "grad_allreduce" not in step_hlo["one"]
    assert re.search(pattern, step_hlo[program]), pattern
