#!/usr/bin/env python
"""Tour of every parallelism axis on one host: dp, tp, ep, pp, sp.

The reference framework is data-parallel only; this framework makes the
other axes first-class via `jax.sharding` meshes (docs/design.md). Each
leg below runs a real training step under the named sharding on 8 virtual
devices and prints the loss — swap the device counts for a TPU slice and
the same code runs over ICI.

    JAX_PLATFORMS=cpu python examples/parallelism_zoo.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import spmd
    from horovod_tpu.models.transformer import TransformerLMTiny
    from horovod_tpu.parallel import expert as epar
    from horovod_tpu.parallel import pipeline as ppar
    from horovod_tpu.parallel import tensor as tpar
    from horovod_tpu.parallel.ring_attention import make_ring_attention

    hvd.init()
    n = hvd.num_replicas()
    print(f"devices: {n} ({jax.default_backend()})")

    # ---- dp: batch sharded, params replicated, psum by GSPMD
    def lin_loss(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] - y) ** 2)

    tx = optax.sgd(0.1)
    step = spmd.make_train_step(lin_loss, tx, donate=False)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4 * n, 8).astype(np.float32))
    y = jnp.asarray(rng.randn(4 * n).astype(np.float32))
    p = spmd.replicate({"w": jnp.zeros(8)}, hvd.mesh())
    o = spmd.replicate(tx.init({"w": jnp.zeros(8)}), hvd.mesh())
    data = spmd.shard_batch((x, y), hvd.mesh())
    p, o, loss = step(p, o, data)
    print(f"dp   loss {float(loss):.4f}")

    # ---- dp x tp: Megatron transformer sharding
    mesh = tpar.make_dp_tp_mesh(dp=max(1, n // 2), tp=min(2, n))
    vocab = 97
    lm = TransformerLMTiny(vocab_size=vocab, dtype=jnp.float32,
                           attn_fn=tpar.plain_attention)
    toks = jnp.asarray(rng.randint(0, vocab, (2 * max(1, n // 2), 17)))
    params = lm.init(jax.random.PRNGKey(0), toks[:, :-1])["params"]

    def lm_loss(pr, b):
        logits = lm.apply({"params": pr}, b[0])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b[1]).mean()

    params = tpar.shard_params_tp(params, mesh)
    opt = tx.init(params)
    tp_step = tpar.make_tp_train_step(lm_loss, tx, mesh)
    batch = tpar.shard_batch_dp((toks[:, :-1], toks[:, 1:]), mesh)
    params, opt, loss = tp_step(params, opt, batch)
    print(f"tp   loss {float(loss):.4f}")

    # ---- dp x ep: switch-MoE experts sharded
    emesh = epar.make_dp_ep_mesh(dp=max(1, n // 2), ep=min(2, n))
    moe = epar.MoEMLP(num_experts=4, dtype=jnp.float32)
    xm = jnp.asarray(rng.randn(2 * max(1, n // 2), 6, 16).astype(np.float32))
    mp = moe.init(jax.random.PRNGKey(1), xm)["params"]

    def moe_loss(pr, b):
        out, aux = moe.apply({"params": pr}, b)
        return (out ** 2).mean() + 0.01 * aux

    mp = epar.shard_params_ep(mp, emesh)
    mo = tx.init(mp)
    ep_step = epar.make_ep_train_step(moe_loss, tx, emesh)
    mp, mo, loss = ep_step(mp, mo, tpar.shard_batch_dp(xm, emesh))
    print(f"ep   loss {float(loss):.4f}")

    # ---- pp: GPipe microbatch pipeline
    pmesh = ppar.make_pp_mesh(n)
    xp = jnp.asarray(rng.randn(8, 16).astype(np.float32))
    stacked = ppar.stack_stage_params(
        lambda r, s: {"w": 0.3 * jax.random.normal(
            r, (s.shape[-1], s.shape[-1]), jnp.float32)},
        jax.random.PRNGKey(2), n, xp)
    pp_step = ppar.make_pp_train_step(
        lambda pr, a: jnp.tanh(a @ pr["w"]),
        lambda a, t: ((a - t) ** 2).mean(), tx, pmesh, n_microbatches=4)
    sp_p = ppar.shard_stage_params(stacked, pmesh)
    sp_o = tx.init(sp_p)
    sp_p, sp_o, loss = pp_step(sp_p, sp_o, xp, jnp.zeros_like(xp))
    print(f"pp   loss {float(loss):.4f}")

    # ---- sp: ring attention over a sequence-sharded axis
    from jax.sharding import Mesh

    smesh = Mesh(np.asarray(jax.devices()[:n]), ("sp",))
    ring = make_ring_attention(smesh, axis_name="sp", causal=True)
    q = jnp.asarray(rng.randn(1, 8 * n, 2, 8).astype(np.float32) * 0.1)
    out = ring(q, q, q)
    print(f"sp   ring-attention out norm {float(jnp.linalg.norm(out)):.4f}")

    # ---- dp x tp x sp: 3D hybrid (manual dp/sp + GSPMD-auto tp)
    if n >= 8:
        from horovod_tpu.parallel import hybrid as hpar

        hmesh = hpar.make_dp_tp_sp_mesh(dp=2, tp=2, sp=n // 4)
        hm = hpar.hybrid_model(TransformerLMTiny, vocab_size=vocab,
                               dtype=jnp.float32)
        htoks = jnp.asarray(rng.randint(0, vocab, (4, 16 * (n // 4) + 1)))
        hx, hy = htoks[:, :-1], htoks[:, 1:]
        hp0 = TransformerLMTiny(vocab_size=vocab, dtype=jnp.float32).init(
            jax.random.PRNGKey(3), hx)["params"]
        hstep = hpar.make_hybrid_train_step(hm, tx, hmesh)
        hp = hpar.shard_params_hybrid(hp0, hmesh)
        ho = hpar.shard_opt_state_hybrid(tx.init(hp0), hp0, hmesh)
        hp, ho, loss = hstep(hp, ho, hpar.shard_data_hybrid(hx, hmesh),
                             hpar.shard_data_hybrid(hy, hmesh))
        print(f"3d   loss {float(loss):.4f} (dp x tp x sp)")

    print("all parallelism axes ran")
    hvd.shutdown()


if __name__ == "__main__":
    main()
