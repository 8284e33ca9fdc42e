#!/usr/bin/env python
"""Switch-MoE LM training with capacity-factor dispatch over a dp x ep mesh.

One weight-tied MoE block (embedding -> top-1 routed expert MLP ->
tied-head logits) trained two ways on synthetic tokens:

1. ``dispatch="exact"`` — the dense one-hot reference: every token
   reaches its expert, communication inserted by GSPMD.
2. ``dispatch="capacity"`` — the classic Switch recipe: fixed per-expert
   buffers (``ceil(CF * tokens / experts)`` slots), overflow tokens
   dropped, and the token exchange an explicit ``all_to_all`` over the
   ``ep`` axis — which is where ``HOROVOD_MOE_WIRE=int8|int4`` (or the
   ``wire=`` argument used here) ships the exchange quantized with an
   error-feedback residual per direction. Router logits, gates, and
   gradients always stay exact (docs/moe.md).

    JAX_PLATFORMS=cpu python examples/train_moe_lm.py
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

VOCAB, D_MODEL, EXPERTS, TOKENS, STEPS = 256, 64, 8, 2048, 20
CAPACITY_FACTOR = 1.25


def main():
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.metrics import instruments
    from horovod_tpu.parallel import expert as epar

    hvd.init()
    world = jax.device_count()
    ep = min(world, EXPERTS)
    dp = world // ep
    mesh = epar.make_dp_ep_mesh(dp, ep)
    print(f"devices: {world} ({jax.default_backend()}), mesh dp={dp} ep={ep}")

    key = jax.random.PRNGKey(0)
    host_params = dict(epar.init_moe_params(key, D_MODEL, EXPERTS,
                                            hidden_mult=2))
    host_params["emb"] = 0.02 * jax.random.normal(
        jax.random.PRNGKey(1), (VOCAB, D_MODEL), jnp.float32)
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, VOCAB, (TOKENS + 1,)))
    tokens, targets = toks[:-1], toks[1:]

    def head_loss(p, h, y, tgt, aux):
        logits = (h + y) @ p["emb"].T          # weight-tied readout
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, tgt).mean()
        return ce + 0.01 * aux                 # Switch balance loss

    def dense_loss(p, batch):
        tok, tgt = batch
        h = p["emb"][tok]
        y, aux = epar.dense_moe_apply(p, h)
        return head_loss(p, h, y, tgt, aux)

    def cap_loss(p, batch, moe):
        tok, tgt = batch
        h = p["emb"][tok]
        y, aux = moe(p, h)
        return head_loss(p, h, y, tgt, aux)

    tx = optax.adam(1e-2)

    # ---- exact one-hot reference (GSPMD-inserted communication)
    params = epar.shard_params_ep(
        jax.tree_util.tree_map(jnp.array, host_params), mesh)
    opt = epar.shard_params_ep(tx.init(params), mesh)
    step = epar.make_ep_train_step(dense_loss, tx, mesh)
    batch = (jax.device_put(tokens, NamedSharding(mesh, P("dp"))),
             jax.device_put(targets, NamedSharding(mesh, P("dp"))))
    for i in range(STEPS):
        params, opt, loss = step(params, opt, batch)
    print(f"exact one-hot dispatch:        final loss {float(loss):.4f}")

    # ---- capacity dispatch over the quantized int8 all_to_all
    params = epar.shard_params_ep(
        jax.tree_util.tree_map(jnp.array, host_params), mesh)
    opt = epar.moe_opt_state(tx, params, mesh, TOKENS, CAPACITY_FACTOR)
    step = epar.make_ep_train_step(
        cap_loss, tx, mesh, dispatch="capacity",
        capacity_factor=CAPACITY_FACTOR, wire="int8")
    sh = NamedSharding(mesh, P(("dp", "ep")))
    batch = (jax.device_put(tokens, sh), jax.device_put(targets, sh))
    for i in range(STEPS):
        params, opt, loss, stats = step(params, opt, batch)
    load = np.asarray(stats["load"])
    print(f"capacity dispatch (int8 wire): final loss {float(loss):.4f}")
    print(f"  capacity {int(stats['capacity'])} slots/expert "
          f"(CF={CAPACITY_FACTOR}), dropped "
          f"{float(stats['dropped']) / TOKENS:.1%} of tokens, "
          f"load imbalance {load.max() / load.mean():.2f}x")
    wire = instruments.wire_bytes().labels(compression="moe-int8").value
    exact = instruments.wire_bytes_exact().value
    if wire and exact:
        print(f"  dispatch bytes on the wire: {int(wire)} "
              f"({wire / exact:.1%} of the exact f32 exchange)")
    hvd.shutdown()


if __name__ == "__main__":
    main()
