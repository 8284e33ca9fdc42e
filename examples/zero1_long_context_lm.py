#!/usr/bin/env python
"""Memory-lever tour: ZeRO-1 state sharding + rematerialization + chunked
vocab loss on the transformer LM.

The three knobs that decide what fits in HBM (measured on a v5e in
docs/benchmarks.md):

  * ``optim.zero``      — AdamW m/v sharded 1/N over the replica axis
  * ``remat="full"``    — recompute block internals in backward, all but
                          the attention kernel's output
  * ``lm_loss_chunked`` — never materialize the [B, T, vocab] fp32 logits

Run on the 8-device virtual CPU mesh:

    JAX_PLATFORMS=cpu \\
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/zero1_long_context_lm.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu import spmd
from horovod_tpu.models.transformer import TransformerLM, lm_loss_chunked
from horovod_tpu.optim.zero import shard_opt_state


def main():
    hvd.init()
    mesh = hvd.mesh()
    n = hvd.num_replicas()
    vocab, batch, seq = 211, 2 * n, 128

    model = TransformerLM(vocab_size=vocab, num_layers=2, num_heads=2,
                          d_model=64, max_seq_len=seq, dtype=jnp.float32,
                          remat="full")
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, vocab, (batch, seq + 1)))
    params = model.init(jax.random.PRNGKey(0), toks[:1, :-1])["params"]
    tx = optax.adamw(3e-3, mu_dtype=jnp.bfloat16)  # bf16 first moment
    opt_state = tx.init(params)

    def loss_fn(p, data):
        x, y = data
        hid = model.apply({"params": p}, x, return_hidden=True)
        return lm_loss_chunked(hid, p["tok_emb"]["embedding"], y,
                               chunk_tokens=64)

    step = spmd.make_train_step(loss_fn, tx, mesh=mesh, zero1=True,
                                example_opt_state=opt_state)
    params = spmd.replicate(params, mesh)
    opt_state = shard_opt_state(opt_state, mesh)

    mu = jax.tree_util.tree_leaves(opt_state[0].mu)[1]
    print(f"devices={n}; a mu leaf holds "
          f"{mu.addressable_shards[0].data.shape} of {mu.shape} per device")

    data = (spmd.shard_batch(toks[:, :-1], mesh),
            spmd.shard_batch(toks[:, 1:], mesh))
    for i in range(30):
        params, opt_state, loss = step(params, opt_state, data)
        if i % 10 == 0 or i == 29:
            print(f"step {i:3d}  loss {float(loss):.4f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
