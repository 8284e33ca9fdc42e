#!/usr/bin/env python
"""Skip-gram word2vec with SPARSE gradient allreduce.

Reference parity: `examples/tensorflow_word2vec.py` — embedding training
where each step touches a handful of vocabulary rows, so dense gradient
allreduce would ship the whole embedding matrix every step. Here the
embedding gradient is an `IndexedSlices` leaf: the engine reduces it as
two allgathers of (values, indices) — per-rank row counts may differ —
and the optimizer wrapper densifies the combined update
(`horovod_tpu.ops.sparse`).

    JAX_PLATFORMS=cpu python examples/word2vec_sparse.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        # the 2-rank local cluster below needs 2 devices
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")

import numpy as np


def train(vocab=200, dim=16, steps=30, window_batch=32):
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.ops import sparse as sp

    hvd.init()
    r = hvd.rank()
    rng = np.random.RandomState(7 + r)

    # toy corpus: token i co-occurs with i±1 (ring) — embeddings should pull
    # neighbors together
    emb_in = np.asarray(hvd.broadcast(
        0.1 * np.random.RandomState(0).randn(vocab, dim).astype(np.float32),
        root_rank=0, name="emb_in0"))
    emb_out = np.asarray(hvd.broadcast(
        0.1 * np.random.RandomState(1).randn(vocab, dim).astype(np.float32),
        root_rank=0, name="emb_out0"))

    tx = hvd.DistributedOptimizer(optax.sgd(0.5), op=hvd.Sum)
    state = tx.init({"in": emb_in, "out": emb_out})

    for step in range(steps):
        centers = rng.randint(0, vocab, (window_batch,))
        contexts = (centers + rng.choice([-1, 1], window_batch)) % vocab
        negatives = rng.randint(0, vocab, (window_batch,))

        # manual skip-gram grad with negative sampling (logistic loss)
        ci, co, ng = emb_in[centers], emb_out[contexts], emb_out[negatives]
        pos_sig = 1 / (1 + np.exp(-(ci * co).sum(1)))
        neg_sig = 1 / (1 + np.exp(-(ci * ng).sum(1)))
        d_ci = (pos_sig - 1)[:, None] * co + neg_sig[:, None] * ng
        d_co = (pos_sig - 1)[:, None] * ci
        d_ng = neg_sig[:, None] * ci

        grads = {
            "in": sp.IndexedSlices(d_ci.astype(np.float32), centers,
                                   dense_shape=(vocab, dim)),
            "out": sp.IndexedSlices(
                np.concatenate([d_co, d_ng]).astype(np.float32),
                np.concatenate([contexts, negatives]),
                dense_shape=(vocab, dim)),
        }
        updates, state = tx.update(grads, state)
        emb_in = emb_in + np.asarray(updates["in"])
        emb_out = emb_out + np.asarray(updates["out"])

        if step % 10 == 0:
            loss = float(-np.log(pos_sig + 1e-9).mean()
                         - np.log(1 - neg_sig + 1e-9).mean())
            if r == 0:
                print(f"step {step}  rank0 logistic loss {loss:.4f}")
    return emb_in


def main():
    from horovod_tpu import testing

    results = testing.run_cluster(train, np=2)
    assert np.allclose(results[0], results[1]), "ranks diverged"
    print("embeddings identical across 2 ranks after sparse training")


if __name__ == "__main__":
    main()
