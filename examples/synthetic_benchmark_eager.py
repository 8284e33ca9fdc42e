#!/usr/bin/env python
"""ResNet-50 synthetic benchmark through the EAGER engine path.

Reference parity: `examples/pytorch_synthetic_benchmark.py` — per-gradient
async allreduce through the background engine (DistributedOptimizer hook
flow), 10 warmup + 10x10 timed iters, img/sec ± 1.96σ. The SPMD
whole-step path (`spmd.make_train_step`) is what the benchmark's cells
run (`python3 -m chipbench.run`); this is the per-gradient flow beside it.

    hvdrun -np 1 python examples/synthetic_benchmark_eager.py
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models.resnet import ResNet50


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--image-size", type=int, default=None)
    p.add_argument("--num-warmup-batches", type=int, default=10)
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--num-iters", type=int, default=10)
    p.add_argument("--fp16-allreduce", action="store_true")
    args = p.parse_args()

    hvd.init()
    on_tpu = jax.default_backend() == "tpu"
    size = args.image_size or (224 if on_tpu else 32)

    model = ResNet50(num_classes=1000,
                     dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    rng = jax.random.PRNGKey(0)
    x = jnp.asarray(np.random.RandomState(0).randn(
        args.batch_size, size, size, 3), jnp.float32)
    y = jnp.asarray(np.random.RandomState(1).randint(
        0, 1000, (args.batch_size,)))
    variables = model.init(rng, x[:1], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    compression = (hvd.Compression.fp16 if args.fp16_allreduce
                   else hvd.Compression.none)
    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                  compression=compression)
    opt_state = tx.init(params)

    def loss_fn(p, bs, x, y):
        logits, st = model.apply({"params": p, "batch_stats": bs}, x,
                                 train=True, mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean(), st["batch_stats"]

    grad_fn = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))

    def step():
        nonlocal params, batch_stats, opt_state
        (loss, batch_stats), grads = grad_fn(params, batch_stats, x, y)
        # eager path: each gradient leaf is a named async allreduce through
        # the engine (fusion buckets, response cache, timeline)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return loss

    for _ in range(args.num_warmup_batches):
        loss = step()
    float(loss)

    img_secs = []
    for _ in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            loss = step()
        float(loss)
        dt = time.perf_counter() - t0
        img_secs.append(args.batch_size * args.num_batches_per_iter / dt)

    img_sec_mean = np.mean(img_secs)
    img_sec_conf = 1.96 * np.std(img_secs)
    if hvd.rank() == 0:
        print(f"Img/sec per rank: {img_sec_mean:.1f} +-{img_sec_conf:.1f}")
        print(f"Total img/sec on {hvd.size()} rank(s): "
              f"{hvd.size() * img_sec_mean:.1f} "
              f"+-{hvd.size() * img_sec_conf:.1f}")


if __name__ == "__main__":
    main()
