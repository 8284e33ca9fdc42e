#!/usr/bin/env python
"""TF2 synthetic benchmark on the TensorFlow binding surface.

Reference parity: `examples/tensorflow2_synthetic_benchmark.py` — synthetic
ImageNet-shaped data, DistributedGradientTape around a compiled train step,
warmup + timed rounds, img/sec ± 1.96σ. TF runs on the host in this build;
the per-gradient collectives execute on the device mesh through the shared
engine — use this to see the TF-binding/engine path; device throughput is
the SPMD fast path's, measured by `python3 -m chipbench.run`.

    hvdrun -np 2 python examples/tensorflow2_synthetic_benchmark.py \
        --batch-size 4 --num-iters 3
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="ResNet50",
                   help="any tf.keras.applications constructor name")
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--image-size", type=int, default=96)
    p.add_argument("--num-warmup-batches", type=int, default=2)
    p.add_argument("--num-batches-per-iter", type=int, default=3)
    p.add_argument("--num-iters", type=int, default=3)
    p.add_argument("--fp16-allreduce", action="store_true")
    p.add_argument("--eager", action="store_true",
                   help="skip tf.function compilation (op-by-op eager)")
    args = p.parse_args()

    import tensorflow as tf

    import horovod_tpu.tensorflow as hvd

    hvd.init()

    model = getattr(tf.keras.applications, args.model)(
        weights=None, input_shape=(args.image_size, args.image_size, 3))
    opt = tf.optimizers.SGD(0.01)
    compression = (hvd.Compression.fp16 if args.fp16_allreduce
                   else hvd.Compression.none)

    data = tf.random.uniform(
        [args.batch_size, args.image_size, args.image_size, 3], seed=1)
    target = tf.random.uniform([args.batch_size, 1], minval=0, maxval=999,
                               dtype=tf.int64, seed=2)
    # keras.applications heads end in softmax, so probabilities pair with
    # the default from_logits=False (`tensorflow2_synthetic_benchmark.py:79`)
    loss_obj = tf.losses.SparseCategoricalCrossentropy()

    def benchmark_step():
        with hvd.DistributedGradientTape(
                tf.GradientTape(), compression=compression) as tape:
            probs = model(data, training=True)
            loss = loss_obj(target, probs)
        grads = tape.gradient(loss, model.trainable_variables)
        opt.apply_gradients(zip(grads, model.trainable_variables))

    if not args.eager:
        benchmark_step = tf.function(benchmark_step)

    # broadcast after the first step so optimizer slots exist
    benchmark_step()
    hvd.broadcast_variables(model.variables, root_rank=0)
    hvd.broadcast_variables(opt.variables, root_rank=0)

    def log(s):
        if hvd.rank() == 0:
            print(s)

    log(f"Model: {args.model}, batch size {args.batch_size}, "
        f"{hvd.size()} rank(s)")
    for _ in range(args.num_warmup_batches):
        benchmark_step()

    img_secs = []
    for x in range(args.num_iters):
        t0 = time.time()
        for _ in range(args.num_batches_per_iter):
            benchmark_step()
        t = (time.time() - t0) / args.num_batches_per_iter
        img_sec = args.batch_size / t
        log(f"Iter #{x}: {img_sec:.1f} img/sec per rank")
        img_secs.append(img_sec)

    img_sec_mean, img_sec_conf = np.mean(img_secs), 1.96 * np.std(img_secs)
    log(f"Img/sec per rank: {img_sec_mean:.1f} +-{img_sec_conf:.1f}")
    log(f"Total img/sec on {hvd.size()} rank(s): "
        f"{hvd.size() * img_sec_mean:.1f} +-{hvd.size() * img_sec_conf:.1f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
