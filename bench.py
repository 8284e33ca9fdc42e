#!/usr/bin/env python
"""Synthetic training benchmark — the reference's headline measurement.

Parity: `examples/tensorflow2_synthetic_benchmark.py` (synthetic
ImageNet-sized data, 10 warmup iters, 10 rounds x 10 timed iters, reports
img/sec ± 1.96σ) rebuilt on the SPMD fast path: the whole train step (forward,
backward, gradient averaging over the replica mesh, SGD update) is one XLA
program; batch sharded over replicas, params replicated.

``BENCH_MODEL`` selects the model family (default ResNet50; the reference's
scaling table also covers InceptionV3 and VGG16). Prints ONE JSON line:
  {"metric": "<model>_images_per_sec_per_chip", "value": N,
   "unit": "img/s/chip", "vs_baseline": N / 103.55,
   "platform": "tpu", "device_kind": "...", "devices": N}

The benchmark needs a TPU: without one it is an error, not a smaller run
under the same metric name. ``--cpu-smoke`` is the explicit smoke that runs
anywhere (batch 4, 32 px, f32, two short rounds); it reports
``<model>_cpu_smoke_images_per_sec`` and no baseline ratio, and exists so
that CI can drive the flags (``--metrics-dump``, ``--trace``, ``--chaos``).

``vs_baseline`` is non-null only for ResNet50, whose published denominator
exists: 1656.82 img/s on 16 Pascal GPUs = 103.55 img/s/GPU
(`docs/benchmarks.rst:43`, BASELINE.md).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="Synthetic training benchmark (env knobs: BENCH_MODEL, "
                    "BENCH_BATCH, BENCH_IMAGE, BENCH_WARMUP, BENCH_ROUNDS, "
                    "BENCH_ITERS).")
    p.add_argument("--cpu-smoke", action="store_true",
                   help="run a toy-sized step on whatever device JAX finds "
                        "(CI's way to drive the other flags); reported as "
                        "<model>_cpu_smoke_images_per_sec, never under the "
                        "benchmark's metric name")
    p.add_argument("--metrics-dump", metavar="PATH", default=None,
                   help="write the final aggregated runtime-metrics snapshot "
                        "(hvd.metrics(), docs/metrics.md) as JSON to PATH")
    p.add_argument("--chaos", metavar="SPEC", default=None,
                   help="inject faults while benchmarking: a "
                        "HOROVOD_FAULT_SPEC string, e.g. "
                        "'conn_drop@tick:100;corrupt@frame:50' for the "
                        "control plane or 'nan@grad:50' / "
                        "'hang@collective:2:50' for the data-plane guards "
                        "(docs/fault-tolerance.md). Measures throughput "
                        "with recovery on the path")
    p.add_argument("--trace", metavar="PATH", default=None,
                   help="enable distributed tracing (sets HOROVOD_TRACE): "
                        "rank 0 writes one merged Chrome trace to PATH at "
                        "shutdown; analyze with bin/hvdprof report PATH "
                        "(docs/tracing.md). Adds a per-iteration device "
                        "sync so STEP spans bound real step time")
    p.add_argument("--history", metavar="PATH", default=None,
                   help="append this run's result to a schema-versioned "
                        "JSONL perf history (benchmarks/history.py)")
    p.add_argument("--check-regression", action="store_true",
                   help="with --history: compare this run against the "
                        "recorded trajectory BEFORE appending; exit 3 when "
                        "it falls below the tolerance floor")
    p.add_argument("--regression-window", type=int, default=None,
                   metavar="N", help="trailing records the baseline median "
                                     "uses (default 5)")
    p.add_argument("--regression-tolerance", type=float, default=None,
                   metavar="F", help="fraction below baseline that fails "
                                     "(default 0.15)")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.chaos:
        # must land before hvd.init(): the controller builds its injector
        # (and wraps its control socket) at connect time
        os.environ["HOROVOD_FAULT_SPEC"] = args.chaos
    if args.trace:
        # also before hvd.init(): the engine activates the tracer (and the
        # worker runs its clock handshake) during init
        os.environ["HOROVOD_TRACE"] = args.trace
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import models, spmd
    from horovod_tpu.utils import compile_cache

    hvd.init()
    compile_cache.enable()
    dev = jax.devices()[0]
    backend = dev.platform
    n_dev = hvd.num_replicas()

    smoke = args.cpu_smoke
    if backend != "tpu" and not smoke:
        sys.exit(f"bench: no TPU (JAX found platform {backend!r}); a "
                 f"benchmark needs the chip. --cpu-smoke runs a toy step "
                 f"anywhere, under its own metric name.")
    # BENCH_MODEL picks the reference benchmark family (the scaling table
    # covers ResNet, Inception V3 and VGG-16): ResNet50 | ResNet101 |
    # InceptionV3 | VGG16 | ...
    model_name = os.environ.get("BENCH_MODEL", "ResNet50")
    default_batch = {"InceptionV3": "128", "VGG16": "128", "VGG19": "128"}
    batch_per_device = int(os.environ.get(
        "BENCH_BATCH",
        "4" if smoke else default_batch.get(model_name, "256")))
    image_size = int(os.environ.get(
        "BENCH_IMAGE",
        ("139" if model_name == "InceptionV3" else "32") if smoke
        else ("299" if model_name == "InceptionV3" else "224")))
    warmup = int(os.environ.get("BENCH_WARMUP", "2" if smoke else "10"))
    num_rounds = int(os.environ.get("BENCH_ROUNDS", "2" if smoke else "10"))
    iters_per_round = int(os.environ.get("BENCH_ITERS",
                                         "2" if smoke else "10"))

    batch = batch_per_device * n_dev
    model = getattr(models, model_name)(
        num_classes=1000, dtype=jnp.float32 if smoke else jnp.bfloat16)

    rng = jax.random.PRNGKey(0)
    images_h = np.random.RandomState(0).randn(
        batch, image_size, image_size, 3).astype(np.float32)
    labels_h = np.random.RandomState(1).randint(0, 1000, (batch,))

    variables = model.init(rng, jnp.zeros((1, image_size, image_size, 3),
                                          jnp.float32), train=True)
    params = variables["params"]
    has_bn = "batch_stats" in variables
    batch_stats = variables.get("batch_stats", {})
    tx = optax.sgd(0.01, momentum=0.9)
    opt_state = tx.init(params)

    mesh = hvd.mesh()
    params = spmd.replicate(params, mesh)
    batch_stats = spmd.replicate(batch_stats, mesh)
    opt_state = spmd.replicate(opt_state, mesh)
    images = spmd.shard_batch(jnp.asarray(images_h), mesh)
    labels = spmd.shard_batch(jnp.asarray(labels_h), mesh)

    def loss_fn(p, bs, x, y):
        if has_bn:
            logits, new_state = model.apply(
                {"params": p, "batch_stats": bs}, x, train=True,
                mutable=["batch_stats"])
            new_bs = new_state["batch_stats"]
        else:  # e.g. VGG: no BN; dropout keyed per-compile is fine here
            logits = model.apply({"params": p}, x, train=True,
                                 rngs={"dropout": jax.random.PRNGKey(7)})
            new_bs = bs
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, new_bs

    from jax.sharding import NamedSharding, PartitionSpec as P
    repl = NamedSharding(mesh, P())

    def _train_step(p, bs, opt, x, y):
        (loss, new_bs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, bs, x, y)
        updates, opt = tx.update(grads, opt, p)
        p = optax.apply_updates(p, updates)
        return p, new_bs, opt, loss

    # Donation is deliberately off: profiled on v5e it makes XLA insert ~370
    # extra aliasing copies (~0.7 GB/step) and costs ~8% on this HBM-bound
    # step; there is ample spare HBM (temp ≈ 9 GB of 16 GB) without it.
    jitted = jax.jit(_train_step, out_shardings=(repl, repl, repl, repl))
    # The step is HBM-bandwidth-bound (~790 GB/s avg of 819 peak, profiled);
    # the latency-hiding scheduler reclaims a few % of scheduling slack.
    train_step = jitted
    if backend == "tpu":
        train_step = jitted.lower(
            params, batch_stats, opt_state, images, labels,
        ).compile(compiler_options={
            "xla_tpu_enable_latency_hiding_scheduler": "true"})

    # warmup (includes compile)
    for _ in range(warmup):
        params, batch_stats, opt_state, loss = train_step(
            params, batch_stats, opt_state, images, labels)
    jax.block_until_ready(loss)

    # All rounds are timed under one final sync (the device queue stays
    # full); the error bar comes from a short second pass that syncs per
    # round — its spread includes sync jitter, making the bar conservative.
    tracer = hvd.tracing.active() if args.trace else None
    t0 = time.perf_counter()
    for _ in range(num_rounds):
        for _ in range(iters_per_round):
            if tracer is not None:
                sp = tracer.begin_block(hvd.tracing.K_STEP, hvd.rank(),
                                        "STEP", hvd.tracing.clock.trace_us())
            params, batch_stats, opt_state, loss = train_step(
                params, batch_stats, opt_state, images, labels)
            if tracer is not None:
                # per-iteration sync so the STEP span bounds real device
                # time, not async-dispatch time (skews throughput; the
                # --trace help text says so)
                jax.block_until_ready(loss)
                tracer.end_block(sp, hvd.tracing.clock.trace_us())
    jax.block_until_ready(loss)
    total = time.perf_counter() - t0
    mean = batch * iters_per_round * num_rounds / total

    round_rates = []
    for _ in range(min(num_rounds, 3)):
        r0 = time.perf_counter()
        for _ in range(iters_per_round):
            params, batch_stats, opt_state, loss = train_step(
                params, batch_stats, opt_state, images, labels)
        jax.block_until_ready(loss)
        round_rates.append(batch * iters_per_round /
                           (time.perf_counter() - r0))
    conf = float(1.96 * np.std(round_rates))
    per_chip = mean / n_dev
    print(f"# platform={backend} kind={dev.device_kind} devices={n_dev} "
          f"batch/device={batch_per_device} img={image_size} "
          f"loss={float(loss):.3f}", file=sys.stderr)
    print(f"# Img/sec total: {mean:.1f} +- {conf:.1f}; per chip: {per_chip:.1f}",
          file=sys.stderr)
    baseline = model_name == "ResNet50" and not smoke
    result = {
        "metric": (f"{model_name.lower()}_cpu_smoke_images_per_sec" if smoke
                   else f"{model_name.lower()}_images_per_sec_per_chip"),
        "value": round(per_chip, 2),
        "unit": "img/s/chip",
        "platform": backend, "device_kind": dev.device_kind,
        "devices": n_dev,
        # the published per-GPU baseline exists only for the ResNet bench
        # (103.55 img/s, BASELINE.md) — a ratio for other models would
        # compare against the wrong denominator
        "vs_baseline": round(per_chip / 103.55, 3) if baseline else None,
        # denominator context so the ratio cannot mislead on its own: it
        # divides by the reference's 2017-era per-GPU number — from its
        # ResNet-101 illustrative run, the only published figure — not a
        # same-generation or same-model part; the roofline story lives in
        # docs/benchmarks.md (this step runs at ~97% of v5e HBM bandwidth)
        "baseline_denominator": (
            "103.55 img/s per Pascal GPU, 2017, from the reference's "
            "ResNet-101 run (docs/benchmarks.rst:43) — its only published "
            "throughput figure" if baseline else None),
    }
    print(json.dumps(result))

    rc = 0
    if args.history:
        from benchmarks.history import (append_record, check_regression,
                                        load_history)

        # compare against the trajectory BEFORE appending: today's run
        # must not be allowed to vote in its own baseline
        if args.check_regression:
            verdict = check_regression(
                load_history(args.history, metric=result["metric"]),
                result["value"],
                **{k: v for k, v in (
                    ("window", args.regression_window),
                    ("tolerance", args.regression_tolerance))
                   if v is not None})
            print("# regression check: %s" % json.dumps(verdict),
                  file=sys.stderr)
            if verdict["regression"]:
                print(f"# REGRESSION: {result['metric']} = "
                      f"{result['value']} fell below the floor "
                      f"{verdict['floor']} (baseline {verdict['baseline']} "
                      f"over {verdict['samples']} runs)", file=sys.stderr)
                rc = 3
        append_record(args.history, {
            "metric": result["metric"], "value": result["value"],
            "unit": result["unit"], "model": model_name,
            "backend": backend, "devices": n_dev,
            "batch_per_device": batch_per_device, "image_size": image_size,
        })
        print(f"# perf history appended to {args.history}", file=sys.stderr)

    if args.metrics_dump:
        with open(args.metrics_dump, "w") as f:
            json.dump(hvd.metrics(), f, indent=2, sort_keys=True)
        print(f"# metrics snapshot written to {args.metrics_dump}",
              file=sys.stderr)

    if args.trace:
        # the merged Chrome trace is written by rank 0 inside shutdown()
        hvd.shutdown()
        print(f"# trace written; analyze with: bin/hvdprof report "
              f"{args.trace}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
