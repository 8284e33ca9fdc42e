#!/usr/bin/env python
"""Weak-scaling drill — the shape of the BASELINE headline metric.

A drill of features no benchmark cell runs yet, kept for what it checks
inside one run: the train step partitions over 1..n devices, and the
``--three-way`` byte floors hold. Its timings on the CPU mesh are not
measurements of this system (the benchmark is ``python3 -m chipbench.run``,
docs/benchmarks.md); the cell that would measure the source's scaling
efficiency on chips is ROADMAP W6, the quantized ring's is W4.

The reference's headline claim is *scaling efficiency*: 90% on Inception
V3/ResNet-101 at 512 GPUs (`README.rst:74-79`, `docs/benchmarks.rst:13-14`),
measured by running the same synthetic per-device batch at increasing world
sizes. This harness does the TPU-native version: the jitted data-parallel
train step (`spmd.make_train_step`) over meshes of 1, 2, 4, ... devices with
a fixed per-device batch; efficiency(n) = throughput(n) / (n x throughput(1)).

On real hardware the mesh is ICI; in CI it's the 8-device virtual CPU
platform (same strategy as the test suite), which still measures the
collective + SPMD-partitioning overhead share, just not ICI bandwidth.

Run:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python benchmarks/scaling_bench.py

Prints one JSON line per world size; final line is the summary
{"metric": "weak_scaling_efficiency", ...} with efficiency at the largest n.

``--three-way`` switches to the quantized-GSPMD head-to-head instead
(docs/gspmd.md): the same linear-regression step on (a) the coordinator
wire (eager engine, int8 + error feedback), (b) plain GSPMD
(`spmd.make_train_step`, raw f32 collectives), and (c) the quantized
GSPMD ring (`HOROVOD_GSPMD_WIRE` int8 and int4) — one JSON line per arm
with step time, algorithmic bandwidth, and exact-vs-wire bytes, all read
from the one footprint catalog (`ops/compression.py` +
hvd_wire_bytes_total). Asserts the acceptance floors: int4 wire bytes
<= 60% of plain GSPMD, int8 <= 1.05 bytes per moved element.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _shard_map(f, mesh, in_specs, out_specs):
    """jax.shard_map with replication checking off (the no-comm timing
    variant deliberately lets params diverge)."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def run_one(n, batch_per_device, image_size, iters, warmup, model_name):
    """Returns (img/s with gradient allreduce, img/s without).

    The no-comm variant runs the identical per-device program minus the
    cross-device gradient reduction — on shared-core virtual devices this
    isolates collective overhead from core contention; on real chips the
    ratio is the classic scaling-efficiency numerator.
    """
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu import models, spmd
    from horovod_tpu.basics import MESH_AXIS

    mesh = Mesh(np.asarray(jax.devices()[:n]), (MESH_AXIS,))
    batch = batch_per_device * n
    model_cls = getattr(models, model_name)
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    model = model_cls(num_classes=100, dtype=dtype)

    rng = jax.random.PRNGKey(0)
    variables = model.init(rng, jnp.zeros((1, image_size, image_size, 3),
                                          jnp.float32), train=False)
    tx = optax.sgd(0.01, momentum=0.9)

    def local_loss(p, x, y):
        logits = model.apply({"params": p,
                              "batch_stats": variables.get("batch_stats", {})},
                             x, train=False)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    def make_step(with_comm):
        def local_step(p, o, x, y):
            loss, grads = jax.value_and_grad(local_loss)(p, x, y)
            if with_comm:
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, MESH_AXIS), grads)
            updates, o = tx.update(grads, o, p)
            p = optax.apply_updates(p, updates)
            return p, o, loss

        return jax.jit(_shard_map(
            local_step, mesh,
            in_specs=(P(), P(), P(MESH_AXIS), P(MESH_AXIS)),
            out_specs=(P(), P(), P())))

    x = np.random.RandomState(0).randn(
        batch, image_size, image_size, 3).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 100, (batch,))
    data = spmd.shard_batch((jnp.asarray(x), jnp.asarray(y)), mesh)

    rates = []
    for with_comm in (True, False):
        params = spmd.replicate(variables["params"], mesh)
        opt_state = spmd.replicate(tx.init(variables["params"]), mesh)
        step = make_step(with_comm)
        loss = None
        for _ in range(warmup):
            params, opt_state, loss = step(params, opt_state, *data)
        if loss is not None:
            jax.block_until_ready(loss)
        best = 0.0
        for _ in range(3):  # best-of-3 rounds: host CPU timing is noisy
            t0 = time.perf_counter()
            for _ in range(iters):
                params, opt_state, loss = step(params, opt_state, *data)
            jax.block_until_ready(loss)
            best = max(best, batch * iters / (time.perf_counter() - t0))
        rates.append(best)
    return rates[0], rates[1]


def run_three_way(elements, iters, warmup, batch_per_device=8):
    """The quantized-GSPMD head-to-head (ROADMAP item 1, docs/gspmd.md).

    One [elements]-parameter linear-regression step on every arm, so the
    gradient traffic is exactly ``elements`` f32 values per step and the
    byte columns are directly comparable. Step times are honest wall
    clocks but the arms differ structurally (the coordinator arm computes
    the full batch on the eager path; the GSPMD arms shard it), so the
    byte ratios — not the CPU-contended step times — are the acceptance
    numbers. Returns the list of per-arm result rows.
    """
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    import horovod_tpu as hvd
    from horovod_tpu import spmd
    from horovod_tpu.basics import MESH_AXIS
    from horovod_tpu.metrics import instruments
    from horovod_tpu.ops import compression as comp

    n = len(jax.devices())
    mesh = Mesh(np.asarray(jax.devices()), (MESH_AXIS,))
    rng = np.random.RandomState(0)
    batch = batch_per_device * n
    # 1/sqrt(d) feature scale keeps y ~ N(0,1) so the loss column stays
    # readable at any --elements
    x = rng.randn(batch, elements).astype(np.float32) / np.sqrt(elements)
    target = rng.randn(elements).astype(np.float32)
    y = x @ target
    params0 = {"w": jnp.zeros((elements,), jnp.float32)}

    def loss_fn(p, b):
        xb, yb = b
        return jnp.mean((xb @ p["w"] - yb) ** 2)

    results = []

    def report(arm, wire_label, step_s, wire_b, exact_b, loss):
        row = {"arm": arm, "wire": wire_label,
               "step_ms": round(1e3 * step_s, 3),
               "wire_bytes_per_step": int(wire_b),
               "exact_bytes_per_step": int(exact_b),
               "wire_ratio": round(wire_b / exact_b, 4) if exact_b else 0.0,
               "algbw_exact_gbps":
                   round(exact_b / step_s / 1e9, 4) if step_s else 0.0,
               "loss": round(float(loss), 4)}
        print(json.dumps(row))
        results.append(row)
        return row

    # arm 1: coordinator wire — eager engine path, int8 + error feedback;
    # bytes from the coordinator catalog (wire_footprint, per rank,
    # world-independent)
    dist = hvd.DistributedOptimizer(optax.sgd(0.05),
                                    compression=comp.Int8Compressor,
                                    error_feedback=True)
    p = {"w": jnp.zeros((elements,), jnp.float32)}
    o = dist.init(p)
    gfn = jax.jit(jax.value_and_grad(loss_fn))
    xb, yb = jnp.asarray(x), jnp.asarray(y)

    def coord_step(p, o):
        loss, g = gfn(p, (xb, yb))
        u, o = dist.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    loss = None
    for _ in range(warmup):
        p, o, loss = coord_step(p, o)
    jax.block_until_ready(p["w"])
    t0 = time.perf_counter()
    for _ in range(iters):
        p, o, loss = coord_step(p, o)
    jax.block_until_ready(p["w"])
    report("coordinator", "int8", (time.perf_counter() - t0) / iters,
           comp.wire_footprint(elements, "int8"),
           comp.wire_footprint(elements, "none"), loss)

    # arm 2: plain GSPMD — raw f32 ring inserted by the partitioner
    data = spmd.shard_batch((jnp.asarray(x), jnp.asarray(y)), mesh)
    plain_bytes = comp.gspmd_wire_footprint(elements, "none", n)

    def run_gspmd(arm, compression):
        tx = optax.sgd(0.05)
        step = spmd.make_train_step(loss_fn, tx, mesh=mesh, donate=False,
                                    compression=compression)
        p = spmd.replicate(params0, mesh)
        if compression in (None, "off"):
            o = spmd.replicate(tx.init(params0), mesh)
            wire_label, counter = "fp32", None
        else:
            o = spmd.quantized_opt_state(tx, params0, mesh)
            wire_label = spmd.gspmd_wire(compression)  # gate may downgrade
            counter = instruments.wire_bytes().labels(
                compression=f"gspmd-{wire_label}")
        loss = None
        for _ in range(warmup):
            p, o, loss = step(p, o, data)
        jax.block_until_ready(loss)
        before = counter.value if counter else 0.0
        t0 = time.perf_counter()
        for _ in range(iters):
            p, o, loss = step(p, o, data)
        jax.block_until_ready(loss)
        step_s = (time.perf_counter() - t0) / iters
        if counter:  # truthful accounting: read back the instrument
            wire_b = (counter.value - before) / iters
        else:
            wire_b = plain_bytes
        return report(arm, wire_label, step_s, wire_b, plain_bytes, loss)

    run_gspmd("gspmd", "off")
    q8 = run_gspmd("gspmd-int8", "int8")
    q4 = run_gspmd("gspmd-int4", "int4")

    # acceptance floors (ISSUE 13): int4 <= 60% of the plain GSPMD wire;
    # int8 <= 1.05 bytes per exact element moved (scale overhead included)
    int8_per_elem = 4.0 * q8["wire_bytes_per_step"] / plain_bytes
    summary = {"metric": "gspmd_wire_ratio",
               "int4_vs_plain": round(
                   q4["wire_bytes_per_step"] / plain_bytes, 4),
               "int8_bytes_per_elem": round(int8_per_elem, 4),
               "devices": n, "elements": elements}
    print(json.dumps(summary))
    assert q4["wire_bytes_per_step"] <= 0.6 * plain_bytes, summary
    assert int8_per_elem <= 1.05, summary
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="ResNet18",
                    help="any horovod_tpu.models ResNet variant")
    ap.add_argument("--batch-per-device", type=int, default=None)
    ap.add_argument("--image-size", type=int, default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--world-sizes", default=None,
                    help="comma-separated; default 1,2,4,... up to all devices")
    ap.add_argument("--three-way", action="store_true",
                    help="coordinator wire vs plain GSPMD vs quantized "
                         "GSPMD head-to-head instead of the scaling ladder "
                         "(docs/gspmd.md)")
    ap.add_argument("--elements", type=int, default=262144,
                    help="gradient elements for --three-way (default 256k)")
    args = ap.parse_args(argv)
    from horovod_tpu.utils import compile_cache

    compile_cache.enable()

    # under hvdrun (HVD_COORDINATOR_ADDR set) this wires
    # jax.distributed.initialize so jax.devices() spans the whole pod;
    # standalone it is a no-op single-rank init — the SAME command line
    # works on one chip and on a multi-host slice (pod-day contract,
    # docs/running.md)
    import horovod_tpu as hvd
    hvd.init()

    import jax
    on_tpu = jax.default_backend() == "tpu"
    if args.three_way:
        if hvd.size() > 1:
            raise SystemExit(
                "--three-way is single-controller only: the coordinator arm "
                "runs the eager engine in-process and the GSPMD arms span "
                "all local devices — run it standalone, not under hvdrun")
        return run_three_way(args.elements,
                             args.iters or (20 if on_tpu else 5),
                             args.warmup)
    if hvd.size() > 1:
        # multi-controller: every process must participate in every jitted
        # program, so a sub-world mesh (devices[:n] for n < all) is invalid
        # — the pod-day ladder runs one hvdrun per world size instead
        # (docs/running.md)
        ndev_all = len(jax.devices())
        sub = [int(s) for s in (args.world_sizes or "").split(",")
               if s and int(s) != ndev_all]
        if args.world_sizes is None or sub:
            raise SystemExit(
                f"under hvdrun, --world-sizes must equal the full device "
                f"count ({ndev_all}); launch one hvdrun per ladder rung "
                f"(got {args.world_sizes!r} — see docs/running.md pod-day "
                "recipe)")
    ndev = len(jax.devices())
    bpd = args.batch_per_device or (128 if on_tpu else 4)
    img = args.image_size or (224 if on_tpu else 32)
    iters = args.iters or (20 if on_tpu else 5)
    if args.world_sizes:
        world = [int(s) for s in args.world_sizes.split(",")]
        too_big = [n for n in world if n > ndev]
        if too_big:
            raise SystemExit(
                f"requested world sizes {too_big} exceed the {ndev} "
                f"available devices")
    else:
        world = [n for n in (2 ** i for i in range(10)) if n <= ndev]

    shared_cores = jax.default_backend() == "cpu"
    rates = {}
    for n in world:
        comm, nocomm = run_one(n, bpd, img, iters, args.warmup, args.model)
        rates[n] = (comm, nocomm)
        weak = comm / (n * rates[world[0]][0] / world[0])
        print(json.dumps({
            "world_size": n, "img_per_sec": round(comm, 1),
            "per_device": round(comm / n, 1),
            "weak_scaling_pct": round(100 * weak, 1),
            "collective_efficiency_pct": round(100 * comm / nocomm, 1)}))

    n_max = world[-1]
    comm, nocomm = rates[n_max]
    weak = comm / (n_max * rates[world[0]][0] / world[0])
    # On the virtual CPU platform all "devices" share the host's physical
    # cores, so raw weak scaling measures core contention; the collective
    # efficiency (same contention, only the allreduce differs) is the
    # meaningful number there. On real chips both are meaningful.
    headline = 100 * comm / nocomm if shared_cores else 100 * weak
    print(json.dumps({"metric": "weak_scaling_efficiency",
                      "value": round(headline, 1), "unit": "%",
                      "weak_scaling_raw_pct": round(100 * weak, 1),
                      "collective_efficiency_pct":
                          round(100 * comm / nocomm, 1),
                      "config": {"model": args.model, "max_devices": n_max,
                                 "batch_per_device": bpd,
                                 "backend": jax.default_backend(),
                                 "shared_core_virtual_devices":
                                     shared_cores}}))
    return rates


if __name__ == "__main__":
    main()
