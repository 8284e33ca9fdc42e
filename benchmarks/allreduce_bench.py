#!/usr/bin/env python
"""Allreduce drill — SPMD data plane, eager engine, wire modes, algorithms.

A drill of features no benchmark cell runs yet, kept for what it checks
inside one run: bytes on the wire per mode, the adaptive wire's 60% target,
the straggler policy's step ratio, which algorithm each size settles on. Its
timings on the CPU mesh are not measurements of this system (the benchmark
is ``python3 -m chipbench.run``, docs/benchmarks.md); the cell that would
time the quantized ring on ICI is ROADMAP W4, the eager engine's is W6.

The reference's perf story is collective bandwidth (NCCL ring allreduce,
`nccl_operations.cc:55-105`; timeline makes per-op cost visible). This
measures the TPU-native equivalents:

  * ``spmd``  — `psum` inside a jitted `shard_map` over the device mesh: the
    hot path XLA compiles onto ICI. Per-device buffers are distinct, so the
    collective cannot be constant-folded.
  * ``eager`` — `hvd.allreduce` through the background engine (tensor queue →
    negotiation → fused XLA program → host round-trip). The delta vs ``spmd``
    is the engine + host-boundary overhead the reference's timeline exposes
    as QUEUE/MEMCPY/NEGOTIATE spans.

Reports, per message size: algorithm bandwidth (bytes/s of one rank's buffer)
and bus bandwidth (algbw x 2(n-1)/n — the ring-transfer normalization NCCL
uses, so numbers are comparable to `nccl-tests`).

Run on the virtual mesh:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python benchmarks/allreduce_bench.py

Prints one JSON line per (path, size); final line is a summary.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def bench_spmd(sizes_mb, iters, warmup):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import spmd
    from horovod_tpu.basics import MESH_AXIS

    mesh = hvd.mesh()
    n = hvd.num_replicas()
    results = []
    for mb in sizes_mb:
        nelem = max(1, int(mb * (1 << 20)) // 4)
        # distinct per-device shards: [n, nelem] split on dim 0, psum inside
        # shard_map -> a real cross-device reduce, not a replication no-op.
        x = jnp.arange(n * nelem, dtype=jnp.float32).reshape(n, nelem)
        x = jax.device_put(x, NamedSharding(mesh, P(MESH_AXIS)))

        @jax.jit
        def reduce(x):
            return jax.shard_map(
                lambda s: jax.lax.psum(s, MESH_AXIS), mesh=mesh,
                in_specs=P(MESH_AXIS), out_specs=P(MESH_AXIS))(x)

        out = reduce(x)
        for _ in range(warmup - 1):
            out = reduce(x)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = reduce(x)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        algbw = nelem * 4 / dt
        busbw = algbw * (2 * (n - 1) / n) if n > 1 else algbw
        results.append({"path": "spmd", "size_mb": mb, "n": n,
                        "time_us": round(dt * 1e6, 1),
                        "algbw_gbps": round(algbw / 1e9, 3),
                        "busbw_gbps": round(busbw / 1e9, 3)})
        print(json.dumps(results[-1]))
    return results


def bench_eager(sizes_mb, iters, warmup):
    import horovod_tpu as hvd

    n = hvd.size()
    results = []
    for mb in sizes_mb:
        nelem = max(1, int(mb * (1 << 20)) // 4)
        x = np.arange(nelem, dtype=np.float32)
        for _ in range(warmup):
            hvd.allreduce(x, name=f"bench_{mb}")
        t0 = time.perf_counter()
        for i in range(iters):
            hvd.allreduce(x, name=f"bench_{mb}")
        dt = (time.perf_counter() - t0) / iters
        algbw = nelem * 4 / dt
        busbw = algbw * (2 * (n - 1) / n) if n > 1 else algbw
        results.append({"path": "eager", "size_mb": mb, "n": n,
                        "time_us": round(dt * 1e6, 1),
                        "algbw_gbps": round(algbw / 1e9, 3),
                        "busbw_gbps": round(busbw / 1e9, 3)})
        print(json.dumps(results[-1]))
    return results


def bench_allgather(sizes_mb, iters, warmup):
    """Eager allgather across cluster sizes at FIXED total output size.

    The result of each gather is one compiled program whose outputs stay
    replicated on the rank devices (`executor._allgather_assemble_fn`) —
    nothing moves through the host per destination. Evidence: time per op
    stays ~flat as the rank count grows (the round-2 per-destination
    ``device_put`` loop grew linearly in world size x output bytes).
    """
    import jax

    import horovod_tpu as hvd
    from horovod_tpu import testing

    results = []
    world_sizes = [n for n in (2, 4, 8) if n <= len(jax.devices())]
    for mb in sizes_mb:
        for n in world_sizes:
            total_elems = max(n, int(mb * (1 << 20)) // 4)
            rows = total_elems // n  # per-rank contribution; output constant

            def worker():
                import time as _t

                x = np.full((rows,), float(hvd.rank()), np.float32)
                for i in range(warmup):
                    hvd.allgather(x, name="agb")
                t0 = _t.perf_counter()
                for i in range(iters):
                    out = hvd.allgather(x, name="agb")
                return (_t.perf_counter() - t0) / iters

            if hvd.is_initialized():
                hvd.shutdown()
            dts = testing.run_cluster(worker, np=n)
            hvd.shutdown()
            dt = max(dts)
            results.append({"path": "eager-allgather", "size_mb": mb, "n": n,
                            "time_us": round(dt * 1e6, 1),
                            "gather_gbps": round(total_elems * 4 / dt / 1e9,
                                                 3)})
            print(json.dumps(results[-1]))
    return results


_COMPRESSION_MODES = ("none", "bf16", "int8", "int8-dcn", "int4", "adaptive")


def bench_compression(sizes_mb, iters, warmup, modes):
    """Wire-mode sweep through the eager engine: same fp32 payload, each
    wire format. Reports the bytes each mode actually moves (the
    executor's per-rank reduce+gather accounting — int8 pays 1 byte/elem +
    one f32 scale per block, int4 packs two values per byte, on both hops)
    and the resulting wire GB/s. ``int8-dcn`` runs on a synthetic 2-host
    topology (HVD_LOCAL_SIZE=2) so the mixed bf16-ICI/int8-DCN program
    actually compiles. ``adaptive`` feeds the bitwidth selector during
    warmup (extended past the decision interval) so the timed iterations
    ride the converged per-bucket grid.
    """
    import horovod_tpu as hvd
    from horovod_tpu import testing
    from horovod_tpu.ops import compression as comp

    results = []
    for mode in modes:
        two_level = mode == "int8-dcn"
        for mb in sizes_mb:
            nelem = max(1, int(mb * (1 << 20)) // 4)

            def worker():
                import time as _t

                from horovod_tpu import basics
                from horovod_tpu.ops import adaptive as _ad

                c = comp.by_name(mode)
                observe = getattr(c, "observe", None)
                if observe is not None:
                    comp.AdaptiveCompressor.reset()
                # the selector re-decides every interval() observations —
                # warm up past the first boundary so timing sees the
                # converged grid
                warm = (max(warmup, _ad.interval() + 2)
                        if observe is not None else warmup)
                x = np.arange(nelem, dtype=np.float32) / nelem - 0.5
                for _ in range(warm):
                    out = hvd.allreduce(x, name="cb", op=hvd.Sum,
                                        compression=c)
                    if observe is not None:
                        observe("cb", np.asarray(out))
                t0 = _t.perf_counter()
                for _ in range(iters):
                    out = hvd.allreduce(x, name="cb", op=hvd.Sum,
                                        compression=c)
                    if observe is not None:
                        observe("cb", np.asarray(out))
                dt = (_t.perf_counter() - t0) / iters
                ex = basics._engine()._executor
                return dt, ex.last_wire_mode, ex.last_wire_bytes

            if hvd.is_initialized():
                hvd.shutdown()
            if two_level:
                os.environ["HVD_LOCAL_SIZE"] = "2"
            try:
                outs = testing.run_cluster(worker, np=4)
            finally:
                hvd.shutdown()
                if two_level:
                    os.environ.pop("HVD_LOCAL_SIZE", None)
            dt = max(o[0] for o in outs)
            wire_bytes = max(o[2] for o in outs)
            fp32_bytes = comp.wire_footprint(nelem, "none")
            results.append({
                "path": "compression", "mode": mode, "size_mb": mb, "n": 4,
                "wire_mode": outs[0][1],  # the grid that actually compiled
                "time_us": round(dt * 1e6, 1),
                "wire_bytes": wire_bytes,
                "wire_ratio_vs_fp32": round(wire_bytes / fp32_bytes, 4),
                "wire_gbps": round(wire_bytes / dt / 1e9, 3),
                "effective_algbw_gbps": round(nelem * 4 / dt / 1e9, 3),
            })
            print(json.dumps(results[-1]))
    return results


_ALGO_WIRES = ("off", "int8", "int4")


def bench_algo_sweep(sizes_mb, iters, warmup, wires=_ALGO_WIRES):
    """Algorithm-zoo sweep on the compiled fast path: one jitted shard_map
    program per (payload size, algorithm, bitwidth) cell — the flat
    bidirectional ring, the recursive-halving/doubling tree, and the
    two-level hierarchical schedule, each over the exact and quantized
    wires. One JSON row per cell (step time, algbw, catalog wire bytes);
    the driver derives the per-size "tuned" row as the step-time argmin,
    which is what the joint tuner converges to online (docs/autotune.md).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import spmd
    from horovod_tpu.basics import MESH_AXIS, Average
    from horovod_tpu.ops import compression as comp

    mesh = hvd.mesh()
    n = hvd.num_replicas()
    block = comp.block_size()
    hosts = spmd.mesh_hosts(n)
    zoo = (("ring", spmd.quantized_allreduce),
           ("tree", spmd.quantized_allreduce_tree),
           ("hier", spmd.quantized_allreduce_hier))
    results = []
    for mb in sizes_mb:
        nelem = max(1, int(mb * (1 << 20)) // 4)
        x = jnp.arange(n * nelem, dtype=jnp.float32).reshape(n, nelem)
        x = jax.device_put(x, NamedSharding(mesh, P(MESH_AXIS)))
        for algo, fn in zoo:
            for wire in wires:
                def body(row, fn=fn, wire=wire):
                    return fn(row[0], Average, MESH_AXIS, wire)[None]

                reduce = jax.jit(spmd._shard_map(
                    body, mesh, in_specs=P(MESH_AXIS),
                    out_specs=P(MESH_AXIS)))
                out = reduce(x)
                for _ in range(warmup - 1):
                    out = reduce(x)
                jax.block_until_ready(out)
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = reduce(x)
                jax.block_until_ready(out)
                dt = (time.perf_counter() - t0) / iters
                mode = "none" if wire == "off" else wire
                wire_bytes = comp.gspmd_wire_footprint(
                    nelem, mode, n, block, algorithm=algo,
                    hosts=hosts if algo == "hier" else None)
                results.append({
                    "path": "algo", "algorithm": algo, "mode": wire,
                    "size_mb": mb, "n": n,
                    "time_us": round(dt * 1e6, 1),
                    "algbw_gbps": round(nelem * 4 / dt / 1e9, 3),
                    "wire_bytes": wire_bytes,
                })
                print(json.dumps(results[-1]))
    return results


def bench_bucket_overlap(bucket_mbs, iters, warmup, layers=16, np_=8):
    """Backward-pass bucket-overlap sweep (HOROVOD_BUCKET_MB,
    docs/overlap.md): a synthetic gradient pytree (``layers`` x
    [256, 1024] weight + [1024] bias, fp32) rides ``allreduce_gradients``
    with the bucket knob swept; 0 is the per-leaf baseline. Reports the
    drain wall time AND the per-step exposed-communication seconds
    (hvd_exposed_comm_seconds delta — time blocked in synchronize, the
    quantity bucket overlap exists to shrink)."""
    import horovod_tpu as hvd
    from horovod_tpu import testing

    shapes = [(256, 1024), (1024,)] * layers
    total_mb = sum(int(np.prod(s)) for s in shapes) * 4 / (1 << 20)
    results = []
    for bmb in bucket_mbs:

        def worker():
            import time as _t

            from horovod_tpu.metrics import instruments
            from horovod_tpu.optim import distributed as dist

            rng = np.random.RandomState(1234)
            grads = [rng.randn(*s).astype(np.float32) for s in shapes]
            for _ in range(warmup):
                dist.allreduce_gradients(grads, op=hvd.Sum, prefix="ob")
            e0 = instruments.exposed_comm_seconds().value
            t0 = _t.perf_counter()
            for _ in range(iters):
                dist.allreduce_gradients(grads, op=hvd.Sum, prefix="ob")
            dt = (_t.perf_counter() - t0) / iters
            exposed = (instruments.exposed_comm_seconds().value - e0) / iters
            return dt, exposed

        if hvd.is_initialized():
            hvd.shutdown()
        if bmb > 0:
            os.environ["HOROVOD_BUCKET_MB"] = str(bmb)
        else:
            os.environ.pop("HOROVOD_BUCKET_MB", None)
        try:
            outs = testing.run_cluster(worker, np=np_)
        finally:
            hvd.shutdown()
            os.environ.pop("HOROVOD_BUCKET_MB", None)
        dt = max(o[0] for o in outs)
        exposed = max(o[1] for o in outs)
        results.append({
            "path": "bucket-overlap", "bucket_mb": bmb, "n": np_,
            "layers": layers, "total_mb": round(total_mb, 2),
            "time_us": round(dt * 1e6, 1),
            "exposed_comm_us": round(exposed * 1e6, 1),
            "exposed_comm_pct": round(100.0 * exposed / dt, 1) if dt else 0.0,
            "algbw_gbps": round(total_mb * (1 << 20) / dt / 1e9, 3),
        })
        print(json.dumps(results[-1]))
    return results


def bench_straggler_chaos(chaos, iters, warmup, np_=4, victim=1,
                          deadline="3x"):
    """Straggler-chaos acceptance bench (docs/fault-tolerance.md): the same
    eager allreduce loop run twice — clean, then with ``chaos`` (e.g.
    ``slow@rank:500``) injected on ``victim`` — with the straggler policy
    armed (HOROVOD_STRAGGLER_DEADLINE). The claim under test: once the
    policy excludes the slow rank, the SURVIVORS' step time tracks the
    group median, not the victim's injected delay.

    Point ``rank`` is the per-process engine-tick hook (elastic mode); the
    in-process cluster shares one engine across rank threads, so it is
    mapped to ``collective`` — the per-rank enqueue hook — which models
    the same thing: one rank chronically late into every round. Runs with
    HVD_TPU_NATIVE=0 in both phases so the Python controller (the one
    that implements exclusion in-process) negotiates both sides of the
    comparison."""
    import horovod_tpu as hvd
    from horovod_tpu import faultinject, testing

    kind, _, rest = chaos.partition("@")
    point, _, chaos_args = rest.partition(":")
    if point == "rank":
        point = "collective"
    spec = f"{kind}@{point}" + (f":{chaos_args}" if chaos_args else "")
    spec += f"#{victim}"
    faultinject.parse_spec(spec)  # fail fast on a bad --chaos value

    nelem = 1 << 16

    def worker():
        import time as _t

        from horovod_tpu.metrics import instruments

        x = np.arange(nelem, dtype=np.float32) + hvd.rank()
        # in the chaos phase, extend the warmup past the policy's patience
        # window so the exclusion has engaged before the timed iterations
        # begin (same fixed count on every rank — the loop must stay in
        # lockstep). patience late rounds + the exclusion-effective round
        # + slack for arrival jitter around the relative floor.
        extra = ((int(os.environ.get("HOROVOD_STRAGGLER_PATIENCE", "2")) + 5)
                 if os.environ.get("HOROVOD_FAULT_SPEC") else 0)
        for i in range(warmup + extra):
            hvd.allreduce(x, name="chaos_g")
        steps = []
        for i in range(iters):
            t0 = _t.perf_counter()
            hvd.allreduce(x, name="chaos_g")
            steps.append(_t.perf_counter() - t0)
        return (sum(steps) / len(steps),
                instruments.partial_collectives().value)

    def run_phase(env):
        old = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            if hvd.is_initialized():
                hvd.shutdown()
            faultinject.reset_shared()
            return testing.run_cluster(worker, np=np_)
        finally:
            hvd.shutdown()
            faultinject.reset_shared()
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    base_env = {"HVD_TPU_NATIVE": "0"}
    outs = run_phase(base_env)
    baseline = sorted(o[0] for i, o in enumerate(outs)
                      if i != victim)[(np_ - 1) // 2]
    chaos_env = dict(base_env)
    chaos_env.update({
        "HOROVOD_FAULT_SPEC": spec,
        "HOROVOD_STRAGGLER_DEADLINE": deadline,
        "HOROVOD_STRAGGLER_PATIENCE": os.environ.get(
            "HOROVOD_STRAGGLER_PATIENCE", "2"),
    })
    outs = run_phase(chaos_env)
    chaos_step = sorted(o[0] for i, o in enumerate(outs)
                        if i != victim)[(np_ - 1) // 2]
    partial_rounds = max(o[1] for o in outs)
    result = {
        "path": "straggler-chaos", "n": np_, "victim": victim,
        "chaos": spec, "deadline": deadline,
        "baseline_step_us": round(baseline * 1e6, 1),
        "chaos_step_us": round(chaos_step * 1e6, 1),
        "partial_rounds": int(partial_rounds),
        "step_ratio": round(chaos_step / baseline, 3) if baseline else 0.0,
    }
    print(json.dumps(result))
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes-mb", default="0.0625,0.25,1,4,16,64",
                    help="comma-separated message sizes in MB (may be "
                         "empty when --sizes-kb carries the sweep)")
    ap.add_argument("--sizes-kb", default=None,
                    help="extra sub-MB message sizes in KB, merged into "
                         "the sweep (e.g. '4,16' for the latency-bound "
                         "payloads the tree algorithm targets)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--path", choices=["spmd", "eager", "allgather",
                                       "compression", "both"],
                    default="both")
    ap.add_argument("--compression", default=None,
                    help="comma-separated wire modes to sweep "
                         f"({','.join(_COMPRESSION_MODES)}); implies "
                         "--path compression")
    ap.add_argument("--bucket-mb", default=None,
                    help="comma-separated HOROVOD_BUCKET_MB values to sweep "
                         "(0 = per-leaf baseline), e.g. '0,0.5,1,4'; runs "
                         "the bucket-overlap bench instead of --path")
    ap.add_argument("--layers", type=int, default=16,
                    help="synthetic model depth for --bucket-mb")
    ap.add_argument("--np", type=int, default=8, dest="np_",
                    help="cluster size for --bucket-mb")
    ap.add_argument("--chaos", default=None,
                    help="straggler-chaos acceptance run: a fault rule "
                         "like 'slow@rank:500' or 'flaky_slow@rank:500:0.5' "
                         "injected on --chaos-victim while the straggler "
                         "policy is armed; reports survivors' step-time "
                         "ratio vs a clean run and exits 3 past "
                         "--chaos-budget")
    ap.add_argument("--chaos-victim", type=int, default=1,
                    help="rank the --chaos rule applies to (default 1)")
    ap.add_argument("--chaos-budget", type=float, default=1.5,
                    help="max allowed chaos/clean step-time ratio "
                         "(default 1.5, the ISSUE acceptance bound)")
    ap.add_argument("--straggler-deadline", default="3x",
                    help="HOROVOD_STRAGGLER_DEADLINE for the chaos phase "
                         "(default 3x = 3x the median arrival spread)")
    ap.add_argument("--algo-sweep", action="store_true",
                    help="sweep the collective-algorithm zoo (ring/tree/"
                         "hier x off/int8/int4) on the compiled fast path; "
                         "one JSON row per cell plus the per-size tuned "
                         "argmin and the headline "
                         "allreduce_algo_tuned_algbw_gbps")
    args = ap.parse_args(argv)
    from horovod_tpu.utils import compile_cache

    compile_cache.enable()
    sizes = [float(s) for s in args.sizes_mb.split(",") if s.strip()]
    if args.sizes_kb:
        sizes = sorted(set(sizes) | {
            float(k) / 1024.0 for k in args.sizes_kb.split(",") if k.strip()})
    if not sizes:
        ap.error("no message sizes: give --sizes-mb and/or --sizes-kb")

    import horovod_tpu as hvd

    if args.chaos is not None:
        r = bench_straggler_chaos(args.chaos, args.iters, args.warmup,
                                  np_=args.np_, victim=args.chaos_victim,
                                  deadline=args.straggler_deadline)
        print(json.dumps({"metric": "straggler_chaos_step_ratio",
                          "value": r["step_ratio"], "unit": "x",
                          "config": {k: r[k] for k in ("chaos", "n",
                                                       "victim",
                                                       "deadline")}}))
        if r["step_ratio"] > args.chaos_budget:
            print(f"# REGRESSION: straggler_chaos_step_ratio = "
                  f"{r['step_ratio']} exceeds the --chaos-budget "
                  f"{args.chaos_budget} (survivors' step time did not "
                  f"track the median rank)", file=sys.stderr)
            sys.exit(3)
        return [r]

    if args.bucket_mb is not None:
        bucket_mbs = [float(b) for b in args.bucket_mb.split(",")]
        results = bench_bucket_overlap(bucket_mbs, args.iters, args.warmup,
                                       layers=args.layers, np_=args.np_)
        off = next((r for r in results if r["bucket_mb"] == 0), None)
        on = [r for r in results if r["bucket_mb"] > 0]
        if off and on:
            best = min(on, key=lambda r: r["exposed_comm_pct"])
            print(json.dumps({
                "metric": "bucket_overlap_exposed_comm_pct",
                "off_pct": off["exposed_comm_pct"],
                "on_pct": best["exposed_comm_pct"],
                "best_bucket_mb": best["bucket_mb"],
                "time_us_off": off["time_us"],
                "time_us_on": best["time_us"]}))
        return results

    if args.path == "compression" or args.compression is not None:
        modes = ([m.strip() for m in args.compression.split(",")]
                 if args.compression else list(_COMPRESSION_MODES))
        bad = [m for m in modes if m not in _COMPRESSION_MODES]
        if bad:
            ap.error(f"unknown compression mode(s) {bad}; choose from "
                     f"{_COMPRESSION_MODES}")
        results = bench_compression(sizes, args.iters, args.warmup, modes)
        by_mode = {}
        for r in results:
            by_mode.setdefault(r["mode"], []).append(r)
        if "int8" in by_mode:
            biggest = max(by_mode["int8"], key=lambda r: r["size_mb"])
            print(json.dumps({"metric": "allreduce_int8_wire_ratio",
                              "value": biggest["wire_ratio_vs_fp32"],
                              "size_mb": biggest["size_mb"]}))
        if "int8" in by_mode and "adaptive" in by_mode:
            # the ISSUE acceptance: the adaptive wire moves <= 60% of
            # int8's bytes on at least one bucket-size config
            i8 = {r["size_mb"]: r["wire_bytes"] for r in by_mode["int8"]}
            ratios = {r["size_mb"]: r["wire_bytes"] / i8[r["size_mb"]]
                      for r in by_mode["adaptive"] if r["size_mb"] in i8}
            if ratios:
                mb, ratio = min(ratios.items(), key=lambda kv: kv[1])
                print(json.dumps({"metric": "allreduce_adaptive_vs_int8_bytes",
                                  "value": round(ratio, 4), "size_mb": mb,
                                  "meets_60pct_target": ratio <= 0.6}))
        best = max(results, key=lambda r: r["effective_algbw_gbps"])
        print(json.dumps({"metric": "allreduce_compressed_algbw_gbps",
                          "value": best["effective_algbw_gbps"],
                          "unit": "GB/s",
                          "config": {k: best[k]
                                     for k in ("mode", "size_mb", "n")}}))
        return results

    if args.algo_sweep:
        hvd.init()
        results = bench_algo_sweep(sizes, args.iters, args.warmup)
        by_size = {}
        for r in results:
            by_size.setdefault(r["size_mb"], []).append(r)
        tuned = []
        for mb in sorted(by_size):
            # the per-size winner: what the joint tuner's argmin settles on,
            # >= every fixed (algorithm, bitwidth) at this size by
            # construction (the ISSUE acceptance)
            best = min(by_size[mb], key=lambda r: r["time_us"])
            tuned.append(best)
            print(json.dumps({"metric": "allreduce_algo_tuned",
                              "size_mb": mb,
                              "algorithm": best["algorithm"],
                              "mode": best["mode"],
                              "time_us": best["time_us"],
                              "algbw_gbps": best["algbw_gbps"]}))
        peak = max(tuned, key=lambda r: r["algbw_gbps"])
        print(json.dumps({"metric": "allreduce_algo_tuned_algbw_gbps",
                          "value": peak["algbw_gbps"], "unit": "GB/s",
                          "config": {k: peak[k]
                                     for k in ("algorithm", "mode",
                                               "size_mb", "n")}}))
        hvd.shutdown()
        return results

    if args.path == "allgather":
        results = bench_allgather(sizes, args.iters, args.warmup)
        by_size = {}
        for r in results:
            by_size.setdefault(r["size_mb"], []).append(r)
        for mb, rs in by_size.items():
            times = [r["time_us"] for r in sorted(rs, key=lambda r: r["n"])]
            print(json.dumps({"metric": "allgather_time_vs_world_us",
                              "size_mb": mb, "times_us": times,
                              "flat_ratio": round(times[-1] / times[0], 2)}))
        return results

    hvd.init()

    results = []
    if args.path in ("spmd", "both"):
        results += bench_spmd(sizes, args.iters, args.warmup)
    if args.path in ("eager", "both"):
        results += bench_eager(sizes, args.iters, args.warmup)

    best = max((r for r in results if r["path"] == "spmd"),
               key=lambda r: r["busbw_gbps"], default=None)
    if best is None:
        best = max(results, key=lambda r: r["busbw_gbps"])
    print(json.dumps({"metric": "allreduce_busbw_gbps",
                      "value": best["busbw_gbps"], "unit": "GB/s",
                      "config": {k: best[k] for k in ("path", "size_mb", "n")}}))
    hvd.shutdown()
    return results


if __name__ == "__main__":
    main()
