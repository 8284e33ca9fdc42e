#!/usr/bin/env bash
# CI pipeline — the `.buildkite/gen-pipeline.sh` equivalent.
#
# Stages mirror the reference's (build, unit suite, launcher-driven smoke
# runs, stall behavior, drills): the unit suite runs on the 8-device
# virtual CPU platform, and the smoke stages run REAL multi-process jobs
# under the launcher (`hvdrun -np 2 ...`), exercising the cross-process
# control plane the way `horovodrun -np 2 pytest` does upstream.
#
# Everything here checks behaviour. The drills under benchmarks/ gate on
# counts and ratios inside one run; no time they print on the CPU mesh is a
# measurement. Speed is `python3 -m chipbench.run` on the chip, recorded in
# PERF_LEDGER.jsonl (docs/benchmarks.md).
#
# Usage: ci/run_tests.sh [quick]
#   quick — skip the slower drill stage.

set -euo pipefail
cd "$(dirname "$0")/.."

QUICK="${1:-}"
export JAX_PLATFORMS=cpu   # the chip is chip_smoke.py's and chipbench's

stage() { echo; echo "=== $1 ==="; }

stage "build: native engine core"
python setup.py build_native

stage "unit suite (8-device virtual CPU platform)"
python -m pytest tests/ -q -m "not integration"

stage "metrics subsystem (registry, wire roundtrip, /metrics endpoint)"
python -m pytest tests/test_metrics.py -q

stage "chaos: fault injection, frame integrity, reconnect/replay, liveness"
python -m pytest tests/test_faultinject.py -q

stage "chaos: data-plane integrity (grad guard, consistency audit, watchdog)"
python -m pytest tests/test_integrity.py tests/test_stall.py -q

stage "chaos: straggler-adaptive execution (policy, partial rounds, EF rejoin)"
python -m pytest tests/test_straggler.py -q -m "not integration"
# acceptance: with a 500 ms chronic straggler injected, the surviving
# ranks' step time must stay within 1.5x the fault-free baseline
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/allreduce_bench.py --chaos slow@rank:500 --iters 6

stage "controlplane: hierarchical negotiation, coordinator failover, storms"
python -m pytest tests/test_coord.py -q -m "not integration"
# the control-plane integrations run on plain CPU (elastic Popen harness):
# SIGKILL the rank-0 coordinator mid-step, a real hierarchical job, and
# SIGKILL rank 0 with hierarchy AND standby enabled together
python -m pytest -q \
    "tests/test_coord.py::test_coordinator_sigkill_failover_bit_identical" \
    "tests/test_coord.py::test_hierarchical_mode_end_to_end" \
    "tests/test_coord.py::test_hierarchical_standby_sigkill"
# the hierarchical path must beat flat negotiation at scale (rounds/s is
# printed; the >=5x acceptance curve lives in docs/control-plane.md)
python benchmarks/coord_bench.py --ranks 256 --rounds 15 --mode both
# N-tier sweep: 1k/10k/100k fake ranks through the aggregation tree; p99
# round latency at 100k must stay within 5x the 1k point of the same run
python benchmarks/coord_bench.py --mode tier --ranks 1024,10240,102400 \
    --rounds 15 --warmup 3 --p99-gate 5.0

stage "chaos: partition-tolerant fenced leadership (lease, wire epochs, jepsen)"
python -m pytest tests/test_fencing.py -q -m "not integration"
# the split-brain drill: cut a 2-process job in half mid-training, assert
# the old coordinator self-fences before the lease TTL, the standby takes
# over by acquiring the lease, the healed deposed primary's frames are
# rejected by fencing epoch, and the jepsen-lite checker proves
# single-writer leadership + exactly-once step application
python -m pytest -q \
    "tests/test_fencing.py::test_partition_failover_fenced_bit_identical"
python ci/pod_smoke.py check_split_brain

stage "tracing: clock, spans, merge, hvdprof critical-path report"
python -m pytest tests/test_tracing.py -q

stage "doctor: blackbox flight recorder, signatures, hvddoctor, anomaly watch"
python -m pytest tests/test_blackbox.py -q

stage "goodput: wall-clock attribution ledger, SLO burn alerts, hvdtop"
python -m pytest tests/test_goodput.py -q
# acceptance: after a real training run (the MNIST example, 50 steps
# through hvd.init()) hvd.metrics() must attribute >= 99% of each rank's
# wall clock (the ledger's completeness bar, docs/goodput.md)
XLA_FLAGS=--xla_force_host_platform_device_count=8 python - <<'EOF'
import runpy
import horovod_tpu as hvd
runpy.run_path("examples/mnist_dp.py", run_name="__main__")
doc = hvd.metrics()
walls = {s["labels"]["rank"]: s["value"]
         for s in doc["hvd_goodput_wall_seconds"]["series"]}
attributed = {}
for fam in ("hvd_goodput_seconds_total", "hvd_badput_seconds_total"):
    for s in doc.get(fam, {}).get("series", []):
        r = s["labels"]["rank"]
        attributed[r] = attributed.get(r, 0.0) + s["value"]
assert walls, "no goodput attribution in hvd.metrics()"
for r, wall in walls.items():
    frac = attributed.get(r, 0.0) / wall if wall else 0.0
    print(f"rank {r}: {frac:.1%} of {wall:.2f}s attributed")
    assert frac >= 0.99, f"rank {r} attribution {frac:.1%} < 99%"
EOF

stage "restart: async sharded checkpointing + peer-redundant recovery"
python -m pytest tests/test_ckpt.py -q -m "not integration"
# the write-behind contract is the gate: per-commit stall must stay ~0
# (the step path pays a buffer swap, never disk I/O); the O(shard)
# peer-restore breakdown is printed beside it.
# the kill-and-replace integration rides the integration suite below.
python benchmarks/ckpt_bench.py --shard-mb 2 --commits 15

stage "overlap: bucketed backward drain, fused kernels, hvdprof overlap %"
python -m pytest tests/test_overlap.py -q

stage "compression v2: int4 wire, adaptive bitwidth selector, convergence gate"
python -m pytest tests/test_adaptive.py -q
python -m pytest tests/test_compression.py -q -k "Int4 or int4 or adaptive"
# adaptive wire must hit the <=60% of int8 byte target in the drill
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/allreduce_bench.py --compression int8,int4,adaptive \
        --sizes-mb 0.25 --iters 3

stage "gspmd: quantized compiled-path ring, EF residual, cache-key pin"
python -m pytest tests/test_gspmd.py -q
# acceptance: three-way head-to-head (coordinator wire vs plain GSPMD vs
# quantized GSPMD) — asserts int4 wire bytes <=60% of plain and int8
# <=1.05 B per moved element (docs/gspmd.md)
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/scaling_bench.py --three-way --iters 3 \
        --elements 65536

stage "algo: collective algorithm zoo, joint tuner, footprint catalog"
python -m pytest tests/test_algo.py -q
# acceptance: the (size x algorithm x bitwidth) sweep on the compiled
# fast path — the per-size tuned argmin >= every fixed combo by
# construction; sub-64KB points exercise the tree's latency regime
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/allreduce_bench.py --algo-sweep \
        --sizes-mb "" --sizes-kb 4,16 --iters 3

stage "moe: capacity-factor Switch dispatch over the quantized all_to_all"
python -m pytest tests/test_moe.py tests/test_expert_parallel.py -q

stage "serving: continuous batching, paged KV cache, elastic pod serving"
python -m pytest tests/test_serving.py -q -m "not integration"
# in-process load drill (the deterministic mode); exit 4 on any lost
# request
python benchmarks/serving_bench.py --requests 12 --qps 32 --max-new 4

stage "serving-chaos: frontend failover, deadlines, shedding, hedging, drain"
python -m pytest tests/test_serving_failover.py -q -m "not integration"
# the four survivability drills (docs/inference.md failure matrix); each
# exits 4 on any lost or duplicated request delivery (jepsen-checked).
# kill-frontend runs under pod_smoke below so hvddoctor can gate on the
# serving_failover signature over the same blackbox bundle
python benchmarks/serving_bench.py --chaos slow-replica \
    --requests 16 --qps 8 --max-new 4
python benchmarks/serving_bench.py --chaos overload --requests 48 \
    --max-new 4
python benchmarks/serving_bench.py --chaos rolling-restart \
    --requests 24 --qps 16 --max-new 4
# frontend SIGKILL + doctor: hvddoctor must name the serving_failover
python ci/pod_smoke.py check_serving_frontend_kill

stage "integration suite: real multi-process jobs (launcher, SPMD mesh)"
# includes tests/test_spark_real.py (real-pyspark scenarios; they skip
# when pyspark is absent from the image)
python -m pytest tests/ -q -m integration

stage "pod-day smoke: multi-host command lines from docs/running.md"
python ci/pod_smoke.py

stage "launcher smoke: 2-process training job under hvdrun"
cat > /tmp/ci_smoke_worker.py <<'EOF'
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.getcwd())
import jax; jax.config.update("jax_platforms", "cpu")
import numpy as np
import horovod_tpu as hvd
hvd.init()
r = hvd.rank()
w = np.asarray(hvd.broadcast(np.ones(3) * (r + 1), root_rank=0, name="w"))
for i in range(3):
    g = hvd.allreduce(np.ones(3) * (r + 1), name=f"g{i}")
    w = w - 0.1 * np.asarray(g)
assert np.allclose(w, 1.0 - 0.3 * 1.5), w
print(f"rank {r} ok")
hvd.shutdown()
EOF
python bin/hvdrun -np 2 --no-nic-discovery python /tmp/ci_smoke_worker.py

stage "launcher smoke: run() func API across 2 processes"
python examples/interactive_run.py

stage "launcher smoke: ragged alltoall routing across 4 processes"
python examples/alltoallv_routing.py

if [ "$QUICK" != "quick" ]; then
  # outside quick mode: the 2-process run jit-compiles ResNet-50 on CPU,
  # the slowest single stage (unit tests already cover the pipeline)
  stage "real-data input pipeline: rank-sharded image folder across 2 processes"
  rm -rf /tmp/hvd_ci_imgfolder
  python bin/hvdrun -np 2 --no-nic-discovery \
      python examples/imagenet_resnet50_realdata.py \
      --data-dir /tmp/hvd_ci_imgfolder --synthesize 48 \
      --image-size 32 --batch-size 4 --epochs 1

  stage "drills: scaling ladder + allreduce paths (virtual 8-device mesh)"
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python benchmarks/scaling_bench.py --world-sizes 1,8 \
          --batch-per-device 2 --iters 3
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python benchmarks/allreduce_bench.py --sizes-mb 0.25,1 --iters 5
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python benchmarks/allreduce_bench.py --bucket-mb 0,0.5 --iters 5 \
          --layers 4
fi

echo
echo "CI pipeline passed."
