"""Checkpoint subsystem drill: save-interval sweep + recovery breakdown.

A drill of a feature no benchmark cell runs yet (stall per save and time to
resume at real state sizes are ROADMAP V10's cell, on the dp4 cell first),
kept for the write-behind contract it checks inside one run. Its timings on
the CPU are not measurements of this system (the benchmark is
``python3 -m chipbench.run``, docs/benchmarks.md).

Two questions an operator sizing ``HOROVOD_CKPT_INTERVAL`` actually asks
(docs/checkpoint.md):

1. **What does checkpointing cost the step path?** The sweep drives real
   ``CkptManager.on_state_commit`` calls over a synthetic model at several
   intervals and reports the per-commit overhead — pack + double-buffer
   hand-off; the disk write itself rides the writer thread. The
   write-behind contract is the acceptance bar: the cumulative
   ``hvd_checkpoint_stall_seconds`` across the whole sweep must stay ~0
   (default gate 50 ms/commit worst case), or the "async" checkpoint is
   stealing step time.

2. **How long is a rank gone when it dies?** The recovery breakdown times
   each leg of the hot-spare path separately — bare process spawn, buddy
   journal fetch (O(shard) over a real socket), shard unpack, and the
   disk-bundle read a peerless restore falls back to — so a lost-rank
   budget can be computed for any shard size instead of guessed.

Usage::

    python benchmarks/ckpt_bench.py --shard-mb 4 --intervals 1,5,10

The headline lines are ``ckpt_commit_stall_ms`` (the write-behind stall a
commit, gated by ``--stall-gate-ms``) and the recovery breakdown with its
``peer_restore_ms`` (fetch + unpack).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from horovod_tpu.ckpt import buddy as buddy_mod  # noqa: E402
from horovod_tpu.ckpt import bundle, manager  # noqa: E402
from horovod_tpu.elastic import ElasticState  # noqa: E402
from horovod_tpu.metrics import instruments  # noqa: E402


def _make_state(shard_elems):
    state = ElasticState(
        w=np.ones(shard_elems, np.float32),
        opt_shard=np.zeros(shard_elems, np.float32),
        step=0)
    state.mark_sharded("opt_shard")
    return state


def sweep_intervals(intervals, shard_mb, commits):
    """Per-commit step-path overhead at each save interval. The model
    mutates every step (worst case for the journal delta) and the writer
    drains between cells so slow disks can't smear one interval's I/O
    into the next cell's timings."""
    shard_elems = int(shard_mb * (1 << 20) / 4)
    out = []
    stall0 = instruments.checkpoint_stall_seconds().value
    for interval in intervals:
        root = tempfile.mkdtemp(prefix="ckpt_bench_")
        mgr = manager.CkptManager(root, rank=0, world=1, buddy=False,
                                  interval=interval)
        try:
            state = _make_state(shard_elems)
            per_commit = []
            for step in range(1, commits + 1):
                state.opt_shard = state.opt_shard + np.float32(1.0)
                state.step = step
                state._committed.update(state._values)
                t0 = time.perf_counter()
                mgr.on_state_commit(state, step)
                per_commit.append(time.perf_counter() - t0)
            mgr.drain(60)
            snaps = len(bundle.complete_steps(root))
            out.append({
                "metric": "ckpt_commit_overhead_ms",
                "interval": interval,
                "shard_mb": shard_mb,
                "commits": commits,
                "snapshots": snaps,
                "mean_ms": round(1e3 * sum(per_commit) / len(per_commit),
                                 3),
                "max_ms": round(1e3 * max(per_commit), 3),
            })
        finally:
            mgr.stop()
            shutil.rmtree(root, ignore_errors=True)
    stall_s = instruments.checkpoint_stall_seconds().value - stall0
    return out, stall_s


def recovery_breakdown(shard_mb):
    """Time each leg of the lost-rank path once, milliseconds each."""
    shard_elems = int(shard_mb * (1 << 20) / 4)
    payload = manager.pack_tree(
        {"slots": {"opt_shard": np.arange(shard_elems,
                                          dtype=np.float32)},
         "ef": {}})

    # bare process spawn: the floor any replacement pays before one byte
    # of state moves
    t0 = time.perf_counter()
    subprocess.run([sys.executable, "-c", "pass"], check=True)
    spawn_ms = 1e3 * (time.perf_counter() - t0)

    # buddy journal fetch over a real localhost socket (the O(shard) leg)
    secret = "bench"
    srv = buddy_mod.BuddyServer(secret, rank=0, host="127.0.0.1")
    srv.put(1, 100, payload)
    try:
        t0 = time.perf_counter()
        got = buddy_mod.fetch_shard(("127.0.0.1", srv.port), secret, 1)
        fetch_ms = 1e3 * (time.perf_counter() - t0)
        assert got is not None and got[0] == 100
        t0 = time.perf_counter()
        tree = manager.unpack_tree(got[1])
        unpack_ms = 1e3 * (time.perf_counter() - t0)
        assert tree["slots"]["opt_shard"].nbytes == shard_elems * 4
    finally:
        srv.stop()

    # the peerless fallback: latest complete disk bundle
    root = tempfile.mkdtemp(prefix="ckpt_bench_")
    try:
        n, c = bundle.write_shard(root, 100, 0, payload)
        bundle.finalize_manifest(root, 100, 0,
                                 {0: {"nbytes": n, "crc": c}})
        t0 = time.perf_counter()
        data = bundle.read_shard(root, 100, 0)
        manager.unpack_tree(data)
        disk_ms = 1e3 * (time.perf_counter() - t0)
    finally:
        shutil.rmtree(root, ignore_errors=True)

    return {
        "metric": "ckpt_recovery_breakdown",
        "shard_mb": shard_mb,
        "process_spawn_ms": round(spawn_ms, 2),
        "peer_fetch_ms": round(fetch_ms, 2),
        "unpack_ms": round(unpack_ms, 2),
        "disk_restore_ms": round(disk_ms, 2),
        "peer_restore_ms": round(fetch_ms + unpack_ms, 2),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shard-mb", type=float, default=4.0,
                    help="per-rank shard size in MiB")
    ap.add_argument("--intervals", default="1,5,10",
                    help="comma-separated HOROVOD_CKPT_INTERVAL sweep")
    ap.add_argument("--commits", type=int, default=30,
                    help="commits per sweep cell")
    ap.add_argument("--stall-gate-ms", type=float, default=50.0,
                    help="exit 4 when the cumulative write-behind stall "
                         "averages above this per commit (the async "
                         "contract: the step path pays a buffer swap, "
                         "never disk I/O)")
    args = ap.parse_args(argv)

    intervals = [int(i) for i in args.intervals.split(",")]
    cells, stall_s = sweep_intervals(intervals, args.shard_mb,
                                     args.commits)
    for cell in cells:
        print(json.dumps(cell))
    stall_per_commit_ms = 1e3 * stall_s / (len(intervals) * args.commits)
    print(json.dumps({"metric": "ckpt_commit_stall_ms",
                      "value": round(stall_per_commit_ms, 4),
                      "total_stall_s": round(stall_s, 6)}))

    breakdown = recovery_breakdown(args.shard_mb)
    print(json.dumps(breakdown))

    rc = 0
    if stall_per_commit_ms > args.stall_gate_ms:
        print(json.dumps({"gate": "stall", "failed": True,
                          "value_ms": stall_per_commit_ms,
                          "gate_ms": args.stall_gate_ms}))
        rc = 4
    return rc


if __name__ == "__main__":
    sys.exit(main())
