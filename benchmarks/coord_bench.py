"""Control-plane negotiation drill: flat vs hierarchical coordination.

A drill of a feature no benchmark cell runs yet (the eager plane's
coordinator; its cell is ROADMAP W6), kept for the ratios it checks inside
one run. It runs on the host and touches no device: its rounds/s are not
measurements of this system (the benchmark is ``python3 -m chipbench.run``,
docs/benchmarks.md).

Drives the REAL ``CoordState`` barrier with simulated ranks and measures
negotiation rounds per second and p99 round latency as the rank count
grows. ``flat`` mode models the pre-hierarchy control plane: one
``exchange()`` call (= one control frame at rank 0) per rank per round.
``hier`` mode models per-host sub-coordinators: one ``exchange_batch()``
call (= ONE frame) per host per round, each carrying that host's ranks.

``tier`` mode models the N-tier tree (HOROVOD_HIERARCHY_TIERS >= 2): one
``exchange_tier()`` call per TOP-TIER subtree per round, each carrying the
steady-state single GROUP (seq, payload, rank runs) its whole subtree
coalesces into — rank 0's work is O(groups), independent of rank count.

The interesting output is the scaling curve — flat does O(ranks) frame
work and O(ranks) thread wakeups under the coordinator lock per round,
hierarchical does O(hosts), tiered does O(top-tier subtrees). The PR-9
acceptance bar is >= 5x rounds/s for hier over flat at 1024 simulated
ranks (64 ranks/host); the PR-15 bar is tier-mode p99 round latency at
100k ranks <= 5x the 1024-rank point (``--p99-gate``), where the flat
wire degrades linearly.

Usage::

    python benchmarks/coord_bench.py --ranks 64,256,1024 --mode both
    python benchmarks/coord_bench.py --mode tier \
        --ranks 1024,10240,102400 --p99-gate 5.0

The headline line is hier/tier rounds/s at the largest rank count. Flat
mode is capped at ``--flat-cap`` simulated ranks (one OS thread per rank).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from horovod_tpu.runtime import wire  # noqa: E402
from horovod_tpu.runtime.coordinator import CoordState  # noqa: E402


def _make_state(world):
    return CoordState(world, 0, cache_capacity=4096,
                      stall_warning_s=600.0, stall_shutdown_s=0.0)


def _payload():
    return wire.encode_request_list(
        0, [], [wire.ReqMeta("bench", 0, "float32", (1024,))], epoch=-1)


def bench_mode(mode, ranks, ranks_per_host, rounds, warmup,
               tiers=2, fanout=32):
    """One (mode, ranks) cell: persistent worker threads drive ``rounds``
    negotiation rounds through a fresh CoordState; returns rounds/s, p99
    round latency, and the frames-per-round the coordinator observed."""
    if mode == "hier":
        hosts = max(1, ranks // ranks_per_host)
        units = hosts
    elif mode == "tier":
        # one worker per TOP-TIER subtree: the tree below it (hosts
        # coalescing local ranks, mid tiers merging run lists) happens on
        # other machines in reality, so here its steady-state output — one
        # group covering the subtree's whole rank span — is precomputed
        # and only rank 0's per-round work is measured
        hosts = -(-ranks // ranks_per_host)
        if tiers <= 0:
            # auto depth (the docs/control-plane.md deployment rule): add
            # a tier whenever rank 0 would otherwise face more than
            # ``fanout`` direct children — this is what keeps its
            # per-round work bounded as ranks grow two orders
            tiers = 2
            while -(-hosts // fanout ** (tiers - 1)) > fanout:
                tiers += 1
        span = fanout ** (tiers - 1)          # hosts per top-tier subtree
        units = -(-hosts // span)
        unit_ranks = span * ranks_per_host
    else:
        units = ranks
    st = _make_state(ranks)
    payload = _payload()
    total = warmup + rounds
    start = threading.Barrier(units + 1)
    done = threading.Barrier(units + 1)
    errors = []

    def flat_worker(r):
        try:
            for seq in range(total):
                start.wait()
                st.exchange(r, seq, payload)
                done.wait()
        except Exception as exc:  # pragma: no cover - surfaced in main
            errors.append(exc)
            start.abort()
            done.abort()

    def host_worker(h):
        lo = h * ranks_per_host
        hi = min(lo + ranks_per_host, ranks)
        try:
            for seq in range(total):
                start.wait()
                st.exchange_batch(
                    [(r, seq, payload) for r in range(lo, hi)])
                done.wait()
        except Exception as exc:  # pragma: no cover
            errors.append(exc)
            start.abort()
            done.abort()

    def tier_worker(u):
        lo = u * unit_ranks
        hi = min(lo + unit_ranks, ranks)
        subtree = "t%d.%d" % (tiers, u)
        runs = [(lo, hi - lo)]
        try:
            for seq in range(total):
                start.wait()
                st.exchange_tier(tiers, subtree,
                                 [(seq, payload, runs)])
                done.wait()
        except Exception as exc:  # pragma: no cover
            errors.append(exc)
            start.abort()
            done.abort()

    target = {"hier": host_worker, "tier": tier_worker}.get(mode,
                                                            flat_worker)
    threads = [threading.Thread(target=target, args=(u,), daemon=True)
               for u in range(units)]
    for t in threads:
        t.start()

    latencies = []
    frames0 = None
    for seq in range(total):
        t0 = time.perf_counter()
        start.wait()
        done.wait()
        dt = time.perf_counter() - t0
        if seq == warmup - 1:
            frames0 = st.frames_in
        if seq >= warmup:
            latencies.append(dt)
    frames_per_round = (st.frames_in - frames0) / rounds if rounds else 0
    for t in threads:
        t.join(timeout=30)
    if errors:
        raise errors[0]

    latencies.sort()
    p99 = latencies[min(len(latencies) - 1,
                        int(round(0.99 * (len(latencies) - 1))))]
    wall = sum(latencies)
    return {
        "mode": mode,
        "ranks": ranks,
        "tiers": tiers if mode == "tier" else 1,
        "units": units,
        "rounds": rounds,
        "rounds_per_sec": round(rounds / wall, 2) if wall else 0.0,
        "p99_round_ms": round(p99 * 1e3, 3),
        "frames_per_round": round(frames_per_round, 1),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ranks", default="64,256,1024",
                    help="comma-separated simulated rank counts")
    ap.add_argument("--ranks-per-host", type=int, default=64,
                    help="batch size per simulated host in hier mode")
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--mode", choices=["flat", "hier", "tier", "both"],
                    default="both")
    ap.add_argument("--tiers", type=int, default=0,
                    help="aggregation-tree depth modeled in tier mode "
                         "(0 = auto: deepen until rank 0 has at most "
                         "--fanout direct children)")
    ap.add_argument("--fanout", type=int, default=8,
                    help="children per aggregator above the host tier")
    ap.add_argument("--flat-cap", type=int, default=4096,
                    help="skip flat cells above this rank count (flat "
                         "mode spawns one OS thread per rank)")
    ap.add_argument("--p99-gate", type=float, default=None,
                    help="exit 3 when p99 round latency at the LARGEST "
                         "rank count exceeds this multiple of the "
                         "smallest point's p99 (the 100k-rank scaling "
                         "acceptance gate)")
    args = ap.parse_args(argv)

    rank_counts = [int(r) for r in args.ranks.split(",")]
    modes = ["flat", "hier"] if args.mode == "both" else [args.mode]
    results = []
    for ranks in rank_counts:
        for mode in modes:
            if mode == "flat" and ranks > args.flat_cap:
                print(json.dumps({
                    "mode": "flat", "ranks": ranks, "skipped":
                    "above --flat-cap %d (one thread per rank)"
                    % args.flat_cap}))
                continue
            r = bench_mode(mode, ranks, args.ranks_per_host,
                           args.rounds, args.warmup,
                           tiers=args.tiers, fanout=args.fanout)
            results.append(r)
            print(json.dumps(r))
        if args.mode == "both":
            flat = next((r for r in results
                         if r["ranks"] == ranks and r["mode"] == "flat"),
                        None)
            hier = next((r for r in results
                         if r["ranks"] == ranks and r["mode"] == "hier"),
                        None)
            if flat and hier and flat["rounds_per_sec"]:
                print(json.dumps({
                    "metric": "coord_hier_speedup",
                    "ranks": ranks,
                    "value": round(hier["rounds_per_sec"]
                                   / flat["rounds_per_sec"], 2)}))

    biggest = max(rank_counts)
    best_mode = "tier" if args.mode == "tier" else "hier"
    headline = next((r for r in results
                     if r["ranks"] == biggest and r["mode"] == best_mode),
                    results[-1])
    print(json.dumps({
        "metric": "coord_%s_rounds_per_sec" % best_mode,
        "value": headline["rounds_per_sec"],
        "unit": "rounds/s",
        "ranks": headline["ranks"],
    }))

    rc = 0
    # the 100k scaling gate (ISSUE 15 acceptance): p99 round latency at
    # the largest sweep point must stay within --p99-gate times the
    # smallest point's — flat degrades ~linearly, the tree must not
    if args.p99_gate and len(rank_counts) >= 2:
        per_ranks = {r["ranks"]: r for r in results
                     if r["mode"] == best_mode}
        if len(per_ranks) >= 2:
            small = per_ranks[min(per_ranks)]
            big = per_ranks[max(per_ranks)]
            scale = (big["p99_round_ms"] / small["p99_round_ms"]
                     if small["p99_round_ms"] else 0.0)
            verdict = {
                "metric": "coord_p99_scaling",
                "mode": best_mode,
                "ranks_small": small["ranks"], "ranks_big": big["ranks"],
                "p99_small_ms": small["p99_round_ms"],
                "p99_big_ms": big["p99_round_ms"],
                "scale": round(scale, 2), "gate": args.p99_gate,
                "pass": scale <= args.p99_gate,
            }
            print(json.dumps(verdict))
            if not verdict["pass"]:
                print("# P99 GATE FAILED: %dx ranks cost %.2fx p99 "
                      "(gate %.1fx)" % (big["ranks"] // small["ranks"],
                                        scale, args.p99_gate),
                      file=sys.stderr)
                rc = 3
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
