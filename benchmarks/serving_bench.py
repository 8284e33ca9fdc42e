#!/usr/bin/env python
"""Serving-mode load drill: lost requests, shed classes, failover; QPS, p99.

A drill of a feature no benchmark cell runs yet (the served path's cell,
``gpt2m-serve-c8``, is ROADMAP V1 / W1), kept for what it checks inside one
run: no request lost or delivered twice, the right class shed, the drain
contract. Its rates and latencies on the CPU are not measurements of this
system (the benchmark is ``python3 -m chipbench.run``, docs/benchmarks.md).

A Poisson load generator over the inference serving subsystem
(horovod_tpu/serving/, docs/inference.md). Requests arrive with
exponential inter-arrival times at ``--qps``, each a random prompt of
``--prompt-len`` tokens decoding ``--max-new`` tokens; the bench waits for
every completion and reports the sustained rate and the latency tail.

Two modes:

* **in-process** (default): one ``ServingEngine`` replica, submits go
  straight to the engine. This is the deterministic mode, and
  the only one that runs on the chip: a chip belongs to one process, and
  here one process holds the engine.
* **pod** (``--workers N``): spawns a ``ServingFrontend`` plus N worker
  replica subprocesses (``python -m horovod_tpu.serving.worker``) and
  drives them through a ``ServingClient`` over the hardened control
  plane. ``--kill-one`` SIGKILLs a worker mid-run and asserts ZERO lost
  requests — the killed replica's in-flight work must re-admit onto the
  survivors (exit 4 if anything is lost), which is the ISSUE-11
  acceptance demonstration. Pod mode and the ``--chaos`` drills are
  control-plane drills: several replicas cannot share a chip, so their
  workers run on the CPU, and asking for them without ``JAX_PLATFORMS=cpu``
  in the environment is an error, not a silent move off the device.

    JAX_PLATFORMS=cpu python benchmarks/serving_bench.py            # smoke
    python benchmarks/serving_bench.py --workers 2 --kill-one       # pod
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="Poisson load generator for the serving subsystem")
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--qps", type=float, default=16.0,
                   help="Poisson arrival rate (requests/second)")
    p.add_argument("--prompt-len", type=int, default=8)
    p.add_argument("--max-new", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--timeout", type=float, default=300.0,
                   help="per-request completion deadline")
    p.add_argument("--workers", type=int, default=0,
                   help="pod mode: spawn a frontend + N worker replica "
                        "subprocesses, on the CPU (0 = in-process engine, "
                        "the mode that runs on the chip)")
    p.add_argument("--kill-one", action="store_true",
                   help="pod mode: SIGKILL one worker mid-run and require "
                        "zero lost requests (exit 4 on loss)")
    p.add_argument("--chaos", default=None,
                   choices=["kill-frontend", "slow-replica", "overload",
                            "rolling-restart"],
                   help="run one survivable-serving chaos drill instead of "
                        "the load benchmark (exit 4 on any lost or "
                        "duplicated request, or a jepsen violation)")
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--blocks", type=int, default=256)
    p.add_argument("--block-size", type=int, default=16)
    return p.parse_args(argv)


def poisson_load(submit, args, vocab=251):
    """Drive ``submit(prompt, max_new) -> future`` at Poisson arrivals;
    returns (futures, submit_wall_seconds)."""
    rng = np.random.RandomState(args.seed)
    futs = []
    t0 = time.monotonic()
    next_t = t0
    for _ in range(args.requests):
        next_t += rng.exponential(1.0 / max(args.qps, 1e-6))
        while True:
            now = time.monotonic()
            if now >= next_t:
                break
            time.sleep(min(0.002, next_t - now))
        prompt = rng.randint(1, vocab, size=args.prompt_len).tolist()
        futs.append(submit(prompt, args.max_new))
    return futs, time.monotonic() - t0


def run_inprocess(args):
    from horovod_tpu.serving import ServingConfig
    from horovod_tpu.serving.worker import build_replica_engine

    cfg = ServingConfig(block_size=args.block_size, num_blocks=args.blocks,
                        max_batch=args.max_batch, max_context=128)
    engine = build_replica_engine(max_seq_len=128, config=cfg).start()
    # one throwaway request compiles prefill+decode outside the timed window
    engine.submit([1] * args.prompt_len, 2).wait(timeout=args.timeout)

    t0 = time.monotonic()
    futs, _ = poisson_load(engine.submit, args)
    for f in futs:
        f.wait(timeout=args.timeout)
    wall = time.monotonic() - t0
    engine.stop()
    lost = [f for f in futs if not f.done() or f.state != "done"]
    lats = [f.latency() for f in futs if f.latency() is not None]
    toks = sum(len(f.output) for f in futs)
    return lats, toks, wall, len(lost)


def run_pod(args):
    from horovod_tpu.serving import ServingClient, ServingFrontend

    fe = ServingFrontend().start()
    host, port = fe.addr[0], fe.addr[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = []
    for i in range(args.workers):
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "horovod_tpu.serving.worker",
             "--addr", f"{host}:{port}", "--rank", str(i + 1),
             "--max-batch", str(args.max_batch),
             "--blocks", str(args.blocks),
             "--block-size", str(args.block_size)],
            env=env, cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))))
    try:
        fe.wait_for_workers(args.workers, timeout=120)
        cli = ServingClient(host, port, name="bench")
        # warm every replica's compile cache before the timed window
        warm = [cli.submit([1] * args.prompt_len, 2)
                for _ in range(args.workers * args.max_batch)]
        for f in warm:
            f.result(timeout=args.timeout)

        t0 = time.monotonic()
        kill_at = args.requests // 3 if args.kill_one else None
        futs = []
        rng = np.random.RandomState(args.seed)
        next_t = time.monotonic()
        for i in range(args.requests):
            next_t += rng.exponential(1.0 / max(args.qps, 1e-6))
            while time.monotonic() < next_t:
                time.sleep(0.002)
            prompt = rng.randint(1, 251, size=args.prompt_len).tolist()
            futs.append(cli.submit(prompt, args.max_new))
            if kill_at is not None and i == kill_at:
                victim = procs[0]
                print(f"# SIGKILL worker pid {victim.pid} mid-run",
                      file=sys.stderr)
                victim.kill()
        lost = 0
        lats, toks = [], 0
        for f in futs:
            try:
                tokens = f.result(timeout=args.timeout)
            except (RuntimeError, TimeoutError) as exc:
                print(f"# LOST {f.id}: {exc}", file=sys.stderr)
                lost += 1
                continue
            toks += len(tokens)
            lats.append(f.client_latency())
        wall = time.monotonic() - t0
        print("# frontend: %s" % json.dumps(fe.stats()), file=sys.stderr)
        cli.close()
        return lats, toks, wall, lost
    finally:
        for pr in procs:
            if pr.poll() is None:
                pr.terminate()
        for pr in procs:
            try:
                pr.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pr.kill()
        fe.stop()


# --------------------------------------------------------- chaos drills
#
# Each drill exercises one row of the docs/inference.md failure matrix
# end to end with REAL processes/sockets and gates on the exactly-once
# ledger: every submitted request answered terminally exactly once (a
# delivery ledger recorded below the client's dedupe, so a duplicate
# RESULT from a confused frontend would be caught, not hidden).


class _LedgerClient:
    """Wraps a ServingClient to record every terminal RESULT frame as it
    arrives — BEFORE the client's pending-pop dedupe — so duplicated
    deliveries are observable evidence, not silently absorbed."""

    def __init__(self, cli, wire):
        self.cli = cli
        self.delivered = []  # (request_id, status) per terminal frame
        self._wire = wire
        inner = cli._on_result

        def spy(payload):
            rid, status, _, _, _ = wire.decode_serve_result(payload)
            if status != wire.SERVE_REJECTED:
                self.delivered.append((rid, status))
            inner(payload)

        cli._on_result = spy


def _drain_futures(futs, timeout):
    """Wait every future out; returns (lost_ids, statuses by id)."""
    lost = []
    for f in futs:
        if not f.wait(timeout=timeout):
            lost.append(f.id)
    return lost


def chaos_kill_frontend(args):
    """SIGKILL the active frontend under Poisson load with a warm standby
    attached: the standby must win the serving lease, workers and the
    client must follow the failover key, and every request must complete
    exactly once (jepsen-checked over the merged blackbox bundles)."""
    import shutil
    import tempfile

    from horovod_tpu import blackbox as _blackbox
    from horovod_tpu.blackbox.doctor import load_bundle
    from horovod_tpu.faultinject.jepsen import check_serving_history
    from horovod_tpu.run.rendezvous import KVStoreServer
    from horovod_tpu.runtime import wire
    from horovod_tpu.serving import ServingClient, ServingStandby
    from horovod_tpu.serving.worker import build_replica_engine
    from horovod_tpu.serving.worker import ServingWorker
    from horovod_tpu.serving import ServingConfig

    # honor a caller-supplied blackbox dir (pod_smoke runs the doctor
    # over the bundle after the drill); otherwise use a throwaway
    keep_bb = os.environ.get("HOROVOD_BLACKBOX_DIR")
    bb_dir = keep_bb or tempfile.mkdtemp(prefix="hvd_serving_chaos_")
    kv = KVStoreServer("", host="127.0.0.1").start()
    os.environ["HVD_KV_ADDR"] = f"127.0.0.1:{kv.port}"
    os.environ["HOROVOD_LEASE_TTL"] = "1.0"
    os.environ["HOROVOD_SERVING_STANDBY"] = "1"
    os.environ["HOROVOD_BLACKBOX"] = "1"
    os.environ["HOROVOD_BLACKBOX_DIR"] = bb_dir
    os.environ["HOROVOD_RECONNECT_JITTER"] = "0.3"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    # the active frontend is a subprocess — the thing we SIGKILL
    fe_proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.serving.server",
         "--rank", "0", "--gen", "0"],
        env=env, cwd=repo, stdout=subprocess.PIPE, text=True)
    line = fe_proc.stdout.readline().strip()
    assert line.startswith("SERVING_FRONTEND "), line
    host, port = line.split()[1].rsplit(":", 1)
    port = int(port)

    _blackbox.maybe_activate()
    _blackbox.set_identity(1, 4)
    standby = ServingStandby((host, port), "", rank=1, gen=0).start()
    time.sleep(0.3)  # let the replication snapshot land

    # two replica engines in-process (ranks 2 and 3 for the blackbox)
    cfg = lambda: ServingConfig(  # noqa: E731 - tiny local factory
        block_size=args.block_size, num_blocks=args.blocks,
        max_batch=args.max_batch, max_context=128)
    workers = [
        ServingWorker(host, port, build_replica_engine(
            max_seq_len=128, config=cfg()), name=f"worker-{i}",
            rank=2 + i, gen=0).start()
        for i in range(2)]

    rc = 0
    try:
        cli = ServingClient(host, port, name="chaos", gen=0,
                            connect_timeout=30.0)
        ledger = _LedgerClient(cli, wire)
        warm = [cli.submit([1] * args.prompt_len, 2) for _ in range(4)]
        for f in warm:
            f.result(timeout=args.timeout)

        rng = np.random.RandomState(args.seed)
        futs, kill_at = [], args.requests // 3
        for i in range(args.requests):
            time.sleep(rng.exponential(1.0 / max(args.qps, 1e-6)))
            prompt = rng.randint(1, 251, size=args.prompt_len).tolist()
            futs.append(cli.submit(prompt, args.max_new))
            if i == kill_at:
                print(f"# SIGKILL frontend pid {fe_proc.pid} mid-load",
                      file=sys.stderr)
                fe_proc.kill()
        lost = _drain_futures(futs, args.timeout)
        ok = sum(1 for f in futs if f.done() and not f._failed)
        assert standby.promoted, "standby never promoted"
        cli.close()

        submitted = [f.id for f in warm + futs]
        delivered = [rid for rid, _ in ledger.delivered]
        _blackbox.dump("chaos drill complete", force=True)
        verdict = check_serving_history(load_bundle(bb_dir),
                                        submitted, delivered)
        print("# jepsen: %s" % json.dumps(
            {k: verdict[k] for k in ("single_writer", "exactly_once",
                                     "lost", "duplicates",
                                     "fenced_frames", "violations")}),
            file=sys.stderr)
        print(f"# kill-frontend: {ok}/{len(futs)} ok, "
              f"{len(lost)} unresolved, standby promoted epoch "
              f"{standby.frontend.fence_epoch}", file=sys.stderr)
        if lost or verdict["violations"]:
            print("# FAIL: lost=%s violations=%s"
                  % (lost, verdict["violations"]), file=sys.stderr)
            rc = 4
    finally:
        for w in workers:
            w.stop()
        standby.stop()
        if fe_proc.poll() is None:
            fe_proc.kill()
        fe_proc.wait(timeout=10)
        kv.stop()
        if not keep_bb:
            shutil.rmtree(bb_dir, ignore_errors=True)
    return rc


def chaos_slow_replica(args):
    """One replica stalls every engine step: hedged decode must fire
    after the p95-derived delay and keep the run loss-free — the fast
    replica's first-winner answer cancels the laggard's copy."""
    os.environ["HOROVOD_SERVING_HEDGE"] = "2.0"
    from horovod_tpu.runtime import wire
    from horovod_tpu.serving import (ServingClient, ServingConfig,
                                     ServingFrontend)
    from horovod_tpu.serving.worker import ServingWorker, \
        build_replica_engine

    fe = ServingFrontend().start()
    fe.hedge_delay_override = 0.3  # deterministic drill, no warmup ring
    host, port = fe.addr[0], fe.addr[1]

    def mk(i, slow):
        cfg = ServingConfig(block_size=args.block_size,
                            num_blocks=args.blocks,
                            max_batch=args.max_batch, max_context=128)
        eng = build_replica_engine(max_seq_len=128, config=cfg)
        if slow:
            eng.step_delay = 0.5
        return ServingWorker(host, port, eng, name=f"worker-{i}",
                             rank=i).start()

    workers = [mk(0, slow=True), mk(1, slow=False)]
    rc = 0
    try:
        fe.wait_for_workers(2, timeout=60)
        cli = ServingClient(host, port, name="chaos")
        ledger = _LedgerClient(cli, wire)
        futs = [cli.submit(
            [1 + i] * args.prompt_len, args.max_new)
            for i in range(args.requests)]
        lost = _drain_futures(futs, args.timeout)
        cli.close()
        dup = len(ledger.delivered) - len({r for r, _ in ledger.delivered})
        stats = fe.stats()
        print(f"# slow-replica: hedged={stats['hedged']} lost={len(lost)} "
              f"duplicate_deliveries={dup}", file=sys.stderr)
        if lost or dup:
            print(f"# FAIL: lost={lost} dup={dup}", file=sys.stderr)
            rc = 4
        elif stats["hedged"] == 0:
            print("# FAIL: the slow replica never triggered a hedge",
                  file=sys.stderr)
            rc = 1
    finally:
        for w in workers:
            w.stop()
        fe.stop()
        os.environ.pop("HOROVOD_SERVING_HEDGE", None)
    return rc


def chaos_overload(args):
    """Burst at ~4x the sustainable rate with a 50/50 priority mix and
    shedding enabled: the brownout/shed path must confine degradation to
    the best-effort class while high-priority p99 stays within 1.5x of
    its uncontended baseline."""
    os.environ["HOROVOD_SERVING_SHED"] = "0.5"
    from horovod_tpu.runtime import wire
    from horovod_tpu.serving import (ServingClient, ServingConfig,
                                     ServingFrontend)
    from horovod_tpu.serving.worker import ServingWorker, \
        build_replica_engine

    fe = ServingFrontend(max_backlog=2 * args.max_batch).start()
    host, port = fe.addr[0], fe.addr[1]
    cfg = ServingConfig(block_size=args.block_size, num_blocks=args.blocks,
                        max_batch=args.max_batch, max_context=128)
    worker = ServingWorker(host, port, build_replica_engine(
        max_seq_len=128, config=cfg), name="worker-0", rank=0).start()
    rc = 0
    try:
        fe.wait_for_workers(1, timeout=60)
        cli = ServingClient(host, port, name="chaos", max_retries=8)
        # warmup — pay the compile cost outside every measurement window
        for i in range(2):
            cli.submit([1 + i] * args.prompt_len, args.max_new,
                       priority=wire.SERVE_PRIO_HIGH).result(
                           timeout=args.timeout)
        # phase 1a — uncontended baseline: sequential high-priority load
        base_lats = []
        for i in range(max(8, args.requests // 4)):
            f = cli.submit([1 + i % 64] * args.prompt_len, args.max_new,
                           priority=wire.SERVE_PRIO_HIGH)
            f.result(timeout=args.timeout)
            base_lats.append(f.client_latency())
        base_p99 = float(np.percentile(base_lats, 99))
        # phase 1b — sustainable throughput at full batch occupancy (the
        # rate the burst must beat; a sequential probe would undercount
        # capacity by roughly the batch width)
        probe = max(2 * args.max_batch, args.requests // 2)
        t0 = time.monotonic()
        _drain_futures(
            [cli.submit([1 + i % 64] * args.prompt_len, args.max_new,
                        priority=wire.SERVE_PRIO_HIGH)
             for i in range(probe)], args.timeout)
        sustainable = probe / (time.monotonic() - t0)

        # phase 2 — 4x sustainable burst. The high class stays inside
        # capacity (1 in 8 submits ≈ 0.5x sustainable): the contract
        # under test is that best-effort overload cannot starve it, not
        # that an over-capacity high class magically stays fast.
        rng = np.random.RandomState(args.seed)
        futs = {wire.SERVE_PRIO_HIGH: [], wire.SERVE_PRIO_BEST_EFFORT: []}
        for i in range(args.requests):
            time.sleep(rng.exponential(1.0 / (4.0 * sustainable)))
            prio = (wire.SERVE_PRIO_HIGH if i % 8 == 0
                    else wire.SERVE_PRIO_BEST_EFFORT)
            futs[prio].append(cli.submit(
                rng.randint(1, 251, size=args.prompt_len).tolist(),
                args.max_new, priority=prio))
        all_futs = futs[0] + futs[1]
        lost = _drain_futures(all_futs, args.timeout)
        stats = fe.stats()
        cli.close()

        shed_wrong_class = [f.id for f in futs[wire.SERVE_PRIO_HIGH]
                            if f.status == wire.SERVE_SHED]
        hi_lats = [f.client_latency()
                   for f in futs[wire.SERVE_PRIO_HIGH]
                   if f.done() and not f._failed]
        hi_p99 = (float(np.percentile(hi_lats, 99))
                  if hi_lats else float("inf"))
        ratio = hi_p99 / max(base_p99, 1e-9)
        print(f"# overload: sustainable={sustainable:.1f}/s "
              f"base_p99={base_p99 * 1e3:.0f}ms hi_p99={hi_p99 * 1e3:.0f}ms "
              f"ratio={ratio:.2f} shed={stats['shed']} lost={len(lost)}",
              file=sys.stderr)
        if lost or shed_wrong_class:
            print(f"# FAIL: lost={lost} "
                  f"high-priority sheds={shed_wrong_class}",
                  file=sys.stderr)
            rc = 4
        elif stats["shed"] == 0:
            print("# FAIL: the burst never tripped the shed path",
                  file=sys.stderr)
            rc = 1
        if rc == 0 and ratio > 1.5 and hi_p99 > 0.25:
            # absolute guard rail from the acceptance criterion (the
            # 0.25s floor keeps millisecond-scale noise from flaking CI)
            print(f"# FAIL: high-priority p99 degraded {ratio:.2f}x under "
                  "overload (budget 1.5x)", file=sys.stderr)
            rc = 1
    finally:
        worker.stop()
        fe.stop()
        os.environ.pop("HOROVOD_SERVING_SHED", None)
    return rc


def chaos_rolling_restart(args):
    """Drain → kill → replace each replica in turn under load: the drain
    hands queued work back for re-dispatch and lets in-flight work
    finish, so the rolling restart loses and duplicates nothing."""
    from horovod_tpu.runtime import wire
    from horovod_tpu.serving import (ServingClient, ServingConfig,
                                     ServingFrontend)
    from horovod_tpu.serving.worker import ServingWorker, \
        build_replica_engine

    fe = ServingFrontend().start()
    host, port = fe.addr[0], fe.addr[1]

    def mk(name, rank):
        cfg = ServingConfig(block_size=args.block_size,
                            num_blocks=args.blocks,
                            max_batch=args.max_batch, max_context=128)
        return ServingWorker(host, port, build_replica_engine(
            max_seq_len=128, config=cfg), name=name, rank=rank).start()

    workers = {"worker-0": mk("worker-0", 0), "worker-1": mk("worker-1", 1)}
    rc = 0
    try:
        fe.wait_for_workers(2, timeout=60)
        cli = ServingClient(host, port, name="chaos")
        ledger = _LedgerClient(cli, wire)
        rng = np.random.RandomState(args.seed)
        futs = []
        restarts = ["worker-0", "worker-1"]
        restart_at = {args.requests // 3: "worker-0",
                      2 * args.requests // 3: "worker-1"}
        gen = 0
        for i in range(args.requests):
            time.sleep(rng.exponential(1.0 / max(args.qps, 1e-6)))
            futs.append(cli.submit(
                rng.randint(1, 251, size=args.prompt_len).tolist(),
                args.max_new))
            name = restart_at.get(i)
            if name:
                print(f"# rolling restart: draining {name}",
                      file=sys.stderr)
                assert fe.drain_worker(name)
                assert fe.wait_worker_drained(name, timeout=args.timeout)
                workers[name].stop()
                gen += 1
                workers[name] = mk(name, gen + 1)
        lost = _drain_futures(futs, args.timeout)
        cli.close()
        dup = len(ledger.delivered) - len({r for r, _ in ledger.delivered})
        print(f"# rolling-restart: {len(futs) - len(lost)}/{len(futs)} ok, "
              f"restarted {restarts}, dup={dup}", file=sys.stderr)
        if lost or dup:
            print(f"# FAIL: lost={lost} dup={dup}", file=sys.stderr)
            rc = 4
    finally:
        for w in workers.values():
            w.stop()
        fe.stop()
    return rc


_CHAOS = {
    "kill-frontend": chaos_kill_frontend,
    "slow-replica": chaos_slow_replica,
    "overload": chaos_overload,
    "rolling-restart": chaos_rolling_restart,
}


def _require_cpu_drill(what: str) -> None:
    """Pod mode and the chaos drills run several replicas, each of which
    would need the chip for itself; their worker subprocesses are started
    with ``JAX_PLATFORMS=cpu``. The caller has to have said so too."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        sys.exit(f"serving_bench: {what} runs several replicas, and a chip "
                 f"belongs to one process — it is a control-plane drill "
                 f"whose workers run on the CPU. Set JAX_PLATFORMS=cpu to "
                 f"run it; on the chip use the in-process mode (neither "
                 f"--workers nor --chaos).")


def main(argv=None):
    args = parse_args(argv)
    if args.chaos or args.workers:
        _require_cpu_drill(f"--chaos {args.chaos}" if args.chaos
                           else f"--workers {args.workers}")
    else:
        from horovod_tpu.utils import compile_cache

        compile_cache.enable()
    if args.chaos:
        return _CHAOS[args.chaos](args)
    if args.kill_one and args.workers < 2:
        sys.exit("--kill-one needs --workers >= 2 (someone must survive)")
    lats, toks, wall, lost = (run_pod(args) if args.workers
                              else run_inprocess(args))
    if not lats:
        sys.exit("no requests completed")
    p50 = float(np.percentile(lats, 50))
    p99 = float(np.percentile(lats, 99))
    qps = len(lats) / wall
    tok_s = toks / wall
    # bucketed p99 alongside the exact one: the same estimate Prometheus
    # consumers (anomaly watch, SLO engine) compute from the histogram
    # family, so the bench shows the quantization error operators will see
    from horovod_tpu.metrics import LATENCY_BUCKETS, quantile_from_buckets

    counts = [0] * (len(LATENCY_BUCKETS) + 1)
    for lat in lats:
        for i, b in enumerate(LATENCY_BUCKETS):
            if lat <= b:
                counts[i] += 1
                break
        else:
            counts[-1] += 1
    p99_bucketed = quantile_from_buckets(LATENCY_BUCKETS, counts, 0.99)
    print(f"# {len(lats)}/{args.requests} requests in {wall:.2f}s "
          f"({'pod, %d workers' % args.workers if args.workers else 'in-process'})",
          file=sys.stderr)
    print(f"# sustained QPS: {qps:.1f}; tokens/s: {tok_s:.0f}; "
          f"p50: {p50 * 1e3:.1f}ms; p99: {p99 * 1e3:.1f}ms "
          f"(bucketed: {p99_bucketed * 1e3:.1f}ms); lost: {lost}",
          file=sys.stderr)
    print(json.dumps({
        "metric": "serving_p99_seconds",
        "value": round(p99, 4),
        "unit": "s",
        "qps": round(qps, 2),
        "tokens_per_sec": round(tok_s, 1),
        "p50_seconds": round(p50, 4),
        "lost": lost,
    }))

    if lost:
        print(f"# FAIL: {lost} request(s) lost — elastic re-admission must "
              "leave zero behind", file=sys.stderr)
        return 4
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
