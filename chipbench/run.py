"""One process, one cell, one run.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. Loads the cell's files by name, sets the program up through the
entry points a user calls, warms up, measures for ``--seconds``, checks the
outputs, and prints one JSON object as the last line of standard output:
the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics
(window counters plus a short profiler slice) with ``--trace 1``.

``--rehearse`` walks the same code at a toy size on whatever backend JAX
has, for the harness's own tests; its metrics are named ``rehearsal_*`` so
that no CPU number carries a device metric's name.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse   # noqa: E402
import importlib  # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

from . import harness  # noqa: E402


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true")
    return p.parse_args(argv)


def run(args) -> dict:
    cell = harness.load_cell(args.workload, args.rehearse)
    declared = harness.declared_metrics(cell.name)
    if not os.path.isdir(os.path.join(harness.ROOT, "horovod_tpu")):
        raise harness.BenchmarkError(
            "no horovod_tpu/ beside chipbench/: nothing to measure")
    peak = harness.require_devices(cell.chips, args.rehearse)

    import horovod_tpu as hvd
    from horovod_tpu.utils import compile_cache

    harness.say(f"compile cache: {compile_cache.enable()}")
    hvd.init()
    ctx = harness.Context(cell, args.seed, args.seconds, bool(args.trace),
                          args.rehearse, T_START, peak,
                          harness.CompileCounter())
    job = importlib.import_module(f"chipbench.jobs.{cell.job}")
    try:
        window = job.run(ctx)
    finally:
        hvd.shutdown()

    values = (harness.read_layer_metrics(window) if args.trace
              else window.end_to_end)
    wanted = declared["per_layer" if args.trace else "end_to_end"]
    metrics = {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
               for m in wanted if values.get(m["name"]) is not None}
    missing = [m["name"] for m in wanted
               if not args.trace and m["name"] not in metrics]
    if missing:
        raise harness.BenchmarkError(f"the job reported no {missing}")
    if args.rehearse:
        metrics = {f"rehearsal_{k}": v for k, v in metrics.items()}

    for note in window.notes:
        print(note, flush=True)
    print("end to end: " + json.dumps(window.end_to_end), flush=True)
    device = harness.device_report(window.memory_peak_bytes)
    line = {"correct": bool(window.correct), "attempted": window.attempted,
            "failed": window.failed, "metrics": metrics, "device": device}
    if args.trace and window.trace is not None:
        device["busy_s"] = window.trace.busy_s
        device["window_s"] = window.trace.window_s
        line["breakdown"] = window.trace.breakdown()
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        line = run(args)
    except harness.BenchmarkError as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
