"""Smoke tests for the drills under benchmarks/.

Parity model: the reference measures scaling efficiency with
`examples/tensorflow2_synthetic_benchmark.py` run at multiple world sizes
(`docs/benchmarks.rst`); here the drills are importable and asserted for
what they report, on the 8-device virtual CPU platform the whole suite runs
on. Their timings there are not measurements (docs/benchmarks.md).
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))


def test_scaling_bench_reports_efficiency(capsys):
    import scaling_bench

    rates = scaling_bench.main([
        "--model", "ResNet18", "--batch-per-device", "2",
        "--image-size", "32", "--iters", "2", "--warmup", "1",
        "--world-sizes", "1,2"])
    assert set(rates) == {1, 2}
    for comm, nocomm in rates.values():
        assert comm > 0 and nocomm > 0
    lines = [json.loads(l) for l in
             capsys.readouterr().out.strip().splitlines()]
    summary = lines[-1]
    assert summary["metric"] == "weak_scaling_efficiency"
    assert summary["unit"] == "%"
    assert 0 < summary["value"] < 500  # sanity, CPU timing is noisy
    assert summary["config"]["shared_core_virtual_devices"] is True


def test_allreduce_bench_spmd_and_eager(capsys):
    import allreduce_bench

    results = allreduce_bench.main(
        ["--sizes-mb", "0.0625,0.25", "--iters", "3", "--warmup", "1"])
    paths = {r["path"] for r in results}
    assert paths == {"spmd", "eager"}
    for r in results:
        assert r["time_us"] > 0
        assert r["busbw_gbps"] > 0
    spmd_rows = [r for r in results if r["path"] == "spmd"]
    assert all(r["n"] == 8 for r in spmd_rows)  # real 8-device collective
    out = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(out[-1])
    assert summary["metric"] == "allreduce_busbw_gbps"


def test_allreduce_bench_compression_sweep(capsys):
    """The wire-mode sweep emits bytes-on-wire per mode: int8 at ~25.4% of
    the fp32 bytes, bf16 at exactly half."""
    import allreduce_bench

    results = allreduce_bench.main(
        ["--compression", "none,int8", "--sizes-mb", "0.0625",
         "--iters", "2", "--warmup", "1"])
    by_mode = {r["mode"]: r for r in results}
    assert set(by_mode) == {"none", "int8"}
    assert by_mode["none"]["wire_ratio_vs_fp32"] == 1.0
    assert by_mode["int8"]["wire_ratio_vs_fp32"] <= 0.28
    assert all(r["wire_gbps"] > 0 and r["time_us"] > 0 for r in results)
    out = capsys.readouterr().out.strip().splitlines()
    metrics = [json.loads(l) for l in out if '"metric"' in l]
    assert any(m["metric"] == "allreduce_int8_wire_ratio" for m in metrics)
