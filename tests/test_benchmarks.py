"""Smoke tests for the benchmark harnesses (BASELINE headline metrics).

Parity model: the reference measures scaling efficiency with
`examples/tensorflow2_synthetic_benchmark.py` run at multiple world sizes
(`docs/benchmarks.rst`); here the harnesses are importable and asserted on
the 8-device virtual CPU platform the whole suite runs on.
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


def test_lm_bench_smoke(capsys, monkeypatch):
    """LM bench (tokens/sec + MFU) runs end-to-end on the tiny preset and
    emits the one-line JSON contract."""
    monkeypatch.setenv("LM_PRESET", "tiny")
    import lm_bench

    lm_bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    rec = json.loads(out[-1])
    # the smoke's own name: a CPU rate never goes under the device metric
    assert rec["metric"] == "transformer_lm_smoke_tokens_per_sec"
    assert rec["value"] > 0
    assert rec["unit"] == "tok/s"
    assert rec["mfu_pct"] is None
    assert rec["platform"] == "cpu" and rec["devices"] == 8


def test_lm_bench_without_a_chip_is_an_error(monkeypatch):
    """No preset named: the benchmark wants the medium LM on a TPU, and on
    the CPU it refuses instead of shrinking under the same metric name."""
    import pytest

    monkeypatch.delenv("LM_PRESET", raising=False)
    import lm_bench

    with pytest.raises(SystemExit, match="no TPU"):
        lm_bench.main()


def test_lm_bench_moe_smoke(capsys, monkeypatch):
    """--moe runs all four dispatch configs and emits the JSON contract:
    capacity out-runs the dense one-hot reference (the O(E·N·d) einsums
    vs O(C·d) buffers — a large structural gap, safe to assert even on
    noisy CPU timers) and the int4 catalog bytes stay under the 60%
    CI bar vs a bf16 exchange."""
    monkeypatch.setenv("LM_PRESET", "tiny")
    monkeypatch.setenv("LM_MOE_TOKENS", "1024")
    monkeypatch.setenv("LM_MOE_ITERS", "2")
    monkeypatch.setenv("LM_MOE_WARMUP", "1")
    import lm_bench

    assert lm_bench.main(["--moe"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    rec = json.loads(out[-1])
    assert rec["metric"] == "moe_lm_smoke_tokens_per_sec"
    assert rec["value"] > 0
    cfgs = rec["configs"]
    assert set(cfgs) == {"exact", "capacity", "capacity-int8",
                         "capacity-int4"}
    assert (cfgs["capacity"]["tokens_per_sec"]
            > cfgs["exact"]["tokens_per_sec"])
    for name in ("capacity", "capacity-int8", "capacity-int4"):
        assert 0 <= cfgs[name]["drop_rate"] < 1
        assert cfgs[name]["imbalance"] >= 1
    assert rec["wire_byte_ratio_vs_bf16"]["int4"] <= 0.6


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


# -- perf-history store + regression gate (benchmarks/history.py) ----------

def test_history_append_and_load(tmp_path):
    import history

    path = str(tmp_path / "history.jsonl")
    rec = history.append_record(path, {"metric": "imgs_per_sec",
                                       "value": 100.0, "model": "ResNet18"})
    assert rec["schema"] == history.SCHEMA_VERSION
    assert rec["timestamp"] > 0
    history.append_record(path, {"metric": "imgs_per_sec", "value": 110.0})
    history.append_record(path, {"metric": "tokens_per_sec", "value": 5.0})
    assert [r["value"] for r in
            history.load_history(path, metric="imgs_per_sec")] == [100.0,
                                                                   110.0]
    assert len(history.load_history(path)) == 3


def test_history_skips_garbage_and_future_schema(tmp_path):
    import json as _json

    import history

    path = str(tmp_path / "history.jsonl")
    history.append_record(path, {"metric": "m", "value": 1.0})
    with open(path, "a") as f:
        f.write('{"metric": "m", "va')  # truncated tail from a killed run
        f.write("\n")
        f.write(_json.dumps({"metric": "m", "value": 9.0,
                             "schema": history.SCHEMA_VERSION + 1}) + "\n")
        f.write("[1, 2]\n")  # not a record
    recs = history.load_history(path, metric="m")
    assert [r["value"] for r in recs] == [1.0]
    assert history.load_history(str(tmp_path / "absent.jsonl")) == []


def test_check_regression_verdicts():
    import history

    # no usable history: never a failure (the first CI run seeds it)
    v = history.check_regression([], 50.0)
    assert v["regression"] is False and v["reason"] == "no_baseline"

    hist = [{"value": x} for x in (100.0, 102.0, 98.0, 101.0, 99.0)]
    ok = history.check_regression(hist, 95.0, tolerance=0.15)
    assert ok["regression"] is False and ok["reason"] == "ok"
    assert ok["baseline"] == 100.0

    bad = history.check_regression(hist, 80.0, tolerance=0.15)
    assert bad["regression"] is True and bad["reason"] == "below_tolerance"
    assert bad["floor"] == 85.0

    # the window only sees the trailing records
    shifted = hist + [{"value": 10.0}] * 5
    v = history.check_regression(shifted, 9.0, window=5, tolerance=0.15)
    assert v["baseline"] == 10.0 and v["regression"] is False


def test_bench_regression_gate_compares_before_append(tmp_path):
    """bench.py orders compare-then-append so today's run cannot vote in
    its own baseline; exit code 3 flags a regression. Exercised at the
    history layer the same way bench.main does."""
    import history

    path = str(tmp_path / "history.jsonl")
    for v in (100.0, 101.0, 99.0):
        history.append_record(path, {"metric": "imgs_per_sec", "value": v})
    fresh = 50.0
    verdict = history.check_regression(
        history.load_history(path, metric="imgs_per_sec"), fresh)
    history.append_record(path, {"metric": "imgs_per_sec", "value": fresh})
    assert verdict["regression"] is True  # compared against 100-ish, not 50
    assert len(history.load_history(path)) == 4
