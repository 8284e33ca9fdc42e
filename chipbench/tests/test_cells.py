"""Every data file loads and names only things that exist, and
``BENCHMARK.json`` stays inside the contract's limits."""

import glob
import importlib
import json
import os
import re

import pytest

from chipbench import harness, trace_reduce, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
#: the cells BENCHMARK.json does not list yet, with the entries they wait with
PENDING = {os.path.basename(p)[:-5]: harness.load_json("pending", os.path.basename(p))
           for p in glob.glob(os.path.join(harness.HERE, "pending", "*.json"))}
CELLS = sorted(os.path.basename(p)[:-5] for p in glob.glob(
    os.path.join(harness.HERE, "workloads", "*.json")))
#: a job is a file under jobs/, found by the name a mix gives
JOBS = {os.path.basename(p)[:-3] for p in glob.glob(
    os.path.join(harness.HERE, "jobs", "*.py"))} - {"__init__"}


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["chipbench"]
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) < 65536


def with_pending():
    """BENCHMARK.json as it would be with every pending cell added."""
    merged = json.loads(json.dumps(BENCH))
    for entries in PENDING.values():
        merged["workloads"].append(entries["workload"])
        merged["end_to_end"] += entries["end_to_end"]
        merged["per_layer"] += entries["per_layer"]
    return merged


@pytest.mark.parametrize("bench", [BENCH, with_pending()],
                         ids=["declared", "with-pending"])
def test_names_units_and_lines_fit_the_contract(bench):
    BENCH = bench
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m) <= {"name", "unit", "better", "bound", "source", "layer",
                          "moves", "workloads"}
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and len(c["source"]) <= 200
        assert c["file"].startswith("chipbench/")
    for path, _, files in os.walk(harness.HERE):
        if "__pycache__" in path:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(path, f)


@pytest.mark.parametrize("bench", [BENCH, with_pending()],
                         ids=["declared", "with-pending"])
def test_every_declared_cell_has_its_files_and_metrics(bench):
    BENCH = bench
    end_to_end = {m["name"] for m in BENCH["end_to_end"]}
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)
    assert {w["name"] for w in BENCH["workloads"]} <= set(CELLS)
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.spec["config"] == w["config"]
        assert cell.spec["traffic"] == w["traffic"]
        assert cell.chips == w["chips"] and cell.spec["why"] == w["why"]
        assert configs[w["config"]]["file"] == \
            f"chipbench/configs/{w['config']}.json"
        assert cell.config["reduced"] == configs[w["config"]]["reduced"]
        declared = harness.declared_metrics(w["name"])
        e2e = {m["name"] for m in declared["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert declared["per_layer"]
        for m in declared["per_layer"]:
            assert m["moves"] in e2e, (w["name"], m["name"])
    for m in BENCH["per_layer"]:
        assert m["moves"] in end_to_end


@pytest.mark.parametrize("name", CELLS)
def test_workload_file_names_things_that_exist(name):
    cell = harness.load_cell(name)
    importlib.import_module(f"chipbench.jobs.{cell.job}").run
    for key in ("source", "changed", "assumed", "reduced", "deployment"):
        assert key in cell.config, key
    for key in ("why", "who", "sizing"):
        assert cell.spec[key], f"{name}: empty {key}"
    assert cell.vocab_rows % 128 == 0 >= 0
    assert cell.vocab_rows >= cell.config["vocab_size"]


def test_layer_metric_files_match_benchmark_json():
    bench = with_pending()
    assert {w["name"] for w in bench["workloads"]} == set(CELLS)
    declared = {m["name"]: m for m in bench["per_layer"]}
    found = {}
    for mod in harness.layer_metric_modules():
        assert mod.__name__.endswith("." + mod.NAME)
        assert set(mod.JOBS) <= JOBS and callable(mod.read)
        found[mod.NAME] = mod
    assert set(found) == set(declared)
    for name, m in declared.items():
        mod = found[name]
        assert (m["unit"], m["layer"], m["moves"]) == \
            (mod.UNIT, mod.LAYER, mod.MOVES), name
        for cell in m.get("workloads", CELLS):
            assert harness.load_cell(cell).job in mod.JOBS, (name, cell)


def test_every_configuration_names_a_family_with_the_six_names():
    files = sorted(glob.glob(os.path.join(harness.HERE, "configs", "*.json")))
    assert {f"chipbench/configs/{os.path.basename(p)}" for p in files} == {
        c["file"] for c in BENCH["configs"]}
    for path in files:
        config = harness.load_json("configs", os.path.basename(path))
        family = harness.load_family(config["model_type"])
        for name in harness.FAMILY_NAMES:
            assert name == "REHEARSAL" or callable(getattr(family, name)), \
                (path, name)
        # the toy size overrides keys the configuration has, vocab_size among
        # them (the rehearsal's table is padded from it)
        assert set(family.REHEARSAL) <= set(config), path
        assert "vocab_size" in family.REHEARSAL
    with pytest.raises(harness.BenchmarkError, match="families/no_such.py"):
        harness.load_family("no_such")


def test_op_classes_and_peaks_load():
    classes = trace_reduce.load_classes()
    order = [c for c, _ in classes]
    # a later PR may add class files; these three stay, in priority order
    assert [c for c in order if c in ("attention_kernel", "collective", "copy")
            ] == ["attention_kernel", "collective", "copy"]
    assert len(order) == len(set(order)) and trace_reduce.UNMATCHED not in order
    for name, cls in (("tpu_custom_call %flash_fwd.47", "attention_kernel"),
                      ("tpu_custom_call %flash_bwd.36", "attention_kernel"),
                      ("tpu_custom_call %flash_bwd_dq.2", "attention_kernel"),
                      ("tpu_custom_call %flash_bwd_dkv", "attention_kernel"),
                      ("tpu_custom_call %flash_step.1", "attention_kernel"),
                      # another Pallas kernel, named or not, is not attention
                      ("tpu_custom_call %ssd_scan.3", "xla_op"),
                      ("tpu_custom_call %flash_fwd_quantized.3", "xla_op"),
                      ("tpu_custom_call %block_6.3", "xla_op"),
                      ("custom-call %cholesky.1", "xla_op"),
                      ("all-reduce-start %all-reduce-start.3", "collective"),
                      ("all-reduce all-reduce.1", "collective"),
                      ("all-reduce-scatter-fusion %x", "xla_op"),
                      ("copy-done %copy-done.7", "copy"),
                      ("fusion %fusion.991", "xla_op")):
        assert trace_reduce.classify(name, classes) == cls, name
    peaks = harness.load_json("peaks.json")
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert all("source" in p for p in peaks.values())


#: every mix file, and those of the training job, which name an objective
MIXES = sorted(os.path.basename(p)[:-5] for p in glob.glob(
    os.path.join(harness.HERE, "mixes", "*.json")))
TRAINING_MIXES = [m for m in MIXES if traffic.load(m)["job"] == "train_lm"]


def test_traffic_files_load():
    assert TRAINING_MIXES
    for name in MIXES:
        mix = traffic.load(name)
        assert mix["job"] in JOBS and mix["what"]
        assert ("objective" in mix) == (name in TRAINING_MIXES), name


@pytest.mark.parametrize("name", TRAINING_MIXES)
def test_training_mix_names_an_objective_with_the_five_names(name):
    mix = traffic.load(name)
    assert NAME.match(mix["objective"])
    objective = harness.load_objective(mix["objective"])   # the file exists
    for attr in harness.OBJECTIVE_NAMES:
        assert callable(getattr(objective, attr)), (name, attr)
    for cell in map(harness.load_cell, CELLS):
        if cell.spec["traffic"] == name:
            assert cell.objective is objective


def test_a_training_mix_without_its_objective_names_the_missing_file():
    cell = harness.load_cell("gpt2m-train-s1024")
    cell.mix = {k: v for k, v in cell.mix.items() if k != "objective"}
    with pytest.raises(harness.BenchmarkError,
                       match="mixes/train-b8-s1024.json has no \"objective\""):
        cell.objective
    cell.mix["objective"] = "no_such"
    with pytest.raises(harness.BenchmarkError, match="objectives/no_such.py"):
        cell.objective
    # a serving cell has none to name, and is not asked for one
    assert "objective" not in harness.load_cell("gpt2m-serve-c8").mix
