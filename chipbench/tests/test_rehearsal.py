"""Both jobs end to end at a toy size on the CPU backend, through the same
command the driver runs plus ``--rehearse``: interpret-mode kernels, four
virtual devices for the four-chip cell. The last line has exactly the
contract's keys, under ``rehearsal_`` metric names.

The AOT proof that each cell's programs compile for a v5e is run by hand
(it takes minutes and loads libtpu):

    JAX_PLATFORMS=cpu HVD_PALLAS=on python3 -m chipbench.aot_check
"""

import filecmp
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DATA = os.path.join(harness.HERE, "tests", "data")


def rehearse(cell, trace, cwd=harness.ROOT, devices=1, seconds="2"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HVD_PALLAS="interpret",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell, "--seed",
         "5", "--seconds", seconds, "--trace", str(trace), "--rehearse"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


CASES = [("gpt2m-train-s1024", 1), ("gpt2m-train-dp4", 4),
         ("gpt2l-train-s1024", 1), ("gpt2m-serve-c8", 1)]


@pytest.mark.parametrize("cell,devices", CASES)
def test_untraced_rehearsal_prints_the_end_to_end_line(cell, devices):
    line, out = rehearse(cell, 0, devices=devices)
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    declared = harness.declared_metrics(cell)["end_to_end"]
    assert set(line["metrics"]) == {f"rehearsal_{m['name']}" for m in declared}
    for m in declared:
        got = line["metrics"][f"rehearsal_{m['name']}"]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["count"] == devices
    assert "sample count" in out


@pytest.mark.parametrize("cell,devices", [CASES[1], CASES[3]])
def test_traced_rehearsal_prints_per_layer_metrics_and_breakdown(cell, devices):
    line, _ = rehearse(cell, 1, devices=devices)
    assert set(line) == KEYS | {"breakdown"}
    assert line["correct"] is True
    declared = {m["name"] for m in harness.declared_metrics(cell)["per_layer"]}
    got = {k[len("rehearsal_"):] for k in line["metrics"]}
    assert all(k.startswith("rehearsal_") for k in line["metrics"])
    # the CPU backend has no memory statistics; everything else is read
    assert declared - got <= {"peak_hbm_gib", "serve_peak_hbm_gib",
                              "flash_attention_roofline"}
    assert got <= declared
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    b = line["breakdown"]
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    if cell == "gpt2m-train-dp4":
        assert any("[collective]" in n for n, _ in b["device_ops"])
    else:
        assert {"kv_gather_host", "decode_call"} & {n for n, _ in b["idle_gaps"]}


def copy_of_the_benchmark(tmp_path):
    """``chipbench/`` copied beside a link to the program; the copy's
    ``BENCHMARK.json`` is the caller's to write."""
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(harness.ROOT, "horovod_tpu"),
               tmp_path / "horovod_tpu")
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def share_metrics(bench, old_cell, new_cell, but=()):
    for m in bench["end_to_end"] + bench["per_layer"]:
        if old_cell in m.get("workloads", []) and m["name"] not in but:
            m["workloads"].append(new_cell)


def test_a_copied_workload_file_is_a_new_cell_with_no_code_edit(tmp_path):
    bench = copy_of_the_benchmark(tmp_path)
    spec = harness.load_json("workloads", "gpt2m-train-s1024.json")
    spec["name"], spec["traffic"] = "copied-cell", "train-b8-s1024-copy"
    shutil.copy(tmp_path / "chipbench" / "mixes" / "train-b8-s1024.json",
                tmp_path / "chipbench" / "mixes" / "train-b8-s1024-copy.json")
    with open(tmp_path / "chipbench" / "workloads" / "copied-cell.json", "w") as f:
        json.dump(spec, f)
    bench["workloads"].append({**bench["workloads"][0], "name": "copied-cell",
                               "traffic": "train-b8-s1024-copy"})
    share_metrics(bench, "gpt2m-train-s1024", "copied-cell")
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    line, _ = rehearse("copied-cell", 0, cwd=str(tmp_path), seconds="1")
    assert line["correct"] is True
    assert "rehearsal_train_tokens_per_s_chip" in line["metrics"]


def add_as_files_only(tmp_path, what="second_family"):
    """What a ``model_config`` PR brings, as ``tests/data/<what>`` holds it
    for a toy: a family module, its model's reference, a configuration, a
    mix and a workload file (``second_objective``: an objective module too)
    laid over a copy of ``chipbench/``, and entries appended to
    ``BENCHMARK.json``. Returns the new cell's name."""
    bench = copy_of_the_benchmark(tmp_path)
    copy, data = tmp_path / "chipbench", os.path.join(DATA, what)
    added = {os.path.relpath(os.path.join(path, f), data)
             for path, _, files in os.walk(data) for f in files}
    assert not [f for f in added if os.path.exists(copy / f)]
    shutil.copytree(data, copy, dirs_exist_ok=True)
    entries = harness.load_json("tests", "data", what,
                                "benchmark_entries.json")
    cell = entries["workload"]["name"]
    bench["configs"].append(entries["config"])
    bench["workloads"].append(entries["workload"])
    share_metrics(bench, entries["shares_with"], cell, entries["not_shared"])
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return cell


def rehearse_as_files_only(tmp_path, what, module):
    """Lay ``tests/data/<what>`` over a copy, see that no file of the copy
    is edited, rehearse the new cell untraced and traced, and see ``module``
    (found by a name a data file gives) named when its file is missing.
    Returns the traced run's metric names."""
    cell = add_as_files_only(tmp_path, what)
    copy = tmp_path / "chipbench"
    for path, _, files in os.walk(harness.HERE):
        for f in files if "__pycache__" not in path else ():
            ours = os.path.join(path, f)
            theirs = copy / os.path.relpath(ours, harness.HERE)
            assert filecmp.cmp(ours, theirs, shallow=False), ours

    line, out = rehearse(cell, 0, cwd=str(tmp_path), seconds="1")
    assert set(line) == KEYS and line["correct"] is True, out
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"rehearsal_train_tokens_per_s_chip",
                                    "rehearsal_setup_s"}
    line, out = rehearse(cell, 1, cwd=str(tmp_path), seconds="1")
    assert line["correct"] is True, out
    got = {k[len("rehearsal_"):] for k in line["metrics"]}
    assert {"train_mfu", "train_step_ms", "xla_ops_ms", "blocks_fwd_ms",
            "blocks_bwd_ms", "head_loss_ms", "optimizer_ms", "model_other_ms",
            "device_idle_share", "compile_s"} <= got
    assert line["metrics"]["rehearsal_train_mfu"]["value"] > 0
    assert line["metrics"]["rehearsal_train_step_ms"]["value"] > 0
    os.remove(copy / module)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell, "--seed",
         "1", "--seconds", "1", "--trace", "0", "--rehearse"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert f"chipbench/{module}" in proc.stderr
    return got


def rehearse_broken_underneath(tmp_path, what, model, sound, broken):
    """The rest of a run with the timed path broken underneath: ``sound``
    replaced by ``broken`` in the toy's model file, which its reference and
    its objective do not read."""
    cell = add_as_files_only(tmp_path, what)
    path = tmp_path / "chipbench" / model
    text = path.read_text()
    assert text.count(sound) == 1
    path.write_text(text.replace(sound, broken))
    line, out = rehearse(cell, 0, cwd=str(tmp_path), seconds="1")
    assert line["correct"] is False and line["failed"] == 0
    return out


def test_a_second_architecture_is_new_files_and_entries_with_no_code_edit(tmp_path):
    """No file of the copy is edited, and every reader of the training job
    applies to a model that is not ``TransformerLM``; the family is found
    by the configuration's ``model_type``."""
    got = rehearse_as_files_only(tmp_path, "second_family",
                                 "families/toymixer.py")
    assert not got & {"flash_attention_roofline", "attn_kernel_ms",
                      "attn_fwd_kernel_ms", "attn_bwd_kernel_ms",
                      "allreduce_ms", "blocks_recompute_ms"}


def test_a_second_objective_is_new_files_and_entries_with_no_code_edit(tmp_path):
    """No file of the copy is edited, and the training job with its readers
    applies to a model that takes two token arrays, a batch of three arrays
    and a loss that is not ``lm_loss``; the objective is found by the mix's
    ``objective``."""
    rehearse_as_files_only(tmp_path, "second_objective",
                           "objectives/toy_denoise.py")


def test_a_timed_path_that_computes_something_else_is_not_correct(tmp_path):
    """The model the window trains halves each block's update of the
    residual stream, its reference does not, and the run says so."""
    out = rehearse_broken_underneath(
        tmp_path, "second_family", "toymixer_model.py",
        "return x + nn.Dense(", "return x + 0.5 * nn.Dense(")
    assert "CHECK FAILED: logit rms error" in out


def test_a_timed_loss_that_drops_its_weights_is_not_correct(tmp_path):
    """The loss the window trains on counts a masked position once, not by
    the inverse of its block's rate; the objective states what its weighting
    gives at the start, and the run says so."""
    out = rehearse_broken_underneath(
        tmp_path, "second_objective", "toydenoiser_model.py",
        "jnp.sum(weights * nll)", "jnp.sum((weights > 0) * nll)")
    assert "CHECK FAILED: first loss" in out


def test_a_directory_with_only_the_benchmark_fails_without_a_result(tmp_path):
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "gpt2m-train-s1024", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_no_accelerator_is_an_error_not_a_smaller_run():
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "gpt2m-train-s1024", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr
