"""The benchmark's data files in tier-1: ``chipbench/tests/test_cells.py``
collected here, so that a PR that leaves ``BENCHMARK.json`` and the files
under ``chipbench/`` inconsistent fails the repo's own tests; and the
``--rehearse`` walk of the hybrid's cell (CPU, the family's toy size), which
every reader the cell lists has to survive. Nothing here is a device number.
"""

import pytest

from chipbench import harness
from chipbench.tests.test_cells import *  # noqa: F401,F403
from chipbench.tests.test_rehearsal import KEYS, rehearse

HYBRID_CELL = "granite4hm-train-s4096"


def test_hybrid_cell_rehearsal_prints_the_end_to_end_line():
    line, out = rehearse(HYBRID_CELL, 0)
    assert set(line) == KEYS and line["correct"] is True, out
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"rehearsal_train_tokens_per_s_chip",
                                    "rehearsal_setup_s"}
    assert "reference check" in out


def test_hybrid_cell_rehearsal_reads_every_per_layer_metric_it_lists():
    line, out = rehearse(HYBRID_CELL, 1)
    assert line["correct"] is True, out
    declared = {m["name"] for m in
                harness.declared_metrics(HYBRID_CELL)["per_layer"]}
    got = {k[len("rehearsal_"):] for k in line["metrics"]}
    # the CPU backend has no memory statistics, and the interpreter's
    # kernels are no custom calls, so their roofline share has no time
    assert declared - got <= {"peak_hbm_gib", "flash_attention_roofline"}
    assert got <= declared and {"ssd_ms", "ssd_roofline"} <= got
    values = {k: v["value"] for k, v in line["metrics"].items()}
    assert values["rehearsal_ssd_ms"] > 0
    # the scan's scope is its own class: its time is not the blocks'
    assert values["rehearsal_ssd_ms"] < values["rehearsal_xla_ops_ms"]
    assert values["rehearsal_blocks_recompute_ms"] > 0
