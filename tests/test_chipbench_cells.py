"""The benchmark's data files in tier-1: ``chipbench/tests/test_cells.py``
collected here, so that a PR that leaves ``BENCHMARK.json`` and the files
under ``chipbench/`` inconsistent fails the repo's own tests; and the
``--rehearse`` walk of each ``HybridLM`` cell (CPU, its family's toy size),
which every reader the cell lists has to survive. Nothing here is a device
number.
"""

import functools

import pytest

from chipbench import harness
from chipbench.tests.test_causal_conv_ms import *  # noqa: F401,F403
from chipbench.tests.test_cells import *  # noqa: F401,F403
from chipbench.tests.test_moe_capacity_ms import *  # noqa: F401,F403
from chipbench.tests.test_moe_dispatch_ms import *  # noqa: F401,F403
from chipbench.tests.test_rehearsal import KEYS, rehearse
from chipbench.tests.test_setup_phases import *  # noqa: F401,F403

#: which row capacity ran and the router: every cell with a routed layer
CAPACITY = {"moe_worst_case_ms", "moe_fit_ms", "moe_router_ms"}
#: cell -> the per-layer metrics that are its architecture's own
HYBRID_CELLS = {
    "granite4hm-train-s4096": {"ssd_ms", "ssd_roofline", "causal_conv_ms"},
    "lfm2moe-train-s8192": CAPACITY | {
        "moe_ms", "moe_experts_ms", "moe_dispatch_ms", "moe_experts_roofline",
        "short_conv_ms"},
    "nemotron3s-train-s4096": CAPACITY | {
        "ssd_ms", "ssd_roofline", "moe_ms", "moe_experts_ms",
        "moe_experts_roofline", "moe_route_ms", "moe_dispatch_ms",
        "moe_shared_ms", "moe_latent_ms", "lm_head_ms", "causal_conv_ms"},
    "lagunas-train-s8192": CAPACITY | {
        "moe_ms", "moe_experts_ms", "moe_experts_roofline", "moe_route_ms",
        "moe_dispatch_ms", "moe_shared_ms", "lm_head_ms", "attn_gate_ms",
        "attn_window_kernel_ms"},
    "kanana2-train-s16384": CAPACITY | {
        "moe_ms", "moe_experts_ms", "moe_route_ms", "moe_dispatch_ms",
        "moe_shared_ms", "lm_head_ms", "mla_latent_ms", "mla_assemble_ms"},
    "qwen3next-train-s16384": CAPACITY | {
        "moe_ms", "moe_experts_ms", "moe_route_ms", "moe_dispatch_ms",
        "moe_shared_ms", "lm_head_ms", "attn_gate_ms", "delta_rule_ms",
        "delta_rule_roofline", "delta_rule_prep_ms", "causal_conv_ms"},
    "sdarmoe-train-s8192": CAPACITY | {
        "moe_ms", "moe_experts_ms", "moe_route_ms", "moe_dispatch_ms",
        "lm_head_ms", "attn_blockdiff_relayout_ms", "denoise_io_ms"}}

#: the interpreter's kernels are no custom calls: a reader of class
#: ``attention_kernel`` finds its scope and no time under it
NO_KERNEL_TIME = {"attn_window_kernel_ms"}
#: 0.0 where no routed layer of the traced steps ran that capacity: which
#: did is the toy router's to say, and one of the two always has
EITHER_CAPACITY = {"moe_worst_case_ms", "moe_fit_ms"}


#: the eight metrics that split ``setup_s`` (``chipbench/setup_phases.py``)
SETUP = {"setup_import_s", "setup_init_s", "setup_trace_s", "setup_lower_s",
         "setup_cache_read_s", "setup_backend_compile_s",
         "setup_cache_hit_share", "setup_unattributed_s"}


@functools.lru_cache(maxsize=None)
def traced(cell):
    """One ``--trace 1`` rehearsal a cell, shared by the tests that read it."""
    return rehearse(cell, 1)


@pytest.mark.parametrize("cell", ["gpt2m-train-s1024",
                                  "granite4hm-train-s4096"])
def test_traced_rehearsal_splits_setup_s_into_its_phases(cell):
    line, out = traced(cell)
    assert line["correct"] is True, out
    values = {k[len("rehearsal_"):]: v["value"]
              for k, v in line["metrics"].items()}
    assert SETUP <= set(values)
    assert all(values[name] >= 0 for name in SETUP)
    seconds = SETUP - {"setup_cache_hit_share"}
    assert {line["metrics"][f"rehearsal_{name}"]["unit"]
            for name in seconds} == {"s"}
    assert 0 <= values["setup_cache_hit_share"] <= 100
    # the program's own phases are under set-up's length, and with what no
    # span covers they are all of it
    setup_s = float(out.split('"setup_s": ')[1].split("}")[0])
    spans = sum(values[name] for name in seconds - {"setup_unattributed_s"})
    assert 0 < spans <= setup_s
    assert spans + values["setup_unattributed_s"] == pytest.approx(setup_s)
    # the package import and the step's trace and lowering are never free
    assert values["setup_import_s"] > 0.05 and values["setup_trace_s"] > 0.05
    assert values["setup_lower_s"] > 0.05
    # the reference check compiles its two programs after the window: what
    # compiled in set-up is inside the first calls the harness timed there
    # (eager operations between them aside)
    compiled = sum(values[name] for name in (
        "setup_trace_s", "setup_lower_s", "setup_cache_read_s",
        "setup_backend_compile_s"))
    assert compiled < values["compile_s"] + 1.0


@pytest.mark.parametrize("cell", HYBRID_CELLS)
def test_hybrid_cell_rehearsal_prints_the_end_to_end_line(cell):
    line, out = rehearse(cell, 0)
    assert set(line) == KEYS and line["correct"] is True, out
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"rehearsal_train_tokens_per_s_chip",
                                    "rehearsal_setup_s"}
    assert "reference check" in out


@pytest.mark.parametrize("cell", HYBRID_CELLS)
def test_hybrid_cell_rehearsal_reads_every_per_layer_metric_it_lists(cell):
    line, out = traced(cell)
    assert line["correct"] is True, out
    declared = {m["name"] for m in
                harness.declared_metrics(cell)["per_layer"]}
    got = {k[len("rehearsal_"):] for k in line["metrics"]}
    # the CPU backend has no memory statistics, and the interpreter's
    # kernels are no custom calls, so their roofline share has no time
    assert declared - got <= {"peak_hbm_gib", "flash_attention_roofline",
                              "attn_window_roofline"}
    assert got <= declared and HYBRID_CELLS[cell] <= got
    values = {k[len("rehearsal_"):]: v["value"]
              for k, v in line["metrics"].items()}
    assert values["blocks_recompute_ms"] > 0
    assert all(values[name] > 0 for name in HYBRID_CELLS[cell]
               - NO_KERNEL_TIME - EITHER_CAPACITY)
    assert all(values[name] == 0 for name in HYBRID_CELLS[cell]
               & NO_KERNEL_TIME)
    if "ssd_ms" in HYBRID_CELLS[cell]:
        # the scan's scope is its own class: its time is not the blocks'
        assert 0 < values["ssd_ms"] < values["xla_ops_ms"]
    if "moe_ms" in HYBRID_CELLS[cell]:
        # overlays: the routed feed-forward's time stays in the blocks'
        assert 0 < values["moe_experts_ms"] <= values["moe_ms"] \
            < values["xla_ops_ms"]
        # ``dispatch`` and ``experts`` are scopes beside each other
        assert values["moe_dispatch_ms"] + values["moe_experts_ms"] \
            < values["moe_ms"]
        # the expert stage by the capacity that ran, and the router beside
        # it: parts of the layer, the stage's never both empty
        assert min(values[name] for name in EITHER_CAPACITY) >= 0
        assert values["moe_experts_ms"] <= values["moe_worst_case_ms"] \
            + values["moe_fit_ms"] < values["moe_ms"] - values["moe_router_ms"]
        if "moe_route_ms" in HYBRID_CELLS[cell]:
            # what is under ``moe`` and not under ``experts``; the latent's
            # and the shared expert's products lie beside ``moe``, the
            # untied head outside every block
            assert values["moe_route_ms"] == pytest.approx(
                values["moe_ms"] - values["moe_experts_ms"])
            for name in {"moe_shared_ms", "moe_latent_ms", "lm_head_ms",
                         "attn_gate_ms"} & HYBRID_CELLS[cell]:
                assert values[name] < values["xla_ops_ms"], name
        parts = ("blocks_fwd_ms", "blocks_bwd_ms", "blocks_recompute_ms",
                 "head_loss_ms", "optimizer_ms", "model_other_ms")
        assert sum(values[k] for k in parts) == pytest.approx(
            values["xla_ops_ms"], rel=1e-9)
