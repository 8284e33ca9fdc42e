"""What a routed layer says of itself beside its result (``ops/moe.py``,
docs/moe.md): with ``HOROVOD_MOE_REPORT`` on as the layer is traced, one
host report a layer and traced execution into ``moe.report_load`` (rows
held, load, which row capacity they select), as gauges, a counter, spans of
``metrics/phases`` and the summary ``hvd.shutdown()`` prints; with it off,
the program the layer always was. The scopes that say which capacity ran
are compiled for a v5e in ``tests/test_tpu_lowering.py``.
"""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

import horovod_tpu as hvd
from chipbench import setup_phases
from horovod_tpu import spmd
from horovod_tpu.metrics import instruments, phases
from horovod_tpu.metrics.registry import reset_registry
from horovod_tpu.models.hybrid import HybridLM
from horovod_tpu.models.transformer import lm_loss
from horovod_tpu.ops import moe

LAYERS, SEQ, TOP_K, EXPERTS, HELD, VOCAB = 2, 32, 2, 8, (0, 1), 64
LABELS = [f"block_{i}/ffn" for i in range(LAYERS)]
#: one sequence's 64 assignments: a balanced router sends a quarter here
BALANCED, SIZES = 16.0, (32, 64)


@pytest.fixture(autouse=True)
def fresh():
    reset_registry()
    phases.reset()
    moe.routing_summary(clear=True)
    yield
    reset_registry()
    phases.reset()
    moe.routing_summary(clear=True)


def toy(remat="full"):
    return HybridLM(
        vocab_size=VOCAB, layer_kinds=("attention",) * LAYERS, d_model=32,
        ffn_width=64, attn_heads=2, attn_kv_heads=2, attn_head_dim=16,
        ffn_kinds=("moe",) * LAYERS, moe_experts=EXPERTS, moe_held=HELD,
        moe_top_k=TOP_K, moe_width=32, dtype=jnp.float32, remat=remat)


def toy_params(model, toks, bias=0.0):
    """Fresh parameters; with ``bias`` every layer's selection bias prefers
    the experts held here by that much."""
    params = model.init(jax.random.PRNGKey(1), toks)["params"]
    prefer = jnp.zeros((EXPERTS,), jnp.float32).at[jnp.asarray(HELD)].set(
        bias)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: prefer if "expert_bias" in
        jax.tree_util.keystr(path) else leaf, params)


def layer_operands(n=SEQ, d=32, width=32):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    return (jax.random.normal(keys[0], (n, d)),
            jax.random.normal(keys[1], (d, EXPERTS)), jnp.zeros((EXPERTS,)),
            0.02 * jax.random.normal(keys[2], (len(HELD), d, 2 * width)),
            0.02 * jax.random.normal(keys[3], (len(HELD), width, d)))


def reports(layer):
    """``hvd_moe_reports_total`` of ``layer``, by capacity."""
    return {dict(key)["capacity"]: value for key, value in
            instruments.moe_reports().snapshot_values().items()
            if dict(key)["layer"] == layer}


# ------------------------------------------------------------- the switch
@pytest.mark.parametrize("switch,callbacks", [("", 0), ("0", 0), ("1", 1)])
def test_the_switch_decides_whether_the_layer_traces_a_callback(
        switch, callbacks, monkeypatch):
    monkeypatch.setenv("HOROVOD_MOE_REPORT", switch)

    def loss(*operands):
        return jnp.sum(moe.routed_ffn(*operands, held=HELD, top_k=TOP_K,
                                      label="a/layer")[0] ** 2)

    for fn in (loss, jax.grad(loss, argnums=(0, 1, 3, 4))):
        text = str(jax.make_jaxpr(fn)(*layer_operands()))
        assert text.count("debug_callback[") == callbacks
        assert callbacks or "callback" not in text
    if not callbacks:   # nothing registered either: a run says what it said
        assert moe.routing_summary() == []
        assert instruments.moe_reports().snapshot_values() == {}


def test_a_stage_of_one_size_runs_under_capacity_all_without_a_cond():
    held = tuple(range(EXPERTS))
    h, router, bias, w_in, w_out = layer_operands()
    w_in, w_out = (jnp.tile(w[:1], (EXPERTS, 1, 1)) for w in (w_in, w_out))
    assert len(moe.capacities(SEQ * TOP_K, EXPERTS, EXPERTS)) == 1
    text = jax.jit(lambda *operands: moe.routed_ffn(
        *operands, held=held, top_k=TOP_K)[0]).lower(
            h, router, bias, w_in, w_out).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*/moe/[^"]*)"', text))
    stage = {p for p in paths if re.search(r"/(experts|combine)(/|$)", p)}
    assert stage and all("/moe/capacity_all/" in p for p in stage)
    assert not any("/cond/" in p or "capacity_fit" in p for p in paths)


# ------------------------------------------------------- inside the step
@pytest.mark.parametrize("bias,capacity", [(0.0, "fit"), (10.0, "all")])
def test_a_recomputed_step_reports_twice_a_layer(bias, capacity, monkeypatch):
    """Two layers under ``remat="full"`` through ``spmd.make_train_step``
    on one CPU device: the forward pass and its recomputation report, so
    two reports a layer a step, with the rows ``dispatch`` counts for the
    same parameters; a selection bias towards the experts held sends every
    assignment here and the reports cross to ``capacity_all``."""
    model = toy()
    toks = jax.random.randint(jax.random.PRNGKey(0), (1, SEQ), 0, VOCAB)
    params = toy_params(model, toks, bias)
    assert moe.capacities(SEQ * TOP_K, len(HELD), EXPERTS) == SIZES
    sown = model.apply({"params": params}, toks,
                       mutable=["intermediates"])[1]["intermediates"]
    want = {label: int(jnp.sum(moe.dispatch(
        sown[label.split("/")[0]]["ffn"]["chosen"][0].reshape(-1, TOP_K),
        HELD)[2])) for label in LABELS}
    assert all((rows > SIZES[0]) == (capacity == "all")
               for rows in want.values()), want

    monkeypatch.setenv("HOROVOD_MOE_REPORT", "1")
    phases.install_jax_listeners()      # the step's compile/* spans beside
    mesh = Mesh(np.array(jax.devices()[:1]), ("hvd",))
    tx = optax.sgd(1e-3)
    step = spmd.make_train_step(
        lambda p, b: lm_loss(model.apply({"params": p}, b[0]), b[1]), tx,
        mesh=mesh, donate=False)
    p, o = spmd.replicate(params, mesh), spmd.replicate(tx.init(params), mesh)
    batch = spmd.shard_batch((toks, toks), mesh)
    started = time.perf_counter()
    for done in (1, 2):
        p, o, loss = step(p, o, batch)
        jax.block_until_ready(loss)
        jax.effects_barrier()
        for label in LABELS:
            assert reports(label) == {capacity: 2.0 * done}
        if done == 1:   # the parameters the direct count was made with
            for label in LABELS:
                assert instruments.moe_rows().labels(layer=label).value \
                    == want[label]
                assert instruments.moe_rows_over_balanced().labels(
                    layer=label).value == want[label] / BALANCED
    assert instruments.moe_load_imbalance().value >= 1.0
    assert sum(instruments.expert_load().snapshot_values().values()) \
        == SEQ * TOP_K

    # each report is a span of the set-up's store, the layer its program
    spans = [s for s in phases.spans() if s.name == "moe/report"]
    assert sorted(s.program for s in spans) == sorted(2 * 2 * LABELS)
    assert all(started < s.start <= s.end for s in spans)
    # ...which the benchmark's split of ``setup_s`` does not read
    setup_s = time.perf_counter() - started
    assert setup_phases.split(phases.spans(), started, setup_s)[
        "setup_trace_s"] > 0
    assert setup_phases.split(phases.spans(), started, setup_s) \
        == setup_phases.split([s for s in phases.spans() if s not in spans],
                              started, setup_s)

    lines = moe.routing_summary()
    assert len(lines) == LAYERS
    for label, line in zip(LABELS, lines):
        assert line.startswith(f"moe report {label}: 4 reports, ")
        assert f"capacities {SIZES}" in line
        assert ("100.0%" if capacity == "all" else " 0.0%") \
            + " at capacity_all" in line


# ------------------------------------------------------------- the sink
@pytest.mark.parametrize("rows,sizes,capacity", [
    (32, SIZES, "fit"), (33, SIZES, "all"), (0, SIZES, "fit"),
    (64, SIZES, "all"), (5, (64,), "all"),
    # a third capacity between the two: the name is not the index
    (33, (32, 48, 64), "fit"), (49, (32, 48, 64), "all")])
def test_a_report_is_counted_by_the_capacity_its_rows_select(rows, sizes,
                                                             capacity):
    load = np.array([rows, 0, 3, 1, 0, 0, 0, 0])
    picked = jax.jit(lambda: moe._smallest_that_holds(
        sizes, jnp.asarray([rows], jnp.int32), lambda size: size))()
    assert (int(picked) == sizes[-1]) == (capacity == "all")
    moe.report_load(load, HELD, rows, layer="x", sizes=sizes,
                    balanced=BALANCED)
    assert reports("x") == {capacity: 1.0}
    assert instruments.moe_rows().labels(layer="x").value == rows
    assert instruments.moe_rows_over_balanced().labels(layer="x").value \
        == rows / BALANCED


def test_a_sown_load_alone_sets_the_two_gauges_and_no_layers_series():
    load = np.array([6, 2, 3, 1, 0, 0, 0, 4])
    assert moe.report_load(load, HELD) == pytest.approx(6 / 4)
    assert instruments.moe_load_imbalance().value == pytest.approx(1.5)
    assert instruments.expert_load().labels(expert="7").value == 4
    assert instruments.moe_reports().snapshot_values() == {}
    assert moe.routing_summary() == []
    assert [s.name for s in phases.spans()] == ["moe/report"]


def test_shutdown_prints_the_summary_once_and_forgets_it(capsys):
    hvd.init()
    for rows, load in ((12, [8, 4, 0, 0, 0, 0, 0, 52]),
                       (40, [30, 10, 0, 0, 0, 0, 0, 24]),
                       (20, [10, 10, 0, 0, 0, 0, 0, 44])):
        moe.report_load(np.array(load), HELD, rows, layer="block_0/ffn",
                        sizes=SIZES, balanced=BALANCED)
    moe.report_load(np.array([1, 1, 0, 0, 0, 0, 0, 62]), HELD, 2,
                    layer="block_1/ffn", sizes=SIZES, balanced=BALANCED)
    want = [
        "moe report block_0/ffn: 3 reports, rows min / median / max "
        "12 / 20 / 40, 0.75 / 1.25 / 2.50 x the balanced 16, capacities "
        "(32, 64), 33.3% at capacity_all, worst load max / mean 1.50",
        "moe report block_1/ffn: 1 reports, rows min / median / max "
        "2 / 2 / 2, 0.12 / 0.12 / 0.12 x the balanced 16, capacities "
        "(32, 64), 0.0% at capacity_all, worst load max / mean 1.00"]
    assert moe.routing_summary() == want
    capsys.readouterr()
    hvd.shutdown()
    assert capsys.readouterr().err.splitlines() == want
    assert moe.routing_summary() == []
    hvd.init()
    hvd.shutdown()          # nothing reported since: nothing said
    assert capsys.readouterr().err == ""
