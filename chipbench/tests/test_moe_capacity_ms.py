"""``moe_worst_case_ms``, ``moe_fit_ms`` and ``moe_router_ms``: which scope
paths of a routed layer each reads, what it sums, the null rule of the two
capacity readers (None where the trace has neither ``capacity_*`` scope,
0.0 where only the other one ran), and that the six older ``moe_*``
readers see the same operations through the extra path element."""

import re
import types

import pytest

from chipbench import scope_paths
from chipbench.layer_metrics import (moe_dispatch_ms, moe_experts_ms,
                                     moe_fit_ms, moe_ms, moe_router_ms,
                                     moe_worst_case_ms)
from chipbench.tests.test_op_scopes import SCOPED, window_on

F = "jit(step)/jvp(HybridLM)/block_3/ffn/moe"
R = ("jit(step)/transpose(jvp(HybridLM))/jvp(HybridLM)/checkpoint/"
     "rematted_computation/block_3/ffn/moe")
T = "jit(step)/transpose(jvp(HybridLM))/jvp(HybridLM)/checkpoint/block_3/ffn/moe"

# path -> which of (worst case, fit, router) reads it
PATHS = [
    # a branch of the row capacity, in every pass
    (f"{F}/cond/branch_1_fun/capacity_all/dispatch/gather", "all"),
    (f"{R}/cond/branch_1_fun/capacity_all/experts/jit(_gmm)/moe_gmm/moe_gmm/"
     "pallas_call", "all"),
    (f"{T}/cond/branch_1_fun/capacity_all/combine/scatter", "all"),
    (f"{F}/cond/branch_0_fun/capacity_fit/experts/jit(silu)/mul", "fit"),
    (f"{T}/cond/branch_0_fun/capacity_fit/dispatch/jit(_tgmm)/moe_tgmm/"
     "moe_tgmm/pallas_call", "fit"),
    (f"{R}/cond/branch_0_fun/capacity_fit", "fit"),
    # the scope says what the branch is, not where it stands: a third size
    (f"{F}/cond/branch_1_fun/capacity_fit/experts/dot_general", "fit"),
    (f"{F}/cond/branch_2_fun/capacity_all/experts/dot_general", "all"),
    # a stage of one size has no cond; a layer called bare under grad
    (f"{F}/capacity_all/experts/dot_general", "all"),
    ("jit(loss)/jvp(moe)/cond/branch_0_fun/capacity_fit/tanh", "fit"),
    ("jit(loss)/transpose(jvp(moe))/cond/branch_1_fun/capacity_all/mul",
     "all"),
    # the router, in every pass
    (f"{F}/router/dot_general", "router"),
    (f"{R}/router/jit(_one_hot)/eq", "router"),
    (f"{T}/router", "router"),
    ("jit(loss)/jvp(moe)/router/top_k", "router"),
    # the stage's other operations, the layer's neighbours, look-alikes
    (f"{F}/dispatch/jit(argsort)/sort", None),
    (f"{F}/cond", None),
    (f"{F}", None),
    (f"{F}/cond/branch_0_fun/dispatch/gather", None),       # a parent's path
    ("jit(step)/jvp(HybridLM)/block_3/ffn/shared_in/dot_general", None),
    ("jit(step)/jvp(HybridLM)/block_3/capacity_all/experts/mul", None),
    ("jit(step)/jvp(HybridLM)/block_3/mixer/router/dot_general", None),
    (f"{F}/cond/branch_0_fun/capacity_fitted/experts/mul", None),
    (f"{F}/router_bias/add", None),
    (f"{F}/cond/branch_0_fun/capacity_fit/experts/router", "fit"),
    ("ragged-dot-none", None),
    ("", None),
]
READERS = {"all": moe_worst_case_ms, "fit": moe_fit_ms,
           "router": moe_router_ms}


@pytest.mark.parametrize("path,reader", PATHS)
def test_the_paths_each_reads(path, reader):
    for name, module in READERS.items():
        assert bool(re.search(module.PATTERN, path)) is (name == reader), name
    if reader:   # overlays inside ``moe_ms``
        assert re.search(moe_ms.PATTERN, path)


@pytest.mark.parametrize("older,inside", [
    (f"{F}/cond/branch_0_fun/dispatch/gather", "capacity_fit"),
    (f"{T}/cond/branch_1_fun/dispatch/jit(_tgmm)/moe_tgmm/moe_tgmm/"
     "pallas_call", "capacity_all"),
    (f"{T}/cond/branch_0_fun/transpose(jvp(dispatch))/gather",
     "capacity_fit"),
    (f"{T}/cond/branch_0_fun/combine/gather", "capacity_fit"),
    (f"{F}/cond/branch_0_fun/experts/jit(_gmm)/moe_gmm/moe_gmm/pallas_call",
     "capacity_all"),
    (f"{R}/cond/branch_1_fun/experts/jit(silu)/logistic", "capacity_all"),
])
def test_the_older_readers_read_a_path_the_same_under_the_new_scope(older,
                                                                    inside):
    """A path as the parent wrote it and as this program writes it, the
    capacity's scope after ``branch_<n>_fun``: ``moe_ms``,
    ``moe_experts_ms`` and ``moe_dispatch_ms`` class both alike."""
    newer = re.sub(r"(branch_\d_fun)/", rf"\1/{inside}/", older)
    assert newer != older and inside in newer
    for module in (moe_ms, moe_experts_ms, moe_dispatch_ms):
        assert bool(re.search(module.PATTERN, newer)) \
            is bool(re.search(module.PATTERN, older)), module.NAME


def window_of(paths, monkeypatch, units=4):
    """A window whose trace holds one operation a path, ``op.<i>`` taking
    ``i + 1`` ms."""
    seconds = {f"op.{i}": 0.001 * (i + 1) for i in range(len(paths))}
    scopes = {f"op.{i}": path for i, path in enumerate(paths)}
    first = types.SimpleNamespace(device="/device:TPU:0", op_s=seconds)
    monkeypatch.setattr(scope_paths.trace_reduce, "find_xplane",
                        lambda directory: "recorded")
    monkeypatch.setattr(scope_paths.op_scopes, "read",
                        lambda path: {"/device:TPU:0": scopes})
    return types.SimpleNamespace(
        trace=types.SimpleNamespace(first=first, units=units))


def test_each_sums_its_operations_a_step(monkeypatch):
    window = window_of([path for path, _ in PATHS], monkeypatch)
    for name, module in READERS.items():
        want = sum(i + 1.0 for i, (_, reader) in enumerate(PATHS)
                   if reader == name) / 4
        assert module.read(window) == pytest.approx(want), name
    # beside each other inside the layer
    assert sum(module.read(window) for module in READERS.values()) \
        < moe_ms.read(window)


@pytest.mark.parametrize("ran,worst,fit", [
    (("capacity_fit",), 0.0, 1.0),        # a healthy cell: no layer crossed
    (("capacity_all",), 1.0, 0.0),        # every layer in the worst case
    (("capacity_fit", "capacity_all"), 2.0, 1.0),
    ((), None, None),                     # a parent without the scopes
])
def test_the_null_rule(ran, worst, fit, monkeypatch):
    paths = [f"{F}/cond/branch_{i}_fun/{scope}/experts/dot_general"
             for i, scope in enumerate(ran)]
    window = window_of(
        paths + [f"{F}/cond/branch_0_fun/experts/dot_general",
                 f"{F}/router/top_k"], monkeypatch, units=1)
    got = moe_worst_case_ms.read(window), moe_fit_ms.read(window)
    assert got == (pytest.approx(worst), pytest.approx(fit))
    assert [type(value) for value in got] \
        == [type(worst), type(fit)]       # 0.0 is a reading, None is none
    assert moe_router_ms.read(window) > 0


def test_a_model_with_no_routed_layer_leaves_them_out(tmp_path, monkeypatch):
    window = window_on(SCOPED, tmp_path, monkeypatch)
    assert [module.read(window) for module in READERS.values()] == [None] * 3
    window.trace = None                               # an untraced run
    assert [module.read(window) for module in READERS.values()] == [None] * 3
