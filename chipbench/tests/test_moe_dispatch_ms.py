"""``moe_dispatch_ms``: which scope paths of a routed layer it reads (those
the rehearsal's trace has), what it sums, and that a program without such
a layer leaves it out."""

import re
import types

import pytest

from chipbench import scope_paths
from chipbench.layer_metrics import moe_dispatch_ms, moe_experts_ms, moe_ms
from chipbench.tests.test_op_scopes import SCOPED, window_on

F = "jit(step)/jvp(HybridLM)/block_3/ffn/moe"
R = ("jit(step)/transpose(jvp(HybridLM))/jvp(HybridLM)/checkpoint/"
     "rematted_computation/block_3/ffn/moe")
T = "jit(step)/transpose(jvp(HybridLM))/jvp(HybridLM)/checkpoint/block_3/ffn/moe"

PATHS = [
    # the assignments' order, forward and recomputed
    (f"{F}/dispatch/jit(argsort)/sort", True),
    (f"{R}/dispatch/reduce_sum", True),
    (f"{F}/dispatch", True),
    # inside the branch of a row capacity, every pass
    (f"{F}/cond/branch_0_fun/dispatch/gather", True),
    (f"{T}/cond/branch_1_fun/dispatch/jit(_tgmm)/moe_tgmm/moe_tgmm/"
     "pallas_call", True),
    # wrapped by autodiff
    (f"{T}/cond/branch_0_fun/transpose(jvp(dispatch))/gather", True),
    ("jit(step)/jvp(HybridLM)/block_3/ffn/jvp(moe)/dispatch/argsort", True),
    # the stage's other scopes, the layer's neighbours, look-alikes
    (f"{F}/router/top_k", False),
    (f"{T}/cond/branch_0_fun/combine/gather", False),
    (f"{F}/cond/branch_0_fun/experts/jit(_gmm)/moe_gmm/moe_gmm/pallas_call",
     False),
    (f"{F}/cond", False),
    ("jit(step)/jvp(HybridLM)/block_3/ffn/shared_in/dot_general", False),
    ("jit(step)/jvp(HybridLM)/block_3/dispatch/gather", False),
    ("jit(step)/jvp(HybridLM)/block_3/ffn/moe_dispatch/gather", False),
    (f"{F}/redispatch/gather", False),
    ("", False),
]


@pytest.mark.parametrize("path,read", PATHS)
def test_the_paths_it_reads(path, read):
    assert bool(re.search(moe_dispatch_ms.PATTERN, path)) is read
    if read:   # an overlay inside ``moe_ms``, beside ``moe_experts_ms``
        assert re.search(moe_ms.PATTERN, path)
        assert not re.search(moe_experts_ms.PATTERN, path)


def test_it_sums_the_operations_under_dispatch_a_step(monkeypatch):
    seconds = {f"op.{i}": 0.001 * (i + 1) for i in range(len(PATHS))}
    scopes = {f"op.{i}": path for i, (path, _) in enumerate(PATHS)}
    first = types.SimpleNamespace(device="/device:TPU:0", op_s=seconds)
    window = types.SimpleNamespace(
        trace=types.SimpleNamespace(first=first, units=4))
    monkeypatch.setattr(scope_paths.trace_reduce, "find_xplane",
                        lambda directory: "recorded")
    monkeypatch.setattr(scope_paths.op_scopes, "read",
                        lambda path: {"/device:TPU:0": scopes})
    want = sum(1e3 * seconds[f"op.{i}"] for i, (_, read) in enumerate(PATHS)
               if read) / 4
    assert moe_dispatch_ms.read(window) == pytest.approx(want)
    assert moe_dispatch_ms.read(window) < moe_ms.read(window)


def test_a_model_with_no_routed_layer_leaves_it_out(tmp_path, monkeypatch):
    window = window_on(SCOPED, tmp_path, monkeypatch)
    assert moe_dispatch_ms.read(window) is None
    window.trace = None                               # an untraced run
    assert moe_dispatch_ms.read(window) is None
