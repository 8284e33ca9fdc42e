"""``causal_conv_ms``: which scope paths of a mixer's conv it reads (the
jnp form's fusions and the kernel pair's calls, in every pass), what it
sums, and that a program without such a layer leaves it out."""

import re
import types

import pytest

from chipbench import scope_paths
from chipbench.layer_metrics import (causal_conv_ms, delta_rule_prep_ms,
                                     short_conv_ms)
from chipbench.tests.test_op_scopes import SCOPED, window_on

F = "jit(step)/jvp(HybridLM)/block_3/mixer"
R = ("jit(step)/transpose(jvp(HybridLM))/jvp(HybridLM)/checkpoint/"
     "rematted_computation/block_3/mixer")
T = "jit(step)/transpose(jvp(HybridLM))/jvp(HybridLM)/checkpoint/block_3/mixer"

PATHS = [
    # a Mamba-2 layer's conv: forward, recomputed, autodiff's backward
    (f"{F}/conv/mul", True),
    (f"{R}/conv/jit(_pad)/pad", True),
    (f"{T}/conv/reduce_sum", True),
    (f"{F}/conv", True),
    # the kernel pair, under the call site's name stack in every pass
    (f"{F}/conv/jit(_conv_fwd)/conv_fwd/conv_fwd/pallas_call", True),
    (f"{R}/conv/jit(_conv_fwd)/conv_fwd/conv_fwd/pallas_call", True),
    (f"{T}/conv/jit(_conv_bwd)/conv_bwd/conv_bwd/pallas_call", True),
    # a gated delta-rule layer's, inside ``prep``
    (f"{F}/prep/conv/jit(_conv_fwd)/conv_fwd/conv_fwd/pallas_call", True),
    (f"{T}/prep/conv/jit(_conv_bwd)/conv_bwd", True),
    # look-alikes: the short conv (``short_conv_ms``), the projections, a
    # cast, the rest of ``prep``, a conv that is no mixer's
    (f"{F}/short_conv/jit(_conv_fwd)/conv_fwd/conv_fwd/pallas_call", False),
    (f"{T}/short_conv/mul", False),
    (f"{F}/in_proj/dot_general", False),
    (f"{F}/convert_element_type", False),
    (f"{F}/prep/convert_element_type", False),
    (f"{F}/prep/mul", False),
    (f"{F}/conv_norm/mul", False),
    ("jit(step)/jvp(HybridLM)/block_3/conv/mul", False),
    ("", False),
]


@pytest.mark.parametrize("path,read", PATHS)
def test_the_paths_it_reads(path, read):
    assert bool(re.search(causal_conv_ms.PATTERN, path)) is read
    if read:   # never the short conv's; inside ``prep`` where it is there
        assert not re.search(short_conv_ms.PATTERN, path)
        assert bool(re.search(delta_rule_prep_ms.PATTERN, path)) \
            is ("/prep/" in path)


def test_it_sums_the_operations_under_conv_a_step(monkeypatch):
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
    assert causal_conv_ms.read(window) == pytest.approx(want)


def test_a_model_with_no_conv_leaves_it_out(tmp_path, monkeypatch):
    window = window_on(SCOPED, tmp_path, monkeypatch)
    assert causal_conv_ms.read(window) is None
    window.trace = None                               # an untraced run
    assert causal_conv_ms.read(window) is None
