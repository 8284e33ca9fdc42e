"""Device time of op class ``attention_kernel`` whose scope path lies under
``mixer/window`` (``models/hybrid.AttentionMixer``: the flash call of a
layer that sees a window of positions), a step, forward and backward. An
overlay (the same time is in ``attn_kernel_ms``). Nothing to read in a
model with no window layer."""

import re

from .. import harness, op_scopes, trace_reduce

NAME = "attn_window_kernel_ms"
UNIT = "ms/step"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = re.compile(r"/mixer/window([/)]|$)")


def read(window):
    if window.trace is None:
        return None
    first = window.trace.first
    scopes = op_scopes.read(
        trace_reduce.find_xplane(harness.TRACE_DIR)).get(first.device)
    under = [op for op in first.op_s
             if PATTERN.search((scopes or {}).get(op, ""))]
    if not under:
        return None
    return 1e3 * sum(first.op_s[op] for op in under
                     if first.op_class[op] == "attention_kernel") \
        / window.trace.units
