"""Device time under ``mixer/block_diffusion`` (``models/hybrid.
AttentionMixer``: the flash call of a layer that takes the block-diffusion
mask) that is **not** of op class ``attention_kernel``, a step, forward,
recomputed and backward: the relayouts around the flash call, heads-major
copies of q, k, v and the output and of their gradients, which any flash
call at batch 1 pays and XLA cannot fuse into the kernels' reads. The
mask's realisation adds no operation outside its kernels (no merge of
partial softmaxes, no concatenation; its tables of live tiles are
constants), so this is not an overhead of the mask: it is what of the
layer's attention is not the kernels', which ``attn_kernel_ms`` holds.
Nothing to read in a model with no such layer."""

import re

from .. import harness, op_scopes, trace_reduce

NAME = "attn_blockdiff_relayout_ms"
UNIT = "ms/step"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = re.compile(r"/mixer/block_diffusion([/)]|$)")


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
                     if first.op_class[op] != "attention_kernel") \
        / window.trace.units
