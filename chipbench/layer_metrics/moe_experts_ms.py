"""Device time of every operation under the scope ``experts`` inside
``moe`` (``block_<i>/ffn/moe/cond/branch_<n>_fun/experts``, bare or wrapped
as ``jvp(experts)`` / ``transpose(jvp(experts))``: the two grouped
matrix products of a routed feed-forward and the SwiGLU between them,
forward, backward and recomputed), a step. XLA's TPU compiler turns
``jax.lax.ragged_dot`` into kernels of its own whose ``op_name`` is
``ragged-dot-none`` / ``ragged-dot-metadata`` with no scope path left:
they are nothing but this layer's products, and are read by that name."""

from .. import scope_paths

NAME = "moe_experts_ms"
UNIT = "ms/step"
LAYER = "routed feed-forward (ops/moe.routed_ffn)"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
#: what the compiler calls the grouped products it makes
COMPILER_NAMES = r"^ragged-dot-[a-z]+$"
PATTERN = r"[/(]moe/.*[/(]experts([/)]|$)|" + COMPILER_NAMES


def read(window):
    return scope_paths.ms_under(window, PATTERN)
