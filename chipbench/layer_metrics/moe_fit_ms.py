"""Device time of every operation under the scope ``capacity_fit`` inside
``moe`` (``block_<i>/ffn/moe/cond/branch_<n>_fun/capacity_fit/{dispatch,
experts, combine}``), forward, backward and recomputed, a step: what the
routed layers whose rows a size under the worst case held took.
``moe_worst_case_ms`` has the other branch, the sum the two are shares of,
and the null rule (0.0, not None, where every layer of the traced steps
ran the worst case). An overlay (``scope_paths``) inside ``moe_ms``."""

from . import moe_ms, moe_worst_case_ms

NAME = "moe_fit_ms"
UNIT = "ms/step"
LAYER = moe_ms.LAYER
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = moe_worst_case_ms.PATTERNS["fit"]


def read(window):
    return moe_worst_case_ms.ms_at(window, "fit")
