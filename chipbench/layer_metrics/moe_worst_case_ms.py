"""Device time of every operation under the scope ``capacity_all`` inside
``moe`` (``ops/moe._smallest_that_holds``:
``block_<i>/ffn/moe/cond/branch_<n>_fun/capacity_all/{dispatch, experts,
combine}``, and without the ``cond`` where the stage has one size), forward,
backward and recomputed, a step: what the routed layers that ran the
**worst-case program** (every assignment's rows) took, whichever place that
branch has among the sizes. ``moe_fit_ms`` is the same under
``capacity_fit``, any smaller size; over their sum this is the share of the
expert stage's time spent in the worst case, and it moves with what the
router sent the layers in the traced steps, not with the stage's code. An
overlay (``scope_paths``) inside ``moe_ms``.

**Null rule** (a healthy cell may never cross): None only where no
operation of the trace is under either ``capacity_*`` scope (an untraced
run, a program that writes no such scope, a model with no routed layer);
0.0 where the other scope has operations and this one has none."""

from .. import scope_paths
from . import moe_ms

NAME = "moe_worst_case_ms"
UNIT = "ms/step"
LAYER = moe_ms.LAYER
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERNS = {capacity: rf"[/(]moe\)*/(.*[/(])?capacity_{capacity}([/)]|$)"
            for capacity in ("all", "fit")}
PATTERN = PATTERNS["all"]


def ms_at(window, capacity: str):
    """ms a step under ``capacity_<capacity>``, by the null rule above."""
    took = {name: scope_paths.ms_under(window, pattern)
            for name, pattern in PATTERNS.items()}
    if took[capacity] is None and any(t is not None for t in took.values()):
        return 0.0
    return took[capacity]


def read(window):
    return ms_at(window, "all")
