"""The least time the chip could take over a step's delta rules (forward
and backward, the family's ``gdn_train_costs`` for the per-chip shapes: one
``gated_delta_cost.gated_delta_train_cost`` for each such layer, the
recurrence's operations and one pass over its operands) over
``delta_rule_ms``. It prices a kernel for the rule against the chunked form
the program runs. A family without ``gdn_train_costs`` has no such share."""

import math

from .. import flops
from . import delta_rule_ms

NAME = "delta_rule_roofline"
UNIT = "%"
LAYER = delta_rule_ms.LAYER
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    costs = getattr(window.cell.family, "gdn_train_costs", None)
    took_ms = delta_rule_ms.read(window)
    if not took_ms or costs is None:
        return None
    m = window.measured
    least = math.fsum(
        flops.roofline_seconds(cost, window.peak)["seconds"]
        for cost in costs(window.cell.config, m["per_chip_batch"], m["seq"]))
    return 100.0 * least / (took_ms * 1e-3)
