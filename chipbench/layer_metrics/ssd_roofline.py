"""The least time the chip could take over a step's state-space scans
(forward and backward, the family's ``ssd_train_costs`` for the per-chip
shapes: one ``ssd_cost.ssd_train_cost`` for each Mamba-2 layer, the
recurrence's operations and one pass over its operands) over ``ssd_ms``.
It prices a kernel for the scan against the dual form the program runs. A
family without ``ssd_train_costs`` has no such share."""

import math

from .. import flops
from . import ssd_ms

NAME = "ssd_roofline"
UNIT = "%"
LAYER = ssd_ms.LAYER
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    costs = getattr(window.cell.family, "ssd_train_costs", None)
    took_ms = ssd_ms.read(window)
    if not took_ms or costs is None:
        return None
    m = window.measured
    least = math.fsum(
        flops.roofline_seconds(cost, window.peak)["seconds"]
        for cost in costs(window.cell.config, m["per_chip_batch"], m["seq"]))
    return 100.0 * least / (took_ms * 1e-3)
