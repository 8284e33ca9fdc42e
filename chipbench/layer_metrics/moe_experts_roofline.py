"""The least time the chip could take over a step's grouped expert
products (forward and backward, the family's ``moe_train_costs`` for the
per-chip shapes: one ``moe_cost.moe_train_cost`` for each routed layer,
over the rows a balanced router sends here) over ``moe_experts_ms``. A
family without ``moe_train_costs`` has no such share."""

import math

from .. import flops
from . import moe_experts_ms

NAME = "moe_experts_roofline"
UNIT = "%"
LAYER = moe_experts_ms.LAYER
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    costs = getattr(window.cell.family, "moe_train_costs", None)
    took_ms = moe_experts_ms.read(window)
    if not took_ms or costs is None:
        return None
    m = window.measured
    least = math.fsum(
        flops.roofline_seconds(cost, window.peak)["seconds"]
        for cost in costs(window.cell.config, m["per_chip_batch"], m["seq"]))
    return 100.0 * least / (took_ms * 1e-3)
