"""The least time the chip could take over a step's attention (forward
and backward, the family's ``attention_train_costs`` for the per-chip
shapes: one ``flops.flash_attention_train_cost`` for each attention layer)
over the time the ``attention_kernel`` class took. Which limit binds is in
``flops.roofline_seconds`` (both are within 6% at head 64). A model with no
attention layer has no such share."""

import math

from .. import flops

NAME = "flash_attention_roofline"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    t, costs = window.trace, window.measured["attention_train_costs"]
    took_ms = t and t.ms_per_unit("class_s", "attention_kernel")
    if not took_ms or not costs:
        return None
    # fsum: n equal layers sum to n times one, to the bit
    least = math.fsum(flops.roofline_seconds(cost, window.peak)["seconds"]
                      for cost in costs)
    return 100.0 * least / (took_ms * 1e-3)
