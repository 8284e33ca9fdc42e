"""The least time the chip could take over a step's window-attention
layers (forward and backward, the family's ``window_train_costs``: one
``window_attention_cost.window_attention_train_cost`` for each, the band's
scores and not the triangle's) over ``attn_window_kernel_ms``. A family
without ``window_train_costs`` has no such share."""

import math

from .. import flops
from . import attn_window_kernel_ms

NAME = "attn_window_roofline"
UNIT = "%"
LAYER = attn_window_kernel_ms.LAYER
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    costs = getattr(window.cell.family, "window_train_costs", None)
    took_ms = attn_window_kernel_ms.read(window)
    if not took_ms or costs is None:
        return None
    m = window.measured
    least = math.fsum(
        flops.roofline_seconds(cost, window.peak)["seconds"]
        for cost in costs(window.cell.config, m["per_chip_batch"], m["seq"]))
    return 100.0 * least / (took_ms * 1e-3)
