"""The least time the chip could take over a step's attention (forward
and backward, ``flops.flash_attention_train_cost`` for the per-chip shapes,
every layer) over the time the ``attention_kernel`` class took. Which limit
binds is in ``flops.roofline_seconds`` (both are within 6% at head 64)."""

from .. import flops

NAME = "flash_attention_roofline"
UNIT = "%"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    t, m, c = window.trace, window.measured, window.cell.config
    took_ms = t and t.ms_per_unit("class_s", "attention_kernel")
    if not took_ms:
        return None
    cost = flops.flash_attention_train_cost(
        m["per_chip_batch"], c["n_head"], m["seq"], c["n_embd"] // c["n_head"])
    least = c["n_layer"] * flops.roofline_seconds(cost, window.peak)["seconds"]
    return 100.0 * least / (took_ms * 1e-3)
