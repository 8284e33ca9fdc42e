"""Device time of op class ``attention_kernel`` on the first chip, a step."""

NAME = "attn_kernel_ms"
UNIT = "ms/step"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    t = window.trace
    return t and t.ms_per_unit("class_s", "attention_kernel")
