"""Device time of class ``attention_kernel`` under scope class ``attn_fwd``
(``flash_fwd``, ``flash_step``), a step; a forward recomputed in the
backward pass counts here."""

from .. import op_scopes

NAME = "attn_fwd_kernel_ms"
UNIT = "ms/step"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    return op_scopes.ms(window, "attention_kernel", "attn_fwd")
