"""Device time of class ``attention_kernel`` under scope class ``attn_bwd``
(``flash_bwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``), a step."""

from .. import op_scopes

NAME = "attn_bwd_kernel_ms"
UNIT = "ms/step"
LAYER = "attention kernels"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    return op_scopes.ms(window, "attention_kernel", "attn_bwd")
