"""Device time of class ``xla_op`` under scope class ``blocks_fwd``: the
transformer blocks (``block_<i>/...``) in the forward pass, a step."""

from .. import op_scopes

NAME = "blocks_fwd_ms"
UNIT = "ms/step"
LAYER = "model + loss + optimizer"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    return op_scopes.ms(window, "xla_op", "blocks_fwd")
