"""Device time of class ``xla_op`` under scope class ``blocks_bwd``: the
transformer blocks under ``transpose(``, the backward pass without what it
recomputes (``blocks_recompute_ms``), a step."""

from .. import op_scopes

NAME = "blocks_bwd_ms"
UNIT = "ms/step"
LAYER = "model + loss + optimizer"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    return op_scopes.ms(window, "xla_op", "blocks_bwd")
