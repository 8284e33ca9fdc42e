"""Device time of class ``xla_op`` under scope class ``blocks_recompute``: the
blocks' forward pass run again inside the backward pass (remat), a step.
Nothing to read in a cell without recomputation."""

from .. import op_scopes

NAME = "blocks_recompute_ms"
UNIT = "ms/step"
LAYER = "model + loss + optimizer"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    return op_scopes.ms(window, "xla_op", "blocks_recompute")
