"""Device time of class ``xla_op`` under scope class ``optimizer``
(``make_train_step``: ``tx.update`` and ``apply_updates``), a step."""

from .. import op_scopes

NAME = "optimizer_ms"
UNIT = "ms/step"
LAYER = "model + loss + optimizer"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    return op_scopes.ms(window, "xla_op", "optimizer")
