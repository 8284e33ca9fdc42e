"""Device time of class ``xla_op`` that the five metrics of a named part
do not read: the embedding lookup and its gradient, ``ln_f``, the glue of
the step, and operations with no scope at all. With those five it sums to
``xla_ops_ms``."""

from .. import op_scopes

NAME = "model_other_ms"
UNIT = "ms/step"
LAYER = "model + loss + optimizer"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
NAMED = ("blocks_fwd", "blocks_bwd", "blocks_recompute", "head_loss",
         "optimizer")


def read(window):
    by = op_scopes.scope_ms(window)
    if not by:
        return None
    return sum(ms for (op_class, scope), ms in by.items()
               if op_class == "xla_op" and scope not in NAMED)
