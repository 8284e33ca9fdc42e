"""Device time of class ``xla_op`` under scope class ``head_loss``: the tied
head (``tok_emb.attend``) and the loss (``loss``), forward and backward,
a step: the logits chain."""

from .. import op_scopes

NAME = "head_loss_ms"
UNIT = "ms/step"
LAYER = "model + loss + optimizer"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    return op_scopes.ms(window, "xla_op", "head_loss")
