"""Device time of everything no op class claims (``xla_op``): blocks, loss
and optimizer, one number until the program scopes them."""

NAME = "xla_ops_ms"
UNIT = "ms/step"
LAYER = "model + loss + optimizer"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    t = window.trace
    return t and t.ms_per_unit("class_s", "xla_op")
