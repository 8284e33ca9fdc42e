"""Device time of every operation, of any op class, under scope class
``ssd`` (``ops/ssd.ssd_chunked``: ``block_<i>/mixer/ssd``), a step: the
state-space scan forward, backward and recomputed. Nothing to read in a
model with no such layer. Its ``xla_op`` part is also inside
``model_other_ms``, whose list of named parts predates this scope."""

from .. import op_scopes

NAME = "ssd_ms"
UNIT = "ms/step"
LAYER = "state-space scan (ops/ssd.ssd_chunked)"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    parts = [ms for (_, scope), ms in op_scopes.scope_ms(window).items()
             if scope == "ssd"]
    return sum(parts) if parts else None
