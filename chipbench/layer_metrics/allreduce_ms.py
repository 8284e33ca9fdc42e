"""Time a ``collective`` operation runs or is in flight on the first chip
(the union of the op line's and the asynchronous line's intervals), a
step. Nothing to read on one chip (no collective runs)."""

NAME = "allreduce_ms"
UNIT = "ms/step"
LAYER = "collectives"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    t = window.trace
    if t is None or window.measured["chips"] < 2:
        return None
    return t.ms_per_unit("span_s", "collective")
