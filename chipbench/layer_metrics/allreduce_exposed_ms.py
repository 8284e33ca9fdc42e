"""The part of ``allreduce_ms``'s intervals during which no operation of
another class runs on the first chip, a step."""

NAME = "allreduce_exposed_ms"
UNIT = "ms/step"
LAYER = "collectives"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    t = window.trace
    if t is None or window.measured["chips"] < 2:
        return None
    return t.ms_per_unit("exposed_s", "collective")
