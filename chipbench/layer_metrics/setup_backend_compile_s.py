"""Self time of the set-up's ``compile/backend`` spans, the cache read inside
them left out: compiling what the cache did not hold (and the lookup)."""

from .. import setup_phases

NAME = "setup_backend_compile_s"
UNIT = "s"
LAYER = "compile cache"
MOVES = "setup_s"
JOBS = ("train_lm", "serve_lm")


def read(window):
    return setup_phases.read(window, NAME)
