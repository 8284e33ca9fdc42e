"""Union of the set-up's ``compile/lower`` spans: jaxpr to an MLIR module,
Mosaic kernels among it, before the cache key exists."""

from .. import setup_phases

NAME = "setup_lower_s"
UNIT = "s"
LAYER = "compile cache"
MOVES = "setup_s"
JOBS = ("train_lm", "serve_lm")


def read(window):
    return setup_phases.read(window, NAME)
