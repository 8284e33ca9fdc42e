"""The set-up's ``compile/cache_read`` spans: reading and deserialising the
executables the persistent cache held."""

from .. import setup_phases

NAME = "setup_cache_read_s"
UNIT = "s"
LAYER = "compile cache"
MOVES = "setup_s"
JOBS = ("train_lm", "serve_lm")


def read(window):
    return setup_phases.read(window, NAME)
