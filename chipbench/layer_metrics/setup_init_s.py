"""Self time of the program's ``init`` span and its ``init/*`` children:
``hvd.init()``."""

from .. import setup_phases

NAME = "setup_init_s"
UNIT = "s"
LAYER = "runtime init (basics.init)"
MOVES = "setup_s"
JOBS = ("train_lm", "serve_lm")


def read(window):
    return setup_phases.read(window, NAME)
