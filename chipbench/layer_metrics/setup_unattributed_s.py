"""``setup_s`` less the union of the program's set-up spans: what no span
covers (``chipbench/setup_phases.py`` lists it)."""

from .. import setup_phases

NAME = "setup_unattributed_s"
UNIT = "s"
LAYER = "process start and first executions"
MOVES = "setup_s"
JOBS = ("train_lm", "serve_lm")


def read(window):
    return setup_phases.read(window, NAME)
