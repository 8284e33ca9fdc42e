"""Self time of the program's ``import`` span: the first statement of
``horovod_tpu/__init__.py`` to its last, the package's own imports."""

from .. import setup_phases

NAME = "setup_import_s"
UNIT = "s"
LAYER = "package import (horovod_tpu/__init__)"
MOVES = "setup_s"
JOBS = ("train_lm", "serve_lm")


def read(window):
    return setup_phases.read(window, NAME)
