"""Union of the set-up's ``compile/trace`` spans: Python tracing of every
program to a jaxpr (nested ``jax.jit``s counted once)."""

from .. import setup_phases

NAME = "setup_trace_s"
UNIT = "s"
LAYER = "compile cache"
MOVES = "setup_s"
JOBS = ("train_lm", "serve_lm")


def read(window):
    return setup_phases.read(window, NAME)
