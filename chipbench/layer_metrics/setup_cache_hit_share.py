"""Persistent-cache hits over lookups among the set-up's programs. Under
100% even warm: a program under JAX's compile-time threshold is compiled
every run and never written."""

from .. import setup_phases

NAME = "setup_cache_hit_share"
UNIT = "%"
LAYER = "compile cache"
MOVES = "setup_s"
JOBS = ("train_lm", "serve_lm")


def read(window):
    return setup_phases.read(window, NAME)
