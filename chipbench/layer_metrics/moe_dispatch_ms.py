"""Device time of every operation under a scope ``dispatch`` anywhere
inside ``moe`` (``ops/moe.routed_ffn``: ``block_<i>/ffn/moe/dispatch``,
where the assignments are ordered by expert, and
``block_<i>/ffn/moe/cond/branch_<n>_fun/dispatch``, where the rows held
here are found, gathered and, backward, summed back into their tokens; bare
or wrapped as ``jvp(dispatch)`` / ``transpose(jvp(dispatch))``), forward,
backward and recomputed, a step. The part of ``moe_route_ms`` that builds
indices and moves rows on the way in; the router and ``combine`` are its
rest. An overlay (``scope_paths``), as ``moe_experts_ms`` is. Nothing to
read in a model with no such layer."""

from .. import scope_paths
from . import moe_ms

NAME = "moe_dispatch_ms"
UNIT = "ms/step"
LAYER = moe_ms.LAYER
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = r"[/(]moe\)?/(.*[/(])?dispatch([/)]|$)"


def read(window):
    return scope_paths.ms_under(window, PATTERN)
