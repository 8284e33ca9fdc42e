"""Device time of every operation under the scope ``router`` directly
inside ``moe`` (``block_<i>/ffn/moe/router``: the ``[N, E]`` float32 scores
at ``highest`` precision, the top-k, the masked sum that picks the chosen
scores, the weights' renormalisation, the experts' ``load`` and, where the
layer carries one, the auxiliary balancing loss's gradient), forward,
backward and recomputed, a step. It walks every token and every expert
whatever share of them is held here, so it is the part of ``moe_route_ms``
that no row capacity moves; ``moe_dispatch_ms`` and ``combine`` are its
rest. An overlay (``scope_paths``) inside ``moe_ms``. Nothing to read in a
model with no such layer."""

from .. import scope_paths
from . import moe_ms

NAME = "moe_router_ms"
UNIT = "ms/step"
LAYER = moe_ms.LAYER
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = r"[/(]moe\)*/router([/)]|$)"


def read(window):
    return scope_paths.ms_under(window, PATTERN)
