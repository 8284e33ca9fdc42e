"""Device time of the routed feed-forward outside its grouped products, a
step: ``moe_ms`` less ``moe_experts_ms``, that is every operation under the
scope ``moe`` (``block_<i>/ffn/moe/``) but not under ``experts``: the
router (the ``[N, E]`` scores, the top-k and the masked sum that picks the
chosen scores), ``dispatch`` (the sort of the ``N k`` assignments, its
inverse, the row gathers and, backward, ``put_rows`` over the ``k`` slots
of every token) and ``combine``, forward, backward and recomputed. What
costs by assignment and not by row held is here: where few of the
assignments land on the experts held (22 of 512 a token, 8 held), it is
most of the layer. An overlay (``scope_paths``), as its two terms. Nothing
to read in a model with no such layer."""

from . import moe_experts_ms, moe_ms

NAME = "moe_route_ms"
UNIT = "ms/step"
LAYER = moe_ms.LAYER
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    whole, experts = moe_ms.read(window), moe_experts_ms.read(window)
    if whole is None:
        return None
    return whole - (experts or 0.0)
