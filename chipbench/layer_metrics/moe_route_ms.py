"""Device time of the routed feed-forward outside its grouped products, a
step: ``moe_ms`` less ``moe_experts_ms``, that is every operation under the
scope ``moe`` (``block_<i>/ffn/moe/``) but not under ``experts``: the
router (the ``[N, E]`` scores, the top-k and the masked sum that picks the
chosen scores), ``dispatch`` (the range test that finds the assignments
held here, the one stable sort of the ``N k`` keys, the gathers of the rows
held and, backward, ``put_rows(dX)``: a regather in token order and a
segment sum, ``moe_tgmm``) and ``combine`` (the rows' weights, ``put_rows``
the same way and, backward, ``take_rows(dy)`` and the weights' gradient
placed at the rows' assignments), forward, backward and recomputed. What
walks every assignment is the router and that one sort; the rest costs by
the rows of the capacity that ran. Where few of the assignments land on the
experts held (22 of 512 a token, 8 held), it is most of the layer. An
overlay (``scope_paths``), as its two terms. Nothing to read in a model
with no such layer."""

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
