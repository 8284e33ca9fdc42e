"""Device time of every operation, of any op class, under a scope path
holding the scope ``moe`` (``ops/moe.routed_ffn``: ``block_<i>/ffn/moe/``
then ``router``, ``dispatch`` or, inside the ``cond`` that picks the row
capacity, ``dispatch``, ``experts``, ``combine``, bare or wrapped as
``jvp(..)`` / ``transpose(jvp(..))`` by the layer's own backward pass), a
step: the routed feed-forward forward,
backward and recomputed, with XLA's own grouped-product kernels, which
leave the compiler under its names and not the program's
(``moe_experts_ms``). An overlay (``scope_paths``): the same time stays in
the ``blocks_*`` and ``model_other_ms`` parts it is booked to. Nothing to
read in a model with no such layer."""

from .. import scope_paths
from . import moe_experts_ms

NAME = "moe_ms"
UNIT = "ms/step"
LAYER = "routed feed-forward (ops/moe.routed_ffn)"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = r"[/(]moe([/)]|$)|" + moe_experts_ms.COMPILER_NAMES


def read(window):
    return scope_paths.ms_under(window, PATTERN)
