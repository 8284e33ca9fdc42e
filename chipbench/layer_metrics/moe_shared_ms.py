"""Device time of every operation under ``ffn/shared_in`` or
``ffn/shared_out`` (``models/hybrid.RoutedFeedForward``: the two products
of the shared expert that every token passes, with the activation XLA fuses
into them), a step, in any pass. An overlay (``scope_paths``). Nothing to
read in a model whose routed layer has no shared expert."""

from .. import scope_paths

NAME = "moe_shared_ms"
UNIT = "ms/step"
LAYER = "routed feed-forward (ops/moe.routed_ffn)"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = r"/ffn/shared_(in|out)([/)]|$)"


def read(window):
    return scope_paths.ms_under(window, PATTERN)
