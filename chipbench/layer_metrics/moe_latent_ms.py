"""Device time of every operation under ``ffn/latent_in`` or
``ffn/latent_out`` (``models/hybrid.RoutedFeedForward``: the projection of
the block's input down to the experts' latent and of their weighted sum
back up), a step, in any pass. An overlay (``scope_paths``). Nothing to
read in a model whose experts read the block's input itself."""

from .. import scope_paths

NAME = "moe_latent_ms"
UNIT = "ms/step"
LAYER = "routed feed-forward (ops/moe.routed_ffn)"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = r"/ffn/latent_(in|out)([/)]|$)"


def read(window):
    return scope_paths.ms_under(window, PATTERN)
