"""Device time of every operation under ``mixer/short_conv``
(``models/hybrid.GatedShortConv``: the two gates and the depthwise causal
conv of a short-conv mixer, not its two projections), a step, in any pass.
An overlay (``scope_paths``). Nothing to read in a model with no such
layer."""

from .. import scope_paths

NAME = "short_conv_ms"
UNIT = "ms/step"
LAYER = "gated short convolution (models/hybrid.GatedShortConv)"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = r"/mixer/short_conv([/)]|$)"


def read(window):
    return scope_paths.ms_under(window, PATTERN)
