"""Device time of every operation under a mixer's ``conv`` module
(``models/hybrid.CausalConv`` around ``ops/ssd.causal_conv1d``:
``block_<i>/mixer/conv`` in a Mamba-2 layer, ``block_<i>/mixer/prep/conv``
in a gated delta-rule layer; the depthwise causal conv, its activation and
what XLA fuses into them), a step, forward, recomputed and backward. An
overlay (``scope_paths``); in the delta-rule layer it lies inside
``delta_rule_prep_ms``. The gated short convolution's is ``short_conv_ms``.
Nothing to read in a model with no such layer."""

from .. import scope_paths

NAME = "causal_conv_ms"
UNIT = "ms/step"
LAYER = "depthwise causal conv (ops/ssd.causal_conv1d)"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = r"/mixer/(prep/)?conv([/)]|$)"


def read(window):
    return scope_paths.ms_under(window, PATTERN)
