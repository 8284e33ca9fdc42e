"""Device time of every operation under ``mixer/gate``
(``models/hybrid.AttentionMixer``: the projection behind the sigmoid gate
on each head's output, with what XLA fuses into it), a step, in any pass.
An overlay (``scope_paths``). Nothing to read in a model whose attention
has no gate."""

from .. import scope_paths

NAME = "attn_gate_ms"
UNIT = "ms/step"
LAYER = "model + loss + optimizer"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = r"/mixer/gate([/)]|$)"


def read(window):
    return scope_paths.ms_under(window, PATTERN)
