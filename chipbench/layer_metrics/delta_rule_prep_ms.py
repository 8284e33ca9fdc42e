"""Device time of every operation under ``mixer/prep``
(``models/hybrid.GatedDeltaMixer``: the causal conv and SiLU over ``[q | k
| v]``, the two L2 norms, the write strength, the decay and the key heads
handed to their value heads, with what XLA fuses into them), a step, in any
pass. An overlay (``scope_paths``). Nothing to read in a model with no such
layer."""

from .. import scope_paths
from . import delta_rule_ms

NAME = "delta_rule_prep_ms"
UNIT = "ms/step"
LAYER = delta_rule_ms.LAYER
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = r"/mixer/prep([/)]|$)"


def read(window):
    return scope_paths.ms_under(window, PATTERN)
