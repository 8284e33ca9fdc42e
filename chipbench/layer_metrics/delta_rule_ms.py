"""Device time of every operation under ``mixer/delta_rule``
(``ops/gated_delta.gated_delta_chunked``: a chunk's system, its inverse and
products, and the scan over chunks), a step, in any pass. An overlay
(``scope_paths``). Nothing to read in a model with no such layer."""

from .. import scope_paths

NAME = "delta_rule_ms"
UNIT = "ms/step"
LAYER = "gated delta rule (ops/gated_delta.gated_delta_chunked)"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = r"/mixer/delta_rule([/)]|$)"


def read(window):
    return scope_paths.ms_under(window, PATTERN)
