"""Device time of every operation under ``denoise_io``
(``models/hybrid.HybridLM`` built to denoise by blocks: laying the noised
and the clean ids side by side before the table, and cutting the noised
half out of the stream before the final norm, with what XLA fuses into
them), a step, forward, recomputed and backward. An overlay
(``scope_paths``). Nothing to read in a model trained by next-token
prediction."""

from .. import scope_paths

NAME = "denoise_io_ms"
UNIT = "ms/step"
LAYER = "model + loss + optimizer"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = r"/denoise_io([/)]|$)"


def read(window):
    return scope_paths.ms_under(window, PATTERN)
