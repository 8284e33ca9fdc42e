"""Device time of every operation under ``mixer/assemble``
(``models/hybrid.LatentAttentionMixer``: the splits of q and of the
up-projection, the one rotary key handed to every head, and the
concatenations into the keys and queries the flash kernels take), a step,
in any pass. An overlay (``scope_paths``). Nothing to read in a model
without latent attention."""

from .. import scope_paths

NAME = "mla_assemble_ms"
UNIT = "ms/step"
LAYER = "model + loss + optimizer"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = r"/mixer/assemble([/)]|$)"


def read(window):
    return scope_paths.ms_under(window, PATTERN)
