"""Device time of every operation under ``mixer/latent``
(``models/hybrid.LatentAttentionMixer``: the projection to the KV latent
and the shared rotary key, the latent's norm, and the up-projection to
every head's keys and values, with what XLA fuses into them), a step, in
any pass. An overlay (``scope_paths``). Nothing to read in a model without
latent attention."""

from .. import scope_paths

NAME = "mla_latent_ms"
UNIT = "ms/step"
LAYER = "model + loss + optimizer"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = r"/mixer/latent([/)]|$)"


def read(window):
    return scope_paths.ms_under(window, PATTERN)
