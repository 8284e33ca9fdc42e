"""Device time of every operation under ``lm_head`` (``models/hybrid``: an
untied head is a Dense of that name, not ``tok_emb.attend``, so scope class
``head_loss`` does not see its product and ``head_loss_ms`` holds the loss
alone there), a step: the head's product forward and its two gradients. An
overlay (``scope_paths``): the same time stays in ``model_other_ms``.
Nothing to read in a model whose head is its table."""

from .. import scope_paths

NAME = "lm_head_ms"
UNIT = "ms/step"
LAYER = "model + loss + optimizer"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)
PATTERN = r"(^|[/(])lm_head([/)]|$)"


def read(window):
    return scope_paths.ms_under(window, PATTERN)
