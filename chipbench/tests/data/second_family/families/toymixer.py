"""The family of the harness's stand-in second architecture (``"model_type":
"toymixer"``): the six names, for a model with no attention layer."""

import math

REHEARSAL = {"hidden_size": 64, "intermediate_size": 128,
             "num_hidden_layers": 2, "vocab_size": 500}


def build_model(config, vocab_rows, mix):
    from ..toymixer_model import ToyMixerLM

    return ToyMixerLM(vocab_rows, config["hidden_size"],
                      config["intermediate_size"],
                      config["num_hidden_layers"], config["rms_norm_eps"])


def reference_forward(params, tokens, config):
    from .. import toymixer_reference

    return toymixer_reference.forward(params, tokens, config["rms_norm_eps"])


def train_flops_per_token(config, vocab_rows, seq):
    """6 x (a block's up [2d, inner] and down [inner, d] matrices, and the
    head's d V); the running mean is no matmul."""
    d, inner = config["hidden_size"], config["intermediate_size"]
    return 6.0 * (config["num_hidden_layers"] * 3 * d * inner + d * vocab_rows)


def attention_train_costs(config, per_chip_batch, seq):
    return []


def expected_first_loss(config, vocab_rows):
    """Tied head over N(0, 1/d) embeddings on a unit-variance final RMSNorm:
    logits of variance 1."""
    return math.log(vocab_rows) + 0.5
