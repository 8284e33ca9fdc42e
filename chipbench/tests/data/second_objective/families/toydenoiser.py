"""The family of the harness's stand-in model of a second objective
(``"model_type": "toydenoiser"``): the six names, for a model that takes two
token arrays and has no attention layer."""

import math

REHEARSAL = {"hidden_size": 64, "intermediate_size": 128,
             "num_hidden_layers": 2, "vocab_size": 500}


def build_model(config, vocab_rows, mix):
    from ..toydenoiser_model import ToyDenoiserLM

    return ToyDenoiserLM(vocab_rows, config["hidden_size"],
                         config["intermediate_size"],
                         config["num_hidden_layers"], config["rms_norm_eps"])


def reference_forward(params, inputs, config):
    """``inputs`` is the objective's ``model_inputs``: (noised, clean)."""
    from .. import toydenoiser_reference

    noised, clean = inputs
    return toydenoiser_reference.forward(params, noised, clean,
                                         config["rms_norm_eps"])


def train_flops_per_token(config, vocab_rows, seq):
    """6 x (a block's up [d, inner] and down [inner, d] matrices, and the
    head's d V); the running mean of the clean copy is no matmul."""
    d, inner = config["hidden_size"], config["intermediate_size"]
    return 6.0 * (config["num_hidden_layers"] * 2 * d * inner + d * vocab_rows)


def attention_train_costs(config, per_chip_batch, seq):
    return []


def expected_first_loss(config, vocab_rows):
    """Tied head over N(0, 1/d) embeddings on a unit-variance final RMSNorm:
    logits of variance 1, and a masked position's clean token is none the
    model can tell from the others."""
    return math.log(vocab_rows) + 0.5
