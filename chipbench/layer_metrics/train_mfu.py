"""Required operations a token (``flops.train_flops_per_token``: head and
causal attention counted, recomputation not) times tokens a second a chip,
over the chip's bf16 peak."""

NAME = "train_mfu"
UNIT = "%"
LAYER = "train-step builder"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    m = window.measured
    return 100.0 * m["train_flops_per_token"] * m["tokens_per_s_chip"] \
        / window.peak["bf16_flops_per_s"]
