"""Median chunk of the window over the steps in a chunk."""

NAME = "train_step_ms"
UNIT = "ms"
LAYER = "train-step builder"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    m = window.measured
    return 1e3 * m["median_chunk_s"] / m["chunk_steps"]
