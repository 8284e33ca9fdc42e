"""Mean of ``serving_decode_batch`` over the window, of ``max_batch``."""

NAME = "decode_batch_fill"
UNIT = "%"
LAYER = "serving scheduler"
MOVES = "serve_tokens_per_s"
JOBS = ("serve_lm",)


def read(window):
    s, n = window.counters["serving_decode_batch"].get("", (0, 0))
    return 100.0 * s / n / window.measured["max_batch"] if n else None
