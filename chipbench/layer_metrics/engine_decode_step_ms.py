"""``serving_phase_seconds{phase="decode"}``: sum over count, the window."""

NAME = "engine_decode_step_ms"
UNIT = "ms"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
JOBS = ("serve_lm",)


def read(window):
    s, n = window.counters["serving_phase_seconds"].get("decode", (0, 0))
    return 1e3 * s / n if n else None
