"""``serving_phase_seconds{phase="prefill"}``: sum over count, the window."""

NAME = "engine_prefill_step_ms"
UNIT = "ms"
LAYER = "serving engine"
MOVES = "serve_ms_per_token_p50"
JOBS = ("serve_lm",)


def read(window):
    s, n = window.counters["serving_phase_seconds"].get("prefill", (0, 0))
    return 1e3 * s / n if n else None
