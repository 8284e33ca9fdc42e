"""``serving_request_latency{stage="first_token"}``: submit at the engine
to first token, mean over the requests completed in the window. Engine
side only: the client gets its tokens when the request is done."""

NAME = "engine_ttft_ms"
UNIT = "ms"
LAYER = "serving engine"
MOVES = "serve_ms_per_token_p50"
JOBS = ("serve_lm",)


def read(window):
    s, n = window.counters["serving_request_latency"].get(
        "first_token", (0, 0))
    return 1e3 * s / n if n else None
