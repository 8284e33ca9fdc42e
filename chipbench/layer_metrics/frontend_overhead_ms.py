"""Mean client latency minus mean engine latency (``stage="total"``) of
the requests completed in the window: the two sockets, the frontend's
dispatch and the worker's relay."""

NAME = "frontend_overhead_ms"
UNIT = "ms"
LAYER = "frontend / worker / client"
MOVES = "serve_ms_per_token_p50"
JOBS = ("serve_lm",)


def read(window):
    s, n = window.counters["serving_request_latency"].get("total", (0, 0))
    client = window.measured["mean_client_latency_s"]
    return 1e3 * (client - s / n) if n and client is not None else None
