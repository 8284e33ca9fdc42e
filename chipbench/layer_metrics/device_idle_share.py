"""1 - busy union / traced window, on the first chip."""

NAME = "device_idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    if window.trace is None:
        return None
    return 100.0 * window.trace.first.idle_share
