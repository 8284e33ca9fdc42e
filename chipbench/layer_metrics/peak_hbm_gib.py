"""Peak device memory on the fullest chip (``harness.memory_peak_bytes``:
the buffers in use plus the main program's temporaries)."""

NAME = "peak_hbm_gib"
UNIT = "GiB"
LAYER = "device"
MOVES = "train_tokens_per_s_chip"
JOBS = ("train_lm",)


def read(window):
    return window.memory_peak_bytes / 2 ** 30 or None
