"""Peak device memory on the chip that serves: the same
reading as ``peak_hbm_gib`` (see ``serve_device_idle_share`` for why it has
a name of its own)."""

from .peak_hbm_gib import read  # noqa: F401

NAME = "serve_peak_hbm_gib"
UNIT = "GiB"
LAYER = "device"
MOVES = "serve_tokens_per_s"
JOBS = ("serve_lm",)
