"""1 - busy union / traced window, on the chip that serves: the same
reading as ``device_idle_share``, under a name of its own because a metric
moves one end-to-end metric and the serving cell has other ones."""

from .device_idle_share import read  # noqa: F401

NAME = "serve_device_idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "serve_tokens_per_s"
JOBS = ("serve_lm",)
