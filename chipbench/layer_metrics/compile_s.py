"""Host time of the first call of every program the set-up uses: trace,
compile or load from the persistent cache, and one execution. The
benchmark's own reference check runs after the window and is left out."""

NAME = "compile_s"
UNIT = "s"
LAYER = "compile cache"
MOVES = "setup_s"
JOBS = ("train_lm", "serve_lm")


def read(window):
    return sum(seconds for _, seconds, in_setup in window.first_calls
               if in_setup)
