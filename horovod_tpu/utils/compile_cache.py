"""Where compiled programs are kept between processes.

Compiling the GPT-2-medium train step takes most of a minute, and every
entry point (``chip_smoke.py``, ``chipbench``, ``benchmarks/*.py``, the
serving worker) starts in a new process. JAX's persistent compilation cache
keys an entry by the program *and* the cache directory, so a directory
that moves (a temp name, a pid, a timestamp) never hits: the path is
either the one the environment gives or one fixed place in the checkout.
"""

from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` (gitignored) — the parent of the package dir
_IN_CHECKOUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compile cache on; call before the first compile.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses that directory
    and this function sets no other. Unset, the cache goes to the fixed
    in-checkout path. Returns the directory in use. From here on every
    lookup is a ``compile/*`` span and counted by outcome
    (``metrics/phases.py``)."""
    import jax

    from ..metrics import phases

    phases.install_jax_listeners()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _IN_CHECKOUT)
    return _IN_CHECKOUT
