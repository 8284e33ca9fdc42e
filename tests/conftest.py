"""Test bootstrap: force an 8-device virtual CPU platform BEFORE jax imports.

Mirrors the reference's strategy of running the full test matrix as a real
multi-rank job on one machine (`.buildkite/gen-pipeline.sh:104-200`): here the
"pod" is 8 virtual CPU devices (`--xla_force_host_platform_device_count=8`)
and ranks are in-process threads (see horovod_tpu/testing.py).
"""

import os
import sys

# Graph-mode TF collectives block inside py_function sync nodes; the
# in-process cluster rig runs N ranks against ONE TF runtime, so the
# inter-op pool must exceed ranks x max-in-flight-collectives-per-rank or
# another rank's start node starves (single-core CI boxes default to 1).
# Bound: tests run up to 8 ranks with models of up to ~14 reduced tensors
# (8*14=112 < 128). One-rank-per-process deployments are immune (see
# tensorflow/graph.py). Blocked threads are cheap — the pool is not a
# parallelism knob here.
os.environ.setdefault("TF_NUM_INTEROP_THREADS", "128")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)  # fp64/int64 op-matrix parity tests
assert jax.default_backend() == "cpu"
assert len(jax.devices()) == 8

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_state():
    """Each test starts uninitialized (mirrors per-test process isolation)."""
    yield
    import horovod_tpu as hvd

    if hvd.is_initialized():
        hvd.shutdown()
    if jax.config.jax_compilation_cache_dir is not None:
        # a benchmark's main() under test turned the persistent compile
        # cache on (utils/compile_cache.py); the next test starts without
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", None)
        compilation_cache.reset_cache()
