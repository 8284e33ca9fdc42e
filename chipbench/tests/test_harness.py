"""The harness's own bookkeeping: memory arithmetic, metric lookup, the
compilation counter, the order of layer-metric discovery."""

import pytest

from chipbench import harness


class FakeDevice:
    def __init__(self, **stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_memory_peak_adds_the_reserved_temporaries_to_the_resident_buffers():
    gib = 2 ** 30
    train = FakeDevice(bytes_in_use=3 * gib, peak_bytes_in_use=4 * gib,
                       peak_bytes_reserved=8 * gib)
    quiet = FakeDevice(bytes_in_use=1 * gib, peak_bytes_in_use=5 * gib,
                       peak_bytes_reserved=0)
    assert harness.memory_peak_bytes([train]) == 11 * gib
    assert harness.memory_peak_bytes([quiet]) == 5 * gib
    assert harness.memory_peak_bytes([quiet, train]) == 11 * gib
    assert harness.memory_peak_bytes([FakeDevice()]) == 0


def test_declared_metrics_come_from_benchmark_json_or_pending():
    train = harness.declared_metrics("gpt2m-train-dp4")
    assert {m["name"] for m in train["end_to_end"]} == {
        "train_tokens_per_s_chip", "setup_s"}
    assert "allreduce_exposed_ms" in {m["name"] for m in train["per_layer"]}
    one_chip = harness.declared_metrics("gpt2m-train-s1024")
    assert "allreduce_ms" not in {m["name"] for m in one_chip["per_layer"]}
    serve = harness.declared_metrics("gpt2m-serve-c8")       # pending
    assert {m["name"] for m in serve["end_to_end"]} == {
        "serve_tokens_per_s", "serve_ms_per_token_p50", "setup_s"}
    assert "train_mfu" not in {m["name"] for m in serve["per_layer"]}
    with pytest.raises(harness.BenchmarkError):
        harness.declared_metrics("no-such-cell")
    with pytest.raises(harness.BenchmarkError):
        harness.load_cell("no-such-cell")


def test_compile_counter_counts_new_programs_only():
    import jax
    import jax.numpy as jnp

    three, four = jnp.ones(3), jnp.ones(4)       # (making them compiles too)
    counter = harness.CompileCounter()
    f = jax.jit(lambda x: x * 2 + 1)
    f(three).block_until_ready()
    assert counter.count == 1
    f(three).block_until_ready()
    assert counter.count == 1
    f(four).block_until_ready()                  # a new shape compiles
    assert counter.count == 2


def test_first_calls_after_the_window_opens_are_not_set_up():
    ctx = harness.Context(cell=None, seed=0, seconds=1.0, trace=False,
                          rehearse=True, t_start=0.0, peak={}, compiles=None)
    ctx.first_call("a", lambda: 1)
    assert ctx.open_window() > 0
    ctx.first_call("b", lambda: 2)
    assert [(n, s) for n, _, s in ctx.first_calls] == [("a", True), ("b", False)]
    from chipbench.layer_metrics import compile_s

    window = type("W", (), {"first_calls": [("a", 2.0, True), ("b", 5.0, False)]})
    assert compile_s.read(window) == 2.0


def test_replicas_identical_sees_one_chip_that_differs():
    """Four virtual devices: a replicated tree passes, and one whose
    second copy differs in one element (same sharding, other bytes) fails."""
    import subprocess
    import sys

    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from chipbench.jobs.train_lm import replicas_identical
mesh = Mesh(np.asarray(jax.devices()[:4]), ("hvd",))
repl = NamedSharding(mesh, P())
good = {"a": jax.device_put(jnp.arange(12.0).reshape(3, 4), repl),
        "b": jax.device_put(jnp.ones(5), repl)}
assert replicas_identical(good, mesh)
copies = [jax.device_put(np.arange(12.0, dtype=np.float32).reshape(3, 4) + (i == 2) * np.eye(3, 4, dtype=np.float32), d)
          for i, d in enumerate(mesh.devices.flat)]
bad = dict(good, a=jax.make_array_from_single_device_arrays((3, 4), repl, copies))
assert not replicas_identical(bad, mesh)
print("ok")
"""
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], cwd=harness.ROOT,
                          env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", proc.stderr[-2000:]
