"""The yardstick's own arithmetic: traffic, operation counts, the reference."""

import os

import numpy as np
import pytest

from chipbench import flops, harness, reference, traffic
from chipbench.families import gpt2
from chipbench.layer_metrics import flash_attention_roofline


def test_same_seed_same_requests_and_lengths_inside_the_clip():
    mix = traffic.load("serve-closed8-p128-n32")
    a = traffic.request_list(7, 200, mix, 50257)
    assert a == traffic.request_list(7, 200, mix, 50257)
    assert a != traffic.request_list(8, 200, mix, 50257)
    lens = [len(r["prompt"]) for r in a]
    assert min(lens) >= 32 and max(lens) <= 384
    assert all(n % 8 == 0 for n in lens)
    assert set(lens) <= set(traffic.possible_lengths(mix["prompt_len"]))
    assert len(traffic.possible_lengths(mix["prompt_len"])) == 45
    assert all(16 <= r["new"] <= 64 for r in a)
    assert 100 <= np.median(lens) <= 160
    assert all(len(r["prompt"]) + r["new"] <= mix["max_context"] for r in a)


def test_token_batches_are_seeded_and_in_range():
    a = traffic.token_batches(3, 2, 4, 16, 100)
    b = traffic.token_batches(3, 2, 4, 16, 100)
    assert len(a) == 2 and a[0][0].shape == (4, 16)
    for (ta, ya), (tb, yb) in zip(a, b):
        assert np.array_equal(ta, tb) and np.array_equal(ya, yb)
        assert np.array_equal(np.asarray(ta)[:, 1:], np.asarray(ya)[:, :-1])
        assert 0 <= int(np.min(ta)) and int(np.max(ta)) < 100


@pytest.mark.parametrize("burst", [None, {"every_s": 10.0, "length_s": 2.0,
                                          "factor": 5.0}])
def test_arrival_times_keep_the_mean_rate(burst):
    t = traffic.arrival_times(1, 20.0, 20000, burst)
    assert np.all(np.diff(t) > 0)
    assert abs(len(t) / t[-1] - 20.0) < 1.0
    assert np.array_equal(t, traffic.arrival_times(1, 20.0, 20000, burst))


V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_operation_counts_by_hand():
    medium = harness.load_json("configs", "gpt2-medium.json")
    per_token = gpt2.train_flops_per_token(medium, 50304, 1024)
    assert per_token == 6 * (12 * 24 * 1024 ** 2 + 1024 * 50304) \
        + 6 * 24 * 1024 * 1024
    cost = flops.flash_attention_train_cost(8, 16, 1024, 64)
    assert cost["flops"] == 6 * 8 * 16 * 1024 * 1024 * 64
    assert cost["bytes"] == 12 * 8 * 1024 * 16 * 64 * 2
    # 24 layers of it are the attention term of the token count
    assert 24 * cost["flops"] == 6 * 24 * 1024 * 1024 * 8 * 1024
    assert gpt2.attention_train_costs(medium, 8, 1024) == [cost] * 24
    roof = flops.roofline_seconds(cost, V5E)
    assert roof["bound"] == "compute"
    assert roof["seconds"] == pytest.approx(cost["flops"] / 197e12)
    assert flops.roofline_seconds({"flops": 1.0, "bytes": 1e9},
                                  V5E)["bound"] == "memory"
    assert gpt2.expected_first_loss(medium, 50304) == pytest.approx(
        np.log(50304) + 1024 * 0.02 ** 2 / 2)


def test_grouped_kv_heads_read_fewer_bytes_and_as_many_operations():
    """32 query heads over 8 KV heads of 64 (granite-4.0-h-micro's attention
    layer) at 1 x 8192: q, o, do, dq are query-sized, k, v, dk, dv key-sized."""
    full = flops.flash_attention_train_cost(1, 32, 8192, 64)
    assert flops.flash_attention_train_cost(1, 32, 8192, 64, kv_heads=32) == full
    grouped = flops.flash_attention_train_cost(1, 32, 8192, 64, kv_heads=8)
    assert grouped["flops"] == full["flops"]
    operand = 8192 * 64 * 2
    assert grouped["bytes"] == 6 * 32 * operand + 6 * 8 * operand
    assert grouped["bytes"] == full["bytes"] * (32 + 8) / 64


class KernelTime:
    def __init__(self, ms):
        self.ms = ms

    def ms_per_unit(self, table, cls):
        assert (table, cls) == ("class_s", "attention_kernel")
        return self.ms


def roofline_share(costs, kernel_ms):
    return flash_attention_roofline.read(harness.Window(
        cell=None, peak=V5E, correct=True, attempted=1, failed=0,
        end_to_end={}, measured={"attention_train_costs": costs}, counters={},
        first_calls=[], memory_peak_bytes=0,
        trace=None if kernel_ms is None else KernelTime(kernel_ms)))


def test_flash_attention_roofline_sums_the_family_s_attention_layers():
    cost = flops.flash_attention_train_cost(8, 16, 1024, 64)
    least = flops.roofline_seconds(cost, V5E)["seconds"]
    # gpt2-medium's 24 equal layers: what the reader gave when it multiplied
    assert roofline_share([cost] * 24, 41.098) == \
        100.0 * (24 * least) / (41.098 * 1e-3)
    # one attention layer in ten, grouped heads: its own cost, once
    one = flops.flash_attention_train_cost(1, 32, 8192, 64, kv_heads=8)
    assert roofline_share([one], 5.0) == pytest.approx(
        100.0 * flops.roofline_seconds(one, V5E)["seconds"] / 5e-3)
    # nothing to read: no attention layer, no kernel time, no trace
    assert roofline_share([], 41.098) is None
    assert roofline_share([cost], 0.0) is None
    assert roofline_share([cost], None) is None


def test_reference_agrees_with_the_program_in_float32():
    """An independent implementation and the program's model agree to f32
    rounding when the program computes in f32 too: the reference is the
    same mathematics (layout, eps, GELU, tied head)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import TransformerLM, lm_loss
    from horovod_tpu.parallel.ring_attention import reference_attention

    model = TransformerLM(
        vocab_size=256, num_layers=3, num_heads=4, d_model=64, max_seq_len=32,
        dtype=jnp.float32,
        attn_fn=lambda q, k, v: reference_attention(q, k, v, causal=True,
                                                    scale=q.shape[-1] ** -0.5))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    # biases and LayerNorm vectors are zeros/ones at init: perturb them all
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        l + 0.1 * jax.random.normal(k, l.shape) for l, k in zip(leaves, keys)])
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, tokens)
    want = reference.forward(params, tokens, 4, 1e-6)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))
    assert float(reference.loss(want, tokens)) == pytest.approx(
        float(lm_loss(got, tokens)), rel=1e-5)
