#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # from the root of a checkout, on a TPU

One process — the only one that touches JAX, because a chip belongs to one
process — drives the system's main paths once through the entry points a
user calls, at the full width of the repo's ``medium`` LM
(``full_sizes()``: 24 layers, d_model 1024, 16 heads, vocab 32768, seq
1024, batch 8 a chip, bf16 compute / f32 params; weights random from a
seed), and checks what comes out:

* **A — trainer, one chip**: ``hvd.init`` → ``spmd.make_train_step`` →
  ``TransformerLM`` with its default (Pallas flash) attention, AdamW with
  bf16 first moments. Losses finite, first near ln(vocab), last below
  first; the kernel engaged (custom calls counted in the compiled step).
* **B — the same path on four chips** (whenever >= 4 devices are visible):
  compiles; every chip runs attention on its own batch shard, no
  all-gather in the compiled program; replicated params bit-identical
  across chips; and with the *same* global batch of 8 the four-chip loss
  trajectory equals Phase A's inside ``TRAJECTORY_TOL``.
* **C — every Pallas kernel the tree ships** runs on the chip at a shape
  the medium step really produces and matches its jnp reference: bit-equal
  where the docstring promises bits, a stated tolerance elsewhere. On four
  chips also ``matmul_reduce_scatter``, ring attention and
  ``spmd.quantized_allreduce`` (int8, int4) with the pack kernel engaged.
* **D — a server answers**: ``ServingFrontend`` + one ``ServingWorker`` +
  ``ServingClient`` around a ``ServingEngine`` at the same widths.

It exits non-zero, naming the phase, if anything does not hold; without a
TPU it fails before doing any work ("no TPU"). The last line of standard
output of a passing run is one JSON object naming the device. The compile
and step seconds it prints are a record, not a benchmark.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(message: str) -> None:
    print(message, flush=True)


# ------------------------------------------------------------------- sizes
@dataclasses.dataclass(frozen=True)
class Sizes:
    """Model and workload sizes of one run. ``main`` only ever uses
    :func:`full_sizes`; the fields exist so the phases can be debugged at a
    toy size on the CPU (interpret-mode kernels) before chip time is spent."""
    vocab: int
    layers: int
    heads: int
    d_model: int
    seq: int
    batch: int            # per chip
    steps: int = 6
    serve_context: int = 512
    serve_batch: int = 4
    serve_prompt: int = 64
    serve_new: int = 16


def full_sizes() -> Sizes:
    return Sizes(vocab=32768, layers=24, heads=16, d_model=1024, seq=1024,
                 batch=8)


#: Phase A: the first loss sits above ln(vocab) by about half the logit
#: variance (tied head over N(0, 0.02^2) embeddings on a unit-variance
#: final LayerNorm: sigma^2 = d_model * 0.02^2 = 0.41 at d_model 1024),
#: so the band is one-sided-generous around ln(vocab) + 0.2.
FIRST_LOSS_BAND = 0.5

#: Phase B: |loss_4chip[i] - loss_1chip[i]| on the same global batch of 8.
#: The two programs differ in reduction order (mean of 4 shard means, an
#: all-reduce of bf16-computed f32 gradients) and in the tiling XLA picks
#: for batch 2 against batch 8, so activations agree to bf16 rounding
#: (2^-8) and AdamW's sign-like first steps amplify near-zero gradient
#: coordinates; the losses then agree to a few 1e-3. A step that skipped
#: the gradient average would train each chip on 2 of the 8 sequences and
#: land a different loss from the second step on — Phase B requires the
#: six-step drop to be at least 10x this tolerance so that shows.
TRAJECTORY_TOL = 0.02


# ------------------------------------------------------------ device gate
def require_tpu():
    """The device as JAX reports it; exits with 'no TPU' before any work
    when the platform is anything else (no smaller CPU shape under the
    same name)."""
    import jax
    import jaxlib

    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"device: {json.dumps(info)}  jax {jax.__version__}  "
        f"jaxlib {jaxlib.__version__}  libtpu {libtpu}")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX found platform "
                 f"{dev.platform!r}; this script checks the system on the "
                 f"chip and has no CPU mode")
    return info


# -------------------------------------------------------- trainer (A, B)
def build_trainer(sizes: Sizes, mesh):
    """(model, loss_fn, tx, step) through the normal entry points."""
    import jax.numpy as jnp
    import optax

    from horovod_tpu import spmd
    from horovod_tpu.models.transformer import TransformerLM, lm_loss

    model = TransformerLM(
        vocab_size=sizes.vocab, num_layers=sizes.layers,
        num_heads=sizes.heads, d_model=sizes.d_model,
        max_seq_len=sizes.seq, dtype=jnp.bfloat16)

    def loss_fn(params, batch):
        tokens, targets = batch
        return lm_loss(model.apply({"params": params}, tokens), targets)

    tx = optax.adamw(3e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16)
    return model, tx, spmd.make_train_step(loss_fn, tx, mesh=mesh)


def seeded_batch(sizes: Sizes, global_batch: int):
    import numpy as np

    toks = np.random.RandomState(0).randint(
        0, sizes.vocab, (global_batch, sizes.seq + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def train(sizes: Sizes, mesh, global_batch: int, label: str):
    """Init from seed 0, compile, take ``sizes.steps`` steps on the seeded
    batch. Returns a dict with the losses, the compiled step and the final
    params (still on the devices)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import spmd

    model, tx, step = build_trainer(sizes, mesh)
    tokens, targets = seeded_batch(sizes, global_batch)
    params = jax.jit(lambda key: model.init(
        key, jnp.zeros((1, sizes.seq), jnp.int32))["params"])(
        jax.random.PRNGKey(0))
    params = spmd.replicate(params, mesh)
    opt_state = spmd.replicate(jax.jit(tx.init)(params), mesh)
    batch = spmd.shard_batch((jnp.asarray(tokens), jnp.asarray(targets)),
                             mesh)

    lowered = step.lower(params, opt_state, batch)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    losses, step_s = [], []
    for _ in range(sizes.steps):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, batch)
        jax.block_until_ready((params, opt_state, loss))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    # the first call also pays the executable's load onto the device
    steady = statistics.median(step_s[1:])
    say(f"  {label}: compile {compile_s:.1f} s, steady step {steady:.4f} s "
        f"(global batch {global_batch}, {mesh.devices.size} chip(s)); "
        f"losses {' '.join(f'{l:.4f}' for l in losses)}")
    return {"losses": losses, "compile_s": compile_s, "step_s": steady,
            "compiled": compiled, "params": params}


def flash_calls(text: str) -> int:
    return text.count("tpu_custom_call")


def check_trained(sizes: Sizes, run) -> None:
    """What Phases A and B both ask of a :func:`train` run."""
    losses = run["losses"]
    check(all(math.isfinite(l) for l in losses), f"non-finite loss: {losses}")
    ln_v = math.log(sizes.vocab)
    check(abs(losses[0] - (ln_v + 0.2)) <= FIRST_LOSS_BAND,
          f"first loss {losses[0]:.4f} outside {ln_v + 0.2:.3f} "
          f"+- {FIRST_LOSS_BAND}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")
    # forward + fused backward per layer in the compiled step (the lowered
    # one holds a kernel's body once, its dispatcher being jitted): the
    # reference_attention hand-over would leave zero
    found = run["compiled"].as_text().count(
        'custom_call_target="tpu_custom_call"')
    check(found == 2 * sizes.layers,
          f"{found} tpu_custom_calls in the compiled step, expected "
          f"{2 * sizes.layers}")


def phase_a(sizes: Sizes, mesh1):
    """Trainer on one chip, full width. Returns the loss trajectory (Phase
    B compares against it) and the timings for the record."""
    from horovod_tpu.ops import pallas_kernels as pk

    check(pk.mode() == "on", f"pallas_kernels.mode() is {pk.mode()!r}")
    run = train(sizes, mesh1, sizes.batch, "A one chip")
    check_trained(sizes, run)
    return {k: run[k] for k in ("losses", "compile_s", "step_s")}


def phase_b(sizes: Sizes, mesh4, one_chip_losses):
    """The same path on four chips."""
    import re

    import jax
    import numpy as np

    n = mesh4.devices.size
    run = train(sizes, mesh4, sizes.batch * n, "B four chips")
    check_trained(sizes, run)
    record = {"compile_s": run["compile_s"], "step_s": run["step_s"]}

    # each chip's program runs attention on its own shard: the kernels'
    # heads-major operands are [per-chip batch * heads, seq, head_dim],
    # and nothing is gathered anywhere in the program
    compiled = run["compiled"].as_text()
    gathers = re.findall(r"= \S+ all-gather(?:-start)?\(", compiled)
    check(not gathers, f"{len(gathers)} all-gather ops in the compiled step")
    kernel_lines = [l for l in compiled.splitlines()
                    if 'custom_call_target="tpu_custom_call"' in l]
    check(len(kernel_lines) == 2 * sizes.layers,
          f"{len(kernel_lines)} Pallas calls in the compiled step")
    local_bh = sizes.batch * sizes.heads
    for line in kernel_lines:
        lead = re.search(r"= \(?\w+\[(\d+),", line)
        check(lead is not None and int(lead.group(1)) == local_bh,
              f"Pallas call not on the local shard ({local_bh} rows): "
              f"{line.strip()[:160]}")

    # four devices busy, replicated params bit-identical on each
    leaves = jax.tree_util.tree_leaves(run["params"])
    for leaf in leaves:
        shards = leaf.addressable_shards
        check(len({s.device for s in shards}) == n,
              f"a param leaf lives on {len(shards)} device(s), not {n}")
        first = np.asarray(shards[0].data)
        for s in shards[1:]:
            check(np.array_equal(first, np.asarray(s.data)),
                  "replicated params differ between chips")
    for dev in mesh4.devices.flat:
        stats = dev.memory_stats() or {}
        check(stats.get("peak_bytes_in_use", 0) > sum(
            l.nbytes for l in leaves),
            f"{dev} never held the params: {stats.get('peak_bytes_in_use')}")
    del run, leaves

    # same global batch as Phase A (2 sequences a chip): same trajectory
    same = train(sizes, mesh4, sizes.batch, "B four chips, Phase A's batch")
    diffs = [abs(a - b) for a, b in zip(same["losses"], one_chip_losses)]
    say(f"  B vs A on the same global batch: max |dloss| {max(diffs):.5f} "
        f"(tolerance {TRAJECTORY_TOL}); per step "
        f"{' '.join(f'{d:.5f}' for d in diffs)}")
    check(max(diffs) <= TRAJECTORY_TOL,
          f"four-chip trajectory leaves the one-chip one: {diffs}")
    drop = one_chip_losses[0] - one_chip_losses[-1]
    check(drop >= 10 * TRAJECTORY_TOL,
          f"loss drop {drop:.4f} too small for the tolerance to see a "
          f"missing gradient average")
    return {**record, "max_dloss": max(diffs)}


# ------------------------------------------------------------- kernels (C)
# The medium LM at seq 1024 x batch 8: its attention shapes, its activation
# rows, and the rows of its flat f32 gradient's ring chunk over 4 chips
# (336,914,432 params / 4 / 256 — not a multiple of 8, so the quantize
# kernels' partial last tile is part of the real shape).
B, T, H, D, DM, VOCAB = 8, 1024, 16, 64, 1024, 32768
LONG_T = 8192
CHUNK_ROWS, BLOCK = 329018, 256

#: relative-to-max error allowed between a kernel and its f32-math
#: reference on bf16 operands (bf16 keeps 8 bits: 2^-8 = 3.9e-3 per
#: rounding, a few roundings deep) and on f32 operands (summation order)
TOL_BF16 = 2e-2
TOL_F32 = 1e-4


@dataclasses.dataclass(frozen=True)
class KernelCase:
    fn: object            # the dispatcher / kernel, kernels on
    args: tuple           # jax.ShapeDtypeStruct operands
    calls: int            # tpu_custom_calls in the lowered program
    ref: object           # jnp reference over the same operands
    tol: object           # None = bit-equal, else relative-to-max error


def cotangent(shape):
    """A fixed non-uniform cotangent for the gradient checks: a function of
    each index on its own, so that a slice of heads (or a shard of the
    sequence) sees the weights of just those positions, and independent of
    the output's values, so that one path's rounding does not feed back
    into its own gradient."""
    import jax
    import jax.numpy as jnp

    return jnp.cos(sum(
        c * jax.lax.broadcasted_iota(jnp.float32, shape, axis)
        for axis, c in enumerate((0.11, 0.37, 0.71, 1.3)[-len(shape):])))


def kernels_off(fn):
    """``fn`` traced with the Pallas kernels off: a dispatcher's reference
    path (the XLA form, ``ragged_dot``) on the same operands."""
    from unittest import mock

    def traced_off(*operands):
        with mock.patch.dict(os.environ, HVD_PALLAS="0"):
            return fn(*operands)
    return traced_off


def kernel_cases():
    """name -> :class:`KernelCase` for every public single-device kernel.
    ``tests/test_tpu_lowering.py`` lowers the same list without a chip."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import pallas_kernels as pk
    from horovod_tpu.parallel.ring_attention import (_block_attn,
                                                     reference_attention)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    def attn_grads(attn):
        # out and all three gradients
        def f(q, k, v):
            def loss(q, k, v):
                out = attn(q, k, v).astype(jnp.float32)
                return jnp.sum(out * cotangent(out.shape)), out
            grads, out = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(
                q, k, v)
            return out, grads
        return f

    flash = attn_grads(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=True))
    dense = attn_grads(lambda q, k, v: reference_attention(
        q, k, v, causal=True))

    def dense_two_heads(q, k, v):
        # [1, 16, 8192, 8192] f32 scores would not fit; heads are
        # independent, so the reference takes two of them
        return dense(q[:, :, :2], k[:, :, :2], v[:, :, :2])

    def dense_two_heads_exact(q, k, v):
        # 192 ** -0.5 is no power of two: at the TPU's default precision the
        # reference's own products round q * scale to bf16 and its dq leaves
        # the kernel's by 2.0e-2 (head 64's 0.125 is exact: 5e-3)
        with jax.default_matmul_precision("highest"):
            return dense_two_heads(q, k, v)

    def flash_two_heads(q, k, v):
        out, (dq, dk, dv) = flash(q, k, v)
        return out[:, :, :2], (dq[:, :, :2], dk[:, :, :2], dv[:, :, :2])

    def flash_first_head(q, k, v):
        out, grads = flash(q, k, v)
        return out[:, :, :1], tuple(g[:, :, :1] for g in grads)

    def dense_first_head_exact(q, k, v):
        # at 16,384 positions one head's f32 scores are 1 GiB, and the
        # reference holds several such tensors: one head of the two
        with jax.default_matmul_precision("highest"):
            return dense(q[:, :, :1], k[:, :, :1], v[:, :, :1])

    # q rows 1024..3071 against k rows 0..2047: part of the tile masked
    hop = dict(q_off=1024, k_off=0, causal=True, scale=D ** -0.5)

    def adasum_ref(a, b):
        dot, na, nb = jnp.sum(a * b), jnp.sum(a * a), jnp.sum(b * b)
        return (1 - dot / (2 * na)) * a + (1 - dot / (2 * nb)) * b

    def unpacked(pack, unpack):
        return lambda x2: unpack(pack(x2))

    qkv = [s((B, T, H, D), jnp.bfloat16)] * 3
    qkv_long = [s((1, LONG_T, H, D), jnp.bfloat16)] * 3
    # the per-chip calls of the benchmark's other two architectures:
    # gpt2-large (batch 4 x 20 heads) and granite-4.0-h (1 x 32 x 4096,
    # four k sweeps with the dq scratch, no input fusion at batch 1)
    qkv_large = [s((4, T, 20, D), jnp.bfloat16)] * 3
    qkv_4k = [s((1, 4096, 32, D), jnp.bfloat16)] * 3
    # latent attention's widths (keys 192, values 128): at 8192 positions a
    # head's f32 dq is 6 MiB, over what Mosaic's default VMEM limit holds,
    # so the multi-sweep backward names its own (flash_route's
    # backward_vmem), as kanana2-train-s16384's does at 16,384
    qkv_two_widths = ([s((1, LONG_T, H, 192), jnp.bfloat16)] * 2
                      + [s((1, LONG_T, H, 128), jnp.bfloat16)])
    # kanana2-train-s16384's head: 24 MiB of K and V in VMEM for the
    # single-shot forward (the longest in a cell; flash_route's
    # _KV_VMEM_CAP), a 16 MiB dq scratch under the backward's own limit
    qkv_16k = ([s((1, 16384, 2, 192), jnp.bfloat16)] * 2
               + [s((1, 16384, 2, 128), jnp.bfloat16)])
    rows = s((CHUNK_ROWS, BLOCK), jnp.float32)
    return {
        "flash_attention fwd+bwd 8x1024": KernelCase(
            flash, qkv, 2, dense, TOL_BF16),
        "flash_attention fwd+bwd 1x8192": KernelCase(
            flash_two_heads, qkv_long, 2, dense_two_heads, TOL_BF16),
        "flash_attention fwd+bwd 4x20x1024": KernelCase(
            flash, qkv_large, 2, dense, TOL_BF16),
        "flash_attention fwd+bwd 1x32x4096": KernelCase(
            flash_two_heads, qkv_4k, 2, dense_two_heads, TOL_BF16),
        "flash_attention fwd+bwd 1x8192 keys 192 values 128": KernelCase(
            flash_two_heads, qkv_two_widths, 2, dense_two_heads_exact,
            TOL_BF16),
        "flash_attention fwd+bwd 1x16384 keys 192 values 128": KernelCase(
            flash_first_head, qkv_16k, 2, dense_first_head_exact, TOL_BF16),
        "flash_attention_step (ring hop)": KernelCase(
            lambda q, k, v, m, l, o: pk.flash_attention_step(
                q, k, v, m, l, o, hop["q_off"], hop["k_off"],
                causal=hop["causal"], scale=hop["scale"]),
            [s((1, 2048, H, D), jnp.bfloat16)] * 3
            + [s((1, H, 2048), jnp.float32)] * 2
            + [s((1, 2048, H, D), jnp.float32)], 1,
            lambda q, k, v, m, l, o: _block_attn(
                q, k, v, m, l, o, hop["q_off"], hop["k_off"],
                hop["causal"], hop["scale"]), TOL_BF16),
        "adasum_combine": KernelCase(
            pk.adasum_combine, [s((1 << 20,), jnp.float32)] * 2, 2,
            adasum_ref, TOL_F32),
        "int8_quantize_2d": KernelCase(
            pk.int8_quantize_2d, [rows], 1,
            unpacked(pk.int8_quantize_pack_ref, pk.int8_unpack), None),
        "int8_dequantize_2d": KernelCase(
            pk.int8_dequantize_2d,
            [s((CHUNK_ROWS, BLOCK), jnp.int8),
             s((CHUNK_ROWS, 1), jnp.float32)], 1,
            lambda q2, s2: q2.astype(jnp.float32) * s2, None),
        "int8_quantize_pack": KernelCase(
            pk.int8_quantize_pack, [rows], 1, pk.int8_quantize_pack_ref,
            None),
        "int4_quantize_pack": KernelCase(
            pk.int4_quantize_pack, [rows], 1, pk.int4_quantize_pack_ref,
            None),
        **grouped_cases(),
        **scan_cases(),
        **delta_cases(),
        **conv_cases(),
        "matmul_2d (LM head chunk)": KernelCase(
            pk.matmul_2d,
            [s((B * T // 4, DM // 4), jnp.bfloat16),
             s((DM // 4, VOCAB), jnp.bfloat16)], 1,
            lambda x, w: jnp.dot(x, w, preferred_element_type=jnp.float32
                                 ).astype(x.dtype), TOL_BF16),
    }


def grouped_cases():
    """The routed feed-forward's three grouped products (``ops/moe.py``) at
    LFM2-8B-A1B's widths and the cell's smaller row capacity, 22,000 rows
    in eight uneven groups, against a loop of dense products over the
    groups. The rows of no group hold whatever was there and are cut off.
    And the way back to token order, ``put_rows``' segment sum, at
    Laguna-S-2.1's shape (8,192 tokens, ten assignments each, 16 of 256
    experts held and preferred; 20,480 rows 3072 wide) against the plain
    sum over a token's slots."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import moe

    rows, d, width = 32768, 2048, 1792
    sizes = (3011, 2467, 3390, 2214, 2905, 2750, 1893, 3370)
    ends = [sum(sizes[:g + 1]) for g in range(len(sizes))]
    spans = list(zip([0] + ends[:-1], ends))

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def product(fn, rows_out=ends[-1]):
        return lambda lhs, rhs: fn(
            lhs, rhs, jnp.asarray(sizes, jnp.int32))[:rows_out]

    def dot(a, b, contract):
        return jax.lax.dot_general(
            a, b, ((contract, ((), ()))),
            preferred_element_type=jnp.float32).astype(a.dtype)

    def rowwise(contract):
        return lambda lhs, rhs: jnp.concatenate([
            dot(lhs[a:b], rhs[g], contract)
            for g, (a, b) in enumerate(spans)])

    tokens, top_k, experts, held, capacity, wide = 8192, 10, 256, 16, 20480, 3072

    def way_back(rows):
        scores = np.random.RandomState(0).gumbel(size=(tokens, experts))
        scores[:, :held] += 0.75
        chosen = np.argsort(-scores, axis=1)[:, :top_k]
        here = int((chosen < held).sum())
        assert 5120 < here <= capacity
        order, per_token, group_sizes = moe.dispatch(
            jnp.asarray(chosen, jnp.int32), tuple(range(held)))
        _, token, valid, back = moe._rows_at(capacity, top_k, order,
                                             per_token, group_sizes)
        # the plain sum's index, which the stage never builds: the sorted
        # row of each of a token's assignments, ``capacity`` where none here
        inverse = np.argsort(np.argsort(np.where(chosen < held, chosen, held
                                                 ).ravel(), kind="stable"))
        slots = np.where(inverse < here, inverse, capacity)
        return (jnp.where(valid, rows, 0), token,
                jnp.asarray(slots.reshape(tokens, top_k), jnp.int32), back)

    def segment_sum(rows):
        rows, token, _, back = way_back(rows)
        return moe.put_rows(rows, token, back)

    def slot_sum(rows):
        rows, _, slots, _ = way_back(rows)
        padded = jnp.concatenate([rows, jnp.zeros((1, wide), rows.dtype)])
        return sum(padded[slots[:, j]].astype(jnp.float32)
                   for j in range(top_k)).astype(rows.dtype)

    return {
        "moe put_rows (rows summed back into tokens)": KernelCase(
            segment_sum, [s(capacity, wide)], 1, slot_sum, TOL_BF16),
        "moe grouped_matmul (x W1)": KernelCase(
            product(moe.grouped_matmul),
            [s(rows, d), s(len(sizes), d, 2 * width)], 1,
            rowwise(((1,), (0,))), TOL_BF16),
        "moe grouped_matmul_t (dGU W1^T)": KernelCase(
            product(moe.grouped_matmul_t),
            [s(rows, 2 * width), s(len(sizes), d, 2 * width)], 1,
            rowwise(((1,), (1,))), TOL_BF16),
        "moe grouped_outer (dW1)": KernelCase(
            product(moe.grouped_outer, None),
            [s(rows, d), s(rows, 2 * width)], 1,
            lambda lhs, rhs: jnp.stack([
                dot(lhs[a:b], rhs[a:b], ((0,), (0,))) for a, b in spans]),
            TOL_BF16),
        **routed_layer_cases(),
    }


def routed_layer_cases():
    """``ops/moe.routed_ffn`` and its four gradients at a routed layer of
    the benchmark's Qwen3-Next cell (16,384 tokens 2048 wide, top-10 of 512
    softmax experts 512 wide, 16 held), at each of its two row capacities:
    a router that spreads its tokens (about 5,000 rows of the 25,600) and
    one whose selection bias sends every assignment to the experts held
    (163,840 rows, the worst case). The kernels against the same call with
    kernels off, which is the ``ragged_dot`` path; nine grouped products a
    capacity, both capacities in each program. The operands are drawn as
    every case's are and scaled here to a layer's ranges."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import moe

    tokens, d, width, experts, held, top_k = 16384, 2048, 512, 512, 16, 10
    assert moe.capacities(tokens * top_k, held, experts) == (25600, 163840)

    def layer(prefer_held):
        bias = jnp.zeros((experts,), jnp.float32).at[:held].set(prefer_held)

        def f(h, router, w_in, w_out):
            def loss(*a):
                y, _, _, load = moe.routed_ffn(
                    a[0], a[1], bias, a[2], a[3], held=tuple(range(held)),
                    top_k=top_k, scoring="softmax")
                y = y.astype(jnp.float32)
                return jnp.sum(y * cotangent(y.shape)), (y, load)
            grads, (y, load) = jax.grad(loss, argnums=range(4), has_aux=True)(
                h / 3, router / 60, w_in / 100, w_out / 50)
            return y, grads, load[:held]
        return f

    s = jax.ShapeDtypeStruct
    args = [s((tokens, d), jnp.bfloat16), s((d, experts), jnp.float32),
            s((held, d, 2 * width), jnp.float32),
            s((held, width, d), jnp.float32)]
    return {f"moe routed_ffn fwd+bwd 16 of 512 held, {name}": KernelCase(
        layer(prefer), args, 18, kernels_off(layer(prefer)), TOL_BF16)
        for name, prefer in (("rows spread", 0.0), ("every row here", 10.0))}


def scan_cases():
    """The state-space scan (``ops/ssd.ssd_chunked``) and its six gradients
    at the Mamba-2 layers of the benchmark's two hybrids, one sequence of
    4096 positions: the Pallas pair against the dual form in XLA, which is
    what the same call runs with kernels off. The operands are drawn as
    every case's are and brought into the scan's ranges here: steps
    ``softplus`` of a normal, rates ``-exp`` of one."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import ssd

    def scan(chunk):
        def f(x, dt, A, B, C, D):
            dt = jax.nn.softplus(dt - 3.0)
            A = -jnp.exp(A / 6.0)

            def loss(*a):
                y = ssd.ssd_chunked(*a, chunk=chunk).astype(jnp.float32)
                return jnp.sum(y * cotangent(y.shape)), y
            grads, y = jax.grad(loss, argnums=range(6), has_aux=True)(
                x, dt, A, B / 3.0, C / 3.0, D)
            return y, grads
        return f

    def case(heads, groups, chunk):
        t, p, n = 4096, 64, 128
        bc = (1, t, n) if groups is None else (1, t, groups, n)
        s = jax.ShapeDtypeStruct
        return KernelCase(
            scan(chunk),
            [s((1, t, heads, p), jnp.bfloat16), s((1, t, heads), jnp.float32),
             s((heads,), jnp.float32), s(bc, jnp.bfloat16),
             s(bc, jnp.bfloat16), s((heads,), jnp.float32)], 2,
            kernels_off(scan(chunk)), TOL_BF16)

    return {"ssd_scan fwd+bwd 64 heads, one group": case(64, None, 256),
            "ssd_scan fwd+bwd 128 heads, 8 groups": case(128, 8, 128)}


def delta_cases():
    """The gated delta rule (``ops/gated_delta.gated_delta_chunked``) and
    its five gradients at a delta-rule layer of the benchmark's Qwen3-Next
    cell (32 heads of 128 / 128), one sequence of 4,096 positions: the
    Pallas pair against the chunked form in XLA, which is what the same
    call runs with kernels off. The operands are drawn as every case's are
    and brought into the rule's ranges here: keys of unit length, queries
    a ``128 ** -0.5`` of it, decays ``-softplus`` of a normal, write
    strengths its sigmoid."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import gated_delta

    def unit(x, scale=1.0):
        x = x.astype(jnp.float32)
        return (x * scale * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)).astype(
                jnp.bfloat16)

    def rule(q, k, v, g, beta):
        def loss(*a):
            o = gated_delta.gated_delta_chunked(*a).astype(jnp.float32)
            return jnp.sum(o * cotangent(o.shape)), o
        grads, o = jax.grad(loss, argnums=range(5), has_aux=True)(
            unit(q, 128 ** -0.5), unit(k), v, -jax.nn.softplus(g - 2.0),
            jax.nn.sigmoid(beta))
        return o, grads

    s, t, h = jax.ShapeDtypeStruct, 4096, 32
    return {"gated_delta fwd+bwd 32 heads of 128": KernelCase(
        rule, [s((1, t, h, 128), jnp.bfloat16)] * 3
        + [s((1, t, h), jnp.float32)] * 2, 2, kernels_off(rule), TOL_BF16)}


def conv_cases():
    """The depthwise causal conv (``ops/ssd.causal_conv1d``) and its three
    gradients at the widest part a mixer of the benchmark hands it, the
    ``x`` of a Mamba-2 layer of the Nemotron-H cell (4,096 positions, 8,192
    channels, 4 taps, bias, SiLU), and at a short-conv layer of the LFM2
    cell (2 x 8,192 positions, 2,048 channels, 3 taps, neither): the Pallas
    pair against the shifted sums in XLA, which is what the same call runs
    with kernels off. Taps of a fresh layer's range."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import ssd

    def conv(activation, biased):
        def f(x, kernel, bias):
            def loss(x, kernel, bias):
                y = ssd.causal_conv1d(
                    x, kernel, bias if biased else None,
                    activation=activation).astype(jnp.float32)
                return jnp.sum(y * cotangent(y.shape)), y
            grads, y = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(
                x, kernel / 2.0, bias / 10.0)
            return y, grads if biased else grads[:2]
        return f

    def case(b, t, c, k, activation, biased):
        s = jax.ShapeDtypeStruct
        fn = conv(activation, biased)
        return KernelCase(
            fn, [s((b, t, c), jnp.bfloat16), s((k, c), jnp.float32),
                 s((c,), jnp.float32)], 2, kernels_off(fn), TOL_BF16)

    return {
        "causal_conv fwd+bwd 4096x8192, 4 taps, bias, SiLU": case(
            1, 4096, 8192, 4, "silu", True),
        "causal_conv fwd+bwd 2x8192x2048, 3 taps": case(
            2, 8192, 2048, 3, None, False)}


def matmul_reduce_scatter_case(mesh):
    """(fn, ref, operands) of the contraction-sharded LM-head product over
    ``mesh``'s ``"hvd"`` axis."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.ops import pallas_kernels as pk

    def sharded(inner):
        return lambda x, w: jax.shard_map(
            lambda xs, ws: inner(xs, ws, "hvd"), mesh=mesh,
            in_specs=(P(None, "hvd"), P("hvd", None)),
            out_specs=P("hvd", None), check_vma=False)(x, w)

    args = [jax.ShapeDtypeStruct((B * T, DM), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, P(None, "hvd"))),
            jax.ShapeDtypeStruct((DM, VOCAB), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, P("hvd", None)))]
    return (sharded(pk.matmul_reduce_scatter),
            sharded(pk.matmul_reduce_scatter_reference), args)


def quantized_allreduce_case(mesh, wire: str):
    """(fn, exact, operand): ``spmd.quantized_allreduce`` of one
    block-aligned [world, 4 Mi] f32 payload against the exact ``psum``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu import spmd

    def sharded(inner):
        return lambda x: jax.shard_map(
            lambda v: inner(v[0])[None], mesh=mesh, in_specs=P("hvd"),
            out_specs=P("hvd"), check_vma=False)(x)

    n = mesh.devices.size
    arg = jax.ShapeDtypeStruct((n, 1 << 22), jnp.float32,
                               sharding=NamedSharding(mesh, P("hvd")))
    return (sharded(lambda v: spmd.quantized_allreduce(v, wire=wire)),
            sharded(lambda v: spmd.allreduce(v)), arg)


def ring_attention_case(mesh):
    """(fn, ref, operands): ring attention forward and backward over a
    [1, 8192] sequence split across ``mesh``'s chips, against the
    single-chip flash kernel over the whole sequence (itself checked
    against dense attention in :func:`kernel_cases`)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.ops import pallas_kernels as pk
    from horovod_tpu.parallel.ring_attention import ring_attention

    sp = Mesh(mesh.devices, ("sp",))
    spec = P(None, "sp")

    def ringed(q, k, v):
        return jax.shard_map(
            lambda *a: ring_attention(*a, "sp", causal=True), mesh=sp,
            in_specs=(spec,) * 3, out_specs=spec, check_vma=False)(q, k, v)

    def grads(attn):
        def loss(q, k, v):
            out = attn(q, k, v).astype(jnp.float32)
            return jnp.sum(out * cotangent(out.shape))
        return jax.grad(loss, argnums=(0, 1, 2))

    args = [jax.ShapeDtypeStruct((1, LONG_T, H, D), jnp.bfloat16,
                                 sharding=NamedSharding(sp, spec))] * 3
    return (grads(ringed), grads(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=True)), args)


def random_operands(args, seed: int = 0):
    """Concrete seeded operands for abstract ``args`` (normal floats scaled
    by 3 so quantization sees a spread; int8 over its whole range), placed
    as each operand's sharding says."""
    import jax
    import jax.numpy as jnp

    out = []
    for i, a in enumerate(args):
        key = jax.random.PRNGKey(seed + i)
        if jnp.issubdtype(a.dtype, jnp.integer):
            x = jax.random.randint(key, a.shape, -127, 128, jnp.int32
                                   ).astype(a.dtype)
        else:
            x = (3.0 * jax.random.normal(key, a.shape, jnp.float32)
                 ).astype(a.dtype)
        sharding = getattr(a, "sharding", None)
        out.append(jax.device_put(x, sharding) if sharding is not None
                   else x)
    return out


def compare(name: str, out, ref, tol) -> str:
    """Finite, same shapes and dtypes, and equal: bitwise (``tol`` None)
    or within ``tol`` relative to the reference's largest magnitude."""
    import jax
    import jax.numpy as jnp

    outs, refs = (jax.tree_util.tree_leaves(t) for t in (out, ref))
    check(len(outs) == len(refs), f"{name}: {len(outs)} outputs, reference "
                                  f"has {len(refs)}")
    worst = 0.0
    for i, (o, r) in enumerate(zip(outs, refs)):
        name = f"{name.split(' [output ')[0]} [output {i}]"
        check(o.shape == r.shape and o.dtype == r.dtype,
              f"{name}: {o.dtype}{o.shape} vs reference {r.dtype}{r.shape}")
        o32, r32 = o.astype(jnp.float32), r.astype(jnp.float32)
        check(bool(jnp.all(jnp.isfinite(o32))), f"{name}: non-finite output")
        if tol is None:
            bad = int(jnp.sum(o != r))
            check(bad == 0,
                  f"{name}: {bad} of {o.size} elements differ from the "
                  f"reference's bits (largest |diff| "
                  f"{float(jnp.max(jnp.abs(o32 - r32)))})")
        else:
            err = float(jnp.max(jnp.abs(o32 - r32))
                        / jnp.maximum(jnp.max(jnp.abs(r32)), 1e-30))
            worst = max(worst, err)
            check(err <= tol, f"{name}: relative error {err:.3e} > {tol}")
    return "bit-equal" if tol is None else f"rel err {worst:.1e} <= {tol}"


def run_case(name: str, fn, ref, args, calls, tol, ref_device=None) -> None:
    """Run ``fn`` on seeded operands, count its kernel calls, compare with
    ``ref`` on the same operands. ``ref_device``: run the reference (and
    compare) on that one device — for a reference that is itself a Pallas
    call, which a jit over sharded operands could not partition."""
    import jax

    jitted = jax.jit(fn)
    operands = random_operands(args)
    found = flash_calls(jitted.lower(*operands).as_text())
    check(found == calls if calls is not None else found > 0,
          f"{name}: {found} tpu_custom_calls in the lowered program"
          + (f", expected {calls}" if calls is not None else ""))
    out = jax.block_until_ready(jitted(*operands))
    if ref_device is not None:
        out, operands = jax.device_put((out, operands), ref_device)
    verdict = compare(name, out, jax.jit(ref)(*operands), tol)
    say(f"  C {name}: {found} kernel call(s), {verdict}")


def phase_c(mesh4=None) -> None:
    """Every kernel compiles on the chip and matches its reference. Runs
    every case and reports all that fail, not only the first."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu import spmd
    from horovod_tpu.ops import pallas_kernels as pk

    failed = []

    def attempt(name, thunk):
        try:
            thunk()
        except Exception as exc:  # noqa: BLE001 - collected, re-raised below
            traceback.print_exc()
            failed.append(f"{name}: {exc}")

    for name, case in kernel_cases().items():
        attempt(name, lambda c=case, n=name: run_case(
            n, c.fn, c.ref, c.args, c.calls, c.tol))

    # the dispatchers must have chosen the kernel at these shapes, and the
    # choice must be what kernel_path reports
    rows = jnp.zeros((CHUNK_ROWS, BLOCK), jnp.float32)
    for gate in ("int8_quantize", "int8_quantize_pack", "int4_quantize_pack"):
        attempt(f"kernel_path {gate}", lambda g=gate: check(
            pk.kernel_path(g, rows) == "pallas",
            f"kernel_path({g!r}) is not 'pallas' at {rows.shape}"))

    if mesh4 is not None:
        def mrs():
            fn, ref, args = matmul_reduce_scatter_case(mesh4)
            run_case("matmul_reduce_scatter x4", fn, ref, args,
                     mesh4.devices.size, TOL_BF16)
        attempt("matmul_reduce_scatter x4", mrs)

        def ring():
            fn, ref, args = ring_attention_case(mesh4)
            run_case("ring_attention fwd+bwd sp=4", fn, ref, args, None,
                     TOL_BF16, ref_device=mesh4.devices.flat[0])
        attempt("ring_attention fwd+bwd sp=4", ring)

        for wire, rms_tol in (("int8", 0.02), ("int4", 0.3)):
            def quantized(wire=wire, rms_tol=rms_tol):
                name = f"quantized_allreduce ring {wire} x4"
                check(spmd.gspmd_wire(wire) == wire,
                      f"{wire} wire not admitted")
                fn, exact, arg = quantized_allreduce_case(mesh4, wire)
                (x,) = random_operands([arg])
                jitted = jax.jit(fn)
                found = flash_calls(jitted.lower(x).as_text())
                check(found > 0, f"{name}: pack kernel not engaged")
                out = np.asarray(jax.block_until_ready(jitted(x)))
                ref = np.asarray(jax.jit(exact)(x))
                check(np.isfinite(out).all(), f"{name}: non-finite output")
                for row in out[1:]:
                    check(np.array_equal(out[0], row),
                          f"{name}: result differs between chips")
                # each hop adds one rounding of at most half a grid step
                # (absmax/127 for int8, absmax/7 for int4) per block:
                # Gaussian payloads land at ~1% / ~15% rms of the mean
                rms = float(np.sqrt(np.mean((out - ref) ** 2))
                            / np.sqrt(np.mean(ref ** 2)))
                check(1e-6 < rms <= rms_tol,
                      f"{name}: rms error {rms:.4f} vs the exact psum "
                      f"outside (0, {rms_tol}]")
                say(f"  C {name}: {found} kernel call(s), identical on "
                    f"{len(out)} chips, rms error {rms:.4f} <= {rms_tol}")
            attempt(f"quantized_allreduce ring {wire} x4", quantized)

    check(not failed, "; ".join(failed))


# -------------------------------------------------------------- server (D)
def phase_d(sizes: Sizes):
    """Frontend + one worker + client around a full-width engine, all in
    this process (the worker's engine is the one holder of the chip)."""
    import numpy as np

    from horovod_tpu.serving import (ServingClient, ServingConfig,
                                     ServingFrontend)
    from horovod_tpu.serving.worker import ServingWorker, build_replica_engine

    blocks_per_request = -(-(sizes.serve_prompt + sizes.serve_new) // 16)
    engine = build_replica_engine(
        vocab_size=sizes.vocab, num_layers=sizes.layers,
        num_heads=sizes.heads, d_model=sizes.d_model,
        max_seq_len=sizes.serve_context,
        config=ServingConfig(block_size=16,
                             num_blocks=4 * sizes.serve_batch
                             * blocks_per_request,
                             max_batch=sizes.serve_batch,
                             max_context=sizes.serve_context))
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, sizes.vocab, sizes.serve_prompt).tolist()
               for _ in range(sizes.serve_batch)]

    fe = ServingFrontend(secret="").start()
    worker = ServingWorker(fe.addr[0], fe.addr[1], engine, name="w0",
                           rank=1).start()
    cli = None
    try:
        fe.wait_for_workers(1, timeout=60)
        cli = ServingClient(fe.addr[0], fe.addr[1], name="smoke")
        # one throwaway request compiles prefill and decode
        t0 = time.perf_counter()
        cli.submit(prompts[0][:8], 2).result(timeout=900)
        compile_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        futs = [cli.submit(p, sizes.serve_new, request_id=f"req-{i}")
                for i, p in enumerate(prompts)]
        batched = [f.result(timeout=600) for f in futs]
        wall = time.perf_counter() - t0
        for toks in batched:
            check(len(toks) == sizes.serve_new
                  and all(0 <= t < sizes.vocab for t in toks),
                  f"bad completion: {toks}")
        again = cli.submit(prompts[0], sizes.serve_new,
                           request_id="req-0").result(timeout=600)
        check(again == batched[0], "resubmitted request_id returned other "
                                   "tokens")
        alone = cli.submit(prompts[1], sizes.serve_new,
                           request_id="req-alone").result(timeout=600)
        check(alone == batched[1],
              f"batched decode differs from sequential: {batched[1]} vs "
              f"{alone}")
    finally:
        if cli is not None:
            cli.close()
        worker.stop()
        fe.stop()
    # prefill per request, then serve_new - 1 batched decode steps
    decode_s = wall / (sizes.serve_batch + sizes.serve_new - 1)
    say(f"  D server: {len(batched)} requests x {sizes.serve_new} tokens "
        f"in {wall:.2f} s — about {decode_s:.3f} s an engine step "
        f"(prefill+decode compile {compile_s:.1f} s); resubmit identical, "
        f"batched == sequential")
    return {"compile_s": compile_s, "step_s": decode_s}


# -------------------------------------------------------------------- main
def main() -> int:
    info = require_tpu()
    if not os.path.isdir(os.path.join(REPO, "horovod_tpu")):
        sys.exit("chip_smoke: no horovod_tpu/ beside this script — run it "
                 "from the root of a checkout")

    import jax
    import numpy as np
    from jax.sharding import Mesh

    import horovod_tpu as hvd
    from horovod_tpu import basics
    from horovod_tpu.runtime import native
    from horovod_tpu.utils import compile_cache

    native.build()   # from the tracked sources; raises saying why if not
    cache_dir = compile_cache.enable()
    say(f"compile cache: {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        f"entries at start)")

    hvd.init()
    native_controller = basics._engine().native
    say(f"controller: {'native (C++ core)' if native_controller else 'Python'}")
    check(native_controller, "the engine fell back to the Python controller; "
                             "docs/design.md calls the native core the default")

    sizes = full_sizes()
    devices = jax.devices()
    mesh1 = Mesh(np.asarray(devices[:1]), (basics.MESH_AXIS,))
    mesh4 = (Mesh(np.asarray(devices[:4]), (basics.MESH_AXIS,))
             if len(devices) >= 4 else None)

    record, failures = {}, []

    def phase(name, fn):
        say(f"phase {name}")
        t0 = time.perf_counter()
        try:
            record[name] = fn()
            say(f"phase {name} passed in {time.perf_counter() - t0:.0f} s")
        except Exception:  # noqa: BLE001 - every phase runs; any failure
            traceback.print_exc()  # makes the exit code non-zero below
            failures.append(name)
            say(f"phase {name} FAILED after {time.perf_counter() - t0:.0f} s")

    phase("A", lambda: phase_a(sizes, mesh1))
    if mesh4 is not None:
        if "A" in record:
            phase("B", lambda: phase_b(sizes, mesh4, record["A"]["losses"]))
        else:
            failures.append("B")
            say("phase B FAILED: needs Phase A's trajectory")
    else:
        say(f"phase B skipped: {len(devices)} device(s) visible, needs 4")
    phase("C", lambda: phase_c(mesh4))
    phase("D", lambda: phase_d(sizes))
    hvd.shutdown()

    if failures:
        say(f"chip_smoke: FAILED phases: {', '.join(failures)}")
        return 1
    say("record (not a benchmark): " + json.dumps(
        {k: {m: (round(v, 4) if isinstance(v, float) else v)
             for m, v in r.items() if m != "losses"}
         for k, r in record.items() if r}))
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
