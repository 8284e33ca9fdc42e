"""The data-parallel LM training job, as a user of the library runs it:

``hvd.init()`` -> ``hvd.mesh()`` -> ``spmd.make_train_step(loss_fn, tx,
mesh=...)`` with its defaults, the model its family builds through the
library's public constructor with its defaults, ``optax.adamw(3e-4,
weight_decay=0.01, mu_dtype=bf16)``, the loss its objective builds from a
loss function of the library. No environment knob of the program is set and
no compiler option passed.

What is one architecture's comes from the cell's family,
``families/<model_type>.py``: the model, its plain reference, its operation
counts and its attention layers' costs, the first loss its initialisation
gives. What is one training objective's comes from the module the mix names,
``objectives/<name>.py``: the batch, the loss, what of a batch the model
takes, the batch's shapes, the first loss under its weighting. The
optimizer, the step builder, the window, how tokens are counted (the
sequences' own ``global_batch * seq`` a step, whatever an objective lays
beside them) and every tolerance are the job's, and no family or objective
can set them.

Steps are dispatched back to back; every ``chunk_steps`` steps the loss is
waited for and the chunk's host time recorded, until ``--seconds`` is up.
"""

from __future__ import annotations

import statistics
import time

import numpy as np

from .. import harness

#: first loss: |loss - objective.first_loss(family.expected_first_loss(config,
#: rows))|, what the architecture's initialisation gives under the
#: objective's weighting (the band is chip_smoke.py's)
FIRST_LOSS_BAND = 0.5

#: program logits against the float32 reference, as the root-mean-square
#: difference over the reference's root-mean-square, on two seeded
#: sequences at full width, on the weights the window left. The program's
#: matmul operands AND its residual stream are bf16 (models/transformer.py:
#: every Dense and the residual adds run in the model's dtype): 2^-9
#: relative a rounding, through L blocks. Measured on the v5e (PR 22):
#: 0.0022-0.0027 on the weights a window leaves, both configurations, and
#: 0.0129 on fresh N(0, 0.02) weights, whose logits are small. The bound is
#: 1.5 times the larger; 8-bit operands (2^-4 a rounding, 30 times bf16's)
#: or a dropped block would be several times over it.
LOGIT_RMS_TOL = 0.02

#: loss of the four-chip step against the mean of the four shard losses
#: recomputed forward-only on one chip: the same mathematics in two programs
#: that XLA fuses differently, so bf16 roundings differ. Measured on the v5e
#: (PR 22, 8 runs): 1e-6 to 6e-5 at a loss of 10. The bound is eight times
#: the worst; a step that left one chip's shard out would move the mean by
#: a quarter of the shards' spread, 2e-3 or more.
SHARD_LOSS_TOL = 5e-4


def build(cell: harness.Cell, mesh):
    """(model, loss_fn, tx, step) through the normal entry points."""
    import jax.numpy as jnp
    import optax

    from horovod_tpu import spmd

    model = cell.family.build_model(cell.config, cell.vocab_rows, cell.mix)
    loss_fn = cell.objective.loss(model)
    tx = optax.adamw(3e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16)
    return model, loss_fn, tx, spmd.make_train_step(loss_fn, tx, mesh=mesh)


def cell_mesh(chips: int):
    """``hvd.mesh()`` when it has the cell's chips, else its first ones."""
    import jax
    from jax.sharding import Mesh

    import horovod_tpu as hvd
    from horovod_tpu import basics

    mesh = hvd.mesh()
    if mesh.devices.size == chips:
        return mesh
    return Mesh(np.asarray(jax.devices()[:chips]), (basics.MESH_AXIS,))


def input_shapes(cell: harness.Cell, sequences: int):
    """The shapes of what the model takes for ``sequences`` sequences of the
    mix's length: the objective's ``model_inputs`` of its abstract batch."""
    import jax

    objective, mix = cell.objective, cell.mix
    return jax.eval_shape(
        lambda batch: objective.model_inputs(batch, sequences),
        objective.abstract_batch(mix["global_batch"], mix["seq"], None))


def init_params(model, shapes):
    """``key -> params``: the model initialised on zeros of ``shapes``."""
    import jax.numpy as jnp

    return lambda key: model.init(
        key, *(jnp.zeros(s.shape, s.dtype) for s in shapes))["params"]


def on_first_chip(tree):
    """The first chip's copy of a replicated tree (no transfer)."""
    import jax

    return jax.tree_util.tree_map(lambda l: l.addressable_shards[0].data,
                                  tree)


def check_logits(ctx, model, params, inputs, dev):
    """(a): program against reference on ``inputs``, the objective's
    ``model_inputs`` of two sequences, on ``dev``. The family's reference
    takes the same tuple, or the array itself where there is one."""
    import jax
    import jax.numpy as jnp

    cell = ctx.cell
    dev0 = on_first_chip(params)
    inputs = jax.device_put(inputs, dev)
    got = ctx.first_call("program_forward", jax.jit(
        lambda p, *t: model.apply({"params": p}, *t).astype(jnp.float32)),
        dev0, *inputs)
    want = ctx.first_call("reference_forward", cell.family.reference_forward,
                          dev0, inputs[0] if len(inputs) == 1 else inputs,
                          cell.config)

    @jax.jit
    def compare(got, want):
        diff = got - want
        return (jnp.sqrt(jnp.mean(diff ** 2) / jnp.mean(want ** 2)),
                jnp.max(jnp.abs(diff)) / jnp.max(jnp.abs(want)))

    rms, rel_max = compare(got, want)
    return float(rms), float(rel_max)


def check_shards(ctx, loss_fn, step, params, opt_state, batch, mesh):
    """(c), four chips: the step's loss equals the mean of the shard losses
    recomputed forward-only on one chip from the same parameters."""
    import jax

    dev = mesh.devices.flat[0]
    dev0 = on_first_chip(params)
    fwd = jax.jit(loss_fn)
    shard_losses = []
    n = mesh.devices.size
    for i in range(n):
        shard = tuple(jax.device_put(
            sorted(x.addressable_shards, key=lambda s: s.index[0].start or 0)
            [i].data, dev) for x in batch)
        shard_losses.append(float(fwd(dev0, shard)))
    del dev0
    params, opt_state, loss = step(params, opt_state, batch)
    return params, opt_state, float(loss), shard_losses


def replicas_identical(params, mesh) -> bool:
    """Whether every chip holds the same replicated parameters, compared on
    the chips: under ``shard_map`` each chip sees its own copy, and the
    copies agree where the largest equals the smallest across the mesh.
    (Through the host it is 5.7 GB of transfers for gpt2-medium on four.)"""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.basics import MESH_AXIS

    def same(tree):
        return jnp.stack([
            jnp.all(jax.lax.pmax(l, MESH_AXIS) == jax.lax.pmin(l, MESH_AXIS))
            for l in jax.tree_util.tree_leaves(tree)]).all()

    return bool(jax.jit(jax.shard_map(
        same, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))(params))


def run(ctx: harness.Context) -> harness.Window:
    import jax
    import jax.numpy as jnp

    from horovod_tpu import spmd

    cell, mix, c = ctx.cell, ctx.cell.mix, ctx.cell.config
    family, objective = cell.family, cell.objective
    mesh = cell_mesh(cell.chips)
    chips = mesh.devices.size
    devices = list(mesh.devices.flat)
    seq, global_batch, chunk = mix["seq"], mix["global_batch"], mix["chunk_steps"]
    model, loss_fn, tx, step = build(cell, mesh)
    notes, ok = [], True

    def expect(cond, message):
        nonlocal ok
        if not cond:
            ok = False
            notes.append(f"CHECK FAILED: {message}")

    # ---- set-up: weights, optimizer state and batches made on the device
    repl = spmd.replicated_sharding(mesh)
    params = ctx.first_call("init_params", jax.jit(
        init_params(model, input_shapes(cell, 1)), out_shardings=repl),
        jax.random.PRNGKey(ctx.seed))
    opt_state = ctx.first_call(
        "init_optimizer", jax.jit(tx.init, out_shardings=repl), params)
    batches = ctx.first_call(
        "make_batches", objective.make_batches, ctx.seed + 1, mix["batches"],
        global_batch, seq, c, spmd.batch_sharding(mesh))

    params, opt_state, first_loss = ctx.first_call(
        "train_step", step, params, opt_state, batches[0])
    first_loss = float(first_loss)
    for i in range(1, 3):   # the executable is loaded; two more to settle
        params, opt_state, loss = step(params, opt_state,
                                       batches[i % len(batches)])
    jax.block_until_ready(loss)
    warm_compiles = ctx.compiles.count

    # ---- the window
    setup_s = ctx.open_window()
    chunk_s, chunk_losses, losses, steps = [], [], [], 3
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.seconds:
        t1 = time.perf_counter()
        for _ in range(chunk):
            params, opt_state, loss = step(params, opt_state,
                                           batches[steps % len(batches)])
            losses.append(loss)
            steps += 1
        chunk_losses.append(float(loss))   # waits for the chunk
        chunk_s.append(time.perf_counter() - t1)
    window_s = time.perf_counter() - t0
    compiled_inside = ctx.compiles.count - warm_compiles
    losses = np.asarray(jnp.stack(losses))
    attempted, failed = len(losses), int(np.sum(~np.isfinite(losses)))

    median_chunk = statistics.median(chunk_s)
    tokens_per_step = global_batch * seq
    tokens_per_s_chip = chunk * tokens_per_step / median_chunk / chips
    notes.append(
        f"window {window_s:.2f} s: {len(chunk_s)} chunks of {chunk} steps "
        f"(the sample count), median {median_chunk:.4f} s, min "
        f"{min(chunk_s):.4f}, max {max(chunk_s):.4f}; loss {first_loss:.4f} "
        f"-> {chunk_losses[0]:.4f} -> {chunk_losses[-1]:.4f}")

    # ---- the traced slice: three chunks, after the window
    trace = None
    if ctx.trace:
        traced_chunks = 3
        with harness.profiler_slice():
            for _ in range(traced_chunks):
                for _ in range(chunk):
                    params, opt_state, loss = step(
                        params, opt_state, batches[steps % len(batches)])
                    steps += 1
                jax.block_until_ready(loss)
        trace = harness.trace_summary(traced_chunks * chunk)

    # ---- correctness, outside the window, on the weights it left
    memory_peak = harness.memory_peak_bytes(devices)
    rms, rel_max = check_logits(ctx, model, params,
                                objective.model_inputs(batches[0], 2),
                                devices[0])
    notes.append(f"reference check: logit rms error {rms:.5f} of the "
                 f"reference's rms (tolerance {LOGIT_RMS_TOL}), max error "
                 f"{rel_max:.5f} of its max")
    expect(rms <= LOGIT_RMS_TOL, f"logit rms error {rms} > {LOGIT_RMS_TOL}")
    rows = cell.vocab_rows
    want_first = objective.first_loss(family.expected_first_loss(c, rows))
    expect(abs(first_loss - want_first) <= FIRST_LOSS_BAND,
           f"first loss {first_loss:.4f} outside {want_first:.3f} +- "
           f"{FIRST_LOSS_BAND}")
    expect(failed == 0, f"{failed} steps with a non-finite loss")
    expect(chunk_losses[-1] < first_loss,
           f"loss did not fall: {first_loss:.4f} -> {chunk_losses[-1]:.4f}")
    expect(compiled_inside == 0,
           f"{compiled_inside} compilations inside the window")
    if chips > 1:
        params, opt_state, step_loss, shard_losses = check_shards(
            ctx, loss_fn, step, params, opt_state,
            batches[steps % len(batches)], mesh)
        gap = abs(step_loss - sum(shard_losses) / len(shard_losses))
        notes.append(f"{chips}-chip step loss {step_loss:.6f} vs mean of "
                     f"shard losses {shard_losses}: gap {gap:.2e} "
                     f"(tolerance {SHARD_LOSS_TOL})")
        expect(gap <= SHARD_LOSS_TOL, f"step loss leaves the shard mean by {gap}")
        expect(replicas_identical(params, mesh),
               "replicated parameters differ between chips")

    return harness.Window(
        cell=cell, peak=ctx.peak, correct=ok, attempted=attempted,
        failed=failed,
        end_to_end={"train_tokens_per_s_chip": tokens_per_s_chip,
                    "setup_s": setup_s},
        measured={"median_chunk_s": median_chunk, "chunk_steps": chunk,
                  "tokens_per_s_chip": tokens_per_s_chip, "chips": chips,
                  "seq": seq, "per_chip_batch": global_batch // chips,
                  "train_flops_per_token": family.train_flops_per_token(
                      c, rows, seq),
                  "attention_train_costs": family.attention_train_costs(
                      c, global_batch // chips, seq)},
        counters={}, first_calls=ctx.first_calls,
        memory_peak_bytes=memory_peak, trace=trace, notes=notes)
