"""The three controls of the SDAR family's cell that must come out as **not
correct**, and the sound program beside them, each judged as the job judges
a run: through ``families/sdar_moe.reference_forward`` (NaN logits where the
reference refuses the program, so ``correct`` false) and the job's logit
check, on the weights a window of the job's own train steps leaves.

* **4 bits**: every matrix of the program rounded to 4 bits of mantissa
  (the reference reads the weights as they are);
* **the leak**: a noised query also sees the clean keys of its own block
  (``b_j <= b_i``), so a masked position reads the token it is to predict;
* **positions**: the rotary turn by a row's index ``0..2T-1`` and not by its
  position ``0..T-1`` in its half.

    python3 -m chipbench.sdar_moe_controls [--rehearse] [--seed N] [--steps N]

On the chip at the timed size; with ``--rehearse`` the toy on the CPU, under
``HVD_PALLAS=interpret`` (the leak patches the kernels' mask;
``tests/test_sdar_moe.py`` runs the same three at its own size). It prints
each block's readings (``reference_forward`` says them) and one last line, a
JSON object ``{"sound": ..., "four_bits": ..., "leak": ..., "positions":
...}`` of ``{"verdict": "match" | "no match", "logit_rms": ...}``, and exits
with 1 unless the sound program matches and every control does not.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys

from . import harness
from .families import sdar_moe as family


def low(x, bits: int):
    """``x`` rounded to ``bits`` bits of mantissa."""
    import jax.numpy as jnp

    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 2.0 ** bits) / 2.0 ** bits, e)


def four_bits(params):
    """Every matrix rounded to 4 bits of mantissa (e4m3's)."""
    import jax

    return jax.tree_util.tree_map(
        lambda l: low(l, 4) if l.ndim >= 2 else l, params)


def leaky_keep(shape, q_lo, k_lo, q_axis, blocks):
    """``pallas_kernels._blockdiff_keep`` with THE LEAK: a noised query
    sees the clean keys of its own block too (``b_j <= b_i``)."""
    import jax
    import jax.numpy as jnp

    length, half = blocks
    nb = half // length
    q_shape, k_shape = [1, 1], [1, 1]
    q_shape[q_axis], k_shape[1 - q_axis] = shape[q_axis], shape[1 - q_axis]
    gq = (q_lo + jax.lax.broadcasted_iota(
        jnp.int32, q_shape, q_axis)) // length
    gk = (k_lo + jax.lax.broadcasted_iota(
        jnp.int32, k_shape, 1 - q_axis)) // length
    u = jnp.where(gk >= nb, gk - nb, gk + nb)
    clean = gq >= nb
    return (u <= jnp.where(clean, gq - nb, gq)) \
        | (u == jnp.where(clean, -1, gq + nb))


def forget_the_kernels():
    from horovod_tpu.ops import pallas_kernels as pk

    for dispatcher in (pk._flash_fwd_once_call, pk._flash_bwd_fused):
        dispatcher.clear_cache()
    pk._flash_fullattn_vjp.cache_clear()


@contextlib.contextmanager
def leak():
    """The program's kernels under :func:`leaky_keep`, the tables of live
    tiles as they are: at B 4 the own block's clean keys lie in tiles that
    are live already. (The dispatchers forget their traces on the way in
    and out: the mask is read when a shape is first traced.)"""
    from horovod_tpu.ops import pallas_kernels as pk

    if pk.mode() == "off":
        raise RuntimeError(
            "the leak is a patch of the kernels' mask, and the kernels are "
            "off: on the CPU set HVD_PALLAS=interpret")
    sound = pk._blockdiff_keep
    pk._blockdiff_keep = leaky_keep
    forget_the_kernels()
    try:
        yield
    finally:
        pk._blockdiff_keep = sound
        forget_the_kernels()


@contextlib.contextmanager
def rows_for_positions():
    """The program's rotary turn by a row's index ``0..2T-1``: the clean
    half turned as if it came after the noised one."""
    from horovod_tpu.models import hybrid

    sound = hybrid.apply_rope
    hybrid.apply_rope = lambda x, theta, positions=None, **kw: sound(
        x, theta, None, **kw)
    try:
        yield
    finally:
        hybrid.apply_rope = sound


def reference_on(params, program_params, inputs, config: dict):
    """``family.reference_forward`` with the program run on other weights
    than the reference reads."""
    sound = family.program_trace
    family.program_trace = lambda p, *rest: sound(program_params, *rest)
    try:
        return family.reference_forward(params, inputs, config)
    finally:
        family.program_trace = sound


def judged(name, model, params, inputs, config, program_params=None,
           fault=contextlib.nullcontext) -> dict:
    """One program as the job's ``check_logits`` sees it."""
    import jax
    import jax.numpy as jnp

    harness.say(f"== {name}")
    program_params = params if program_params is None else program_params
    with fault():
        got = jax.jit(lambda p, *t: model.apply({"params": p}, *t))(
            program_params, *inputs)
        want = reference_on(params, program_params, inputs, config)
    match = bool(jnp.all(jnp.isfinite(want)))
    rms = float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want ** 2))) \
        if match else float("nan")
    harness.say(f"== {name}: {'match' if match else 'no match'}, logit rms "
                f"error {rms:.5f}")
    return {"verdict": "match" if match else "no match", "logit_rms": rms}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seed", type=int, default=5000000601)
    ap.add_argument("--steps", type=int, default=47,
                    help="train steps before the reading (a 30 s window "
                         "and its warm-up)")
    args = ap.parse_args(argv)

    import jax

    import horovod_tpu as hvd
    from horovod_tpu import spmd
    from horovod_tpu.utils import compile_cache

    from .jobs import train_lm

    harness.say(f"compile cache: {compile_cache.enable()}")
    hvd.init()
    cell = harness.load_cell("sdarmoe-train-s8192", args.rehearse)
    config, mix = cell.config, cell.mix
    mesh = train_lm.cell_mesh(1)
    model, _, tx, step = train_lm.build(cell, mesh)
    repl = spmd.replicated_sharding(mesh)
    params = jax.jit(
        train_lm.init_params(model, train_lm.input_shapes(cell, 1)),
        out_shardings=repl)(jax.random.PRNGKey(args.seed))
    batches = cell.objective.make_batches(
        args.seed + 1, mix["batches"], mix["global_batch"], mix["seq"],
        config, spmd.batch_sharding(mesh))
    opt_state = jax.jit(tx.init, out_shardings=repl)(params)
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state,
                                       batches[i % len(batches)])
        if i in (0, args.steps - 1):
            harness.say(f"step {i}: loss {float(loss):.4f}")
    del opt_state
    inputs = cell.objective.model_inputs(batches[0], 1)
    plain = model.clone(remat="none")
    result = {
        "sound": judged("sound", plain, params, inputs, config),
        "four_bits": judged("4 bits of mantissa", plain, params, inputs,
                            config, program_params=four_bits(params)),
        "leak": judged("the leak", plain, params, inputs, config,
                       fault=leak),
        "positions": judged("positions 0..2T-1", plain, params, inputs,
                            config, fault=rows_for_positions)}
    hvd.shutdown()
    print(json.dumps(result), flush=True)
    ok = result["sound"]["verdict"] == "match" and all(
        result[k]["verdict"] == "no match"
        for k in ("four_bits", "leak", "positions"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
