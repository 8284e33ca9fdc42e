"""Compile each cell's programs for a v5e from shapes, without a chip, and
print XLA's memory analysis: what the TPU compiler refuses costs no chip time.

    python3 -m chipbench.aot_check [cell ...]        # default: every cell

Run on the CPU backend with the program's Pallas kernels switched on (the
command line is in ``chipbench/tests/test_rehearsal.py``); libtpu builds a
compile-only client from the ``v5e:2x2`` topology description (the method
of ``tests/test_tpu_lowering.py``). Nothing runs, so this says nothing about
results or times, and the analysis counts one program, not what else the
process keeps on the device. The output goes into each workload file's
``sizing`` field by hand.
"""

from __future__ import annotations

import glob
import json
import os
import sys

import numpy as np

from . import harness
from .jobs import train_lm


def abstract(tree, sharding):
    import jax

    return jax.tree_util.tree_map(lambda l: jax.ShapeDtypeStruct(
        l.shape, l.dtype, sharding=sharding), tree)


def analysis(compiled) -> dict:
    m = compiled.memory_analysis()
    text = compiled.as_text()
    gib = 2.0 ** 30
    return {"argument_gib": round(m.argument_size_in_bytes / gib, 3),
            "output_gib": round(m.output_size_in_bytes / gib, 3),
            "alias_gib": round(m.alias_size_in_bytes / gib, 3),
            "temp_gib": round(m.temp_size_in_bytes / gib, 3),
            "peak_estimate_gib": round(
                (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes) / gib, 3),
            "pallas_calls": text.count('custom_call_target="tpu_custom_call"'),
            "all_reduces": text.count(" all-reduce(")
            + text.count(" all-reduce-start(")}


def train_programs(cell: harness.Cell, devices) -> dict:
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu import basics

    mesh = Mesh(np.asarray(devices[:cell.chips]), (basics.MESH_AXIS,))
    model, _, tx, step = train_lm.build(cell, mesh)
    repl = NamedSharding(mesh, P())
    params = jax.eval_shape(
        train_lm.init_params(model, train_lm.input_shapes(cell, 1)),
        jax.random.PRNGKey(0))
    opt = abstract(jax.eval_shape(tx.init, params), repl)
    batch = cell.objective.abstract_batch(
        cell.mix["global_batch"], cell.mix["seq"],
        NamedSharding(mesh, P(basics.MESH_AXIS)))
    lowered = step.trace(abstract(params, repl), opt, batch).lower(
        lowering_platforms=("tpu",))
    return {"train_step": analysis(lowered.compile())}


def serve_programs(cell: harness.Cell, devices) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.serving import ServingConfig
    from horovod_tpu.serving.engine import ServingEngine

    c, one = cell.config, SingleDeviceSharding(devices[0])
    model = TransformerLM(vocab_size=cell.vocab_rows, num_layers=c["n_layer"],
                          num_heads=c["n_head"], d_model=c["n_embd"],
                          max_seq_len=c["n_positions"])
    params = abstract(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]), one)
    engine = ServingEngine(model, params, ServingConfig(
        max_context=cell.mix["max_context"], num_blocks=1))
    b, ctx = engine.config.max_batch, engine.config.max_context
    head = (c["n_head"], c["n_embd"] // c["n_head"])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    past = shape((c["n_layer"], b, ctx) + head, jnp.float32)
    programs = {
        "prefill": (engine._prefill_fn, (params, shape((1, ctx), jnp.int32))),
        "decode": (engine._decode_fn, (
            params, shape((b, 1), jnp.int32), past, past,
            shape((b, ctx), jnp.bool_), shape((b, 1), jnp.int32)))}
    return {name: analysis(jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile())
        for name, (fn, args) in programs.items()}


def main(argv=None) -> int:
    from jax.experimental import topologies

    names = list(argv if argv is not None else sys.argv[1:]) or sorted(
        os.path.basename(p)[:-5] for p in glob.glob(
            os.path.join(harness.HERE, "workloads", "*.json")))
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    for name in names:
        cell = harness.load_cell(name)
        programs = (train_programs if cell.job == "train_lm"
                    else serve_programs)(cell, devices)
        print(json.dumps({"cell": name, "topology": "v5e:2x2",
                          "chips": cell.chips, "programs": programs}),
              flush=True)
        if cell.job == "train_lm" and not programs["train_step"]["pallas_calls"]:
            print(f"{name}: no Pallas call in the compiled step: the kernels "
                  f"are not switched on in this process", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
