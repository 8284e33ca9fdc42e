"""Next-token prediction (``"objective": "next_token"``): what of a training
cell is this objective's and neither the job's nor its architecture's.

A batch is ``(tokens, targets)``, a sequence and the same sequence one
position on; the model takes the tokens, gives logits on every position, and
the loss is the library's full-logit ``lm_loss`` against the targets. An
objective gives the five names below and nothing else (``PERF.md`` section
3), and holds no arithmetic of a loss: that is the library's. ``jax`` and
the program are imported inside the functions, as the jobs and families do.
"""

from __future__ import annotations

from .. import traffic


def make_batches(seed: int, count: int, global_batch: int, seq: int,
                 config: dict, sharding):
    """``count`` batches ``(tokens, targets)`` of uniform ids over the
    configuration's ``vocab_size``, made on the device in one jitted call."""
    return traffic.token_batches(seed, count, global_batch, seq,
                                 config["vocab_size"], sharding)


def loss(model):
    """The ``loss_fn(params, batch)`` that ``spmd.make_train_step`` takes."""
    from horovod_tpu.models.transformer import lm_loss

    def loss_fn(params, batch):
        tokens, targets = batch
        return lm_loss(model.apply({"params": params}, tokens), targets)

    return loss_fn


def model_inputs(batch, sequences: int):
    """What ``model.apply({"params": p}, *inputs)`` takes, cut to the first
    ``sequences`` rows: the tokens."""
    tokens, _ = batch
    return (tokens[:sequences],)


def abstract_batch(global_batch: int, seq: int, sharding):
    """The batch as ``ShapeDtypeStruct``s."""
    import jax
    import jax.numpy as jnp

    tok = jax.ShapeDtypeStruct((global_batch, seq), jnp.int32,
                               sharding=sharding)
    return (tok, tok)


def first_loss(family_first_loss: float) -> float:
    """The mean over every position, each counted once: what the family's
    initialisation gives, unchanged."""
    return family_first_loss
