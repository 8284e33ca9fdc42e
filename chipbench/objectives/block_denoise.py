"""Denoising by blocks (``"objective": "block_denoise"``): what of a training
cell is this objective's and neither the job's nor its architecture's.

A batch is three arrays ``[B, T]``: the clean ids, uniform below the
configuration's ``assumed.mask_token_id``; a noised copy in which, block by
block of ``assumed.block_length`` positions, a share ``t`` of the positions
holds the mask id (``t`` one a block, uniform on ``assumed.noise_schedule``'s
``[low, high]``); and a weight a position, ``1 / t`` where it is masked and
nought elsewhere. The model takes ``(noised, clean)`` and gives the logits
of the noised copy, and the loss is the library's ``denoise_loss`` of them
against the clean ids under the weights. The five names, and no arithmetic
of a loss: that is the library's. Shapes do not depend on the draw, so the
timed program does not either.
"""

from __future__ import annotations


def make_batches(seed: int, count: int, global_batch: int, seq: int,
                 config: dict, sharding):
    """``count`` batches ``(clean, noised, weights)``, made on the device in
    one jitted call."""
    import jax
    import jax.numpy as jnp

    assumed = config["assumed"]
    block = int(assumed["block_length"]["value"])
    mask_id = int(assumed["mask_token_id"]["value"])
    low, high = (float(assumed["noise_schedule"][k]) for k in ("low", "high"))
    shape = (count, global_batch, seq)

    def make(key):
        k_ids, k_rate, k_mask = jax.random.split(key, 3)
        clean = jax.random.randint(k_ids, shape, 0, mask_id, dtype=jnp.int32)
        rate = jax.random.uniform(k_rate, (count, global_batch, seq // block),
                                  jnp.float32, low, high)
        rate = jnp.repeat(rate, block, axis=-1)
        masked = jax.random.uniform(k_mask, shape, jnp.float32) < rate
        noised = jnp.where(masked, mask_id, clean)
        weights = masked / rate
        return [(clean[i], noised[i], weights[i]) for i in range(count)]

    return jax.jit(make, out_shardings=sharding)(jax.random.PRNGKey(seed))


def loss(model):
    """The ``loss_fn(params, batch)`` that ``spmd.make_train_step`` takes."""
    from horovod_tpu.models.transformer import denoise_loss

    def loss_fn(params, batch):
        clean, noised, weights = batch
        logits = model.apply({"params": params}, noised, clean)
        return denoise_loss(logits, clean, weights)

    return loss_fn


def model_inputs(batch, sequences: int):
    """What ``model.apply({"params": p}, *inputs)`` takes, cut to the first
    ``sequences`` rows: the noised ids, then the clean ones."""
    clean, noised, _ = batch
    return (noised[:sequences], clean[:sequences])


def abstract_batch(global_batch: int, seq: int, sharding):
    """The batch as ``ShapeDtypeStruct``s."""
    import jax
    import jax.numpy as jnp

    ids = jax.ShapeDtypeStruct((global_batch, seq), jnp.int32,
                               sharding=sharding)
    weights = jax.ShapeDtypeStruct((global_batch, seq), jnp.float32,
                                   sharding=sharding)
    return (ids, ids, weights)


def first_loss(family_first_loss: float) -> float:
    """A masked position counts ``1 / t`` and a share ``t`` of its block is
    masked: the weights are 1 a token on average, and a masked position's
    clean token is none the fresh model can tell from the others, so the
    family's value stands."""
    return family_first_loss
