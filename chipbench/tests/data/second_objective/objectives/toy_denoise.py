"""The harness's stand-in second objective (``"objective": "toy_denoise"``):
denoising by blocks at a toy size. A batch is three arrays: the clean ids;
a noised copy in which, block by block of the configuration's
``block_length``, a share ``t`` of the positions (``t`` uniform between the
configuration's ``min_mask_rate`` and 1, one a block) holds its
``mask_token_id``; and a weight a position, ``1 / t`` where it is masked and
nought elsewhere. The model takes the noised and the clean ids, and the loss
is the weighted cross entropy its model file exports, where the library's
would be. The five names, and no arithmetic of a loss."""


def make_batches(seed, count, global_batch, seq, config, sharding):
    import jax
    import jax.numpy as jnp

    block, low = config["block_length"], config["min_mask_rate"]
    shape = (count, global_batch, seq)

    def make(key):
        k_ids, k_rate, k_mask = jax.random.split(key, 3)
        clean = jax.random.randint(k_ids, shape, 0, config["vocab_size"],
                                   dtype=jnp.int32)
        rate = 1.0 - (1.0 - low) * jax.random.uniform(
            k_rate, (count, global_batch, seq // block))
        rate = jnp.repeat(rate, block, axis=-1)
        masked = jax.random.uniform(k_mask, shape) < rate
        noised = jnp.where(masked, config["mask_token_id"], clean)
        weights = masked / rate
        return [(clean[i], noised[i], weights[i]) for i in range(count)]

    return jax.jit(make, out_shardings=sharding)(jax.random.PRNGKey(seed))


def loss(model):
    from ..toydenoiser_model import weighted_loss

    def loss_fn(params, batch):
        clean, noised, weights = batch
        logits = model.apply({"params": params}, noised, clean)
        return weighted_loss(logits, clean, weights)

    return loss_fn


def model_inputs(batch, sequences):
    clean, noised, _ = batch
    return (noised[:sequences], clean[:sequences])


def abstract_batch(global_batch, seq, sharding):
    import jax
    import jax.numpy as jnp

    ids = jax.ShapeDtypeStruct((global_batch, seq), jnp.int32,
                               sharding=sharding)
    weights = jax.ShapeDtypeStruct((global_batch, seq), jnp.float32,
                                   sharding=sharding)
    return (ids, ids, weights)


def first_loss(family_first_loss):
    """A masked position counts ``1 / t`` and a share ``t`` is masked: the
    weights are 1 a token on average, so the family's value stands."""
    return family_first_loss
