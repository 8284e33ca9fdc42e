"""Transformer LM + sequence-parallel training.

The SP correctness bar mirrors the reference's DP tests (rank-dependent data,
assert the distributed result equals the single-device computation on the
concatenated data, `test_torch.py` optimizer tests): here the sharded axes are
batch AND sequence, and parity is against full-sequence single-device math.
"""

from collections import Counter

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

from horovod_tpu.models.transformer import (
    TransformerLM, TransformerLMTiny, lm_loss)
from horovod_tpu.parallel import (
    make_dp_sp_mesh, make_sp_forward, make_sp_train_step, replicate_to_mesh,
    sp_model)

VOCAB = 97  # prime: catches stride/reshape bugs


def _tiny(attn_fn=None):
    return TransformerLMTiny(vocab_size=VOCAB, dtype=jnp.float32,
                             attn_fn=attn_fn)


def _data(rng, b, t):
    tokens = jnp.asarray(rng.randint(0, VOCAB, (b, t + 1)))
    return tokens[:, :-1], tokens[:, 1:]  # inputs, shifted targets


def test_forward_shapes_and_loss():
    model = _tiny()
    rng = np.random.RandomState(0)
    tokens, targets = _data(rng, 2, 64)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    logits = model.apply({"params": params}, tokens)
    assert logits.shape == (2, 64, VOCAB)
    loss = lm_loss(logits, targets)
    # ~uniform at init: loss close to log(V)
    assert abs(float(loss) - np.log(VOCAB)) < 0.5


def test_parameter_tree_and_scope_names_are_what_their_readers_expect():
    """The names a saved checkpoint, the benchmark's plain reference
    (``chipbench/reference.py``) and its scope classes
    (``chipbench/scope_classes/``) read: the model's norms are Flax's
    ``nn.LayerNorm`` under ``ln_attn``, ``ln_mlp`` and ``ln_f``."""
    from chipbench import reference

    model = TransformerLM(vocab_size=VOCAB, num_layers=2, num_heads=2,
                          d_model=64, max_seq_len=32, dtype=jnp.float32)
    tokens, _ = _data(np.random.RandomState(0), 2, 32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    assert set(params) == {"tok_emb", "pos_emb", "block_0", "block_1",
                           "ln_f"}
    norm = {"scale": ((64,), jnp.float32), "bias": ((64,), jnp.float32)}
    for block in (params["block_0"], params["block_1"]):
        assert set(block) == {"ln_attn", "qkv", "proj", "ln_mlp", "mlp_in",
                              "mlp_out"}
        for name in ("ln_attn", "ln_mlp"):
            assert {k: (v.shape, v.dtype)
                    for k, v in block[name].items()} == norm
    assert {k: (v.shape, v.dtype) for k, v in params["ln_f"].items()} == norm
    # the reference reads the tree by these names and nothing else
    np.testing.assert_allclose(
        np.asarray(model.apply({"params": params}, tokens)),
        np.asarray(reference.forward(params, tokens, n_head=2, eps=1e-6)),
        rtol=2e-4, atol=2e-4)
    # the scope paths of the compiled program's operations
    text = jax.jit(lambda p, x: model.apply({"params": p}, x)).lower(
        params, tokens).as_text(debug_info=True)
    for path in ("block_0/ln_attn", "block_1/ln_mlp", "TransformerLM/ln_f"):
        assert path in text, path


def test_sp_forward_matches_single_device():
    """Ring-attention SP forward over (1, 4) == full-sequence forward."""
    mesh = make_dp_sp_mesh(dp=1, sp=4)
    rng = np.random.RandomState(1)
    tokens, _ = _data(rng, 2, 128)  # 32 per shard

    single = _tiny()
    params = single.init(jax.random.PRNGKey(1), tokens)["params"]
    ref = single.apply({"params": params}, tokens)

    fwd = make_sp_forward(sp_model(
        TransformerLMTiny, vocab_size=VOCAB, dtype=jnp.float32), mesh)
    out = fwd(replicate_to_mesh(params, mesh), tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_sp_train_step_matches_single_device():
    """One SGD step on a (2, 2) mesh == one step on the full batch/sequence
    single-device — gradient flow through the ring (ppermute AD) is exact."""
    mesh = make_dp_sp_mesh(dp=2, sp=2)
    rng = np.random.RandomState(2)
    tokens, targets = _data(rng, 4, 64)

    single = _tiny()
    params = single.init(jax.random.PRNGKey(2), tokens)["params"]
    tx = optax.sgd(0.1)
    opt_state = tx.init(params)

    def single_step(p, o):
        loss, g = jax.value_and_grad(
            lambda p: lm_loss(single.apply({"params": p}, tokens),
                              targets))(p)
        up, o = tx.update(g, o, p)
        return optax.apply_updates(p, up), o, loss

    ref_params, _, ref_loss = jax.jit(single_step)(params, opt_state)

    step = make_sp_train_step(sp_model(
        TransformerLMTiny, vocab_size=VOCAB, dtype=jnp.float32),
        tx, mesh)
    sp_params, _, sp_loss = step(replicate_to_mesh(params, mesh),
                                 replicate_to_mesh(opt_state, mesh),
                                 tokens, targets)

    assert abs(float(sp_loss) - float(ref_loss)) < 1e-5
    flat_ref = jax.tree.leaves(ref_params)
    flat_sp = jax.tree.leaves(sp_params)
    for a, b in zip(flat_sp, flat_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


def test_sp_training_converges():
    """Loss decreases over a few steps on a fixed batch (end-to-end sanity
    of the ring backward under jit + donated buffers)."""
    mesh = make_dp_sp_mesh(dp=2, sp=4)
    rng = np.random.RandomState(3)
    tokens, targets = _data(rng, 2, 128)

    model = sp_model(TransformerLMTiny, vocab_size=VOCAB, dtype=jnp.float32)
    params = _tiny().init(jax.random.PRNGKey(3), tokens)["params"]
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)
    step = make_sp_train_step(model, tx, mesh)

    params = replicate_to_mesh(params, mesh)
    opt_state = replicate_to_mesh(opt_state, mesh)
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.1, losses


def test_pos_offset_changes_output():
    """Sequence-sharded callers rely on pos_offset selecting global position
    embeddings; offset 0 vs t must differ."""
    model = _tiny()
    rng = np.random.RandomState(4)
    tokens, _ = _data(rng, 1, 32)
    params = model.init(jax.random.PRNGKey(4), tokens)["params"]
    a = model.apply({"params": params}, tokens, pos_offset=0)
    b = model.apply({"params": params}, tokens, pos_offset=32)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-4


def test_over_length_sequence_fails_loudly():
    """Positions past max_seq_len must raise, not silently clip to the last
    position embedding (jnp.take clips by default)."""
    model = _tiny()
    rng = np.random.RandomState(5)
    tokens, _ = _data(rng, 1, 32)
    params = model.init(jax.random.PRNGKey(5), tokens)["params"]
    with pytest.raises(ValueError, match="max_seq_len"):
        model.apply({"params": params}, tokens,
                    pos_offset=model.max_seq_len - 16)
    long_toks = np.zeros((1, model.max_seq_len + 1), np.int32)
    with pytest.raises(ValueError, match="max_seq_len"):
        model.apply({"params": params}, long_toks)


def test_sp_over_length_global_sequence_fails_loudly():
    """Inside shard_map pos_offset is traced, so the model can't see the
    GLOBAL length; the step builder must enforce sp*t_local <= max_seq_len
    at trace time (silent jnp.take clipping otherwise)."""
    import optax

    mesh = make_dp_sp_mesh(dp=1, sp=4)
    model = sp_model(TransformerLMTiny, vocab_size=VOCAB, dtype=jnp.float32)
    rng = np.random.RandomState(6)
    # global T = 4 * 160 = 640 > TransformerLMTiny max_seq_len 512
    tokens, targets = _data(rng, 2, 640)
    params = _tiny().init(jax.random.PRNGKey(6),
                          tokens[:, :128])["params"]
    fwd = make_sp_forward(model, mesh)
    with pytest.raises(ValueError, match="max_seq_len"):
        fwd(replicate_to_mesh(params, mesh), tokens)
    tx = optax.sgd(1e-3)
    step = make_sp_train_step(model, tx, mesh)
    opt_state = tx.init(params)
    with pytest.raises(ValueError, match="max_seq_len"):
        step(replicate_to_mesh(params, mesh),
             replicate_to_mesh(opt_state, mesh), tokens, targets)


def test_sp_mesh_validation():
    with pytest.raises(ValueError, match="need 16 devices"):
        make_dp_sp_mesh(dp=4, sp=4)


# ----------------------------------------------- remat + chunked-loss levers
@pytest.mark.parametrize("kernels", ["off", "interpret"])
@pytest.mark.parametrize("mode", ["full", "dots"])
def test_remat_matches_no_remat(mode, kernels, monkeypatch):
    """jax.checkpoint must change memory, never math: the loss and every
    gradient leaf compare with remat="none"'s. With the kernels in interpret
    mode attention goes through flash_attention's custom_vjp, whose named
    outputs the policies keep; off, through the jnp reference."""
    monkeypatch.setenv("HVD_PALLAS", kernels)
    rng = np.random.RandomState(3)
    tokens, targets = _data(rng, 2, 64)
    base = TransformerLMTiny(vocab_size=VOCAB, dtype=jnp.float32)
    params = base.init(jax.random.PRNGKey(0), tokens)["params"]

    def loss_and_grads_for(remat):
        m = TransformerLMTiny(vocab_size=VOCAB, dtype=jnp.float32,
                              remat=remat)
        loss, g = jax.value_and_grad(lambda p: lm_loss(
            m.apply({"params": p}, tokens), targets))(params)
        return [loss] + jax.tree_util.tree_leaves(g)

    for a, b in zip(loss_and_grads_for("none"), loss_and_grads_for(mode)):
        # remat re-fuses the backward HLO, so low-order fp32 bits may
        # legitimately differ; the invariant is numerical equality
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def _saved_intermediates(remat, num_layers, tokens, targets, capsys):
    """What jax.ad_checkpoint.print_saved_residuals lists for the loss's
    gradient, arguments (the parameters) left out: a Counter of shapes, and
    the lines themselves."""
    m = TransformerLMTiny(vocab_size=VOCAB, dtype=jnp.float32, remat=remat,
                          num_layers=num_layers)
    params = m.init(jax.random.PRNGKey(0), tokens)["params"]
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(
        lambda p: lm_loss(m.apply({"params": p}, tokens), targets), params)
    lines = [l for l in capsys.readouterr().out.splitlines()
             if " from the argument " not in l]
    return Counter(l.split()[0] for l in lines), lines


@pytest.mark.parametrize("remat,per_layer", [
    # B, T, D = 2, 128, 128 and H = 2: the block's input, the kernel's
    # heads-major output (as large as a [B, T, D]) and its row statistics,
    # the [BH, 1, T] rows the kernels write and read: the positions on the
    # lanes, no trailing 1 for the TPU to pad to 128
    ("full", {"f32[2,128,128]": 1, "f32[4,128,64]": 1, "f32[4,1,128]": 1}),
    # no policy reads the names: every residual of the kernel is kept, q, k
    # and v heads-major beside its two outputs, the statistics the same rows
    ("none", {"f32[4,128,64]": 4, "f32[4,1,128]": 1}),
])
def test_remat_saved_residuals(remat, per_layer, monkeypatch, capsys):
    """Under "full" one more layer saves one block input, one flash_out and
    one flash_lse, and no other tensor. What a layer adds is the difference
    between three layers and two, so nothing outside the blocks (embedding,
    final LayerNorm, head, loss) is in it."""
    monkeypatch.setenv("HVD_PALLAS", "interpret")
    rng = np.random.RandomState(3)
    tokens, targets = _data(rng, 2, 128)
    two, _ = _saved_intermediates(remat, 2, tokens, targets, capsys)
    three, lines = _saved_intermediates(remat, 3, tokens, targets, capsys)
    assert not two - three
    added = three - two
    assert {shape: added[shape] for shape in per_layer} == per_layer
    if remat == "full":
        assert set(added) == set(per_layer)
        assert sum("named 'flash_lse'" in l for l in lines) == 3
        # the kept output is the named one: remat puts a reduce_precision
        # on a residual that the forward pass also uses, at the name's line
        assert sum(l.startswith("f32[4,128,64]") and "pallas_kernels.py" in l
                   for l in lines) == 3


def test_remat_unknown_mode_raises():
    m = TransformerLMTiny(vocab_size=VOCAB, dtype=jnp.float32, remat="bogus")
    rng = np.random.RandomState(0)
    tokens, _ = _data(rng, 1, 32)
    with pytest.raises(ValueError, match="remat"):
        m.init(jax.random.PRNGKey(0), tokens)


def test_chunked_loss_matches_full_logits():
    """return_hidden + lm_loss_chunked == full-logit lm_loss (fp32 model, so
    the only delta is the chunked path's bf16 head matmul — compare loosely)
    and their gradients agree."""
    from horovod_tpu.models.transformer import lm_loss, lm_loss_chunked
    rng = np.random.RandomState(7)
    tokens, targets = _data(rng, 2, 64)
    model = TransformerLMTiny(vocab_size=VOCAB, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]

    def full(p):
        return lm_loss(model.apply({"params": p}, tokens), targets)

    def chunked(p):
        hid = model.apply({"params": p}, tokens, return_hidden=True)
        return lm_loss_chunked(hid, p["tok_emb"]["embedding"], targets,
                               chunk_tokens=32)

    lf, gf = jax.value_and_grad(full)(params)
    lc, gc = jax.value_and_grad(chunked)(params)
    np.testing.assert_allclose(float(lf), float(lc), rtol=2e-2)
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-2, atol=5e-2)


def test_chunked_loss_indivisible_falls_back():
    """Any (batch, seq) the full-logit path accepts must work chunked: an
    indivisible chunk_tokens silently drops to the largest divisor."""
    from horovod_tpu.models.transformer import lm_loss, lm_loss_chunked
    rng = np.random.RandomState(11)
    hid = jnp.asarray(rng.randn(2, 30, 16), jnp.float32)
    emb = jnp.asarray(rng.randn(11, 16), jnp.float32)
    tg = jnp.asarray(rng.randint(0, 11, (2, 30)))
    got = float(lm_loss_chunked(hid, emb, tg, chunk_tokens=7))
    want = float(lm_loss(hid @ emb.T, tg))
    np.testing.assert_allclose(got, want, rtol=2e-2)


@pytest.mark.integration
def test_sp_seq16384_long_context(monkeypatch):
    """VERDICT r3 #6: the sequence-parallel path actually runs at seq 16384
    — the length docs/benchmarks.md shows OOMs a single chip (17.96 GB for
    GPT-2-medium + fp32 AdamW) — over 4 virtual devices with a REAL
    16384-token sequence (tiny model dims; the sequence axis is the claim
    under test). Runs the Pallas ring-step kernels in interpret mode so the
    measured per-device memory reflects the TPU path (FA2 backward, O(T)
    residuals), not the quadratic jnp fallback. Records compiled per-device
    memory so the docs note is a measurement, not an extrapolation."""
    from functools import partial

    monkeypatch.setenv("HVD_PALLAS", "interpret")

    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.parallel import sp_model as _sp_model

    seq = 16384
    mesh = make_dp_sp_mesh(dp=1, sp=4)
    # head dim 64 (the kernel's minimum lane-aligned width) so the Pallas
    # ring step actually engages rather than the quadratic jnp fallback
    model_cls = partial(TransformerLM, num_layers=1, num_heads=1,
                        d_model=64, max_seq_len=seq)
    rng = np.random.RandomState(11)
    tokens, targets = _data(rng, 1, seq)

    model = _sp_model(model_cls, vocab_size=VOCAB, dtype=jnp.float32)
    params = model_cls(vocab_size=VOCAB, dtype=jnp.float32).init(
        jax.random.PRNGKey(11), tokens[:, :64])["params"]
    tx = optax.sgd(1e-2)
    opt_state = tx.init(params)
    step = make_sp_train_step(model, tx, mesh)

    params = replicate_to_mesh(params, mesh)
    opt_state = replicate_to_mesh(opt_state, mesh)
    compiled = step.lower(params, opt_state, tokens, targets).compile()
    mem = compiled.memory_analysis()
    params, opt_state, loss = step(params, opt_state, tokens, targets)
    assert np.isfinite(float(loss)), float(loss)
    # ring attention keeps per-device temporaries linear in LOCAL seq: the
    # activation working set must stay far below the quadratic [T, T]
    # score tensor a naive global attention would allocate (16384^2 f32 =
    # 1 GiB per batch x head). docs/benchmarks.md cites this number — if
    # the measurement becomes unavailable, skip LOUDLY rather than letting
    # the claim ride an assert that never ran.
    if mem is None or not hasattr(mem, "temp_size_in_bytes"):
        pytest.skip("compiled.memory_analysis() unavailable on this jax — "
                    "the docs/benchmarks.md 35 MiB figure is unverified "
                    "here")
    temp = int(mem.temp_size_in_bytes)
    assert temp < 256 * 2 ** 20, (
        f"per-device temp {temp/2**20:.0f} MiB at seq {seq} — the "
        "sp path should be linear in local sequence length (the quadratic "
        "fallback measures ~1495 MiB)")
    print(f"seq16384 per-device: temp {temp/2**20:.1f} MiB, "
          f"args {mem.argument_size_in_bytes/2**20:.1f} MiB, "
          f"output {mem.output_size_in_bytes/2**20:.1f} MiB")
