"""The depthwise causal conv (``ops/ssd.causal_conv1d``): the Pallas kernel
pair under its hand-written backward pass, run through the interpreter,
against the jnp form that autodiff takes back, which is what the same call
runs with kernels off; the route; and the module that hands a mixer's parts
through it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import hybrid
from horovod_tpu.ops import pallas_kernels as pk
from horovod_tpu.ops import ssd

#: the four call sites at small size: (taps, bias, activation, channels)
SITES = {
    "granite_mamba2": (4, True, "silu", 256),
    "nemotron_mamba2": (4, True, "silu", 384),
    "qwen3next_delta": (4, False, "silu", 128),
    "lfm2_short_conv": (3, False, None, 256),
}


@pytest.fixture
def tiles_of_32(monkeypatch):
    """T tiles of 32 positions (two halos) taken in runs of 16 rows, so
    that a sequence of 96 is three tiles, the halo crosses two tile edges
    and a tile is more than one run."""
    monkeypatch.setattr(pk, "_CONV_TILE_T", 32)
    monkeypatch.setattr(pk, "_CONV_CHUNK", 16)
    for fn in (pk._conv_fwd, pk._conv_bwd):
        fn.clear_cache()
    yield
    for fn in (pk._conv_fwd, pk._conv_bwd):
        fn.clear_cache()


def operands(site, batch, t, dtype, seed=0):
    k, bias, _, c = SITES[site]
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (batch, t, c), jnp.float32).astype(dtype)
    kernel = jax.random.uniform(keys[1], (k, c), jnp.float32, -0.5, 0.5)
    b = 0.1 * jax.random.normal(keys[2], (c,), jnp.float32) if bias else None
    dy = jax.random.normal(keys[3], (batch, t, c), jnp.float32).astype(dtype)
    return x, kernel, b, dy


def both_paths(monkeypatch, site, x, kernel, bias, dy):
    """``(y, dx, dkernel, dbias)`` on the kernel path (interpreted) and on
    the reference path."""
    out = {}
    for mode, path in (("interpret", "pallas"), ("0", "reference")):
        monkeypatch.setenv("HVD_PALLAS", mode)
        assert pk.kernel_path("causal_conv", x, kernel) == path
        y, back = jax.vjp(lambda *a: ssd.causal_conv1d(
            *a, activation=SITES[site][2]), x, kernel, bias)
        out[path] = (y,) + back(dy)
    return out["pallas"], out["reference"]


def bf16_units(got, want):
    """The largest distance in units of ``want``'s last bf16 place (that
    of 2 ** -6 for a smaller value: a sum of terms of order one that
    cancel is off by their float32 rounding, not by its own)."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    unit = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 2.0 ** -6)))
                   - 7)
    return float(np.max(np.abs(got - want) / unit))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("batch,t", [(1, 32), (2, 96), (1, 48)],
                         ids=["one_tile", "three_tiles_two_rows",
                              "a_tile_and_half"])
@pytest.mark.parametrize("site", sorted(SITES))
def test_kernels_match_the_jnp_form_and_its_gradients(
        site, batch, t, dtype, tiles_of_32, monkeypatch):
    """Outputs and every gradient. A tile's first ``K - 1`` rows read the
    tile before (a halo that crosses a tile edge), a sequence's first read
    zeros (with two rows the held halo block is real data of the row
    itself, which a kernel that did not zero it would add), and ``dx``'s
    last rows read the ``dy`` after their tile and zeros at the end."""
    x, kernel, bias, dy = operands(site, batch, t, dtype)
    assert pk.conv_route(t, x.shape[2], kernel.shape[0],
                         x.dtype.itemsize)["tile_t"] == (16 if t == 48 else 32)
    got, want = both_paths(monkeypatch, site, x, kernel, bias, dy)
    (y, dx, dk, db), (y0, dx0, dk0, db0) = got, want
    assert y.dtype == dx.dtype == x.dtype
    assert dk.dtype == dk0.dtype == jnp.float32 and dk.shape == kernel.shape
    if dtype == jnp.float32:
        np.testing.assert_allclose(y, y0, rtol=2e-6, atol=2e-6)
        np.testing.assert_allclose(dx, dx0, rtol=2e-6, atol=2e-6)
    else:
        assert bf16_units(y, y0) <= 1.0
        assert bf16_units(dx, dx0) <= 1.0
    # float32 sums over batch x T terms, in another order
    scale = float(np.abs(dk0).max())
    np.testing.assert_allclose(dk, dk0, rtol=1e-5, atol=1e-5 * scale)
    if bias is None:
        assert db is None and db0 is None
    else:
        assert db.dtype == jnp.float32 and db.shape == bias.shape
        np.testing.assert_allclose(db, db0, rtol=1e-5,
                                   atol=1e-5 * float(np.abs(db0).max()))


def test_the_residuals_are_the_operands(monkeypatch):
    """What the backward pass keeps of a bf16 call is ``x`` and the taps:
    no float32 array of ``[b, T, C]``."""
    monkeypatch.setenv("HVD_PALLAS", "interpret")
    x, kernel, bias, _ = operands("granite_mamba2", 2, 64, jnp.bfloat16)
    _, back = jax.vjp(lambda *a: ssd.causal_conv1d(*a, activation="silu"),
                      x, kernel, bias)
    kept = [a for a in jax.tree.leaves(back) if hasattr(a, "shape")]
    assert any(a.shape == x.shape and a.dtype == jnp.bfloat16 for a in kept)
    assert not [a.shape for a in kept
                if a.dtype == jnp.float32 and a.size >= x.size]


#: (positions, channels, taps) of the four cells' convs, whole and as the
#: parts their mixers hand over -> the lane tile each takes
CELLS = {
    "granite4hm-train-s4096": (4096, 4352, 4, 128),
    "granite4hm-train-s4096 x": (4096, 4096, 4, 128),
    "granite4hm-train-s4096 B, C": (4096, 128, 4, 128),
    "nemotron3s-train-s4096": (4096, 10240, 4, 128),
    "nemotron3s-train-s4096 x": (4096, 8192, 4, 128),
    "nemotron3s-train-s4096 B, C": (4096, 1024, 4, 128),
    "qwen3next-train-s16384": (16384, 8192, 4, 128),
    "qwen3next-train-s16384 q, k": (16384, 2048, 4, 128),
    "qwen3next-train-s16384 v": (16384, 4096, 4, 128),
    "lfm2moe-train-s8192": (8192, 2048, 3, 128),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_route_takes_the_cells_shapes(cell):
    t, c, k, tile_c = CELLS[cell]
    assert pk.conv_route(t, c, k, 2) == {
        "path": "pallas", "tile_t": pk._CONV_TILE_T, "tile_c": tile_c}


@pytest.mark.parametrize("t,c,k,itemsize", [
    (4096, 4352 + 64, 4, 2),     # channels that are no whole lane widths
    (4096, 64, 4, 2),
    (4096, 4352, 9, 2),          # more taps than the halo is read for
    (4100, 4352, 4, 2),          # positions that are no whole halos
    (8, 128, 4, 2),
    (4096, 4352, 4, 1),
], ids=["lanes", "narrow", "taps", "positions", "short", "int8"])
def test_route_refuses(t, c, k, itemsize):
    assert pk.conv_route(t, c, k, itemsize) == {
        "path": "reference", "tile_t": None, "tile_c": None}


def test_kernel_path_says_which_path_runs(monkeypatch):
    x, kernel, _, _ = operands("lfm2_short_conv", 1, 32, jnp.bfloat16)
    monkeypatch.setenv("HVD_PALLAS", "0")
    assert pk.kernel_path("causal_conv", x, kernel) == "reference"
    monkeypatch.setenv("HVD_PALLAS", "interpret")
    assert pk.kernel_path("causal_conv", x, kernel) == "pallas"
    assert pk.kernel_path("causal_conv", x[..., :96], kernel[:, :96]) \
        == "reference"
    monkeypatch.delenv("HVD_PALLAS")                # the CPU's default
    assert pk.kernel_path("causal_conv", x, kernel) == "reference"


def test_an_unknown_activation_is_refused():
    x, kernel, _, _ = operands("lfm2_short_conv", 1, 32, jnp.float32)
    with pytest.raises(ValueError, match="gelu"):
        ssd.causal_conv1d(x, kernel, activation="gelu")


@pytest.mark.parametrize("mode", ["0", "interpret"])
def test_a_mixers_parts_go_through_as_one_conv(mode, monkeypatch):
    """``CausalConv`` over several runs of channels is the conv over them
    side by side, under one ``[width, channels]`` kernel and bias."""
    monkeypatch.setenv("HVD_PALLAS", mode)
    x, _, _, _ = operands("nemotron_mamba2", 2, 32, jnp.float32)
    parts = jnp.split(x, [128, 256], axis=-1)
    conv = hybrid.CausalConv(4, activation="silu")
    params = conv.init(jax.random.PRNGKey(1), x)
    params = jax.tree.map(lambda a: a + 0.1, params)        # a bias that is not 0
    assert params["params"]["kernel"].shape == (4, 384)
    whole = conv.apply(params, x)
    got = conv.apply(params, *parts)
    assert [p.shape for p in got] == [p.shape for p in parts]
    np.testing.assert_allclose(jnp.concatenate(got, axis=-1), whole,
                               rtol=2e-6, atol=2e-6)
    monkeypatch.setenv("HVD_PALLAS", "0")
    np.testing.assert_allclose(whole, jax.nn.silu(ssd.causal_conv1d(
        x, params["params"]["kernel"], params["params"]["bias"])),
        rtol=2e-6, atol=2e-6)
