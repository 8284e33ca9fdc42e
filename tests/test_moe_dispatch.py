"""The routed stage's index work (``ops/moe.dispatch``, ``_rows_at``) between
the router's choice and the grouped products: what it builds against a
stable argsort written here in numpy (the rule the stage has had since it
was written: rows by expert in ``held``'s order, then by ``token * k +
slot``), the layer's values against the parent's (PR 47's) bit for bit, and
the sizes of the gathers, scatters and sorts it traces where a share of the
experts is held.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import moe

EXPERTS, TOKENS = 64, 48


def _held(share: str, contiguous: bool):
    count = {"whole": 64, "half": 32, "16th": 4, "64th": 1}[share]
    if contiguous:
        first = 0 if share == "whole" else 7
        return tuple(range(first, first + count))
    # every place out of order, no two neighbours consecutive ids
    return tuple(int(e) for e in np.random.RandomState(count).permutation(
        EXPERTS)[:count])


def _chosen(kind: str, held, top_k: int, seed: int):
    """``[TOKENS, top_k]`` expert ids: ``mixed`` as a router's (distinct a
    token) with one held expert that no token chooses, ``all_here`` every
    assignment on a held expert (the worst case the larger capacity is
    for), ``none_here`` every one elsewhere."""
    rs = np.random.RandomState(seed)
    if kind == "mixed":
        pool = [e for e in range(EXPERTS) if e != held[len(held) // 2]]
        return np.stack([rs.permutation(pool)[:top_k] for _ in range(TOKENS)])
    pool = list(held) if kind == "all_here" else \
        [e for e in range(EXPERTS) if e not in held]
    return rs.choice(pool, size=(TOKENS, top_k))


_CASES = [(share, contiguous, top_k, kind)
          for share in ("whole", "half", "16th", "64th")
          for contiguous in (True, False)
          for top_k in (1, 4, 22)
          for kind in ("mixed", "all_here", "none_here")
          if not (share == "whole" and kind == "none_here")
          and not (share == "64th" and not contiguous)]


@pytest.mark.parametrize(
    "share,contiguous,top_k,kind", _CASES,
    ids=[f"{s}-{'run' if c else 'scattered'}-k{k}-{kind}"
         for s, c, k, kind in _CASES])
def test_the_stage_builds_the_stable_argsorts_rows(share, contiguous, top_k,
                                                   kind):
    """``dispatch`` and ``_rows_at`` at each capacity that holds the rows:
    the rows' assignments, tokens and weights, their order back to tokens,
    the rows a token has here and the rows an expert has, against numpy's
    stable argsort of each assignment's place in ``held``."""
    held = _held(share, contiguous)
    chosen = _chosen(kind, held, top_k, seed=len(held) + top_k)
    place = {expert: i for i, expert in enumerate(held)}
    group = np.array([place.get(int(e), len(held)) for e in chosen.ravel()])
    want_order = np.argsort(group, kind="stable")
    here = int((group < len(held)).sum())
    assert here == {"all_here": chosen.size, "none_here": 0}.get(kind, here)

    order, per_token, group_sizes = moe.dispatch(
        jnp.asarray(chosen, jnp.int32), held)
    assert order.dtype == per_token.dtype == group_sizes.dtype == jnp.int32
    np.testing.assert_array_equal(order, want_order)
    np.testing.assert_array_equal(
        group_sizes, np.bincount(group, minlength=len(held) + 1)[:len(held)])
    np.testing.assert_array_equal(
        per_token, (group.reshape(TOKENS, top_k) < len(held)).sum(axis=1))
    if kind == "mixed":
        assert int(group_sizes[len(held) // 2]) == 0

    weights = np.random.RandomState(0).rand(TOKENS, top_k).astype(np.float32)
    sizes = moe.capacities(chosen.size, len(held), EXPERTS)
    assert sizes[-1] == chosen.size
    holding = [rows for rows in sizes if rows >= here]
    if kind == "all_here":
        assert holding == [chosen.size]
    for rows in holding:
        picked, token, valid, (by_token, rows_a_token) = moe._rows_at(
            rows, top_k, order, per_token, group_sizes)
        assert picked.shape == token.shape == by_token.shape == (rows,)
        np.testing.assert_array_equal(valid[:, 0], np.arange(rows) < here)
        np.testing.assert_array_equal(picked[:here], want_order[:here])
        np.testing.assert_array_equal(token[:here],
                                      want_order[:here] // top_k)
        np.testing.assert_array_equal(
            weights.reshape(-1)[np.asarray(picked[:here])],
            weights.reshape(-1)[want_order[:here]])
        # the rows in token order, those of one token in the rows' order,
        # and the rows of no group after them all
        np.testing.assert_array_equal(
            by_token[:here],
            np.argsort(want_order[:here] // top_k, kind="stable"))
        assert sorted(np.asarray(by_token[here:])) == list(range(here, rows))
        np.testing.assert_array_equal(rows_a_token, per_token)


# ------------------------------------------------ the parent's values
def _layer(case: str):
    """A seeded layer and the call's keywords: ``(h, router, bias, w_in,
    w_out, x, keywords)``."""
    experts, held, top_k, d, f, kw = {
        "swiglu_sigmoid_scattered": (8, (6, 1, 4), 2, 32, 16, {}),
        "relu2_softmax_latent_run": (
            16, (2, 3, 4, 5), 4, 32, 16,
            {"activation": "relu2", "scoring": "softmax", "scale": 2.5}),
        "swiglu_whole": (8, tuple(range(8)), 3, 32, 16,
                         {"norm_eps": 1e-20}),
    }[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 6)
    f32 = jnp.float32
    latent = 24 if "latent" in case else d
    wide = f if kw.get("activation") == "relu2" else 2 * f
    h = jax.random.normal(keys[0], (96, d), f32)
    x = jax.random.normal(keys[5], (96, latent), f32) \
        if "latent" in case else None
    return (h, 0.5 * jax.random.normal(keys[1], (d, experts), f32),
            0.3 * jax.random.normal(keys[2], (experts,), f32),
            0.2 * jax.random.normal(keys[3], (len(held), latent, wide), f32),
            0.2 * jax.random.normal(keys[4], (len(held), f, latent), f32), x,
            dict(kw, held=held, top_k=top_k))


def _digest(a) -> str:
    a = np.asarray(a)
    return hashlib.sha256(a.tobytes()).hexdigest()[:16] + \
        f" {a.dtype}{list(a.shape)}"


#: sha256 of each array's bytes, made by running this very test on the
#: parent's tree (c35ae11, PR 47: ``git archive`` with this file laid over
#: it) on this repository's CPU image, ``ragged_dot`` path: ``y``, then the
#: gradients of ``sum(y * cos)`` in ``h``, ``router``, ``w_in``, ``w_out``
#: (and ``x``)
PARENTS = {
    "swiglu_sigmoid_scattered": [
        "d59d89c60f9ebe47 float32[96, 32]", "a0e1819084a4048a float32[96, 32]",
        "4ce2714aeffbb1b3 float32[32, 8]",
        "afd266ca0317aad0 float32[3, 32, 32]",
        "cba7a19245a4a675 float32[3, 16, 32]"],
    "relu2_softmax_latent_run": [
        "fdeab348659cdf87 float32[96, 24]", "7570e447412618e0 float32[96, 32]",
        "b892b22ecc0fd890 float32[32, 16]",
        "f994eba365925d1c float32[4, 24, 16]",
        "ee8a8cab0952d534 float32[4, 16, 24]",
        "26f44dfd1dc72450 float32[96, 24]"],
    "swiglu_whole": [
        "a8805368b5286fa1 float32[96, 32]", "7543dc337c9a57fc float32[96, 32]",
        "d075c727e8009c3a float32[32, 8]",
        "c21a17de1a6dc8f1 float32[8, 32, 32]",
        "794cf743c1564dc3 float32[8, 16, 32]"],
}


@pytest.mark.parametrize("case", sorted(PARENTS))
def test_the_layer_equals_the_parents_bit_for_bit(case):
    """``routed_ffn``'s ``y`` and its gradients on the ``ragged_dot`` path,
    float32, against fixtures made on the parent (its ``dispatch`` gathered
    each assignment's group from a table, scattered the order's inverse and
    gathered the routing weights' gradient over it): the same rows in the
    same order give the same bits. The loop reference (tests/test_lfm2_moe,
    test_nemotron_h) holds the values themselves to a tolerance; this holds
    this PR to having changed none of them."""
    h, router, bias, w_in, w_out, x, keywords = _layer(case)
    diff = (h, router, w_in, w_out) + (() if x is None else (x,))

    def loss(h, router, w_in, w_out, x=None):
        y = moe.routed_ffn(h, router, bias, w_in, w_out, x=x, **keywords)[0]
        weight = jnp.cos(jnp.arange(y.size, dtype=y.dtype)).reshape(y.shape)
        return jnp.sum(y * weight), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(diff))), has_aux=True))(*diff)
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in grads)
    assert [_digest(a) for a in (y, *grads)] == PARENTS[case]


# ------------------------------------------------ what the stage traces
def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def _index_work(jaxpr, found, skip_last_branch, outer=""):
    """(primitive, scope path, entries) of every gather, scatter and sort
    under ``jaxpr``: the slices a gather takes, the updates a scatter
    places, the keys a sort orders. The path is the scopes' and, for an
    equation of an inner program, the primitives' that hold it."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        path = f"{outer}/{eqn.source_info.name_stack}"
        inner = list(_sub_jaxprs(eqn))
        if name == "cond" and skip_last_branch:
            inner = [b.jaxpr for b in eqn.params["branches"][:-1]]
        for sub in inner:
            _index_work(sub, found, skip_last_branch,
                        f"{path}/{eqn.params.get('name', name)}")
        if name == "gather" or name.startswith("scatter"):
            entries = int(np.prod(eqn.invars[1].aval.shape[:-1]))
        elif name == "sort":
            entries = int(np.prod(eqn.invars[0].aval.shape))
        else:
            continue
        found.append((name, path, entries))
    return found


@pytest.mark.parametrize("cell,tokens,d,width,experts,held,top_k,keywords", [
    ("qwen3next-train-s16384", 16384, 2048, 512, 512, 16, 10,
     {"scoring": "softmax"}),
    ("nemotron3s-train-s4096", 4096, 1024, 2688, 512, 8, 22,
     {"activation": "relu2", "scale": 5.0, "norm_eps": 1e-20}),
    ("lfm2moe-train-s8192", 16384, 2048, 1792, 32, 8, 4, {}),
])
def test_no_index_of_the_stage_is_sized_by_the_assignments(
        cell, tokens, d, width, experts, held, top_k, keywords):
    """The gradient of ``routed_ffn`` at a cell's published sizes, a share
    of the experts held, traced and not run: outside the router, no gather
    takes, no scatter places and no sort orders ``N k`` or more entries,
    but for the one stable sort of the assignments' groups under
    ``moe/dispatch`` (0.15 ms for 163,840 keys on a v5e, under the 0.3 that
    ISSUE 48 lets stay: PERF.md, PR 48), forward and nowhere else. The
    branch compiled for the worst case, every assignment landing here, is
    left out: its ``rows`` *are* ``N k``."""
    assignments = tokens * top_k
    sizes = moe.capacities(assignments, held, experts)
    assert len(sizes) == 2 and sizes[0] < assignments == sizes[1]
    wide = width if keywords.get("activation") == "relu2" else 2 * width

    def loss(h, router, w_in, w_out):
        y = moe.routed_ffn(h, router, jnp.zeros((experts,), jnp.float32),
                           w_in, w_out, held=tuple(range(held)), top_k=top_k,
                           **keywords)[0]
        return jnp.sum(y.astype(jnp.float32) ** 2)

    s = jax.ShapeDtypeStruct
    with jax.enable_x64(False):
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(
            s((tokens, d), jnp.bfloat16), s((d, experts), jnp.float32),
            s((held, d, wide), jnp.float32), s((held, width, d), jnp.float32))
    found = [f for f in _index_work(jaxpr.jaxpr, [], skip_last_branch=True)
             if "router" not in f[1]]
    assert {name for name, _, _ in found} >= {"gather", "sort"}
    assert any(name.startswith("scatter") for name, _, _ in found)
    large = [f for f in found if f[2] >= assignments]
    assert [(name, entries) for name, _, entries in large] == \
        [("sort", assignments)], large
    assert re.search(r"moe\)?/dispatch/argsort", large[0][1]), large
    # what the smaller capacity's branch does is sized by its rows
    assert max(f[2] for f in found if f not in large) == sizes[0]
    # and the whole program, the worst case's branch with it, takes no
    # gather and places no scatter at the top level, where the parent's
    # dispatch had one of each over the assignments
    every = _index_work(jaxpr.jaxpr, [], skip_last_branch=False)
    assert not [f for f in every if "cond" not in f[1] and f[0] != "sort"
                and "router" not in f[1]], every
