"""A routed (mixture-of-experts) feed-forward that drops no token.

Every token scores all ``E`` experts of the layer and goes to its ``k``
best; the layer is told which experts it **holds** (a tuple of ids: all of
them on a chip that holds the layer whole, the chip's share under expert
parallelism) and computes those experts' part of the result,

    y_t = sum over e chosen by t and held here of  w_te * expert_e(h_t)

with ``expert_e(h) = W2_e (silu(a) * b)``, ``[a, b] = W1_e h``. What the
experts held elsewhere add is their chips' to compute and to send: nothing
here stands in for them, and on one chip the layer runs without its
exchange.

No capacity that drops and no one-hot dispatch over experts x tokens: the
(token, expert) assignments are sorted by expert, the rows of the experts
held gathered in that order, the two matrix products done as **grouped**
products over the experts' row ranges (``jax.lax.ragged_dot``, which XLA's
TPU compiler turns into a tiled kernel that walks only the tiles a group
has rows in), and the rows summed back into their tokens. Shapes are
static: the expert stage is compiled at two row counts, up to the worst
case, every assignment landing here (``tokens * k`` rows), and a step runs
the smallest that holds its rows (:func:`capacities`), so that the cost
follows the rows really routed here.

``parallel/expert.py``'s ``MoEMLP`` is the older stand-alone block (top-1,
capacity with drops, its own train step); this layer lives inside
``models/hybrid.py`` and trains through ``spmd.make_train_step``.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: added to the sum of a token's chosen scores before it divides them
#: (the published modelling code's ``1e-6``)
NORM_EPS = 1e-6


def route(logits, bias, top_k: int):
    """Sigmoid routing with a selection bias: ``scores = sigmoid(logits)``
    ``[N, E]`` in float32; each token's ``top_k`` experts are those with the
    largest ``scores + bias`` (the bias steers the choice only: it is under
    ``stop_gradient`` and not in the weights); the weights are the chosen
    experts' scores renormalised to sum to one. Returns ``(chosen [N, k]
    int32, weights [N, k] float32, scores [N, E])``."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
    # (a masked sum, not take_along_axis: its gather is the slowest thing in
    # the router on the chip, and its transpose a scatter)
    picked = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1],
                                    dtype=scores.dtype)
                     * scores[..., None, :], axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + NORM_EPS)
    return chosen, weights, scores


def dispatch(chosen, held: Sequence[int], num_experts: int):
    """Sort the ``N * k`` (token, expert) assignments by expert, those of
    the experts held here first, in the order of ``held``.

    Returns ``(order, inverse, group_sizes)``: ``order[r]`` is the
    assignment (``token * k + slot``) that sorted row ``r`` holds,
    ``inverse`` its inverse permutation, and ``group_sizes [len(held)]`` the
    rows of each expert held. Rows from ``sum(group_sizes)`` on belong to
    experts held elsewhere."""
    local = np.full((num_experts,), len(held), np.int32)
    local[list(held)] = np.arange(len(held), dtype=np.int32)
    group = jnp.asarray(local)[chosen.reshape(-1)]           # [N * k]
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32), unique_indices=True)
    group_sizes = jnp.sum(
        group[:, None] == jnp.arange(len(held), dtype=jnp.int32), axis=0,
        dtype=jnp.int32)
    return order, inverse, group_sizes


def capacities(assignments: int, held: int, num_experts: int) -> Tuple[int, ...]:
    """The static row counts the expert stage is compiled at, ascending:
    twice the rows a balanced router sends here (``assignments * held /
    num_experts``, rounded up to whole sublane tiles) and the worst case,
    every assignment. A step runs the smallest that holds its rows, so the
    cost of moving and activating rows follows the routing and no row is
    ever dropped. (Not finer: a router trained on a share of the experts
    learns to prefer them, 1.5 times the balanced rows within 50 steps, and
    a count inside the range the rows wander through makes the step's time
    jump as each layer crosses it: PERF.md, PR 32.)"""
    balanced = assignments * held / num_experts
    return tuple(sorted({min(assignments, -(-int(2 * balanced) // 8) * 8),
                         assignments}))


# Rows move between token order [N, d] and sorted-row order [R, d] by
# gathers in both directions: ``take_rows`` and ``put_rows`` are each
# other's transposes (autodiff's would be a scatter-add of R rows, which
# the chip runs at a third of the speed: PERF.md, PR 32).
@jax.custom_vjp
def take_rows(x, token, slots):
    """``x[token]``: the row of each sorted row's token (``token`` ``[R]``).
    ``slots`` ``[N, k]`` is the way back: the sorted row of each of a
    token's assignments, ``R`` where it has none here."""
    return x[token]


@jax.custom_vjp
def put_rows(rows, token, slots):
    """``y[n] = sum_j rows[slots[n, j]]``, a slot of ``R`` adding nothing:
    each token's rows summed in float32, in ``rows.dtype``."""
    padded = jnp.concatenate([rows, jnp.zeros((1, rows.shape[1]), rows.dtype)])
    y = sum(padded[slots[:, j]].astype(jnp.float32)
            for j in range(slots.shape[1]))
    return y.astype(rows.dtype)


take_rows.defvjp(
    lambda x, token, slots: (x[token], (token, slots)),
    lambda res, g: (put_rows(g, *res), None, None))
put_rows.defvjp(
    lambda rows, token, slots: (put_rows(rows, token, slots), (token, slots)),
    lambda res, g: (take_rows(g, *res), None, None))


def grouped_matmul(lhs, rhs, group_sizes):
    """``out[r] = lhs[r] @ rhs[g]`` for row ``r`` in group ``g``: ``lhs``
    ``[R, K]`` with its rows in group order, ``rhs`` ``[G, K, N]``,
    ``group_sizes`` ``[G]`` int32 (a group may be empty). Rows from
    ``sum(group_sizes)`` on are in no group: nothing is computed for them
    and what they hold is unspecified (on the chip: whatever was there).
    Float32 accumulation, ``lhs.dtype`` out; differentiable in ``lhs`` and
    ``rhs``."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)


def _experts_at(rows: int, h, w_in, w_out, weights, order, inverse,
                group_sizes):
    """The expert stage at a static capacity of ``rows`` sorted rows, which
    must hold every row of the experts here (``sum(group_sizes) <= rows``)."""
    top_k = weights.shape[1]
    with jax.named_scope("dispatch"):
        picked = order[:rows]
        token = picked // top_k
        here = jnp.sum(group_sizes)
        # an assignment held elsewhere has no row here, whatever its place
        # in the sorted order: the rows from ``here`` on are in no group
        slots = jnp.where(inverse < here, inverse, rows).reshape(-1, top_k)
        valid = (jnp.arange(rows, dtype=jnp.int32) < here)[:, None]
        x = take_rows(h, token, slots)
    with jax.named_scope("experts"):
        gate, up = jnp.split(grouped_matmul(
            x, w_in.astype(h.dtype), group_sizes), 2, axis=-1)
        out = grouped_matmul(jax.nn.silu(gate) * up, w_out.astype(h.dtype),
                             group_sizes)
    with jax.named_scope("combine"):
        # a row of no group holds whatever the product left there. No slot
        # points at it, so it reaches no token, forward or backward; it is
        # zeroed so that it reaches no routing weight's gradient either
        weighted = jnp.where(valid, out, 0).astype(jnp.float32) \
            * weights.reshape(-1)[picked][:, None]
        return put_rows(weighted.astype(h.dtype), token, slots)


def _smallest_that_holds(sizes: Tuple[int, ...], group_sizes, fn, *operands):
    """``fn(size, *operands)`` at the smallest of ``sizes`` (ascending, the
    last one the worst case) that is at least ``sum(group_sizes)``."""
    index = jnp.sum(jnp.sum(group_sizes) > jnp.asarray(sizes[:-1], jnp.int32))
    return jax.lax.switch(index, [partial(fn, size) for size in sizes],
                          *operands)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _experts(sizes, h, w_in, w_out, weights, order, inverse, group_sizes):
    return _smallest_that_holds(sizes, group_sizes, _experts_at, h, w_in,
                                w_out, weights, order, inverse, group_sizes)


def _experts_fwd(sizes, *operands):
    # the residuals are the operands: which capacity ran is not a shape the
    # backward pass may depend on, so it runs the stage again at its own
    return _experts(sizes, *operands), operands


def _experts_bwd(sizes, operands, dy):
    def at(rows, h, w_in, w_out, weights, order, inverse, group_sizes, dy):
        _, vjp = jax.vjp(lambda *diff: _experts_at(
            rows, *diff, order, inverse, group_sizes), h, w_in, w_out, weights)
        return vjp(dy)

    grads = _smallest_that_holds(sizes, operands[-1], at, *operands, dy)
    return (*grads, None, None, None)


_experts.defvjp(_experts_fwd, _experts_bwd)


def routed_ffn(h, router, bias, w_in, w_out, *, held: Tuple[int, ...],
               top_k: int):
    """The layer above for ``h`` ``[N, d]``: ``router`` ``[d, E]`` and
    ``bias`` ``[E]`` (float32), ``w_in`` ``[H, d, 2 f]`` (gate and up side
    by side) and ``w_out`` ``[H, f, d]`` for the ``H = len(held)`` experts
    held (cast to ``h.dtype`` here). Returns ``(y [N, d], chosen [N, k],
    scores [N, E], load [E])``: ``load`` counts the tokens each of the ``E``
    experts was chosen by (held or not).

    The backward pass runs the expert stage's forward again (it keeps the
    layer's inputs and the routing, not the ``[rows, 2 f]`` activations),
    whatever the model's ``remat``."""
    num_experts = router.shape[-1]
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            # E columns: nothing to the MXU's bf16 passes, and a choice
            # between near-equal scores should not hang on them
            logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                             precision="highest")
            chosen, weights, scores = route(logits, bias, top_k)
            load = jnp.sum(jax.nn.one_hot(chosen, num_experts,
                                          dtype=jnp.int32), axis=(0, 1))
        with jax.named_scope("dispatch"):
            order, inverse, group_sizes = dispatch(chosen, held, num_experts)
        y = _experts(capacities(order.shape[0], len(held), num_experts), h,
                     w_in, w_out, weights, order, inverse, group_sizes)
    return y, chosen, scores, load


def report_load(load, held: Sequence[int]) -> float:
    """Set ``hvd_expert_load`` (tokens an expert, by id) and
    ``hvd_moe_load_imbalance`` (max over mean, over the experts ``held``)
    from one layer's ``load`` counts, on the host. Returns the imbalance."""
    from ..metrics import instruments

    load = np.asarray(load)
    for expert, count in enumerate(load):
        instruments.expert_load().labels(expert=str(expert)).set(float(count))
    here = load[list(held)].astype(np.float64)
    imbalance = float(here.max() / max(here.mean(), 1e-9))
    instruments.moe_load_imbalance().set(imbalance)
    return imbalance
