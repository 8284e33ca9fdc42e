"""A routed (mixture-of-experts) feed-forward that drops no token.

Every token scores all ``E`` experts of the layer and goes to its ``k``
best; the layer is told which experts it **holds** (a tuple of ids: all of
them on a chip that holds the layer whole, the chip's share under expert
parallelism) and computes those experts' part of the result,

    y_t = sum over e chosen by t and held here of  w_te * expert_e(h_t)

with ``expert_e(h) = W2_e (silu(a) * b)``, ``[a, b] = W1_e h`` (SwiGLU) or
``W2_e relu(W1_e h)^2`` (squared ReLU, no gate), the weights of a token
summing to a scale. The experts may read something other than what the
router reads: a latent of ``h`` the caller projects down before them and
back up after their sum (``routed_ffn``'s ``x``). What the
experts held elsewhere add is their chips' to compute and to send: nothing
here stands in for them, and on one chip the layer runs without its
exchange.

No capacity that drops and no one-hot dispatch over experts x tokens: each
(token, expert) assignment's group is its expert's place among those held
(a range test, elementwise), one stable sort of those keys puts the
assignments in order, those held here first, and **everything after it is
built over the rows held here**: the first ``rows`` places of the order
are the stage's rows, gathered in that order, the two matrix products are
done as **grouped** products over the experts' row ranges, and the rows
are summed back into their tokens: put in token order (a sort of the
stage's rows, not of the assignments) and summed tile of tokens by tile as
one more grouped product, a one-hot of each row's place in its tile against
the rows (:func:`put_rows`). The routing weights' gradient is placed at the
rows' assignments. Of all ``tokens * k`` assignments nothing is gathered,
nothing scattered and no inverse of the order made (:func:`dispatch`), so
that the stage costs by the rows held here and not by every assignment
(31 in 32 of them are other chips' where a 32nd of the experts is held).
Shapes are static: the expert stage is compiled at two
row counts, up to the worst case, every assignment landing here
(``tokens * k`` rows), and a step runs the smallest that holds its rows
(:func:`capacities`), so that the cost follows the rows really routed here.

**What a trace and a run say of it.** The layer's operations lie under the
name scopes ``moe`` and, inside it, ``router``, ``dispatch``, ``experts``
and ``combine``; the branch of the row capacity that ran is one scope more,
named for what it is and not for its place among the sizes:
``capacity_all`` for the worst case and ``capacity_fit`` for any smaller
count (``.../ffn/moe/cond/branch_<n>_fun/capacity_fit/experts/...``), in the
forward pass, the recomputed one and the backward pass alike. The rows a
layer held and its experts' load leave the step only where
``HOROVOD_MOE_REPORT`` is set as the layer is traced: one host callback a
layer and execution, into :func:`report_load` (docs/moe.md).

**Which kernel runs where.** A grouped product is one of three:
:func:`grouped_matmul` (rows against their group's matrix),
:func:`grouped_matmul_t` (against its transpose) and :func:`grouped_outer`
(a group's rows against a group's rows: the matrices' gradient). On the
chip they are the Pallas kernels ``pallas_kernels.gmm`` / ``tgmm``, which
walk only the row tiles a group has rows in, at the tiles
``pallas_kernels.grouped_route`` gives the shape; off the chip
(``pallas_kernels.mode() == "off"``) and for a shape the route refuses,
``jax.lax.ragged_dot``. Nothing is set: the platform and the shape decide,
and ``pallas_kernels.kernel_path`` answers for a given call.
:func:`put_rows`' segment sum is a :func:`grouped_outer` too (its groups
the tiles of 128 tokens), under the scopes ``combine`` and ``dispatch``; the
products of the experts themselves are under ``experts``. **Seven
products a layer and step**: two forward (``x W1``, ``act W2``) and five in
the stage's backward pass, which is written out (:func:`_experts_bwd_at`;
a Pallas call has no autodiff rule): ``x W1`` again (the stage keeps its
operands, not the ``[rows, 2 f]`` activations), ``g W2^T``, ``dGU W1^T``
and the two weight gradients, for either kind of expert. ``act W2`` is not
run again: its one consumer there, the routing weights' gradient
``<out, g>``, is ``<act, g W2^T>``. (A model that recomputes its blocks
runs the two forward products again only where something after the layer
keeps their result for its own gradient, as a latent's up-projection does:
nine products then.)

``parallel/expert.py``'s ``MoEMLP`` is the older stand-alone block (top-1,
capacity with drops, its own train step); this layer lives inside
``models/hybrid.py`` and trains through ``spmd.make_train_step``.
"""

from __future__ import annotations

import math
import statistics
import sys
import threading
from collections import deque
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.env import env_on
from . import pallas_kernels as pk

#: added to the sum of a token's chosen scores before it divides them
#: (the published modelling code's ``1e-6``)
NORM_EPS = 1e-6


def route(logits, bias, top_k: int, scale: float = 1.0,
          norm_eps: float = NORM_EPS, scoring: str = "sigmoid",
          aux_loss: float = 0.0):
    """Top-k routing with a selection bias: ``scores`` ``[N, E]`` in
    float32 are ``sigmoid(logits)`` or, with ``scoring="softmax"``, the
    softmax of a token's logits over all ``E``; each token's ``top_k``
    experts are those with the largest ``scores + bias`` (the bias steers
    the choice only: it is under ``stop_gradient`` and not in the weights);
    the weights are the chosen experts' scores renormalised to sum to
    ``scale`` (their sum takes ``norm_eps`` before it divides). With
    ``aux_loss`` the scores the weights are made of carry the layer's
    auxiliary balancing loss in their backward pass (:func:`balancing`).
    Returns ``(chosen [N, k] int32, weights [N, k] float32, scores [N,
    E])``."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"scoring={scoring!r}; expected 'sigmoid' or "
                         f"'softmax'")
    logits = logits.astype(jnp.float32)
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
    if aux_loss:
        scores = balancing(scores, chosen, aux_loss)
    # (a masked sum, not take_along_axis: its gather is the slowest thing in
    # the router on the chip, and its transpose a scatter)
    picked = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1],
                                    dtype=scores.dtype)
                     * scores[..., None, :], axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + norm_eps)
    return chosen, weights if scale == 1.0 else scale * weights, scores


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def balancing(scores, chosen, coefficient: float):
    """``scores`` ``[N, E]``, as they are. Their backward pass adds the
    gradient of ``coefficient`` times the layer's auxiliary balancing loss,
    ``E sum_e f_e P_e`` with ``f_e`` the share of the ``N`` tokens that
    chose expert ``e`` (a count: no gradient; the ``f_e`` sum to ``top_k``)
    and ``P_e`` the mean of their scores for it: ``coefficient E f_e / N``
    on every token's score for ``e``. It is 'top_k' where every expert is
    chosen as often as every other and rises as the choice narrows. This
    is the load-balancing loss of the Switch Transformer (arXiv:2101.03961,
    eq. 4-6) as the Qwen3-MoE lineage computes it, a layer at a time;
    attached here, the step's loss stays the objective's own and the model
    returns nothing more (as Megatron-LM's ``MoEAuxLossAutoScaler``
    attaches its)."""
    return scores


def _balancing_fwd(scores, chosen, coefficient):
    load = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1],
                                  dtype=jnp.float32), axis=(0, 1))
    return scores, load


def _balancing_bwd(coefficient, load, d_scores):
    n, e = d_scores.shape
    return d_scores + (coefficient * e / (n * n)) * load, None


balancing.defvjp(_balancing_fwd, _balancing_bwd)


def _runs(held: Sequence[int]):
    """``held`` as its maximal runs of consecutive ids: ``[first id, its
    place in held, length]`` of each (one run for a chip's contiguous
    share, and for the layer held whole)."""
    runs = []
    for place, expert in enumerate(held):
        if runs and expert == runs[-1][0] + runs[-1][2]:
            runs[-1][2] += 1
        else:
            runs.append([expert, place, 1])
    return runs


def dispatch(chosen, held: Sequence[int]):
    """Order the ``N * k`` (token, expert) assignments by expert, those of
    the experts held here first, in the order of ``held``.

    Returns ``(order, per_token, group_sizes)``: ``order[r]`` is the
    assignment (``token * k + slot``) that sorted row ``r`` holds,
    ``per_token [N]`` the rows each token has here and ``group_sizes
    [len(held)]`` the rows of each expert held. Rows from
    ``sum(group_sizes)`` on belong to experts held elsewhere.

    What walks all ``N k`` assignments is elementwise, a count or the one
    stable sort of their keys (0.15 ms for 163,840 on a v5e, where a gather
    or a scatter of as many scalars is 0.8-1.3: PERF.md, PR 48): an
    assignment's group is its expert's place in ``held``, found by a range
    test against each run of consecutive ids (``len(held)`` for an expert
    held elsewhere), not looked up in a table of ``num_experts``; nothing
    is gathered, and no inverse of ``order`` is made (a row finds its
    assignment, ``_rows_at``; an assignment never looks for its row)."""
    group = jnp.full(chosen.shape, len(held), jnp.int32)
    for first, place, length in _runs(held):
        inside = (chosen >= first) & (chosen < first + length)
        group = jnp.where(inside, chosen - (first - place), group)
    order = jnp.argsort(group.reshape(-1), stable=True).astype(jnp.int32)
    per_token = jnp.sum(group < len(held), axis=1, dtype=jnp.int32)
    group_sizes = jnp.sum(
        group.reshape(-1, 1) == jnp.arange(len(held), dtype=jnp.int32),
        axis=0, dtype=jnp.int32)
    return order, per_token, group_sizes


def capacities(assignments: int, held: int, num_experts: int) -> Tuple[int, ...]:
    """The static row counts the expert stage is compiled at, ascending:
    ``log2(num_experts / held)`` times the rows a balanced router sends
    here (``assignments * held / num_experts``; twice at least) or an
    eighth of the worst case, whichever is more (rounded up to whole
    sublane tiles), and the worst case, every assignment. A step runs the
    smallest that holds its rows, so the cost of moving and activating rows
    follows the routing and no row is ever dropped. (Not finer, and the
    margin wider the smaller the share: a router trained on a share of the
    experts learns to prefer them, 1.5 times the balanced rows within 50
    steps where a quarter is held, 2.1 times within 70 where a 16th is and
    2 to 5 times where a 64th is, and a count inside the range the rows
    wander through makes the step's time jump as each layer crosses it, by
    20 ms a layer from 2,816 rows to 90,112 and by the whole spread of a
    cell's runs at 10,240 of 81,920: PERF.md, PR 32, PR 34 and PR 39. Under
    an eighth of the worst case a smaller count saves little and is
    crossed.) Which count a layer ran is in every trace, the scopes
    ``capacity_fit`` / ``capacity_all``: a crossing is read in the
    benchmark's ``moe_worst_case_ms``, and counted a layer by
    :func:`report_load`."""
    balanced = assignments * held / num_experts
    margin = max(2.0, math.log2(num_experts / held))
    smaller = max(int(margin * balanced), assignments // 8)
    return tuple(sorted({min(assignments, -(-smaller // 8) * 8), assignments}))


def _kernel_tiles(name: str, lhs, rhs, n: int, key: str = "tiling"):
    """The tiles the Pallas kernel of dispatcher ``name`` takes for these
    operands (``n`` the product's other width), None where the call goes to
    ``jax.lax.ragged_dot``: ``pallas_kernels.kernel_path`` decides, as for
    every kernel there."""
    if pk.kernel_path(name, lhs, rhs) == "reference":
        return None
    return pk.grouped_route(*lhs.shape, n, lhs.dtype.itemsize)[key]


def grouped_matmul(lhs, rhs, group_sizes):
    """``out[r] = lhs[r] @ rhs[g]`` for row ``r`` in group ``g``: ``lhs``
    ``[R, K]`` with its rows in group order, ``rhs`` ``[G, K, N]``,
    ``group_sizes`` ``[G]`` int32 (a group may be empty). Rows from
    ``sum(group_sizes)`` on are in no group: nothing is computed for them
    and what they hold is unspecified (on the chip: whatever was there).
    Float32 accumulation, ``lhs.dtype`` out. On the chip the Pallas kernel
    ``pallas_kernels.gmm`` (``moe_gmm`` in a trace) at
    ``pallas_kernels.grouped_route``'s tiles; off it, and for a shape the
    route refuses, ``jax.lax.ragged_dot``, which alone is differentiable:
    the expert stage's backward pass is written out below."""
    tiling = _kernel_tiles("grouped_matmul", lhs, rhs, rhs.shape[2])
    if tiling:
        return pk.gmm(lhs, rhs, group_sizes, tiling=tiling)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)


def grouped_matmul_t(lhs, rhs, group_sizes):
    """``out[r] = lhs[r] @ rhs[g].T``: :func:`grouped_matmul` against the
    groups' matrices transposed, ``rhs`` ``[G, N, K]`` as the forward
    product holds it (the same kernel reading its tiles the other way; no
    transposed copy)."""
    tiling = _kernel_tiles("grouped_matmul_t", lhs, rhs, rhs.shape[1])
    if tiling:
        return pk.gmm(lhs, rhs, group_sizes, tiling=tiling,
                      transpose_rhs=True)
    return jax.lax.ragged_dot(lhs, rhs.swapaxes(1, 2), group_sizes,
                              preferred_element_type=lhs.dtype)


def grouped_outer(lhs, rhs, group_sizes):
    """``out[g] = lhs[rows of g].T @ rhs[rows of g]``, ``[G, K, N]`` from
    ``lhs`` ``[R, K]`` and ``rhs`` ``[R, N]``: the gradient of
    :func:`grouped_matmul` in its matrices. An empty group's is zero; rows
    in no group are not read. On the chip ``pallas_kernels.tgmm``
    (``moe_tgmm``), which contracts over the rows inside the kernel."""
    tiling = _kernel_tiles("grouped_outer", lhs, rhs, rhs.shape[1],
                           "outer_tiling")
    if tiling:
        return pk.tgmm(lhs, rhs, group_sizes, tiling=tiling)
    return jax.lax.ragged_dot_general(
        lhs, rhs, group_sizes, jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[]),
        preferred_element_type=lhs.dtype)


# Rows move between token order [N, d] and sorted-row order [R, d] by one
# gather of R rows in either direction, and ``take_rows`` and ``put_rows``
# are each other's transposes (autodiff's transpose of the gather would be
# a scatter-add of R rows). The way back to tokens is a sum, and what it
# costs is how it finds a token's rows. One call, ms on a v5e (PERF.md §6,
# PR 40), as k gathers of N rows (before PR 40) and as the segment sum with
# its sort and regather: [20480, 3072] into 8,192 tokens at k 10 5.70-5.76
# and 1.81-1.92; [11264, 1024] into 4,096 at k 22 0.68-0.69 and 0.21-0.22;
# [32768, 2048] into 16,384 at k 4 3.69-3.72 and 2.23-2.36 (half of the
# segment sum's time is the regather); the layer held whole ([65536, 2048],
# R = N k) 4.34 and 4.27. A scatter-add of the R rows read 2.16-2.69 where
# the k gathers read 1.25 ([20480, 2048], k 4; PR 32).

#: tokens a segment of :func:`put_rows`' sum: a lane width, so that a row's
#: place in its segment is one K tile of ``pallas_kernels.tgmm``
_TOKEN_TILE = 128


@jax.custom_vjp
def take_rows(x, token, back):
    """``x[token]``: the row of each sorted row's token (``token`` ``[R]``).
    ``back`` is the way back, :func:`put_rows`' (``_rows_at`` makes it)."""
    return x[token]


@jax.custom_vjp
def put_rows(rows, token, back):
    """``y[n]`` = the sum of the sorted rows whose token is ``n``, in
    float32, in ``rows.dtype``; ``[N, d]`` from ``rows`` ``[R, d]``.
    ``back = (by_token [R], held [N])``: the permutation that puts the rows
    in token order with those of no group last, and the rows each token has
    here. A **segment sum over the rows in token order**: the rows are
    regathered in that order (a gather of R rows) and each tile of
    ``_TOKEN_TILE`` tokens is ``one_hot.T @ rows`` over its own rows, the
    one-hot ``[R, _TOKEN_TILE]`` of ``token mod _TOKEN_TILE``:
    :func:`grouped_outer` with the tiles as its groups, so on the chip
    ``pallas_kernels.tgmm`` (rows times 1.0 accumulated in float32 on the
    MXU; a row past the rows held is not read, a tile with none is zero)
    and off it, or for a shape ``grouped_route`` refuses,
    ``jax.lax.ragged_dot_general``. The cost follows ``R`` and, inside the
    kernel, the rows really here, not the ``N k`` assignments."""
    by_token, held = back
    tokens = held.shape[0]
    tiles = -(-tokens // _TOKEN_TILE)
    one_hot = jax.nn.one_hot(token[by_token] % _TOKEN_TILE, _TOKEN_TILE,
                             dtype=rows.dtype)
    counts = jnp.sum(jnp.pad(held, (0, tiles * _TOKEN_TILE - tokens)).reshape(
        tiles, _TOKEN_TILE), axis=1)
    y = grouped_outer(one_hot, rows[by_token], counts)
    return y.reshape(tiles * _TOKEN_TILE, rows.shape[1])[:tokens]


take_rows.defvjp(
    lambda x, token, back: (x[token], (token, back)),
    lambda res, g: (put_rows(g, *res), None, None))
put_rows.defvjp(
    lambda rows, token, back: (put_rows(rows, token, back), (token, back)),
    lambda res, g: (take_rows(g, *res), None, None))


def _rows_at(rows: int, top_k: int, order, per_token, group_sizes):
    """Of the first ``rows`` sorted rows: ``(picked, token, valid, back)``,
    the assignment and the token each holds, ``[rows, 1]`` whether the row
    is in a group here (an assignment held elsewhere has no row here,
    whatever its place in the sorted order: the rows from
    ``sum(group_sizes)`` on are in no group), and the way back to token
    order (:func:`put_rows`' ``back``). Nothing here is sized by the
    ``N k`` assignments: ``rows`` of ``order`` are read."""
    picked = order[:rows]
    token = picked // top_k
    valid = jnp.arange(rows, dtype=jnp.int32) < jnp.sum(group_sizes)
    # (a sort of ``rows`` keys, not of the N k assignments again)
    by_token = jnp.argsort(jnp.where(valid, token, per_token.shape[0]))
    return picked, token, valid[:, None], (by_token.astype(jnp.int32),
                                          per_token)


def _experts_at(rows: int, h, w_in, w_out, weights, order, per_token,
                group_sizes, activation: str = "swiglu"):
    """The expert stage at a static capacity of ``rows`` sorted rows, which
    must hold every row of the experts here (``sum(group_sizes) <= rows``)."""
    with jax.named_scope("dispatch"):
        picked, token, valid, back = _rows_at(
            rows, weights.shape[1], order, per_token, group_sizes)
        x = take_rows(h, token, back)
    with jax.named_scope("experts"):
        first = grouped_matmul(x, w_in.astype(h.dtype), group_sizes)
        if activation == "swiglu":
            gate, up = jnp.split(first, 2, axis=-1)
            act = jax.nn.silu(gate) * up
        else:
            act = jnp.square(jax.nn.relu(first))
        out = grouped_matmul(act, w_out.astype(h.dtype), group_sizes)
    with jax.named_scope("combine"):
        # a row of no group holds whatever the product left there. It is in
        # no token's segment, so it reaches none; it is zeroed all the same
        weighted = jnp.where(valid, out, 0).astype(jnp.float32) \
            * weights.reshape(-1)[picked][:, None]
        return put_rows(weighted.astype(h.dtype), token, back)


def _experts_bwd_at(rows: int, h, w_in, w_out, weights, order, per_token,
                    group_sizes, dy, activation: str = "swiglu"):
    """The gradients of :func:`_experts_at` in ``h``, ``w_in``, ``w_out``
    and ``weights`` for the cotangent ``dy`` of its result, at the same
    capacity: five grouped products. With ``act = silu(G) * U`` (or
    ``relu(A) ** 2``) and
    ``out = act W2`` the forward pass gave ``y = put_rows(w * out)``; here
    ``g = take_rows(dy)``, ``u = g W2^T``, and the routing weight of a row
    has the gradient ``<out, g> = <act, u>``, so ``out`` is not computed
    again. Every product leaves the rows of no group as they were: ``u`` and
    ``G, U`` meet a sum over a row only under ``valid``, the two
    weight-gradient products read no such row, and ``put_rows`` reads none
    of ``dX``."""
    swiglu = activation == "swiglu"
    dtype = h.dtype
    wide = jnp.promote_types(dtype, jnp.float32)    # between the products
    with jax.named_scope("dispatch"):
        picked, token, valid, back = _rows_at(
            rows, weights.shape[1], order, per_token, group_sizes)
        x = take_rows(h, token, back)
    with jax.named_scope("combine"):
        g = take_rows(dy, token, back)
        w = weights.reshape(-1)[picked][:, None]
    with jax.named_scope("experts"):
        w1, w2 = w_in.astype(dtype), w_out.astype(dtype)
        first = grouped_matmul(x, w1, group_sizes)
        if swiglu:
            # (split, then widen: XLA keeps a widened [rows, 2 f] copy in
            # HBM rather than fuse the convert into both halves' readers)
            gate, up = (half.astype(wide)
                        for half in jnp.split(first, 2, axis=-1))
        u = grouped_matmul_t(g, w2, group_sizes).astype(wide)
        if swiglu:
            sig = jax.nn.sigmoid(gate)
            act = gate * sig * up
        else:
            positive = jax.nn.relu(first.astype(wide))
            act = positive * positive
        act = act.astype(dtype).astype(wide)    # the operand of W2
        d_w = jnp.sum(jnp.where(valid, act * u, 0), axis=-1)
        d_act = w * u
        if swiglu:
            d_first = jnp.concatenate(
                [d_act * up * sig * (1 + gate * (1 - sig)),
                 d_act * gate * sig], axis=-1)
        else:
            d_first = 2 * d_act * positive
        d_first = d_first.astype(dtype)
        d_w2 = grouped_outer((w * act).astype(dtype), g, group_sizes)
        d_x = grouped_matmul_t(d_first, w1, group_sizes)
        d_w1 = grouped_outer(x, d_first, group_sizes)
    with jax.named_scope("combine"):
        # each row's gradient placed at its assignment (``rows`` places, and
        # a row of no group brings the zero its assignment has anyway)
        d_weights = jnp.zeros((weights.size,), d_w.dtype).at[picked].set(
            d_w, unique_indices=True).reshape(weights.shape)
    with jax.named_scope("dispatch"):
        d_h = put_rows(d_x, token, back)
    return (d_h, d_w1.astype(w_in.dtype), d_w2.astype(w_out.dtype),
            d_weights.astype(weights.dtype))


def _smallest_that_holds(sizes: Tuple[int, ...], group_sizes, fn, *operands):
    """``fn(size, *operands)`` at the smallest of ``sizes`` (ascending, the
    last one the worst case) that is at least ``sum(group_sizes)``, under
    the name scope ``capacity_all`` for the last size and ``capacity_fit``
    for any other (a single size is no ``cond``, and ``capacity_all``)."""
    def at(size):
        def branch(*operands):
            with jax.named_scope("capacity_all" if size == sizes[-1]
                                 else "capacity_fit"):
                return fn(size, *operands)
        return branch

    index = jnp.sum(jnp.sum(group_sizes) > jnp.asarray(sizes[:-1], jnp.int32))
    return jax.lax.switch(index, [at(size) for size in sizes], *operands)


@partial(jax.custom_vjp, nondiff_argnums=(0, 8))
def _experts(sizes, h, w_in, w_out, weights, order, per_token, group_sizes,
             activation="swiglu"):
    return _smallest_that_holds(
        sizes, group_sizes, partial(_experts_at, activation=activation), h,
        w_in, w_out, weights, order, per_token, group_sizes)


def _experts_fwd(sizes, *operands_and_activation):
    # the residuals are the operands: which capacity ran is not a shape the
    # backward pass may depend on, so it picks its own, the same
    return (_experts(sizes, *operands_and_activation),
            operands_and_activation[:-1])


def _experts_bwd(sizes, activation, operands, dy):
    grads = _smallest_that_holds(
        sizes, operands[-1], partial(_experts_bwd_at, activation=activation),
        *operands, dy)
    return (*grads, None, None, None)


_experts.defvjp(_experts_fwd, _experts_bwd)


def routed_ffn(h, router, bias, w_in, w_out, *, held: Tuple[int, ...],
               top_k: int, x=None, activation: str = "swiglu",
               scale: float = 1.0, norm_eps: float = NORM_EPS,
               scoring: str = "sigmoid", aux_loss: float = 0.0,
               label: str = ""):
    """The layer above for ``h`` ``[N, d]``: ``router`` ``[d, E]`` and
    ``bias`` ``[E]`` (float32), ``w_in`` ``[H, d, 2 f]`` (gate and up side
    by side; ``[H, d, f]`` for ``activation="relu2"``) and ``w_out``
    ``[H, f, d]`` for the ``H = len(held)`` experts held (cast to
    ``h.dtype`` here). The router always reads ``h``; the experts read
    ``x`` ``[N, l]`` where one is given (a latent of ``h``: their ``d`` is
    then ``l``, and so is ``y``'s). ``scale``, ``norm_eps``, ``scoring``
    and ``aux_loss`` are :func:`route`'s. Returns ``(y [N, d], chosen [N,
    k], scores [N, E], load [E])``: ``load`` counts the tokens each of the
    ``E`` experts was chosen by (held or not). ``label`` names the layer
    (a module's path, ``block_<i>/ffn``) in what :func:`report_load` is
    told of it: where ``HOROVOD_MOE_REPORT`` is on as the layer is traced,
    each execution hands the host the rows held here and ``load`` through
    one ``jax.debug.callback``; where it is off there is no callback in the
    program and no operand kept for one.

    The expert stage keeps the layer's inputs and the routing, not the
    ``[rows, 2 f]`` activations, whatever the model's ``remat``: its
    backward pass runs the first product again and four more, seven grouped
    products a step with the forward's two (the module's docstring says
    which, and which kernel each is)."""
    num_experts = router.shape[-1]
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            # E columns: nothing to the MXU's bf16 passes, and a choice
            # between near-equal scores should not hang on them
            logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                             precision="highest")
            # (the defaults are left to ``route``: tests put one of three
            # arguments in its place)
            renorm = () if (scale, norm_eps) == (1.0, NORM_EPS) \
                else (scale, norm_eps)
            kind = {} if scoring == "sigmoid" else {"scoring": scoring}
            if aux_loss:
                kind["aux_loss"] = aux_loss
            chosen, weights, scores = route(logits, bias, top_k, *renorm,
                                            **kind)
            load = jnp.sum(jax.nn.one_hot(chosen, num_experts,
                                          dtype=jnp.int32), axis=(0, 1))
        with jax.named_scope("dispatch"):
            order, per_token, group_sizes = dispatch(chosen, held)
        sizes = capacities(order.shape[0], len(held), num_experts)
        if env_on("HOROVOD_MOE_REPORT"):
            jax.debug.callback(
                partial(report_load, held=held, layer=label, sizes=sizes,
                        balanced=order.shape[0] * len(held) / num_experts),
                load, rows=jnp.sum(group_sizes))
        y = _experts(sizes, h if x is None else x, w_in, w_out, weights,
                     order, per_token, group_sizes, activation)
    return y, chosen, scores, load


#: a layer's latest reports whose rows the summary's min / median / max are
#: over; its counts and its worst load are over every report
KEPT_REPORTS = 65536


class _LayerReports:
    """What :func:`report_load` keeps of one layer for
    :func:`routing_summary`."""

    def __init__(self, balanced: float, sizes: Tuple[int, ...]):
        self.balanced, self.sizes = balanced, sizes
        self.reports = self.at_all = 0
        self.worst = 0.0
        self.rows = deque(maxlen=KEPT_REPORTS)

    def line(self, layer: str) -> str:
        rows = (min(self.rows), statistics.median(self.rows), max(self.rows))
        return (
            f"moe report {layer or '(no label)'}: {self.reports} reports, "
            f"rows min / median / max {rows[0]} / {rows[1]:g} / {rows[2]}, "
            + " / ".join(f"{r / self.balanced:.2f}" for r in rows)
            + f" x the balanced {self.balanced:g}, capacities {self.sizes}, "
            f"{100.0 * self.at_all / self.reports:.1f}% at capacity_all, "
            f"worst load max / mean {self.worst:.2f}")


_reports: Dict[str, _LayerReports] = {}
_reports_lock = threading.Lock()   # callbacks come from the runtime's threads


def report_load(load, held: Sequence[int], rows=None, *, layer: str = "",
                sizes: Tuple[int, ...] = (), balanced: float = 0.0) -> float:
    """The one host sink for a routed layer's counts. From ``load`` (tokens
    an expert, ``[E]``) it sets ``hvd_expert_load`` (by expert id) and
    ``hvd_moe_load_imbalance`` (max over mean, over the experts ``held``),
    and returns the imbalance: the gauges ``parallel/expert.py``'s
    stand-alone block sets for itself, so that the anomaly watch follows
    either path. A caller that has only a layer's sown ``load`` stops
    there.

    :func:`routed_ffn` under ``HOROVOD_MOE_REPORT`` calls it from inside the
    step with ``rows`` (``sum(group_sizes)``: the rows the layer ``layer``
    held in that execution), the ``sizes`` it is compiled at
    (:func:`capacities`) and the ``balanced`` row count. That sets, by
    ``layer``, ``hvd_moe_rows`` and ``hvd_moe_rows_over_balanced`` and
    counts the report in ``hvd_moe_reports_total{layer, capacity}``:
    ``all`` where the rows select the worst-case program and ``fit``
    otherwise, by ``_smallest_that_holds``' comparison. A **report is one
    traced execution of the layer, not a step**: a model that recomputes its
    blocks runs the layer's forward pass twice a step and reports twice.
    The body runs under ``phases.phase("moe/report", program=layer)``, so
    each report is a host span beside the set-up's (and, under an active
    profile, a ``TraceAnnotation`` ``hvd/moe/report`` beside the device's
    operations), and is kept for :func:`routing_summary`, which
    ``hvd.shutdown()`` prints."""
    from ..metrics import instruments, phases

    with phases.phase("moe/report", program=layer):
        load = np.asarray(load)
        instruments.expert_load().set_each(load.tolist())
        here = load[list(held)].astype(np.float64)
        imbalance = float(here.max() / max(here.mean(), 1e-9))
        instruments.moe_load_imbalance().set(imbalance)
        if rows is None:
            return imbalance
        rows = int(rows)
        at_all = all(rows > size for size in sizes[:-1])
        instruments.moe_rows().labels(layer=layer).set(float(rows))
        instruments.moe_rows_over_balanced().labels(layer=layer).set(
            rows / balanced)
        instruments.moe_reports().labels(
            layer=layer, capacity="all" if at_all else "fit").inc()
        with _reports_lock:
            if not _reports:   # the first report of a run
                from .. import basics

                basics.register_shutdown_hook(_say_summary)
            kept = _reports.get(layer)
            if kept is None:
                kept = _reports[layer] = _LayerReports(balanced, sizes)
            kept.reports += 1
            kept.at_all += at_all
            kept.worst = max(kept.worst, imbalance)
            kept.rows.append(rows)
        return imbalance


def routing_summary(clear: bool = False) -> List[str]:
    """One line a routed layer that has reported (:func:`report_load` with
    ``rows``), in the order the layers first did: how many reports, the rows
    held (min / median / max of the latest ``KEPT_REPORTS``) as counts and
    over the balanced count, the sizes compiled, the share of reports whose
    rows select ``capacity_all``, and the worst max / mean load. Empty
    where nothing has reported. ``clear`` forgets the reports."""
    with _reports_lock:
        lines = [kept.line(layer) for layer, kept in _reports.items()]
        if clear:
            _reports.clear()
    return lines


def _say_summary() -> None:
    """``hvd.shutdown()``'s hook: the summary on standard error (a run's
    standard output may be a protocol), once."""
    jax.effects_barrier()   # a callback still on its way counts
    for line in routing_summary(clear=True):
        print(line, file=sys.stderr, flush=True)
