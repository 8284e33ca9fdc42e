"""Expert parallelism: a switch-style MoE layer sharded over an ``ep`` axis.

Beyond-reference extension (the reference is DP-only). The MoE MLP holds
all experts as stacked parameter tensors ``[E, d, hidden]`` / ``[E,
hidden, d]``; sharding the expert dimension over the mesh's ``ep`` axis
puts ``E/ep`` experts on each device group.

Two dispatch strategies (docs/moe.md):

* **exact** (default, the numerical reference): dense one-hot
  dispatch/combine einsums over the full token set — every token reaches
  its routed expert, the communication is inserted by GSPMD, and the
  sharded computation is numerically identical to the unsharded one
  (which the tests pin). O(E·N·d) compute.
* **capacity** (the classic Switch recipe, ``dispatch="capacity"``):
  fixed-size per-expert buffers (``capacity = ceil(CF · N / E)``),
  position-in-expert via cumsum, tokens past capacity dropped (they
  contribute zero to the MoE output and are counted), and the token
  exchange is an explicit ``all_to_all`` over the ``ep`` axis inside a
  ``shard_map`` — which is where the quantized wire engages:
  ``HOROVOD_MOE_WIRE=int8|int4`` ships the exchange through the fused
  quantize+pack kernels (``ops/pallas_kernels``, the same
  ``[payload | 4 f32-scale bytes]`` rows and eligibility fallbacks as
  ``spmd.py``'s quantized ring) with an EF-SGD residual banked per
  exchange direction. Router logits, gates, and gradients always stay on
  the exact wire.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import spmd
from .tensor import make_2d_mesh, make_sharded_train_step


class MoEMLP(nn.Module):
    """Top-1 (switch) routed MLP with a load-balancing auxiliary loss.

    Returns ``(y, aux_loss)``; add ``aux_weight * aux_loss`` to the
    training loss (Switch Transformer's balance loss: E * sum_e f_e * p_e,
    with f the fraction of tokens routed to e and p the mean router
    probability).
    """

    num_experts: int
    hidden_mult: int = 4
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x) -> Tuple[jax.Array, jax.Array]:
        b, t, d = x.shape
        e, h = self.num_experts, self.hidden_mult * x.shape[-1]
        x2 = x.reshape(b * t, d)

        router = nn.Dense(e, dtype=jnp.float32, param_dtype=jnp.float32,
                          name="router")(x2.astype(jnp.float32))
        probs = jax.nn.softmax(router, axis=-1)          # [N, E]
        expert_idx = jnp.argmax(probs, axis=-1)          # [N]
        onehot = jax.nn.one_hot(expert_idx, e, dtype=probs.dtype)
        gate = (probs * onehot).sum(-1)                  # chosen prob

        w_in = self.param("w_in", nn.initializers.normal(0.02), (e, d, h),
                          jnp.float32)
        w_out = self.param("w_out", nn.initializers.normal(0.02), (e, h, d),
                           jnp.float32)
        # dispatch/combine as einsums over the (shardable) expert dim:
        # every expert sees the full token set masked by its assignment
        xe = jnp.einsum("nd,ne->end", x2.astype(self.dtype),
                        onehot.astype(self.dtype))       # [E, N, d]
        he = nn.gelu(jnp.einsum("end,edh->enh", xe,
                                w_in.astype(self.dtype)))
        ye = jnp.einsum("enh,ehd->end", he, w_out.astype(self.dtype))
        y = ye.sum(0) * gate[:, None].astype(self.dtype)  # combine
        frac = onehot.mean(0)                            # f_e
        balance = e * jnp.sum(frac * probs.mean(0))      # aux loss
        return y.reshape(b, t, d).astype(x.dtype), balance.astype(jnp.float32)


# ------------------------------------------------------------ knobs & math
_MOE_WIRES = ("int8", "int4")


def moe_wire(value: Optional[str] = None) -> str:
    """Resolve the MoE token-exchange wire mode (``HOROVOD_MOE_WIRE``).

    Returns ``""`` (wire off — the exact bf16/f32 all_to_all), ``"int8"``
    or ``"int4"``. ``value`` overrides the env var (the
    ``make_ep_train_step(wire=...)`` argument). int4 must pass the PR 10
    ``ConvergenceGate`` A/B harness; a refusal downgrades to int8 — the
    same admission rule as ``HOROVOD_GSPMD_WIRE``
    (`ops/adaptive.admit_wire`).
    """
    v = os.environ.get("HOROVOD_MOE_WIRE", "") if value is None else value
    v = (v or "").strip().lower()
    if v in ("", "0", "off", "none"):
        return ""
    if v not in _MOE_WIRES:
        raise ValueError(f"HOROVOD_MOE_WIRE must be int8|int4|off, got {v!r}")
    from ..ops.adaptive import admit_wire

    return admit_wire(v)


def expert_capacity(num_tokens: int, num_experts: int,
                    capacity_factor: float) -> int:
    """Per-expert buffer slots for ``num_tokens`` routed tokens:
    ``ceil(CF · N / E)``, at least 1 (the Switch Transformer rule). At
    CF=1.0 a perfectly balanced router drops nothing; CF=1.25 (the paper
    default) leaves 25% headroom for imbalance."""
    if num_tokens <= 0 or num_experts <= 0:
        raise ValueError(
            f"need positive tokens/experts, got {num_tokens}/{num_experts}")
    if capacity_factor <= 0:
        raise ValueError(f"capacity_factor must be positive, "
                         f"got {capacity_factor}")
    return max(1, int(math.ceil(capacity_factor * num_tokens / num_experts)))


def init_moe_params(key, d: int, num_experts: int, hidden_mult: int = 4):
    """Functional (non-flax) parameter tree for the capacity-dispatch MoE:
    ``router`` (replicated f32) plus the expert-stacked ``w_in``/``w_out``
    — the same names :func:`ep_param_spec` shards. Init matches
    :class:`MoEMLP` (normal 0.02, zero router bias)."""
    h = hidden_mult * d
    kr, ki, ko = jax.random.split(key, 3)
    return {
        "router": {
            "kernel": 0.02 * jax.random.normal(kr, (d, num_experts),
                                               jnp.float32),
            "bias": jnp.zeros((num_experts,), jnp.float32),
        },
        "w_in": 0.02 * jax.random.normal(ki, (num_experts, d, h),
                                         jnp.float32),
        "w_out": 0.02 * jax.random.normal(ko, (num_experts, h, d),
                                          jnp.float32),
    }


def _router(params, x2):
    """Shared exact top-1 routing: f32 logits -> (probs, onehot, gate).
    The router ALWAYS computes and exchanges exactly — quantizing routing
    decisions desynchronizes dispatch across ranks (docs/moe.md)."""
    logits = (x2.astype(jnp.float32) @ params["router"]["kernel"]
              + params["router"]["bias"])
    probs = jax.nn.softmax(logits, axis=-1)              # [N, E]
    onehot = jax.nn.one_hot(jnp.argmax(probs, axis=-1), probs.shape[-1],
                            dtype=jnp.float32)
    gate = (probs * onehot).sum(-1)                      # chosen prob
    return probs, onehot, gate


def dense_moe_apply(params, x2) -> Tuple[jax.Array, jax.Array]:
    """Exact dense one-hot dispatch on a functional param tree (the
    numerical reference the capacity path is measured against): ``x2``
    is ``[N, d]``; returns ``(y [N, d], balance aux loss)``. Same math
    as :class:`MoEMLP` in f32."""
    e = params["w_in"].shape[0]
    probs, onehot, gate = _router(params, x2)
    xe = jnp.einsum("nd,ne->end", x2.astype(jnp.float32), onehot)
    he = jax.nn.gelu(jnp.einsum("end,edh->enh", xe, params["w_in"]))
    ye = jnp.einsum("enh,ehd->end", he, params["w_out"])
    y = ye.sum(0) * gate[:, None]
    balance = e * jnp.sum(onehot.mean(0) * probs.mean(0))
    return y.astype(x2.dtype), balance.astype(jnp.float32)


def dispatch_mask(onehot, capacity: int):
    """Switch position-in-expert assignment: ``onehot`` is the ``[N, E]``
    top-1 routing; returns ``(dmask [N, E, C], keep [N])`` where
    ``dmask[n, e, c] = 1`` iff token n is the c-th token routed to expert
    e with ``c < capacity``. Position comes from a cumulative sum over
    the token dimension, so earlier tokens win slots and overflow tokens
    get an all-zero row (dropped — they contribute nothing to the
    dispatch einsum and recombine to zero)."""
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot    # [N, E]
    pos_tok = pos.sum(-1)                                # rank within expert
    keep = pos_tok < capacity
    slot = jax.nn.one_hot(pos_tok.astype(jnp.int32), capacity,
                          dtype=jnp.float32)             # 0-rows past C
    dmask = onehot[:, :, None] * slot[:, None, :]
    return dmask, keep


class SwitchDispatch:
    """Capacity-factor Switch dispatch bound to one train-step invocation.

    Built by the capacity train step and handed to ``loss_fn(params,
    batch, moe)``; call ``moe(moe_params, x2)`` with the functional param
    tree (:func:`init_moe_params` layout; expert leaves arrive ep-local
    inside the step's shard_map) and the local ``[n_loc, d]`` token slab.
    Returns ``(y, aux_loss)`` like :func:`dense_moe_apply`.

    The first call banks dispatch statistics (per-expert load, dropped
    tokens — psum'd, so identical on every device) and the new EF
    residual pair on the object; the step returns them through
    ``has_aux`` so nothing leaks out of the gradient trace. Later calls
    (multi-layer MoE) exchange with zero EF — only the first exchange
    pair carries the banked residual.
    """

    def __init__(self, dp_axis: str, ep_axis: str, capacity_factor: float,
                 wire: str, block: Optional[int], ef_loc):
        self.dp_axis = dp_axis
        self.ep_axis = ep_axis
        self.capacity_factor = capacity_factor
        self.wire = wire
        self.block = block
        self._ef_loc = ef_loc          # [2, E, C, d] this device's rows
        self.stats = None              # banked by the first __call__
        self.new_ef = None

    def __call__(self, params, x2) -> Tuple[jax.Array, jax.Array]:
        axes = (self.dp_axis, self.ep_axis)
        ep = jax.lax.psum(1, self.ep_axis)
        e_loc = params["w_in"].shape[0]                  # ep-local experts
        e = ep * e_loc
        n_loc, d = x2.shape
        cap = expert_capacity(n_loc, e, self.capacity_factor)

        probs, onehot, gate = _router(params, x2)
        dmask, keep = dispatch_mask(onehot, cap)
        buf = jnp.einsum("nec,nd->ecd", dmask,
                         x2.astype(jnp.float32))         # [E, C, d]

        first = self.stats is None
        ef = self._ef_loc if (first and self._ef_loc is not None) else None
        if ef is not None and ef.shape[1:] != buf.shape:
            raise ValueError(
                f"EF residual shaped {ef.shape[1:]} does not match the "
                f"[E, C, d] exchange {buf.shape}; rebuild the optimizer "
                f"state with moe_opt_state() for this batch size")

        def exchange(z, direction):
            if not self.wire:
                y = jax.lax.all_to_all(z, self.ep_axis, 0, 0, tiled=True)
                return y, (jnp.zeros_like(z) if ef is not None else None)
            out = spmd.quantized_all_to_all(
                z, self.ep_axis, self.wire, self.block,
                ef=ef[direction] if ef is not None else None)
            return out if ef is not None else (out, None)

        # dispatch: peer p owns global experts [p*e_loc, (p+1)*e_loc) —
        # buf's expert-major dim 0 is already grouped by destination peer
        recv, ef_d = exchange(buf, 0)
        xe = (recv.reshape(ep, e_loc, cap, d)
              .transpose(1, 0, 2, 3).reshape(e_loc, ep * cap, d))
        he = jax.nn.gelu(jnp.einsum("egd,edh->egh", xe, params["w_in"]))
        ye = jnp.einsum("egh,ehd->egd", he, params["w_out"])
        back = (ye.reshape(e_loc, ep, cap, d)
                .transpose(1, 0, 2, 3).reshape(e, cap, d))
        # combine: group p of `back` holds our experts' outputs for the
        # tokens peer p sent; the reverse exchange returns every token's
        # expert output to its home device
        out, ef_c = exchange(back, 1)
        y = jnp.einsum("ecd,nec->nd", out, dmask) * gate[:, None]

        # balance loss over the GLOBAL batch (pmean of local means)
        frac = jax.lax.pmean(onehot.mean(0), axes)
        pmean_probs = jax.lax.pmean(probs.mean(0), axes)
        balance = e * jnp.sum(frac * pmean_probs)

        if first:
            load = jax.lax.psum(onehot.sum(0), axes)     # [E] tokens/expert
            dropped = jax.lax.psum(
                n_loc - keep.astype(jnp.float32).sum(), axes)
            self.stats = {"load": load, "dropped": dropped,
                          "capacity": jnp.asarray(cap, jnp.float32)}
            if self._ef_loc is not None:
                self.new_ef = jnp.stack([ef_d, ef_c])
        return y.astype(x2.dtype), balance.astype(jnp.float32)


# ------------------------------------------------------- sharding helpers
def make_dp_ep_mesh(dp: int, ep: int, devices=None) -> Mesh:
    return make_2d_mesh(("dp", "ep"), (dp, ep), devices)


def _path_name(entry) -> str:
    """One jax.tree_util path entry as its plain key/attr name — DictKey,
    GetAttrKey, and SequenceKey all stringify to the bare name instead of
    repr noise like ``['w_in']``."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def ep_param_spec(path_keys, leaf, ep_axis: str = "ep") -> P:
    """Stacked expert tensors shard dim 0 (the expert dim) over ``ep``;
    the router and everything else replicate."""
    names = [str(k) for k in path_keys]
    if names and names[-1] in ("w_in", "w_out"):
        return P(ep_axis)
    return P()


def ep_specs(tree, ep_axis: str = "ep"):
    """Pytree of PartitionSpecs matching :func:`ep_param_spec` — shared by
    param placement, optimizer-state placement, and the capacity step's
    shard_map in/out specs (optax state mirrors the param tree, so its
    expert leaves keep the ``w_in``/``w_out`` path suffix)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: ep_param_spec(
            [_path_name(p) for p in path], leaf, ep_axis), tree)


def shard_params_ep(params, mesh: Mesh, ep_axis: str = "ep"):
    ep = mesh.shape[ep_axis]

    def one(path, leaf):
        names = [_path_name(p) for p in path]
        spec = ep_param_spec(names, leaf, ep_axis)
        if spec and spec[0] == ep_axis and leaf.shape[0] % ep != 0:
            raise ValueError(
                f"{'/'.join(names)}: expert dim "
                f"{leaf.shape[0]} not divisible by ep={ep}")
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(one, params)


def moe_opt_state(tx, params, mesh: Mesh, num_tokens: int,
                  capacity_factor: float = 1.25, dp_axis: str = "dp",
                  ep_axis: str = "ep"):
    """Initial ``(inner_state, ef_residual)`` for a capacity-dispatch step.

    ``num_tokens`` is the GLOBAL tokens per step (batch × seq); the EF
    residual covers both exchange directions as one zero-initialized leaf
    of global shape ``[n_devices, 2, E, C, d]`` sharded one row per
    device over ``(dp, ep)`` — inside the step's shard_map each device
    sees exactly its own ``[2, E, C, d]`` rows, mirroring
    :func:`spmd.quantized_opt_state`. The inner optimizer state is placed
    with the same ep sharding as the params (optax state mirrors the
    param tree)."""
    dp, ep = mesh.shape[dp_axis], mesh.shape[ep_axis]
    world = dp * ep
    if num_tokens % world:
        raise ValueError(f"global tokens {num_tokens} not divisible by "
                         f"{world} devices")
    e, d, _ = params["w_in"].shape
    cap = expert_capacity(num_tokens // world, e, capacity_factor)
    ef = jax.device_put(
        jnp.zeros((world, 2, e, cap, d), jnp.float32),
        NamedSharding(mesh, P((dp_axis, ep_axis))))
    inner = shard_params_ep(tx.init(params), mesh, ep_axis)
    return inner, ef


# ------------------------------------------------------------- train steps
def make_ep_train_step(loss_fn: Callable, tx, mesh: Mesh,
                       dp_axis: str = "dp", ep_axis: str = "ep",
                       dispatch: str = "exact",
                       capacity_factor: float = 1.25,
                       wire: Optional[str] = None,
                       block: Optional[int] = None,
                       donate: bool = True) -> Callable:
    """EP train step.

    ``dispatch="exact"`` (the default): expert params stay ep-sharded,
    batch over ``dp``, dense one-hot dispatch with GSPMD-inserted
    communication (see :func:`tensor.make_sharded_train_step`) — with the
    knobs unset this compiles the exact same program as before the
    capacity variant existed (the pin tested in tests/test_moe.py).

    ``dispatch="capacity"``: the Switch recipe. ``loss_fn(params, batch,
    moe) -> scalar`` receives a :class:`SwitchDispatch` and the LOCAL
    batch shard; the step runs as a shard_map over the full ``(dp, ep)``
    mesh with per-device gradients reduced explicitly (pmean over both
    axes for replicated leaves; the backward all_to_all already sums the
    ep group for expert shards, so those psum over ``dp`` only). ``wire``
    resolves ``HOROVOD_MOE_WIRE`` at build time (:func:`moe_wire`,
    including the int4 gate admission); opt state must come from
    :func:`moe_opt_state`. Returns ``step(params, opt_state, batch) ->
    (params, opt_state, loss, stats)`` with ``stats`` the banked
    dispatch statistics; byte/load/drop accounting ticks eagerly per call
    (``step.jitted`` is the bare compiled step).
    """
    if dispatch == "exact":
        return make_sharded_train_step(loss_fn, tx, mesh, batch_axis=dp_axis)
    if dispatch != "capacity":
        raise ValueError(f"dispatch must be exact|capacity, got {dispatch!r}")
    import optax

    wire = moe_wire(wire)
    block = spmd._wire_block(block)
    dp, ep = mesh.shape[dp_axis], mesh.shape[ep_axis]
    world = dp * ep
    axes = (dp_axis, ep_axis)

    def local_step(params, inner, ef, batch):
        def local_loss(p):
            moe = SwitchDispatch(dp_axis, ep_axis, capacity_factor, wire,
                                 block, ef[0])
            loss = loss_fn(p, batch, moe)
            if moe.stats is None:
                raise ValueError(
                    "dispatch='capacity' requires loss_fn(params, batch, "
                    "moe) to call moe(moe_params, tokens)")
            return loss, (moe.stats, moe.new_ef)

        (loss, (stats, new_ef)), grads = jax.value_and_grad(
            local_loss, has_aux=True)(params)
        specs = ep_specs(grads, ep_axis)

        def reduce_one(spec, g):
            # replicated leaves: mean of per-device grads over the whole
            # mesh. ep-sharded leaves: each device's grad already sums its
            # ep row's cotangents (the backward all_to_all delivered
            # them), so only the dp copies remain to fold in — psum over
            # dp, then the same 1/world of the global-mean loss.
            if spec and spec[0] == ep_axis:
                return jax.lax.psum(g, dp_axis) / world
            return jax.lax.pmean(g, axes)

        grads = jax.tree_util.tree_map(reduce_one, specs, grads)
        updates, inner = tx.update(grads, inner, params)
        params = optax.apply_updates(params, updates)
        loss = jax.lax.pmean(loss, axes)
        return params, inner, new_ef[None], loss, stats

    def step(params, opt_state, batch):
        inner, ef = opt_state
        p_specs = ep_specs(params, ep_axis)
        i_specs = ep_specs(inner, ep_axis)
        fn = spmd._shard_map(
            local_step, mesh,
            in_specs=(p_specs, i_specs, P(axes), P(axes)),
            out_specs=(p_specs, i_specs, P(axes), P(), P()))
        params, inner, ef, loss, stats = fn(params, inner, ef, batch)
        return params, (inner, ef), loss, stats

    jitted = jax.jit(step, donate_argnums=(0, 1) if donate else ())

    @functools.wraps(jitted)
    def instrumented(params, opt_state, batch):
        per_peer = int(np.prod(opt_state[1].shape[2:])) // ep  # E_loc·C·d
        out = jitted(params, opt_state, batch)
        _record_moe(out[3], capacity_factor, wire, per_peer, ep, block)
        return out

    instrumented.jitted = jitted  # .lower()/.compile() escape hatch
    return instrumented


def _record_moe(stats, capacity_factor: float, wire: str, per_peer: int,
                ep: int, block: int):
    """Truthful eager accounting for one capacity-dispatch step (counters
    cannot tick inside the compiled program): per-expert load and
    imbalance gauges, the dropped-token counter, and — when the wire is
    on — exchange bytes from the same catalog the bench reads
    (`ops/compression.moe_wire_footprint`)."""
    from ..metrics import instruments
    from ..ops import compression as comp

    load = np.asarray(stats["load"], dtype=np.float64)
    instruments.expert_load().set_each(load.tolist())
    mean = float(load.mean()) if load.size else 0.0
    instruments.moe_load_imbalance().set(
        float(load.max()) / mean if mean > 0 else 0.0)
    instruments.moe_dropped_tokens().inc(float(stats["dropped"]))
    instruments.moe_capacity_factor().set(float(capacity_factor))
    if wire and spmd._wire_eligible(per_peer, jnp.float32, wire, block):
        wire_b = comp.moe_wire_footprint(per_peer, wire, ep, block)
        exact_b = comp.moe_wire_footprint(per_peer, "none", ep, block)
        instruments.wire_bytes().labels(
            compression=f"moe-{wire}").inc(wire_b)
        instruments.wire_bytes_exact().inc(exact_b)
