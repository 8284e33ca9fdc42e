#!/usr/bin/env python
"""Transformer-LM training benchmark: tokens/sec and model FLOP utilization.

Complements `bench.py` (ResNet-50, HBM-bandwidth-bound — see
docs/benchmarks.md): a GPT-2-class LM is matmul-dominated, so this bench
shows what fraction of the MXU the SPMD train step actually sustains. Same
protocol as the reference's synthetic harness
(`examples/tensorflow2_synthetic_benchmark.py:106-133`): warmup, timed
rounds, one JSON line.

MFU = achieved FLOP/s ÷ peak FLOP/s, with the standard 6·P·T transformer
training FLOP count (fwd 2·P·T + bwd 4·P·T, P = non-embedding params,
T = tokens) per Kaplan et al. / PaLM appendix B.

    python benchmarks/lm_bench.py                 # on the chip
    LM_PRESET=tiny python benchmarks/lm_bench.py  # CPU smoke

Without a TPU the bench is an error: a tokens/s or MFU figure from the CPU
is not a measurement of this system. ``LM_PRESET=tiny`` is the explicit
smoke that runs anywhere; it reports under its own metric names
(``transformer_lm_smoke_tokens_per_sec``, ``moe_lm_smoke_tokens_per_sec``)
and no MFU. Every result names the platform, device kind and count.

With ``--history PATH`` the final record (tokens/s + MFU) appends to the
same schema-versioned JSONL store bench.py uses (benchmarks/history.py);
``--check-regression`` compares against the trajectory BEFORE appending
and exits 3 below the tolerance floor.

``--moe`` switches to the Switch-MoE dispatch benchmark
(parallel/expert.py): one MoE block trained over a ``dp × ep`` mesh in
four configs — exact one-hot dispatch, capacity dispatch (bf16/f32
wire), and capacity over the quantized int8/int4 all_to_all — each
reporting tokens/s, MFU (6 · active-params FLOP model: router + the one
routed expert per token), final loss, drop rate, and expert-load
imbalance, plus the catalog dispatch-byte ratios vs a bf16 exchange.
The history/regression gate then keys on ``moe_lm_tokens_per_sec``
(the capacity+int8 config — the shipped quantized default). Knobs:
``LM_MOE_EXPERTS`` (8), ``LM_MOE_D``, ``LM_MOE_TOKENS`` (global tokens
per step), ``LM_MOE_CF`` (1.25), ``LM_MOE_EP`` (expert-parallel mesh
extent; default gcd(devices, experts)), ``LM_MOE_WARMUP``/``LM_MOE_ITERS``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# bf16 peak of one chip in TFLOP/s, keyed by jax's ``device_kind``
# (Google Cloud documentation, "TPU v5e": 197). MFU against a peak assumed
# for whatever device turned up is not a number; an unknown kind is an error.
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0}

PRESETS = {
    # ~GPT-2 medium: d=1024, 24 layers, 16 heads
    "medium": dict(num_layers=24, d_model=1024, num_heads=16, vocab=32768,
                   batch=8, seq=1024, warmup=5, rounds=5, iters=5),
    "small": dict(num_layers=12, d_model=768, num_heads=12, vocab=32768,
                  batch=8, seq=1024, warmup=5, rounds=5, iters=5),
    # the smoke: f32, runs anywhere, reported under its own metric name
    "tiny": dict(num_layers=2, d_model=64, num_heads=2, vocab=256,
                 batch=2, seq=64, warmup=1, rounds=2, iters=2),
}
SMOKE_PRESET = "tiny"


def resolve_preset():
    """``(name, device)`` for this run: ``LM_PRESET`` (default ``medium``)
    and the first JAX device. Anything but the smoke preset needs a TPU
    whose peak is in ``PEAK_BF16_TFLOPS``."""
    import jax

    name = os.environ.get("LM_PRESET", "medium")
    dev = jax.devices()[0]
    if name != SMOKE_PRESET:
        if dev.platform != "tpu":
            sys.exit(f"lm_bench: no TPU (JAX found platform "
                     f"{dev.platform!r}); a benchmark needs the chip. "
                     f"LM_PRESET={SMOKE_PRESET} is the smoke that runs "
                     f"anywhere, under its own metric name.")
        if dev.device_kind not in PEAK_BF16_TFLOPS:
            sys.exit(f"lm_bench: no bf16 peak recorded for device kind "
                     f"{dev.device_kind!r}; add it to PEAK_BF16_TFLOPS "
                     f"with its source")
    return name, dev


def device_stamp(dev):
    import jax

    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "devices": len(jax.devices())}


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="Transformer-LM training benchmark (config via LM_* "
                    "env knobs; see module docstring)")
    p.add_argument("--moe", action="store_true",
                   help="benchmark Switch-MoE capacity dispatch (exact vs "
                        "capacity vs capacity+int8/int4 wire) instead of "
                        "the dense LM")
    p.add_argument("--history", metavar="PATH", default=None,
                   help="append this run's tokens/s + MFU to a "
                        "schema-versioned JSONL perf history "
                        "(benchmarks/history.py)")
    p.add_argument("--check-regression", action="store_true",
                   help="with --history: compare this run against the "
                        "recorded trajectory BEFORE appending; exit 3 when "
                        "it falls below the tolerance floor")
    p.add_argument("--regression-window", type=int, default=None,
                   metavar="N", help="trailing records the baseline median "
                                     "uses (default 5)")
    p.add_argument("--regression-tolerance", type=float, default=None,
                   metavar="F", help="fraction below baseline that fails "
                                     "(default 0.15)")
    return p.parse_args(argv)


def _gcd(a, b):
    while b:
        a, b = b, a % b
    return a


def run_moe(args):
    """Switch-MoE dispatch benchmark: exact vs capacity vs quantized wire.

    One weight-tied MoE block (embed -> top-1 routed expert MLP ->
    tied-head logits) trained on synthetic tokens over a ``dp x ep``
    mesh, timed per dispatch config. The capacity configs run the
    explicit all_to_all exchange (quantized when a wire is named); the
    exact config is the dense one-hot reference with GSPMD-inserted
    communication."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.ops import compression as comp
    from horovod_tpu.parallel import expert as epar
    from horovod_tpu.utils import compile_cache

    hvd.init()
    compile_cache.enable()
    preset, dev = resolve_preset()
    smoke = preset == SMOKE_PRESET
    world = jax.device_count()

    n_experts = int(os.environ.get("LM_MOE_EXPERTS", "8"))
    ep = int(os.environ.get("LM_MOE_EP", "0")) or _gcd(world, n_experts)
    if world % ep or n_experts % ep:
        sys.exit(f"LM_MOE_EP={ep} must divide both the device count "
                 f"({world}) and LM_MOE_EXPERTS ({n_experts})")
    dp = world // ep
    d_model = int(os.environ.get("LM_MOE_D", "64" if smoke else "1024"))
    hidden_mult = int(os.environ.get("LM_MOE_HIDDEN_MULT",
                                     "2" if smoke else "4"))
    vocab = int(os.environ.get("LM_VOCAB", PRESETS[preset]["vocab"]))
    n_tokens = int(os.environ.get("LM_MOE_TOKENS",
                                  "2048" if smoke else "65536"))
    n_tokens = max(world, n_tokens // world * world)
    cf = float(os.environ.get("LM_MOE_CF", "1.25"))
    warmup = int(os.environ.get("LM_MOE_WARMUP", "1" if smoke else "3"))
    iters = int(os.environ.get("LM_MOE_ITERS", "4" if smoke else "20"))

    mesh = epar.make_dp_ep_mesh(dp, ep)
    from jax.sharding import NamedSharding, PartitionSpec as P

    rng = np.random.RandomState(0)
    key = jax.random.PRNGKey(0)
    host_params = dict(epar.init_moe_params(
        key, d_model, n_experts, hidden_mult=hidden_mult))
    host_params["emb"] = 0.02 * jax.random.normal(
        jax.random.PRNGKey(1), (vocab, d_model), jnp.float32)
    toks = jnp.asarray(rng.randint(0, vocab, (n_tokens + 1,)))
    tokens, targets = toks[:-1], toks[1:]

    def _head_loss(p, h, y, tgt, aux):
        logits = (h + y) @ p["emb"].T      # weight-tied readout
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, tgt).mean()
        return ce + 0.01 * aux

    def dense_loss(p, batch):
        tok, tgt = batch
        h = p["emb"][tok]
        y, aux = epar.dense_moe_apply(p, h)
        return _head_loss(p, h, y, tgt, aux)

    def cap_loss(p, batch, moe):
        tok, tgt = batch
        h = p["emb"][tok]
        y, aux = moe(p, h)
        return _head_loss(p, h, y, tgt, aux)

    tx = optax.adam(1e-2)
    # per-token active params: router + the ONE routed expert's MLP; the
    # embedding lookup and tied head are excluded like the dense bench
    hidden = hidden_mult * d_model
    n_active = d_model * n_experts + 2 * d_model * hidden

    configs = [("exact", None), ("capacity", "off"),
               ("capacity-int8", "int8"), ("capacity-int4", "int4")]
    results = {}
    for name, wire in configs:
        # fresh leaves per config: the donated step consumes the sharded
        # buffers, and device_put may alias the host tree's
        params = epar.shard_params_ep(jax.tree_util.tree_map(
            jnp.array, host_params), mesh)
        if wire is None:
            step = epar.make_ep_train_step(dense_loss, tx, mesh)
            opt = epar.shard_params_ep(tx.init(params), mesh)
            batch = (jax.device_put(tokens, NamedSharding(mesh, P("dp"))),
                     jax.device_put(targets, NamedSharding(mesh, P("dp"))))
        else:
            step = epar.make_ep_train_step(
                cap_loss, tx, mesh, dispatch="capacity",
                capacity_factor=cf, wire=wire)
            opt = epar.moe_opt_state(tx, params, mesh, n_tokens, cf)
            sh = NamedSharding(mesh, P(("dp", "ep")))
            batch = (jax.device_put(tokens, sh),
                     jax.device_put(targets, sh))

        stats = None
        for _ in range(warmup):
            out = step(params, opt, batch)
            params, opt = out[0], out[1]
            jax.block_until_ready(out[2])
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step(params, opt, batch)
            params, opt = out[0], out[1]
        loss = out[2]
        if wire is not None:
            stats = out[3]
        jax.block_until_ready(loss)
        total = time.perf_counter() - t0

        tok_per_s = n_tokens * iters / total
        entry = {
            "tokens_per_sec": round(tok_per_s, 1),
            "mfu_pct": None if smoke else round(
                100 * 6.0 * n_active * tok_per_s
                / (world * PEAK_BF16_TFLOPS[dev.device_kind] * 1e12), 2),
            "loss": round(float(loss), 4),
        }
        if stats is not None:
            load = np.asarray(stats["load"])
            entry["drop_rate"] = round(float(stats["dropped"]) / n_tokens, 4)
            entry["imbalance"] = round(float(load.max() / load.mean()), 3)
        results[name] = entry
        print(f"# {name}: {tok_per_s:,.0f} tok/s loss={entry['loss']} "
              + (f"drop={entry['drop_rate']} imb={entry['imbalance']}"
                 if stats is not None else ""), file=sys.stderr)

    # dispatch-byte catalog for this shape (per step, both directions)
    cap = epar.expert_capacity(n_tokens // world, n_experts, cf)
    per_peer = n_experts * cap * d_model // ep
    bytes_bf16 = comp.moe_wire_footprint(per_peer, "bf16", ep)
    wire_bytes = {m: comp.moe_wire_footprint(per_peer, m, ep)
                  for m in ("bf16", "int8", "int4")}
    ratios = {m: round(v / bytes_bf16, 3) if bytes_bf16 else 0.0
              for m, v in wire_bytes.items()}
    print(f"# dispatch bytes vs bf16: {json.dumps(ratios)}", file=sys.stderr)

    result = {
        "metric": ("moe_lm_smoke_tokens_per_sec" if smoke
                   else "moe_lm_tokens_per_sec"),
        # the shipped quantized default is the headline number the
        # regression gate tracks
        "value": results["capacity-int8"]["tokens_per_sec"],
        "unit": "tok/s",
        **device_stamp(dev),
        "configs": results,
        "wire_byte_ratio_vs_bf16": ratios,
        "experts": n_experts, "ep": ep, "capacity_factor": cf,
    }
    print(json.dumps(result))

    rc = 0
    if args.history:
        from benchmarks.history import (append_record, check_regression,
                                        load_history)

        if args.check_regression:
            verdict = check_regression(
                load_history(args.history, metric=result["metric"]),
                result["value"],
                **{k: v for k, v in (
                    ("window", args.regression_window),
                    ("tolerance", args.regression_tolerance))
                   if v is not None})
            print("# regression check: %s" % json.dumps(verdict),
                  file=sys.stderr)
            if verdict["regression"]:
                print(f"# REGRESSION: {result['metric']} = "
                      f"{result['value']} fell below the floor "
                      f"{verdict['floor']} (baseline {verdict['baseline']} "
                      f"over {verdict['samples']} runs)", file=sys.stderr)
                rc = 3
        append_record(args.history, {
            "metric": result["metric"], "value": result["value"],
            "unit": result["unit"],
            "backend": jax.default_backend(), "devices": world,
            "experts": n_experts, "ep": ep,
            "tokens_per_step": n_tokens,
        })
        print(f"# perf history appended to {args.history}", file=sys.stderr)
    return rc


def main(argv=None):
    # callers (tests) invoke main() bare: no argv means no flags, never
    # pytest's sys.argv
    args = parse_args([] if argv is None else argv)
    if args.moe:
        return run_moe(args)
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import spmd
    from horovod_tpu.models.transformer import (
        TransformerLM, lm_loss, lm_loss_chunked)
    from horovod_tpu.utils import compile_cache

    hvd.init()
    compile_cache.enable()
    preset, dev = resolve_preset()
    smoke = preset == SMOKE_PRESET
    cfg = dict(PRESETS[preset])
    if os.environ.get("LM_BATCH"):
        cfg["batch"] = int(os.environ["LM_BATCH"])
    if os.environ.get("LM_SEQ"):
        cfg["seq"] = int(os.environ["LM_SEQ"])
    vocab = int(os.environ.get("LM_VOCAB", cfg["vocab"]))
    batch, seq = cfg["batch"] * hvd.num_replicas(), cfg["seq"]

    # perf levers (each delta measured in docs/benchmarks.md):
    #   remat=none    — the fused backward keeps only O(T) residuals, so at
    #                   these batch sizes full recompute is pure waste:
    #                   none measured +24.8% over full at seq 1024 (round 5);
    #                   'full' remains the knob for activation-bound shapes
    #                   (e.g. batch 32, or seq 16k with the full-logit loss)
    #   chunked loss  — never materialize [B,T,vocab] fp32 logits
    #   mu_dtype=bf16 — halve AdamW first-moment HBM
    #   donation      — update params/opt state in place (no double buffer)
    remat = os.environ.get("LM_REMAT", "none")
    attn = os.environ.get("LM_ATTN", "pallas")
    # loss path: "auto" takes the full-logit loss while the f32 logit
    # tensor stays under 2 GiB (measured +1.2% at the headline config —
    # the chunked scan's loop boundaries cost more than the logits save
    # at small batch) and the chunked scan beyond (batch >= 16 at the
    # headline vocab; it is what unlocks those batches at all)
    _chunk_env = os.environ.get("LM_CHUNKED_LOSS", "auto")
    if _chunk_env == "auto":
        # PER-REPLICA logit size: logits are batch-sharded over the mesh,
        # so the global batch would over-select the chunked path
        chunked = cfg["batch"] * seq * vocab * 4 > 2 * 2 ** 30
    else:
        chunked = _chunk_env == "1"
    mu_dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[
        os.environ.get("LM_MU_DTYPE", "bf16")]
    donate = os.environ.get("LM_DONATE", "1") == "1"

    attn_fn = None
    if attn == "xla":
        attn_fn = lambda q, k, v: jax.nn.dot_product_attention(
            q, k, v, is_causal=True)
    elif attn == "upstream":
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as _jf)
        def attn_fn(q, k, v):
            d = q.shape[-1]
            o = _jf(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), causal=True,
                    sm_scale=1.0 / float(np.sqrt(d)))
            return o.transpose(0, 2, 1, 3)
    elif attn == "linear":
        # attribution probe, NOT a model: v passes through untouched (wrong
        # math, zero attention FLOPs/DMA) — the measured rate is the step's
        # non-attention ceiling, so (1/rate - 1/linear_rate) is the
        # attention bucket's share of step time
        attn_fn = lambda q, k, v: v
    elif attn != "pallas":
        raise ValueError(
            f"LM_ATTN={attn!r}: expected pallas|xla|upstream|linear")

    model = TransformerLM(
        vocab_size=vocab, num_layers=cfg["num_layers"],
        num_heads=cfg["num_heads"], d_model=cfg["d_model"],
        max_seq_len=seq, dtype=jnp.float32 if smoke else jnp.bfloat16,
        remat=remat, attn_fn=attn_fn)

    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, vocab, (batch, seq + 1)))
    tokens, targets = toks[:, :-1], toks[:, 1:]
    params = model.init(jax.random.PRNGKey(0), tokens[:1])["params"]

    # non-embedding param count for the 6·P·T FLOP model; fail loudly if
    # the model's table names ever change rather than mis-reporting MFU
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    n_emb = params["tok_emb"]["embedding"].size + params["pos_emb"].size
    n_nonemb = n_params - n_emb

    tx = optax.adamw(3e-4, weight_decay=0.01, mu_dtype=mu_dtype)
    opt_state = tx.init(params)
    mesh = hvd.mesh()
    params = spmd.replicate(params, mesh)
    opt_state = spmd.replicate(opt_state, mesh)
    data = spmd.shard_batch((tokens, targets), mesh)

    if chunked:
        chunk_tokens = int(os.environ.get("LM_LOSS_CHUNK", "2048"))
        loss_unroll = int(os.environ.get("LM_LOSS_UNROLL", "1"))

        def loss_fn(p, batch):
            x, y = batch
            hid = model.apply({"params": p}, x, return_hidden=True)
            return lm_loss_chunked(hid, p["tok_emb"]["embedding"], y,
                                   chunk_tokens=chunk_tokens,
                                   unroll=loss_unroll)
    elif os.environ.get("LM_HEAD_BF16", "0") == "1":
        # unchunked full-logit loss, but the weight-tied head matmul in
        # bf16 with f32 accumulation (the MXU-native contraction the
        # chunked path uses) instead of the model's f32 attend
        def loss_fn(p, batch):
            x, y = batch
            hid = model.apply({"params": p}, x, return_hidden=True)
            emb_t = p["tok_emb"]["embedding"].astype(jnp.bfloat16).T
            logits = jnp.dot(hid.astype(jnp.bfloat16), emb_t,
                             preferred_element_type=jnp.float32)
            return lm_loss(logits, y)
    else:
        def loss_fn(p, batch):
            x, y = batch
            return lm_loss(model.apply({"params": p}, x), y)

    # the product's builder: on more than one chip it is what makes the
    # Pallas attention partitionable (spmd.make_train_step)
    zero1 = os.environ.get("LM_ZERO1", "0") == "1"
    if zero1:
        # shard AdamW m/v 1/N over the replica axis (optim/zero.py); a
        # single-chip mesh degenerates to replicated
        from horovod_tpu.optim.zero import shard_opt_state

        opt_state = shard_opt_state(opt_state, mesh)
    step = spmd.make_train_step(
        loss_fn, tx, mesh=mesh, donate=donate, zero1=zero1,
        example_opt_state=opt_state if zero1 else None)
    if dev.platform == "tpu":
        opts = {"xla_tpu_enable_latency_hiding_scheduler": "true"}
        if os.environ.get("LM_VMEM_KIB"):
            opts["xla_tpu_scoped_vmem_limit_kib"] = os.environ["LM_VMEM_KIB"]
        step = step.lower(params, opt_state, data).compile(
            compiler_options=opts)

    for _ in range(cfg["warmup"]):
        params, opt_state, loss = step(params, opt_state, data)
    jax.block_until_ready(loss)

    if os.environ.get("LM_PROFILE"):
        # capture a few steady-state steps; reduce with
        # python3 -m chipbench.op_scopes <file.xplane.pb> 3
        with jax.profiler.trace(os.environ["LM_PROFILE"]):
            for _ in range(3):
                params, opt_state, loss = step(params, opt_state, data)
            jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(cfg["rounds"] * cfg["iters"]):
        params, opt_state, loss = step(params, opt_state, data)
    jax.block_until_ready(loss)
    total = time.perf_counter() - t0

    steps = cfg["rounds"] * cfg["iters"]
    n_dev = hvd.num_replicas()
    tok_per_s = batch * seq * steps / total
    print(f"# platform={dev.platform} kind={dev.device_kind} "
          f"devices={n_dev} params={n_params/1e6:.1f}M "
          f"(non-emb {n_nonemb/1e6:.1f}M) batch={batch} seq={seq} "
          f"loss={float(loss):.3f}", file=sys.stderr)
    mfu_pct = None
    if not smoke:
        # 6·P·T with non-embedding P only — conservative: excludes the
        # logit matmul (weight-tied head) and attention-score FLOPs
        flops_per_s = 6.0 * n_nonemb * tok_per_s
        mfu_pct = round(100 * flops_per_s / (
            n_dev * PEAK_BF16_TFLOPS[dev.device_kind] * 1e12), 2)
        print(f"# tokens/sec: {tok_per_s:,.0f}; model TFLOP/s: "
              f"{flops_per_s/1e12:.1f}; MFU/chip: {mfu_pct:.1f}%",
              file=sys.stderr)
    result = {
        "metric": ("transformer_lm_smoke_tokens_per_sec" if smoke
                   else "transformer_lm_tokens_per_sec"),
        "value": round(tok_per_s, 1),
        "unit": "tok/s",
        "mfu_pct": mfu_pct,
        **device_stamp(dev),
    }
    print(json.dumps(result))

    rc = 0
    if args.history:
        from benchmarks.history import (append_record, check_regression,
                                        load_history)

        # compare against the trajectory BEFORE appending: today's run
        # must not be allowed to vote in its own baseline
        if args.check_regression:
            verdict = check_regression(
                load_history(args.history, metric=result["metric"]),
                result["value"],
                **{k: v for k, v in (
                    ("window", args.regression_window),
                    ("tolerance", args.regression_tolerance))
                   if v is not None})
            print("# regression check: %s" % json.dumps(verdict),
                  file=sys.stderr)
            if verdict["regression"]:
                print(f"# REGRESSION: {result['metric']} = "
                      f"{result['value']} fell below the floor "
                      f"{verdict['floor']} (baseline {verdict['baseline']} "
                      f"over {verdict['samples']} runs)", file=sys.stderr)
                rc = 3
        append_record(args.history, {
            "metric": result["metric"], "value": result["value"],
            "unit": result["unit"], "mfu_pct": result["mfu_pct"],
            "backend": jax.default_backend(), "devices": n_dev,
            "preset": preset,
            "batch": batch, "seq": seq,
        })
        print(f"# perf history appended to {args.history}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
