"""The standard horovod_tpu metric catalog (docs/metrics.md).

Each accessor returns the live metric from the process-global registry,
creating it on first touch.  Accessors re-resolve through the registry on
every call (a dict lookup under a lock) so handles never go stale across
``reset_metrics()`` — instrumentation sites may still cache the returned
object locally when they sit in a tight loop.
"""

from __future__ import annotations

from .registry import exponential_buckets, get_registry

#: Fused-batch fill: tensors per executed response.
FUSION_TENSOR_BUCKETS = [1, 2, 4, 8, 16, 32, 64, 128, 256, 512]
#: Fused-batch fill: bytes per executed response (1 KiB .. 1 GiB).
FUSION_BYTE_BUCKETS = exponential_buckets(1024.0, 4.0, 10)


def engine_ticks():
    return get_registry().counter(
        "hvd_engine_ticks_total", "Background engine loop iterations.")


def allreduce_latency():
    return get_registry().histogram(
        "hvd_allreduce_latency_seconds",
        "Wall time of one executed allreduce/adasum response (fused "
        "bucket), submit-batch to results-ready.",
        labels=("dtype", "compression"))


def collective_latency():
    return get_registry().histogram(
        "hvd_collective_latency_seconds",
        "Wall time of one executed response, any collective op.",
        labels=("op",))


def fusion_tensors():
    return get_registry().histogram(
        "hvd_fusion_tensors",
        "Tensors fused into one executed response.",
        buckets=FUSION_TENSOR_BUCKETS)


def fusion_bytes():
    return get_registry().histogram(
        "hvd_fusion_bytes",
        "Payload bytes of one executed response (pre-compression).",
        buckets=FUSION_BYTE_BUCKETS)


def response_cache_hits():
    return get_registry().counter(
        "hvd_response_cache_hits_total",
        "Negotiations answered from the response cache.")


def response_cache_misses():
    return get_registry().counter(
        "hvd_response_cache_misses_total",
        "Negotiations that required a full metadata exchange.")


def negotiations():
    return get_registry().counter(
        "hvd_negotiations_total",
        "Coordinator negotiation rounds that produced responses (rank 0).")


def wire_bytes():
    return get_registry().counter(
        "hvd_wire_bytes_total",
        "Collective payload bytes this rank put on the wire, after "
        "compression — both data planes: the coordinator wire (engine "
        "path) and the compiled GSPMD ring "
        "(compression=\"gspmd-int8\"/\"gspmd-int4\", spmd.py; "
        "docs/gspmd.md).", labels=("compression",))


def wire_bytes_exact():
    return get_registry().counter(
        "hvd_wire_bytes_exact_total",
        "Collective payload bytes the same traffic would have cost "
        "uncompressed (ratio denominator; covers the coordinator wire "
        "and the GSPMD ring).")


def quantization_ratio():
    return get_registry().gauge(
        "hvd_quantization_ratio",
        "Running wire-bytes / exact-bytes ratio (1.0 = no compression "
        "win), over both the coordinator wire and the GSPMD ring.",
        agg="max")


def expert_load():
    return get_registry().gauge(
        "hvd_expert_load",
        "Tokens routed to each expert in the latest MoE report: a routed "
        "layer of the normal path (ops/moe.report_load, from inside the "
        "step under HOROVOD_MOE_REPORT; whichever layer reported last) "
        "or the capacity-dispatch step of parallel/expert.py (global "
        "count, identical on every rank).", labels=("expert",), agg="max")


def moe_load_imbalance():
    return get_registry().gauge(
        "hvd_moe_load_imbalance",
        "max/mean expert load of the latest MoE report, set by the same "
        "two paths as hvd_expert_load (over the experts held here on the "
        "normal path; 1.0 = perfectly balanced router; sustained high "
        "values mean idle experts and, under capacity dispatch, dropped "
        "tokens: the anomaly watch tracks this like straggler skew).",
        agg="max")


def moe_rows():
    return get_registry().gauge(
        "hvd_moe_rows",
        "Rows (token, expert assignments) a routed layer held in its "
        "latest report (ops/moe.report_load under HOROVOD_MOE_REPORT: "
        "sum(group_sizes), what picks the row capacity that runs).",
        labels=("layer",), agg="max")


def moe_rows_over_balanced():
    return get_registry().gauge(
        "hvd_moe_rows_over_balanced",
        "hvd_moe_rows over the rows a balanced router sends the layer "
        "(assignments x held / experts).", labels=("layer",), agg="max")


def moe_reports():
    return get_registry().counter(
        "hvd_moe_reports_total",
        "Reports of a routed layer by the row capacity its rows select: "
        "capacity=all is the worst-case program (every assignment), fit "
        "any smaller one. A report is one traced execution of the layer, "
        "not a step: a recomputed forward pass reports again.",
        labels=("layer", "capacity"))


def moe_dropped_tokens():
    return get_registry().counter(
        "hvd_moe_dropped_tokens_total",
        "Tokens dropped by capacity-factor MoE dispatch (parallel/expert.py "
        "alone: routed past their expert's buffer, they contribute zero "
        "to the MoE output; ops/moe.routed_ffn drops none — docs/moe.md).")


def moe_capacity_factor():
    return get_registry().gauge(
        "hvd_moe_capacity_factor",
        "Capacity factor of the running parallel/expert.py train step "
        "(buffer slots = ceil(CF * tokens / experts); ops/moe.routed_ffn "
        "has none).", agg="max")


def bitwidth_decisions():
    return get_registry().counter(
        "hvd_bitwidth_decisions_total",
        "Adaptive-wire bitwidth decision changes, labelled by the grid "
        "switched to (ops/adaptive.py BitwidthSelector).",
        labels=("wire",))


def adaptive_bitwidth():
    return get_registry().gauge(
        "hvd_adaptive_bitwidth",
        "Most recently selected adaptive-wire grid, in bits "
        "(4 = int4, 8 = int8, 16 = bf16 fallback).")


def collective_algorithm():
    return get_registry().gauge(
        "hvd_collective_algorithm",
        "Collective algorithm in play per payload-size class "
        "(0 = ring, 1 = tree, 2 = hierarchical — ops/adaptive.ALGO_CODES).",
        labels=("class",))


def error_feedback_roundtrips():
    return get_registry().counter(
        "hvd_error_feedback_roundtrips_total",
        "Eager quantize/dequantize round trips with EF-SGD residual "
        "accumulation (ops/compression.py quantize_roundtrip).")


def control_bytes():
    return get_registry().counter(
        "hvd_control_bytes_total",
        "Control-plane (coordinator TCP) frame bytes.",
        labels=("direction",))


def elastic_epoch():
    return get_registry().gauge(
        "hvd_elastic_epoch",
        "Current membership epoch (0 for non-elastic jobs).", agg="max")


def elastic_rank_lost():
    return get_registry().counter(
        "hvd_elastic_rank_lost_total",
        "Workers declared lost by the coordinator (elastic membership).")


def stalled_tensors():
    return get_registry().gauge(
        "hvd_stalled_tensors",
        "Tensors currently past the stall-check deadline with ranks "
        "missing.", agg="max")


def control_reconnects():
    return get_registry().counter(
        "hvd_control_reconnects_total",
        "Successful worker-side control-plane reconnects (transparent "
        "recovery from a dropped coordinator connection).")


def heartbeat_misses():
    return get_registry().counter(
        "hvd_heartbeat_misses_total",
        "Worker heartbeat intervals the coordinator observed as missed "
        "(HOROVOD_HEARTBEAT_INTERVAL elapsed with no frame from a rank).")


def frames_rejected():
    return get_registry().counter(
        "hvd_frames_rejected_total",
        "Control-plane frames rejected for integrity violations "
        "(CRC32/HMAC mismatch or an over-bound length prefix).")


def grad_nonfinite():
    return get_registry().counter(
        "hvd_grad_nonfinite_total",
        "Gradient tensors this rank observed with NaN/Inf values before "
        "allreduce (HOROVOD_GRAD_GUARD detection, any policy but off).")


def steps_skipped():
    return get_registry().counter(
        "hvd_steps_skipped_total",
        "Optimizer steps dropped globally because some rank's gradients "
        "were non-finite (HOROVOD_GRAD_GUARD=skip).")


def param_desync():
    return get_registry().counter(
        "hvd_param_desync_total",
        "Parameter tensors whose cross-rank digest diverged from the "
        "root's (consistency auditor, HOROVOD_CONSISTENCY_INTERVAL).")


def integrity_heals():
    return get_registry().counter(
        "hvd_integrity_heals_total",
        "Self-heal re-broadcasts of the full parameter set from the root "
        "after a digest divergence (HOROVOD_CONSISTENCY_POLICY=heal).")


def collective_timeouts():
    return get_registry().counter(
        "hvd_collective_timeouts_total",
        "Collectives forcibly failed after stalling past "
        "HOROVOD_COLLECTIVE_TIMEOUT (enforced watchdog; each firing also "
        "names the missing ranks in the CollectiveTimeoutError).")


def exposed_comm_seconds():
    return get_registry().gauge(
        "hvd_exposed_comm_seconds",
        "Cumulative wall time this rank spent blocked in synchronize() "
        "waiting on collective results — communication NOT hidden behind "
        "compute (the hvdprof exposed-communication headline).", agg="sum")


def straggler_skew_seconds():
    return get_registry().gauge(
        "hvd_straggler_skew_seconds",
        "Enqueue-time spread (slowest minus fastest rank) observed at the "
        "most recent negotiation a tensor became ready — how long fast "
        "ranks waited for the straggler.", agg="max")


def partial_collectives():
    return get_registry().counter(
        "hvd_partial_collectives_total",
        "Collectives completed over a straggler-excluded subgroup instead "
        "of the full member set (rank 0 straggler policy).")


def excluded_rank():
    return get_registry().gauge(
        "hvd_excluded_rank",
        "Highest rank currently excluded by the straggler policy, or -1 "
        "when every member is participating.", agg="max")


def straggler_promotions():
    return get_registry().counter(
        "hvd_straggler_promotions_total",
        "Chronically slow ranks escalated to rank_lost / hot-spare "
        "promotion after trailing excluded past "
        "HOROVOD_STRAGGLER_MAX_SKIP rounds.")


def trace_dropped_events():
    return get_registry().counter(
        "hvd_trace_dropped_events_total",
        "Trace spans dropped because the HOROVOD_TRACE_BUFFER ring (or "
        "rank 0's merge store) was full.")


def anomaly_active():
    return get_registry().gauge(
        "hvd_anomaly_active",
        "Live anomaly-watch verdict per tracked signal (1 = the current "
        "window deviates from its rolling baseline; HOROVOD_ANOMALY_WATCH, "
        "docs/observability.md).", labels=("signal",), agg="max")


def blackbox_dumps():
    return get_registry().counter(
        "hvd_blackbox_dumps_total",
        "Flight-recorder postmortem dumps written by this process on "
        "abnormal exit (HOROVOD_BLACKBOX).")


def coord_batch_ranks():
    return get_registry().histogram(
        "hvd_coord_batch_ranks",
        "Ranks carried per batched negotiation frame received by the "
        "coordinator, labeled by the sending tier ('host' for legacy "
        "MSG_BATCH host frames, the tier number for grouped MSG_TBATCH "
        "frames; HOROVOD_HIERARCHICAL_COORD, HOROVOD_HIERARCHY_TIERS; "
        "docs/control-plane.md).", labels=("tier",),
        buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 4096,
                 16384, 65536, 262144))


def coord_tier_depth():
    return get_registry().gauge(
        "hvd_coord_tier_depth",
        "Configured aggregation-tree depth of the hierarchical control "
        "plane (1 = the single host tier; HOROVOD_HIERARCHY_TIERS; "
        "docs/control-plane.md).", agg="max")


def coord_failovers():
    return get_registry().counter(
        "hvd_coord_failovers_total",
        "Coordinator failovers: the warm standby promoted itself after "
        "losing its replication stream to rank 0 "
        "(HOROVOD_STANDBY_COORD; docs/control-plane.md).")


def fencing_epoch():
    return get_registry().gauge(
        "hvd_fencing_epoch",
        "Highest coordinator fencing epoch this process has observed "
        "(0 until lease-based leadership is enabled or seen; "
        "HOROVOD_LEASE_TTL; docs/fault-tolerance.md).", agg="max")


def lease_renewals():
    return get_registry().counter(
        "hvd_lease_renewals_total",
        "Successful coordinator-lease renewals by the active leader "
        "(HOROVOD_LEASE_TTL/HOROVOD_LEASE_RENEW; a stalling rate here "
        "predicts a self-fence; docs/fault-tolerance.md).")


def frames_fenced():
    return get_registry().counter(
        "hvd_frames_fenced_total",
        "Control frames rejected for carrying a stale fencing epoch — a "
        "deposed-but-still-running coordinator's traffic being ignored "
        "(docs/fault-tolerance.md).")


def epoch_coalesced_joins():
    return get_registry().counter(
        "hvd_epoch_coalesced_joins_total",
        "Extra joiners folded into an already-pending membership epoch "
        "bump by admission batching (HOROVOD_ADMISSION_BATCH_MS) — each "
        "one is an epoch reset the job did NOT pay for.")


def standby_journal_lag():
    return get_registry().gauge(
        "hvd_standby_journal_lag",
        "Journal records queued at rank 0 but not yet shipped to a warm "
        "standby, labeled by the standby's tier ('root' for the global "
        "rank-0 standby, the tier number for subtree-scoped streams; "
        "docs/control-plane.md).", labels=("tier",), agg="max")


# --------------------------------------------------------------- serving
# The inference-serving catalog (serving/, docs/inference.md). Request
# latencies use the default LATENCY_BUCKETS, whose bucket-count deltas are
# also what the anomaly watch derives its live p99 from.

def serving_requests():
    return get_registry().counter(
        "hvd_serving_requests_total",
        "Serving requests by terminal disposition (submitted / completed / "
        "failed / rejected / readmitted).", labels=("status",))


def serving_request_latency():
    return get_registry().histogram(
        "hvd_serving_request_latency_seconds",
        "Request latency: submit-to-done (stage=total) and submit-to-first-"
        "token (stage=first_token). p50/p99 derive from bucket counts.",
        labels=("stage",))


def serving_phase_seconds():
    return get_registry().histogram(
        "hvd_serving_phase_seconds",
        "Engine phase wall time per step (phase=prefill|decode).",
        labels=("phase",))


def serving_tokens():
    return get_registry().counter(
        "hvd_serving_tokens_total",
        "Tokens processed: prompt tokens prefilled (phase=prefill) and "
        "tokens generated (phase=decode). rate(phase=decode) is the "
        "tokens/s headline.", labels=("phase",))


def serving_decode_batch():
    return get_registry().histogram(
        "hvd_serving_decode_batch",
        "In-flight requests per batched decode step (continuous-batching "
        "fill; max is the HOROVOD_SERVING_MAX_BATCH width).",
        buckets=(1, 2, 4, 8, 16, 32, 64, 128))


def serving_queue_depth():
    return get_registry().gauge(
        "hvd_serving_queue_depth",
        "Requests waiting in the admission queue (bounded by "
        "HOROVOD_SERVING_MAX_QUEUE; sustained depth = saturation).",
        agg="max")


def serving_active_requests():
    return get_registry().gauge(
        "hvd_serving_active_requests",
        "Requests currently in the decode batch.", agg="max")


def serving_kv_occupancy():
    return get_registry().gauge(
        "hvd_serving_kv_occupancy",
        "Fraction of KV-cache blocks allocated (the admission-control "
        "currency; 1.0 = no new request can be admitted).", agg="max")


def serving_kv_tokens():
    return get_registry().gauge(
        "hvd_serving_kv_tokens",
        "Token slots actually written in the KV cache (live context "
        "payload, vs the block-granular hvd_serving_kv_occupancy).",
        agg="max")


def serving_shed():
    return get_registry().counter(
        "hvd_serving_shed_total",
        "Requests degraded by overload admission control: class=best_effort "
        "counts hard sheds (SERVE_SHED answered without dispatch), "
        "class=brownout counts best-effort requests whose max_new was "
        "clamped. High-priority traffic is never shed.",
        labels=("class",))


def serving_hedges():
    return get_registry().counter(
        "hvd_serving_hedges_total",
        "Tail-latency hedges: outcome=launched (second replica engaged "
        "after the p95-derived delay), outcome=won (hedge answered first; "
        "original cancelled), outcome=lost (original answered first; hedge "
        "cancelled).", labels=("outcome",))


def serving_cancels():
    return get_registry().counter(
        "hvd_serving_cancels_total",
        "Request cancellations by reason: client (explicit / disconnect), "
        "deadline (wire budget expired), ttl (orphan sweep), propagated "
        "(frontend-to-worker MSG_SERVE_CANCEL applied), hedge (losing "
        "duplicate).", labels=("reason",))


def serving_frontend_failovers():
    return get_registry().counter(
        "hvd_serving_frontend_failovers_total",
        "Serving-frontend standby promotions (lease takeover or replication "
        "stream loss). Paired with a K_FAILOVER blackbox event naming the "
        "promoted address.")


def checkpoint_stall_seconds():
    return get_registry().counter(
        "hvd_checkpoint_stall_seconds",
        "Seconds the training step path spent handing snapshots to the "
        "async checkpoint writer (ckpt/writer.py). The write-behind design "
        "keeps this ~0; growth means the step path is blocking on "
        "checkpoint I/O.")


def checkpoint_bytes():
    return get_registry().counter(
        "hvd_checkpoint_bytes_total",
        "Checkpoint bytes shipped, by destination: kind=disk (shard + "
        "replica files landed in HOROVOD_CKPT_DIR) and kind=peer (buddy "
        "journal payloads to the ring successor).", labels=("kind",))


def ckpt_bundle_age_steps():
    return get_registry().gauge(
        "hvd_ckpt_bundle_age_steps",
        "Steps since the last FINALIZED checkpoint bundle (0 right after a "
        "manifest lands). Sustained age above ~2x HOROVOD_CKPT_INTERVAL "
        "means shards are being written but bundles never complete — a "
        "lagging or wedged member (hvddoctor: stale_checkpoint).",
        agg="max")


# --------------------------------------------------------------- goodput
# The time-attribution ledger (goodput/, docs/goodput.md). Counters carry
# a rank label so per-rank attribution survives the cross-rank merge
# (counters sum, but label sets stay disjoint per rank).

def goodput_seconds():
    return get_registry().counter(
        "hvd_goodput_seconds_total",
        "Wall-clock seconds attributed to useful compute by the goodput "
        "ledger, per rank (goodput/ledger.py; docs/goodput.md).",
        labels=("rank",))


def badput_seconds():
    return get_registry().counter(
        "hvd_badput_seconds_total",
        "Wall-clock seconds NOT spent computing, by cause (exposed_comm / "
        "stall / checkpoint / recovery / excluded / idle) and rank — the "
        "goodput ledger's badput breakdown (docs/goodput.md).",
        labels=("cause", "rank"))


def goodput_ratio():
    return get_registry().gauge(
        "hvd_goodput_ratio",
        "Fraction of this rank's wall-clock attributed to compute since "
        "init (merge takes the min: the fleet is only as good as its "
        "worst rank; the fleet-weighted ratio derives from the seconds "
        "counters).", labels=("rank",), agg="min")


def goodput_wall_seconds():
    return get_registry().gauge(
        "hvd_goodput_wall_seconds",
        "Wall-clock seconds the goodput ledger has been attributing on "
        "each rank (the completeness denominator: the per-rank state sums "
        "should cover >= 99% of this).", labels=("rank",), agg="max")


def slo_burn_rate():
    return get_registry().gauge(
        "hvd_slo_burn_rate",
        "Error-budget burn rate per declared SLO (HOROVOD_SLO): the "
        "fast-window bad-fraction divided by the objective's allowance. "
        "1.0 = burning exactly the budget; sustained >1 exhausts it "
        "(goodput/slo.py; docs/goodput.md).", labels=("slo",), agg="max")


def up():
    return get_registry().gauge(
        "hvd_up",
        "1 while the engine loop is alive (set at init, refreshed every "
        "metrics push, 0 at shutdown). Scrape alongside "
        "hvd_snapshot_unix_seconds to tell a wedged-but-listening rank "
        "from a healthy one.", agg="min")


def snapshot_unix_seconds():
    return get_registry().gauge(
        "hvd_snapshot_unix_seconds",
        "Unix time the engine loop last refreshed this registry (NOT the "
        "scrape time — a stale value under a live /metrics endpoint means "
        "the process is wedged).", agg="max")


# -- set-up of the compiled path (metrics/phases.py; docs/observability.md) --

def phase_seconds():
    return get_registry().counter(
        "hvd_phase_seconds_total",
        "Host seconds by phase of the compiled path (import, init, "
        "shutdown, compile/trace, compile/lower, compile/backend, "
        "compile/cache_read): each span's self time, its interval less "
        "what spans nested in it on the same thread cover, so a thread's "
        "phases add up to its wall time (metrics/phases.py).",
        labels=("phase",))


def phase_count():
    return get_registry().counter(
        "hvd_phase_total", "Spans closed, by phase.", labels=("phase",))


def phase_spans_dropped():
    return get_registry().counter(
        "hvd_phase_spans_dropped_total",
        "Spans counted in hvd_phase_* and left out of the in-memory span "
        "list because it was at its cap (phases.MAX_SPANS).")


def compiles():
    return get_registry().counter(
        "hvd_compiles_total",
        "Programs JAX handed to the backend (compiled, or loaded from the "
        "persistent cache), by jitted function; names past the first few "
        "dozen are _other. An increment after warm-up is a recompile.",
        labels=("program",))


def compile_cache_requests():
    return get_registry().counter(
        "hvd_compile_cache_requests_total",
        "Programs looked up in JAX's persistent compilation cache.")


def compile_cache():
    return get_registry().counter(
        "hvd_compile_cache_total",
        "Persistent compilation cache outcomes: hit = loaded, miss = "
        "compiled and written. A request that is neither was compiled and "
        "not kept (under JAX's compile-time or entry-size threshold).",
        labels=("outcome",))


def compile_seconds_saved():
    return get_registry().counter(
        "hvd_compile_seconds_saved_total",
        "Compile seconds the persistent cache saved: each hit's recorded "
        "compile time less the time its load took, where positive.")
