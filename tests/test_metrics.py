"""Runtime telemetry subsystem tests (docs/metrics.md).

Unit layer: registry semantics (counter/gauge/histogram, label children,
kind collisions), snapshot/merge aggregation modes, Prometheus rendering
and the strict parser, the MetricsReport wire codec, and the HTTP endpoint
(ephemeral port, urllib scrape). API layer: ``hvd.metrics()`` against a
live thread-cluster run, ``MetricsCallback``. Integration layer: a real
2-process job with ``HOROVOD_METRICS_PORT`` set — rank 1 ships its snapshot
over the control channel and rank 0's endpoint serves counts no single rank
could have produced alone (the acceptance criterion).
"""

import json
import os
import pickle
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import testing
from horovod_tpu.metrics import (MetricsRegistry, aggregate, clear_reports,
                                 instruments, local_snapshot,
                                 maybe_start_server, merge_snapshots,
                                 metrics_text, parse_prometheus,
                                 render_prometheus, server_port,
                                 stop_server, store_report)
from horovod_tpu.metrics.http import MetricsHTTPServer
from horovod_tpu.runtime import wire


# ---------------------------------------------------------------- registry
class TestRegistry:
    def test_counter_semantics(self):
        reg = MetricsRegistry()
        c = reg.counter("t_total", "help text")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        assert reg.counter("t_total") is c  # same name -> same object
        with pytest.raises(ValueError):
            c.inc(-1)
        with pytest.raises(TypeError):
            reg.gauge("t_total")  # kind collision

    def test_labeled_children(self):
        reg = MetricsRegistry()
        c = reg.counter("bytes_total", labels=("direction",))
        c.labels(direction="sent").inc(10)
        c.labels(direction="recv").inc(4)
        c.labels(direction="sent").inc(1)
        assert c.labels(direction="sent").value == 11
        assert c.labels(direction="recv").value == 4
        with pytest.raises(ValueError):
            c.labels(wrong="x")
        with pytest.raises(ValueError):
            c.inc()  # labeled metric has no default child

    def test_gauge_agg_modes_in_merge(self):
        snaps = []
        for v in (3.0, 7.0, 5.0):
            reg = MetricsRegistry()
            reg.gauge("g_max", agg="max").set(v)
            reg.gauge("g_min", agg="min").set(v)
            reg.gauge("g_sum", agg="sum").set(v)
            reg.gauge("g_last", agg="last").set(v)
            snaps.append(reg.snapshot())
        merged = merge_snapshots(snaps)
        vals = {n: merged[n]["series"][0]["value"]
                for n in ("g_max", "g_min", "g_sum", "g_last")}
        assert vals == {"g_max": 7.0, "g_min": 3.0, "g_sum": 15.0,
                        "g_last": 5.0}

    def test_histogram_buckets(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=[0.1, 1.0, 10.0])
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        snap = reg.snapshot()["lat"]
        s = snap["series"][0]
        assert s["counts"] == [1, 2, 1, 1]  # non-cumulative, +Inf last
        assert s["count"] == 5
        assert s["sum"] == pytest.approx(56.05)

    def test_merge_empty_snapshot_list(self):
        assert merge_snapshots([]) == {}

    def test_merge_gauge_agg_conflict_first_snapshot_wins(self):
        # two ranks disagree on a gauge's agg mode (version skew during a
        # rolling restart): the first snapshot's mode governs the merge
        # instead of crashing or flip-flopping per input order
        a, b = MetricsRegistry(), MetricsRegistry()
        a.gauge("skewed", agg="max").set(3.0)
        b.gauge("skewed", agg="min").set(9.0)
        merged = merge_snapshots([a.snapshot(), b.snapshot()])
        assert merged["skewed"]["agg"] == "max"
        assert merged["skewed"]["series"][0]["value"] == 9.0
        merged = merge_snapshots([b.snapshot(), a.snapshot()])
        assert merged["skewed"]["agg"] == "min"
        assert merged["skewed"]["series"][0]["value"] == 3.0

    def test_counters_and_histograms_sum_in_merge(self):
        snaps = []
        for _ in range(2):
            reg = MetricsRegistry()
            reg.counter("c_total").inc(4)
            h = reg.histogram("h", buckets=[1.0])
            h.observe(0.5)
            h.observe(2.0)
            snaps.append(reg.snapshot())
        merged = merge_snapshots(snaps)
        assert merged["c_total"]["series"][0]["value"] == 8
        hs = merged["h"]["series"][0]
        assert hs["counts"] == [2, 2] and hs["count"] == 4


# ----------------------------------------------------- render + parse + wire
class TestExposition:
    def _snapshot(self):
        reg = MetricsRegistry()
        reg.counter("hvd_x_total", "bytes moved",
                    labels=("compression",)).labels(
                        compression="int8").inc(100)
        reg.gauge("hvd_epoch", "epoch", agg="max").set(2)
        h = reg.histogram("hvd_lat_seconds", "latency", buckets=[0.1, 1.0])
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        return reg.snapshot()

    def test_render_and_parse_roundtrip(self):
        text = render_prometheus(self._snapshot())
        assert "# TYPE hvd_x_total counter" in text
        assert "# TYPE hvd_lat_seconds histogram" in text
        samples = parse_prometheus(text)
        assert samples["hvd_x_total"][(("compression", "int8"),)] == 100
        assert samples["hvd_epoch"][()] == 2
        buckets = samples["hvd_lat_seconds_bucket"]
        # cumulative: 0.1 -> 1, 1.0 -> 2, +Inf -> 3
        assert buckets[(("le", "0.1"),)] == 1
        assert buckets[(("le", "1"),)] == 2
        assert buckets[(("le", "+Inf"),)] == 3
        assert samples["hvd_lat_seconds_count"][()] == 3

    def test_label_escaping_roundtrip(self):
        # render escapes backslash/quote/newline; the parser must invert
        # them exactly — including the adversarial r'\\n' corner (an
        # escaped backslash followed by a literal n, NOT a newline)
        values = ['plain', 'quo"te', 'back\\slash', 'new\nline',
                  'back\\slash\nand newline', '\\n', '\\\\n', 'tail\\']
        reg = MetricsRegistry()
        c = reg.counter("esc_total", "escape probe", labels=("v",))
        for i, v in enumerate(values):
            c.labels(v=v).inc(i + 1)
        text = render_prometheus(reg.snapshot())
        samples = parse_prometheus(text)
        for i, v in enumerate(values):
            assert samples["esc_total"][(("v", v),)] == i + 1, repr(v)

    def test_parser_is_strict(self):
        with pytest.raises(ValueError):
            parse_prometheus("foo bar baz")  # unparsable value
        with pytest.raises(ValueError):
            parse_prometheus('foo{a=unquoted} 3')  # bad label syntax

    def test_metrics_report_wire_roundtrip(self):
        snap = self._snapshot()
        payload = wire.encode_metrics_report(3, 1234.5, snap)
        rank, ts, decoded = wire.decode_metrics_report(payload)
        assert (rank, ts) == (3, 1234.5)
        # label values survive; the decoded snapshot renders identically
        assert render_prometheus(decoded) == render_prometheus(snap)
        # and merges cleanly with the original (counters double)
        merged = merge_snapshots([snap, decoded])
        assert merged["hvd_x_total"]["series"][0]["value"] == 200

    def test_store_report_aggregation(self):
        clear_reports()
        try:
            reg = MetricsRegistry()
            reg.counter("agg_probe_total").inc(5)
            store_report(1, reg.snapshot(), timestamp=1.0)
            merged = aggregate()
            assert merged["agg_probe_total"]["series"][0]["value"] == 5
            # last-write-wins per rank: a newer report replaces, not adds
            reg.counter("agg_probe_total").inc(2)
            store_report(1, reg.snapshot(), timestamp=2.0)
            merged = aggregate()
            assert merged["agg_probe_total"]["series"][0]["value"] == 7
        finally:
            clear_reports()


# ------------------------------------------------------- bucket quantiles
class TestQuantileFromBuckets:
    def test_basic_walk(self):
        from horovod_tpu.metrics import quantile_from_buckets

        buckets = [0.1, 0.5, 1.0]
        # 10 obs: 5 in <=0.1, 4 in <=0.5, 1 in <=1.0
        assert quantile_from_buckets(buckets, [5, 4, 1], 0.5) == 0.1
        assert quantile_from_buckets(buckets, [5, 4, 1], 0.99) == 1.0

    def test_overflow_reports_past_largest_bound(self):
        from horovod_tpu.metrics import quantile_from_buckets

        # all mass in the implicit +Inf slot
        assert quantile_from_buckets([0.1, 1.0], [0, 0, 7], 0.5) == 2.0

    def test_empty_inputs(self):
        from horovod_tpu.metrics import quantile_from_buckets

        assert quantile_from_buckets([0.1], [0], 0.99) is None
        assert quantile_from_buckets([], [], 0.99) is None


# ----------------------------------------------------------------- endpoint
class TestEndpoint:
    def test_http_server_smoke(self):
        srv = MetricsHTTPServer(0, lambda: "probe_total 42\n")
        srv.start()
        try:
            assert srv.port > 0
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5).read()
            assert parse_prometheus(body.decode())["probe_total"][()] == 42
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope", timeout=5)
        finally:
            srv.stop()

    def test_maybe_start_server_env(self, monkeypatch):
        stop_server()
        monkeypatch.delenv("HOROVOD_METRICS_PORT", raising=False)
        assert maybe_start_server() is None  # unset -> off
        monkeypatch.setenv("HOROVOD_METRICS_PORT", "0")
        try:
            srv = maybe_start_server()
            assert srv is not None and server_port() == srv.port
            assert maybe_start_server() is srv  # idempotent
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5).read()
            parse_prometheus(body.decode())  # endpoint serves the registry
        finally:
            stop_server()
        assert server_port() is None

    def test_liveness_stamps_on_metrics_and_healthz(self, monkeypatch):
        # hvd_up + hvd_snapshot_unix_seconds distinguish a wedged-but-
        # listening job (stale stamp) from a live one: the ENGINE loop
        # stamps them, the endpoint only serves — so a dead engine behind
        # a live HTTP thread shows an aging snapshot, not a fresh one
        from horovod_tpu.metrics import (get_registry, health_summary,
                                         reset_registry)

        stop_server()
        reset_registry()
        monkeypatch.setenv("HOROVOD_METRICS_PORT", "0")
        instruments.up().set(1.0)
        stamped = time.time() - 42.0
        instruments.snapshot_unix_seconds().set(stamped)
        try:
            srv = maybe_start_server()
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5
            ).read().decode()
            samples = parse_prometheus(body)
            assert samples["hvd_up"][()] == 1.0
            assert samples["hvd_snapshot_unix_seconds"][()] == \
                pytest.approx(stamped, abs=1.0)
            doc = health_summary()
            assert doc["snapshot_unix_seconds"] == \
                pytest.approx(stamped, abs=1.0)
            health = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=5
            ).read().decode())
            assert health["snapshot_unix_seconds"] == \
                pytest.approx(stamped, abs=1.0)
        finally:
            stop_server()
            reset_registry()


# ------------------------------------------------------------- live API
class TestLiveAPI:
    def test_hvd_metrics_thread_cluster(self):
        def fn():
            for i in range(3):
                hvd.allreduce(np.ones((8,), np.float32), name="m",
                              op=hvd.Sum)
            return True

        assert all(testing.run_cluster(fn, np=2))
        snap = hvd.metrics()
        text = hvd.metrics(prometheus=True)
        hvd.shutdown()
        for want in ("hvd_allreduce_latency_seconds",
                     "hvd_wire_bytes_total",
                     "hvd_response_cache_hits_total",
                     "hvd_elastic_epoch",
                     "hvd_engine_ticks_total",
                     "hvd_collective_latency_seconds",
                     "hvd_fusion_tensors"):
            assert want in snap and want in text, want
        samples = parse_prometheus(text)
        # 3 allreduces of 8 f32 x 2 thread-ranks = 192 post-negotiation bytes
        key = (("compression", "none"),)
        assert samples["hvd_wire_bytes_total"][key] >= 192
        lat = samples["hvd_allreduce_latency_seconds_count"]
        assert sum(lat.values()) >= 3

    def test_metrics_callback(self, tmp_path):
        path = tmp_path / "m.json"
        cb = hvd.MetricsCallback(str(path), every_n_epochs=2)
        cb.on_epoch_end(0, {})  # (0+1) % 2 != 0 -> no write
        assert not path.exists()
        cb.on_epoch_end(1, {})
        data = json.loads(path.read_text())
        assert data["epoch"] == 1 and isinstance(data["metrics"], dict)


# ----------------------------------------------------------- integration (2p)
def _metrics_job_fn():
    """2 ranks. Both run 4 allreduces under one name (sig-cache traffic),
    rank 1 ships its snapshot, then one more allreduce fences the report's
    arrival at the coordinator (TCP ordering on the control socket). Rank 0
    scrapes its own /metrics endpoint and returns the text."""
    import urllib.request as _url

    import numpy as np  # noqa: F811 (subprocess re-import)

    import horovod_tpu as hvd  # noqa: F811
    from horovod_tpu.metrics import server_port as _port

    hvd.init()
    for i in range(4):
        hvd.allreduce(np.ones((8,), np.float32), name="g", op=hvd.Sum)
    if hvd.rank() != 0:
        # explicit push: deterministic, no reliance on the 5s interval
        hvd.basics._engine().controller.push_metrics()
    hvd.allreduce(np.ones((8,), np.float32), name="fence", op=hvd.Sum)
    out = None
    if hvd.rank() == 0:
        port = _port()
        assert port, "rank 0 did not start the metrics endpoint"
        out = _url.urlopen(f"http://127.0.0.1:{port}/metrics",
                           timeout=10).read().decode()
    hvd.shutdown()
    return out


@pytest.mark.integration
def test_metrics_aggregated_across_processes():
    """Acceptance criterion: a 2-process run with HOROVOD_METRICS_PORT set
    serves Prometheus-parsable text whose allreduce/wire counts exceed what
    rank 0 alone could have produced — i.e. rank 1's MSG_METRICS report was
    aggregated in."""
    import cloudpickle

    from horovod_tpu.run import rendezvous

    here = os.path.dirname(os.path.abspath(__file__))
    secret = rendezvous.make_secret()
    kv = rendezvous.KVStoreServer(secret).start()
    addr = f"127.0.0.1:{kv.port}"
    client = rendezvous.KVStoreClient(addr, secret)
    client.put("runfunc", "fn", cloudpickle.dumps((_metrics_job_fn, (), {})))

    procs = []
    try:
        for r in range(2):
            env = dict(os.environ)
            env.update({
                "HVD_NUM_PROCS": "2",
                "HVD_PROCESS_ID": str(r),
                "HVD_KV_ADDR": addr,
                "HVD_SECRET": secret,
                "HVD_ELASTIC": "1",
                "HOROVOD_METRICS_PORT": "0",
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": os.pathsep.join(
                    [os.path.dirname(here), here]),
            })
            env.pop("XLA_FLAGS", None)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "horovod_tpu.run.task"], env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))

        deadline = time.time() + 150
        blob = None
        while time.time() < deadline:
            blob = client.get("result", "0")
            if blob is not None:
                break
            if procs[0].poll() is not None:
                time.sleep(1.0)  # final result PUT may still be in flight
                blob = client.get("result", "0")
                break
            time.sleep(0.25)
        assert blob is not None, "rank 0 produced no result (deadlocked?)"
        ok, text = pickle.loads(blob)
        assert ok, f"rank 0 raised:\n{text}"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        kv.stop()

    samples = parse_prometheus(text)  # ValueError if not Prometheus text
    # the acceptance catalog is present
    for want in ("hvd_allreduce_latency_seconds_count",
                 "hvd_wire_bytes_total",
                 "hvd_response_cache_hits_total",
                 "hvd_elastic_epoch"):
        assert want in samples, f"/metrics output missing {want}:\n{text}"
    # cross-rank aggregation: rank 0 observed 5 allreduce responses locally;
    # rank 1's report adds >= 4 more. A single rank could never reach 9.
    lat_count = sum(samples["hvd_allreduce_latency_seconds_count"].values())
    assert lat_count >= 9, f"not aggregated across ranks: {lat_count}\n{text}"
    # rank 0: 5 ops x 32B; rank 1's report covers >= its first 4 ops
    wire_bytes = sum(samples["hvd_wire_bytes_total"].values())
    assert wire_bytes >= 9 * 8 * 4, wire_bytes
    # coordinator-side counters: repeated name "g" hit the response cache
    assert sum(samples["hvd_response_cache_hits_total"].values()) > 0
    assert samples["hvd_elastic_epoch"][()] >= 0  # present and sane
