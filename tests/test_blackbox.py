"""Black-box flight recorder + hvddoctor + anomaly watch tests
(docs/observability.md).

Unit layer: the bounded event ring and its env-sized capacity, dump
construction / idempotence / dead-rank stubs / bundle assembly, the
MSG_BLACKBOX wire codec, every known-failure signature detector over
synthetic bundles, first-divergence and merged-timeline analysis, the
hvddoctor CLI, the RollingBaseline and the AnomalyWatch fed synthetic
snapshots, the /healthz summary and endpoint, and the dropped-rank
metrics ledger (a stale MSG_METRICS after rank_lost must not resurrect
a dead rank's gauges). Acceptance: with ``HOROVOD_BLACKBOX`` unset the
engine allocates ZERO blackbox objects across a full cluster run; a
real 2-process job wedged at a collective under the enforced watchdog
leaves dumps from BOTH ranks that hvddoctor diagnoses as a collective
deadlock naming the tensor and the missing rank.
"""

import json
import os
import sys

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import blackbox, testing
from horovod_tpu.blackbox import doctor, signatures as sigs, watch
from horovod_tpu.blackbox.recorder import (DEFAULT_EVENTS, Event,
                                           FlightRecorder, allocation_count,
                                           ring_capacity)
from horovod_tpu.blackbox.signatures import RollingBaseline
from horovod_tpu.blackbox.watch import AnomalyWatch
from horovod_tpu.metrics import (clear_reports, drop_report, health_summary,
                                 readmit_report, report_ranks,
                                 set_health_source, store_report)
from horovod_tpu.runtime import coordinator, wire

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ENV_VARS = ("HOROVOD_BLACKBOX", "HOROVOD_BLACKBOX_DIR",
             "HOROVOD_BLACKBOX_EVENTS", "HOROVOD_ANOMALY_WATCH",
             "HOROVOD_ANOMALY_INTERVAL", "HOROVOD_ANOMALY_WINDOW",
             "HOROVOD_ANOMALY_FACTOR")


@pytest.fixture(autouse=True)
def _fresh_blackbox(monkeypatch):
    """Blackbox off and module state clean on both sides of every test."""
    for var in _ENV_VARS:
        monkeypatch.delenv(var, raising=False)
    watch.stop_watch()
    blackbox.reset_for_tests()
    clear_reports()
    set_health_source(None)
    yield
    watch.stop_watch()
    blackbox.reset_for_tests()
    clear_reports()
    set_health_source(None)


def _activate(monkeypatch, tmp_path, rank=0, world=2):
    monkeypatch.setenv("HOROVOD_BLACKBOX", "1")
    monkeypatch.setenv("HOROVOD_BLACKBOX_DIR", str(tmp_path))
    rec = blackbox.maybe_activate()
    blackbox.set_identity(rank, world)
    return rec


# ---------------------------------------------------------------- recorder
class TestRecorder:
    def test_ring_caps_and_drops_oldest(self):
        rec = FlightRecorder(capacity=4)
        for i in range(10):
            rec.record(blackbox.K_COLLECTIVE, f"t{i}")
        assert len(rec) == 4
        assert rec.dropped == 6
        assert [e.name for e in rec.events()] == ["t6", "t7", "t8", "t9"]

    def test_capacity_env_knob(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_BLACKBOX_EVENTS", "16")
        assert ring_capacity() == 16
        monkeypatch.setenv("HOROVOD_BLACKBOX_EVENTS", "not-a-number")
        assert ring_capacity() == DEFAULT_EVENTS
        monkeypatch.setenv("HOROVOD_BLACKBOX_EVENTS", "0")
        assert ring_capacity() == 1  # never zero: a ring must hold the end

    def test_event_dict_roundtrip(self):
        ev = Event(12.5, 3, blackbox.K_TIMEOUT, "g0", "waited 3s on ranks [1]")
        assert ev.as_dict() == {"t": 12.5, "rank": 3, "kind": "timeout",
                                "name": "g0",
                                "detail": "waited 3s on ranks [1]"}

    def test_off_by_default(self):
        assert "HOROVOD_BLACKBOX" not in os.environ
        assert blackbox.maybe_activate() is None
        assert blackbox.active() is None
        blackbox.record(blackbox.K_ERROR, "x", "noop when off")
        assert blackbox.dump("nothing to dump") is None

    def test_maybe_activate_idempotent(self, monkeypatch, tmp_path):
        rec = _activate(monkeypatch, tmp_path)
        assert rec is not None
        assert blackbox.maybe_activate() is rec
        assert blackbox.active() is rec


# ------------------------------------------------------------------- dumps
class TestDump:
    def test_dump_writes_doc_once(self, monkeypatch, tmp_path):
        _activate(monkeypatch, tmp_path, rank=0, world=2)
        blackbox.record(blackbox.K_COLLECTIVE, "g0", "enqueue ALLREDUCE")
        path = blackbox.dump("test: boom")
        assert path == str(tmp_path / "rank_0.json")
        doc = json.load(open(path))
        assert doc["rank"] == 0 and doc["world_size"] == 2
        assert doc["reason"] == "test: boom"
        assert [e["name"] for e in doc["events"]] == ["g0"]
        assert "metrics" in doc and "open_spans" in doc
        # idempotent: the first abnormal symptom wins
        assert blackbox.dump("cascade symptom") is None
        assert json.load(open(path))["reason"] == "test: boom"

    def test_worker_dump_ships_to_rank0(self, monkeypatch, tmp_path):
        _activate(monkeypatch, tmp_path, rank=1, world=2)
        shipped = []
        blackbox.set_shipper(shipped.append)
        blackbox.dump("worker abort")
        assert os.path.exists(tmp_path / "rank_1.json")  # local copy too
        assert len(shipped) == 1
        assert json.loads(shipped[0])["rank"] == 1

    def test_rank0_writes_dead_stubs_and_bundle(self, monkeypatch, tmp_path):
        _activate(monkeypatch, tmp_path, rank=0, world=2)
        blackbox.note_dead_rank(1, "heartbeat timeout after 10s")
        blackbox.dump("rank 1 never came back")
        stub = json.load(open(tmp_path / "rank_1.json"))
        assert stub["stub"] is True
        assert "heartbeat timeout" in stub["reason"]
        bundle = json.load(open(tmp_path / "bundle.json"))
        assert bundle["blackbox_bundle"] == blackbox.BLACKBOX_VERSION
        assert sorted(bundle["ranks"]) == ["0", "1"]

    def test_store_dump_reassembles_for_late_arrivals(self, monkeypatch,
                                                      tmp_path):
        _activate(monkeypatch, tmp_path, rank=0, world=2)
        blackbox.dump("rank 0 died first")
        worker_doc = {"blackbox": 1, "rank": 1, "world_size": 2,
                      "reason": "late worker dump", "events": []}
        blackbox.store_dump(1, json.dumps(worker_doc))
        assert json.load(open(tmp_path / "rank_1.json"))["reason"] \
            == "late worker dump"
        bundle = json.load(open(tmp_path / "bundle.json"))
        assert sorted(bundle["ranks"]) == ["0", "1"]

    def test_excepthook_dumps(self, monkeypatch, tmp_path, capsys):
        _activate(monkeypatch, tmp_path, rank=0, world=1)
        assert sys.excepthook is not sys.__excepthook__
        sys.excepthook(ValueError, ValueError("boom"), None)
        doc = json.load(open(tmp_path / "rank_0.json"))
        assert doc["reason"].startswith("unhandled exception: ValueError")
        assert doc["events"][-1]["kind"] == blackbox.K_ERROR
        capsys.readouterr()  # swallow the chained default hook's traceback

    def test_finalize_is_silent(self, monkeypatch, tmp_path):
        _activate(monkeypatch, tmp_path)
        blackbox.finalize()  # normal shutdown: no dump, hooks restored
        assert blackbox.active() is None
        assert not os.path.exists(tmp_path / "rank_0.json")
        assert sys.excepthook is not blackbox._on_unhandled


# -------------------------------------------------------------- wire codec
class TestWire:
    def test_msg_blackbox_is_distinct(self):
        others = {coordinator.MSG_HELLO, coordinator.MSG_LIST,
                  coordinator.MSG_RESP, coordinator.MSG_BYE,
                  coordinator.MSG_DATA, coordinator.MSG_DATA_RESP,
                  coordinator.MSG_METRICS, coordinator.MSG_HEARTBEAT,
                  coordinator.MSG_RESUME, coordinator.MSG_TRACE,
                  coordinator.MSG_CLOCK, coordinator.MSG_CLOCK_RESP}
        assert coordinator.MSG_BLACKBOX not in others

    def test_dump_codec_roundtrip(self):
        doc = json.dumps({"rank": 3, "events": [{"kind": "error"}],
                          "reason": "unicode détail ✓"})
        payload = wire.encode_blackbox_dump(3, 1234.5, doc)
        rank, t, out = wire.decode_blackbox_dump(payload)
        assert (rank, t, out) == (3, 1234.5, doc)


# -------------------------------------------------------------- signatures
def _ev(kind, name="", detail="", rank=0, t=0.0):
    return {"t": t, "rank": rank, "kind": kind, "name": name,
            "detail": detail}


def _bundle(events_by_rank, world=None, reasons=None):
    world = world if world is not None else len(events_by_rank)
    return {r: {"blackbox": 1, "rank": r, "world_size": world,
                "reason": (reasons or {}).get(r, "test"), "events": evs,
                "metrics": {}, "open_spans": []}
            for r, evs in events_by_rank.items()}


class TestSignatures:
    def test_parse_ranks_phrasings(self):
        assert sigs.parse_ranks("waited 3s on ranks [1, 2]") == [1, 2]
        assert sigs.parse_ranks("from rank(s) ['0']") == [0]
        assert sigs.parse_ranks("no brackets here") == []

    def test_parse_step(self):
        assert sigs.parse_step("non-finite gradients (step 7)") == 7
        assert sigs.parse_step("no step") is None

    def test_collective_deadlock_from_timeout(self):
        b = _bundle({0: [_ev(blackbox.K_TIMEOUT, "g0",
                             "collective timeout: tensor 'g0' waited 3s on "
                             "ranks [1] (HOROVOD_COLLECTIVE_TIMEOUT=3s "
                             "exceeded)")],
                     1: []})
        out = sigs.match_signatures(b)
        dl = [s for s in out if s["id"] == "collective_deadlock"]
        assert len(dl) == 1
        assert dl[0]["severity"] == sigs.SEV_CRITICAL
        assert dl[0]["evidence"]["tensor"] == "g0"
        assert dl[0]["evidence"]["missing_ranks"] == [1]

    def test_collective_deadlock_from_unresolved_stall(self):
        b = _bundle({0: [_ev(blackbox.K_STALL, "g1",
                             "waiting on ranks [1] for 60s")]})
        dl = sigs.detect_collective_deadlock(b)
        assert len(dl) == 1 and "never resolved" in dl[0]["summary"]
        assert dl[0]["evidence"]["missing_ranks"] == [1]

    def test_param_desync_earliest_step_wins(self):
        b = _bundle({0: [_ev(blackbox.K_VERDICT, "auditor",
                             "parameter desync on rank(s) [1] (step 12)"),
                         _ev(blackbox.K_VERDICT, "auditor",
                             "parameter desync on rank(s) [1] (step 7)")]})
        out = sigs.detect_param_desync(b)
        assert len(out) == 1
        assert out[0]["evidence"]["origin_step"] == 7
        assert out[0]["evidence"]["ranks"] == [1]

    def test_nan_first_earliest_event_names_origin(self):
        b = _bundle({0: [_ev(blackbox.K_VERDICT, "gradguard",
                             "non-finite values in tensor 'g' submitted by "
                             "rank(s) [1]", t=5.0)],
                     1: [_ev(blackbox.K_VERDICT, "gradguard",
                             "non-finite values in tensor 'g' submitted by "
                             "rank(s) [0]", t=9.0)]})
        out = sigs.detect_nan_first(b)
        assert len(out) == 1 and out[0]["evidence"]["rank"] == 1

    def test_dead_worker(self):
        b = _bundle({0: [_ev(blackbox.K_RANK_LOST, "rank_1",
                             "heartbeat timeout", rank=1)]})
        out = sigs.detect_dead_worker(b)
        assert len(out) == 1 and out[0]["evidence"]["rank"] == 1

    def test_straggler_repeat_offender(self):
        b = _bundle({0: [_ev(blackbox.K_STALL, "g0",
                             "waiting on ranks [1] for 60s"),
                         _ev(blackbox.K_STALL, "g1",
                             "waiting on ranks [1] for 60s")]})
        out = sigs.detect_straggler(b)
        assert len(out) == 1 and out[0]["evidence"]["rank"] == 1

    def test_reconnect_storm_threshold(self):
        evs = [_ev(blackbox.K_RECONNECT, "rank_1", "resumed", rank=1, t=i)
               for i in range(sigs.RECONNECT_STORM_COUNT)]
        assert sigs.detect_reconnect_storm(_bundle({0: evs}))
        assert not sigs.detect_reconnect_storm(_bundle({0: evs[:-1]}))

    def test_tier_aggregator_flap(self):
        evs = [_ev(blackbox.K_RECONNECT, "tier_1",
                   "sub-coordinator tier 1 index 0 reconnected upstream",
                   rank=8, t=i)
               for i in range(sigs.TIER_FLAP_COUNT)]
        out = sigs.detect_tier_aggregator_flap(_bundle({8: evs}))
        assert len(out) == 1
        assert out[0]["id"] == "tier_aggregator_flap"
        assert out[0]["evidence"]["tier"] == 1
        assert out[0]["evidence"]["reconnects"] == sigs.TIER_FLAP_COUNT
        assert not sigs.detect_tier_aggregator_flap(
            _bundle({8: evs[:-1]}))
        # per-rank reconnect events never count toward a TIER flap
        rank_evs = [_ev(blackbox.K_RECONNECT, "rank_1", "resumed", rank=1,
                        t=i) for i in range(sigs.TIER_FLAP_COUNT)]
        assert not sigs.detect_tier_aggregator_flap(
            _bundle({0: rank_evs}))

    def test_heartbeat_flap_counts_silences(self):
        evs = [_ev(blackbox.K_HEARTBEAT, "rank_1",
                   "rank 1 missed 1 heartbeat interval(s)", rank=1, t=1),
               _ev(blackbox.K_HEARTBEAT, "rank_1",
                   "rank 1 ok (heartbeats resumed)", rank=1, t=2),
               _ev(blackbox.K_HEARTBEAT, "rank_1",
                   "rank 1 missed 2 heartbeat interval(s)", rank=1, t=3)]
        out = sigs.detect_heartbeat_flap(_bundle({0: evs}))
        assert len(out) == 1 and out[0]["evidence"]["flaps"] == 2
        assert not sigs.detect_heartbeat_flap(_bundle({0: evs[:2]}))

    def test_budget_exhausted_names_dominant_cause_and_ranks(self):
        b = _bundle({0: [], 1: []})
        b[0]["metrics"] = {
            "hvd_slo_burn_rate": {"series": [
                {"labels": {"slo": "goodput"}, "value": 6.0},
                {"labels": {"slo": "step_p99"}, "value": 0.5}]},
            "hvd_badput_seconds_total": {"series": [
                {"labels": {"cause": "recovery", "rank": "1"},
                 "value": 40.0},
                {"labels": {"cause": "stall", "rank": "0"}, "value": 5.0},
                {"labels": {"cause": "idle", "rank": "0"},
                 "value": 500.0}]}}
        out = sigs.detect_budget_exhausted(b)
        assert len(out) == 1  # step_p99 burns below threshold: no signature
        ev = out[0]["evidence"]
        assert out[0]["id"] == "budget_exhausted"
        assert ev["slo"] == "goodput"
        # idle is excluded from the naming when an actionable cause exists
        assert ev["dominant_cause"] == "recovery"
        assert ev["driving_ranks"][0] == "1"
        assert "recovery" in out[0]["summary"]

    def test_budget_exhausted_quiet_without_burn(self):
        b = _bundle({0: []})
        b[0]["metrics"] = {"hvd_slo_burn_rate": {"series": [
            {"labels": {"slo": "goodput"}, "value": 1.2}]}}
        assert sigs.detect_budget_exhausted(b) == []
        assert sigs.detect_budget_exhausted(_bundle({0: []})) == []

    def test_sorted_critical_first(self):
        events = [_ev(blackbox.K_RECONNECT, "rank_1", "r", rank=1, t=i)
                  for i in range(3)]  # warning-grade storm...
        events.append(_ev(blackbox.K_TIMEOUT, "g0", "ranks [1]", t=4))
        out = sigs.match_signatures(_bundle({0: events}))
        assert len(out) >= 2  # ...plus the critical deadlock
        assert out[0]["severity"] == sigs.SEV_CRITICAL

    def test_first_divergence_names_absent_rank(self):
        b = _bundle({0: [_ev(blackbox.K_COLLECTIVE, "g0", t=1.0),
                         _ev(blackbox.K_COLLECTIVE, "g1", t=2.0)],
                     1: [_ev(blackbox.K_COLLECTIVE, "g0", t=1.0)]})
        div = sigs.first_divergence(b)
        assert div["name"] == "g1"
        assert div["present_ranks"] == [0] and div["absent_ranks"] == [1]
        # agreement, or a single rank, is not divergence
        assert sigs.first_divergence(_bundle({0: b[0]["events"]})) is None

    def test_merged_timeline_clips_and_stamps_rank(self):
        old = _ev(blackbox.K_COLLECTIVE, "ancient", t=0.0)
        recent = {"t": 100.0, "kind": "error", "name": "end", "detail": ""}
        tl = sigs.merged_timeline(_bundle({1: [old, recent]}), window_s=30.0)
        assert [e["name"] for e in tl] == ["end"]
        assert tl[0]["rank"] == 1  # stamped from the source dump


# -------------------------------------------------------- rolling baseline
class TestRollingBaseline:
    def test_no_alarm_before_min_samples(self):
        rb = RollingBaseline(window=4, factor=2.0, min_samples=2, floor=0.0)
        assert rb.observe(1.0) is False
        assert rb.baseline() is None

    def test_spike_over_factor_fires(self):
        rb = RollingBaseline(window=4, factor=2.0, min_samples=2, floor=0.0)
        for _ in range(3):
            assert rb.observe(1.0) is False
        assert rb.observe(3.0) is True

    def test_floor_suppresses_idle_noise(self):
        rb = RollingBaseline(window=4, factor=2.0, min_samples=2, floor=10.0)
        for _ in range(3):
            rb.observe(0.001)
        assert rb.observe(0.05) is False  # 0.05 << factor * floor


# ------------------------------------------------------------ anomaly watch
def _lat_snapshot(total_sum, total_count):
    return {"hvd_allreduce_latency_seconds": {
        "kind": "histogram", "help": "", "buckets": [],
        "series": [{"labels": {}, "sum": total_sum, "count": total_count,
                    "counts": []}]}}


class TestAnomalyWatch:
    def test_step_time_spike_fires_and_clears(self):
        w = AnomalyWatch(interval=1.0, window=8, factor=3.0, min_samples=2)
        fired = []
        for i in range(1, 7):  # steady 0.1 s steps
            fired += w.observe_snapshot(_lat_snapshot(0.1 * i, i))
        assert fired == []
        fired = w.observe_snapshot(_lat_snapshot(0.6 + 5.0, 7))  # 5 s step
        assert [s["evidence"]["signal"] for s in fired] == ["step_seconds"]
        assert "step_seconds" in w.state()["active"]
        w.observe_snapshot(_lat_snapshot(5.7, 8))  # back to 0.1 s
        assert w.state()["active"] == {}

    def test_slo_burn_fires_and_clears(self):
        from horovod_tpu.goodput.slo import Objective, SLOEngine

        eng = SLOEngine([Objective("goodput", ">=", 0.9)],
                        fast_window=3, slow_window=6, min_samples=2)
        w = AnomalyWatch(interval=1.0, slo_engine=eng)

        def snap(good, bad):
            return {"hvd_goodput_seconds_total": {
                        "kind": "counter", "series": [
                            {"labels": {"rank": "0"}, "value": good}]},
                    "hvd_badput_seconds_total": {
                        "kind": "counter", "series": [
                            {"labels": {"cause": "recovery", "rank": "0"},
                             "value": bad}]}}

        fired = []
        good = bad = 0.0
        for _ in range(4):  # half of every interval is badput
            good += 1.0
            bad += 1.0
            fired += w.observe_snapshot(snap(good, bad))
        assert [s["id"] for s in fired] == ["slo_burn_rate"]
        assert fired[0]["evidence"]["slo"] == "goodput"
        assert "budget_exhausted" in fired[0]["summary"]
        assert w.state()["slo"]["alerting"] == ["goodput"]
        for _ in range(6):  # recovery: clean intervals clear the alert
            good += 10.0
            w.observe_snapshot(snap(good, bad))
        assert w.state()["slo"]["alerting"] == []

    def test_watch_without_slo_env_has_no_engine(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_SLO", raising=False)
        w = AnomalyWatch(interval=1.0)
        assert w._slo is None
        assert "slo" not in w.state()

    def test_watch_lifecycle_and_state(self, monkeypatch):
        assert watch.watch_state() is None
        assert watch.maybe_start_watch() is None  # env unset
        monkeypatch.setenv("HOROVOD_ANOMALY_INTERVAL", "60")
        w = watch.maybe_start_watch(force=True)
        assert watch.maybe_start_watch(force=True) is w  # idempotent
        assert watch.watch_state()["running"] is True
        watch.stop_watch()
        assert watch.watch_state() is None


# ------------------------------------------------------------------ doctor
def _write_rank_dump(dirpath, rank, events, world=2, reason="test"):
    doc = _bundle({rank: events}, world=world, reasons={rank: reason})[rank]
    with open(os.path.join(dirpath, "rank_%d.json" % rank), "w") as f:
        json.dump(doc, f)
    return doc


class TestDoctor:
    def test_load_and_diagnose_directory(self, tmp_path, capsys):
        _write_rank_dump(str(tmp_path), 0, [
            _ev(blackbox.K_TIMEOUT, "bb_probe",
                "collective timeout: tensor 'bb_probe' waited 3s on "
                "ranks [1]")], reason="CollectiveTimeoutError")
        _write_rank_dump(str(tmp_path), 1, [], reason="signal SIGTERM")
        bundle = doctor.load_bundle(str(tmp_path))
        assert sorted(bundle) == [0, 1]
        diag = doctor.diagnose(bundle)
        assert diag["missing_ranks"] == []
        assert diag["signatures"][0]["id"] == "collective_deadlock"
        assert doctor.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "collective deadlock" in out and "bb_probe" in out
        assert "[1]" in out and "DIAGNOSIS" in out

    def test_missing_rank_detected_from_world_size(self, tmp_path):
        _write_rank_dump(str(tmp_path), 0, [], world=3)
        diag = doctor.diagnose(doctor.load_bundle(str(tmp_path)))
        assert diag["missing_ranks"] == [1, 2]

    def test_bundle_manifest_only(self, tmp_path):
        docs = _bundle({0: [], 1: []})
        manifest = {"blackbox_bundle": 1, "assembled_at": 0.0,
                    "reason": "x", "ranks": {str(r): d
                                             for r, d in docs.items()}}
        with open(tmp_path / "bundle.json", "w") as f:
            json.dump(manifest, f)
        assert sorted(doctor.load_bundle(str(tmp_path))) == [0, 1]

    def test_json_output(self, tmp_path, capsys):
        _write_rank_dump(str(tmp_path), 0, [])
        assert doctor.main([str(tmp_path), "--json"]) == 0
        diag = json.loads(capsys.readouterr().out)
        assert diag["ranks"] == [0]

    def test_exit_codes(self, tmp_path, capsys):
        assert doctor.main([str(tmp_path)]) == 1  # empty dir
        bad = tmp_path / "rank_0.json"
        bad.write_text("{not json")
        assert doctor.main([str(tmp_path)]) == 1
        with pytest.raises(SystemExit) as exc:
            doctor.main([])  # usage: the bundle argument is required
        assert exc.value.code == 2
        capsys.readouterr()

    def test_bin_entrypoint(self, tmp_path):
        import subprocess
        _write_rank_dump(str(tmp_path), 0, [])
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "hvddoctor"),
             str(tmp_path)], capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr
        assert "hvddoctor:" in r.stdout


# ------------------------------------------------- healthz + report ledger
class TestHealth:
    def test_health_summary_defaults_ok(self):
        doc = health_summary()
        assert doc["status"] == "ok"
        assert doc["anomaly_watch"] == {"running": False}
        assert "control_plane" not in doc  # no coordinator registered

    def test_health_degrades_on_control_plane_trouble(self):
        set_health_source(lambda: {"silent_ranks": [2]})
        assert health_summary()["status"] == "degraded"
        set_health_source(lambda: {"shutting_down": True})
        assert health_summary()["status"] == "degraded"
        set_health_source(lambda: {})
        assert health_summary()["status"] == "ok"

    def test_healthz_endpoint_and_bind_addr(self):
        import urllib.request
        from horovod_tpu.metrics.http import MetricsHTTPServer

        srv = MetricsHTTPServer(0, lambda: "x 1\n", addr="127.0.0.1",
                                health_fn=lambda: {"status": "ok",
                                                   "reporting_ranks": []})
        srv.start()
        try:
            base = "http://127.0.0.1:%d" % srv.port
            body = urllib.request.urlopen(base + "/healthz",
                                          timeout=10).read()
            assert json.loads(body) == {"status": "ok",
                                        "reporting_ranks": []}
            assert urllib.request.urlopen(
                base + "/metrics", timeout=10).read() == b"x 1\n"
        finally:
            srv.stop()

    def test_stale_report_cannot_resurrect_dropped_rank(self):
        snap = {"hvd_fake_total": {"kind": "counter", "help": "",
                                   "series": [{"labels": {}, "value": 3.0}]}}
        store_report(1, snap)
        assert report_ranks() == [1]
        drop_report(1)  # coordinator rank_lost
        assert report_ranks() == []
        store_report(1, snap)  # a stale MSG_METRICS racing the death
        assert report_ranks() == [], \
            "stale snapshot resurrected a dead rank's gauges"
        readmit_report(1)  # elastic re-admission
        store_report(1, snap)
        assert report_ranks() == [1]

    def test_dropped_rank_goodput_counters_stay_out_of_aggregate(self):
        from horovod_tpu.metrics import aggregate

        snap = {"hvd_badput_seconds_total": {
            "kind": "counter", "help": "", "series": [
                {"labels": {"cause": "stall", "rank": "1"},
                 "value": 12.0}]}}
        store_report(1, snap)
        merged = aggregate()
        assert any(s["labels"].get("rank") == "1"
                   for s in merged["hvd_badput_seconds_total"]["series"])
        drop_report(1)
        store_report(1, snap)  # stale ledger report racing the death
        merged = aggregate()
        assert not any(s["labels"].get("rank") == "1" for s in merged.get(
            "hvd_badput_seconds_total", {}).get("series", [])), \
            "dead rank's goodput attribution resurrected in the fleet view"


# ------------------------------------------------------------- engine path
class TestEnginePath:
    def test_noop_fast_path_allocates_nothing(self):
        """Acceptance: HOROVOD_BLACKBOX unset -> zero blackbox allocations
        across a full init / allreduce / shutdown cluster cycle."""
        assert "HOROVOD_BLACKBOX" not in os.environ
        before = allocation_count()

        def fn():
            for i in range(3):
                g = hvd.allreduce(np.ones((8,), np.float32), name=f"g{i}",
                                  op=hvd.Sum)
            return float(np.asarray(g)[0])

        res = testing.run_cluster(fn, np=2)
        assert res == [2.0, 2.0]
        hvd.shutdown()
        assert blackbox.active() is None
        assert allocation_count() == before, \
            "blackbox-off engine path allocated flight-recorder objects"

    def test_cluster_records_collective_events(self, monkeypatch, tmp_path):
        """With the blackbox armed, a healthy run records collective
        lifecycle events and dumps NOTHING (normal exit stays silent)."""
        _activate(monkeypatch, tmp_path)

        def fn():
            g = hvd.allreduce(np.ones((4,), np.float32), name="bb_g",
                              op=hvd.Sum)
            return float(np.asarray(g)[0])

        assert testing.run_cluster(fn, np=2) == [2.0, 2.0]
        rec = blackbox.active()
        assert rec is not None
        names = [e.name for e in rec.events()
                 if e.kind == blackbox.K_COLLECTIVE]
        assert "bb_g" in names
        hvd.shutdown()
        assert not list(tmp_path.glob("rank_*.json")), \
            "healthy shutdown must not dump"
        assert blackbox.active() is None  # finalize ran


# -------------------------------------------------------------- integration
@pytest.mark.integration
class TestIntegration:
    def test_wedged_collective_leaves_diagnosable_bundle(self, tmp_path):
        """Acceptance: a REAL 2-process job with rank 1 wedged at its first
        collective under a 3 s enforced watchdog dies leaving dumps from
        BOTH ranks; hvddoctor names the deadlock, tensor, missing rank."""
        from horovod_tpu.run.api import run

        bbdir = str(tmp_path / "bb")

        def fn():
            import numpy as np
            import horovod_tpu as hvd
            hvd.init()
            hvd.allreduce(np.ones((8,), np.float32), name="bb_probe",
                          op=hvd.Sum)
            hvd.shutdown()
            return True

        env = {
            "JAX_PLATFORMS": "cpu",
            "HOROVOD_FAULT_SPEC": "hang@collective:30:1#1",
            "HOROVOD_COLLECTIVE_TIMEOUT": "3",
            "HOROVOD_BLACKBOX": "1",
            "HOROVOD_BLACKBOX_DIR": bbdir,
            "PYTHONPATH": REPO,
        }
        with pytest.raises(RuntimeError, match="CollectiveTimeoutError"):
            run(fn, np=2, env=env, start_timeout=120)

        bundle = doctor.load_bundle(bbdir)
        assert sorted(bundle) == [0, 1], "expected dumps from BOTH ranks"
        assert not bundle[1].get("stub"), "rank 1 should have dumped itself"
        diag = doctor.diagnose(bundle)
        dl = [s for s in diag["signatures"]
              if s["id"] == "collective_deadlock"]
        assert dl, f"no deadlock diagnosis in {diag['signatures']}"
        assert dl[0]["evidence"]["tensor"] == "bb_probe"
        assert dl[0]["evidence"]["missing_ranks"] == [1]
