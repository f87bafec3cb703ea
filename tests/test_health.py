"""Cluster health monitor (ISSUE 5): rule engine, monitor, wire transport,
HTTP surfaces, `cli status`, heartbeat hardening, and the tier-1 guards
(concurrent-scrape hammer, <2% monitor overhead).

Engine tests drive a fake clock — every time-window rule is exercised
without sleeping. Wire tests run a real gRPC server on a loopback port.
"""

from __future__ import annotations

import json
import threading
import time
from urllib.error import HTTPError
from urllib.request import urlopen

import numpy as np
import pytest

from distributed_parameter_server_for_ml_training_tpu.comms.client import (
    RemoteStore)
from distributed_parameter_server_for_ml_training_tpu.comms.service import (
    ParameterService, pack_msg, serve)
from distributed_parameter_server_for_ml_training_tpu.ps.store import (
    ParameterStore, StoreConfig)
from distributed_parameter_server_for_ml_training_tpu.telemetry import (
    RULE_CATALOG, VALUE_BUCKETS, ClusterMonitor, HealthRuleEngine,
    HealthThresholds, set_cluster_monitor, start_metrics_server)
from distributed_parameter_server_for_ml_training_tpu.telemetry.cluster import (
    sanitize_report)
from distributed_parameter_server_for_ml_training_tpu.telemetry.health import (
    ClusterState, WorkerState)


def _report(step=1, loss=2.0, grad=1.0, **extra):
    return {"step": step, "loss": loss, "grad_norm": grad,
            "loss_finite": loss is not None,
            "grad_finite": grad is not None, **extra}


def _state(ts, workers, global_step=0, **kw) -> ClusterState:
    """workers: {wid: report|None}; report freshness defaults to ts."""
    ws = {wid: WorkerState(worker_id=wid, report=rep, received_ts=ts,
                           last_seen=ts, in_membership=True)
          for wid, rep in workers.items()}
    return ClusterState(ts=ts, global_step=global_step, workers=ws, **kw)


class TestValueBuckets:
    def test_log_scale_scheme(self):
        assert list(VALUE_BUCKETS) == sorted(VALUE_BUCKETS)
        assert VALUE_BUCKETS[0] <= 1e-4 and VALUE_BUCKETS[-1] >= 1e6
        # Dense where losses/grad-norms live: >= 3 edges per decade in 0.1..10.
        assert sum(0.1 <= b <= 10.0 for b in VALUE_BUCKETS) >= 6

    def test_used_by_monitor_histograms(self):
        store = ParameterStore({"w": np.ones(4, np.float32)},
                               StoreConfig(total_workers=1))
        mon = ClusterMonitor(store)
        assert mon._tm_loss.buckets == tuple(float(b)
                                             for b in VALUE_BUCKETS)


class TestSanitizeReport:
    def test_garbage_in_nothing_out(self):
        assert sanitize_report(None) is None
        assert sanitize_report("junk") is None
        assert sanitize_report([1, 2]) is None
        assert sanitize_report({"unknown_field": 3}) is None

    def test_coercion_and_unknown_fields_dropped(self):
        out = sanitize_report({"step": "7", "loss": "2.5", "evil": "x",
                               "grad_norm": {"not": "a number"}})
        assert out == {"step": 7, "loss": 2.5}

    def test_nan_normalized_to_null_plus_flag(self):
        out = sanitize_report({"loss": float("nan"),
                               "grad_norm": float("inf")})
        assert out["loss"] is None and out["loss_finite"] is False
        assert out["grad_norm"] is None and out["grad_finite"] is False


class TestRuleEngine:
    def test_healthy_cluster_fires_nothing(self):
        e = HealthRuleEngine()
        for i in range(10):
            evs = e.evaluate(_state(1000.0 + i,
                                    {0: _report(step=i, loss=2.0 - 0.05 * i),
                                     1: _report(step=i, loss=2.1 - 0.05 * i)},
                                    global_step=2 * i))
            assert evs == [] and e.active_alerts() == []

    def test_nonfinite_loss_and_grad(self):
        e = HealthRuleEngine()
        evs = e.evaluate(_state(1000.0, {
            0: {"step": 3, "loss": None, "loss_finite": False,
                "grad_norm": None, "grad_finite": False}}))
        rules = {(ev["rule"], ev["worker"], ev["state"]) for ev in evs}
        assert ("nonfinite_loss", 0, "fired") in rules
        assert ("nonfinite_grad", 0, "fired") in rules
        assert all(ev["severity"] == "critical" for ev in evs)

    def test_fire_dedupe_and_resolve(self):
        t = HealthThresholds(realert_interval_s=60.0)
        e = HealthRuleEngine(t)
        bad = {0: {"step": 1, "loss": None, "loss_finite": False}}
        assert [ev["state"] for ev in e.evaluate(_state(1000.0, bad))] \
            == ["fired"]
        # Still firing inside the cooldown: no event, still active.
        assert e.evaluate(_state(1005.0, bad)) == []
        assert [a.rule for a in e.active_alerts()] == ["nonfinite_loss"]
        # Past the cooldown: ONE refire, not one per tick.
        assert [ev["state"] for ev in e.evaluate(_state(1061.0, bad))] \
            == ["refired"]
        # Healthy again: resolves exactly once.
        evs = e.evaluate(_state(1062.0, {0: _report(step=2)}))
        assert [ev["state"] for ev in evs] == ["resolved"]
        assert e.active_alerts() == []
        assert e.evaluate(_state(1063.0, {0: _report(step=3)})) == []

    def test_grad_explosion_rolling_median(self):
        e = HealthRuleEngine(HealthThresholds(grad_explosion_factor=10.0,
                                              grad_median_warmup=3))
        for i in range(4):
            assert e.evaluate(_state(1000.0 + i,
                                     {0: _report(step=i, grad=1.0)})) == []
        evs = e.evaluate(_state(1004.0, {0: _report(step=4, grad=50.0)}))
        assert [(ev["rule"], ev["severity"]) for ev in evs] \
            == [("grad_explosion", "warning")]

    def test_grad_explosion_absolute_ceiling_before_warmup(self):
        e = HealthRuleEngine(HealthThresholds(grad_norm_ceiling=1e6))
        evs = e.evaluate(_state(1000.0, {0: _report(step=1, grad=1e7)}))
        assert [ev["rule"] for ev in evs] == ["grad_explosion"]

    def test_loss_divergence_after_warmup(self):
        e = HealthRuleEngine(HealthThresholds(loss_divergence_factor=3.0,
                                              loss_divergence_warmup=3))
        for i, loss in enumerate([2.0, 1.5, 1.0, 1.1]):
            assert e.evaluate(_state(1000.0 + i,
                                     {0: _report(step=i, loss=loss)})) == []
        evs = e.evaluate(_state(1004.0, {0: _report(step=4, loss=4.0)}))
        assert [ev["rule"] for ev in evs] == ["loss_divergence"]

    def test_loss_plateau(self):
        e = HealthRuleEngine(HealthThresholds(plateau_window_s=100.0,
                                              plateau_min_improvement=1e-3))
        for i in range(3):
            e.evaluate(_state(1000.0 + i, {0: _report(step=i, loss=1.0)},
                              global_step=i))
        evs = e.evaluate(_state(1200.0, {0: _report(step=99, loss=1.0)},
                                global_step=99))
        assert "loss_plateau" in [ev["rule"] for ev in evs]

    def test_worker_stall_needs_cluster_progress(self):
        e = HealthRuleEngine(HealthThresholds(stall_after_s=10.0))
        e.evaluate(_state(1000.0, {0: _report(step=5)}, global_step=10))
        # Step frozen but the CLUSTER is idle too (e.g. between epochs):
        # not a stall.
        assert e.evaluate(_state(1020.0, {0: _report(step=5)},
                                 global_step=10)) == []
        # Cluster advanced while this worker's step stayed frozen: stall.
        evs = e.evaluate(_state(1040.0, {0: _report(step=5)},
                                global_step=40))
        assert [ev["rule"] for ev in evs] == ["worker_stall"]

    def test_straggler_lag_relative_to_leader(self):
        e = HealthRuleEngine(HealthThresholds(straggler_lag_steps=50))
        evs = e.evaluate(_state(1000.0, {0: _report(step=500),
                                         1: _report(step=100)}))
        assert [(ev["rule"], ev["worker"]) for ev in evs] \
            == [("straggler_lag", 1)]

    def test_staleness_spike_cluster_scoped(self):
        e = HealthRuleEngine(HealthThresholds(staleness_reject_ratio=0.5,
                                              staleness_min_pushes=8))
        evs = e.evaluate(_state(1000.0, {0: _report()},
                                pushes_accepted_delta=2,
                                pushes_rejected_delta=8))
        assert [(ev["rule"], ev["worker"]) for ev in evs] \
            == [("staleness_spike", None)]
        # Below the minimum sample size: silent.
        e2 = HealthRuleEngine(HealthThresholds(staleness_min_pushes=8))
        assert e2.evaluate(_state(1000.0, {0: _report()},
                                  pushes_accepted_delta=1,
                                  pushes_rejected_delta=3)) == []

    def test_staleness_spike_holds_through_undersampled_window(self):
        """An ACTIVE spike must not flap resolved/re-fired every window
        roll while thrashing persists: a freshly-rolled window below
        staleness_min_pushes but at the same bad ratio HOLDS the alert;
        only a quiet or healthy-ratio window resolves it."""
        e = HealthRuleEngine(HealthThresholds(staleness_reject_ratio=0.5,
                                              staleness_min_pushes=8))
        assert [ev["rule"] for ev in
                e.evaluate(_state(1000.0, {0: _report()},
                                  pushes_accepted_delta=2,
                                  pushes_rejected_delta=8))] \
            == ["staleness_spike"]
        # Young window, 3 pushes (< min), 2/3 rejected: still thrashing.
        assert e.evaluate(_state(1005.0, {0: _report(step=2)},
                                 pushes_accepted_delta=1,
                                 pushes_rejected_delta=2)) == []
        assert [a.rule for a in e.active_alerts()] == ["staleness_spike"]
        # Quiet window: resolves. (The small sample never FIRES fresh —
        # pinned by test_staleness_spike_cluster_scoped above.)
        evs = e.evaluate(_state(1010.0, {0: _report(step=3)}))
        assert [ev["state"] for ev in evs] == ["resolved"]

    def test_warmup_counts_reports_not_evaluations(self):
        """Evaluation frequency is set by scrape traffic (every /healthz and
        /cluster request evaluates); re-seeing the SAME report many times
        must not advance the divergence/median warmups or flood the
        grad-norm median window with duplicates."""
        e = HealthRuleEngine(HealthThresholds(loss_divergence_factor=3.0,
                                              loss_divergence_warmup=3,
                                              grad_median_warmup=3))
        rep = _report(step=1, loss=1.0, grad=1.0)
        # One report, scraped 10 times: warmup must still be at 1.
        for i in range(10):
            st = ClusterState(
                ts=1000.0 + i,
                workers={0: WorkerState(worker_id=0, report=rep,
                                        received_ts=1000.0,
                                        last_seen=1000.0 + i)})
            assert e.evaluate(st) == []
        assert e._tracks[0].reports == 1
        assert len(e._tracks[0].grad_norms) == 1
        # A 3x-best loss right after: still inside warmup, no divergence.
        evs = e.evaluate(_state(1011.0, {0: _report(step=2, loss=4.0)}))
        assert "loss_divergence" not in [ev["rule"] for ev in evs]

    def test_dead_worker_latches_until_seen_again(self):
        e = HealthRuleEngine(HealthThresholds(dead_after_s=30.0))
        evs = e.evaluate(_state(1000.0, {}, expired=[3]))
        assert [(ev["rule"], ev["worker"], ev["severity"]) for ev in evs] \
            == [("dead_worker", 3, "critical")]
        # Still gone next pass: active, no duplicate event inside cooldown.
        assert e.evaluate(_state(1001.0, {})) == []
        assert [a.worker for a in e.active_alerts()] == [3]
        # Reappears with a fresh report: resolves.
        evs = e.evaluate(_state(1002.0, {3: _report(step=1)}))
        assert [ev["state"] for ev in evs] == ["resolved"]

    def test_dead_worker_by_report_age_without_expiry(self):
        """Faithful-mode stores never expire membership (quirk 10); the
        monitor still notices a silent worker by report age."""
        e = HealthRuleEngine(HealthThresholds(dead_after_s=30.0))
        e.evaluate(_state(1000.0, {0: _report(step=1)}))
        st = ClusterState(ts=1040.0, workers={
            0: WorkerState(0, report=_report(step=1), received_ts=1000.0,
                           last_seen=1000.0, in_membership=True)})
        evs = e.evaluate(st)
        assert [ev["rule"] for ev in evs] == ["dead_worker"]

    def test_rate_limit_caps_fired_events_and_defers_the_rest(self):
        e = HealthRuleEngine(HealthThresholds(max_alerts_per_eval=2))
        workers = {i: {"step": 1, "loss": None, "loss_finite": False}
                   for i in range(8)}
        # A mass failure drains through the cap over successive passes —
        # every alert eventually gets its own "fired" edge (never a
        # refired-without-fired), 2 per pass.
        seen: list[int] = []
        for tick in range(4):
            evs = e.evaluate(_state(1000.0 + tick, workers))
            assert [ev["state"] for ev in evs] == ["fired", "fired"]
            seen += [ev["worker"] for ev in evs]
            assert len(e.active_alerts()) == 2 * (tick + 1)
        assert sorted(seen) == list(range(8))
        assert e.evaluate(_state(1004.0, workers)) == []


class TestClusterMonitor:
    def _mk(self, **thresh):
        store = ParameterStore({"w": np.ones(4, np.float32)},
                               StoreConfig(mode="async", total_workers=4,
                                           push_codec="none"))
        mon = ClusterMonitor(store, HealthThresholds(**thresh))
        return store, mon

    def test_ingest_evaluate_view_roundtrip(self):
        store, mon = self._mk()
        wid, _ = store.register_worker("w0")
        assert mon.ingest(wid, _report(step=7, loss=1.25, grad=0.5,
                                       examples_per_s=100.0)) is True
        assert mon.evaluate() == []
        view = mon.cluster_view()
        row = next(r for r in view["workers"] if r["worker"] == wid)
        assert row["step"] == 7 and row["loss"] == 1.25 and row["alive"]
        assert view["alerts"] == []
        assert view["alerts_total"] == {"critical": 0, "warning": 0,
                                        "info": 0}

    def test_histograms_observe_new_reports_not_every_rpc(self):
        """The worker rebuilds its report at push boundaries but EVERY
        fetch/push/heartbeat re-carries the current one; the loss/grad
        value histograms must be weighted by training observations, not
        by each worker's RPC rate."""
        store, mon = self._mk()
        wid, _ = store.register_worker("w0")
        n0 = mon._tm_loss.count
        rep = _report(step=7, loss=1.25, grad=0.5)
        for _ in range(5):  # 5 heartbeats carrying the SAME report
            assert mon.ingest(wid, dict(rep)) is True
        assert mon._tm_loss.count == n0 + 1
        assert mon.ingest(wid, _report(step=8, loss=1.2, grad=0.5)) is True
        assert mon._tm_loss.count == n0 + 2
        # The reports_total counter still counts carried reports (wire
        # traffic), not deduped observations.
        assert mon._tm_reports.value >= 6

    def test_ingest_never_raises_on_garbage(self):
        _, mon = self._mk()
        assert mon.ingest("not-an-id", _report()) is False
        assert mon.ingest(0, "garbage") is False
        assert mon.ingest(0, {"no_known_fields": 1}) is False

    def test_dead_worker_via_membership_expiry(self):
        store, mon = self._mk(dead_after_s=1000.0)
        store.config.worker_timeout = 0.05
        wid, _ = store.register_worker("w0")
        mon.ingest(wid, _report(step=1))
        assert mon.evaluate() == []
        time.sleep(0.1)
        expired = store.expire_stale_workers()
        assert expired == [wid]
        mon.note_expired(expired)
        evs = mon.evaluate()
        assert [(ev["rule"], ev["worker"]) for ev in evs] \
            == [("dead_worker", wid)]
        assert mon.has_critical()
        view = mon.cluster_view()
        row = next(r for r in view["workers"] if r["worker"] == wid)
        assert row["alive"] is False

    def test_clean_departure_never_alerts(self):
        store, mon = self._mk(dead_after_s=0.05)
        wid, _ = store.register_worker("w0")
        mon.ingest(wid, _report(step=1))
        store.job_finished(wid)
        time.sleep(0.1)
        assert mon.evaluate() == []
        assert all(r["worker"] != wid
                   for r in mon.cluster_view()["workers"])

    def test_staleness_spike_window_survives_scrapes(self):
        """Regression: the store counts accepted pushes in
        gradients_processed and rejected ones ONLY in gradients_rejected —
        no cross-subtraction — and intermediate evaluations (every
        /healthz / /cluster scrape is one) must NOT consume the
        measurement window."""
        now = [1000.0]
        store = ParameterStore({"w": np.ones(4, np.float32)},
                               StoreConfig(mode="async", total_workers=4,
                                           push_codec="none"))
        mon = ClusterMonitor(store, HealthThresholds(), interval=5.0,
                             clock=lambda: now[0])
        assert mon.evaluate() == []
        # 8 accepted + 12 staleness-rejected arrivals this window.
        store.stats.gradients_processed += 8
        store.stats.gradients_rejected += 12
        now[0] += 1.0  # scrape-shaped evaluation, inside the window
        evs = mon.evaluate()
        assert [(ev["rule"], ev["state"]) for ev in evs] \
            == [("staleness_spike", "fired")]
        spike = evs[0]
        assert spike["value"] == pytest.approx(12 / 20)
        # More scrapes inside the window: still active, window intact.
        now[0] += 1.0
        assert mon.evaluate() == []
        assert [a["rule"] for a in mon.active_alerts(evaluate=False)] \
            == ["staleness_spike"]
        # Window rolls after the interval with no fresh rejects: resolves.
        now[0] += 10.0
        mon.evaluate()  # rolls the window
        now[0] += 1.0
        evs = mon.evaluate()
        assert [ev["state"] for ev in evs] == ["resolved"]

    def test_alerts_total_counter_and_flight_recorder(self):
        from distributed_parameter_server_for_ml_training_tpu.telemetry import (
            get_recorder, get_registry)
        store, mon = self._mk()
        wid, _ = store.register_worker("w0")
        c = get_registry().counter("dps_alerts_total",
                                   rule="nonfinite_loss",
                                   severity="critical")
        n0 = c.value
        mon.ingest(wid, {"step": 1, "loss": None, "loss_finite": False})
        mon.evaluate()
        assert c.value == n0 + 1
        alerts = [s for s in get_recorder().tail()
                  if s.get("name") == "cluster.alert"]
        assert alerts and alerts[-1]["attrs"]["rule"] == "nonfinite_loss"

    def test_cluster_stream_record_roundtrips_through_etl(self, capsys):
        from distributed_parameter_server_for_ml_training_tpu.analysis import (
            alert_timeline, cluster_worker_series, parse_cluster_series,
            parse_experiment)
        store, mon = self._mk()
        wid, _ = store.register_worker("w0")
        mon.ingest(wid, _report(step=3, loss=1.5))
        mon.emit_once()
        mon.ingest(wid, {"step": 4, "loss": None, "loss_finite": False})
        mon.emit_once()
        out = capsys.readouterr().out
        series = parse_cluster_series(out)
        assert len(series) == 1
        recs = next(iter(series.values()))
        assert [r["seq"] for r in recs] == [1, 2]
        tl = alert_timeline(out)
        assert [(e["state"], e["rule"]) for e in tl] \
            == [("fired", "nonfinite_loss")]
        ws = cluster_worker_series(out)
        assert ws["workers"][f"worker-{wid}"]["step"] == [3, 4]
        # Cluster records never pollute the classic exit-line aggregation.
        rec = parse_experiment(out, "t")
        assert rec["server_metrics"] == {} and \
            rec["raw_worker_metrics"] == []


@pytest.fixture()
def monitored_server():
    store = ParameterStore({"w": np.ones(8, np.float32)},
                           StoreConfig(mode="async", total_workers=4,
                                       push_codec="none"))
    mon = ClusterMonitor(store, HealthThresholds(dead_after_s=1000.0))
    svc = ParameterService(store, monitor=mon)
    server, port = serve(store, port=0, service=svc)
    yield store, mon, port
    server.stop(grace=None)


class TestWireTransport:
    def test_capability_advertised_and_report_rides_fetch_and_push(
            self, monitored_server):
        store, mon, port = monitored_server
        client = RemoteStore(f"localhost:{port}")
        wid, _ = client.register_worker("w0")
        assert client.supports_health_report is True
        reports = iter([_report(step=1, loss=2.0),
                        _report(step=2, loss=1.9)])
        client.health_provider = lambda: next(reports)
        client.fetch(wid)  # heartbeat-shaped: report rides the envelope
        assert mon.cluster_view()["workers"][0]["step"] == 1
        client.push(wid, {"w": np.ones(8, np.float32)}, fetched_step=0)
        assert mon.cluster_view()["workers"][0]["step"] == 2
        client.close()

    def test_legacy_client_reportless_heartbeat_still_works(
            self, monitored_server):
        """Wire degradation: a peer that never attaches a report (legacy
        build / no provider) heartbeats and trains normally; the monitor
        sees membership only."""
        store, mon, port = monitored_server
        client = RemoteStore(f"localhost:{port}")
        wid, _ = client.register_worker("legacy")
        assert client.health_provider is None
        params, step = client.fetch(wid)  # plain ping
        assert step == 0 and "w" in params
        assert client.push(wid, {"w": np.ones(8, np.float32)},
                           fetched_step=0) is True
        assert mon.evaluate() == []
        row = next(r for r in mon.cluster_view()["workers"]
                   if r["worker"] == wid)
        assert row["alive"] and "step" not in row
        client.close()

    def test_monitorless_server_keeps_client_silent(self):
        store = ParameterStore({"w": np.ones(8, np.float32)},
                               StoreConfig(mode="async", total_workers=2,
                                           push_codec="none"))
        server, port = serve(store, port=0)  # no monitor
        try:
            client = RemoteStore(f"localhost:{port}")
            wid, _ = client.register_worker("w0")
            assert client.supports_health_report is False
            calls = []
            client.health_provider = lambda: calls.append(1) or _report()
            client.fetch(wid)
            assert calls == []  # capability-gated: never even built
            client.close()
        finally:
            server.stop(grace=None)

    def test_garbled_health_meta_never_fails_the_rpc(self,
                                                     monitored_server):
        import grpc
        store, mon, port = monitored_server
        ch = grpc.insecure_channel(f"localhost:{port}")
        ident = lambda b: b  # noqa: E731
        call = ch.unary_unary("/ps.ParameterServer/FetchParameters",
                              request_serializer=ident,
                              response_deserializer=ident)
        for bad in ("junk", 42, ["a"], {"loss": {"deep": "garbage"}}):
            reply = call(pack_msg({"worker_id": 0, "health": bad}))
            assert reply  # RPC succeeded; report degraded to nothing
        assert mon.evaluate() == []
        ch.close()

    def test_failing_provider_degrades_to_reportless(self,
                                                     monitored_server):
        store, mon, port = monitored_server
        client = RemoteStore(f"localhost:{port}")
        wid, _ = client.register_worker("w0")
        def boom():
            raise RuntimeError("provider bug")
        client.health_provider = boom
        params, step = client.fetch(wid)  # must not raise
        assert step == 0 and "w" in params
        client.close()


class TestHttpSurfaces:
    def _serve_monitor(self, mon):
        set_cluster_monitor(mon)
        server, port = start_metrics_server(port=0)
        return server, port

    def test_cluster_endpoint_and_healthz_readiness_flip(self):
        store = ParameterStore({"w": np.ones(4, np.float32)},
                               StoreConfig(mode="async", total_workers=2,
                                           push_codec="none"))
        mon = ClusterMonitor(store)
        wid, _ = store.register_worker("w0")
        server, port = self._serve_monitor(mon)
        try:
            mon.ingest(wid, _report(step=5, loss=1.0))
            body = json.loads(urlopen(
                f"http://127.0.0.1:{port}/cluster", timeout=5).read())
            assert body["workers"][0]["step"] == 5
            health = json.loads(urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5).read())
            assert health == {"ok": True}
            # Critical alert -> readiness flips to 503 naming it.
            mon.ingest(wid, {"step": 6, "loss": None,
                             "loss_finite": False})
            with pytest.raises(HTTPError) as exc:
                urlopen(f"http://127.0.0.1:{port}/healthz", timeout=5)
            assert exc.value.code == 503
            payload = json.loads(exc.value.read())
            assert payload["ok"] is False
            assert payload["critical"][0]["rule"] == "nonfinite_loss"
            assert payload["critical"][0]["worker"] == wid
        finally:
            set_cluster_monitor(None)
            server.shutdown()

    def test_cluster_404_without_monitor(self):
        set_cluster_monitor(None)
        server, port = start_metrics_server(port=0)
        try:
            with pytest.raises(HTTPError) as exc:
                urlopen(f"http://127.0.0.1:{port}/cluster", timeout=5)
            assert exc.value.code == 404
        finally:
            server.shutdown()

    def test_cli_status_renders_and_exits_by_severity(self, capsys):
        from distributed_parameter_server_for_ml_training_tpu import cli
        store = ParameterStore({"w": np.ones(4, np.float32)},
                               StoreConfig(mode="async", total_workers=2,
                                           push_codec="none"))
        mon = ClusterMonitor(store)
        wid, _ = store.register_worker("w0")
        server, port = self._serve_monitor(mon)
        try:
            mon.ingest(wid, _report(step=9, loss=1.5,
                                    examples_per_s=123.0))
            assert cli.main(["status", "--metrics-port", str(port)]) == 0
            out = capsys.readouterr().out
            assert "no active alerts" in out and "mode=async" in out
            assert "123.0" in out
            mon.ingest(wid, {"step": 10, "loss": None,
                             "loss_finite": False})
            assert cli.main(["status", "--metrics-port", str(port)]) == 2
            out = capsys.readouterr().out
            assert "[CRIT] nonfinite_loss (worker 0)" in out
            # --json emits the raw payload.
            assert cli.main(["status", "--metrics-port", str(port),
                             "--json"]) == 2
            payload = json.loads(capsys.readouterr().out)
            assert payload["alerts_total"]["critical"] == 1
        finally:
            set_cluster_monitor(None)
            server.shutdown()

    def test_cli_status_unreachable_exits_1(self, capsys):
        from distributed_parameter_server_for_ml_training_tpu import cli
        assert cli.main(["status", "--url", "http://127.0.0.1:1"]) == 1

    def test_status_table_shows_negotiated_push_codec(self):
        """ISSUE 6 satellite: the worker table surfaces each worker's
        negotiated push codec/bitwidth (the health report's push_codec
        field, sanitized server-side)."""
        from distributed_parameter_server_for_ml_training_tpu.cli import (
            _render_status)
        from distributed_parameter_server_for_ml_training_tpu.telemetry.cluster import (
            sanitize_report)
        report = sanitize_report({"step": 4, "push_codec":
                                  "adaptive(int4)+ef"})
        assert report["push_codec"] == "adaptive(int4)+ef"
        # hostile length is capped on ingest
        assert len(sanitize_report({"push_codec": "x" * 999})
                   ["push_codec"]) == 32
        view = {"mode": "sync", "global_step": 7,
                "workers": [{"worker": 0, "alive": True, **report}],
                "alerts": [], "alerts_total": {}}
        out = _render_status(view)
        header, row = out.splitlines()[2], out.splitlines()[3]
        assert "codec" in header
        assert "adaptive(int4)+ef" in row


class TestHeartbeatHardening:
    def _mk_worker(self, store):
        from distributed_parameter_server_for_ml_training_tpu.ps.worker import (
            PSWorker, WorkerConfig)
        w = PSWorker.__new__(PSWorker)  # no model compile needed
        w.store = store
        w.config = WorkerConfig(heartbeat_interval=0.02)
        w.worker_name = "hb-test"
        w._health_lock = threading.Lock()
        w._health = {}
        w._health_rev = 0
        from distributed_parameter_server_for_ml_training_tpu.ps.worker import (
            WorkerResult)
        w.result = WorkerResult(worker_id=0)
        w._last_fetched_step = None
        w._done = threading.Event()
        w._init_telemetry(0)
        return w

    def test_tick_errors_counted_and_transition_logged_once(self, capsys):
        class FlakyStore:
            supports_delta_fetch = False

            def __init__(self):
                self.fail = True
                self.fetches = 0

            def fetch(self, wid, have_step=None):
                self.fetches += 1
                if self.fail:
                    raise ConnectionError("down")
                return {}, 0

        store = FlakyStore()
        w = self._mk_worker(store)
        n0 = w._tm_hb_err.value
        t = threading.Thread(target=w._heartbeat_loop, args=(0.02,),
                             daemon=True)
        t.start()
        deadline = time.time() + 5
        while store.fetches < 4 and time.time() < deadline:
            time.sleep(0.02)
        store.fail = False
        while w.result.heartbeats < 2 and time.time() < deadline:
            time.sleep(0.02)
        w._done.set()
        t.join(timeout=5)
        assert w._tm_hb_err.value - n0 >= 4
        assert w._health["heartbeat_errors"] >= 4
        out = capsys.readouterr().out
        # Logged once per TRANSITION, not once per failing tick.
        assert out.count("HEARTBEAT_FAILING") == 1
        assert out.count("HEARTBEAT_RECOVERED") == 1


class TestConcurrentScrapeHammer:
    def test_scrapes_survive_active_training_load(self):
        """ISSUE 5 satellite: /metrics + /cluster + /debug/trace hammered
        concurrently while pushes/fetches churn the store — no deadlock,
        every response well-formed, bounded latency."""
        from distributed_parameter_server_for_ml_training_tpu.telemetry import (
            disable_tracing, enable_tracing, trace_enabled)
        was_tracing = trace_enabled()
        enable_tracing()
        store = ParameterStore({"w": np.ones((64, 64), np.float32)},
                               StoreConfig(mode="async", total_workers=8,
                                           push_codec="none"))
        mon = ClusterMonitor(store)
        set_cluster_monitor(mon)
        server, port = start_metrics_server(port=0)
        stop = threading.Event()
        errors: list = []

        def trainer(wid):
            grads = {"w": np.ones((64, 64), np.float32)}
            try:
                while not stop.is_set():
                    _, step = store.fetch(wid)
                    store.push(wid, grads, step)
                    mon.ingest(wid, _report(step=step, loss=1.0))
            except Exception as e:  # pragma: no cover
                errors.append(e)

        def scraper(path):
            try:
                while not stop.is_set():
                    t0 = time.perf_counter()
                    body = urlopen(
                        f"http://127.0.0.1:{port}{path}",
                        timeout=10).read()
                    latencies.append((path, time.perf_counter() - t0))
                    assert body
                    counts[path] = counts.get(path, 0) + 1
            except Exception as e:  # pragma: no cover
                errors.append((path, e))

        latencies: list = []
        counts: dict = {}
        workers = [store.register_worker(f"w{i}")[0] for i in range(4)]
        threads = [threading.Thread(target=trainer, args=(w,), daemon=True)
                   for w in workers]
        threads += [threading.Thread(target=scraper, args=(p,), daemon=True)
                    for p in ("/metrics", "/cluster", "/debug/trace")
                    for _ in range(2)]
        try:
            for t in threads:
                t.start()
            time.sleep(2.0)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=15)
            set_cluster_monitor(None)
            server.shutdown()
            if not was_tracing:
                disable_tracing()
        alive = [t for t in threads if t.is_alive()]
        assert not alive, f"deadlocked threads: {alive}"
        assert not errors, errors
        for path in ("/metrics", "/cluster", "/debug/trace"):
            assert counts.get(path, 0) >= 3, counts
        worst = max(d for _, d in latencies)
        assert worst < 5.0, f"scrape latency blew up: {worst:.1f}s"


class TestMonitorOverheadGuard:
    def test_monitor_ingest_under_2_percent_of_push_fetch(self):
        """ISSUE 5 satellite, same methodology as the PR 1 telemetry
        guard: measure the EXACT per-RPC monitor cost (one ingest — the
        only health work on a handler thread) directly, then compare
        against a realistic push/fetch pair.

        What it asserts (ISSUE 38): the BEST of several repetitions of
        each side, the two sides taken in turn so that both see the same
        stretches of a loaded machine. A neighbour's load can only add to
        a timing, so the least of ten is what the code costs, and the
        ratio of two such minima is the same on an idle machine and under
        six test workers (0.55% here, against a bound of 2%); one ratio
        of two wall clocks read 2.58% under load (ROADMAP D12)."""
        store = ParameterStore({"w": np.zeros((1024, 1024), np.float32)},
                               StoreConfig(mode="async", total_workers=1,
                                           push_codec="none"))
        mon = ClusterMonitor(store)
        wid, _ = store.register_worker()
        grads = {"w": np.ones((1024, 1024), np.float32)}
        report = _report(step=1, loss=2.0, grad=1.0, examples_per_s=100.0,
                         pipeline_depth=0, reconnects=0,
                         heartbeat_errors=0)

        store.fetch(wid)
        ingests, pairs = [], []
        for _ in range(10):
            t0 = time.perf_counter()
            for _i in range(500):
                mon.ingest(wid, report)
            ingests.append((time.perf_counter() - t0) / 500)
            for _i in range(5):
                t0 = time.perf_counter()
                store.push(wid, grads, store.global_step)
                store.fetch(wid)
                pairs.append(time.perf_counter() - t0)
        ingest_per_op, op = min(ingests), min(pairs)
        overhead = 2 * ingest_per_op / op  # one ingest per RPC, 2 RPCs
        assert overhead < 0.02, (
            f"monitor ingest adds {overhead:.2%} to a push/fetch pair "
            f"({ingest_per_op*1e6:.2f} us/op vs {op*1e3:.3f} ms/pair)")
