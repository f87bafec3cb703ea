"""Telemetry-layer tests: registry semantics, snapshot round-trip, staleness
recording on the async store path, ETL time-series, Prometheus rendering,
the bench.py hardening (retry + diagnostic JSON), and the < 2% hot-path
overhead guard.
"""

import io
import json
import threading
import time

import numpy as np
import pytest

from distributed_parameter_server_for_ml_training_tpu.telemetry import (
    BYTES_BUCKETS, LATENCY_BUCKETS_S, MetricsRegistry, STALENESS_BUCKETS,
    SnapshotEmitter, get_registry, render_prometheus, span,
    start_metrics_server)
from distributed_parameter_server_for_ml_training_tpu.utils.metrics import (
    parse_metrics_lines)


class TestRegistry:
    def test_counter_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("pushes_total", backend="x")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)
        assert c.value == 3.5  # the rejected delta must not half-apply

    def test_get_or_create_identity(self):
        reg = MetricsRegistry()
        a = reg.counter("n", k="v")
        b = reg.counter("n", k="v")
        assert a is b
        c = reg.counter("n", k="other")
        assert c is not a  # distinct label set = distinct instrument

    def test_kind_mismatch_raises(self):
        reg = MetricsRegistry()
        reg.counter("m")
        with pytest.raises(TypeError):
            reg.gauge("m")
        reg.histogram("h")
        with pytest.raises(ValueError):
            reg.histogram("h", buckets=(1, 2, 3))  # different edges

    def test_gauge(self):
        reg = MetricsRegistry()
        g = reg.gauge("step")
        g.set(5)
        g.inc()
        g.dec(2)
        assert g.value == 4.0

    def test_histogram_bucket_edges(self):
        """``le`` edges are INCLUSIVE upper bounds; above the last edge
        lands in the overflow bucket (the fixed-scheme contract the ETL
        and the Prometheus renderer both rely on)."""
        reg = MetricsRegistry()
        h = reg.histogram("st", buckets=(0, 1, 2, 5))
        for v in [0, 0, 1, 1.5, 2, 5, 6, 100]:
            h.observe(v)
        snap = h.snapshot()
        assert snap["le"] == [0.0, 1.0, 2.0, 5.0]
        #                    le=0  le=1  le=2  le=5  +inf
        assert snap["counts"] == [2, 1, 2, 1, 2]
        assert snap["count"] == 8
        assert snap["sum"] == pytest.approx(115.5)

    def test_bucket_schemes_sorted(self):
        for scheme in (LATENCY_BUCKETS_S, BYTES_BUCKETS, STALENESS_BUCKETS):
            assert list(scheme) == sorted(scheme)
            assert len(set(scheme)) == len(scheme)

    def test_thread_safety_counts_exact(self):
        reg = MetricsRegistry()
        c = reg.counter("racy")
        h = reg.histogram("racy_h", buckets=(1,))

        def hammer():
            for _ in range(2000):
                c.inc()
                h.observe(0.5)

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 8000
        assert h.count == 8000

    def test_span_records_on_exception(self):
        reg = MetricsRegistry()
        h = reg.histogram("s")
        c = reg.counter("s_total")
        with pytest.raises(RuntimeError):
            with span(h, c):
                raise RuntimeError("boom")
        assert h.count == 1 and c.value == 1


class TestSnapshotEmitter:
    def test_line_roundtrip_through_parse_metrics_lines(self):
        """The snapshot line IS a METRICS_JSON line — the reference regex
        (utils/metrics.py METRICS_RE) must recover it bit-for-bit."""
        reg = MetricsRegistry()
        reg.counter("steps_total", worker="0").inc(7)
        reg.gauge("acc").set(0.25)
        reg.histogram("lat", buckets=(1, 2)).observe(1.5)
        buf = io.StringIO()
        em = SnapshotEmitter(reg, interval=60, role="worker", stream=buf)
        payload = em.emit_once()
        parsed = parse_metrics_lines(buf.getvalue())
        assert parsed == [payload]
        m = parsed[0]
        assert m["kind"] == "snapshot" and m["seq"] == 1
        assert m["role"] == "worker"
        assert m["counters"]["steps_total{worker=0}"] == 7
        assert m["gauges"]["acc"] == 0.25
        assert m["histograms"]["lat"]["counts"] == [0, 1, 0]

    def test_periodic_emission_and_final_flush(self):
        reg = MetricsRegistry()
        c = reg.counter("n")
        buf = io.StringIO()
        em = SnapshotEmitter(reg, interval=0.05, role="t", stream=buf).start()
        c.inc()
        time.sleep(0.2)
        c.inc()
        em.stop(final=True)
        snaps = parse_metrics_lines(buf.getvalue())
        assert len(snaps) >= 2
        assert [s["seq"] for s in snaps] == list(range(1, len(snaps) + 1))
        assert snaps[-1]["counters"]["n"] == 2  # final flush has the total

    def test_rejects_bad_interval(self):
        with pytest.raises(ValueError):
            SnapshotEmitter(MetricsRegistry(), interval=0)


class TestStoreInstrumentation:
    def _mk_store(self, mode="async", **kw):
        from distributed_parameter_server_for_ml_training_tpu.ps.store import (
            ParameterStore, StoreConfig)
        params = {"w": np.zeros((4, 4), np.float32),
                  "b": np.zeros((4,), np.float32)}
        return ParameterStore(params, StoreConfig(
            mode=mode, total_workers=2, push_codec="none",
            staleness_bound=2, **kw))

    def test_async_staleness_histogram_recorded(self):
        """The ISSUE's core runtime signal: every arriving async push
        observes its staleness (accepted AND rejected) into the fixed
        STALENESS_BUCKETS histogram on the process registry."""
        store = self._mk_store()
        h = store._tm_staleness
        rej = store._tm_push_rej
        count0, rej0 = h.count, rej.value
        ok0 = store._tm_push_ok.value
        wid, _ = store.register_worker()
        grads = {"w": np.ones((4, 4), np.float32),
                 "b": np.ones((4,), np.float32)}
        _, step = store.fetch(wid)
        assert store.push(wid, grads, step)          # staleness 0
        assert store.push(wid, grads, step)          # staleness 1
        assert not store.push(wid, grads, step - 5)  # beyond bound: reject
        assert h.count - count0 == 3
        assert rej.value - rej0 == 1
        assert store._tm_push_ok.value - ok0 == 2
        # bucket placement: two observations <= bound, one overflow-ish
        snap = h.snapshot()
        assert snap["le"] == [float(b) for b in STALENESS_BUCKETS]

    def test_sync_round_counters(self):
        store = self._mk_store(mode="sync")
        rounds0 = store._tm_rounds.value
        grads = {"w": np.ones((4, 4), np.float32),
                 "b": np.ones((4,), np.float32)}
        w0, _ = store.register_worker()
        w1, _ = store.register_worker()
        store.push(w0, grads, 0)
        assert store._tm_rounds.value == rounds0
        store.push(w1, grads, 0)
        assert store._tm_rounds.value == rounds0 + 1
        assert store._tm_step.value == store.global_step

    def test_fetch_span_recorded(self):
        store = self._mk_store()
        n0 = store._tm_fetches.value
        store.fetch()
        store.fetch()
        assert store._tm_fetches.value - n0 == 2

    def test_overhead_guard_under_2_percent(self):
        """ISSUE satellite: instrumentation overhead < 2% on a store
        push/fetch microloop. Methodology: measure the per-op cost of the
        EXACT instrument calls a push makes (2 perf_counter reads + span
        observe + staleness observe + counter inc + gauge set), then
        measure a real push+fetch pair on a realistic payload (1M params,
        the regime the store exists for), and compare medians — direct
        cost measurement, immune to run-to-run store variance."""
        from distributed_parameter_server_for_ml_training_tpu.telemetry import (
            now)
        store = None
        from distributed_parameter_server_for_ml_training_tpu.ps.store import (
            ParameterStore, StoreConfig)
        params = {"w": np.zeros((1024, 1024), np.float32)}
        store = ParameterStore(params, StoreConfig(
            mode="async", total_workers=1, push_codec="none"))
        wid, _ = store.register_worker()
        grads = {"w": np.ones((1024, 1024), np.float32)}

        # Per-op telemetry cost: N iterations of the push-path instrument
        # sequence.
        reg = MetricsRegistry()
        h1 = reg.histogram("a")
        h2 = reg.histogram("b", buckets=STALENESS_BUCKETS)
        c1 = reg.counter("c")
        g1 = reg.gauge("d")
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            ts = now()
            h2.observe(1)
            c1.inc()
            g1.set(3)
            h1.observe(now() - ts)
        telemetry_per_op = (time.perf_counter() - t0) / n

        # Median real push+fetch pair.
        durations = []
        _, step = store.fetch(wid)
        for _ in range(30):
            t0 = time.perf_counter()
            store.push(wid, grads, store.global_step)
            store.fetch(wid)
            durations.append(time.perf_counter() - t0)
        op = float(np.median(durations))
        # Two instrumented ops (push + fetch) per pair.
        overhead = 2 * telemetry_per_op / op
        assert overhead < 0.02, (
            f"telemetry adds {overhead:.2%} to a push/fetch pair "
            f"({telemetry_per_op*1e6:.2f} us/op vs {op*1e3:.3f} ms/pair)")


class TestPrometheus:
    def test_render_format(self):
        reg = MetricsRegistry()
        reg.counter("dps_pushes_total", backend="python").inc(3)
        reg.gauge("dps_step").set(9)
        reg.histogram("dps_lat_seconds", buckets=(0.1, 1.0)).observe(0.5)
        text = render_prometheus(reg)
        assert "# TYPE dps_pushes_total counter" in text
        assert 'dps_pushes_total{backend="python"} 3' in text
        assert "dps_step 9" in text
        # cumulative buckets + +Inf + sum/count
        assert 'dps_lat_seconds_bucket{le="0.1"} 0' in text
        assert 'dps_lat_seconds_bucket{le="1"} 1' in text
        assert 'dps_lat_seconds_bucket{le="+Inf"} 1' in text
        assert "dps_lat_seconds_sum 0.5" in text
        assert "dps_lat_seconds_count 1" in text

    def test_http_endpoint(self):
        from urllib.request import urlopen
        reg = MetricsRegistry()
        reg.counter("dps_x_total").inc(5)
        server, port = start_metrics_server(reg, port=0, addr="127.0.0.1")
        try:
            body = urlopen(f"http://127.0.0.1:{port}/metrics",
                           timeout=10).read().decode()
            assert "dps_x_total 5" in body
            health = json.loads(urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10).read())
            assert health == {"ok": True}
        finally:
            server.shutdown()


class TestTimeseriesETL:
    def _log(self):
        """Two processes' interleaved snapshot streams + classic exit
        lines, as one captured stdout."""
        lines = []
        for seq, (steps, stale_counts) in enumerate(
                [(10, [5, 3, 0]), (25, [12, 8, 1]), (40, [20, 12, 3])],
                start=1):
            lines.append("METRICS_JSON: " + json.dumps({
                "kind": "snapshot", "seq": seq, "ts": 100.0 + 5 * seq,
                "uptime_seconds": 5.0 * seq, "role": "worker", "pid": 42,
                "counters": {
                    "dps_worker_steps_total{worker=0}": steps,
                    "dps_rpc_client_bytes_total{direction=out,rpc=PushGradrients}":
                        steps * 1000,
                    "dps_store_pushes_total{backend=python,outcome=accepted}":
                        steps,
                },
                "gauges": {"dps_store_global_step{backend=python}": steps},
                "histograms": {
                    "dps_store_staleness_versions{backend=python}": {
                        "le": [0, 1, 2], "counts": stale_counts,
                        "sum": 1.0, "count": sum(stale_counts)}},
            }))
        lines.append("METRICS_JSON: " + json.dumps(
            {"worker_id": 0, "total_workers": 1,
             "total_training_time_seconds": 15.0,
             "epoch_times_seconds": [15.0], "final_test_accuracy": 0.5,
             "all_test_accuracies": [0.5],
             "average_epoch_time_seconds": 15.0}))
        lines.append("METRICS_JSON: " + json.dumps(
            {"mode": "async", "total_workers": 1,
             "total_training_time_seconds": 16.0}))
        return "\n".join(lines)

    def test_snapshots_excluded_from_final_aggregation(self):
        from distributed_parameter_server_for_ml_training_tpu.analysis import (
            parse_experiment)
        rec = parse_experiment(self._log(), "t")
        # exactly one worker exit row; the 3 snapshots must not pollute it
        assert len(rec["raw_worker_metrics"]) == 1
        assert rec["server_metrics"]["mode"] == "async"
        agg = rec["worker_metrics_aggregated"]
        assert agg["total_training_time_seconds"] == 15.0

    def test_build_timeseries_rates(self):
        from distributed_parameter_server_for_ml_training_tpu.analysis import (
            build_telemetry_timeseries)
        ts = build_telemetry_timeseries(self._log())
        assert list(ts["procs"]) == ["worker:42"]
        proc = ts["procs"]["worker:42"]
        assert proc["t"] == [5.0, 10.0, 15.0]
        key = "dps_worker_steps_total{worker=0}"
        assert proc["counters"][key] == [10.0, 25.0, 40.0]
        assert proc["rates"][key] == [3.0, 3.0]  # 15 steps / 5 s
        assert proc["gauges"][
            "dps_store_global_step{backend=python}"] == [10, 25, 40]

    def test_worker_throughput_series(self):
        from distributed_parameter_server_for_ml_training_tpu.analysis import (
            build_telemetry_timeseries, worker_throughput_series)
        thr = worker_throughput_series(
            build_telemetry_timeseries(self._log()))
        assert list(thr) == ["worker-0"]
        assert thr["worker-0"]["steps_per_second"] == [3.0, 3.0]
        assert thr["worker-0"]["t"] == [10.0, 15.0]

    def test_staleness_series(self):
        from distributed_parameter_server_for_ml_training_tpu.analysis import (
            build_telemetry_timeseries, staleness_series)
        st = staleness_series(build_telemetry_timeseries(self._log()))
        assert st["le"] == [0, 1, 2]
        assert st["counts"] == [20, 12, 3]  # final cumulative histogram
        assert any("accepted" in k for k in st["push_rates"])

    def test_plot_telemetry(self, tmp_path):
        import os

        from distributed_parameter_server_for_ml_training_tpu.analysis import (
            ExperimentVisualizer, build_telemetry_timeseries)
        ts = build_telemetry_timeseries(self._log())
        out = tmp_path / "telemetry.png"
        ExperimentVisualizer.plot_telemetry(ts, str(out))
        assert os.path.getsize(out) > 1000


class TestBenchHardening:
    def test_retry_then_success(self, monkeypatch):
        import bench
        monkeypatch.setattr(bench, "_fail_inject_remaining", 2)
        sleeps = []
        devices = bench.acquire_backend(retries=5, backoff=3.0,
                                        sleep=sleeps.append)
        assert devices  # real jax.devices() after 2 injected failures
        assert sleeps == [3.0, 6.0]  # exponential backoff

    def test_exhausted_retries_raise_with_attempts(self, monkeypatch):
        import bench
        monkeypatch.setattr(bench, "_fail_inject_remaining", 99)
        with pytest.raises(RuntimeError) as ei:
            bench.acquire_backend(retries=2, backoff=1.0,
                                  sleep=lambda s: None)
        assert ei.value.bench_attempts == 3

    def test_diagnostic_json_on_failure(self, monkeypatch, capsys):
        """The acceptance property: a backend-init failure yields a
        parseable {"ok": false, ...} line where the result would have
        been — never a bare rc=1."""
        import bench
        monkeypatch.setattr(bench, "_fail_inject_remaining", 99)
        # --init-backoff 0: acquire_backend's sleep default is bound at
        # definition time, so patching time.sleep never reached it and
        # this test really slept 93 s.
        monkeypatch.setattr("sys.argv", ["bench.py", "--trials", "1",
                                         "--init-backoff", "0"])
        rc = bench.main()
        assert rc == 1
        out = capsys.readouterr().out
        diag = json.loads(out.strip().splitlines()[-1])
        assert diag["ok"] is False
        assert diag["stage"] == "backend_init"
        assert diag["attempts"] == 6
        assert "injected backend init failure" in diag["error"]


class TestBenchNoCpuFallback:
    """bench.py measures the chip: a run that finds no accelerator fails
    with the diagnostic line and exit code 1 — it never re-runs on CPU and
    never writes a CPU number under the device metric's name."""

    def test_cpu_devices_are_refused(self):
        import bench
        import jax
        with pytest.raises(RuntimeError, match="no accelerator") as ei:
            bench.require_accelerator(jax.devices(), attempts=4)
        assert ei.value.bench_attempts == 4

    def test_main_without_chip_emits_diagnostic_and_exits_1(
            self, monkeypatch, capsys):
        """xla_bridge can fail TPU init WITHOUT raising — jax.devices()
        then answers CpuDevice. That must end the run at backend_init,
        before any workload is built."""
        import bench
        monkeypatch.setattr(bench, "_fail_inject_remaining", 0)
        monkeypatch.setattr("sys.argv", ["bench.py", "--trials", "1"])
        assert bench.main() == 1
        lines = capsys.readouterr().out.strip().splitlines()
        diag = json.loads(lines[-1])
        assert diag["ok"] is False
        assert diag["stage"] == "backend_init"
        assert "no accelerator" in diag["error"]
        assert not any('"metric"' in ln for ln in lines)

    def test_fallback_surface_is_gone(self, monkeypatch):
        import bench
        assert not hasattr(bench, "acquire_backend_with_fallback")
        monkeypatch.setattr("sys.argv", ["bench.py", "--no-cpu-fallback"])
        with pytest.raises(SystemExit) as ei:
            bench.main()
        assert ei.value.code == 2  # argparse: unrecognized argument


