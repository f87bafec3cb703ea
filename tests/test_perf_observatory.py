"""Perf observatory (ISSUE 12): SLO burn rates, profiler accounting,
device-time attribution, and the benchwatch regression gate.

Everything here is tier-1: pure-python synthetic inputs, no accelerator,
no subprocesses. The recorded-demo artifact checks live in
``test_perf_observatory_demo.py``.
"""

from __future__ import annotations

import gzip
import json
import os

import pytest

from distributed_parameter_server_for_ml_training_tpu.analysis import (
    attribute_profile,
    classify_op,
    critical_path_report,
    device_time_tables,
)
from distributed_parameter_server_for_ml_training_tpu.analysis. \
    device_profile import _merge_tables
from distributed_parameter_server_for_ml_training_tpu.telemetry import (
    LATENCY_BUCKETS,
    MetricsRegistry,
    SloEvaluator,
    SloObjective,
    default_objectives,
)
from distributed_parameter_server_for_ml_training_tpu.telemetry. \
    profiler import compiled_cost, find_profile_dumps, mfu, peak_flops
from tools.benchwatch import (
    check_regressions,
    load_ledger,
    render_markdown,
    validate_record,
)


# -- SLO objectives + burn-rate evaluation -----------------------------------

def _slo(objectives, registry, **kw):
    kw.setdefault("fast_window_s", 60.0)
    kw.setdefault("slow_window_s", 300.0)
    return SloEvaluator(objectives, registry=registry, **kw)


def _observe(reg, method, latency_s, n):
    h = reg.histogram("dps_rpc_server_latency_seconds",
                      buckets=LATENCY_BUCKETS, method=method)
    for _ in range(n):
        h.observe(latency_s)


class TestSloObjective:
    def test_validation_rejects_bad_targets_and_thresholds(self):
        with pytest.raises(ValueError):
            SloObjective("x", "FetchParameters", 1.0)
        with pytest.raises(ValueError):
            SloObjective("x", "FetchParameters", 0.0)
        with pytest.raises(ValueError):
            SloObjective("x", "FetchParameters", 0.99, threshold_s=0.0)

    def test_evaluator_rejects_duplicates_and_inverted_windows(self):
        reg = MetricsRegistry()
        objs = [SloObjective("a", "FetchParameters", 0.99),
                SloObjective("a", "FetchParameters", 0.9)]
        with pytest.raises(ValueError):
            _slo(objs, reg)
        with pytest.raises(ValueError):
            _slo(default_objectives(), reg, fast_window_s=300.0,
                 slow_window_s=60.0)

    def test_defaults_use_the_wire_method_names(self):
        methods = {o.method for o in default_objectives()}
        # PushGradrients [sic] is the wire protocol's frozen typo.
        assert methods == {"FetchParameters", "PushGradrients"}


class TestBurnRates:
    def _fetch_latency_slo(self, reg, threshold_ms=50.0):
        return _slo([SloObjective("fetch_latency", "FetchParameters",
                                  0.99, threshold_s=threshold_ms / 1e3)],
                    reg)

    def test_no_traffic_no_breach(self):
        reg = MetricsRegistry()
        assert self._fetch_latency_slo(reg).evaluate(0.0) == []

    def test_slow_traffic_fires_both_windows_immediately(self):
        """A fresh server gets no grace period: with no baseline sample
        the full cumulative counts ARE the window delta."""
        reg = MetricsRegistry()
        ev = self._fetch_latency_slo(reg)
        _observe(reg, "FetchParameters", 0.5, 100)  # all past threshold
        breaches = ev.evaluate(0.0)
        rules = {b["rule"]: b for b in breaches}
        assert set(rules) == {"slo_burn_fast", "slo_burn_slow"}
        assert rules["slo_burn_fast"]["severity"] == "critical"
        assert rules["slo_burn_slow"]["severity"] == "warning"
        assert rules["slo_burn_fast"]["burn"] == pytest.approx(100.0)

    def test_breach_resolves_when_window_slides_past_the_fault(self):
        reg = MetricsRegistry()
        ev = self._fetch_latency_slo(reg)
        _observe(reg, "FetchParameters", 0.5, 100)
        assert ev.evaluate(0.0)  # breaching at t=0
        _observe(reg, "FetchParameters", 0.001, 500)  # fault cleared
        # Fast window (60s) at t=61 deltas against the t=0 baseline:
        # only the good traffic is inside the window.
        breaches = ev.evaluate(61.0)
        assert all(b["rule"] != "slo_burn_fast" for b in breaches)
        # Slow window (300s) still sees the cumulative bad.
        assert any(b["rule"] == "slo_burn_slow" for b in breaches)
        assert ev.evaluate(302.0) == []  # fully slid past

    def test_availability_objective_counts_errors(self):
        reg = MetricsRegistry()
        ev = _slo([SloObjective("push_availability", "PushGradrients",
                                0.99)], reg)
        _observe(reg, "PushGradrients", 0.001, 100)
        reg.counter("dps_rpc_server_errors_total",
                    method="PushGradrients").inc(50)
        b = {x["rule"]: x for x in ev.evaluate(0.0)}
        assert b["slo_burn_fast"]["bad"] == 50
        assert b["slo_burn_fast"]["burn"] == pytest.approx(50.0)

    def test_threshold_snaps_down_to_bucket_edge(self):
        """40 ms sits between the 25 ms and 50 ms edges; good counting
        must use 25 ms (conservative) and report the snapped value."""
        reg = MetricsRegistry()
        ev = self._fetch_latency_slo(reg, threshold_ms=40.0)
        _observe(reg, "FetchParameters", 0.030, 100)  # good at 40, bad at 25
        ev.evaluate(0.0)
        obj = ev.view()["objectives"][0]
        assert obj["threshold_ms"] == pytest.approx(40.0)
        assert obj["snapped_threshold_ms"] == pytest.approx(25.0)
        fast = obj["windows"]["slo_burn_fast"]
        assert fast["bad"] == 100  # conservative: counted bad

    def test_view_shape_for_the_cluster_block(self):
        reg = MetricsRegistry()
        ev = _slo(default_objectives(), reg)
        _observe(reg, "FetchParameters", 0.001, 10)
        ev.evaluate(0.0)
        view = ev.view()
        assert {o["name"] for o in view["objectives"]} == \
            {"fetch_latency", "fetch_availability", "push_availability"}
        for obj in view["objectives"]:
            assert set(obj["windows"]) == {"slo_burn_fast",
                                           "slo_burn_slow"}
            for w in obj["windows"].values():
                assert {"window_s", "total", "bad", "burn",
                        "burn_threshold", "breaching"} <= set(w)
        assert view["breaches"] == []
        json.dumps(view)  # JSON-serializable end to end


# -- profiler accounting ------------------------------------------------------

class TestProfilerAccounting:
    def test_peak_flops_unknown_kind_is_none_not_guess(self):
        assert peak_flops("TPU v4") == pytest.approx(275.0e12)
        assert peak_flops("cpu") is None
        assert mfu(1e12, 10.0, "cpu") is None
        assert mfu(None, 10.0, "TPU v4") is None
        assert mfu(1e12, None, "TPU v4") is None

    def test_mfu_math(self):
        # 1e12 flops * 27.5 steps/s over 1 chip of 275e12 peak = 10%.
        assert mfu(1e12, 27.5, "TPU v4", 1) == pytest.approx(0.10)
        assert mfu(1e12, 27.5, "TPU v4", 2) == pytest.approx(0.05)

    def test_compiled_cost_normalizes_all_backend_shapes(self):
        class Dict:
            def cost_analysis(self):
                return {"flops": 5.0, "bytes accessed": 7.0}

        class ListOfDict:
            def cost_analysis(self):
                return [{"flops": 5.0}]

        class Raises:
            def cost_analysis(self):
                raise RuntimeError("unsupported")

        assert compiled_cost(Dict()) == {"flops": 5.0,
                                         "bytes_accessed": 7.0}
        assert compiled_cost(ListOfDict())["flops"] == 5.0
        assert compiled_cost(Raises()) == {"flops": None,
                                           "bytes_accessed": None}

    def test_find_profile_dumps_layouts(self, tmp_path):
        run = tmp_path / "plugins" / "profile" / "2026_08_05"
        run.mkdir(parents=True)
        f = run / "host.trace.json.gz"
        f.write_bytes(gzip.compress(b"{}"))
        assert find_profile_dumps(str(tmp_path)) == [str(f)]
        assert find_profile_dumps(str(f)) == [str(f)]
        assert find_profile_dumps(str(tmp_path / "plugins")) == []


# -- device-time attribution --------------------------------------------------

def _ev(name, pid, ts, dur):
    return {"ph": "X", "name": name, "pid": pid, "tid": 1,
            "ts": ts, "dur": dur}


def _meta(pid, name):
    return {"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": name}}


class TestClassifyOp:
    def test_first_match_wins_collective_over_dot(self):
        assert classify_op("fusion.all_reduce.dot.3") == "collective"
        assert classify_op("dot_general.12") == "matmul"
        assert classify_op("convolution.687") == "conv"
        assert classify_op("quantize_i8.4") == "quantize-pack"
        assert classify_op("memcpy-h2d") == "transfer"
        assert classify_op("opaque_fusion_123") == "other"


class TestDeviceTimeTables:
    def test_device_lanes_basis_counts_everything(self):
        trace = {"traceEvents": [
            _meta(1, "/device:TPU:0"), _meta(2, "/host:CPU"),
            _ev("dot.1", 1, 0, 600.0),
            _ev("opaque_fusion", 1, 600, 400.0),
            _ev("python_frame", 2, 0, 9000.0),  # host lane: ignored
        ]}
        t = device_time_tables(trace)
        assert t["basis"] == "device_lanes"
        assert t["device_lanes_present"] is True
        assert t["op_classes"]["matmul"]["time_s"] == pytest.approx(6e-4)
        assert t["op_classes"]["other"]["time_s"] == pytest.approx(4e-4)
        assert sum(r["fraction"] for r in t["op_classes"].values()) == \
            pytest.approx(1.0)

    def test_host_ops_basis_skips_unmatched_host_names(self):
        """CPU backend: per-op thunk events classify; python frames and
        bookkeeping stay UNATTRIBUTED instead of polluting 'other'."""
        trace = {"traceEvents": [
            _meta(2, "/host:CPU"),
            _ev("convolution.687", 2, 0, 500.0),
            _ev("SomePythonFrame", 2, 0, 9000.0),
            _ev("ThunkExecutor::Execute", 2, 0, 600.0),  # ops win over proxy
        ]}
        t = device_time_tables(trace)
        assert t["basis"] == "host_ops"
        assert t["device_lanes_present"] is False
        assert set(t["op_classes"]) == {"conv"}
        assert t["total_attributed_s"] == pytest.approx(5e-4)

    def test_host_execute_proxy_excludes_wait_wrapper(self):
        """No op events at all: the executor wrapper stands in, but the
        outer '(wait for completion)' variant wraps the inner Execute
        and would double-count."""
        trace = {"traceEvents": [
            _meta(2, "/host:CPU"),
            _ev("ThunkExecutor::Execute (wait for completion)", 2, 0,
                1000.0),
            _ev("ThunkExecutor::Execute", 2, 0, 450.0),
            _ev("ThunkExecutor::Execute", 2, 500, 450.0),
        ]}
        t = device_time_tables(trace)
        assert t["basis"] == "host_execute_proxy"
        assert t["op_classes"]["host_execute"]["events"] == 2
        assert t["total_attributed_s"] == pytest.approx(9e-4)

    def test_empty_trace_is_basis_none(self):
        t = device_time_tables({"traceEvents": []})
        assert t["basis"] == "none"
        assert t["total_attributed_s"] == 0.0

    def test_merge_keeps_strongest_basis_only(self):
        """One host dumped device lanes, another only host events:
        averaging a proxy into measured device time would corrupt both,
        so only the strongest-basis tables are summed."""
        dev = device_time_tables({"traceEvents": [
            _meta(1, "/device:TPU:0"), _ev("dot.1", 1, 0, 100.0)]})
        host = device_time_tables({"traceEvents": [
            _meta(2, "/host:CPU"), _ev("convolution.1", 2, 0, 900.0)]})
        m = _merge_tables([dev, host])
        assert m["basis"] == "device_lanes"
        assert set(m["op_classes"]) == {"matmul"}
        assert m["total_attributed_s"] == pytest.approx(1e-4)


class TestAttributeProfile:
    def _capture_dir(self, tmp_path, events):
        run = tmp_path / "plugins" / "profile" / "run1"
        run.mkdir(parents=True)
        (run / "host.trace.json").write_text(
            json.dumps({"traceEvents": events}))
        return str(tmp_path)

    def _critical(self):
        t0 = 1000.0
        spans = [
            {"name": "worker.step", "trace_id": "T1", "span_id": "s0",
             "parent_id": None, "ts": t0, "dur": 1.0, "role": "w",
             "pid": 1, "tid": 1, "attrs": {"worker": 0, "step": 0}},
            {"name": "worker.compute", "trace_id": "T1", "span_id": "s1",
             "parent_id": "s0", "ts": t0, "dur": 0.8, "role": "w",
             "pid": 1, "tid": 1, "attrs": {}},
        ]
        return critical_path_report(spans)

    def test_reconciliation_reports_residual_not_hides_it(self, tmp_path):
        # 0.6 s attributed device time against a 1.0 s step wall.
        logdir = self._capture_dir(tmp_path, [
            _meta(1, "/device:TPU:0"), _ev("dot.1", 1, 0, 600000.0)])
        rep = attribute_profile(logdir, critical=self._critical(),
                                cost={"flops": 1e9,
                                      "bytes_accessed": 2e7},
                                mfu_value=0.42, device_kind="TPU v4")
        rec = rep["reconciliation"]
        assert rec["step_wall_s"] == pytest.approx(1.0)
        assert rec["attributed_s"] == pytest.approx(0.6)
        assert rec["residual_s"] == pytest.approx(0.4)
        assert rec["residual_fraction"] == pytest.approx(0.4)
        assert rec["attribution_basis"] == "device_lanes"
        assert rep["cost"]["mfu"] == 0.42
        assert rep["trace_files"] == ["host.trace.json"]
        json.dumps(rep)

    def test_attributed_beyond_wall_clamps_residual_at_zero(self, tmp_path):
        # Multi-chip capture can attribute more device-seconds than one
        # host's wall; residual clamps at 0 rather than going negative.
        logdir = self._capture_dir(tmp_path, [
            _meta(1, "/device:TPU:0"), _ev("dot.1", 1, 0, 5e6)])
        rec = attribute_profile(
            logdir, critical=self._critical())["reconciliation"]
        assert rec["residual_s"] == 0.0

    def test_empty_capture_dir_reports_no_files(self, tmp_path):
        rep = attribute_profile(str(tmp_path))
        assert rep["trace_files"] == []
        assert rep["profile"]["basis"] == "none"


# -- benchwatch ---------------------------------------------------------------

def _bench_record(value, rc=0, parsed_extra=None, metric="imgs_per_sec"):
    rec = {"n": 1, "cmd": "python bench.py", "rc": rc, "tail": "ok",
           "parsed": None}
    if rc == 0:
        rec["parsed"] = {"metric": metric, "value": value,
                         "unit": "images/sec/chip", "vs_baseline": 0.0}
        rec["parsed"].update(parsed_extra or {})
    return rec


def _write_ledger(tmp_path, records):
    for i, rec in enumerate(records):
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(json.dumps(rec))
    return load_ledger(str(tmp_path))


class TestBenchwatchSchema:
    def test_valid_record_passes(self):
        assert validate_record("bench", _bench_record(100.0)) == []

    def test_missing_and_mistyped_fields_flag(self):
        assert validate_record("bench", {"n": 1}) != []
        bad = _bench_record(100.0)
        bad["rc"] = True  # bool is not an int here
        assert any("rc" in e for e in validate_record("bench", bad))
        bad2 = _bench_record(100.0)
        del bad2["parsed"]["vs_baseline"]
        assert any("vs_baseline" in e
                   for e in validate_record("bench", bad2))

    def test_multichip_schema(self):
        ok = {"n_devices": 8, "rc": 0, "ok": True, "skipped": False,
              "tail": ""}
        assert validate_record("multichip", ok) == []
        assert validate_record("multichip", {"n_devices": 8}) != []

    def test_parsed_extras_allowed_for_forward_compat(self):
        rec = _bench_record(100.0, parsed_extra={"mfu": None,
                                                 "fetch_qps": 12.0})
        assert validate_record("bench", rec) == []


class TestBenchwatchRegression:
    def test_twenty_percent_drop_flags(self, tmp_path):
        ledger = _write_ledger(tmp_path, [
            _bench_record(v) for v in (100.0, 101.0, 99.0, 80.0)])
        v = check_regressions(ledger, tolerance=0.05,
                              baseline_window=3, recent_window=1)
        assert v["status"] == "regression"
        assert v["regressions"] == ["imgs_per_sec"]
        row = v["metrics"]["imgs_per_sec"]
        assert row["baseline_median"] == pytest.approx(100.0)
        assert row["recent_median"] == pytest.approx(80.0)
        assert "REGRESSION" in render_markdown(v)

    def test_noise_within_tolerance_passes(self, tmp_path):
        ledger = _write_ledger(tmp_path, [
            _bench_record(v) for v in (100.0, 101.0, 99.0, 97.0)])
        v = check_regressions(ledger)
        assert v["status"] == "pass"

    def test_failed_and_fallback_runs_skip_with_reason(self, tmp_path):
        ledger = _write_ledger(tmp_path, [
            _bench_record(100.0), _bench_record(100.0),
            _bench_record(100.0),
            _bench_record(0.0, rc=1),  # TPU-init flake
            _bench_record(5.0, parsed_extra={"platform_fallback": "cpu"}),
            _bench_record(99.0)])
        v = check_regressions(ledger)
        assert v["status"] == "pass"  # the flake is NOT a regression
        reasons = {s["file"]: s["reason"] for s in v["skipped"]}
        assert reasons["BENCH_r03.json"].startswith("rc=1")
        assert "platform_fallback" in reasons["BENCH_r04.json"]
        md = render_markdown(v)
        assert "BENCH_r03.json" in md and "BENCH_r04.json" in md

    def test_malformed_record_fails_the_gate(self, tmp_path):
        ledger = _write_ledger(tmp_path, [
            _bench_record(100.0), {"not": "a bench record"}])
        v = check_regressions(ledger)
        assert v["status"] == "malformed"
        assert v["malformed"][0]["file"] == "BENCH_r01.json"

    def test_codec_mb_per_s_tracked_as_secondary_series(self, tmp_path):
        """ISSUE 14: the device-codec throughput extra becomes its own
        watched series — absent/null in old records (no point, no gate),
        regression-flagged once enough rounds carry it."""
        from tools.benchwatch import EXTRA_METRIC_FIELDS
        assert EXTRA_METRIC_FIELDS["codec_mb_per_s"] == "MB/s"
        ledger = _write_ledger(tmp_path, [
            _bench_record(100.0),  # pre-codec round: no extra field
            _bench_record(100.0, parsed_extra={"codec_mb_per_s": None}),
            _bench_record(100.0, parsed_extra={"codec_mb_per_s": 900.0}),
            _bench_record(100.0, parsed_extra={"codec_mb_per_s": 910.0}),
            _bench_record(100.0, parsed_extra={"codec_mb_per_s": 905.0}),
            _bench_record(100.0, parsed_extra={"codec_mb_per_s": 400.0}),
        ])
        v = check_regressions(ledger)
        assert v["status"] == "regression"
        assert v["regressions"] == ["codec_mb_per_s"]
        row = v["metrics"]["codec_mb_per_s"]
        assert row["runs"] == 4  # null/absent rounds contribute nothing
        assert row["unit"] == "MB/s"
        # with only the three good rounds there is no verdict yet
        sub = tmp_path / "short"
        sub.mkdir()
        short = _write_ledger(sub, [
            _bench_record(100.0, parsed_extra={"codec_mb_per_s": x})
            for x in (900.0, 910.0, 905.0)] + [_bench_record(100.0)] * 2)
        vs = check_regressions(short)
        assert vs["metrics"]["codec_mb_per_s"]["status"] == \
            "insufficient_history"
        assert vs["status"] == "pass"

    def test_insufficient_history_reports_not_flags(self, tmp_path):
        ledger = _write_ledger(tmp_path,
                               [_bench_record(100.0),
                                _bench_record(50.0)])
        v = check_regressions(ledger)
        assert v["status"] == "pass"
        assert v["metrics"]["imgs_per_sec"]["status"] == \
            "insufficient_history"

    def test_committed_ledger_is_schema_clean(self):
        """The root holds no BENCH_r*.json any more (the earlier
        installation's records went in PR 21; the driver's ledger takes
        their place): whatever is there must be schema-clean, and the
        watch must cope with there being no bench history at all."""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ledger = load_ledger(repo)
        assert ledger["malformed"] == []
        assert not [e for e in ledger["entries"] if e["kind"] == "bench"]
        assert check_regressions(ledger)["status"] == "pass"

    def test_empty_ledger_passes(self, tmp_path):
        ledger = load_ledger(str(tmp_path))
        assert ledger["entries"] == [] and ledger["malformed"] == []
        v = check_regressions(ledger)
        assert v["status"] == "pass" and v["regressions"] == []
        assert "pass" in render_markdown(v).lower()


# -- cli status degradation ---------------------------------------------------

class TestStatusSloDegradation:
    def _view(self, **extra):
        view = {"mode": "async", "global_step": 5, "workers": [],
                "alerts": [], "alerts_total": {}}
        view.update(extra)
        return view

    def test_status_without_slo_block_renders(self):
        """Forward/backward compat: an older server (or --no-slo) sends
        no "slo" key and the dashboard must not mention SLOs."""
        from distributed_parameter_server_for_ml_training_tpu.cli import (
            _render_status)
        out = _render_status(self._view())
        assert "cluster: mode=async" in out
        assert "slo" not in out.lower()

    def test_status_with_slo_block_renders_rows_and_breach(self):
        from distributed_parameter_server_for_ml_training_tpu.cli import (
            _render_status)
        reg = MetricsRegistry()
        ev = _slo([SloObjective("fetch_latency", "FetchParameters",
                                0.99, threshold_s=0.05)], reg)
        _observe(reg, "FetchParameters", 0.5, 100)
        ev.evaluate(0.0)
        out = _render_status(self._view(slo=ev.view()))
        assert "slo objectives:" in out
        assert "fetch_latency" in out
        assert "BREACH" in out
