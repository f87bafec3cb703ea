"""Tier-1 telemetry smoke: `cli serve` with telemetry enabled + one worker
for a few steps; the Prometheus endpoint AND the snapshot stream must both
parse (ISSUE satellite: the smoke target wired into the tier-1 suite).

Both CLI entry points run IN-PROCESS (threads, real gRPC over localhost
sockets) rather than as subprocesses: the suite's jit cache then covers the
model compile, keeping this inside the tier-1 budget while still exercising
`cli.main` end to end — argument parsing, the telemetry session wiring, the
serve loop, the worker loop, and both read surfaces.
"""

import json
import socket
import threading
import time
from urllib.request import urlopen

from distributed_parameter_server_for_ml_training_tpu.utils.metrics import (
    parse_metrics_lines)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_cli_serve_worker_telemetry_smoke(capsys):
    from distributed_parameter_server_for_ml_training_tpu import cli

    grpc_port = _free_port()
    metrics_port = _free_port()
    errors: list = []

    def run(argv):
        try:
            cli.main(argv)
        except BaseException as e:  # surfaced after join
            errors.append(e)

    server = threading.Thread(target=run, args=([
        "serve", "--mode", "async", "--workers", "1",
        "--port", str(grpc_port), "--model", "vit_tiny",
        "--num-classes", "100", "--image-size", "32",
        "--platform", "cpu", "--emit-metrics",
        "--telemetry", "--telemetry-interval", "0.5",
        "--metrics-port", str(metrics_port)],), daemon=True)
    server.start()

    # Endpoint is up before the worker starts (the session wrapper starts
    # it around the command body) — wait for /healthz, not a sleep.
    deadline = time.time() + 60
    while True:
        try:
            health = json.loads(urlopen(
                f"http://127.0.0.1:{metrics_port}/healthz",
                timeout=5).read())
            assert health == {"ok": True}
            break
        except (OSError, ValueError):
            if time.time() > deadline:
                raise TimeoutError("metrics endpoint never came up")
            time.sleep(0.25)

    worker = threading.Thread(target=run, args=([
        "worker", "--server", f"localhost:{grpc_port}",
        "--worker-name", "smoke-w0", "--model", "vit_tiny",
        "--synthetic", "--num-train", "96", "--num-test", "32",
        "--epochs", "1", "--batch-size", "32",
        "--platform", "cpu", "--dtype", "float32", "--no-augment",
        "--emit-metrics", "--telemetry",
        "--telemetry-interval", "0.5"],), daemon=True)
    worker.start()

    # Scrape WHILE the run is live: keep the last body that shows handler
    # activity (the point of the endpoint is mid-run visibility).
    live_scrape = ""
    deadline = time.time() + 300
    while worker.is_alive() and time.time() < deadline:
        try:
            body = urlopen(f"http://127.0.0.1:{metrics_port}/metrics",
                           timeout=5).read().decode()
            if "dps_rpc_handler_calls_total" in body:
                live_scrape = body
        except OSError:
            pass
        time.sleep(0.5)

    worker.join(timeout=300)
    assert not worker.is_alive(), "worker did not finish"
    # Server exits on its own once the registered worker JobFinished.
    server.join(timeout=60)
    assert not server.is_alive(), "server did not exit after JobFinished"
    assert not errors, errors

    # 1) Prometheus surface parsed and showed live handler/store activity.
    assert live_scrape, "never scraped a live /metrics body with activity"
    assert "# TYPE dps_rpc_handler_seconds histogram" in live_scrape
    assert 'dps_rpc_handler_calls_total{rpc="RegisterWorker"}' in live_scrape
    assert 'dps_store_pushes_total{' in live_scrape

    # 2) Snapshot stream parsed: both roles emitted, and the extended ETL
    # turns the stream into per-worker throughput + staleness series.
    out = capsys.readouterr().out
    snaps = [m for m in parse_metrics_lines(out)
             if m.get("kind") == "snapshot"]
    roles = {s["role"] for s in snaps}
    assert {"server", "worker"} <= roles, roles

    from distributed_parameter_server_for_ml_training_tpu.analysis import (
        build_telemetry_timeseries, parse_experiment, staleness_series,
        worker_throughput_series)
    ts = build_telemetry_timeseries(out)
    assert len(ts["procs"]) >= 1  # same pid: roles merge per (role,pid)
    thr = worker_throughput_series(ts)
    assert any(k.startswith("worker-") for k in thr), thr.keys()
    wk = next(k for k in thr if k.startswith("worker-"))
    # Counters are CUMULATIVE on the process-global registry — under the
    # full suite, earlier tests' workers share the worker=0 label — so
    # assert the DELTA across this run's stream: at most this run's 3
    # steps (96 imgs / batch 32), monotonically non-decreasing.
    steps = thr[wk]["cumulative_steps"]
    assert 0 <= steps[-1] - steps[0] <= 3.0, steps
    assert steps == sorted(steps)
    assert all(r >= 0 for r in thr[wk]["steps_per_second"])
    st = staleness_series(ts)
    assert st["le"] and sum(st["counts"]) >= 3  # one per push

    # 3) The classic exit lines still aggregate (snapshots filtered out).
    rec = parse_experiment(out, "smoke")
    assert rec["server_metrics"]["mode"] == "async"
    assert rec["server_metrics"]["global_steps_completed"] == 3
    assert len(rec["raw_worker_metrics"]) == 1
    row = rec["raw_worker_metrics"][0]
    assert row["local_steps_completed"] == 3
    # ...and say which device the worker computed on (PR 21).
    assert (row["platform"], row["device_id"]) == ("cpu", 0)
    assert row["device_count"] == 8 and row["device_kind"]
    assert row["final_train_loss"] > 0
