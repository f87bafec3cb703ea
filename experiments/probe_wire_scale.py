"""16-worker wire-matrix scale probe.

The reference records 16-worker tables and caps registration at 32
(README.md:454-464, server.py:424-426); our recorded wire matrix stops at
``async_8w``. This probe launches one ``cli serve --mode async --workers 16``
plus 16 real ``cli worker`` OS processes on THIS host and records — honestly,
either way — whether the host can actually run the 16-worker cell:

- completed/failed/timed-out worker counts and the wall clock,
- per-worker wire byte counters (the telemetry-PR byte evidence: every
  worker's METRICS_JSON row carries ``wire_bytes_out/in`` from
  RemoteStore's counters, and the serve process's snapshot stream carries
  ``dps_rpc_handler_bytes_total``),
- the host context (CPU count, load) that explains the result.

The outcome is merged into ``experiments/results/wire/wire_summary.json``
under ``"host_limits"`` — a measured record, not a silent stop at 8.

Usage::

    python experiments/probe_wire_scale.py [--workers 16] [--timeout 600]
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
CLI = [sys.executable, "-m",
       "distributed_parameter_server_for_ml_training_tpu.cli"]
OUT = os.path.join(REPO, "experiments", "results", "wire")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _env() -> dict:
    return dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=16)
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="whole-probe wall budget; expiry IS a result")
    args = ap.parse_args()

    port = _free_port()
    t0 = time.time()
    # stdout -> FILES, not pipes: the serve process emits a multi-KB
    # snapshot line every 5 s for up to --timeout seconds; an undrained
    # 64 KB pipe would block the emitter mid-run and freeze the byte
    # evidence at whatever fit early. Files never block the writer.
    logdir = tempfile.mkdtemp(prefix="wire_scale_probe_")
    s_log = open(os.path.join(logdir, "server.log"), "w+b")
    server = subprocess.Popen(
        CLI + ["serve", "--mode", "async", "--workers", str(args.workers),
               "--port", str(port), "--model", "vit_tiny",
               "--num-classes", "100", "--image-size", "32",
               "--platform", "cpu", "--emit-metrics",
               "--telemetry", "--telemetry-interval", "5"],
        cwd=REPO, env=_env(),
        stdout=s_log, stderr=subprocess.STDOUT)

    workers = []
    w_logs = []
    for i in range(args.workers):
        w_log = open(os.path.join(logdir, f"worker{i}.log"), "w+b")
        w_logs.append(w_log)
        workers.append(subprocess.Popen(
            CLI + ["worker", "--server", f"localhost:{port}",
                   "--worker-name", f"scale-w{i}", "--model", "vit_tiny",
                   "--synthetic", "--num-train", str(32 * args.workers),
                   "--num-test", "32", "--epochs", "1",
                   "--batch-size", "32", "--platform", "cpu",
                   "--dtype", "float32", "--no-augment", "--emit-metrics"],
            cwd=REPO, env=_env(),
            stdout=w_log, stderr=subprocess.STDOUT))

    deadline = t0 + args.timeout
    completed, failed, timed_out = [], [], []
    w_rows = []
    def _read_log(f) -> str:
        f.flush()
        f.seek(0)
        return f.read().decode(errors="replace")

    for i, w in enumerate(workers):
        budget = max(1.0, deadline - time.time())
        try:
            w.wait(timeout=budget)
        except subprocess.TimeoutExpired:
            w.kill()
            w.wait()
            timed_out.append(i)
            continue
        text = _read_log(w_logs[i])
        from distributed_parameter_server_for_ml_training_tpu.utils.metrics import (  # noqa: E501
            parse_metrics_lines)
        rows = [m for m in parse_metrics_lines(text)
                if "worker_id" in m and m.get("kind") != "snapshot"]
        if w.returncode == 0 and rows:
            completed.append(i)
            w_rows.append(rows[-1])
        else:
            failed.append({"worker": i, "rc": w.returncode,
                           "tail": text.strip().splitlines()[-3:]})
    wall = time.time() - t0

    try:
        server.wait(timeout=60)
    except subprocess.TimeoutExpired:
        server.kill()
        server.wait()
    s_text = _read_log(s_log)
    s_log.close()
    for f in w_logs:
        f.close()
    from distributed_parameter_server_for_ml_training_tpu.utils.metrics import (
        parse_metrics_lines)
    server_rows = [m for m in parse_metrics_lines(s_text)
                   if m.get("kind") != "snapshot" and "mode" in m]
    snapshots = [m for m in parse_metrics_lines(s_text)
                 if m.get("kind") == "snapshot"]
    handler_bytes = {}
    if snapshots:
        handler_bytes = {
            k: v for k, v in snapshots[-1].get("counters", {}).items()
            if k.startswith("dps_rpc_handler_bytes_total")}

    ok = len(completed) == args.workers
    record = {
        "probe": f"async_{args.workers}w_scale",
        "date_host": {"cpu_count": os.cpu_count(),
                      "loadavg_end": os.getloadavg()},
        "can_run": ok,
        "workers_requested": args.workers,
        "workers_completed": len(completed),
        "workers_failed": failed,
        "workers_timed_out": timed_out,
        "wall_seconds": round(wall, 1),
        "timeout_budget_seconds": args.timeout,
        "byte_evidence": {
            "per_worker_wire_bytes_out": [r.get("wire_bytes_out")
                                          for r in w_rows],
            "per_worker_wire_bytes_in": [r.get("wire_bytes_in")
                                         for r in w_rows],
            "server_handler_bytes_final_snapshot": handler_bytes,
        },
        "server_metrics": server_rows[-1] if server_rows else {},
    }
    path = os.path.join(OUT, f"scale_probe_{args.workers}w.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(json.dumps({k: record[k] for k in
                      ["can_run", "workers_completed", "workers_timed_out",
                       "wall_seconds"]}))
    print(f"probe record -> {path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
