"""Recorded end-to-end telemetry demo (ISSUE acceptance artifact).

Runs one SYNC and one ASYNC training run with ``--telemetry`` enabled as
real CLI subprocesses, captures their stdout (snapshot stream + classic
exit lines), and parses both through the extended ETL into per-worker
throughput and staleness time-series. Also records ``bench.py``'s
diagnostic JSON under an injected backend-init failure.

Outputs (checked into experiments/results/telemetry/):

- ``sync_demo.log`` / ``async_demo.log`` — raw captured stdout (the
  evidence the parses are real, and a fixture for re-running the ETL),
- ``sync_demo.json`` / ``async_demo.json`` — experiment record (reference
  schema, snapshots filtered) + built time-series + derived
  throughput/staleness series,
- ``telemetry_timeseries.png`` — 4-panel plot from the async stream,
- ``bench_diag_demo.json`` — bench.py stdout + rc under
  ``DPS_BENCH_FAIL_INJECT=99`` (proves the flake-proofing artifact).

Usage::

    python experiments/run_telemetry_demo.py [--out-dir experiments/results/telemetry]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # runnable from any cwd
    sys.path.insert(0, REPO)
CLI = [sys.executable, "-m",
       "distributed_parameter_server_for_ml_training_tpu.cli"]


def _env(n_devices: int = 1) -> dict:
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               PYTHONUNBUFFERED="1")
    if n_devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{n_devices}")
    return env


def run_mode(mode: str, out_dir: str, epochs: int = 2,
             workers: int = 2) -> dict:
    cmd = CLI + ["train", "--mode", mode, "--workers", str(workers),
                 "--model", "vit_tiny", "--synthetic",
                 "--num-train", "256", "--num-test", "64",
                 "--epochs", str(epochs), "--batch-size", "32",
                 "--platform", "cpu", "--dtype", "float32", "--no-augment",
                 "--emit-metrics", "--telemetry",
                 "--telemetry-interval", "1.0"]
    print(f"[{mode}] {' '.join(cmd)}", file=sys.stderr)
    proc = subprocess.run(cmd, cwd=REPO, env=_env(workers),
                          capture_output=True, timeout=900)
    log = proc.stdout.decode(errors="replace")
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr.decode(errors="replace")[-3000:])
        raise SystemExit(f"{mode} demo run failed rc={proc.returncode}")

    with open(os.path.join(out_dir, f"{mode}_demo.log"), "w") as f:
        f.write(log)

    from distributed_parameter_server_for_ml_training_tpu.analysis import (
        build_telemetry_timeseries, parse_experiment, staleness_series,
        worker_throughput_series)
    ts = build_telemetry_timeseries(log)
    record = {
        "experiment": parse_experiment(log, f"telemetry_{mode}_demo"),
        "timeseries": ts,
        "worker_throughput": worker_throughput_series(ts),
        "staleness": staleness_series(ts),
        "command": cmd[2:],
    }
    with open(os.path.join(out_dir, f"{mode}_demo.json"), "w") as f:
        json.dump(record, f, indent=2)
    n_snaps = sum(len(v["t"]) for v in ts["procs"].values())
    print(f"[{mode}] ok: {n_snaps} snapshots, "
          f"throughput series: {sorted(record['worker_throughput'])}",
          file=sys.stderr)
    return record


def run_bench_diag(out_dir: str) -> None:
    cmd = [sys.executable, "bench.py", "--init-backoff", "0.2",
           "--trials", "1"]
    proc = subprocess.run(cmd, cwd=REPO,
                          env=dict(os.environ, JAX_PLATFORMS="cpu",
                                   DPS_BENCH_FAIL_INJECT="99"),
                          capture_output=True, timeout=300)
    line = proc.stdout.decode(errors="replace").strip().splitlines()[-1]
    diag = json.loads(line)
    assert diag["ok"] is False and diag["stage"] == "backend_init", diag
    with open(os.path.join(out_dir, "bench_diag_demo.json"), "w") as f:
        json.dump({"rc": proc.returncode, "stdout_last_line": diag,
                   "command": cmd,
                   "env": {"DPS_BENCH_FAIL_INJECT": "99"}}, f, indent=2)
    print(f"[bench-diag] ok: rc={proc.returncode}, stage="
          f"{diag['stage']}, attempts={diag['attempts']}", file=sys.stderr)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir",
                    default=os.path.join(REPO, "experiments", "results",
                                         "telemetry"))
    ap.add_argument("--epochs", type=int, default=2)
    args = ap.parse_args()
    os.makedirs(args.out_dir, exist_ok=True)

    async_rec = run_mode("async", args.out_dir, epochs=args.epochs)
    run_mode("sync", args.out_dir, epochs=args.epochs)
    run_bench_diag(args.out_dir)

    from distributed_parameter_server_for_ml_training_tpu.analysis import (
        ExperimentVisualizer)
    ExperimentVisualizer.plot_telemetry(
        async_rec["timeseries"],
        os.path.join(args.out_dir, "telemetry_timeseries.png"))
    print(f"artifacts in {args.out_dir}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
