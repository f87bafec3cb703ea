"""Recorded chaos soak: training survives a parameter-server kill+restart.

The crash-recovery subsystem's acceptance artifact (ISSUE 4,
docs/ROBUSTNESS.md), written to ``experiments/results/chaos/``:

**Cell A — sync parity through a server restart.** One ``serve``-equivalent
server SUBPROCESS (tiny ResNet store, periodic checkpoints + push-token
journal, SIGTERM snapshot flush through the telemetry shutdown path) and
one PSWorker over gRPC. Mid-run — deterministically, just before the
worker's Nth push leaves — the server is SIGTERM'd (its handler flushes a
final durable snapshot and exits 143), a replacement starts on the same
port with ``--restore``, and the worker's reconnect state machine rides
through: re-register, re-fetch at the restored step, reconcile the
in-flight gradient with its ORIGINAL exactly-once token. The run must
reach the **same step count and accuracy curve** as a fault-free control,
with **zero double-applied pushes** (journal-verified: restored step +
post-restart applies == total accepted pushes).

**Cell B — async convergence under faults + restart.** Two workers against
an async server, with deterministic client-side fault injection
(``comms/faults.py``: seeded UNAVAILABLE blips + replies dropped AFTER the
server-side apply) and the same mid-run SIGTERM/restore restart. The run
must complete with final accuracy within tolerance of its fault-free
control and no double-applies (final step <= total accepted, bounded
apply loss at the kill edge).

Both cells capture worker-side telemetry snapshot streams; the recorded
``dps_worker_reconnect_total`` > 0 is part of the artifact.

Run: JAX_PLATFORMS=cpu python experiments/run_chaos_soak.py [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
from distributed_parameter_server_for_ml_training_tpu.utils.compile_cache \
    import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np  # noqa: E402

OUT_DIR = os.path.join(REPO, "experiments", "results", "chaos")


def _build_model_and_params():
    from distributed_parameter_server_for_ml_training_tpu.models import (
        ResNet)
    from distributed_parameter_server_for_ml_training_tpu.utils.pytree \
        import flatten_params
    model = ResNet(stage_sizes=(1, 1), num_filters=8, num_classes=10)
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 32, 32, 3), np.float32),
                           train=False)
    return model, flatten_params(variables["params"])


# -- server child -------------------------------------------------------------

def server_child(args) -> int:
    """The parameter-server process for one life: tiny-model store +
    service + periodic checkpointer + SIGTERM snapshot flush, telemetry
    snapshots on stdout. ``--restore`` resumes params/step/journal from
    the checkpoint dir (the second life after a kill)."""
    from distributed_parameter_server_for_ml_training_tpu.checkpoint import (
        PeriodicStoreCheckpointer, restore_server_state)
    from distributed_parameter_server_for_ml_training_tpu.comms import (
        ParameterService, serve)
    from distributed_parameter_server_for_ml_training_tpu.ps import (
        ParameterStore, StoreConfig)
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        SnapshotEmitter, add_shutdown_flush, install_shutdown_hooks)

    _, flat = _build_model_and_params()
    store = ParameterStore(flat, StoreConfig(
        mode=args.mode, total_workers=args.workers, learning_rate=0.05,
        staleness_bound=10, elastic=True, worker_timeout=30.0,
        push_codec="none"))
    svc = ParameterService(store)
    if args.restore:
        step, journal_n = restore_server_state(store, svc, args.ckpt_dir)
        print(f"CHAOS_SERVER_RESTORED step={step} journal={journal_n}",
              flush=True)
    ckpt = PeriodicStoreCheckpointer(store, args.ckpt_dir,
                                     interval=args.ckpt_interval,
                                     journal_fn=svc.journal_snapshot)
    ckpt.start()
    # SIGTERM drains the end state through the SAME shutdown path that
    # dumps the flight recorder (telemetry/trace.py) — the tentpole's
    # durable-kill semantics, exercised for real by the parent's kill.
    install_shutdown_hooks(role="server")
    add_shutdown_flush(ckpt.flush_now)
    emitter = SnapshotEmitter(interval=1.0, role="server").start()
    server, port = serve(store, port=args.port, service=svc)
    print(f"CHAOS_SERVER_READY port={port}", flush=True)
    lifetime_deadline = time.time() + args.max_lifetime
    while not store.wait_all_finished(timeout=0.5):
        store.expire_stale_workers()
        if time.time() > lifetime_deadline:
            print("CHAOS_SERVER_LIFETIME_EXCEEDED", flush=True)
            break
    time.sleep(0.3)
    server.stop(grace=1.0)
    ckpt.stop(final_snapshot=True)
    emitter.stop(final=True)
    print("CHAOS_SERVER_EXIT " + json.dumps({
        "global_step": store.global_step,
        "gradients_processed": store.stats.gradients_processed,
        "gradients_rejected": store.stats.gradients_rejected,
    }), flush=True)
    return 0


# -- parent-side orchestration ------------------------------------------------

def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_server(out_dir, tag, port, ckpt_dir, mode, workers,
                  restore=False, ckpt_interval=2.0):
    log_path = os.path.join(out_dir, f"{tag}.log")
    log = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--server-child",
         "--port", str(port), "--ckpt-dir", ckpt_dir, "--mode", mode,
         "--workers", str(workers), "--ckpt-interval", str(ckpt_interval)]
        + (["--restore"] if restore else []),
        stdout=log, stderr=subprocess.STDOUT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO)
    deadline = time.time() + 120
    while time.time() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(f"server {tag} died at startup; see "
                               f"{log_path}")
        with open(log_path) as f:
            if "CHAOS_SERVER_READY" in f.read():
                return proc, log_path
        time.sleep(0.1)
    raise RuntimeError(f"server {tag} never came up; see {log_path}")


def _server_exit_stats(log_path) -> dict:
    with open(log_path) as f:
        for line in f:
            if line.startswith("CHAOS_SERVER_EXIT "):
                return json.loads(line[len("CHAOS_SERVER_EXIT "):])
    return {}


class _KillSwitch:
    """Deterministic crash point: before the worker's Nth push leaves,
    SIGTERM the server (its handler flushes the durable snapshot), wait
    for it to die, and arm the delayed restart."""

    def __init__(self, client, at_push, kill_fn):
        self._inner = client._call["PushGradrients"]
        self._at = at_push
        self._kill_fn = kill_fn
        self.calls = 0
        self.fired = False
        client._call["PushGradrients"] = self

    def __call__(self, request, timeout=None):
        self.calls += 1
        if self.calls == self._at and not self.fired:
            self.fired = True
            self._kill_fn()
        return self._inner(request, timeout=timeout)


def _run_worker_cell(model, ds, *, port, n_workers, sync_steps, epochs,
                     batch, log_path, faults=None, reconnect_timeout=120.0,
                     kill_at_push=None, kill_fn=None, grad_step=None,
                     eval_step=None):
    """Run N PSWorkers against the (already-up) server at ``port``,
    telemetry snapshots to ``log_path``. Returns per-worker results."""
    from distributed_parameter_server_for_ml_training_tpu.comms import (
        RemoteStore)
    from distributed_parameter_server_for_ml_training_tpu.ps import (
        PSWorker, WorkerConfig)
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        SnapshotEmitter)

    with open(log_path, "w") as stream:
        emitter = SnapshotEmitter(interval=0.5, role="worker",
                                  stream=stream).start()
        clients, workers = [], []
        try:
            for i in range(n_workers):
                c = RemoteStore(f"localhost:{port}", rpc_timeout=15.0,
                                rpc_retries=1, rpc_backoff=0.05,
                                faults=faults)
                if i == 0 and kill_at_push is not None:
                    _KillSwitch(c, kill_at_push, kill_fn)
                clients.append(c)
                cfg = WorkerConfig(batch_size=batch, num_epochs=epochs,
                                   sync_steps=sync_steps, augment=False,
                                   heartbeat_interval=2.0,
                                   reconnect_timeout=reconnect_timeout,
                                   reconnect_backoff=0.1)
                workers.append(PSWorker(c, model, ds, cfg,
                                        grad_step=grad_step,
                                        eval_step=eval_step,
                                        worker_name=f"worker-{i}"))
            for w in workers:
                w.start()
            for w in workers:
                w.join(timeout=900)
        finally:
            emitter.stop(final=True)
            for c in clients:
                c.close()
    for w in workers:
        if w.result.error is not None:
            raise RuntimeError(
                f"{w.worker_name} failed") from w.result.error
    return [w.result for w in workers]


def _reconnect_counter_from_snapshots(log_path) -> float:
    from distributed_parameter_server_for_ml_training_tpu.analysis. \
        parse_logs import parse_snapshot_series
    series = parse_snapshot_series(open(log_path).read())
    total = 0.0
    for payloads in series.values():
        last = payloads[-1].get("counters", {})
        total += sum(v for k, v in last.items()
                     if k.startswith("dps_worker_reconnect_total"))
    return total


def _load_final_snapshot(ckpt_dir):
    from distributed_parameter_server_for_ml_training_tpu.checkpoint import (
        load_store_record)
    return load_store_record(ckpt_dir)


def run_soak(args) -> int:
    from distributed_parameter_server_for_ml_training_tpu.data import (
        synthetic_cifar100)
    from distributed_parameter_server_for_ml_training_tpu.train.steps \
        import make_eval_step, make_grad_step

    out_dir = args.out_dir
    os.makedirs(out_dir, exist_ok=True)
    quick = args.quick
    epochs = 2 if quick else 3
    n_train = 128 if quick else 256
    batch = 32
    model, _flat = _build_model_and_params()
    ds = synthetic_cifar100(n_train=n_train, n_test=64, num_classes=10,
                            seed=1)
    grad_step = make_grad_step(model, augment=False)
    eval_step = jax.jit(make_eval_step())
    summary = {"quick": quick, "cells": {}}
    checks: list[tuple[str, bool, str]] = []

    # ---- Cell A: sync parity through a kill+restart ------------------------
    sync_steps = 2
    pushes_per_epoch = (n_train // batch) // sync_steps
    total_pushes = pushes_per_epoch * epochs
    kill_at = total_pushes // 2 + 1

    port = _free_port()
    ctl_ckpt = os.path.join(out_dir, "ckpt_sync_control")
    p, ctl_log = _spawn_server(out_dir, "sync_control_server", port,
                               ctl_ckpt, "sync", 1)
    control = _run_worker_cell(
        model, ds, port=port, n_workers=1, sync_steps=sync_steps,
        epochs=epochs, batch=batch,
        log_path=os.path.join(out_dir, "sync_control_worker.log"),
        grad_step=grad_step, eval_step=eval_step)[0]
    p.wait(timeout=120)
    ctl_stats = _server_exit_stats(ctl_log)

    port = _free_port()
    chaos_ckpt = os.path.join(out_dir, "ckpt_sync_chaos")
    p1, log1 = _spawn_server(out_dir, "sync_chaos_server1", port,
                             chaos_ckpt, "sync", 1)
    restart_ready = threading.Event()
    holder = {}

    def kill_and_schedule_restart():
        p1.send_signal(signal.SIGTERM)  # handler flushes the snapshot
        rc = p1.wait(timeout=60)
        print(f"server1 SIGTERM'd (rc={rc}); restarting shortly",
              flush=True)
        def _restart():
            time.sleep(0.5)  # let the worker hit SessionLost first
            holder["p2"], holder["log2"] = _spawn_server(
                out_dir, "sync_chaos_server2", port, chaos_ckpt, "sync",
                1, restore=True)
            restart_ready.set()
        threading.Thread(target=_restart, daemon=True).start()

    chaos = _run_worker_cell(
        model, ds, port=port, n_workers=1, sync_steps=sync_steps,
        epochs=epochs, batch=batch,
        log_path=os.path.join(out_dir, "sync_chaos_worker.log"),
        kill_at_push=kill_at, kill_fn=kill_and_schedule_restart,
        grad_step=grad_step, eval_step=eval_step)[0]
    assert restart_ready.wait(120)
    holder["p2"].wait(timeout=120)
    chaos_stats = _server_exit_stats(holder["log2"])
    _, final_meta = _load_final_snapshot(chaos_ckpt)
    reconnects_in_snapshots = _reconnect_counter_from_snapshots(
        os.path.join(out_dir, "sync_chaos_worker.log"))

    restored_step = None
    with open(holder["log2"]) as f:
        for line in f:
            if line.startswith("CHAOS_SERVER_RESTORED"):
                restored_step = int(line.split("step=")[1].split()[0])
    applies_life2 = chaos_stats.get("gradients_processed", -1)

    checks += [
        ("A.control_completed",
         control.local_steps_completed == epochs * n_train // batch
         and ctl_stats.get("global_step") == total_pushes,
         f"{control.local_steps_completed} steps, server "
         f"{ctl_stats.get('global_step')}"),
        ("A.worker_survived_restart", chaos.reconnects == 1,
         f"reconnects={chaos.reconnects}"),
        ("A.step_parity",
         chaos_stats.get("global_step") == ctl_stats.get("global_step"),
         f"chaos={chaos_stats.get('global_step')} "
         f"control={ctl_stats.get('global_step')}"),
        ("A.accuracy_curve_parity",
         np.allclose(control.test_accuracies, chaos.test_accuracies,
                     atol=1e-12),
         f"control={control.test_accuracies} "
         f"chaos={chaos.test_accuracies}"),
        ("A.zero_double_applies_journal_verified",
         restored_step is not None
         and restored_step + applies_life2 == chaos.pushes_accepted
         and chaos.pushes_accepted == total_pushes,
         f"restored={restored_step} + life2={applies_life2} vs "
         f"accepted={chaos.pushes_accepted} (expected {total_pushes})"),
        ("A.reconnect_counter_in_snapshots", reconnects_in_snapshots > 0,
         f"dps_worker_reconnect_total={reconnects_in_snapshots}"),
    ]
    summary["cells"]["sync_parity"] = {
        "epochs": epochs, "sync_steps": sync_steps,
        "total_pushes": total_pushes, "killed_before_push": kill_at,
        "control": {"server": ctl_stats,
                    "accuracy_curve": control.test_accuracies,
                    "pushes_accepted": control.pushes_accepted},
        "chaos": {"server_life2": chaos_stats,
                  "restored_step": restored_step,
                  "accuracy_curve": chaos.test_accuracies,
                  "pushes_accepted": chaos.pushes_accepted,
                  "reconnects": chaos.reconnects,
                  "reconnect_counter_in_snapshots":
                      reconnects_in_snapshots},
        "final_snapshot_meta": {
            "global_step": final_meta["global_step"],
            "push_journal": final_meta["push_journal"]},
    }

    # ---- Cell B: async convergence under injected faults + restart ---------
    n_workers = 2
    fault_spec = ("seed=5;push.unavailable@p=0.08;push.drop_reply@every=5;"
                  "fetch.unavailable@p=0.04")
    from distributed_parameter_server_for_ml_training_tpu.comms import (
        FaultInjector)
    schedule_preview = FaultInjector(fault_spec).schedule_preview(
        "PushGradrients", 24)

    port = _free_port()
    b_ctl_ckpt = os.path.join(out_dir, "ckpt_async_control")
    p, b_ctl_log = _spawn_server(out_dir, "async_control_server", port,
                                 b_ctl_ckpt, "async", n_workers)
    b_control = _run_worker_cell(
        model, ds, port=port, n_workers=n_workers, sync_steps=1,
        epochs=epochs, batch=batch,
        log_path=os.path.join(out_dir, "async_control_worker.log"),
        grad_step=grad_step, eval_step=eval_step)
    p.wait(timeout=120)
    b_ctl_stats = _server_exit_stats(b_ctl_log)

    port = _free_port()
    b_ckpt = os.path.join(out_dir, "ckpt_async_chaos")
    bp1, b_log1 = _spawn_server(out_dir, "async_chaos_server1", port,
                                b_ckpt, "async", n_workers)
    b_restart_ready = threading.Event()
    b_holder = {}

    def b_kill_and_restart():
        bp1.send_signal(signal.SIGTERM)
        bp1.wait(timeout=60)
        def _restart():
            time.sleep(0.5)
            b_holder["p2"], b_holder["log2"] = _spawn_server(
                out_dir, "async_chaos_server2", port, b_ckpt, "async",
                n_workers, restore=True)
            b_restart_ready.set()
        threading.Thread(target=_restart, daemon=True).start()

    b_chaos = _run_worker_cell(
        model, ds, port=port, n_workers=n_workers, sync_steps=1,
        epochs=epochs, batch=batch,
        log_path=os.path.join(out_dir, "async_chaos_worker.log"),
        faults=fault_spec, kill_at_push=max(3, epochs),
        kill_fn=b_kill_and_restart,
        grad_step=grad_step, eval_step=eval_step)
    assert b_restart_ready.wait(120)
    b_holder["p2"].wait(timeout=120)
    b_stats = _server_exit_stats(b_holder["log2"])
    b_restored = None
    with open(b_holder["log2"]) as f:
        for line in f:
            if line.startswith("CHAOS_SERVER_RESTORED"):
                b_restored = int(line.split("step=")[1].split()[0])

    accepted = sum(r.pushes_accepted for r in b_chaos)
    acc_ctl = float(np.mean([r.test_accuracies[-1] for r in b_control]))
    acc_chaos = float(np.mean([r.test_accuracies[-1] for r in b_chaos]))
    final_step = b_stats.get("global_step", -1)
    applied_total = (b_restored or 0) + b_stats.get("gradients_processed",
                                                    0)
    checks += [
        ("B.workers_survived",
         all(r.reconnects >= 1 for r in b_chaos[:1]),
         f"reconnects={[r.reconnects for r in b_chaos]}"),
        ("B.no_double_applies",
         applied_total <= accepted,
         f"applied={applied_total} accepted={accepted}"),
        ("B.bounded_apply_loss_at_kill_edge",
         applied_total >= accepted - n_workers,
         f"applied={applied_total} accepted={accepted}"),
        ("B.converges_within_tolerance",
         abs(acc_chaos - acc_ctl) <= 0.15,
         f"control={acc_ctl:.4f} chaos={acc_chaos:.4f}"),
    ]
    summary["cells"]["async_faults"] = {
        "workers": n_workers, "epochs": epochs,
        "fault_spec": fault_spec,
        "fault_schedule_preview_push": schedule_preview,
        "control": {"server": b_ctl_stats, "final_accuracy": acc_ctl},
        "chaos": {"server_life2": b_stats, "restored_step": b_restored,
                  "final_accuracy": acc_chaos,
                  "pushes_accepted_total": accepted,
                  "reconnects": [r.reconnects for r in b_chaos]},
    }

    summary["checks"] = [
        {"name": n, "ok": bool(ok), "detail": d} for n, ok, d in checks]
    summary["ok"] = all(ok for _, ok, _ in checks)
    out_path = os.path.join(out_dir, "chaos_soak.json")
    with open(out_path, "w") as f:
        json.dump(summary, f, indent=2)
    for n, ok, d in checks:
        print(f"{'PASS' if ok else 'FAIL'} {n}: {d}")
    print(f"wrote {out_path}")
    return 0 if summary["ok"] else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out-dir", default=OUT_DIR)
    # internal: server-child mode
    ap.add_argument("--server-child", action="store_true")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=float, default=2.0)
    ap.add_argument("--mode", default="sync")
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--max-lifetime", type=float, default=600.0,
                    help="server-child self-destruct (orphan guard)")
    args = ap.parse_args()
    if args.server_child:
        return server_child(args)
    return run_soak(args)


if __name__ == "__main__":
    raise SystemExit(main())
