"""Recorded probe for the overlapped comms pipeline + delta fetch (ISSUE 2).

Two honest A/B cells over real localhost gRPC (CPU backend), writing
``experiments/results/pipeline/overlap_probe.json`` + the raw telemetry
snapshot streams:

**A. overlap** — one `serve` (in-process gRPC server, sync store) + one
PSWorker over RemoteStore, K-step faithful loop, serial vs ``overlap=True``
with identical seeds. Records mean per-step wall time (post-compile
epochs), the accuracy-vs-step curves (must be EQUAL — the pipeline keeps
the serial RPC sequence), and the ``dps_worker_overlap_saved_seconds``
evidence from the snapshot stream.

**B. delta fetch** — sync store expecting 2 workers where one is an
artificial straggler (sleep-wrapped grad step), K=1: the fast worker's
boundary refetches mostly hit an unchanged step. Records client-side
FetchParameters wire bytes with ``delta_fetch`` off vs on; the ISSUE
acceptance bar is a >50% fetch-byte reduction in this straggler-wait
scenario, visible in the store/client not-modified counters.

Run: JAX_PLATFORMS=cpu python experiments/run_overlap_probe.py [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
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

OUT_DIR = os.path.join(REPO, "experiments", "results", "pipeline")


def _build(filters: int):
    from distributed_parameter_server_for_ml_training_tpu.models import (
        ResNet)
    model = ResNet(stage_sizes=(1, 1), num_filters=filters, num_classes=10)
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 32, 32, 3), np.float32),
                           train=False)
    from distributed_parameter_server_for_ml_training_tpu.utils.pytree \
        import flatten_params
    return model, flatten_params(variables["params"])


def _registry_deltas(before: dict, after: dict) -> dict:
    """Counter + histogram-sum deltas between two registry snapshots
    (the registry is process-global and cumulative across cells)."""
    out = {}
    for key, v in after.get("counters", {}).items():
        d = v - before.get("counters", {}).get(key, 0.0)
        if d:
            out[key] = round(d, 3)
    for key, h in after.get("histograms", {}).items():
        prev = before.get("histograms", {}).get(key, {})
        d_sum = h.get("sum", 0.0) - prev.get("sum", 0.0)
        d_n = h.get("count", 0) - prev.get("count", 0)
        if d_n:
            out[key] = {"sum": round(d_sum, 4), "count": d_n}
    return out


def _delay_calls(client, delay_s: float) -> None:
    """Inject symmetric one-way latency into the hot RPCs — the cross-host
    DCN term a localhost loopback doesn't have. ``time.sleep`` releases
    the GIL, so (like a real network wait) the delay is hideable by the
    comms pipeline but costs the serial loop its full duration."""
    for name in ("FetchParameters", "PushGradrients"):
        inner = client._call[name]

        def delayed(request, timeout=None, _inner=inner):
            time.sleep(delay_s)
            return _inner(request, timeout=timeout)

        client._call[name] = delayed


def _run_worker_cell(model, store_params, *, overlap: bool,
                     delta_fetch: bool, mode: str, total_workers: int,
                     sync_steps: int, epochs: int, n_train: int,
                     batch: int, straggle_s: float, log_path: str,
                     role: str, strict_rounds: bool = False,
                     rpc_delay_s: float = 0.0) -> dict:
    """One serve+worker(s) cell over localhost gRPC, snapshot stream to
    ``log_path``. Returns measurements + per-cell registry deltas."""
    from distributed_parameter_server_for_ml_training_tpu.comms import (
        RemoteStore, serve)
    from distributed_parameter_server_for_ml_training_tpu.data import (
        synthetic_cifar100)
    from distributed_parameter_server_for_ml_training_tpu.ps import (
        ParameterStore, PSWorker, StoreConfig, WorkerConfig)
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        SnapshotEmitter, get_registry)
    from distributed_parameter_server_for_ml_training_tpu.train.steps \
        import make_eval_step, make_grad_step

    ds = synthetic_cifar100(n_train=n_train, n_test=64, num_classes=10,
                            seed=1)
    store = ParameterStore(
        {k: v.copy() for k, v in store_params.items()},
        StoreConfig(mode=mode, total_workers=total_workers,
                    learning_rate=0.05, strict_rounds=strict_rounds))
    server, port = serve(store, port=0)
    grad_step = make_grad_step(model, augment=False)
    eval_step = jax.jit(make_eval_step())

    def straggler_step(*a):
        time.sleep(straggle_s)
        return grad_step(*a)

    reg_before = get_registry().snapshot()
    clients, workers = [], []
    log_f = open(log_path, "a")
    emitter = SnapshotEmitter(interval=1.0, role=role,
                              stream=log_f).start()
    t0 = time.time()
    try:
        for i in range(total_workers):
            c = RemoteStore(f"localhost:{port}")
            if rpc_delay_s:
                _delay_calls(c, rpc_delay_s)
            clients.append(c)
            workers.append(PSWorker(
                c, model, ds,
                WorkerConfig(batch_size=batch, num_epochs=epochs,
                             sync_steps=sync_steps, augment=False,
                             overlap=overlap, delta_fetch=delta_fetch,
                             # Liveness pings ride the same delta gating:
                             # a ping against an unchanged step costs a
                             # header instead of the full model (the
                             # polling half of the straggler-wait story).
                             heartbeat_interval=(0.15 if straggle_s
                                                 else 0.0)),
                grad_step=straggler_step if (straggle_s and i > 0)
                else grad_step,
                eval_step=eval_step, worker_name=f"{role}-w{i}"))
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=1800)
        for w in workers:
            if w.result.error is not None:
                raise w.result.error
    finally:
        emitter.stop(final=True)
        log_f.close()
        server.stop(grace=None)
        for c in clients:
            c.close()
    wall = time.time() - t0
    reg_after = get_registry().snapshot()
    r0 = workers[0].result
    # Post-compile per-step wall time: epoch 0 pays jit, drop it.
    steady = r0.epoch_times[1:] or r0.epoch_times
    steps_per_epoch = r0.local_steps_completed // epochs
    return {
        "wall_seconds": round(wall, 2),
        "epoch_times_seconds": [round(t, 3) for t in r0.epoch_times],
        "mean_step_seconds_post_compile": round(
            sum(steady) / (len(steady) * steps_per_epoch), 5),
        "test_accuracies": r0.test_accuracies,
        "local_steps": r0.local_steps_completed,
        "pushes_accepted": r0.pushes_accepted,
        "wire": clients[0].wire_stats(),
        "registry_deltas": _registry_deltas(reg_before, reg_after),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller model/dataset (CI smoke, not recorded)")
    ap.add_argument("--filters", type=int, default=32)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--n-train", type=int, default=768)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--sync-steps", type=int, default=4)
    ap.add_argument("--straggle", type=float, default=0.25)
    args = ap.parse_args()
    if args.quick:
        args.filters, args.epochs = 16, 2
        args.n_train, args.batch = 256, 16

    os.makedirs(OUT_DIR, exist_ok=True)
    model, params = _build(args.filters)
    n_params = sum(int(v.size) for v in params.values())
    print(f"model: {n_params} params "
          f"({n_params * 4 / 1e6:.2f} MB fp32 fetch payload)", flush=True)

    # -- A: overlap serial vs pipelined, across injected RPC latencies -----
    # This host has ONE core: CPU-bound codec/handler work cannot truly
    # run under CPU-bound XLA compute, so the 0 ms row measures pipeline
    # OVERHEAD honestly. The injected one-way delays simulate the
    # cross-host DCN latency the pipeline exists to hide (the reference's
    # deployed topology); sleeps release the GIL exactly like a socket
    # wait, so the overlap they show is real, not an artifact.
    overlap_log = os.path.join(OUT_DIR, "overlap_cells.log")
    open(overlap_log, "w").close()
    latencies = [0.0, 0.01] if args.quick else [0.0, 0.01, 0.025]
    by_latency = {}
    for delay in latencies:
        cells = {}
        for name, overlap in (("serial", False), ("overlapped", True)):
            tag = f"{name}@{int(delay * 1e3)}ms"
            print(f"[A:{tag}] running...", flush=True)
            cells[name] = _run_worker_cell(
                model, params, overlap=overlap, delta_fetch=True,
                mode="sync", total_workers=1, sync_steps=args.sync_steps,
                epochs=args.epochs, n_train=args.n_train, batch=args.batch,
                straggle_s=0.0, log_path=overlap_log,
                role=f"overlap-{tag}", rpc_delay_s=delay)
            print(f"[A:{tag}] mean step "
                  f"{cells[name]['mean_step_seconds_post_compile'] * 1e3:.2f}"
                  f" ms, accs {cells[name]['test_accuracies']}", flush=True)
        s, o = (cells["serial"]["mean_step_seconds_post_compile"],
                cells["overlapped"]["mean_step_seconds_post_compile"])
        by_latency[f"{int(delay * 1e3)}ms"] = {
            **{k: cells[k] for k in ("serial", "overlapped")},
            "accuracy_vs_step_equal": (cells["serial"]["test_accuracies"]
                                       == cells["overlapped"]
                                       ["test_accuracies"]),
            "mean_step_reduction_pct": round(100.0 * (s - o) / s, 2),
        }
    overlap_result = {"by_rpc_latency": by_latency}

    # -- B: delta fetch in a straggler-wait sync scenario -------------------
    delta_log = os.path.join(OUT_DIR, "delta_cells.log")
    open(delta_log, "w").close()
    fetch_key = ("dps_rpc_client_bytes_total"
                 "{direction=in,rpc=FetchParameters}")
    dcells = {}
    for name, on in (("delta_off", False), ("delta_on", True)):
        print(f"[B:{name}] running...", flush=True)
        # strict_rounds: a round needs BOTH workers, so the step genuinely
        # waits on the straggler (with quirk-3 counting, the fast worker's
        # own double pushes would complete rounds and advance the step,
        # which is restart pollution, not a straggler wait).
        dcells[name] = _run_worker_cell(
            model, params, overlap=False, delta_fetch=on, mode="sync",
            total_workers=2, sync_steps=1, epochs=2,
            n_train=256, batch=32, straggle_s=args.straggle,
            log_path=delta_log, role=f"delta-{name}", strict_rounds=True)
        fetched = dcells[name]["registry_deltas"].get(fetch_key, 0.0)
        print(f"[B:{name}] FetchParameters bytes in: {fetched:.0f}",
              flush=True)
    f_off = dcells["delta_off"]["registry_deltas"].get(fetch_key, 0.0)
    f_on = dcells["delta_on"]["registry_deltas"].get(fetch_key, 0.0)
    delta_result = {
        **dcells,
        "fetch_bytes_in": {"delta_off": f_off, "delta_on": f_on},
        "fetch_bytes_reduction_pct": round(
            100.0 * (f_off - f_on) / f_off, 2) if f_off else None,
    }

    # -- telemetry-stream evidence (the wins, visible in snapshots) ---------
    from distributed_parameter_server_for_ml_training_tpu.analysis.parse_logs \
        import build_telemetry_timeseries
    streams = {}
    for label, path in (("overlap", overlap_log), ("delta", delta_log)):
        with open(path) as f:
            ts = build_telemetry_timeseries(f.read())
        streams[label] = {
            proc_key: proc.get("pipeline", {})
            for proc_key, proc in ts["procs"].items()}

    record = {
        "experiment": "overlap_probe",
        "topology": "in-process gRPC serve + RemoteStore PSWorker threads, "
                    "localhost, JAX_PLATFORMS=cpu",
        "model_params": n_params,
        "config": vars(args),
        "overlap": overlap_result,
        "delta_fetch": delta_result,
        "telemetry_pipeline_sections": streams,
        "notes": [
            "mean_step_seconds_post_compile drops epoch 0 (jit compile).",
            "A-cell runs are seed-identical; accuracy_vs_step_equal is the "
            "pipeline's serial-RPC-sequence guarantee, checked not assumed.",
            "SINGLE-CORE HOST: the 0ms A-row measures pipeline overhead "
            "honestly (CPU-bound comms cannot hide under CPU-bound compute "
            "on one core); the 10/25ms rows inject symmetric one-way RPC "
            "latency simulating the cross-host DCN term — sleeps release "
            "the GIL exactly like socket waits, so the overlap they show "
            "is the mechanism's real effect on its target topology.",
            "B-cell fetch bytes are the client-side FetchParameters "
            "direction=in counter delta over both clients (fast worker + "
            "straggler); strict_rounds makes the round genuinely wait on "
            "the straggler.",
            "registry deltas are per-cell differences of the process-global "
            "registry; the raw snapshot streams are in *_cells.log.",
        ],
    }
    out_path = os.path.join(OUT_DIR, "overlap_probe.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(f"\nwrote {out_path}")
    for lat, row in by_latency.items():
        print(f"overlap@{lat}: step "
              f"{row['serial']['mean_step_seconds_post_compile'] * 1e3:.2f}"
              f" -> "
              f"{row['overlapped']['mean_step_seconds_post_compile'] * 1e3:.2f}"
              f" ms ({row['mean_step_reduction_pct']}%), "
              f"acc equal: {row['accuracy_vs_step_equal']}")
    print(f"delta fetch: {f_off / 1e6:.2f} -> {f_on / 1e6:.2f} MB in "
          f"({delta_result['fetch_bytes_reduction_pct']}% reduction)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
