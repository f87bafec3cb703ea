"""Recorded distributed-tracing demo (ISSUE 3 acceptance artifacts).

Three recorded scenarios, artifacts under ``experiments/results/trace/``:

(a) **Multi-process sync trace tree** — a real ``cli serve`` + two
    ``cli worker`` processes with ``--trace --trace-dump-dir``; their
    flight-recorder dumps are assembled by ``trace_id`` and the demo
    verifies a server-side ``store.apply`` span is parented — through the
    RPC chain — by the originating worker's ``worker.step`` span.
    Artifacts: ``sync_trace_tree.json``, ``sync_trace.perfetto.json``
    (validated Perfetto-loadable by ``tests/test_trace.py``), raw dumps
    under ``raw/``.

(b) **Async staleness-attributed straggler** — an in-process async run
    where one worker's fetches are delayed (the injected-latency
    technique of run_overlap_probe.py): the critical-path report must
    attribute >=95% of the straggler step's wall time across
    compute/fetch-wait/push-wait/server-apply/codec and carry the
    staleness its pushes incurred. Artifacts:
    ``async_straggler_report.json``, ``async_trace.perfetto.json``.

(c) **SIGTERM post-mortem** — a ``cli train`` process is TERM'd mid-run
    after scraping its live ``/debug/trace``; the dump the signal handler
    writes must contain the live trace's spans. Artifact:
    ``sigterm_postmortem.json``.

Usage::

    python experiments/run_trace_demo.py [--out-dir experiments/results/trace]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time
from urllib.request import urlopen

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # runnable from any cwd
    sys.path.insert(0, REPO)
CLI = [sys.executable, "-m",
       "distributed_parameter_server_for_ml_training_tpu.cli"]


def _env() -> dict:
    return dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_port(port: int, deadline_s: float = 120.0) -> None:
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=1):
                return
        except OSError:
            time.sleep(0.25)
    raise TimeoutError(f"port {port} never opened")


def _ancestor_chain(span: dict, by_id: dict) -> list[str]:
    chain, node = [], span
    while node is not None:
        chain.append(node["name"])
        node = by_id.get(node.get("parent_id"))
    return chain


# -- (a) multi-process sync run -> assembled trace tree ----------------------

def run_sync_tree(out_dir: str) -> None:
    raw_dir = os.path.join(out_dir, "raw")
    os.makedirs(raw_dir, exist_ok=True)
    port = _free_port()
    serve_cmd = CLI + [
        "serve", "--mode", "sync", "--workers", "2", "--port", str(port),
        "--model", "vit_tiny", "--image-size", "32", "--platform", "cpu",
        "--trace", "--trace-buffer", "2048", "--trace-dump-dir", raw_dir]
    print(f"[sync] {' '.join(serve_cmd)}", file=sys.stderr)
    server = subprocess.Popen(serve_cmd, cwd=REPO, env=_env(),
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE)
    try:
        _wait_port(port)
        workers = []
        for i in range(2):
            cmd = CLI + [
                "worker", "--server", f"localhost:{port}",
                "--worker-name", f"trace-w{i}", "--model", "vit_tiny",
                "--synthetic", "--num-train", "256", "--num-test", "32",
                "--epochs", "1", "--batch-size", "32", "--sync-steps", "2",
                "--platform", "cpu", "--dtype", "float32", "--no-augment",
                "--trace", "--trace-buffer", "2048",
                "--trace-dump-dir", raw_dir]
            workers.append(subprocess.Popen(
                cmd, cwd=REPO, env=_env(), stdout=subprocess.PIPE,
                stderr=subprocess.PIPE))
        for w in workers:
            out, err = w.communicate(timeout=900)
            if w.returncode != 0:
                sys.stderr.write(err.decode(errors="replace")[-3000:])
                raise SystemExit(f"sync demo worker failed rc={w.returncode}")
        sout, serr = server.communicate(timeout=120)
        if server.returncode != 0:
            sys.stderr.write(serr.decode(errors="replace")[-3000:])
            raise SystemExit(f"sync demo server failed "
                             f"rc={server.returncode}")
    finally:
        if server.poll() is None:
            server.kill()

    from distributed_parameter_server_for_ml_training_tpu.analysis import (
        assemble_traces, find_trace_dumps, load_trace_dumps,
        save_chrome_trace)
    dumps = find_trace_dumps(raw_dir)
    spans = load_trace_dumps(dumps)
    roles = {s.get("role") for s in spans}
    assert {"server", "worker"} <= roles, roles
    by_id = {s["span_id"]: s for s in spans}

    # The acceptance join: a server apply span whose ancestor chain (via
    # the wire-propagated context) reaches the originating worker's step.
    joined = []
    for s in spans:
        if s["name"] == "store.apply" and s.get("role") == "server":
            chain = _ancestor_chain(s, by_id)
            if chain[-1] == "worker.step":
                joined.append({
                    "apply_span_id": s["span_id"],
                    "trace_id": s["trace_id"],
                    "ancestor_chain": chain,
                    "originating_step": by_id[
                        _root_of(s, by_id)]["attrs"],
                })
    assert joined, "no server apply span joined a worker step"

    assembled = assemble_traces(spans)
    save_chrome_trace(spans, os.path.join(out_dir,
                                          "sync_trace.perfetto.json"))
    record = {
        "scenario": "multi-process sync serve + 2 workers, traced",
        "processes": sorted(
            {f"{s.get('role')}:{s.get('pid')}" for s in spans}),
        "dump_files": [os.path.basename(p) for p in dumps],
        "span_count": len(spans),
        "trace_count": len(assembled["traces"]),
        "orphan_spans": assembled["orphan_spans"],
        "server_apply_joined_to_worker_step": joined[:5],
        "example_trace_tree": _tree_summary(next(
            t for t in assembled["traces"]
            if t["trace_id"] == joined[0]["trace_id"])),
    }
    with open(os.path.join(out_dir, "sync_trace_tree.json"), "w") as f:
        json.dump(record, f, indent=2)
    print(f"[sync] ok: {len(spans)} spans from {len(dumps)} dumps, "
          f"{len(joined)} server-apply spans parented to worker steps",
          file=sys.stderr)


def _root_of(span: dict, by_id: dict) -> str:
    node = span
    while by_id.get(node.get("parent_id")) is not None:
        node = by_id[node["parent_id"]]
    return node["span_id"]


def _tree_summary(trace: dict) -> dict:
    def node(n):
        out = {"name": n["name"], "role": n.get("role"),
               "dur_ms": round(n.get("dur", 0.0) * 1e3, 3)}
        if n.get("attrs"):
            out["attrs"] = n["attrs"]
        if n.get("children"):
            out["children"] = [node(c) for c in n["children"]]
        return out

    return {"trace_id": trace["trace_id"],
            "span_count": trace["span_count"],
            "roots": [node(r) for r in trace["roots"]]}


# -- (b) async straggler: injected slow fetch + critical-path report ---------

class _SlowFetchStore:
    """Per-worker store wrapper injecting one-way fetch latency — the
    straggler-injection technique of run_overlap_probe.py (sleeps release
    the GIL exactly like a blocking socket read would)."""

    def __init__(self, inner, delay_s: float):
        self._inner = inner
        self._delay_s = delay_s

    def fetch(self, *a, **kw):
        time.sleep(self._delay_s)
        return self._inner.fetch(*a, **kw)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def run_async_straggler(out_dir: str, delay_s: float = 3.0) -> None:
    import jax
    import numpy as np

    from distributed_parameter_server_for_ml_training_tpu import (
        telemetry as T)
    from distributed_parameter_server_for_ml_training_tpu.analysis import (
        critical_path_report, save_chrome_trace)
    from distributed_parameter_server_for_ml_training_tpu.data import (
        synthetic_cifar100)
    from distributed_parameter_server_for_ml_training_tpu.models import (
        get_model)
    from distributed_parameter_server_for_ml_training_tpu.ps.store import (
        ParameterStore, StoreConfig)
    from distributed_parameter_server_for_ml_training_tpu.ps.worker import (
        PSWorker, WorkerConfig)
    from distributed_parameter_server_for_ml_training_tpu.train.steps \
        import make_eval_step, make_grad_step
    from distributed_parameter_server_for_ml_training_tpu.utils.pytree \
        import flatten_params

    rec = T.enable_tracing(buffer=8192, role="trainer")
    rec.clear()

    ds = synthetic_cifar100()
    ds.x_train, ds.y_train = ds.x_train[:256], ds.y_train[:256]
    ds.x_test, ds.y_test = ds.x_test[:64], ds.y_test[:64]
    model = get_model("vit_tiny", num_classes=ds.num_classes,
                      image_size=32)
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 32, 32, 3), np.float32),
                           train=False)
    store = ParameterStore(
        flatten_params(variables["params"]),
        StoreConfig(mode="async", total_workers=2, staleness_bound=32))
    cfg = WorkerConfig(batch_size=32, num_epochs=2, augment=False,
                       eval_each_epoch=False)
    grad_step = make_grad_step(model, augment=False)
    eval_step = jax.jit(make_eval_step())
    slow = PSWorker(_SlowFetchStore(store, delay_s), model, ds, cfg,
                    grad_step=grad_step, eval_step=eval_step,
                    worker_name="slow-w0")
    fast = PSWorker(store, model, ds, cfg, grad_step=grad_step,
                    eval_step=eval_step, worker_name="fast-w1")
    slow.start()
    time.sleep(0.1)  # deterministic id order: slow registers first
    fast.start()
    slow.join(600)
    fast.join(600)
    T.disable_tracing()
    for w in (slow, fast):
        if w.result.error is not None:
            raise w.result.error

    spans = rec.tail()
    report = critical_path_report(spans, top=10_000)
    # The straggler we injected: slowest fetch-wait-dominant step.
    fetch_bound = [e for e in report["stragglers"]
                   if e["dominant_phase"] == "fetch_wait"]
    assert fetch_bound, report["by_dominant_phase"]
    straggler = fetch_bound[0]
    assert straggler["coverage"] >= 0.95, straggler
    assert straggler["phases_s"]["fetch_wait"] >= delay_s * 0.9, straggler
    staleness_steps = [e for e in report["stragglers"]
                       if e.get("staleness") is not None]

    save_chrome_trace(spans, os.path.join(out_dir,
                                          "async_trace.perfetto.json"))
    record = {
        "scenario": f"in-process async, 2 workers, worker 0 fetches "
                    f"delayed {delay_s * 1e3:.0f} ms (injected straggler)",
        "injected_fetch_delay_s": delay_s,
        "steps_attributed": report["steps"],
        "by_dominant_phase": report["by_dominant_phase"],
        "phase_totals_s": report["phase_totals_s"],
        "straggler": straggler,
        "straggler_note": "coverage = attributed phase time / step wall "
                          "time; the acceptance bar is >= 0.95",
        "staleness_attributed_examples": staleness_steps[:3],
        "stragglers_top": report["stragglers"][:12],
        "worker_results": {
            "slow-w0": {"steps": slow.result.local_steps_completed,
                        "accepted": slow.result.pushes_accepted,
                        "rejected": slow.result.pushes_rejected},
            "fast-w1": {"steps": fast.result.local_steps_completed,
                        "accepted": fast.result.pushes_accepted,
                        "rejected": fast.result.pushes_rejected},
        },
    }
    with open(os.path.join(out_dir, "async_straggler_report.json"),
              "w") as f:
        json.dump(record, f, indent=2)
    print(f"[async] ok: straggler coverage={straggler['coverage']}, "
          f"dominant={straggler['dominant_phase']}, "
          f"fetch_wait={straggler['phases_s']['fetch_wait']:.3f}s of "
          f"wall={straggler['wall_s']:.3f}s", file=sys.stderr)


# -- (c) SIGTERM post-mortem --------------------------------------------------

def run_sigterm_postmortem(out_dir: str) -> None:
    raw_dir = os.path.join(out_dir, "raw_sigterm")
    os.makedirs(raw_dir, exist_ok=True)
    mport = _free_port()
    cmd = CLI + [
        "train", "--mode", "async", "--workers", "2", "--model",
        "vit_tiny", "--synthetic", "--num-train", "4096", "--num-test",
        "64", "--epochs", "50", "--batch-size", "32", "--platform", "cpu",
        "--dtype", "float32", "--no-augment",
        "--trace", "--trace-buffer", "4096", "--trace-dump-dir", raw_dir,
        "--metrics-port", str(mport),
        "--telemetry", "--telemetry-interval", "2.0"]
    print(f"[sigterm] {' '.join(cmd)}", file=sys.stderr)
    proc = subprocess.Popen(cmd, cwd=REPO, env=_env(),
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE)
    live = None
    try:
        deadline = time.time() + 600
        while time.time() < deadline:
            if proc.poll() is not None:
                out, err = proc.communicate()
                sys.stderr.write(err.decode(errors="replace")[-3000:])
                raise SystemExit("sigterm demo run exited early")
            try:
                body = json.loads(urlopen(
                    f"http://127.0.0.1:{mport}/debug/trace",
                    timeout=2).read())
                if sum(1 for s in body.get("spans", [])
                       if s["name"] == "worker.step") >= 8:
                    live = body
                    break
            except OSError:
                pass
            time.sleep(0.5)
        assert live is not None, "never scraped a live trace with steps"
        proc.send_signal(signal.SIGTERM)
        out, err = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()

    dump_path = os.path.join(raw_dir,
                             f"trace-trainer-{proc.pid}-sigterm.json")
    assert os.path.exists(dump_path), os.listdir(raw_dir)
    with open(dump_path) as f:
        dump = json.load(f)
    live_ids = {s["span_id"] for s in live["spans"]}
    dump_ids = {s["span_id"] for s in dump["spans"]}
    overlap = live_ids & dump_ids
    # The post-mortem's tail must contain the trace that was live just
    # before the kill (the buffer is far larger than the run's span
    # count, so nothing was evicted in between).
    assert len(overlap) >= 0.9 * len(live_ids), (len(overlap),
                                                 len(live_ids))
    final_snaps = [ln for ln in out.decode(errors="replace").splitlines()
                   if "METRICS_JSON" in ln and '"kind": "snapshot"' in ln]
    record = {
        "scenario": "cli train --mode async TERM'd mid-run",
        "rc": proc.returncode,
        "rc_note": "143 = 128 + SIGTERM via the shutdown handler "
                   "(dump + final snapshot ran instead of a silent kill)",
        "live_scrape_spans": len(live_ids),
        "sigterm_dump_spans": len(dump_ids),
        "live_spans_found_in_dump": len(overlap),
        "dump_reason": dump["reason"],
        "dump_file": os.path.basename(dump_path),
        "final_snapshot_flushed_on_sigterm": bool(final_snaps),
        "dump_tail_example": dump["spans"][-6:],
    }
    assert proc.returncode == 143, proc.returncode
    assert dump["reason"] == "sigterm"
    assert final_snaps, "snapshot emitter tail was dropped"
    with open(os.path.join(out_dir, "sigterm_postmortem.json"), "w") as f:
        json.dump(record, f, indent=2)
    print(f"[sigterm] ok: rc=143, {len(overlap)}/{len(live_ids)} live "
          f"spans present in the post-mortem dump", file=sys.stderr)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir",
                    default=os.path.join(REPO, "experiments", "results",
                                         "trace"))
    ap.add_argument("--skip-sync", action="store_true")
    ap.add_argument("--skip-async", action="store_true")
    ap.add_argument("--skip-sigterm", action="store_true")
    args = ap.parse_args()
    os.makedirs(args.out_dir, exist_ok=True)

    if not args.skip_sync:
        run_sync_tree(args.out_dir)
    if not args.skip_async:
        run_async_straggler(args.out_dir)
    if not args.skip_sigterm:
        run_sigterm_postmortem(args.out_dir)
    print(f"artifacts in {args.out_dir}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
