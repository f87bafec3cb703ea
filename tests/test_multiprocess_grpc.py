"""True multi-process gRPC: `cli serve` and `cli worker` as separate OS
processes over localhost — the reference's multi-machine topology
(terraform/main.tf:387-435, worker -> NLB -> server) minus the NLB.

The in-process gRPC tests (test_comms.py) exercise the wire format and the
4-RPC protocol; this one proves the actual CLI entry points interoperate
across process boundaries end-to-end: register -> fetch/push epochs ->
JobFinished -> server exits cleanly and emits METRICS_JSON.
"""

import json
import os
import re
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _cli_env():
    return dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONUNBUFFERED="1",
    )


CLI = [sys.executable, "-m",
       "distributed_parameter_server_for_ml_training_tpu.cli"]


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["python", "native"])
def test_sync_round_semantics_across_processes(backend):
    """Round-4 VERDICT missing 1: sync mode had never crossed a process
    boundary. A real `cli serve --mode sync` + a worker OS process + an
    in-test gRPC client assert the round semantics over real sockets:

    - quirk 2 (server.py:286-288): PushReply returns BEFORE the round
      completes — a lone pushing worker runs to completion while the
      other registered worker never pushes;
    - quirk 3 (server.py:267-268): the lone worker's pushes complete
      rounds by COUNT (2 pushes from ONE distinct worker -> 1 round);
    - rounds otherwise complete at N pushes (2 observer pushes -> +1 step);
    - per-worker METRICS_JSON rows aggregate across the boundary.
    """
    if backend == "native":
        from distributed_parameter_server_for_ml_training_tpu.native import (
            bindings)
        if not bindings.native_available():
            pytest.skip("libps_core.so not built and no toolchain")
    import numpy as np

    from distributed_parameter_server_for_ml_training_tpu.comms.client import (
        RemoteStore)

    port = _free_port()
    server = subprocess.Popen(
        CLI + ["serve", "--mode", "sync", "--workers", "2",
               "--port", str(port), "--model", "vit_tiny",
               "--num-classes", "100", "--image-size", "32",
               "--store-backend", backend,
               "--platform", "cpu", "--emit-metrics"],
        cwd=REPO, env=_cli_env(),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    worker = None
    observer = None
    try:
        # Observer client: takes slot 0, keeps the server alive after the
        # subprocess worker finishes, and gives the test a wire-level probe.
        observer = RemoteStore(f"localhost:{port}", register_retries=8)
        obs_id, total = observer.register_worker("observer")
        assert (obs_id, total) == (0, 2)
        assert observer.config.mode == "sync"

        # Worker subprocess: id 1 -> half of 128 synthetic images = 2
        # batches = 2 pushes, all with the observer never pushing.
        worker = subprocess.Popen(
            CLI + ["worker", "--server", f"localhost:{port}",
                   "--worker-name", "sync-proc-w1", "--model", "vit_tiny",
                   "--synthetic", "--num-train", "128", "--num-test", "32",
                   "--epochs", "1", "--batch-size", "32",
                   "--platform", "cpu", "--dtype", "float32",
                   "--no-augment", "--emit-metrics"],
            cwd=REPO, env=_cli_env(),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        w_out, _ = worker.communicate(timeout=540)
        w_text = w_out.decode(errors="replace")
        # Quirk 2 over the wire: the worker ran to completion (both its
        # PushGradrients replies arrived) though worker 0 never pushed.
        assert worker.returncode == 0, w_text

        # Quirk 3 over the wire: its 2 pushes completed ONE round by count.
        params, step = observer.fetch(obs_id)
        assert step == 1, step

        # A round at N distinct pushes: two observer pushes -> one round,
        # and the FIRST push's reply returns while the round is incomplete
        # (the fetch between them observes an unchanged step).
        zeros = {k: np.zeros_like(v) for k, v in params.items()}
        assert observer.push(obs_id, zeros, fetched_step=step)
        _, mid = observer.fetch(obs_id)
        assert mid == 1, mid
        assert observer.push(obs_id, zeros, fetched_step=step)
        _, after = observer.fetch(obs_id)
        assert after == 2, after

        observer.job_finished(obs_id)
        observer.close()
        observer = None
        s_out, _ = server.communicate(timeout=120)
    finally:
        if observer is not None:
            observer.close()
        for p in (server, worker):
            if p is not None and p.poll() is None:
                p.kill()

    s_text = s_out.decode(errors="replace")
    assert server.returncode == 0, s_text
    sm = json.loads(re.search(r"METRICS_JSON:\s*(\{.*\})", s_text).group(1))
    wm = json.loads(re.search(r"METRICS_JSON:\s*(\{.*\})", w_text).group(1))
    assert sm["mode"] == "sync"
    assert sm["gradients_processed"] == 4      # 2 worker + 2 observer
    assert sm["global_steps_completed"] == 2   # = pushes // N
    assert wm["worker_id"] == 1
    assert wm["local_steps_completed"] == 2


@pytest.mark.slow
def test_sync_two_worker_processes_concurrent():
    """The convoy regime: two worker OS processes push sync rounds into one
    server over real sockets concurrently. Round accounting is
    deterministic under ANY interleaving (pushes serialize on the
    server's sync lock): 4 total pushes -> 2 rounds."""
    port = _free_port()
    server = subprocess.Popen(
        CLI + ["serve", "--mode", "sync", "--workers", "2",
               "--port", str(port), "--model", "vit_tiny",
               "--num-classes", "100", "--image-size", "32",
               "--platform", "cpu", "--emit-metrics"],
        cwd=REPO, env=_cli_env(),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    workers = []
    try:
        for i in range(2):
            workers.append(subprocess.Popen(
                CLI + ["worker", "--server", f"localhost:{port}",
                       "--worker-name", f"sync-conc-w{i}",
                       "--model", "vit_tiny", "--synthetic",
                       "--num-train", "128", "--num-test", "32",
                       "--epochs", "1", "--batch-size", "32",
                       "--platform", "cpu", "--dtype", "float32",
                       "--no-augment", "--emit-metrics"],
                cwd=REPO, env=_cli_env(),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
        w_texts = []
        for w in workers:
            out, _ = w.communicate(timeout=540)
            w_texts.append(out.decode(errors="replace"))
            assert w.returncode == 0, w_texts[-1][-2000:]
        s_out, _ = server.communicate(timeout=120)
    finally:
        for p in [server] + workers:
            if p.poll() is None:
                p.kill()

    s_text = s_out.decode(errors="replace")
    assert server.returncode == 0, s_text
    sm = json.loads(re.search(r"METRICS_JSON:\s*(\{.*\})", s_text).group(1))
    assert sm["mode"] == "sync"
    assert sm["gradients_processed"] == 4
    assert sm["global_steps_completed"] == 2
    rows = [json.loads(re.search(r"METRICS_JSON:\s*(\{.*\})", t).group(1))
            for t in w_texts]
    assert sorted(r["worker_id"] for r in rows) == [0, 1]
    assert all(r["local_steps_completed"] == 2 for r in rows)


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["python", "native"])
def test_serve_and_worker_processes(backend):
    if backend == "native":
        from distributed_parameter_server_for_ml_training_tpu.native import (
            bindings)
        if not bindings.native_available():
            pytest.skip("libps_core.so not built and no toolchain")
    port = _free_port()
    server = subprocess.Popen(
        CLI + ["serve", "--mode", "async", "--workers", "1",
               "--port", str(port), "--model", "vit_tiny",
               "--num-classes", "100", "--image-size", "32",
               "--store-backend", backend,
               "--platform", "cpu", "--emit-metrics"],
        cwd=REPO, env=_cli_env(),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    worker = None
    try:
        worker = subprocess.Popen(
            CLI + ["worker", "--server", f"localhost:{port}",
                   "--worker-name", "proc-w0", "--model", "vit_tiny",
                   "--synthetic", "--num-train", "64", "--num-test", "32",
                   "--epochs", "1", "--batch-size", "32",
                   "--platform", "cpu", "--dtype", "float32",
                   "--no-augment", "--emit-metrics"],
            cwd=REPO, env=_cli_env(),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        # Generous: two cold jit compiles on a potentially shared/slow CPU.
        w_out, _ = worker.communicate(timeout=540)
        # Server exits on its own once all registered workers JobFinished.
        s_out, _ = server.communicate(timeout=120)
    finally:
        for p in (server, worker):
            if p is not None and p.poll() is None:
                p.kill()

    w_text = w_out.decode(errors="replace")
    s_text = s_out.decode(errors="replace")
    assert worker.returncode == 0, w_text
    assert server.returncode == 0, s_text

    # Both ends emitted the reference's METRICS_JSON convention
    # (server.py:367, worker.py:435; parsed like parse_cloudwatch_logs).
    sm = json.loads(re.search(r"METRICS_JSON:\s*(\{.*\})", s_text).group(1))
    wm = json.loads(re.search(r"METRICS_JSON:\s*(\{.*\})", w_text).group(1))
    assert sm["mode"] == "async"
    assert sm["global_steps_completed"] == 2   # 64 imgs / batch 32
    assert sm["gradients_processed"] == 2
    assert wm["local_steps_completed"] == 2
    assert wm["worker_id"] == 0
