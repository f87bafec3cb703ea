"""Recorded two-job tenancy soak: isolation, QoS, and worker autoscaling.

The multi-job tenancy subsystem's acceptance artifact (ISSUE 15,
docs/TENANCY.md), written to ``experiments/results/tenancy/``:

**Phase 0 — solo control.** A pre-tenancy server subprocess plus one
PSWorker records the control accuracy curve for the tiny-ResNet run.

**Phase 1 — parity under neighbor chaos.** ONE tenancy server subprocess
(JobManager + weighted-fair QoS + ClusterMonitor + per-job checkpoint
lineages + the real ``/metrics`` HTTP endpoint serving ``GET /cluster``
— the same wiring ``cli serve --jobs`` assembles). Job B trains the same
model from the same seed as the control while job A takes concurrent
chaos: a push storm whose exactly-once tokens carry a leak-sentinel
string, a NaN gradient that poisons job A's params in place, and a
worker-child subprocess SIGKILLed mid-run (the reaper must expire it).
Job B's accuracy curve must match the control EXACTLY and its params
must stay finite — the poison landed, and stayed, in job A's namespace.

**Phase 2 — autoscale under load.** A real WorkerSupervisor spawns
fetch-loop worker children for job B; a real WorkerAutoscaler polls the
server's ``GET /cluster`` jobs block for admission-queue pressure while
the fetch load generator hammers job B with concurrency far above its
``max_inflight``. The scaler must grow (>= 1 ``worker_grow``, the grown
children visible as registered members in ``/cluster``) and, once the
storm ends, shrink back to the floor (>= 1 ``worker_shrink``).

**Leakage audit.** After SIGTERM (checkpoint flush through the shutdown
path), every byte of job B's and the default job's checkpoint lineage is
scanned for the sentinel: it must appear in job A's journal and NOWHERE
else — zero cross-job leakage, byte-verified.

Run: JAX_PLATFORMS=cpu python experiments/run_tenancy_demo.py [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
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

OUT_DIR = os.path.join(REPO, "experiments", "results", "tenancy")
#: The cross-job leak marker: seeded as the chaos client's push-token
#: nonce, so every storm push's exactly-once token carries it into job
#: A's dedupe journal — and, at flush, into job A's checkpoint lineage
#: and nobody else's (the byte scan at the end is the proof).
SENTINEL = "LEAKSENTRYJOBA"
JOBS_SPEC = ("joba:weight=1,max_inflight=4;"
             "jobb:weight=2,max_inflight=2,min_workers=1,max_workers=3")


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
    """One parameter-server life. With ``--jobs`` this is the tenancy
    stack ``cli serve --jobs`` wires: JobManager (per-job stores, strided
    worker ids), ParameterService with weighted-fair admission,
    ClusterMonitor feeding ``GET /cluster``, a real metrics HTTP
    endpoint, and one checkpoint lineage PER JOB (each journaling only
    its own tenant's push tokens). Without ``--jobs`` it is the plain
    pre-tenancy server (the control)."""
    import functools

    from distributed_parameter_server_for_ml_training_tpu.checkpoint \
        import PeriodicStoreCheckpointer
    from distributed_parameter_server_for_ml_training_tpu.comms import (
        ParameterService, serve)
    from distributed_parameter_server_for_ml_training_tpu.ps import (
        ParameterStore, StoreConfig)
    from distributed_parameter_server_for_ml_training_tpu.ps.tenancy \
        import DEFAULT_JOB, JobManager, parse_jobs_spec
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        ClusterMonitor, HealthThresholds, add_shutdown_flush,
        install_shutdown_hooks, set_cluster_monitor, start_metrics_server)

    _, flat = _build_model_and_params()
    store = ParameterStore(flat, StoreConfig(
        mode="async", total_workers=1, learning_rate=0.05,
        staleness_bound=10, elastic=True,
        worker_timeout=args.worker_timeout, push_codec="none"))
    jobs = None
    if args.jobs:
        jobs = JobManager(store, parse_jobs_spec(args.jobs))
    monitor = ClusterMonitor(
        store,
        HealthThresholds(dead_after_s=max(2.0, args.worker_timeout),
                         straggler_lag_steps=100_000),
        interval=0.5)
    set_cluster_monitor(monitor)
    monitor.start()
    if jobs is not None:
        monitor.jobs = jobs
    svc = ParameterService(store, monitor=monitor, jobs=jobs)
    if args.serve_cost > 0:
        # Synthetic per-fetch serve cost, held INSIDE the admission slot
        # (the tiny demo model's encode path is near-free; a production
        # model's is not). This is what lets the weighted-fair queue
        # actually build under the phase-2 load storm — the admission
        # math under test is real, only the handler occupancy is
        # simulated.
        inner_fetch = svc._fetch_body

        def slow_fetch_body(meta, job, store_, lwid):
            time.sleep(args.serve_cost)
            return inner_fetch(meta, job, store_, lwid)

        svc._fetch_body = slow_fetch_body
    ckpts = []
    if args.ckpt_dir:
        primary_journal = (svc.journal_snapshot if jobs is None
                           else functools.partial(svc.journal_snapshot,
                                                  job=DEFAULT_JOB))
        ckpts.append(PeriodicStoreCheckpointer(
            store, args.ckpt_dir, interval=args.ckpt_interval,
            journal_fn=primary_journal))
        if jobs is not None:
            for jname in jobs.names():
                if jname == DEFAULT_JOB:
                    continue
                ckpts.append(PeriodicStoreCheckpointer(
                    jobs.store_for(jname),
                    os.path.join(args.ckpt_dir, f"job-{jname}"),
                    interval=args.ckpt_interval,
                    journal_fn=functools.partial(svc.journal_snapshot,
                                                 job=jname)))
        for c in ckpts:
            c.start()
    # SIGTERM drains every lineage's end state through the telemetry
    # shutdown path — the parent's kill at the end of the soak is what
    # makes the leakage byte-scan read FINAL journals, not stale ones.
    install_shutdown_hooks(role="server")
    for c in ckpts:
        add_shutdown_flush(c.flush_now)
    _http, mport = start_metrics_server(port=args.metrics_port)
    server, port = serve(store, port=args.port, service=svc)
    print(f"TENANCY_SERVER_READY port={port} metrics={mport}", flush=True)
    lifetime_deadline = time.time() + args.max_lifetime
    while not store.wait_all_finished(timeout=0.5):
        if jobs is not None:
            jobs.expire_stale_workers()
        else:
            store.expire_stale_workers()
        if time.time() > lifetime_deadline:
            print("TENANCY_SERVER_LIFETIME_EXCEEDED", flush=True)
            break
    time.sleep(0.3)
    server.stop(grace=1.0)
    for c in ckpts:
        c.stop(final_snapshot=True)
    monitor.stop()
    print("TENANCY_SERVER_EXIT " + json.dumps({
        "global_step": store.global_step,
        "gradients_processed": store.stats.gradients_processed,
    }), flush=True)
    return 0


# -- worker child (supervisor-spawned fetch loop / kill victim) ---------------

def worker_child(args) -> int:
    """A registered fetch-loop worker for one job: what the supervisor's
    elastic slots spawn in phase 2 (and what phase 1 SIGKILLs). Liveness
    comes from the fetches; it runs until its lifetime guard or a
    supervisor SIGTERM."""
    from distributed_parameter_server_for_ml_training_tpu.comms import (
        RemoteStore)
    rs = RemoteStore(f"localhost:{args.server_port}", rpc_timeout=10.0,
                     rpc_retries=2, rpc_backoff=0.1, job=args.job or None)
    wid, _total = rs.register_worker(args.worker_name)
    print(f"TENANCY_WORKER_REGISTERED wid={wid} job={rs.job}", flush=True)
    deadline = time.time() + args.max_lifetime
    while time.time() < deadline:
        try:
            rs.fetch(worker_id=wid)
        except Exception:  # throttled/expired past retries: keep looping
            pass
        time.sleep(0.25)
    rs.close()
    return 0


# -- parent-side orchestration ------------------------------------------------

def _spawn_server(out_dir, tag, *, jobs="", ckpt_dir="", worker_timeout,
                  ckpt_interval=1.0, serve_cost=0.0):
    """Start a server child, poll its log for READY, and return
    (proc, log_path, grpc_port, metrics_port) — both ports are
    OS-assigned and parsed back from the READY line."""
    log_path = os.path.join(out_dir, f"{tag}.log")
    log = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--server-child",
         "--jobs", jobs, "--ckpt-dir", ckpt_dir,
         "--worker-timeout", str(worker_timeout),
         "--ckpt-interval", str(ckpt_interval),
         "--serve-cost", str(serve_cost)],
        stdout=log, stderr=subprocess.STDOUT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO)
    deadline = time.time() + 120
    while time.time() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(f"server {tag} died at startup; see "
                               f"{log_path}")
        with open(log_path) as f:
            for line in f:
                if line.startswith("TENANCY_SERVER_READY"):
                    fields = dict(p.split("=") for p in line.split()[1:])
                    return (proc, log_path, int(fields["port"]),
                            int(fields["metrics"]))
        time.sleep(0.1)
    raise RuntimeError(f"server {tag} never came up; see {log_path}")


def _server_exit_stats(log_path) -> dict:
    with open(log_path) as f:
        for line in f:
            if line.startswith("TENANCY_SERVER_EXIT "):
                return json.loads(line[len("TENANCY_SERVER_EXIT "):])
    return {}


def _cluster_view(mport) -> dict:
    from urllib.request import urlopen
    raw = urlopen(f"http://127.0.0.1:{mport}/cluster", timeout=5).read()
    return json.loads(raw)


def _metrics_text(mport) -> str:
    from urllib.request import urlopen
    return urlopen(f"http://127.0.0.1:{mport}/metrics",
                   timeout=5).read().decode()


def _run_training_worker(model, ds, *, port, job, epochs, batch, name,
                         grad_step, eval_step):
    """One PSWorker against the server at ``port``, optionally inside a
    job's namespace. Returns the worker result (accuracy curve etc.)."""
    from distributed_parameter_server_for_ml_training_tpu.comms import (
        RemoteStore)
    from distributed_parameter_server_for_ml_training_tpu.ps import (
        PSWorker, WorkerConfig)
    c = RemoteStore(f"localhost:{port}", rpc_timeout=15.0, rpc_retries=2,
                    rpc_backoff=0.1, job=job)
    try:
        cfg = WorkerConfig(batch_size=batch, num_epochs=epochs,
                           sync_steps=1, augment=False,
                           heartbeat_interval=1.0,
                           reconnect_timeout=60.0, reconnect_backoff=0.1)
        w = PSWorker(c, model, ds, cfg, grad_step=grad_step,
                     eval_step=eval_step, worker_name=name)
        w.start()
        w.join(timeout=600)
    finally:
        c.close()
    if w.result.error is not None:
        raise RuntimeError(f"{name} failed") from w.result.error
    return w.result


def _joba_chaos(port, *, pushes, nan_at):
    """Job A's bad day, driven from one registered chaos client: a push
    storm whose tokens all carry the leak sentinel, with one NaN
    gradient in the middle. Zero-valued gradients elsewhere keep job A's
    params constant until the poison turns them NaN — which must never
    show up in job B (the parity check runs concurrently)."""
    from distributed_parameter_server_for_ml_training_tpu.comms import (
        RemoteStore)
    rs = RemoteStore(f"localhost:{port}", rpc_timeout=10.0, rpc_retries=2,
                     rpc_backoff=0.1, job="joba")
    rs._push_nonce = SENTINEL  # every storm token now carries the marker
    out = {"sent": 0, "accepted": 0, "errors": []}
    wid, _ = rs.register_worker("storm-a")
    out["wid"] = wid
    params, step = rs.fetch(worker_id=wid)
    zero = {k: np.zeros_like(v) for k, v in params.items()}
    poison = {k: np.full_like(v, np.nan) for k, v in params.items()}
    for i in range(pushes):
        grads = poison if i == nan_at else zero
        out["sent"] += 1
        try:
            if rs.push(wid, grads, step):
                out["accepted"] += 1
        except Exception as e:
            out["errors"].append(repr(e))
        try:
            params, step = rs.fetch(worker_id=wid)
        except Exception as e:
            out["errors"].append(repr(e))
    out["params_nonfinite_after"] = bool(any(
        not np.all(np.isfinite(np.asarray(v, np.float32)))
        for v in params.values()))
    rs.close()
    return out


def _fetch_job_params(port, job):
    from distributed_parameter_server_for_ml_training_tpu.comms import (
        RemoteStore)
    rs = RemoteStore(f"localhost:{port}", rpc_timeout=10.0, rpc_retries=2,
                     rpc_backoff=0.1, job=job)
    try:
        # The job label is capability-gated on the registration
        # handshake — an unregistered probe would read the DEFAULT job.
        wid, _ = rs.register_worker(f"probe-{job}")
        params, step = rs.fetch(worker_id=wid)
        return params, step
    finally:
        rs.close()


def _spawn_kill_victim(out_dir, port):
    log_path = os.path.join(out_dir, "kill_victim.log")
    log = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker-child",
         "--server-port", str(port), "--job", "joba",
         "--worker-name", "victim-a", "--max-lifetime", "120"],
        stdout=log, stderr=subprocess.STDOUT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO)
    deadline = time.time() + 60
    while time.time() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(f"kill victim died early; see {log_path}")
        with open(log_path) as f:
            for line in f:
                if line.startswith("TENANCY_WORKER_REGISTERED"):
                    fields = dict(p.split("=") for p in line.split()[1:])
                    return proc, int(fields["wid"])
        time.sleep(0.1)
    raise RuntimeError(f"kill victim never registered; see {log_path}")


def _wait_worker_gone(mport, job, wid, timeout=30.0) -> bool:
    deadline = time.time() + timeout
    while time.time() < deadline:
        row = (_cluster_view(mport).get("jobs") or {}).get(job) or {}
        if wid not in (row.get("workers") or []):
            return True
        time.sleep(0.5)
    return False


def _run_autoscale_phase(port, mport, out_dir, *, storm_s, settle_s):
    """Phase 2: a real WorkerSupervisor (elastic slots spawning
    ``--worker-child`` fetch loops for job B) actuated by a real
    WorkerAutoscaler whose pressure_fn polls the server's live
    ``GET /cluster`` jobs block, while the fetch load generator hammers
    job B with concurrency far above its max_inflight=2."""
    from distributed_parameter_server_for_ml_training_tpu.comms.loadgen \
        import run_loadgen
    from distributed_parameter_server_for_ml_training_tpu.ps.supervisor \
        import SupervisorConfig, WorkerSupervisor
    from distributed_parameter_server_for_ml_training_tpu.telemetry. \
        remediation import WorkerAutoscalePolicy, WorkerAutoscaler

    row0 = (_cluster_view(mport).get("jobs") or {}).get("jobb") or {}
    members_start = len(row0.get("workers") or [])

    def argv_for(slot: int, attempt: int):
        return [sys.executable, os.path.abspath(__file__),
                "--worker-child", "--server-port", str(port),
                "--job", "jobb", "--worker-name",
                f"scale-{slot}-{attempt}", "--max-lifetime", "120"]

    sup = WorkerSupervisor(argv_for, 1, SupervisorConfig(
        respawn=True, backoff_initial=0.2, backoff_max=1.0,
        healthy_after=1.0, crash_loop_after=5, graceful_timeout=3.0))
    sup.start()
    run_t = threading.Thread(target=sup.run, daemon=True,
                             name="demo-supervisor")
    run_t.start()

    def pressure() -> dict:
        row = (_cluster_view(mport).get("jobs") or {}).get("jobb") or {}
        return {"queue_depth": row.get("waiting") or 0,
                "stragglers": 0,
                "workers": len(row.get("workers") or [])}

    scaler = WorkerAutoscaler(
        "jobb", pressure, supervisor=sup,
        policy=WorkerAutoscalePolicy(depth_high=4.0, depth_low=1.0,
                                     sustain_ticks=2, min_workers=1,
                                     max_workers=3, cooldown_s=2.0))
    lg_result: dict = {}

    def _storm():
        lg_result.update(run_loadgen(
            [f"localhost:{port}"], duration_s=storm_s, concurrency=12,
            mode="full", rpc_timeout=10.0, job="jobb"))

    # Let the base slot's child come up and register before the storm —
    # the grown-members check below is measured against a settled floor.
    time.sleep(settle_s)
    storm_t = threading.Thread(target=_storm, daemon=True,
                               name="demo-loadgen")
    storm_t.start()
    samples = []
    max_members = 0
    max_slots = 0
    t0 = time.time()
    deadline = t0 + storm_s + 45.0
    while time.time() < deadline:
        event = scaler.tick()
        try:
            row = (_cluster_view(mport).get("jobs") or {}).get("jobb") or {}
        except Exception:
            row = {}
        members = len(row.get("workers") or [])
        max_members = max(max_members, members)
        max_slots = max(max_slots, sup.count())
        samples.append({"t": round(time.time() - t0, 2),
                        "waiting": row.get("waiting"),
                        "inflight": row.get("inflight"),
                        "slots": sup.count(), "members": members,
                        "event": event})
        if (not storm_t.is_alive() and sup.count() <= 1
                and scaler.actions["worker_shrink"] >= 1):
            break
        time.sleep(0.5)
    storm_t.join(timeout=60)
    while sup.remove_slot() is not None:  # retire the floor -> run() exits
        pass
    run_t.join(timeout=30)
    return {"members_start": members_start, "max_members": max_members,
            "max_slots": max_slots, "actions": dict(scaler.actions),
            "events": scaler.view()["events"], "samples": samples,
            "loadgen": lg_result}


def _scan_lineage_for_sentinel(ckpt_dir) -> dict:
    """Byte-scan every checkpoint file: which lineage dirs carry the
    sentinel? Keys are '<default>' for top-level files and the job-*
    subdir name otherwise."""
    marker = SENTINEL.encode()
    hits: dict[str, list[str]] = {}
    files_scanned = 0
    for root, _dirs, files in os.walk(ckpt_dir):
        rel_root = os.path.relpath(root, ckpt_dir)
        top = rel_root.split(os.sep)[0]
        lineage = "<default>" if top == "." else top
        for fname in files:
            files_scanned += 1
            path = os.path.join(root, fname)
            with open(path, "rb") as f:
                if marker in f.read():
                    hits.setdefault(lineage, []).append(
                        os.path.relpath(path, ckpt_dir))
    return {"files_scanned": files_scanned, "hits": hits}


def _metric_value(metrics_text, name, **labels) -> float | None:
    """Parse one sample out of the Prometheus text exposition."""
    want = None
    for line in metrics_text.splitlines():
        if not line.startswith(name):
            continue
        if labels:
            rendered = [f'{k}="{v}"' for k, v in labels.items()]
            if not all(r in line for r in rendered):
                continue
        try:
            want = float(line.rsplit(" ", 1)[1])
        except (ValueError, IndexError):
            continue
    return want


def run_demo(args) -> int:
    from distributed_parameter_server_for_ml_training_tpu.data import (
        synthetic_cifar100)
    from distributed_parameter_server_for_ml_training_tpu.train.steps \
        import make_eval_step, make_grad_step

    out_dir = args.out_dir
    os.makedirs(out_dir, exist_ok=True)
    quick = args.quick
    epochs = 1 if quick else 2
    n_train = 128 if quick else 256
    batch = 32
    storm_pushes = 16 if quick else 40
    loadgen_s = 8.0 if quick else 14.0
    worker_timeout = 6.0
    total_steps = epochs * (n_train // batch)

    model, _flat = _build_model_and_params()
    ds = synthetic_cifar100(n_train=n_train, n_test=64, num_classes=10,
                            seed=1)
    grad_step = make_grad_step(model, augment=False)
    eval_step = jax.jit(make_eval_step())
    summary: dict = {"quick": quick, "jobs_spec": JOBS_SPEC,
                     "sentinel": SENTINEL, "phases": {}}
    checks: list[tuple[str, bool, str]] = []

    # ---- Phase 0: solo control --------------------------------------------
    ctl_ckpt = os.path.join(out_dir, "ckpt_control")
    p_ctl, ctl_log, ctl_port, _ctl_mport = _spawn_server(
        out_dir, "control_server", ckpt_dir=ctl_ckpt,
        worker_timeout=worker_timeout)
    control = _run_training_worker(
        model, ds, port=ctl_port, job=None, epochs=epochs, batch=batch,
        name="control-0", grad_step=grad_step, eval_step=eval_step)
    p_ctl.wait(timeout=120)
    ctl_stats = _server_exit_stats(ctl_log)
    summary["phases"]["control"] = {
        "server": ctl_stats,
        "accuracy_curve": control.test_accuracies,
        "pushes_accepted": control.pushes_accepted}

    # ---- Phase 1: tenancy server, parity under neighbor chaos -------------
    ten_ckpt = os.path.join(out_dir, "ckpt_tenancy")
    p_ten, ten_log, port, mport = _spawn_server(
        out_dir, "tenancy_server", jobs=JOBS_SPEC, ckpt_dir=ten_ckpt,
        worker_timeout=worker_timeout, serve_cost=0.025)

    parity_holder: dict = {}

    def _parity():
        try:
            parity_holder["result"] = _run_training_worker(
                model, ds, port=port, job="jobb", epochs=epochs,
                batch=batch, name="parity-b", grad_step=grad_step,
                eval_step=eval_step)
        except Exception as e:
            parity_holder["error"] = repr(e)

    parity_t = threading.Thread(target=_parity, daemon=True,
                                name="demo-parity-b")
    parity_t.start()

    victim, victim_wid = _spawn_kill_victim(out_dir, port)
    storm = _joba_chaos(port, pushes=storm_pushes,
                        nan_at=storm_pushes // 3)
    victim.kill()  # SIGKILL: no goodbye — the reaper must notice
    victim.wait(timeout=30)
    victim_expired = _wait_worker_gone(mport, "joba", victim_wid,
                                       timeout=worker_timeout * 4)
    parity_t.join(timeout=600)
    if "result" not in parity_holder:
        raise RuntimeError(f"parity worker failed: "
                           f"{parity_holder.get('error', 'timeout')}")
    parity = parity_holder["result"]
    jobb_params, _ = _fetch_job_params(port, "jobb")
    jobb_finite = bool(all(
        np.all(np.isfinite(np.asarray(v, np.float32)))
        for v in jobb_params.values()))
    jobb_row = (_cluster_view(mport).get("jobs") or {}).get("jobb") or {}
    summary["phases"]["parity_under_chaos"] = {
        "accuracy_curve": parity.test_accuracies,
        "pushes_accepted": parity.pushes_accepted,
        "jobb_global_step": jobb_row.get("global_step"),
        "jobb_params_finite": jobb_finite,
        "storm": storm, "victim_wid": victim_wid,
        "victim_expired": victim_expired}

    checks += [
        ("control.completed",
         control.local_steps_completed == total_steps
         and ctl_stats.get("global_step") == control.pushes_accepted,
         f"{control.local_steps_completed}/{total_steps} steps, server "
         f"step {ctl_stats.get('global_step')}"),
        ("B.accuracy_parity_exact",
         np.allclose(control.test_accuracies, parity.test_accuracies,
                     atol=1e-12),
         f"control={control.test_accuracies} "
         f"jobb={parity.test_accuracies}"),
        ("B.step_parity",
         jobb_row.get("global_step") == ctl_stats.get("global_step"),
         f"jobb={jobb_row.get('global_step')} "
         f"control={ctl_stats.get('global_step')}"),
        ("B.params_finite_after_neighbor_nan", jobb_finite,
         "all job B tensors finite"),
        ("A.storm_applied_with_sentinel_tokens",
         storm["accepted"] == storm["sent"] and not storm["errors"],
         f"accepted={storm['accepted']}/{storm['sent']} "
         f"errors={len(storm['errors'])}"),
        ("A.nan_poison_landed_in_joba",
         storm["params_nonfinite_after"], "job A params went NaN"),
        ("A.killed_worker_expired", victim_expired,
         f"wid={victim_wid} reaped within {worker_timeout * 4:.0f}s"),
        ("server.survived_chaos", p_ten.poll() is None,
         "tenancy server still serving after phase 1"),
    ]

    # ---- Phase 2: autoscale under load ------------------------------------
    time.sleep(worker_timeout + 2.0)  # let phase-1 members expire out
    scale = _run_autoscale_phase(port, mport, out_dir,
                                 storm_s=loadgen_s, settle_s=6.0)
    summary["phases"]["autoscale"] = scale

    metrics_txt = _metrics_text(mport)
    with open(os.path.join(out_dir, "metrics_final.txt"), "w") as f:
        f.write(metrics_txt)
    final_view = _cluster_view(mport)
    with open(os.path.join(out_dir, "cluster_final.json"), "w") as f:
        json.dump(final_view, f, indent=2)
    admitted_a = _metric_value(metrics_txt, "dps_job_admitted_total",
                               job="joba")
    admitted_b = _metric_value(metrics_txt, "dps_job_admitted_total",
                               job="jobb")
    throttled_b = _metric_value(metrics_txt, "dps_job_throttled_total",
                                job="jobb")
    summary["qos"] = {
        "admitted_joba": admitted_a, "admitted_jobb": admitted_b,
        "throttled_jobb": throttled_b,
        "loadgen_jobs": scale["loadgen"].get("jobs")}
    peak_waiting = max((s["waiting"] or 0) for s in scale["samples"])
    lg_jobb = (scale["loadgen"].get("jobs") or {}).get("jobb") or {}

    checks += [
        ("qos.per_job_attribution",
         bool(admitted_a and admitted_a > 0
              and admitted_b and admitted_b > 0),
         f"admitted joba={admitted_a} jobb={admitted_b} "
         f"throttled_jobb={throttled_b}"),
        ("qos.pressure_observed_over_depth_high", peak_waiting > 4.0,
         f"peak jobb waiting={peak_waiting}"),
        ("qos.loadgen_per_job_latency_recorded",
         bool(lg_jobb.get("ok", 0) > 0
              and "p99" in (lg_jobb.get("latency_ms") or {})),
         f"jobb loadgen={lg_jobb}"),
        ("autoscale.grew", scale["actions"]["worker_grow"] >= 1,
         f"actions={scale['actions']}"),
        ("autoscale.shrank", scale["actions"]["worker_shrink"] >= 1,
         f"actions={scale['actions']}"),
        ("autoscale.grown_workers_in_cluster_view",
         scale["max_members"] >= scale["members_start"] + 2,
         f"members {scale['members_start']} -> max "
         f"{scale['max_members']} (slots max {scale['max_slots']})"),
    ]

    # ---- Teardown + leakage audit -----------------------------------------
    p_ten.send_signal(signal.SIGTERM)  # flush every lineage's journal
    p_ten.wait(timeout=60)
    scan = _scan_lineage_for_sentinel(ten_ckpt)
    summary["leakage_scan"] = scan
    leaked_into = sorted(k for k in scan["hits"] if k != "job-joba")
    checks += [
        ("leakage.sentinel_in_joba_lineage",
         bool(scan["hits"].get("job-joba")),
         f"hits={scan['hits'].get('job-joba')}"),
        ("leakage.zero_cross_job_bytes", not leaked_into,
         f"scanned {scan['files_scanned']} files; "
         f"foreign hits={leaked_into or 'none'}"),
    ]

    summary["checks"] = [
        {"name": n, "ok": bool(ok), "detail": d} for n, ok, d in checks]
    summary["ok"] = all(ok for _, ok, _ in checks)
    out_path = os.path.join(out_dir, "tenancy_demo.json")
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
    ap.add_argument("--metrics-port", type=int, default=0)
    ap.add_argument("--jobs", default="")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-interval", type=float, default=1.0)
    ap.add_argument("--worker-timeout", type=float, default=6.0)
    ap.add_argument("--serve-cost", type=float, default=0.0,
                    help="synthetic seconds of per-fetch handler cost "
                         "held inside the admission slot")
    ap.add_argument("--max-lifetime", type=float, default=600.0,
                    help="child self-destruct (orphan guard)")
    # internal: worker-child mode (fetch loop)
    ap.add_argument("--worker-child", action="store_true")
    ap.add_argument("--server-port", type=int, default=0)
    ap.add_argument("--job", default="")
    ap.add_argument("--worker-name", default="child")
    args = ap.parse_args()
    if args.server_child:
        return server_child(args)
    if args.worker_child:
        return worker_child(args)
    return run_demo(args)


if __name__ == "__main__":
    raise SystemExit(main())
