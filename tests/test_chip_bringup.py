"""What PR 21 (chip bring-up) added, as far as a CPU host can check it:
where the compile cache lives, that nothing mistakes this host for a TPU,
that ``chip_smoke.py``'s parent stays off JAX and fails without a chip, that
host-side CLI processes pin themselves to the CPU backend, and that every
trainer/worker METRICS_JSON row names the device it ran on."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "distributed_parameter_server_for_ml_training_tpu"


def _python(code: str, env: dict, cwd: str = REPO, timeout: float = 240):
    env = {**env, "PYTHONPATH": REPO}
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def _env_without(*names: str) -> dict:
    return {k: v for k, v in os.environ.items() if k not in names}


# -- compile cache -------------------------------------------------------------

def test_compile_cache_unset_env_uses_fixed_checkout_path(monkeypatch):
    from distributed_parameter_server_for_ml_training_tpu.utils import (
        compile_cache)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        used = compile_cache.enable_compile_cache()
        assert used == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == used
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_set_leaves_jax_config_alone():
    """With the variable set, JAX reads it itself; the function must not
    set anything in code."""
    proc = _python(f"""
        import jax
        from {PKG}.utils.compile_cache import enable_compile_cache
        before = jax.config.jax_compilation_cache_dir
        updates = []
        real = jax.config.update
        jax.config.update = lambda *a, **k: (updates.append(a), real(*a, **k))
        used = enable_compile_cache()
        assert used == before == jax.config.jax_compilation_cache_dir \\
            == "/some/outer/dir", (used, before)
        assert not updates, updates
        print("OK")
    """, {**os.environ, "JAX_COMPILATION_CACHE_DIR": "/some/outer/dir"})
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr[-2000:]


def test_no_cache_path_built_from_tempfile_pid_or_time():
    """A cache directory that moves never hits: the path is part of every
    entry's key."""
    offenders = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in (
            ".git", ".jax_cache", "chiprun_out", "__pycache__", "results")]
        for name in files:
            if not name.endswith(".py") or name == "test_chip_bringup.py":
                continue
            path = os.path.join(root, name)
            with open(path, errors="replace") as f:
                for i, line in enumerate(f, 1):
                    if "compilation_cache" in line.lower() and any(
                            w in line for w in ("tempfile", "mkdtemp",
                                                "getpid", "time.")):
                        offenders.append(f"{path}:{i}")
    assert not offenders, offenders


# -- no fallback that hides the device ----------------------------------------

def test_on_tpu_is_false_on_the_cpu_backend():
    from distributed_parameter_server_for_ml_training_tpu.ops import attention
    from distributed_parameter_server_for_ml_training_tpu.ops.pallas import (
        quantize)
    assert jax.default_backend() == "cpu"
    assert attention._on_tpu() is False
    assert quantize._on_tpu() is False


# -- chip_smoke.py -------------------------------------------------------------

def test_importing_chip_smoke_leaves_jax_unimported():
    proc = _python("""
        import sys
        import chip_smoke
        assert "jax" not in sys.modules
        assert not any(m.startswith("distributed_parameter_server")
                       for m in sys.modules)
        print("OK")
    """, dict(os.environ))
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr[-2000:]


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    """The driver parses the last stdout line and refuses anything but
    {"ok", "device": {"platform", "kind", "count"}} — the per-phase summary
    goes on its own line before it."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    line = chip_smoke.result_line({"platform": "tpu",
                                   "device_kind": "TPU v5 lite",
                                   "device_count": 4, "extra": 1})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}}


def test_chip_smoke_fails_without_a_tpu(tmp_path):
    """No accelerator here: exit code != 0 and no result line — never a
    CPU run reported as a pass. Run from a copy beside a link to the
    package, so its logs do not land on a real chip run's
    ``chiprun_out/``."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    os.symlink(os.path.join(REPO, PKG), tmp_path / PKG)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=_env_without("PYTHONPATH"),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "Unable to initialize backend 'tpu'" in proc.stderr
    assert "no TPU" in proc.stderr


def test_chip_smoke_fails_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=_env_without("PYTHONPATH"),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


# -- one process per chip ------------------------------------------------------

def test_which_commands_may_take_the_accelerator():
    from distributed_parameter_server_for_ml_training_tpu import cli
    parse = cli.build_parser().parse_args
    uses = cli._uses_accelerator
    assert uses(parse(["train", "--mode", "sync"]))
    assert uses(parse(["worker", "--server", "h:1"]))
    assert uses(parse(["serve", "--store-backend", "device"]))
    assert not uses(parse(["train", "--platform", "cpu"]))
    for backend in ("python", "native"):
        assert not uses(parse(["serve", "--store-backend", backend]))
    host_side = [
        ["replica", "--primary", "h:1"],
        ["supervise", "--workers", "1", "--", "--server", "h:1"],
        ["observe", "--targets", "h:1"], ["status"], ["top"],
        ["loadgen", "--targets", "h:1"],
        ["reshard", "--primaries", "a:1,b:2", "--donor", "0",
         "--recipient", "1", "--slots", "0:1"],
        ["query", "--journal", "d"], ["incident", "list", "--dir", "d"],
        ["goodput"], ["infer", "--target", "h:1"],
        ["lint"],
    ]
    for argv in host_side:
        assert not uses(parse(argv)), argv


def test_serve_with_a_host_store_initialises_only_the_cpu_backend():
    """Run with JAX_PLATFORMS unset, as on a TPU host: `cli serve` with
    the python store must pin itself to CPU before its first JAX call,
    or it would take the chip from the worker it serves."""
    proc = _python(f"""
        import io, os, sys, threading, time
        from {PKG} import cli
        err = sys.stderr = io.StringIO()
        threading.Thread(target=cli.main, daemon=True, args=([
            "serve", "--mode", "async", "--workers", "1", "--port", "0",
            "--model", "vit_tiny", "--store-backend", "python"],)).start()
        deadline = time.time() + 180
        while "parameter server up" not in err.getvalue():
            assert time.time() < deadline, err.getvalue()[-2000:]
            time.sleep(0.1)
        import jax
        from jax._src import xla_bridge
        print("PLATFORMS", jax.config.jax_platforms)
        print("BACKENDS", sorted(xla_bridge._backends))
        print("UP", [ln for ln in err.getvalue().splitlines()
                     if "parameter server up" in ln][0])
        sys.stdout.flush()
        os._exit(0)
    """, _env_without("JAX_PLATFORMS", "JAX_PLATFORM_NAME"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "PLATFORMS cpu" in proc.stdout, proc.stdout
    assert "BACKENDS ['cpu']" in proc.stdout, proc.stdout
    assert "platform=cpu" in proc.stdout, proc.stdout


# -- METRICS_JSON names the device ---------------------------------------------

def test_worker_metrics_row_names_the_device():
    from distributed_parameter_server_for_ml_training_tpu.ps.worker import (
        WorkerConfig, WorkerResult)
    row = WorkerResult(worker_id=0, final_train_loss=1.5, device_id=3) \
        .metrics(2, 0.1, WorkerConfig())
    assert row["platform"] == "cpu"
    assert row["device_kind"] == jax.devices()[0].device_kind
    assert row["device_count"] == len(jax.devices())
    assert row["device_id"] == 3 and row["final_train_loss"] == 1.5
    json.dumps(row)  # the row is what METRICS_JSON prints


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_trainer_metrics_rows_name_the_device(mode, capsys):
    from distributed_parameter_server_for_ml_training_tpu.data import (
        synthetic_cifar100)
    from distributed_parameter_server_for_ml_training_tpu.train \
        .distributed import AsyncTrainer, DistributedConfig, SyncTrainer
    from distributed_parameter_server_for_ml_training_tpu.utils.metrics \
        import parse_metrics_lines

    ds = synthetic_cifar100(n_train=64, n_test=32, num_classes=10, seed=2)
    cfg = DistributedConfig(mode=mode, num_workers=2, num_epochs=1,
                            batch_size=16, augment=False, model="vit_tiny",
                            dtype="float32", num_classes=10,
                            store_backend="device")
    (SyncTrainer if mode == "sync" else AsyncTrainer)(ds, cfg).train(
        emit_metrics=True)
    rows = parse_metrics_lines(capsys.readouterr().out)
    assert len(rows) == 3  # trainer/server row + one per worker
    for row in rows:
        assert row["platform"] == "cpu"
        assert row["device_kind"] == jax.devices()[0].device_kind
        assert row["device_count"] == len(jax.devices())
    losses = [r["final_train_loss"] for r in rows
              if "worker_id" in r or mode == "sync"
              if "final_train_loss" in r]
    assert losses and all(l == l and abs(l) < 1e9 for l in losses)
    if mode == "async":
        assert all(r["device_id"] is not None for r in rows
                   if "worker_id" in r)


# -- one worker per chip -------------------------------------------------------

def test_async_workers_compute_on_the_devices_they_are_given(devices,
                                                              tiny_model):
    """run_workers puts worker i on devices[i % n]: its step's own output
    says where it ran, while the device store stays where it was."""
    import numpy as np

    from distributed_parameter_server_for_ml_training_tpu.data import (
        synthetic_cifar100)
    from distributed_parameter_server_for_ml_training_tpu.ps import (
        make_store)
    from distributed_parameter_server_for_ml_training_tpu.ps.store import (
        StoreConfig)
    from distributed_parameter_server_for_ml_training_tpu.ps.worker import (
        WorkerConfig, run_workers)
    from distributed_parameter_server_for_ml_training_tpu.utils.pytree \
        import flatten_params

    ds = synthetic_cifar100(n_train=96, n_test=32, num_classes=10, seed=2)
    model = tiny_model()
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 32, 32, 3), np.float32), train=False)
    store = make_store("device", flatten_params(variables["params"]),
                       StoreConfig(mode="async", total_workers=3))
    cfg = WorkerConfig(batch_size=16, num_epochs=1, augment=False)
    results = run_workers(store, model, ds, 3, cfg, devices=devices[1:3])
    assert [r.device_id for r in results] == [devices[1].id, devices[2].id,
                                              devices[1].id]
    assert all(r.final_train_loss is not None for r in results)
    assert store.metrics()["total_parameter_updates"] > 0
    assert {d for a in store.parameters.values()
            for d in a.devices()} == {devices[0]}
    # The default on the CPU backend: one device (virtual CPU devices
    # share the same cores; spreading only multiplies compiles).
    store = make_store("device", flatten_params(variables["params"]),
                       StoreConfig(mode="async", total_workers=2))
    results = run_workers(store, model, ds, 2, cfg)
    assert [r.device_id for r in results] == [devices[0].id] * 2


def test_slot_env_keeps_every_variable_of_a_slot(monkeypatch):
    """One child needs three variables to get one chip of a four-chip host
    (PERF.md, PR 21); `--slot-env` used to keep only the last one given
    for a slot."""
    from distributed_parameter_server_for_ml_training_tpu import cli
    from distributed_parameter_server_for_ml_training_tpu.ps import (
        supervisor)
    seen = {}

    class FakeSupervisor:
        def __init__(self, argv_for, n_workers, config):
            seen["first"] = argv_for(1, 0)
            seen["respawn"] = argv_for(1, 1)

        def start(self):
            pass

        def run(self):
            return 0

    monkeypatch.setattr(supervisor, "WorkerSupervisor", FakeSupervisor)
    monkeypatch.setattr(supervisor, "install_signal_stop", lambda sup: None)
    args = cli.build_parser().parse_args([
        "supervise", "--workers", "2",
        "--slot-env", "1:TPU_VISIBLE_CHIPS=1",
        "--slot-env", "1:TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1",
        "--slot-env", "1:TPU_PROCESS_BOUNDS=1,1,1",
        "--", "--server", "h:1"])
    assert cli._cmd_supervise(args) == 0
    assert seen["first"][1] == {"TPU_VISIBLE_CHIPS": "1",
                                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                                "TPU_PROCESS_BOUNDS": "1,1,1"}
    # ...and only a slot's FIRST spawn gets them (chaos-drill semantics):
    # a respawned one-chip child would start without its chip assignment.
    assert seen["respawn"][1] is None
