"""Who holds the chips — the builder's checks for PR 21 that need more than
``chip_smoke.py`` (run that first). Like it (whose process plumbing this
reuses) the parent never imports jax; every check is a child process.

    python experiments/check_chips.py host-side     # any TPU host
    python experiments/check_chips.py multichip     # the four-chip host

host-side  Every host-side CLI command (``serve`` with a host store,
           ``replica``, ``observe``, ``supervise``, ``top``, ``status``,
           ``loadgen`` running; ``status``/``query``/``incident``/
           ``goodput``/``reshard``/``infer``/``lint`` one-shot) must leave
           the chip alone. While the long-running ones are alive: none has
           a ``/dev/vfio`` or ``/dev/accel`` descriptor, and another
           process can take the chip. While that process HOLDS the chip:
           the one-shot ones still run to an end.
replicas   ``SyncTrainer`` at ResNet-18 width, ``--compression bf16`` and
           ``int8`` (stochastic int8 quantize inside ``shard_map``): after
           real steps every device holds one shard of the batch and one
           replica of every parameter, and the replicas are bit-identical.
one-chip   N concurrent processes, each started with
           ``TPU_VISIBLE_CHIPS=<i>``: does each get exactly one chip while
           the others hold theirs? Tried bare and with the per-process
           bounds variables libtpu documents for splitting a host.
supervise  ``cli serve`` (host store, CPU) + ``cli supervise --workers N
           --slot-env i:TPU_VISIBLE_CHIPS=i ...``: N one-chip ``cli
           worker`` processes on one host, end to end.

Prints one ``CHIPS_JSON: {...}`` line per check and writes them to
``chiprun_out/check_chips_<mode>.json``. ``one-chip`` and ``supervise``
RECORD what the installation does (either answer is a finding for the
README); the others must pass for exit code 0.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402 — jax-free plumbing

BOUNDS = {"TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
          "TPU_PROCESS_BOUNDS": "1,1,1"}

# Each probe keeps its chip for a while after computing on it, so that all
# N are alive at once: a chip belongs to one process, so N live one-chip
# processes hold N different chips.
PROBE = """
import json, time, jax, jax.numpy as jnp
d = jax.devices()
x = jnp.ones((1024, 1024), jnp.bfloat16)
print("PROBE_JSON: " + json.dumps({
    "platform": d[0].platform, "device_count": len(d),
    "device_ids": [x.id for x in d], "coords": [list(x.coords) for x in d],
    "matmul": float((x @ x)[0, 0])}), flush=True)
time.sleep(20)
"""


def _child_replicas() -> int:
    import jax
    import numpy as np

    from distributed_parameter_server_for_ml_training_tpu.data import (
        synthetic_cifar100)
    from distributed_parameter_server_for_ml_training_tpu.data.cifar import (
        make_batches)
    from distributed_parameter_server_for_ml_training_tpu.train \
        .distributed import DistributedConfig, SyncTrainer
    from distributed_parameter_server_for_ml_training_tpu.utils \
        .compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.local_devices()
    n = len(devices)
    ds = synthetic_cifar100(n_train=3 * 128 * n, n_test=100)
    rng = jax.random.PRNGKey(1)
    for compression in ("bf16", "int8"):
        trainer = SyncTrainer(ds, DistributedConfig(
            mode="sync", num_workers=n, batch_size=128,
            compression=compression))
        losses = []
        for xb, yb in make_batches(ds.x_train, ds.y_train, 128 * n, seed=0):
            bi, bl = trainer._shard((xb, yb))
            shards = bi.addressable_shards
            assert {s.device for s in shards} == set(devices), shards
            assert all(s.data.shape == (128, 32, 32, 3) for s in shards)
            trainer.state, m = trainer._step(trainer.state, bi, bl, rng)
            losses.append(float(m["loss"]))
        assert len(losses) == 3 and np.isfinite(losses).all(), losses
        leaves = jax.tree_util.tree_leaves(trainer.state.params)
        n_params = 0
        for leaf in leaves:
            shards = leaf.addressable_shards
            assert {s.device for s in shards} == set(devices)
            assert all(s.data.shape == leaf.shape for s in shards)
            first = np.asarray(shards[0].data)
            assert np.isfinite(first).all()
            for s in shards[1:]:
                np.testing.assert_array_equal(first, np.asarray(s.data))
            n_params += first.size
        print("REPLICAS_JSON: " + json.dumps({
            "compression": compression, "devices": n,
            "device_kind": devices[0].device_kind,
            "platform": devices[0].platform, "steps": len(losses),
            "losses": [round(x, 4) for x in losses],
            "parameters": n_params, "param_tensors": len(leaves),
            "batch_shard_on_every_device": True,
            "param_replica_on_every_device": True,
            "replicas_bit_identical": True}), flush=True)
    return 0


def check_replicas() -> dict:
    proc = cs._spawn("mc-replicas", [sys.executable,
                                     os.path.abspath(__file__),
                                     "--child-replicas"], chip=True)
    cs._wait(proc, 600)
    rows = cs._tagged(cs._read("mc-replicas", "out"), "REPLICAS_JSON")
    cs._require([r["compression"] for r in rows] == ["bf16", "int8"],
                f"replicas: got {rows}")
    return {"runs": rows}


def check_one_chip_per_process(n: int, extra_env: dict) -> dict:
    """N concurrent children, child i with TPU_VISIBLE_CHIPS=i."""
    tag = "bounds" if extra_env else "bare"
    procs = [cs._spawn(f"mc-probe-{tag}-{i}", [sys.executable, "-c", PROBE],
                       chip=True,
                       env={"TPU_VISIBLE_CHIPS": str(i), **extra_env})
             for i in range(n)]
    children = []
    for i, proc in enumerate(procs):
        name = f"mc-probe-{tag}-{i}"
        try:
            cs._wait(proc, 120)
            children.append(cs._tagged(cs._read(name, "out"),
                                       "PROBE_JSON")[0])
        except cs.PhaseFailed as e:
            children.append({"failed": str(e).splitlines()[0],
                             "stderr_tail": cs._read(name, "err")[-600:]})
    ok = all(c.get("platform") == "tpu" and c.get("device_count") == 1
             for c in children)
    return {"env": {"TPU_VISIBLE_CHIPS": "<i>", **extra_env},
            "each_child_one_chip": ok, "children": children}


def check_supervise(n: int, extra_env: dict) -> dict:
    server = cs._spawn("mc-serve", cs.CLI + [
        "serve", "--mode", "async", "--workers", str(n),
        "--store-backend", "python", "--push-codec", "int8",
        "--fetch-codec", "bf16", "--port", "0", "--emit-metrics"],
        chip=False)
    try:
        port = cs._served_port(server, 120)
        slot_env = []
        for i in range(n):
            for key, val in {"TPU_VISIBLE_CHIPS": str(i),
                             **extra_env}.items():
                slot_env += ["--slot-env", f"{i}:{key}={val}"]
        sup = cs._spawn("mc-supervise", cs.CLI + [
            "supervise", "--workers", str(n), "--no-respawn"] + slot_env + [
            "--", "--server", f"localhost:{port}",
            "--batch-size", "128", "--num-train", str(4 * 128 * n)]
            + cs.TRAIN_ARGS, chip=True)
        cs._wait(sup, 420)
        cs._wait(server, 60)
    finally:
        cs._kill(server)
    servers, _ = cs._rows("mc-serve")
    _, workers = cs._rows("mc-supervise")
    return {"workers": [{k: w.get(k) for k in (
        "worker_name", "platform", "device_count", "device_id",
        "local_steps_completed", "final_train_loss")} for w in workers],
        "gradients_processed": servers[0]["gradients_processed"]}


HOLDER = """
import json, time, jax, jax.numpy as jnp
x = jnp.ones((1024, 1024), jnp.bfloat16)
print("HOLDER_JSON: " + json.dumps({
    "platform": jax.devices()[0].platform,
    "matmul": float((x @ x)[0, 0])}), flush=True)
time.sleep(60)
"""


def _device_fds(pid: int) -> list[str]:
    """Accelerator device files this process has open."""
    found = []
    try:
        for fd in os.listdir(f"/proc/{pid}/fd"):
            try:
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if "/dev/vfio" in target or "/dev/accel" in target:
                found.append(target)
    except OSError:
        pass
    return found


def check_host_side() -> dict:
    p0, m0, p1, fleet = 18500, 18501, 18502, 18503
    tiny = ["--model", "vit_tiny"]
    running = {
        "serve-python": ["serve", "--mode", "async", "--workers", "2",
                         "--store-backend", "python", "--port", str(p0),
                         "--metrics-port", str(m0)] + tiny,
        "serve-native": ["serve", "--mode", "async", "--workers", "2",
                         "--store-backend", "native", "--port", str(p1)]
        + tiny,
        "replica": ["replica", "--primary", f"localhost:{p0}",
                    "--port", "0"],
        "observe": ["observe", "--targets", f"localhost:{m0}",
                    "--port", str(fleet)],
        "supervise": ["supervise", "--workers", "1", "--no-respawn", "--",
                      "--server", f"localhost:{p1}", "--platform", "cpu",
                      "--synthetic", "--epochs", "50", "--num-train", "2048",
                      "--num-test", "64"] + tiny,
        "top": ["top", "--url", f"http://localhost:{fleet}", "--watch", "2"],
        "status-watch": ["status", "--url", f"http://localhost:{m0}",
                         "--watch", "2"],
        "loadgen": ["loadgen", "--targets", f"localhost:{p0}",
                    "--duration", "120", "--concurrency", "1"],
    }
    one_shot = {
        "status": ["status", "--url", f"http://localhost:{m0}"],
        "goodput": ["goodput", "--url", f"http://localhost:{m0}"],
        "query": ["query", "--journal", "chiprun_out/no-such-journal"],
        "incident": ["incident", "list", "--dir",
                     "chiprun_out/no-such-incidents"],
        "reshard": ["reshard", "--primaries",
                    f"localhost:{p0},localhost:{p1}", "--donor", "0",
                    "--recipient", "1", "--slots", "0:1"],
        "infer": ["infer", "--target", f"localhost:{p0}", "--count", "1"],
        "lint": ["lint"],
    }
    procs = {}
    for name, argv in running.items():
        procs[name] = cs._spawn(f"hs-{name}", cs.CLI + argv, chip=False)
        if name.startswith("serve"):
            time.sleep(8)   # its port must be up for those that dial it
    time.sleep(20)
    report = {}
    for name, proc in procs.items():
        children = subprocess.run(
            ["pgrep", "-s", str(proc.pid)], capture_output=True,
            text=True).stdout.split()
        fds = [f for pid in children for f in _device_fds(int(pid))]
        report[name] = {"alive": proc.poll() is None,
                        "processes": len(children), "device_fds": fds}
    holder = cs._spawn("hs-holder", [sys.executable, "-c", HOLDER],
                       chip=True)
    deadline = time.monotonic() + 90
    took_chip = None
    while took_chip is None and time.monotonic() < deadline:
        found = cs._tagged(cs._read("hs-holder", "out"), "HOLDER_JSON")
        if found:
            took_chip = found[0]
        elif holder.poll() is not None:
            break
        else:
            time.sleep(0.5)
    # The chip is now held by another process: a one-shot command that
    # tried to initialise the accelerator would fail or hang here.
    shots = {name: cs._spawn(f"hs-once-{name}", cs.CLI + argv, chip=False)
             for name, argv in one_shot.items()}
    once = {}
    for name, proc in shots.items():
        try:
            rc = proc.wait(timeout=90)
            del cs._LIVE[proc]
        except subprocess.TimeoutExpired:
            cs._kill(proc)
            rc = "hung"
        err = cs._read(f"hs-once-{name}", "err")
        once[name] = {"rc": rc, "mentions_tpu_init": bool(re.search(
            r"Unable to initialize backend|TPU initialization",
            err))}
    still = {name: proc.poll() is None for name, proc in procs.items()}
    for proc in [holder, *procs.values()]:
        cs._kill(proc)
    clean = all(r["alive"] and not r["device_fds"] for r in report.values())
    calm = all(o["rc"] != "hung" and not o["mentions_tpu_init"]
               for o in once.values())
    cs._require(clean and took_chip is not None
                and took_chip["platform"] == "tpu" and calm,
                "host-side: " + json.dumps(
                    {"running": report, "holder": took_chip, "once": once,
                     "holder_err": cs._read("hs-holder", "err")[-800:]}))
    return {"running": report, "second_process_took_chip": took_chip,
            "one_shot_while_chip_held": once, "alive_at_end": still}


def main(mode: str) -> int:
    results = {}

    def run(name, fn, *args):
        t0 = time.monotonic()
        try:
            results[name] = {"ok": True, **fn(*args)}
        except cs.PhaseFailed as e:
            results[name] = {"ok": False, "error": str(e)[-4000:]}
        results[name]["seconds"] = round(time.monotonic() - t0, 1)
        print("CHIPS_JSON: " + json.dumps({name: results[name]}),
              flush=True)

    try:
        if mode == "host-side":
            run("host_side", check_host_side)
            must_pass = ["host_side"]
        else:
            run("replicas", check_replicas)
            runs = results["replicas"].get("runs") or [{}]
            n = runs[0].get("devices", 4)
            run("one_chip_bare", check_one_chip_per_process, n, {})
            run("one_chip_bounds", check_one_chip_per_process, n, BOUNDS)
            if results["one_chip_bare"].get("each_child_one_chip"):
                run("supervise", check_supervise, n, {})
            elif results["one_chip_bounds"].get("each_child_one_chip"):
                run("supervise", check_supervise, n, BOUNDS)
            must_pass = ["replicas"]
    finally:
        for proc in list(cs._LIVE):
            cs._kill(proc)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    out = os.path.join(REPO, "chiprun_out",
                       f"check_chips_{mode.replace('-', '_')}.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    return 0 if all(results[k]["ok"] for k in must_pass) else 1


if __name__ == "__main__":
    if sys.argv[1:] == ["--child-replicas"]:
        raise SystemExit(_child_replicas())
    if sys.argv[1:] not in (["host-side"], ["multichip"]):
        raise SystemExit(__doc__)
    raise SystemExit(main(sys.argv[1]))
