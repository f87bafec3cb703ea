"""A temporary copy of the benchmark that gains a tiny cell as NEW FILES
only (a configuration, a traffic mix, a driver, a per-layer metric, a peaks
row for the CPU backend) plus entries in its ``BENCHMARK.json``: what a later
PR does to add a cell, and how the tests drive the real harness on the CPU
without producing or naming a chip number.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIG = {
    "name": "vit-tiny-32",
    "source": "tests only: models/vit.py:ViT_Tiny",
    "model": "vit_tiny",
    "ops_count": "vit",
    "architecture": {
        "image_size": 32, "patch_size": 4, "hidden_size": 192,
        "mlp_dim": 768, "num_heads": 3, "head_dim": 64, "num_layers": 4,
        "num_classes": 10, "class_token": True},
    "compute_dtype": "float32",
    "optimizer": {"name": "sgd", "learning_rate": 0.05},
    "eval_images": 16,
    "data": {"kind": "class_template", "coarse_px": 4, "template_amp": 0.18,
             "noise": 0.12, "distinct_train_images": None},
    "learned": {"min_test_accuracy": 0.0, "min_loss_drop": -10.0},
}

TINY_SYNC = {"driver": "tiny_sync", "per_chip_batch": 16,
             "steps_per_epoch": 2, "augment": True, "exchange_dtype": "bf16",
             "trace_slice_s": 0.5}

NEW_METRIC = '''"""A per-layer metric a later PR adds: steps dispatched."""

LAYER = "trainer"
UNIT = "count"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "images_per_s_per_chip"
DRIVERS = ("tiny_sync",)
CHIPS = None


def read(run):
    return run.delta("steps")
'''

RUNNER = """
import sys, time
t0 = time.monotonic()
sys.path.insert(0, {bench!r})
from harness import runner
runner.main(sys.argv[1:], t_start=t0, allow_cpu=True, root={root!r})
"""


def make_copy(dst: str) -> str:
    """Copy ``benchmarks/`` and ``BENCHMARK.json`` to ``dst`` and add the
    tiny cells. Returns ``dst``. No file that was copied is changed except
    ``BENCHMARK.json``, which gains entries."""
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(dst, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bdir = os.path.join(dst, "benchmarks")

    def new_file(rel: str, text: str) -> None:
        path = os.path.join(bdir, rel)
        assert not os.path.exists(path), f"{rel} would overwrite a file"
        with open(path, "w") as f:
            f.write(text)

    new_file("configs/vit-tiny-32.json", json.dumps(TINY_CONFIG))
    new_file("traffic/tiny-sync.json", json.dumps(TINY_SYNC))
    new_file("traffic/tiny-sync-mesh4.json", json.dumps(TINY_SYNC))
    with open(os.path.join(bdir, "drivers", "sync_mesh.py")) as f:
        new_file("drivers/tiny_sync.py", f.read())
    new_file("layer_metrics/tiny.steps.py", NEW_METRIC)
    new_file("peaks/cpu.json", json.dumps(
        {"device_kind": "cpu", "bf16_flops_per_s": 1e12,
         "source": "tests only: no peak of any chip"}))

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "vit-tiny-32", "source": TINY_CONFIG["source"],
        "file": "benchmarks/configs/vit-tiny-32.json", "reduced": [],
        "why": "tests only"})
    bench["workloads"] += [
        {"name": "tiny-sync", "config": "vit-tiny-32",
         "traffic": "tiny-sync", "chips": 1, "why": "tests only"},
        {"name": "tiny-sync-mesh4", "config": "vit-tiny-32",
         "traffic": "tiny-sync-mesh4", "chips": 4, "why": "tests only"}]
    bench["per_layer"].append({
        "name": "tiny.steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "images_per_s_per_chip", "workloads": ["tiny-sync"]})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst


def run_tiny(root: str, workload: str, *, seconds: float = 1.0,
             trace: int = 0, seed: int = 2**31 + 11, timeout: float = 300):
    """Run one cell of the copy on the CPU backend, through the harness's
    own ``main`` (steered from here: ``allow_cpu`` is no option of the
    command)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    code = RUNNER.format(bench=os.path.join(root, "benchmarks"), root=root)
    return subprocess.run(
        [sys.executable, "-c", code, "--workload", workload, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=root)
