"""The harness end to end on the CPU backend, at a tiny size, through the
same drivers: a temporary copy of the benchmark gains a configuration, a
traffic mix, a driver and a per-layer metric as new files only and runs
them. No chip number is produced or named here: every result says
``"platform": "cpu"`` and is thrown away.
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import tinybench
from harness import data, spec, validate


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return tinybench.make_copy(str(tmp_path_factory.mktemp("bench")))


def test_the_copy_gains_a_cell_as_new_files_only(copy):
    """What a later PR does: new files, and entries in BENCHMARK.json."""
    before = {}
    src = os.path.join(spec.ROOT, "benchmarks")
    for base, _dirs, files in os.walk(src):
        for fn in files:
            if "__pycache__" in base:
                continue
            path = os.path.join(base, fn)
            with open(path, "rb") as f:
                before[os.path.relpath(path, src)] = f.read()
    added = []
    for base, _dirs, files in os.walk(os.path.join(copy, "benchmarks")):
        for fn in files:
            path = os.path.join(base, fn)
            rel = os.path.relpath(path, os.path.join(copy, "benchmarks"))
            if "__pycache__" in rel:
                continue
            with open(path, "rb") as f:
                if rel in before:
                    assert f.read() == before[rel], f"{rel} was edited"
                else:
                    added.append(rel)
    assert sorted(added) == [
        "configs/vit-tiny-32.json", "drivers/tiny_sync.py",
        "layer_metrics/tiny.steps.py", "peaks/cpu.json",
        "traffic/tiny-sync-mesh4.json", "traffic/tiny-sync.json"]
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # the copy's table keeps the repo's rules but for its share of 4-chip
    # cells, which only a real benchmark has to keep
    problems = [p for p in validate.check_benchmark(bench)
                if "ask for 4 chips" not in p]
    assert problems == []
    cell = spec.load_cell("tiny-sync", copy)
    assert cell.traffic["driver"] == "tiny_sync"
    assert cell.driver().__file__.endswith("drivers/tiny_sync.py")
    assert "tiny.steps" in cell.per_layer
    assert "tiny.steps" not in spec.load_cell("tiny-sync-mesh4",
                                              copy).per_layer
    assert spec.load_module("layer_metrics", "tiny.steps",
                            cell.bench_dir).MOVES == "images_per_s_per_chip"
    assert cell.peak("cpu")["device_kind"] == "cpu"


def _result(p, cell):
    assert p.returncode == 0, p.stderr[-3000:]
    assert validate.check_last_line(p.stdout, owed=cell.end_to_end,
                                    trace=False) == []
    assert p.stdout.count("\n") == 1, "only the result is on stdout"
    return json.loads(p.stdout)


@pytest.mark.parametrize("workload", ["tiny-sync", "tiny-sync-mesh4"])
def test_a_tiny_cell_runs_through_the_real_drivers(copy, workload):
    cell = spec.load_cell(workload, copy)
    result = _result(tinybench.run_tiny(copy, workload, seconds=3.0), cell)
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    checks = result["checks"]
    assert checks["losses_finite"] and checks["counts_reconcile"]
    assert checks["work_was_done"] and checks["learned"]
    if workload == "tiny-sync-mesh4":
        assert checks["replicas_identical"]
    assert checks["no_compile_in_window"] and result["correct"]
    log = os.path.join(copy, "chiprun_out", "benchmarks",
                       f"{workload}.seed{2**31 + 11}.trace0", "run.log")
    with open(log) as f:
        text = f.read()
    assert "[sync x" in text
    # both edges are epoch ends: the window holds whole epochs of two steps
    # and is the first to reach --seconds
    opens = re.search(r"window opens.*'epochs': (\d+), 'steps': (\d+)", text)
    closes = re.search(r"window closes after (\S+) s.*'epochs': (\d+), "
                       r"'steps': (\d+)", text)
    epochs = int(closes.group(2)) - int(opens.group(1))
    assert int(opens.group(1)) == 1 and epochs >= 1
    assert result["attempted"] == 2 * epochs
    assert int(closes.group(3)) - int(opens.group(2)) == 2 * epochs
    assert float(closes.group(1)) >= 3.0
    seconds = re.search(r"epoch seconds in the window min (\S+) median "
                        r"(\S+) max (\S+)", text)
    assert float(closes.group(1)) - float(seconds.group(3)) < 3.0


def test_a_traced_run_without_a_device_plane_fails_and_prints_nothing(copy):
    p = tinybench.run_tiny(copy, "tiny-sync", seconds=1.0, trace=1)
    assert p.returncode != 0 and p.stdout == ""
    assert "no device plane" in p.stderr


def test_the_command_refuses_to_measure_without_an_accelerator():
    """The committed command, from the repo, on a machine with no chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    p = subprocess.run(
        bench["command"] + ["--workload", bench["workloads"][0]["name"],
                            "--seed", "7", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=spec.ROOT)
    assert p.returncode == 3
    assert p.stdout == ""
    assert "no accelerator" in p.stderr


def test_an_unknown_workload_fails_and_prints_nothing():
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", "nothing", "--seed", "7", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout == ""
    assert "no workload 'nothing'" in p.stderr


def test_alone_with_its_own_files_the_benchmark_prints_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths``: the program is not there, so there is nothing to measure."""
    import shutil
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "resnet18-sync-1chip", "--seed", "7", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env=dict(env, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout == ""


def _arrays(seed, **kw):
    args = dict(image_size=32, num_classes=10, n_train=64, n_test=16,
                seed=seed, coarse_px=4, template_amp=0.18, noise=0.12)
    args.update(kw)
    return data.class_template_arrays(**args)


def test_the_same_seed_gives_the_same_inputs_and_another_seed_others():
    big = 2**31 + 11
    a, b, c = _arrays(big), _arrays(big), _arrays(big + 1)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    x_tr, y_tr, x_te, y_te = a
    assert x_tr.shape == (64, 32, 32, 3) and x_tr.dtype == np.uint8
    assert y_tr.dtype == np.int32 and sorted(set(y_tr)) == list(range(10))
    assert x_te.shape == (16, 32, 32, 3) and len(y_te) == 16


def test_distinct_images_are_tiled_to_the_epoch_length():
    x, y, _xt, _yt = _arrays(3, n_train=40, distinct_train=16)
    assert x.shape[0] == 40 and y.shape[0] == 40
    assert np.array_equal(x[:16], x[16:32]) and np.array_equal(x[:8], x[32:])
    assert np.array_equal(y[:16], y[16:32])


def test_images_of_one_class_share_a_template():
    x, y, x_te, y_te = _arrays(5, n_train=200, n_test=50, noise=0.01)
    mean = {c: x[y == c].mean(axis=0) for c in range(10)}
    nearest = [min(mean, key=lambda c: np.abs(mean[c] - img).mean())
               for img in x_te.astype(np.float32)]
    assert nearest == list(y_te)   # the test split is separable by template


def test_a_template_that_does_not_divide_the_image_is_an_error():
    with pytest.raises(ValueError):
        _arrays(1, image_size=30)


def _learned(clauses, losses, accuracy):
    return spec.load_module("drivers", "sync_mesh",
                            spec.BENCH_DIR).learned(clauses, losses, accuracy)


@pytest.mark.parametrize("clauses, losses, accuracy, expected", [
    # every clause binds: a fine loss does not excuse a poor accuracy
    ({"min_test_accuracy": 0.9, "max_train_loss": 0.1},
     [4.2, 0.5, 0.02], 1.0, True),
    ({"min_test_accuracy": 0.9, "max_train_loss": 0.1},
     [4.2, 0.5, 0.02], 0.5, False),
    ({"min_test_accuracy": 0.9, "max_train_loss": 0.1},
     [4.2, 3.9, 3.5], 1.0, False),
    # a loss that only falls is not enough: it has to fall by the margin
    ({"min_loss_drop": 0.15}, [7.27, 7.07, 7.02], 0.0, True),
    ({"min_loss_drop": 0.15}, [7.27, 7.25, 7.20], 0.0, False),
    ({"min_loss_drop": 0.15}, [7.27, 7.40], 0.0, False),
    # one epoch is no history, and a loss that is not finite never learned
    ({"min_loss_drop": 0.15}, [7.27], 0.0, False),
    ({"min_test_accuracy": 0.0}, [4.2, float("nan")], 1.0, False),
])
def test_every_clause_of_learned_binds(clauses, losses, accuracy, expected):
    assert _learned(clauses, losses, accuracy) is expected


@pytest.mark.parametrize("clauses", [{}, {"loss_must_fall": True}])
def test_a_learned_group_the_driver_cannot_read_is_an_error(clauses):
    with pytest.raises(ValueError):
        _learned(clauses, [4.2, 0.5], 1.0)


@pytest.mark.parametrize("workload", ["resnet18-sync-1chip",
                                      "vit-b16-sync-1chip"])
def test_every_committed_cell_says_how_it_has_to_learn(workload):
    cell = spec.load_cell(workload)
    assert _learned(cell.config["learned"], [9.0, 0.0], 1.0) is True
    assert _learned(cell.config["learned"], [9.0, 9.0], 0.0) is False
