"""The one epoch loop (train/loop.py) and the one image forward/backward
(train/steps.py:image_forward_backward) under every trainer.

An epoch is published in one order: its losses fetched, ``epoch_times`` and
``epoch_losses`` appended, its line written whole and flushed, and only then
``test_accuracies`` grows. A watcher woken by that last append (the
benchmark's driver polls it) finds the line already in the log.
"""

import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_parameter_server_for_ml_training_tpu.data import (
    synthetic_cifar100)

EPOCHS, STEPS, BATCH = 2, 2, 16
FLUSH = object()


class Writes:
    """A ``sys.stdout`` that keeps every ``write`` call apart."""

    def __init__(self):
        self.calls = []

    def write(self, text):
        self.calls.append(text)
        return len(text)

    def flush(self):
        self.calls.append(FLUSH)


class Watched(list):
    """``test_accuracies`` whose ``append`` records what the log and the
    trainer's lists held at that moment."""

    def __init__(self, trainer, out):
        super().__init__()
        self.trainer, self.out, self.seen = trainer, out, []

    def append(self, acc):
        self.seen.append((list(self.out.calls),
                          list(self.trainer.epoch_losses),
                          list(self.trainer.epoch_times)))
        super().append(acc)


def _dataset():
    return synthetic_cifar100(n_train=STEPS * BATCH, n_test=16,
                              num_classes=10, seed=3)


def _sync():
    from distributed_parameter_server_for_ml_training_tpu.train.distributed \
        import DistributedConfig, SyncTrainer
    return SyncTrainer(_dataset(), DistributedConfig(
        mode="sync", num_workers=2, num_epochs=EPOCHS, batch_size=BATCH // 2,
        dtype="float32", num_classes=10, model="vit_tiny", seed=3))


def _tp():
    from distributed_parameter_server_for_ml_training_tpu.train \
        .model_parallel import ModelParallelConfig, TPTrainer
    return TPTrainer(_dataset(), ModelParallelConfig(
        model="vit_tiny", num_workers=1, tp_degree=2, num_epochs=EPOCHS,
        batch_size=BATCH, dtype="float32", num_classes=10, seed=3))


def _baseline(device_loop=False):
    from distributed_parameter_server_for_ml_training_tpu.train.baseline \
        import BaselineConfig, BaselineTrainer
    return BaselineTrainer(_dataset(), BaselineConfig(
        model="vit_tiny", num_epochs=EPOCHS, batch_size=BATCH,
        dtype="float32", num_classes=10, seed=3, device_loop=device_loop))


SYNC_LINE = r"\[sync x2\] epoch (\d+): loss (\S+) test \S+% \(\S+s\)\n"
CASES = {
    "sync": (_sync, SYNC_LINE),
    "tp": (_tp, r"\[tp 1x2\] epoch (\d+): loss (\S+) test \S+% \(\S+s\)\n"),
    "baseline": (_baseline, r"epoch (\d+)/2: loss (\S+) train \S+% "
                            r"test \S+% \(\S+s\)\n"),
    "baseline_device_loop": (lambda: _baseline(True),
                             r"epoch (\d+)/2: loss (\S+) train \S+% "
                             r"test \S+% \(\S+s\)\n"),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_an_epoch_is_visible_only_after_its_whole_line(name, monkeypatch):
    make, pattern = CASES[name]
    trainer = make()
    out = Writes()
    watched = trainer.test_accuracies = Watched(trainer, out)
    monkeypatch.setattr(sys, "stdout", out)
    trainer.train()
    monkeypatch.undo()

    assert len(watched) == EPOCHS == len(watched.seen)
    for n, (calls, losses, times) in enumerate(watched.seen, start=1):
        # the line went out in ONE write that ends the line, then a flush,
        # and nothing else since
        assert calls[-1] is FLUSH
        lines = [re.fullmatch(pattern, c) for c in calls
                 if c is not FLUSH and "epoch" in c]
        assert all(lines) and len(lines) == n, calls
        assert [int(m.group(1)) for m in lines] == list(range(1, n + 1))
        assert calls[-2] == lines[-1].group(0)
        # and the trainer keeps what it printed
        assert len(losses) == n == len(times)
        assert all(math.isfinite(loss) for loss in losses)
        assert [m.group(2) for m in lines] == [f"{x:.4f}" for x in losses]
    assert trainer.epoch_losses == watched.seen[-1][1]
    if name.startswith("baseline"):     # its record, behind its line too
        assert trainer.metrics.train_losses == trainer.epoch_losses
        assert trainer.metrics.test_accuracies == [100.0 * a
                                                   for a in watched]


def test_the_benchmarks_regex_still_reads_the_sync_trainers_line():
    """benchmarks/drivers/sync_mesh.py takes the epochs' losses from this
    line until it reads ``epoch_losses``: the regex is imported, not
    copied."""
    from distributed_parameter_server_for_ml_training_tpu.train.distributed \
        import SyncTrainer
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks"))
    try:
        from drivers.sync_mesh import EPOCH_LINE
    finally:
        sys.path.pop(0)
    trainer = SyncTrainer.__new__(SyncTrainer)
    trainer.config = type("C", (), {"num_workers": 4})
    line = trainer._epoch_line(2, 0.1234, 0.98, 1.64)
    assert line == "[sync x4] epoch 3: loss 0.1234 test 98.00% (1.6s)"
    m = EPOCH_LINE.match(line + "\n")
    assert (m.group(1), m.group(2), m.group(3)) == ("3", "0.1234", "98.00")
    assert re.fullmatch(SYNC_LINE.replace("x2", "x4"), line + "\n")


# -- one forward/backward: every step's loss and update from one seed ---------

LR = 0.1


@pytest.fixture(scope="module")
def reference(tiny_model):
    """``make_train_step``'s loss, parameters and batch statistics after one
    step of plain SGD, float32, no augmentation."""
    from distributed_parameter_server_for_ml_training_tpu.train import (
        create_train_state, make_train_step, server_sgd)
    r = np.random.default_rng(11)
    images = r.integers(0, 255, (8, 32, 32, 3), dtype=np.uint8)
    labels = (np.arange(8) % 10).astype(np.int32)
    state = create_train_state(tiny_model(), jax.random.PRNGKey(0),
                               server_sgd(LR))
    start = jax.device_get((state.params, state.batch_stats))
    after, m = jax.jit(make_train_step(augment=False))(
        state, images, labels, jax.random.PRNGKey(9))
    return {"images": images, "labels": labels, "start": start,
            "loss": float(m["loss"]), "params": after.params,
            "batch_stats": after.batch_stats}


def _grad_step(model, ref):
    from distributed_parameter_server_for_ml_training_tpu.train.steps import (
        make_grad_step)
    params, stats = ref["start"]
    grads, new_stats, loss, _ = make_grad_step(model, augment=False)(
        params, stats, ref["images"], ref["labels"], jax.random.PRNGKey(9), 0)
    return loss, jax.tree_util.tree_map(lambda p, g: p - LR * g,
                                        params, grads), new_stats


def _fused_local_step(model, ref):
    from distributed_parameter_server_for_ml_training_tpu.train.steps import (
        make_fused_local_step)
    params, stats = jax.tree_util.tree_map(jnp.asarray, ref["start"])
    accum = jax.tree_util.tree_map(jnp.zeros_like, params)
    new_params, _, new_stats, loss, _ = make_fused_local_step(
        model, augment=False)(params, accum, stats, ref["images"],
                              ref["labels"], jax.random.PRNGKey(9), 0, LR)
    return loss, new_params, new_stats


def _sync_one_worker(model, ref):
    from distributed_parameter_server_for_ml_training_tpu.parallel import (
        make_mesh, make_sync_dp_step, shard_batch)
    from distributed_parameter_server_for_ml_training_tpu.train import (
        create_train_state, server_sgd)
    mesh = make_mesh(1)
    state = create_train_state(model, jax.random.PRNGKey(0), server_sgd(LR))
    step = make_sync_dp_step(mesh, compression="none", augment=False)
    after, m = step(state, *shard_batch(mesh, (ref["images"],
                                               ref["labels"])),
                    jax.random.PRNGKey(9))
    return m["loss"], after.params, after.batch_stats


@pytest.mark.parametrize("step", [_grad_step, _fused_local_step,
                                  _sync_one_worker],
                         ids=lambda f: f.__name__.strip("_"))
def test_every_step_takes_the_one_forward_backward(step, tiny_model,
                                                   reference):
    model = tiny_model("data" if step is _sync_one_worker else None)
    loss, params, stats = step(model, reference)
    np.testing.assert_allclose(float(loss), reference["loss"], rtol=1e-6)
    for got, want in ((params, reference["params"]),
                      (stats, reference["batch_stats"])):
        for x, y in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want), strict=True):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-6, atol=1e-6)
