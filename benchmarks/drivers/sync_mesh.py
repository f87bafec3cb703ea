"""Driver ``sync_mesh``: ``SyncTrainer`` over a mesh of the cell's chips,
exactly what ``cli train --mode sync`` builds, in one ``train()`` call that
outlives the run.

Window edge (device-complete): an epoch end. The trainer ends an epoch by
fetching the epoch's per-step metrics, evaluating, fetching the accuracy and
appending it to ``trainer.test_accuracies``. At that moment every step of
the epoch and its evaluation are finished on the device, because the host
holds their results, and nothing of the next epoch is dispatched. The driver
polls the list's length every 2 ms and stamps the moment it grows. The pair
(epochs x images an epoch, time) is off by that poll at most, a few
milliseconds, and both edges lie at the same point of the epoch's cycle, so
a window holds whole epochs: the same work in every run, with every epoch
end it caused.

An edge at an arbitrary moment, a counter read and a fence behind it, is
what this driver had first. The trainer enqueues a whole epoch in one burst
(32 ViT steps in 45 ms) and then waits at the epoch end, so a read that fell
into a burst raced the dispatch by several steps: two ViT runs of twelve
read 0.8% high (PERF.md, Findings).
"""

from __future__ import annotations

import math
import re
import time

EPOCH_LINE = re.compile(
    r"^\[sync x\d+\] epoch (\d+): loss (\S+) test (\S+)% \(")
POLL_S = 0.002


def learned(clauses: dict, losses: list[float], accuracy: float) -> bool:
    """The configuration's ``learned`` group, every clause of which binds.
    ``losses`` are the mean training losses of the epochs up to the last
    edge, ``accuracy`` the test accuracy after the last of them."""
    unknown = set(clauses) - {"min_test_accuracy", "max_train_loss",
                              "min_loss_drop"}
    if unknown or not clauses:
        raise ValueError(f"'learned' needs clauses this driver knows, not "
                         f"{sorted(clauses)}")
    if len(losses) < 2 or not all(map(math.isfinite, losses)):
        return False
    ok = True
    if "min_test_accuracy" in clauses:
        ok &= accuracy >= clauses["min_test_accuracy"]
    if "max_train_loss" in clauses:
        ok &= losses[-1] <= clauses["max_train_loss"]
    if "min_loss_drop" in clauses:
        ok &= losses[0] - losses[-1] >= clauses["min_loss_drop"]
    return bool(ok)


class Session:
    def __init__(self, ctx):
        from distributed_parameter_server_for_ml_training_tpu.telemetry \
            import get_registry
        from distributed_parameter_server_for_ml_training_tpu.train \
            .distributed import DistributedConfig, SyncTrainer
        from harness.data import make_dataset
        from harness.session import TrainerThread

        traffic, config = ctx.cell.traffic, ctx.cell.config
        self.ctx = ctx
        self.chips = len(ctx.devices)
        self.global_batch = int(traffic["per_chip_batch"]) * self.chips
        self.images_per_device_step = int(traffic["per_chip_batch"])
        self.steps_per_epoch = int(traffic["steps_per_epoch"])
        dataset = make_dataset(
            config, self.steps_per_epoch * self.global_batch, ctx.seed)
        self.trainer = SyncTrainer(dataset, DistributedConfig(
            mode="sync", num_workers=self.chips,
            learning_rate=float(config["optimizer"]["learning_rate"]),
            num_epochs=10 ** 9,  # one train() call; the run leaves it alive
            batch_size=int(traffic["per_chip_batch"]),
            compression=traffic["exchange_dtype"],
            augment=bool(traffic["augment"]),
            num_classes=int(config["architecture"]["num_classes"]),
            dtype=config["compute_dtype"], model=config["model"],
            seed=ctx.seed))
        reg = get_registry()
        self._steps = reg.counter("dps_trainer_steps_total", mode="sync")
        self._dispatch = reg.histogram("dps_trainer_step_seconds",
                                       mode="sync")
        self._thread = TrainerThread(self.trainer.train, "sync-trainer")

    def edge(self, not_before: float, deadline: float) -> dict:
        """The first epoch end at or after the monotonic ``not_before``.
        The first edge of a run (``not_before`` 0) is the end of the first
        epoch, by which every program the window uses has run: the step, the
        evaluation, the epoch's host sync."""
        time.sleep(max(0.0, not_before - time.monotonic()))
        done = self.trainer.test_accuracies
        seen = len(done)
        while len(done) == seen:
            self._thread.check()
            if time.monotonic() > deadline:
                raise TimeoutError("no epoch ended in time")
            time.sleep(POLL_S)
        t, epochs = time.monotonic(), len(done)
        # dispatched so far: this epoch's steps and a few of the next's
        dispatch_sum, dispatch_n = self._dispatch.sum, self._dispatch.count
        steps = epochs * self.steps_per_epoch
        return {"t": t, "epochs": epochs, "steps": steps, "attempted": steps,
                "images": steps * self.global_batch,
                "steps_counted": int(self._steps.value),
                "dispatch_sum_s": dispatch_sum, "dispatch_n": dispatch_n}

    def _epochs(self, upto: int) -> list[tuple[float, float]]:
        """(mean training loss, test accuracy) of epochs 1..``upto``, as
        far as the trainer has reported them. It keeps its accuracies; its
        losses it only prints."""
        losses = {}
        with open(self.ctx.log_path, errors="replace") as f:
            for line in f:
                m = EPOCH_LINE.match(line)
                if m:
                    losses[int(m.group(1))] = float(m.group(2))
        accuracies = list(self.trainer.test_accuracies)
        out = []
        for epoch in range(1, upto + 1):
            if epoch not in losses or epoch > len(accuracies):
                break
            out.append((losses[epoch], accuracies[epoch - 1]))
        return out

    def finish(self, first: dict, last: dict) -> tuple[dict, int]:
        """The driver's clauses of ``correct``, and the window's failed
        steps: those of epochs whose mean loss is not finite."""
        # the last epoch's line is printed a moment after its edge
        deadline = time.monotonic() + 5.0
        while (len(self._epochs(last["epochs"])) < last["epochs"]
               and time.monotonic() < deadline):
            time.sleep(0.05)
        epochs = self._epochs(last["epochs"])
        losses = [loss for (loss, _a) in epochs]
        seconds = sorted(
            self.trainer.epoch_times[first["epochs"]:last["epochs"]])
        print(f"[bench] epochs {len(epochs)} of {last['epochs']}: losses "
              f"{losses[:1]}..{losses[-1:]}, last test accuracy "
              f"{epochs[-1][1] if epochs else None}; epoch seconds in the "
              f"window min {seconds[0]:.4f} median "
              f"{seconds[len(seconds) // 2]:.4f} max {seconds[-1]:.4f}",
              flush=True)
        checks = {
            "losses_finite": len(epochs) == last["epochs"] and all(
                math.isfinite(loss) for loss in losses),
            # the trainer's own count of steps agrees with the epochs it
            # has ended: it is in the epoch after the edge's, no further
            "counts_reconcile": all(
                0 <= e["steps_counted"] - e["steps"] <= self.steps_per_epoch
                for e in (first, last)),
            "learned": bool(epochs) and learned(
                self.ctx.cell.config["learned"], losses, epochs[-1][1]),
        }
        if self.chips > 1:
            checks["replicas_identical"] = self._replicas_identical()
        failed = self.steps_per_epoch * sum(
            1 for loss in losses[first["epochs"]:] if not math.isfinite(loss))
        return checks, failed

    def _replicas_identical(self, attempts: int = 200) -> bool:
        """One parameter leaf's shards, bit for bit, across the devices.
        The trainer is still training and donates its state at every step,
        so a read can find its array already given away: read again."""
        import jax
        import numpy as np
        for _ in range(attempts):
            try:
                leaf = jax.tree_util.tree_leaves(
                    self.trainer.state.params)[0]
                shards = [np.asarray(s.data)
                          for s in leaf.addressable_shards]
            except RuntimeError:
                time.sleep(0.01)
                continue
            devices = {s.device for s in leaf.addressable_shards}
            same = all(np.array_equal(shards[0], s) for s in shards[1:])
            print(f"[bench] replicas: {len(shards)} shards on "
                  f"{len(devices)} devices, identical {same}", flush=True)
            return (same and len(shards) == self.chips
                    and np.isfinite(shards[0]).all())
        print("[bench] replicas: no read succeeded", flush=True)
        return False


def start(ctx) -> Session:
    return Session(ctx)
