"""The one epoch loop: resume -> for epoch -> batches -> step -> epoch sync
-> evaluate -> report -> checkpoint -> final metrics.

``SyncTrainer`` (train/distributed.py), the model-parallel trainers
(train/model_parallel.py) and ``BaselineTrainer`` (train/baseline.py) all
run :meth:`EpochLoop.train`; each hands it only what differs: how a batch is
made and placed, the step, the evaluation's batches, its line, its final
METRICS_JSON rows. What the loop records (the ``trainer.*`` phase spans, the
``dps_trainer_*`` instruments, the goodput account) it records for whoever
runs it.

An epoch is published in one order, inside ``trainer.epoch_report``: the
epoch's losses are fetched and averaged, ``epoch_times`` and
``epoch_losses`` take their entry, the epoch's line goes to ``sys.stdout``
whole (one ``write`` ending in a newline, flushed), and only then does
``test_accuracies`` grow. Whoever sees ``len(test_accuracies) == n`` finds
``n`` whole lines in the log and ``n`` entries in ``epoch_losses``: a
watcher woken by the epoch's end cannot print into the epoch's line.
"""

from __future__ import annotations

import sys
import time
from types import SimpleNamespace

import jax
import numpy as np

from ..data.cifar import make_batches
from ..telemetry import GoodputAccount, get_registry, now, trace_span
from ..utils.metrics import emit_metrics_json


def phase(name: str, **attrs):
    """A phase span of the loop (telemetry/trace.py): on the trainer's
    thread and recorded in every run (``always=True``), so that the
    ``trainer.epoch`` root's self time is what is still unnamed.
    docs/OBSERVABILITY.md has the table."""
    return trace_span(name, always=True, **attrs)


class EpochLoop:
    """What a trainer with ``config`` (``seed``, ``num_epochs``),
    ``dataset``, ``state``, ``_step`` and ``_eval_step`` inherits. The
    defaults below are the image trainers' on one process; a trainer
    overrides what differs."""

    mode = "?"              # the label of its instruments and step spans

    def _init_loop(self) -> None:
        self.epoch_times: list[float] = []
        self.epoch_losses: list[float] = []     # an epoch's mean train loss
        self.test_accuracies: list[float] = []  # grows LAST (see above)
        self.global_steps = 0

    # -- what a trainer hands the loop ---------------------------------------
    def _global_batch(self) -> int:
        return self.config.batch_size

    def _train_batches(self, seed: int):
        """An epoch's host batches, in the order ``seed`` shuffles."""
        return make_batches(self.dataset.x_train, self.dataset.y_train,
                            self._global_batch(), seed=seed)

    def _shard(self, batch):
        """The host batch where the step wants it."""
        return batch

    def _train_step(self, placed, rng) -> dict:
        self.state, m = self._step(self.state, *placed, rng)
        return m

    def _fetch_step(self, m: dict):
        """The host's copy of what the report wants of a step beside its
        loss; fetched at the epoch's sync."""

    def _epoch_synced(self, metrics: list, fetched: list) -> None:
        """The epoch's steps are done: their metrics and the fetched."""

    def _eval_batching(self) -> tuple[int, bool]:
        """(images an evaluation batch holds, whether a remainder drops)."""
        return 1000, False

    def _eval_batch_count(self) -> int:
        size, drop = self._eval_batching()
        n = len(self.dataset.x_test)
        return n // size if drop else -(-n // size)

    def _eval_batches(self):
        size, drop = self._eval_batching()
        return make_batches(self.dataset.x_test, self.dataset.y_test, size,
                            shuffle=False, drop_remainder=drop)

    def _eval_state(self):
        return self.state

    def _after_restore(self) -> None:
        """Re-place restored (host) params on the mesh."""

    def _epoch_line(self, epoch: int, loss: float, acc: float,
                    seconds: float) -> str:
        raise NotImplementedError

    def _epoch_visible(self, epoch: int, acc: float) -> None:
        self.test_accuracies.append(acc)

    def _final_metrics(self, total: float) -> dict:
        """The run's METRICS_JSON row, which ``train`` returns."""
        raise NotImplementedError

    def _worker_rows(self, total: float) -> list[dict]:
        """Further METRICS_JSON rows of a run that emits them."""
        return []

    # -- the loop --------------------------------------------------------------
    def evaluate(self) -> float:
        """Top-1 over the evaluation's batches, as a fraction."""
        state = self._eval_state()
        correct = total = 0
        for batch in self._eval_batches():
            c, t = self._eval_step(state, *batch)
            correct += int(c)
            total += int(t)
        return correct / max(total, 1)

    def _run_epoch(self, epoch: int, rng, tel) -> tuple[list, float]:
        """One epoch's steps, its sync and its evaluation: the steps'
        losses (not fetched yet) and the test accuracy."""
        steps_per_epoch = len(self.dataset.x_train) // self._global_batch()
        metrics = []        # per step: what the step reports
        batches = iter(self._train_batches(self.config.seed * 997 + epoch))
        for _ in range(steps_per_epoch):
            with phase("trainer.input", epoch=epoch,
                       step=self.global_steps) as sp:
                batch = next(batches)
                sp.attrs["bytes"] = sum(a.nbytes for a in batch)
                placed = self._shard(batch)
            t_step = now()
            with phase("trainer.step", mode=self.mode, epoch=epoch,
                       step=self.global_steps), tel.goodput.span("compute"):
                metrics.append(self._train_step(placed, rng))
            tel.step_s.observe(now() - t_step)
            tel.steps.inc()
            tel.images.inc(len(batch[0]))
            self.global_steps += 1
            tel.global_step.set(self.global_steps)
            tel.goodput.tick_wall()
        # The epoch's first wait for the device. What the report wants of
        # each step is fetched as that step ends, while the device works
        # on the steps after it; only the last step's wait for the whole
        # epoch. Between them the block_until_ready, whose return is the
        # moment the host knows the epoch's steps are done (a trace's
        # readers anchor the device's clock on it), gives runs that fetch
        # nothing the same span.
        with phase("trainer.epoch_sync", epoch=epoch) as sp, \
                tel.goodput.span("compute"):
            fetched = [self._fetch_step(m) for m in metrics[:-1]]
            jax.block_until_ready([m["loss"] for m in metrics[-1:]])
            sp.attrs["ready_mono"] = time.monotonic()
            fetched += [self._fetch_step(m) for m in metrics[-1:]]
            self._epoch_synced(metrics, fetched)
        # Of several processes only rank 0 pays for the full test pass:
        # the state is replicated, so the others' evaluations would be
        # identical duplicated work on the critical path.
        acc = float("nan")
        if jax.process_index() == 0:
            with phase("trainer.eval", epoch=epoch,
                       batches=self._eval_batch_count()), \
                    tel.goodput.span("compute"):
                acc = self.evaluate()
        return [m["loss"] for m in metrics], acc

    def train(self, emit_metrics: bool = False,
              checkpoint_dir: str | None = None,
              resume: bool = False) -> dict:
        cfg = self.config
        rng = jax.random.PRNGKey(cfg.seed + 1)
        first = jax.process_index() == 0

        # Orbax checkpoint per epoch (the recovery story the reference only
        # planned: DEPLOYMENT.md:309, <30 s target in baseline_summary.json).
        mgr = None
        start_epoch = 0
        if checkpoint_dir:
            from ..checkpoint import CheckpointManager
            mgr = CheckpointManager(checkpoint_dir)
            if resume and mgr.latest_step() is not None:
                self.state = mgr.restore(self.state)
                self._after_restore()
                self.global_steps = int(self.state.step)
                start_epoch = self.global_steps // max(
                    1, len(self.dataset.x_train) // self._global_batch())
                if first:
                    print(f"resumed from step {self.global_steps} "
                          f"(epoch {start_epoch + 1})")

        # Live telemetry (telemetry/): a trainer IS the whole deployment
        # here, so one set of mode-labeled instruments gives the snapshot
        # stream its throughput series. Goodput (telemetry/goodput.py): the
        # wall classifies into compute / checkpoint / other. The host
        # enqueues a step in about a millisecond and the device works while
        # the host waits at the epoch end, so that wait is compute too; the
        # residual is host-side input and bookkeeping.
        reg = get_registry()
        tel = SimpleNamespace(
            step_s=reg.histogram("dps_trainer_step_seconds", mode=self.mode),
            steps=reg.counter("dps_trainer_steps_total", mode=self.mode),
            images=reg.counter("dps_trainer_images_total", mode=self.mode),
            global_step=reg.gauge("dps_store_global_step", backend="spmd"),
            goodput=GoodputAccount(reg))
        tm_epoch = reg.gauge("dps_trainer_epoch", mode=self.mode)
        tm_acc = reg.gauge("dps_trainer_test_accuracy", mode=self.mode)
        tel.goodput.start_wall()

        t_start = time.time()
        for epoch in range(start_epoch, cfg.num_epochs):
            with trace_span("trainer.epoch", root=True, always=True,
                            epoch=epoch, first_step=self.global_steps):
                t0 = time.time()
                losses, acc = self._run_epoch(epoch, rng, tel)
                with phase("trainer.epoch_report", epoch=epoch):
                    # one batched fetch, not a transfer a step: the edge a
                    # watcher takes at the append below stays within
                    # milliseconds of the evaluation's end
                    loss = float(np.mean(
                        [float(l) for l in jax.device_get(losses)]))
                    self.epoch_times.append(time.time() - t0)
                    self.epoch_losses.append(loss)
                    tm_epoch.set(epoch + 1)
                    if acc == acc:  # skip non-evaluating ranks' NaN
                        tm_acc.set(acc)
                    if first:
                        sys.stdout.write(self._epoch_line(
                            epoch, loss, acc, self.epoch_times[-1]) + "\n")
                        sys.stdout.flush()
                    self._epoch_visible(epoch, acc)
                if mgr is not None and first:
                    # State is replicated; process 0's copy is the full
                    # model.
                    with phase("trainer.checkpoint", epoch=epoch), \
                            tel.goodput.span("checkpoint"):
                        mgr.save(self.state)
                tel.goodput.tick_wall()
        total = time.time() - t_start
        if mgr is not None:
            mgr.close()
        metrics = self._final_metrics(total)
        if emit_metrics and first:
            for row in [metrics] + self._worker_rows(total):
                emit_metrics_json(row)
        return metrics
