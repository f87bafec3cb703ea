"""On-device epoch loop: a whole training epoch as ONE compiled program.

The reference's trainer re-fed every batch from a host DataLoader each epoch
(baseline_training.py:149-179): one host dispatch and one host-to-device
copy per batch, each of which the device can end up waiting for. Here the
dataset is uploaded ONCE (CIFAR-100's 50k uint8 images are ~150 MB — trivial
for HBM), and each epoch runs as one XLA program:

    device-side shuffle (jax.random.permutation)
    -> lax.scan over jitted train steps (gathered uint8 batches)
    -> lax.scan over the test set for top-1
    -> scalar metrics out.

Only a handful of scalars cross the host<->device link per epoch, so epoch
time approaches pure device time (not measured on the current installation).

Epoch semantics match data/cifar.py's host iterator: full shuffle, then
``n // batch_size`` full batches with the remainder dropped
(worker.py:182-187 used DataLoader(shuffle=True, drop_last default False —
the reference *kept* ragged last batches; we drop them for static shapes and
document the difference: <0.3% of data at batch 128).
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from ..data.cifar import Dataset
from .steps import make_eval_step


def prefetch_to_device(batches: Iterable, depth: int = 2,
                       device_put: Callable = jax.device_put) -> Iterator:
    """Host->device double buffering for a host batch iterator.

    Keeps ``depth`` batches' transfers in flight: ``jax.device_put``
    returns immediately (async dispatch), so batch N+1's host->device
    copy overlaps the consumer's compute on batch N instead of serializing
    in front of it — the input-side half of the double-buffered-transfer
    story (the gradient pull's half lives in the worker's comms pipeline;
    ps/worker.py). Yields ``(xb, yb)`` device pairs in the source order;
    values are exactly the source's (``device_put`` is a bitwise copy).
    ``depth=0`` degrades to a plain pass-through of host batches.
    """
    it = iter(batches)
    if depth <= 0:
        yield from it
        return
    buf: deque = deque()
    try:
        while len(buf) < depth:
            xb, yb = next(it)
            buf.append((device_put(xb), device_put(yb)))
    except StopIteration:
        pass  # fewer batches than the pipeline depth
    while buf:
        out = buf.popleft()
        nxt = next(it, None)
        if nxt is not None:
            buf.append((device_put(nxt[0]), device_put(nxt[1])))
        yield out


class DeviceEpochLoop:
    """Compiled epoch runner over a device-resident dataset.

    ``step_fn(state, images_u8, labels, rng) -> (state, {'loss','accuracy'})``
    is any train step with the standard signature (single-chip
    ``make_train_step`` or a sharded sync-DP step).
    """

    def __init__(self, dataset: Dataset, step_fn: Callable, *,
                 batch_size: int, eval_batch_size: int = 1000,
                 device_put: Callable = jnp.asarray):
        self.batch_size = batch_size
        n = (len(dataset.x_train) // batch_size) * batch_size
        self.steps_per_epoch = n // batch_size
        if self.steps_per_epoch == 0:
            raise ValueError("dataset smaller than one batch")
        self._n = n
        x_tr = device_put(np.ascontiguousarray(dataset.x_train))
        y_tr = device_put(np.ascontiguousarray(
            dataset.y_train.astype(np.int32)))

        # Pad the test set to a multiple of eval_batch_size with label -1
        # (argmax is always >= 0, so padding never counts as correct).
        n_te = len(dataset.x_test)
        pad = (-n_te) % eval_batch_size
        x_te = np.concatenate(
            [dataset.x_test,
             np.zeros((pad,) + dataset.x_test.shape[1:], np.uint8)])
        y_te = np.concatenate(
            [dataset.y_test.astype(np.int32), np.full((pad,), -1, np.int32)])
        eb = eval_batch_size
        x_te = device_put(x_te.reshape(-1, eb, *x_te.shape[1:]))
        y_te = device_put(y_te.reshape(-1, eb))
        self._n_test = n_te

        steps, bs = self.steps_per_epoch, batch_size
        eval_step = make_eval_step()

        n_total = len(dataset.x_train)
        n_test = self._n_test
        self._data = (x_tr, y_tr, x_te, y_te)

        # The dataset arrays are jit ARGUMENTS, not closure captures: a
        # closed-over array is embedded in the HLO as a constant, which makes
        # every dataset a fresh cache key (and hashes 150 MB per compile).
        # As arguments the executable is data-independent and the persistent
        # compilation cache hits across datasets and processes.
        def epoch(state, key, x_tr, y_tr, x_te, y_te):
            # Permute the FULL set, then keep the first n indices: the ragged
            # tail is dropped at random each epoch (as the host iterator's
            # shuffle-then-truncate does), not excluded permanently.
            perm = jax.random.permutation(key, n_total)[:n].reshape(steps, bs)

            def train_body(st, idx):
                xb = jnp.take(x_tr, idx, axis=0)
                yb = jnp.take(y_tr, idx, axis=0)
                st, m = step_fn(st, xb, yb, key)
                return st, (m["loss"], m["accuracy"])

            state, (losses, accs) = jax.lax.scan(train_body, state, perm)

            def eval_body(carry, batch):
                return carry + eval_step(state, *batch)[0], None

            correct, _ = jax.lax.scan(
                eval_body, jnp.zeros((), jnp.int32), (x_te, y_te))
            metrics = {
                "train_loss": jnp.mean(losses),
                "train_accuracy": jnp.mean(accs),
                "test_accuracy": correct / n_test,
            }
            return state, metrics

        self._epoch = jax.jit(epoch, donate_argnums=0)

    def run_epoch(self, state, key):
        """One epoch; returns (state, scalar metrics dict). The input state
        is donated."""
        state, metrics = self._epoch(state, key, *self._data)
        return state, {k: float(v) for k, v in metrics.items()}
