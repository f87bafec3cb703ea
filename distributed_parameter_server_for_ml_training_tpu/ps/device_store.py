"""Device-resident parameter store: the async/sync PS with params in HBM.

The reference keeps canonical params in server RAM as numpy and moves the
full ~45 MB parameter/gradient payload across the network on every fetch and
push (server.py:96, 222, 245). :class:`~.store.ParameterStore` re-hosts that
faithfully on the host CPU — which is the right shape for a *multi-host*
deployment, but on a TPU host it forces two full host<->device transfers per
worker step. This store is the TPU-native alternative for workers that share
the accelerator:

- canonical parameters live ON DEVICE as a flat ``{name: jax.Array}`` dict
  (fp32, like server.py:96's state_dict copy),
- ``fetch`` returns *references* to the current device arrays (jax arrays
  are immutable, so a fetched snapshot stays consistent while later pushes
  rebind the store to new arrays) — zero bytes moved,
- ``push`` takes device gradient arrays straight from ``jax.grad`` and
  applies the update with a jitted on-device SGD kernel — zero bytes moved.

A host with several chips runs one worker per chip (``ps/worker.py``
``run_workers``) while the store stays on the default device: a worker on
another chip copies the fetched params to its chip and ``push`` copies its
gradients back, chip to chip — never through host memory.

Aggregation/membership orchestration (sync rounds, bounded staleness,
elastic expiry, metrics) is shared with the host store via
:class:`~.store.AggregationBase` — only the three kernels differ (jitted
device mean/apply + a block_until_ready so update timings measure compute,
not dispatch). Staleness math is therefore identical to the reference
(server.py:145-169, 126-143, 171-186).

No wire codec applies (``push_codec='none'``): nothing crosses a wire. The
fp16-compression analogue for this path is the bf16/int8 *collective*
compression in parallel/sync_dp.py.
"""

from __future__ import annotations

import threading
import time
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from .store import AggregationBase, StoreConfig, _Stats
from ..telemetry import now as _tnow, trace_span


@jax.jit
def _sgd_apply_device(params: dict, grads: dict, scale):
    """p <- p - scale * g for the params present in ``grads``
    (server.py:126-143 apply_gradients; scale = lr * staleness_weight)."""
    return {
        k: (params[k] - scale * grads[k] if k in grads else params[k])
        for k in params
    }


@jax.jit
def _mean_grads_device(stacked: dict):
    """Per-parameter mean over the leading (worker) axis
    (server.py:145-169 aggregate_gradients_sync)."""
    return {k: jnp.mean(v, axis=0) for k, v in stacked.items()}


@jax.jit
def _mean_apply_device(params: dict, stacked: dict, scale):
    """Fused sync-round update: worker-mean + SGD apply in ONE compiled
    program — one dispatch per round instead of two (the round completes
    while other workers wait on the sync lock)."""
    return {
        k: (params[k] - scale * jnp.mean(stacked[k], axis=0)
            if k in stacked else params[k])
        for k in params
    }


class DeviceParameterStore(AggregationBase):
    """Thread-safe parameter store whose tensors never leave the device.

    API-compatible with :class:`~.store.ParameterStore` for in-process
    workers (register/fetch/push/job_finished/metrics), with
    ``keeps_device_arrays = True`` advertising that fetch returns jax arrays
    and push expects them (PSWorker skips its host round-trip accordingly).
    """

    keeps_device_arrays = True
    store_backend = "device"
    push_codec = "none"
    fetch_codec = "none"

    # AggregationBase's contracts re-declared (tools/dpslint checks are
    # module-local), plus this backend's own sampling counter.
    parameters: dict  # guarded by: self._param_lock
    global_step: int  # guarded by: self._param_lock
    last_seen: dict  # guarded by: self._registration_lock
    _updates_since_wait: int  # guarded by: self._wait_lock

    def __init__(self, initial_params: Mapping[str, np.ndarray],
                 config: StoreConfig | None = None):
        self.config = config or StoreConfig()
        if self.config.push_codec not in (None, "none"):
            # An EXPLICITLY requested codec cannot apply: nothing crosses a
            # wire here, so the reference's fp16 gradient quantization
            # (worker.py:264-268) is skipped — gradient numerics differ
            # from the python/native backends. Make that explicit instead of
            # silently ignoring the config.
            import warnings
            warnings.warn(
                f"DeviceParameterStore ignores push_codec="
                f"{self.config.push_codec!r}: device-resident pushes are "
                f"uncompressed fp32 (no wire); gradients skip the fp16 "
                f"quantization the python/native backends apply",
                stacklevel=2)
        if self.config.fetch_codec != "none":
            import warnings
            warnings.warn(
                f"DeviceParameterStore ignores fetch_codec="
                f"{self.config.fetch_codec!r}: fetches hand back device "
                f"arrays directly (no wire to compress)", stacklevel=2)
        self.parameters: dict[str, jax.Array] = {
            k: jnp.asarray(v, jnp.float32) for k, v in initial_params.items()
        }
        # The store lives on JAX's default device; workers computing on
        # other chips of the host hand over gradients from there (push
        # moves them here — the update must not follow them away).
        self._device = jax.local_devices()[0]
        self.global_step = 0

        self._param_lock = threading.Lock()
        self._sync_lock = threading.Lock()
        self._registration_lock = threading.Lock()
        self._wait_lock = threading.Lock()
        self._updates_since_wait = 0

        self._next_worker_id = 0
        self.active_workers: set[int] = set()
        self.last_seen: dict[int, float] = {}

        self._pending: dict[int, dict[str, jax.Array]] = {}
        self._gradients_received = 0

        self.stats = _Stats()
        self._finished_event = threading.Event()
        self._init_telemetry()
        self._init_round_state()

    # -- hot path ------------------------------------------------------------

    # dpslint: hot-path — zero-byte fetch: references, never copies
    def fetch(self, worker_id: int | None = None
              ) -> tuple[dict[str, jax.Array], int]:
        """Consistent (params, step) snapshot — references, not copies
        (immutability makes the reference's copy-under-lock, server.py:222,
        free here)."""
        t0 = _tnow()
        with trace_span("store.fetch", always=True,
                        backend=self.store_backend):
            with self._param_lock:
                payload = dict(self.parameters)
                step = self.global_step
        if worker_id is not None:
            # Registration lock: the bare dict store raced the reaper's
            # iteration in expire_stale_workers.
            with self._registration_lock:
                self.last_seen[worker_id] = time.time()
        # NOTE: the span measures the dict-copy handoff (~us) — fetch here
        # moves zero bytes by design, so this histogram is the proof, not
        # the cost (compare against the python/native backends' ms-scale
        # fetch distributions in the same snapshot stream).
        self._tm_fetch_s.observe(_tnow() - t0)
        self._tm_fetches.inc()
        return payload, step

    # dpslint: hot-path — device arrays in, device arrays applied
    def push(self, worker_id: int, gradients: Mapping[str, jax.Array],
             fetched_step: int) -> bool:
        """Accept device-array gradients; apply per the configured mode.

        Same accept/reject contract as ParameterStore.push (PushGradrients,
        ps.proto:12): sync always accepts, async rejects past the staleness
        bound.
        """
        t0 = _tnow()
        gradients = jax.device_put(dict(gradients), self._device)
        with self._registration_lock:
            self.last_seen[worker_id] = time.time()
        with self._param_lock:
            param_shapes = {k: v.shape for k, v in self.parameters.items()}
        for name, g in gradients.items():
            p_shape = param_shapes.get(name)
            if p_shape is not None and p_shape != g.shape:
                self.stats.gradients_rejected += 1
                self._tm_push_rej.inc()
                print(f"rejecting push from worker {worker_id}: {name} "
                      f"shape {g.shape} != server {p_shape}")
                return False
        try:
            # Recorded in every run, on the pushing worker's thread: who
            # pushed and whether the store took it (an accepted push's
            # staleness is on the ``store.apply`` span it encloses).
            with trace_span("store.push", always=True,
                            backend=self.store_backend,
                            worker=worker_id) as sp:
                push = (self._push_sync if self.config.mode == "sync"
                        else self._push_async)
                accepted = push(worker_id, gradients, fetched_step)
                sp.attrs["accepted"] = accepted
                return accepted
        finally:
            self._tm_push_s.observe(_tnow() - t0)

    # -- aggregation kernels (orchestration in AggregationBase) --------------

    def _mean(self, grad_dicts: list) -> dict:
        """Mean each parameter over the workers that supplied it
        (server.py:145-169 iterates params independently, so partial pushes
        average over their own supplier count)."""
        names = {n for g in grad_dicts for n in g}
        full = [n for n in names if all(n in g for g in grad_dicts)]
        # Common case — every worker supplied every param — is one jitted
        # stacked mean; stragglers (ragged pushes) are averaged per name.
        mean = _mean_grads_device(
            {n: jnp.stack([g[n] for g in grad_dicts]) for n in full})
        for n in names:
            if n not in mean:
                have = [g[n] for g in grad_dicts if n in g]
                mean[n] = jnp.mean(jnp.stack(have), axis=0)
        return mean

    def _apply(self, grads: dict, lr: float, weight: float = 1.0) -> None:
        # Kernel contract (AggregationBase): callers hold _param_lock.
        self.parameters = _sgd_apply_device(  # dpslint: ignore[lock-guard]
            self.parameters, grads,  # dpslint: ignore[lock-guard]
            jnp.float32(lr * weight))

    def _round_update(self, grad_dicts: list, lr: float) -> None:
        """Fused path for the common full round (every worker supplied
        every param): ONE dispatch for mean + apply. Ragged rounds
        (stragglers / partial pushes) fall back to the two-kernel base."""
        names = {n for g in grad_dicts for n in g}
        if any(n not in g for n in names for g in grad_dicts):
            return super()._round_update(grad_dicts, lr)
        stacked = {n: jnp.stack([g[n] for g in grad_dicts]) for n in names}
        with self._param_lock:
            self.parameters = _mean_apply_device(
                self.parameters, stacked, jnp.float32(lr))
            self.global_step += 1

    #: Sync with the device every Nth update. Waiting on EVERY update
    #: stalls the host for one device round trip per round while pushes
    #: queue behind it; correctness never needs the wait (jax dataflow
    #: orders the param chain), only update-time METRICS do.
    #: Sampling keeps update_times honest — entries measure real completion
    #: of everything queued since the last sync — while letting the update
    #: stream run at device speed between samples.
    wait_every = 8

    def _after_apply(self):
        # Counter guarded by its own lock: finish() callables (and async
        # pushes) run concurrently outside the sync lock, and a lost
        # increment would stretch the sampling interval — the only
        # backpressure on dispatched device work.
        with self._wait_lock:
            self._updates_since_wait += 1
            if self._updates_since_wait < self.wait_every:
                return False  # declined: caller must not record a timing
            self._updates_since_wait = 0
        # One consistent (parameters, step) pair under the lock; the wait
        # itself deliberately outside it (jax arrays are immutable, and
        # blocking the device under the lock would convoy every concurrent
        # push behind the wait).
        with self._param_lock:
            reference, updates = self.parameters, self.global_step
        # A device-complete stamp in every run: when the wait returns
        # (``ready_mono``), ``updates`` updates, and every gradient step
        # that fed one, are finished on the device. A floor, not a count:
        # up to workers-1 gradient steps of later updates, dispatched
        # before this apply, are finished by then too, and the host cannot
        # tell how many (two threads' dispatches overlap).
        with trace_span("store.sync", always=True,
                        backend=self.store_backend, updates=updates,
                        rejected=self.stats.gradients_rejected) as sp:
            jax.block_until_ready(reference)
            sp.attrs["ready_mono"] = time.monotonic()
        return True
