"""Async-mode worker runtime: threads driving device-compiled local steps.

Re-hosts the reference worker loop (src/workers/worker.py:350-403) against
the in-process :class:`~.store.ParameterStore` (or a gRPC client with the
same interface): register -> shard data by worker id -> per batch
[fetch params if step%K==0] -> local fwd/bwd on the accelerator ->
[push gradients if step%K==0] -> per-epoch full-test-set eval -> finished.

K-step ("--sync-steps") semantics: the reference computes gradients on every
batch but only pushes on ``batch_idx % K == 0`` batches — gradients from the
other K-1 batches are DISCARDED (worker.py:339+376; SURVEY.md quirk 7), so
K>1 trains on 1/K of the data. ``k_step_mode='faithful'`` reproduces that;
``'accumulate'`` is the corrected local-SGD behavior (mean of the window's
gradients pushed at the window end).
"""

from __future__ import annotations

import math
import threading
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from functools import cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..data.cifar import Dataset, make_batches, shard_range
from ..ops.compression import (  # hot-path imports hoisted, like ps/store
    QUANTIZED_PUSH_CODECS,
    ErrorFeedback,
    compress_push,
    fp16_compress,
    fp16_decompress,
)
from ..ops.device_codec import DeviceCodec, DevicePayload, is_device_tree
from ..telemetry import (
    GoodputAccount,
    current_wire_trace,
    now as _tnow,
    trace_enabled,
    trace_span,
    use_wire_context,
)
from ..train.device_loop import prefetch_to_device
from ..train.steps import make_eval_step, make_fused_local_step, \
    make_grad_step
from ..utils.metrics import device_fields
from ..utils.pytree import flatten_params, unflatten_params
from .store import ParameterStore

# Shared no-op bracket for goodput spans before telemetry init (and on
# the comms-pipeline thread, whose seconds overlap training compute).
_NULL_GP = nullcontext()


@dataclass
class WorkerConfig:
    batch_size: int = 128      # worker.py:474-482 distributed defaults
    num_epochs: int = 3
    sync_steps: int = 1        # K; CLI default 1 (worker.py:468)
    # 'faithful' | 'accumulate' | 'local_sgd'. local_sgd runs the DONATED
    # fused step (train/steps.py make_fused_local_step): grads + plain-SGD
    # apply + window accumulation as one compiled program, params updated
    # in place on device — no param round-trip inside the K-step window.
    # The window's gradient MEAN is pushed at the boundary (same payload
    # shape as 'accumulate'); with K=1 it matches 'faithful' bit-for-bit
    # up to +0/-0 on exactly-zero gradient entries.
    k_step_mode: str = "faithful"
    augment: bool = True
    eval_batch_size: int = 1000
    eval_each_epoch: bool = True   # worker.py:393-394
    seed: int = 0
    # Liveness ping via periodic fetch. The reference WROTE this (30 s
    # FetchParameters ping, worker.py:112-119) but never ran it — the loop
    # was dead code (SURVEY.md quirk 8). 0 disables; set e.g. 30.0 to enable
    # the capability the reference intended.
    heartbeat_interval: float = 0.0
    # Overlapped comms pipeline: pushes (and the following prefetch) run on
    # a bounded single-slot background thread while the training thread
    # computes the window's remaining batches. The per-worker RPC ORDER is
    # identical to the serial loop (push then fetch, exactly-once tokens
    # preserved); with a single worker every fetched_step is identical too
    # and curves match bit-for-bit (pinned by test). With MULTIPLE workers
    # the prefetch runs up to K-1 batches earlier than the serial loop's
    # boundary fetch, so it can observe a step another worker's push would
    # have advanced by then — at most one round per window, the same
    # no-barrier staleness class the store already tolerates (quirk 2 in
    # sync, the staleness bound in async). Pays off when sync_steps > 1
    # (there is compute to hide the comms behind).
    overlap: bool = False
    # Version-gated delta fetches: refetches send have_step so a store
    # whose step hasn't advanced answers NOT_MODIFIED (header-only) and
    # the worker keeps the params it already holds — byte-identical to a
    # full refetch at the same step, minus the wire bytes.
    delta_fetch: bool = True
    # Session resume (docs/ROBUSTNESS.md): when a remote store loses its
    # session (transient RPC failures outlive the retry budget —
    # SessionLostError), the worker re-registers, re-fetches at the
    # restored server step, and reconciles the in-flight gradient instead
    # of dying. This bounds the whole reconnect window in seconds;
    # 0 (default) disables resume and keeps the terminal-failure behavior.
    reconnect_timeout: float = 0.0
    # First reconnect retry delay; doubles per attempt (capped at 10 s).
    reconnect_backoff: float = 0.5
    # Deterministic compute-fault injection (the health demo / tests,
    # docs/OBSERVABILITY.md): at this 0-based local step, this batch's loss
    # and gradients are poisoned with NaN — the worker's own health report
    # must flag them non-finite and the cluster monitor must alert. Env
    # DPS_NAN_STEP provides the same hook to subprocess workers. None
    # disables (production default).
    nan_inject_step: int | None = None
    # Error feedback for the quantized push codecs (int8/int4/topk/
    # adaptive; docs/WIRE_PROTOCOL.md): the quantization residual of each
    # push is carried into the next step's gradient, so compressed updates
    # sum to the true gradient over time — what makes int4 and top-k
    # accuracy-safe. No effect on the none/fp16 codecs.
    error_feedback: bool = True
    # Fraction of entries a 'topk' push keeps per tensor (largest
    # magnitude; int8-quantized values + int32 indices on the wire).
    topk_frac: float = 0.01
    # Device-resident push codec (ops/device_codec.py): quantize/pack on
    # the accelerator and pull only the packed wire bytes, instead of
    # pulling fp32 gradients and encoding them with NumPy. Wire bytes and
    # error-feedback residuals are bit-identical to the NumPy reference
    # (property-tested, tests/test_quantize.py); engages only when a
    # quantized codec was negotiated and the gradients are device arrays.
    # False forces the NumPy reference path.
    device_codec: bool = True
    # Host->device input double buffering: keep this many batches'
    # transfers in flight ahead of compute (train/device_loop.py
    # prefetch_to_device), so batch N+1's upload overlaps batch N's
    # compute. 0 feeds host batches directly (the prior behavior).
    prefetch_batches: int = 2
    # 'local_sgd' mode: the worker-local SGD learning rate; None adopts
    # the store's configured learning_rate.
    local_lr: float | None = None

    def __post_init__(self):
        if self.k_step_mode not in ("faithful", "accumulate", "local_sgd"):
            raise ValueError(self.k_step_mode)
        if self.sync_steps < 1:
            raise ValueError("sync_steps must be >= 1")
        if self.prefetch_batches < 0:
            raise ValueError("prefetch_batches must be >= 0")


@dataclass
class WorkerResult:
    worker_id: int = -1
    worker_name: str = ""
    epoch_times: list = field(default_factory=list)
    test_accuracies: list = field(default_factory=list)
    local_steps_completed: int = 0
    pushes_accepted: int = 0
    pushes_rejected: int = 0
    heartbeats: int = 0
    # Session resumes survived (server restarts / network partitions the
    # reconnect state machine rode through; docs/ROBUSTNESS.md).
    reconnects: int = 0
    # Server->worker control directives acted on, by action name
    # (docs/ROBUSTNESS.md "Self-healing"); empty when none arrived.
    directives_applied: dict = field(default_factory=dict)
    # Push windows skipped under a quarantine directive.
    pushes_quarantined: int = 0
    # Client-side wire accounting (RemoteStore.wire_stats); empty for
    # in-process stores, which cross no wire.
    wire: dict = field(default_factory=dict)
    # Last batch's train loss (None until a batch ran, or when non-finite:
    # NaN never rides a JSON hop) and where the step computed, read off
    # the step's own output array — not the device it was meant for.
    final_train_loss: float | None = None
    device_id: int | None = None
    error: Exception | None = None

    def metrics(self, total_workers: int, learning_rate: float,
                config: WorkerConfig) -> dict:
        """METRICS_JSON field parity with worker.py:421-434 (+ wire
        accounting when the store is remote)."""
        out = self._base_metrics(total_workers, learning_rate, config)
        if self.directives_applied:
            out["directives_applied"] = dict(self.directives_applied)
        if self.pushes_quarantined:
            out["pushes_quarantined"] = self.pushes_quarantined
        if self.wire:
            out.update(self.wire)
        return out

    def _base_metrics(self, total_workers: int, learning_rate: float,
                      config: WorkerConfig) -> dict:
        return {
            "worker_id": self.worker_id,
            "worker_name": self.worker_name,
            "total_workers": total_workers,
            "total_training_time_seconds": round(sum(self.epoch_times), 2),
            "average_epoch_time_seconds": (
                round(float(np.mean(self.epoch_times)), 2)
                if self.epoch_times else 0.0),
            "epoch_times_seconds": [round(t, 2) for t in self.epoch_times],
            "final_test_accuracy": (self.test_accuracies[-1]
                                    if self.test_accuracies else 0.0),
            "all_test_accuracies": self.test_accuracies,
            "local_steps_completed": self.local_steps_completed,
            "batch_size": config.batch_size,
            "learning_rate": learning_rate,
            "num_epochs": config.num_epochs,
            "reconnects": self.reconnects,
            "final_train_loss": self.final_train_loss,
            **device_fields(),
            "device_id": self.device_id,
        }


def _window_mean(accum_tree, n: int):
    """Mean of an accumulated K-step gradient window — ONE definition
    shared by the serial and overlapped push paths, so their numerics
    cannot drift apart."""
    scale = np.float32(n)
    return jax.tree_util.tree_map(lambda a: a / scale, accum_tree)


class _BitwidthController:
    """Per-layer push-codec chooser for the quantized codec family.

    Fixed codecs (``int8``/``int4``/``topk``) pin the aggressiveness
    level; ``adaptive`` moves the level with measured LINK PRESSURE — the
    fraction of wall time the push spends on the wire (push RPC seconds
    over the window since the previous push, the same signal the
    ``worker.push_wait`` span and pipeline telemetry already expose).
    Sustained pressure above ``hi`` escalates int8 -> int4 -> +topk;
    sustained pressure below ``lo`` de-escalates. ``patience``
    consecutive windows are required either way, so one slow RPC doesn't
    whipsaw the codec.

    The plan is per-layer: tiny tensors (biases, norms) stay int8 at any
    level — their bytes are noise and sparse/packed overhead would exceed
    the savings; topk only applies above ``min_topk_size``.
    """

    LEVEL_NAMES = ("int8", "int4", "topk")

    def __init__(self, codec: str, hi: float = 0.25, lo: float = 0.05,
                 patience: int = 2, min_int4_size: int = 256,
                 min_topk_size: int = 4096):
        self.adaptive = codec == "adaptive"
        self.level = 0 if self.adaptive \
            else {"int8": 0, "int4": 1, "topk": 2}.get(codec, 0)
        self.hi, self.lo, self.patience = hi, lo, patience
        self.min_int4_size = min_int4_size
        self.min_topk_size = min_topk_size
        self._hot = self._cold = 0

    def note_push(self, push_seconds: float, window_seconds: float) -> None:
        """Feed one push's timing (adaptive only): RPC seconds vs the
        wall-clock window since the previous push completed."""
        if not self.adaptive or window_seconds <= 0:
            return
        pressure = push_seconds / window_seconds
        if pressure > self.hi:
            self._hot += 1
            self._cold = 0
            if self._hot >= self.patience and self.level < 2:
                self.level += 1
                self._hot = 0
        elif pressure < self.lo:
            self._cold += 1
            self._hot = 0
            if self._cold >= self.patience and self.level > 0:
                self.level -= 1
                self._cold = 0
        else:
            self._hot = self._cold = 0

    def plan(self, flat: dict) -> dict:
        """{tensor name: 'int8'|'int4'|'topk'} for this push."""
        out = {}
        for name, a in flat.items():
            # .size, not np.asarray(a).size: the flat dict may hold DEVICE
            # arrays (device codec path) and the plan must not pull them.
            size = int(a.size)
            if self.level >= 2 and size >= self.min_topk_size:
                out[name] = "topk"
            elif self.level >= 1 and size >= self.min_int4_size:
                out[name] = "int4"
            else:
                out[name] = "int8"
        return out

    def describe(self) -> str:
        name = self.LEVEL_NAMES[self.level]
        return f"adaptive({name})" if self.adaptive else name


class _CommsPipeline:
    """Bounded single-slot comms thread for one worker.

    Executes (push, then optional prefetch) work items in submission order
    on ONE background thread, so a worker's pushes stay strictly sequential
    — the RemoteStore push-token dedupe contract ("a retry always precedes
    that worker's next distinct push") holds exactly as in the serial loop
    — and a prefetch can never overtake the push it follows. At most ONE
    item is in flight: ``submit`` blocks until the previous item completed
    (natural backpressure; the depth gauge is therefore 0 or 1).

    Timing caveat: the prefetch is issued right after its push, up to K-1
    batches EARLIER than the serial loop's next-boundary fetch, so with
    multiple workers it can see a step that a peer's push would have
    advanced by boundary time — bounded at one round per window and
    within the store's existing no-barrier staleness model (see the
    ``WorkerConfig.overlap`` comment and docs/WIRE_PROTOCOL.md). With one
    worker the fetch results are identical and parity is exact.

    The training thread's contract:

    - ``submit(grads, fetched_step, prefetch_current)`` — push ``grads``
      with ``fetched_step``; if ``prefetch_current`` is not None, follow
      with a params fetch (``have_step=fetched_step``, delta-gated) whose
      result ``await_params`` later returns.
    - ``await_params()`` — block until the pending prefetch result is
      available and take it.
    - ``flush()`` — block until the pipeline is idle (epoch boundaries:
      every push must be on the server before the epoch closes).

    Comms-thread exceptions surface on the NEXT training-thread call, so a
    dead server still fails the worker (with the original traceback as
    ``__cause__``) instead of hanging it.
    """

    def __init__(self, worker: "PSWorker", worker_id: int):
        self._worker = worker
        self._worker_id = worker_id
        self._item = None
        self._error: Exception | None = None
        # The (grads, fetched_step) of a PUSH that died on the comms
        # thread — what the session-resume reconciliation must decide
        # about. A failed PREFETCH leaves this None: its push already
        # landed and must not be re-sent.
        self._failed_push = None
        self._go = threading.Event()
        self._done = threading.Event()
        self._done.set()
        self._stop = False
        self._result = None            # (params, step) of the last prefetch
        self._result_ready = threading.Event()
        self._pending_prefetch = False  # training thread only
        self._last_comms_s = 0.0
        from ..telemetry import get_registry
        reg = get_registry()
        w = str(worker_id)
        self._tm_depth = reg.gauge("dps_worker_pipeline_depth", worker=w)
        # Comms seconds the training thread did NOT spend blocked: the
        # item's comms-thread duration minus the time await/flush actually
        # waited for it — the per-window overlap win, live.
        self._tm_saved = reg.histogram("dps_worker_overlap_saved_seconds",
                                       worker=w)
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"comms-pipeline-{worker_id}")
        self._thread.start()

    # -- comms thread --------------------------------------------------------

    def _loop(self) -> None:
        while True:
            self._go.wait()
            self._go.clear()
            if self._stop:
                return
            grads, fetched_step, prefetch_current, wctx, at = self._item
            self._item = None
            # The submitting step's span attributes, for this thread's
            # always-on ``worker.codec`` spans (the training thread has
            # moved on to the next step by now).
            self._worker._span_attrs = at
            t0 = _tnow()
            try:
                # Adopt the submitting step's trace context so this item's
                # comms span (and the RPC/store spans under it) attach to
                # the step whose window hides the latency.
                with use_wire_context(wctx), \
                        trace_span("pipeline.comms",
                                   worker=self._worker_id,
                                   prefetch=prefetch_current is not None):
                    if grads is not None:
                        try:
                            self._worker._push(self._worker_id, grads,
                                               fetched_step)
                        except Exception:  # noqa: BLE001 — stash, then re-raise
                            self._failed_push = (grads, fetched_step)
                            raise
                    if prefetch_current is not None:
                        result = self._worker._fetch_params(
                            self._worker_id, have_step=fetched_step,
                            current=prefetch_current)
                        # Duration published BEFORE the ready flag: a
                        # waiter that wakes immediately must see THIS
                        # item's comms time in its overlap-savings
                        # record, not the previous one's.
                        self._last_comms_s = _tnow() - t0
                        self._result = result
                        self._result_ready.set()
            except Exception as e:  # noqa: BLE001 — surfaced via await_params
                self._error = e
                self._result_ready.set()  # wake a blocked await_params
            finally:
                self._last_comms_s = _tnow() - t0
                self._tm_depth.set(0)
                self._done.set()

    # -- training thread -----------------------------------------------------

    def _raise_if_failed(self) -> None:
        if self._error is not None:
            raise RuntimeError("comms pipeline failed") from self._error

    def submit(self, grads, fetched_step: int, prefetch_current) -> None:
        self._done.wait()  # single-slot bound: previous item must be done
        self._raise_if_failed()
        # Double-buffered gradient pull: start the device->host copies NOW,
        # on the training thread, so they run behind the next window's
        # compute and the comms thread's device_get finds the bytes already
        # on the host. A DevicePayload started its own copies at encode
        # time; device-resident stores never pull, so nothing to stage.
        if grads is not None and not isinstance(grads, DevicePayload) \
                and not getattr(self._worker.store, "keeps_device_arrays",
                                False):
            for leaf in jax.tree_util.tree_leaves(grads):
                if hasattr(leaf, "copy_to_host_async"):
                    leaf.copy_to_host_async()
        # Trace context captured on the TRAINING thread (the submitting
        # step's push_wait span) — the comms thread re-enters it.
        self._item = (grads, fetched_step, prefetch_current,
                      current_wire_trace(), self._worker._span_attrs)
        self._pending_prefetch = prefetch_current is not None
        self._done.clear()
        self._tm_depth.set(1)
        self._go.set()

    def params_pending(self) -> bool:
        return self._pending_prefetch

    def await_params(self):
        """Take the pending prefetch result; records the overlap saving
        (comms time hidden behind compute) for this window."""
        t0 = _tnow()
        self._result_ready.wait()
        waited = _tnow() - t0
        self._raise_if_failed()
        params, step = self._result
        self._result = None
        self._result_ready.clear()
        self._pending_prefetch = False
        self._tm_saved.observe(max(0.0, self._last_comms_s - waited))
        return params, step

    def flush(self) -> None:
        """Epoch barrier: wait until the in-flight item (if any) finished.
        A pending prefetch RESULT survives a flush — the next epoch's
        opening fetch consumes it."""
        self._done.wait()
        self._raise_if_failed()

    def take_failed_item(self):
        """The (grads, fetched_step) of the push that killed this
        pipeline, if any — consumed once by the session-resume
        reconciliation (ps/worker.py:_recover_session)."""
        item, self._failed_push = self._failed_push, None
        return item

    def close(self) -> None:
        # Bounded wait: a comms thread stuck deep in RPC retries must not
        # wedge worker teardown — it is a daemon thread and will observe
        # _stop when (if) its RPC returns.
        self._done.wait(timeout=120.0)
        self._stop = True
        self._go.set()
        self._thread.join(timeout=10.0)


@cache
def _eval_tx():
    """The evaluation state's optimizer, one a process: ``tx`` is a static
    field of the jitted ``eval_step``'s argument and compares by identity,
    so a fresh ``optax.identity()`` at every ``evaluate`` compiled the
    evaluation again at every epoch end."""
    import optax
    return optax.identity()


class PSWorker(threading.Thread):
    """One logical worker. Runs as a thread; compute runs on the accelerator
    via a shared jit-compiled grad step (one compile for all workers)."""

    def __init__(self, store: ParameterStore, model, dataset: Dataset,
                 config: WorkerConfig | None = None,
                 grad_step=None, eval_step=None, fused_step=None,
                 worker_name: str = "", device=None):
        super().__init__(daemon=True)
        self.store = store
        # The local device this worker computes on: fetched params and
        # input batches are placed there, and the compiled step follows
        # its inputs. None = JAX's default device.
        self._device = device
        self.model = model
        self.dataset = dataset
        self.config = config or WorkerConfig()
        self.worker_name = worker_name
        self.result = WorkerResult()
        # Step of the last successful fetch; the heartbeat thread reads it
        # to delta-gate its pings (int read/write is atomic enough).
        self._last_fetched_step: int | None = None
        # Overlapped comms pipeline (set in _run when overlap=True); an
        # attribute so the session-resume path can drain and rebuild it.
        self._pipe: _CommsPipeline | None = None
        # What the loop's phase spans carry (worker, epoch, step), a
        # thread each: set by the training thread at the top of each
        # iteration and by the comms thread from the item it was handed,
        # read by the span sites in the methods they call.
        self._span = threading.local()
        self._tm_reconnect = None  # created at _init_telemetry
        self._tm_hb_err = None
        # Worker health report (docs/OBSERVABILITY.md): built at push
        # boundaries by _note_health, shipped by the RemoteStore on every
        # fetch/push/heartbeat via the provider installed in _run. The lock
        # covers training-thread writes vs heartbeat/comms-thread reads.
        self._health_lock = threading.Lock()
        self._health: dict = {}  # guarded by: self._health_lock
        self._health_enabled = False
        self._health_rate: tuple[float, int] | None = None
        # Report revision, bumped under the lock on every mutation: lets
        # the RemoteStore cache the report's JSON encode across the many
        # heartbeat pings between boundary updates (comms/client.py
        # health_revision).
        self._health_rev = 0  # guarded by: self._health_lock
        # Quantized-codec state (set up after registration, once the
        # store's negotiated codec is known): error-feedback residuals and
        # the per-layer bitwidth controller (docs/WIRE_PROTOCOL.md).
        self._ef: ErrorFeedback | None = None
        self._bitwidth: _BitwidthController | None = None
        # Device-resident codec (ops/device_codec.py): set in _run when a
        # quantized codec is negotiated and config.device_codec is on.
        # Carries its own error-feedback residuals ON DEVICE.
        self._device_codec: DeviceCodec | None = None
        self._prev_push_done: float | None = None
        # Directive-channel state (docs/ROBUSTNESS.md "Self-healing"):
        # server->worker directives arrive on fetch/push reply meta and
        # are acted on at step boundaries by the training thread.
        self._force_full_fetch = False     # refetch_params
        self._quarantine_windows = 0       # quarantine: windows to skip
        self._epoch_break = False          # rebalance_shard
        self._draining = False             # drain
        # Injected per-step compute slowdown (comms/faults.py COMPUTE_OP):
        # set in _run from the store's fault injector, if any.
        self._compute_faults = None
        # Goodput ledger (telemetry/goodput.py): created at
        # _init_telemetry; every second of the training thread's wall is
        # classified into GOODPUT_CATEGORIES.
        self._goodput: GoodputAccount | None = None
        ns = self.config.nan_inject_step
        if ns is None:
            import os as _os
            env = _os.environ.get("DPS_NAN_STEP")
            ns = int(env) if env else None
        self._nan_step = ns
        # Shared compiled functions may be passed in to avoid re-tracing per
        # worker; otherwise built here.
        self._grad_step = grad_step or make_grad_step(
            model, augment=self.config.augment)
        self._eval_step = eval_step or jax.jit(make_eval_step())
        # local_sgd's donated fused step: built lazily in _run (only that
        # mode pays the trace) unless a shared compile was passed in.
        self._fused_step = fused_step

    # -- the training loop (worker.py:350-403) ------------------------------

    def run(self) -> None:
        self._done = threading.Event()
        try:
            self._run()
        except Exception as e:  # noqa: BLE001 — surfaced via .result
            self.result.error = e
        finally:
            self._done.set()
            if self.result.worker_id >= 0:
                try:
                    self.store.job_finished(self.result.worker_id)
                except Exception as e:  # noqa: BLE001
                    # A dead server at goodbye time must not erase an
                    # otherwise-complete run (the result already holds
                    # the training outcome); the server's liveness reaper
                    # expires the slot instead.
                    print(f"JobFinished failed for worker "
                          f"{self.result.worker_id}: {e!r}", flush=True)
            # After JobFinished so the final RPC is counted too.
            ws = getattr(self.store, "wire_stats", None)
            if callable(ws):
                self.result.wire = ws()

    def _heartbeat_loop(self, interval: float) -> None:
        """Liveness ping: periodic fetch (the reference's intended
        health_check_loop, worker.py:112-119, implemented for real).
        Delta-gated when possible: the ping's payload is discarded anyway,
        so against a store that supports it a ping costs a header whenever
        the step hasn't advanced past the training thread's last fetch.
        The worker id is re-read every tick, so after a session resume the
        same thread keeps the NEW registration alive — heartbeats
        re-establish themselves with no thread churn.

        Tick failures are COUNTED (dps_worker_heartbeat_errors_total) and
        logged once per transition into the failing state — previously they
        were swallowed silently, so a half-dead worker (pings failing,
        training limping along) was invisible until the server expired it.
        Transient blips still don't kill the thread; the next tick retries."""
        failing = False
        while not self._done.wait(interval):
            try:
                worker_id = self.result.worker_id
                have = self._last_fetched_step
                if (have is not None and self.config.delta_fetch
                        and getattr(self.store, "supports_delta_fetch",
                                    False)):
                    self.store.fetch(worker_id, have_step=have)
                else:
                    self.store.fetch(worker_id)
                self.result.heartbeats += 1
                if failing:
                    failing = False
                    print(f"HEARTBEAT_RECOVERED worker={self.worker_name} "
                          f"id={self.result.worker_id}", flush=True)
            except Exception as e:  # noqa: BLE001 — next tick retries
                if self._tm_hb_err is not None:
                    self._tm_hb_err.inc()
                with self._health_lock:
                    self._health["heartbeat_errors"] = \
                        self._health.get("heartbeat_errors", 0) + 1
                    self._health_rev += 1
                if not failing:
                    failing = True
                    print(f"HEARTBEAT_FAILING worker={self.worker_name} "
                          f"id={self.result.worker_id} err={e!r}",
                          flush=True)

    def _compute_shard(self, worker_id: int, total_workers: int):
        """This worker's contiguous data shard.

        Faithful mode: fixed split by registration id over the configured
        total (worker.py:166-179), ids wrapping into range. Elastic mode:
        split over the LIVE membership by rank among active workers — at
        epoch boundaries this rebalances coverage as workers join/leave.
        """
        n = len(self.dataset.x_train)
        # Works for remote (gRPC) stores too: elastic servers piggyback live
        # membership on Register/Fetch replies and RemoteStore caches it, so
        # its membership_snapshot() serves the same role as the in-process
        # store's lock-guarded one.
        cfg = getattr(self.store, "config", None)
        if getattr(cfg, "elastic", False) \
                and hasattr(self.store, "membership_snapshot"):
            active = self.store.membership_snapshot()
            if worker_id in active:
                rank, total = active.index(worker_id), len(active)
            else:  # raced with own expiry: keep the fallback split
                rank, total = worker_id % total_workers, total_workers
        else:
            rank, total = worker_id % total_workers, total_workers
        lo, hi = shard_range(n, rank, total)
        return self.dataset.x_train[lo:hi], self.dataset.y_train[lo:hi]

    def _init_telemetry(self, worker_id: int) -> None:
        """Per-worker live instruments (telemetry/), labeled by worker id
        so a multi-worker process's snapshot stream separates into
        per-worker time-series. Created once, after registration (the id
        IS the label)."""
        from ..telemetry import get_registry
        reg = get_registry()
        w = str(worker_id)
        self._tm_step_s = reg.histogram("dps_worker_step_seconds", worker=w)
        self._tm_steps = reg.counter("dps_worker_steps_total", worker=w)
        self._tm_epochs = reg.counter("dps_worker_epochs_total", worker=w)
        self._tm_acc = reg.gauge("dps_worker_test_accuracy", worker=w)
        # Payload bytes around the push codec: 'precodec' counts the fp32
        # gradient payload, 'wire' what actually leaves after compression
        # — the live per-worker form of the reference's one-off size log
        # (worker.py:292), and the per-update byte accounting compression
        # studies need (PAPERS.md).
        self._tm_push_pre = reg.counter("dps_worker_push_bytes_total",
                                        stage="precodec", worker=w)
        self._tm_push_wire = reg.counter("dps_worker_push_bytes_total",
                                         stage="wire", worker=w)
        self._tm_fetch_post = reg.counter("dps_worker_fetch_bytes_total",
                                          stage="postcodec", worker=w)
        # Refetches answered NOT_MODIFIED (delta fetch): the worker kept
        # its params and moved ~zero payload bytes.
        self._tm_fetch_nm = reg.counter(
            "dps_worker_fetch_not_modified_total", worker=w)
        # Session resumes survived (reconnect state machine,
        # docs/ROBUSTNESS.md). Labeled by the INITIAL registration id —
        # the logical worker's identity for the whole run, even though a
        # resume may register under a fresh id (the id is in the resume
        # log line and the worker.reconnect span attrs).
        self._tm_reconnect = reg.counter("dps_worker_reconnect_total",
                                         worker=w)
        # Heartbeat ticks that failed (satellite: a half-dead worker's
        # failing pings were previously invisible — no counter, no log).
        self._tm_hb_err = reg.counter("dps_worker_heartbeat_errors_total",
                                      worker=w)
        # Wire bytes the push codec saved vs the fp32 payload (precodec −
        # wire, cumulative), and the effective bits/value of the LAST push
        # — the live bitwidth the adaptive controller settled on
        # (32 = fp32, 8 = int8, ~4 = int4, <1 = topk).
        self._tm_push_saved = reg.counter(
            "dps_worker_push_bytes_saved_total", worker=w)
        self._tm_push_bits = reg.gauge("dps_worker_push_bitwidth", worker=w)
        # Push-codec seconds per push (device encode + packed-bytes pull,
        # or the NumPy compress when the device codec is off), and the
        # device->host gradient-pull seconds that ran on the comms
        # pipeline thread instead of blocking the training thread — the
        # double-buffered-transfer win, live (docs/OBSERVABILITY.md).
        self._tm_codec_s = reg.histogram("dps_worker_codec_seconds",
                                         worker=w)
        self._tm_d2h_saved = reg.histogram(
            "dps_worker_d2h_overlap_saved_seconds", worker=w)
        # Server->worker directives acted on, one series per catalog
        # action (docs/ROBUSTNESS.md "Self-healing").
        from ..comms.service import DIRECTIVE_CATALOG
        self._tm_directives = {
            a: reg.counter("dps_worker_directives_total", worker=w,
                           action=a)
            for a in DIRECTIVE_CATALOG
        }
        # Wall-clock goodput ledger: the shared cumulative counters sum
        # worker-seconds across every account in the process; the
        # instance keeps its own totals so _note_health reports an
        # honest per-worker goodput fraction.
        self._goodput = GoodputAccount(reg)

    def _gp(self, category: str):
        """Goodput bracket for the TRAINING thread's wall. The
        comms-pipeline thread's overlapped work is deliberately NOT
        charged — those seconds run under the window's compute, and
        charging them would make the categories sum past the wall."""
        gp = self._goodput
        if gp is None:
            return _NULL_GP
        pipe = self._pipe
        if pipe is not None and threading.current_thread() is pipe._thread:
            return _NULL_GP
        return gp.span(category)

    def _compute_category(self) -> str:
        """Quarantined windows still burn device seconds, but their
        pushes are dropped at the boundary — that wall is idle-by-
        directive, not goodput."""
        return "quarantine_idle" if self._quarantine_windows > 0 \
            else "compute"

    # -- worker health report (docs/OBSERVABILITY.md) ------------------------

    def _health_snapshot(self) -> dict | None:
        """Provider installed on the RemoteStore: the current report, or
        None before the first boundary note (a report-less heartbeat is a
        valid legacy ping, not an error)."""
        with self._health_lock:
            return dict(self._health) if self._health else None

    def _health_revision(self) -> int:
        """Companion provider: the report's revision, so the store can
        reuse its cached JSON encode while the report is unchanged
        (heartbeat pings far outnumber boundary updates)."""
        with self._health_lock:
            return self._health_rev

    def _note_health(self, loss, grads_tree, epoch: int,
                     grad_scale: float = 1.0) -> None:
        """Refresh the health report at a push boundary — the one place the
        loop already synchronizes with the device, so the float() / norm
        materializations add no extra sync points. Skipped entirely unless
        the store advertised the health_report capability (zero cost for
        unmonitored runs).

        ``grads_tree`` must be (proportional to) what is PUSHED — in
        accumulate mode that is the window's gradient sum with
        ``grad_scale=1/n`` (norm of the pushed mean; a NaN from ANY batch
        in the window is in the sum, so the finite check flags exactly the
        payload that poisons the server, not just the boundary batch)."""
        if not self._health_enabled:
            return
        try:
            lval = float(loss)
        except (TypeError, ValueError):
            lval = float("nan")
        try:
            import jax.numpy as jnp
            sq = sum(jnp.sum(jnp.square(jnp.asarray(g, jnp.float32)))
                     for g in jax.tree_util.tree_leaves(grads_tree))
            gval = float(jnp.sqrt(sq)) * float(grad_scale)
        except (TypeError, ValueError):
            gval = float("nan")
        loss_finite = math.isfinite(lval)
        grad_finite = math.isfinite(gval)
        now = time.time()
        steps = self.result.local_steps_completed
        eps = None
        prev = self._health_rate
        if prev is not None and now > prev[0] and steps > prev[1]:
            eps = (steps - prev[1]) * self.config.batch_size \
                / (now - prev[0])
        self._health_rate = (now, steps)
        pipe = self._pipe
        depth = 0 if pipe is None or pipe._done.is_set() else 1
        gpf = self._goodput.fraction() if self._goodput is not None \
            else None
        with self._health_lock:
            h = self._health
            h["step"] = steps
            h["epoch"] = epoch
            # Non-finite values travel as null + a false finite flag so
            # NaN never rides a JSON hop (telemetry/cluster.py schema).
            h["loss"] = round(lval, 6) if loss_finite else None
            h["loss_finite"] = loss_finite
            h["grad_norm"] = round(gval, 6) if grad_finite else None
            h["grad_finite"] = grad_finite
            if eps is not None:
                h["examples_per_s"] = round(eps, 3)
            h["pipeline_depth"] = depth
            h["reconnects"] = self.result.reconnects
            # Negotiated push codec, live (the adaptive controller's
            # CURRENT level, '+ef' when error feedback is on) — surfaces
            # in /cluster and the `cli status` worker table.
            codec = self._bitwidth.describe() if self._bitwidth \
                else getattr(self.store, "push_codec", "none")
            h["push_codec"] = codec + ("+ef" if self._ef is not None
                                       else "")
            if gpf is not None:
                # Productive fraction of this worker's wall so far
                # (telemetry/goodput.py) — the status/top goodput column.
                h["goodput_fraction"] = round(gpf, 4)
            h.setdefault("heartbeat_errors", 0)
            self._health_rev += 1

    # -- directive channel (docs/ROBUSTNESS.md "Self-healing") ---------------

    def _poll_directives(self) -> None:
        """Drain and act on server->worker directives (step boundaries —
        the places the loop already talks to the server). No-op against
        stores without the channel (in-process, legacy servers)."""
        take = getattr(self.store, "take_directives", None)
        if not callable(take):
            return
        try:
            directives = take()
        except Exception:  # noqa: BLE001 — directives must not kill a run
            return
        for d in directives:
            self._apply_directive(d)

    def _apply_directive(self, d: dict) -> None:
        action = d.get("action")
        if action == "refetch_params":
            # Drop the delta basis: the next boundary fetch is a full
            # fresh fetch even if the step did not advance.
            self._force_full_fetch = True
        elif action == "quarantine":
            try:
                steps = max(1, int(d.get("steps", 3)))
            except (TypeError, ValueError):
                steps = 3
            self._quarantine_windows = max(self._quarantine_windows, steps)
            if self._ef is not None:
                # The residual carry may hold the same poison the server
                # quarantined us for — restart it clean.
                self._ef = ErrorFeedback()
            if self._device_codec is not None:
                self._device_codec.reset()  # same carry, device-resident
            self._force_full_fetch = True
        elif action == "rebalance_shard":
            # Finish the current epoch early; the next epoch recomputes
            # the shard from live membership (the per-epoch reshard the
            # loop already does).
            self._epoch_break = True
        elif action == "drain":
            self._draining = True
        else:
            return  # unknown directive from a newer server: ignore
        self.result.directives_applied[action] = \
            self.result.directives_applied.get(action, 0) + 1
        tm = getattr(self, "_tm_directives", None)
        if tm and action in tm:
            tm[action].inc()
        print(f"DIRECTIVE worker={self.worker_name} "
              f"id={self.result.worker_id} action={action} "
              f"seq={d.get('seq')}", flush=True)

    def _run(self) -> None:
        t_run0 = _tnow()
        cfg = self.config
        worker_id, total_workers = self.store.register_worker(self.worker_name)
        self.result.worker_id = worker_id
        self.result.worker_name = self.worker_name
        self._init_telemetry(worker_id)
        # Quantized push codec (negotiated: the store advertised it at
        # registration): error-feedback residuals + the per-layer bitwidth
        # controller. Legacy servers advertise fp16/none and neither
        # engages — same degradation discipline as delta-fetch.
        codec = getattr(self.store, "push_codec", "none")
        if codec in QUANTIZED_PUSH_CODECS:
            self._ef = ErrorFeedback() if cfg.error_feedback else None
            self._bitwidth = _BitwidthController(codec)
            if cfg.device_codec:
                # Device-resident encode (ops/device_codec.py): when the
                # gradients are device arrays the quantize/pack runs on
                # the accelerator and only the packed wire bytes cross
                # the link — bit-identical to the NumPy path, which
                # remains the fallback (host-resident trees) and the
                # server-side decode. Its EF carry supersedes self._ef
                # whenever it engages (one push never pays both).
                self._device_codec = DeviceCodec(
                    error_feedback=cfg.error_feedback,
                    topk_frac=cfg.topk_frac)
        # Health reports ride fetch/push/heartbeat envelopes when the
        # server advertised the capability at registration; otherwise the
        # note path stays disabled and costs nothing (the same degradation
        # discipline as delta-fetch / trace-context).
        if getattr(self.store, "supports_health_report", False) \
                and hasattr(self.store, "health_provider"):
            self.store.health_provider = self._health_snapshot
            if hasattr(self.store, "health_revision"):
                self.store.health_revision = self._health_revision
            self._health_enabled = True
        # Injected compute slowdown (comms/faults.py 'compute' pseudo-op):
        # the same --faults spec that drives RPC chaos can make THIS
        # worker a deterministic straggler.
        injector = getattr(self.store, "faults", None)
        if injector is not None and hasattr(injector,
                                            "maybe_delay_compute"):
            self._compute_faults = injector
        if cfg.heartbeat_interval > 0:
            threading.Thread(
                target=self._heartbeat_loop,
                args=(cfg.heartbeat_interval,),
                daemon=True).start()

        # Template structure for flat<->pytree conversion.
        h, w = self.dataset.x_train.shape[1:3]
        variables = self.model.init(
            jax.random.PRNGKey(cfg.seed),
            np.zeros((1, h, w, 3), np.float32), train=False)
        batch_stats = variables.get("batch_stats", {})  # ViT has no BN
        params = variables["params"]

        rng = jax.random.PRNGKey(cfg.seed + worker_id)
        fetched_step = 0
        params = None
        k = cfg.sync_steps
        accum = None
        accum_n = 0
        # local_sgd mode: the donated fused step walks a LOCAL parameter
        # trajectory between push boundaries (train/steps.py). local_params
        # is an explicit COPY of the fetched params — the fused step
        # donates its inputs, and the fetched tree must stay intact as the
        # delta-fetch basis.
        local_sgd = cfg.k_step_mode == "local_sgd"
        local_params = None
        local_lr = None
        if local_sgd:
            if self._fused_step is None:
                self._fused_step = make_fused_local_step(
                    self.model, augment=cfg.augment)
            local_lr = cfg.local_lr
            if local_lr is None:
                local_lr = float(getattr(
                    getattr(self.store, "config", None),
                    "learning_rate", 0.1) or 0.1)
            local_lr = np.float32(local_lr)
        # Overlapped comms: pushes + prefetches ride a bounded single-slot
        # background thread; the RPC sequence is IDENTICAL to the serial
        # loop (see _CommsPipeline), only the training thread stops
        # blocking on it. Held as an attribute so the session-resume path
        # can drain and rebuild it (docs/ROBUSTNESS.md).
        self._pipe = _CommsPipeline(self, worker_id) if cfg.overlap else None

        gp = self._goodput
        if gp is not None:
            # Everything from _run entry to here — registration, codec
            # negotiation, model/template init, pipeline spin-up — is the
            # startup bucket; backdating the wall anchor puts it INSIDE
            # the wall so the ledger reconciles end to end.
            gp.add("startup", _tnow() - t_run0)
            gp.start_wall(t_run0)
        loss = None  # last batch's loss array; None until a batch ran
        try:
            for epoch in range(cfg.num_epochs):
                t_epoch = time.time()
                self._epoch_break = False
                # The epoch's first fetch happens BEFORE the shard
                # computation: batch 0 is always a fetch boundary anyway
                # (batch_idx % K == 0), and hoisting it means a REMOTE
                # store's membership cache is fresh when the shard is
                # computed — at registration time the first worker only
                # sees itself, and an epoch-1 shard computed from that
                # would cover the whole dataset. An overlapped pipeline's
                # pending prefetch serves the same role (it IS a fetch,
                # moments old, and refreshed the membership cache).
                # The opening fetch gets its own root trace entry (attr
                # epoch_open): a worker stuck here — a stale server, a
                # slow wire — shows up in the straggler report as a
                # fetch-wait-dominant step rather than vanishing into
                # epoch bookkeeping.
                at = self._span_attrs = {
                    "worker": worker_id, "epoch": epoch,
                    "step": self.result.local_steps_completed}
                with trace_span("worker.step", root=True, always=True,
                                epoch_open=True, **at):
                    with trace_span("worker.fetch_wait", always=True, **at):
                        params, fetched_step = self._boundary_fetch(
                            worker_id, fetched_step, params)
                # A session resume inside the fetch may have re-registered
                # under a fresh id; everything downstream (shard, spans,
                # pushes) must use the CURRENT registration.
                worker_id = self.result.worker_id
                # Contiguous shard by worker id (worker.py:166-179); ids
                # beyond total_workers wrap (vs the reference's skewed
                # coverage, SURVEY.md quirk 10). Recomputed each epoch: in
                # elastic mode the split covers the LIVE membership, so a
                # net-new joiner takes a fair slice instead of doubling up
                # on a shard.
                x_shard, y_shard = self._compute_shard(worker_id,
                                                       total_workers)
                batches = make_batches(x_shard, y_shard, cfg.batch_size,
                                       seed=cfg.seed * 1000 + epoch)
                if cfg.prefetch_batches > 0:
                    # Input double buffering: batch N+1's host->device
                    # upload overlaps batch N's compute (device_put is
                    # async dispatch; train/device_loop.py). Bitwise the
                    # same batches, off the critical path.
                    batches = prefetch_to_device(
                        batches, depth=cfg.prefetch_batches,
                        device_put=partial(jax.device_put,
                                           device=self._device))
                for batch_idx, (xb, yb) in enumerate(batches):
                    boundary = batch_idx % k == 0
                    # One ROOT trace per loop iteration: fetch wait,
                    # compute, and push wait nest under it, the push's
                    # context crosses the wire, and the server's
                    # handler/store/apply spans join the same trace —
                    # the per-step causal tree the critical-path
                    # attribution consumes (analysis/traces.py).
                    # Recorded in every run (``always``), --trace or not,
                    # and never touching the device: ``cli perf phases``
                    # reads the loop's phases from /debug/trace or a dump
                    # of a run nobody thought to trace.
                    at = self._span_attrs = {
                        "worker": worker_id, "epoch": epoch,
                        "step": self.result.local_steps_completed}
                    with trace_span("worker.step", root=True, always=True,
                                    **at):
                        if boundary and batch_idx > 0:
                            with trace_span("worker.fetch_wait",
                                            always=True, **at):
                                params, fetched_step = \
                                    self._boundary_fetch(
                                        worker_id, fetched_step, params)
                            worker_id = self.result.worker_id

                        t_step = _tnow()
                        if local_sgd:
                            if boundary:
                                # Window open: adopt the fetched params as
                                # the local trajectory (fresh copy — the
                                # fused step donates) and zero the window
                                # accumulator.
                                local_params = jax.tree_util.tree_map(
                                    lambda a: jnp.array(a), params)
                                accum = jax.tree_util.tree_map(
                                    jnp.zeros_like, local_params)
                                accum_n = 0
                            with trace_span("worker.compute", always=True,
                                            **at), \
                                    self._gp(self._compute_category()):
                                (local_params, accum, batch_stats, loss,
                                 acc) = self._fused_step(
                                    local_params, accum, batch_stats,
                                    xb, yb, rng,
                                    self.result.local_steps_completed,
                                    local_lr)
                                if trace_enabled():
                                    jax.block_until_ready(accum)
                            grads = None
                        else:
                            with trace_span("worker.compute", always=True,
                                            **at), \
                                    self._gp(self._compute_category()):
                                grads, batch_stats, loss, acc = \
                                    self._grad_step(
                                        params, batch_stats, xb, yb, rng,
                                        self.result.local_steps_completed)
                                if trace_enabled():
                                    # --trace only (the span itself is
                                    # recorded in every run and must not
                                    # synchronize anything):
                                    # pin jax's async dispatch so
                                    # device time lands on THIS span
                                    # instead of on whichever later span
                                    # first materializes the grads (the
                                    # codec's device_get would otherwise
                                    # absorb the whole step and poison the
                                    # attribution).
                                    jax.block_until_ready(grads)
                        if self._nan_step is not None \
                                and self.result.local_steps_completed \
                                == self._nan_step:
                            # Deterministic compute-fault injection
                            # (WorkerConfig.nan_inject_step / DPS_NAN_STEP):
                            # poison THIS batch — the health report must
                            # flag it and the cluster monitor must alert.
                            nan = np.float32("nan")
                            if local_sgd:
                                # Poison the window accumulator — that is
                                # what gets pushed at the boundary.
                                accum = jax.tree_util.tree_map(
                                    lambda a: a * nan, accum)
                            else:
                                grads = jax.tree_util.tree_map(
                                    lambda a: a * nan, grads)
                            loss = loss * nan
                            print(f"fault injection: NaN gradients/loss at "
                                  f"worker={self.worker_name} local_step="
                                  f"{self.result.local_steps_completed}",
                                  flush=True)
                        if self._compute_faults is not None:
                            # Deterministic straggler injection: the sleep
                            # lands inside the step timing, so the health
                            # report's throughput and the straggler_lag
                            # rule see it like real slow compute.
                            self._compute_faults.maybe_delay_compute()
                        # Span = dispatch-to-return of the compiled step.
                        # Under jax async dispatch that can undercount
                        # device time on non-boundary batches; boundary
                        # steps (push/fetch) force completion, so the
                        # per-window totals stay honest.
                        self._tm_step_s.observe(_tnow() - t_step)
                        self._tm_steps.inc()
                        self.result.local_steps_completed += 1

                        if local_sgd:
                            accum_n += 1
                            if accum_n == k:
                                self._note_health(loss, accum, epoch,
                                                  grad_scale=1.0 / accum_n)
                                params, fetched_step = \
                                    self._dispatch_push_mean(
                                        worker_id, accum, accum_n,
                                        fetched_step, params)
                                worker_id = self.result.worker_id
                                accum, accum_n = None, 0
                        elif cfg.k_step_mode == "accumulate" and k > 1:
                            accum = grads if accum is None else \
                                jax.tree_util.tree_map(
                                    lambda a, b: a + b, accum, grads)
                            accum_n += 1
                            if accum_n == k:
                                self._note_health(loss, accum, epoch,
                                                  grad_scale=1.0 / accum_n)
                                params, fetched_step = \
                                    self._dispatch_push_mean(
                                        worker_id, accum, accum_n,
                                        fetched_step, params)
                                worker_id = self.result.worker_id
                                accum, accum_n = None, 0
                        elif boundary:
                            # Faithful: push THIS batch's gradients; the
                            # other K-1 batches' gradients are computed
                            # and dropped (quirk 7).
                            self._note_health(loss, grads, epoch)
                            params, fetched_step = self._dispatch_push(
                                worker_id, grads, fetched_step, params)
                            worker_id = self.result.worker_id

                    if gp is not None:
                        # Wall accrues step by step whether or not a
                        # category claimed it (residual -> 'other').
                        gp.tick_wall()
                    if self._draining or self._epoch_break:
                        # Directive: stop this epoch's batch loop at the
                        # step boundary (rebalance_shard resumes at the
                        # next epoch with a fresh shard; drain exits the
                        # run after the epoch bookkeeping below).
                        break

                # An epoch ending mid-window flushes the partial
                # accumulator, divided by the ACTUAL number of accumulated
                # batches — it must not leak into the next epoch's first
                # window (which would push a >K-batch sum divided by K,
                # against stale params).
                if accum is not None:
                    self._note_health(loss, accum, epoch,
                                      grad_scale=1.0 / accum_n)
                    params, fetched_step = self._dispatch_push_mean(
                        worker_id, accum, accum_n, fetched_step, params)
                    worker_id = self.result.worker_id
                    accum, accum_n = None, 0
                if self._pipe is not None:
                    # Epoch barrier: the epoch's last push must be ON the
                    # server before the epoch closes, so epoch timings and
                    # sync-round accounting match the serial loop; the
                    # prefetch RESULT survives into the next epoch's
                    # opening fetch.
                    try:
                        self._pipe.flush()
                    except Exception as e:  # noqa: BLE001 — session recovery
                        params, fetched_step = self._recover_session(e)
                        worker_id = self.result.worker_id

                self.result.epoch_times.append(time.time() - t_epoch)
                self._tm_epochs.inc()
                if loss is not None:
                    # The worker's device-complete edge: the last step's
                    # loss is on the host, so every step of this worker's
                    # epoch is finished on the device (the store's own
                    # edge is ``store.sync``).
                    with trace_span(
                            "worker.epoch_sync", root=True, always=True,
                            worker=worker_id, epoch=epoch,
                            steps=self.result.local_steps_completed) as sp:
                        lval = float(loss)
                        sp.attrs["ready_mono"] = time.monotonic()
                    self.result.final_train_loss = \
                        round(lval, 6) if math.isfinite(lval) else None
                    self.result.device_id = min(
                        d.id for d in loss.devices())
                if cfg.eval_each_epoch:
                    with trace_span("worker.eval", root=True, always=True,
                                    worker=worker_id, epoch=epoch), \
                            self._gp("compute"):
                        self.result.test_accuracies.append(
                            self.evaluate(params, batch_stats))
                    self._tm_acc.set(self.result.test_accuracies[-1])
                # Per-epoch progress line (the reference workers logged
                # epochs to CloudWatch, worker.py:329-335);
                # run_wire_matrix's elastic cell also keys its mid-run kill
                # off this marker.
                acc = (f", test_acc={self.result.test_accuracies[-1]:.4f}"
                       if self.result.test_accuracies else "")
                print(f"EPOCH_DONE worker={self.worker_name} id={worker_id} "
                      f"epoch={epoch + 1}/{cfg.num_epochs} "
                      f"time={self.result.epoch_times[-1]:.1f}s{acc}",
                      flush=True)
                if gp is not None:
                    gp.tick_wall()  # eval + epoch bookkeeping wall
                if self._draining:
                    print(f"DRAINED worker={self.worker_name} "
                          f"id={worker_id} epoch={epoch + 1}", flush=True)
                    break
        finally:
            if self._goodput is not None:
                self._goodput.tick_wall()
            if self._pipe is not None:
                self._pipe.close()

    # -- session resume (docs/ROBUSTNESS.md) ---------------------------------

    @staticmethod
    def _session_lost(exc):
        """The SessionLostError behind ``exc`` (direct, or carried as the
        ``__cause__`` of a comms-pipeline RuntimeError), else None."""
        from ..comms.client import SessionLostError
        if isinstance(exc, SessionLostError):
            return exc
        cause = getattr(exc, "__cause__", None)
        if isinstance(cause, SessionLostError):
            return cause
        return None

    def _repush_viable(self, old_fetched: int, server_step: int) -> bool:
        """Worker-side half of the staleness semantics for a gradient
        stranded by a session loss: never push a gradient whose basis is
        AHEAD of the restored server (the down-weighting math assumes
        non-negative staleness), and don't bother re-sending one the async
        staleness gate would reject anyway. Sync mode accepts any
        contribution (the no-barrier round model, quirk 2)."""
        if server_step < old_fetched:
            return False
        cfg = getattr(self.store, "config", None)
        if getattr(cfg, "mode", "sync") == "async":
            from .semantics import DEFAULT_STALENESS_BOUND
            bound = getattr(cfg, "staleness_bound",
                            DEFAULT_STALENESS_BOUND)
            return server_step - old_fetched <= bound
        return True

    def _reconcile_inflight(self, worker_id: int, inflight,
                            server_step: int) -> str:
        """Decide the fate of the gradient that was mid-push when the
        session died: discard (stale or rewound basis) or re-push. The
        re-push prefers the client's recorded request — SAME exactly-once
        token, so a push the crashed server already applied and journaled
        replays as a duplicate instead of double-applying."""
        grads_tree, old_fetched = inflight
        if not self._repush_viable(old_fetched, server_step):
            return "discarded"
        repush = getattr(self.store, "repush_last", None)
        if callable(repush):
            accepted = repush(worker_id)
            if accepted is not None:
                if accepted:
                    self.result.pushes_accepted += 1
                else:
                    self.result.pushes_rejected += 1
                return "repushed"
        # No recorded request to replay (in-process store duck-typing):
        # fall back to a fresh push with the original basis step.
        self._push(worker_id, grads_tree, old_fetched)
        return "repushed"

    def _recover_session(self, exc, inflight=None):
        """The reconnect state machine: on SessionLostError (server died
        or restarted), drain the comms pipeline, re-register — under
        elastic membership the fresh registration takes the lowest free
        slot, so sync rounds re-size to the post-restart membership
        instead of wedging — re-fetch params at the restored server step,
        reconcile the in-flight gradient, and rebuild the pipeline.
        Bounded by ``reconnect_timeout`` with exponential backoff;
        disabled (0, the default) re-raises ``exc`` unchanged. Returns the
        fresh ``(params, fetched_step)`` the training loop adopts."""
        lost = self._session_lost(exc)
        cfg = self.config
        if lost is None or cfg.reconnect_timeout <= 0:
            raise exc
        if self._pipe is not None:
            # Drain/reset: capture the failed push (if that is what died)
            # for reconciliation, then retire the comms thread. A fresh
            # pipeline starts once the new session is up.
            failed = self._pipe.take_failed_item()
            if inflight is None:
                inflight = failed
            try:
                self._pipe.close()
            except Exception:  # noqa: BLE001 — teardown must not mask
                pass
            self._pipe = None
        old_id = self.result.worker_id
        deadline = time.time() + cfg.reconnect_timeout
        delay = cfg.reconnect_backoff
        attempts = 0
        with trace_span("worker.reconnect", root=True,
                        worker=old_id) as sp, \
                self._gp("reconnect_recovery"):
            while True:
                attempts += 1
                try:
                    # The WHOLE resume attempt — register, refetch,
                    # reconcile — retries inside the window: a server
                    # that flaps again mid-refetch costs one backoff
                    # turn, not the worker (the reconcile re-push is
                    # idempotent: same token, journal-deduped).
                    # A channel that watched its server die can wedge in
                    # connect backoff even once the replacement listens
                    # on the same port — start every attempt on a fresh
                    # channel (RemoteStore.reset_channel; no-op for
                    # in-process stores).
                    reset = getattr(self.store, "reset_channel", None)
                    if callable(reset):
                        reset()
                    # Single registration attempt per turn of OUR backoff
                    # loop (the client's internal x5 backoff would blow
                    # through the reconnect window in one call).
                    if hasattr(self.store, "register_retries"):
                        worker_id, _ = self.store.register_worker(
                            self.worker_name, retries=1)
                    else:
                        worker_id, _ = self.store.register_worker(
                            self.worker_name)
                    # Fresh FULL fetch at the restored server step (the
                    # old session's delta basis is gone with the old
                    # server).
                    params, fetched_step = self._fetch_params(worker_id)
                    outcome = "none"
                    if inflight is not None:
                        outcome = self._reconcile_inflight(
                            worker_id, inflight, fetched_step)
                    break
                except ConnectionError as e:
                    if time.time() + delay > deadline:
                        sp.attrs["outcome"] = "gave_up"
                        from ..comms.client import SessionLostError
                        raise SessionLostError(
                            f"reconnect window "
                            f"({cfg.reconnect_timeout:.0f}s) exhausted "
                            f"after {attempts} attempts: {e}") from lost
                    time.sleep(delay)
                    delay = min(delay * 2.0, 10.0)
            self.result.worker_id = worker_id
            self.result.reconnects += 1
            self._tm_reconnect.inc()
            sp.attrs.update(attempts=attempts, new_worker_id=worker_id,
                            inflight=outcome)
            if cfg.overlap:
                self._pipe = _CommsPipeline(self, worker_id)
        print(f"RECONNECTED worker={self.worker_name} old_id={old_id} "
              f"new_id={worker_id} server_step={fetched_step} "
              f"attempts={attempts} inflight={outcome}", flush=True)
        return params, fetched_step

    def _boundary_fetch(self, worker_id: int, fetched_step: int, params):
        """The (pipeline-aware) boundary params fetch, resuming the
        session on failure. Returns (params pytree, fetched step).
        A pending ``refetch_params`` directive bypasses the delta basis
        (and any prefetched result) with a full fresh fetch."""
        try:
            with self._gp("fetch_wait"):
                pipe = self._pipe
                if pipe is not None and pipe.params_pending():
                    # The prefetch issued right after the window's push —
                    # its latency ran under the window's compute instead
                    # of on the critical path.
                    result = pipe.await_params()
                    if not self._force_full_fetch:
                        self._poll_directives()
                        if not self._force_full_fetch:
                            return result
                elif pipe is not None:
                    pipe.flush()  # a fetch must never overtake a push
                if self._force_full_fetch:
                    self._force_full_fetch = False
                    result = self._fetch_params(worker_id)
                else:
                    result = self._fetch_params(
                        worker_id,
                        have_step=fetched_step if params is not None
                        else None,
                        current=params)
                self._poll_directives()
                return result
        except Exception as e:  # noqa: BLE001 — session recovery
            return self._recover_session(e)

    def _dispatch_push(self, worker_id: int, grads_tree,
                       fetched_step: int, params):
        """Push now (serial) or hand to the comms pipeline with a prefetch
        of the next params riding behind it (overlapped). Returns the
        (params, fetched_step) the loop should continue with — unchanged
        on the happy path, the restored server state after a session
        resume.

        The push_wait span is the training thread's blocked time either
        way: the full push RPC when serial, the single-slot backpressure
        when overlapped (near zero while the pipeline keeps up — the
        overlap win, visible per step in the trace)."""
        if self._skip_quarantined_push():
            return params, fetched_step
        with trace_span("worker.push_wait", always=True,
                        **self._span_attrs), self._gp("push_wait"):
            item = grads_tree
            try:
                if self._pipe is None:
                    self._push(worker_id, grads_tree, fetched_step)
                else:
                    # Overlapped path: ENCODE at dispatch, on the training
                    # thread — the device quantize/pack is dispatched (and
                    # its EF residual carried) in program order before the
                    # next window's gradients touch it; the comms thread
                    # later pulls only the finished packed bytes.
                    payload = self._maybe_encode_device(grads_tree)
                    if payload is not None:
                        item = payload
                    self._pipe.submit(item, fetched_step,
                                      prefetch_current=params)
                self._poll_directives()
                return params, fetched_step
            except Exception as e:  # noqa: BLE001 — push recovery
                return self._recover_push(e, item, fetched_step)

    def _dispatch_push_mean(self, worker_id: int, accum_tree, n: int,
                            fetched_step: int, params):
        if self._skip_quarantined_push():
            return params, fetched_step
        with trace_span("worker.push_wait", always=True,
                        **self._span_attrs), self._gp("push_wait"):
            item = None
            try:
                if self._pipe is None:
                    self._push_mean(worker_id, accum_tree, n, fetched_step)
                else:
                    item = _window_mean(accum_tree, n)
                    payload = self._maybe_encode_device(item)
                    if payload is not None:
                        item = payload
                    self._pipe.submit(item, fetched_step,
                                      prefetch_current=params)
                self._poll_directives()
                return params, fetched_step
            except Exception as e:  # noqa: BLE001 — push recovery
                grads = item if item is not None \
                    else _window_mean(accum_tree, n)
                return self._recover_push(e, grads, fetched_step)

    def _skip_quarantined_push(self) -> bool:
        """Quarantine directive: this window's push stays local (the
        server refuses it anyway); the window counts down so training
        resumes pushing automatically."""
        if self._quarantine_windows <= 0:
            return False
        self._quarantine_windows -= 1
        self.result.pushes_quarantined += 1
        return True

    def _recover_push(self, exc, grads_tree, fetched_step: int):
        """Session recovery from a push dispatch. Serial case: THIS push
        died mid-RPC — it is the in-flight gradient to reconcile.
        Pipelined case: ``submit`` surfaced a PREVIOUS item's failure
        (that item is reconciled from the pipeline's failed slot) and
        this window's gradients never left — send them after the resume
        if still viable against the restored step."""
        pipelined = self._pipe is not None
        inflight = None if pipelined else (grads_tree, fetched_step)
        params, new_step = self._recover_session(exc, inflight=inflight)
        if pipelined and self._repush_viable(fetched_step, new_step):
            try:
                self._push(self.result.worker_id, grads_tree, fetched_step)
            except Exception as e2:  # noqa: BLE001 — double-flap handoff
                # The server flapped AGAIN between the resume and this
                # send: this push is now the in-flight gradient of a new
                # session loss — recover once more (bounded by its own
                # reconnect window).
                params, new_step = self._recover_session(
                    e2, inflight=(grads_tree, fetched_step))
        return params, new_step

    def _fetch_params(self, worker_id: int, have_step: int | None = None,
                      current=None):
        """One FetchParameters round trip -> (params pytree, fetched step).

        With ``have_step`` + ``current`` (the pytree fetched at that step)
        and a delta-capable store, a NOT_MODIFIED reply hands back
        ``current`` unchanged — the params a full refetch would have
        returned byte-for-byte, since the canonical step didn't move."""
        use_delta = (have_step is not None and current is not None
                     and self.config.delta_fetch
                     and getattr(self.store, "supports_delta_fetch", False))
        if use_delta:
            flat, fetched_step = self.store.fetch(worker_id,
                                                  have_step=have_step)
            if not flat and fetched_step == have_step:
                self._tm_fetch_nm.inc()
                return current, fetched_step
        else:
            flat, fetched_step = self.store.fetch(worker_id)
        with self._codec("decode"):
            if (getattr(self.store, "fetch_codec", "none")
                    in ("fp16", "bf16")
                    and not getattr(self.store, "decompresses_fetches",
                                    False)):
                # In-process compressed fetch (RemoteStore already
                # decompressed client-side — casting again would copy the
                # full parameter set a second time per fetch for nothing).
                flat = fp16_decompress(flat)
            if not getattr(self.store, "keeps_device_arrays", False):
                # Decoded (fp32) payload bytes; the on-the-wire size
                # lives in the RPC-layer counters (device stores move
                # zero bytes — skip).
                self._tm_fetch_post.inc(
                    sum(int(v.nbytes) for v in flat.values()))
            self._last_fetched_step = fetched_step
            params = unflatten_params(flat)
            if self._device is not None:
                # Host arrays upload here; a device store's references
                # (its own chip) copy chip-to-chip. No-op when already
                # there.
                params = jax.device_put(params, self._device)
            return params, fetched_step

    def _push_mean(self, worker_id, accum_tree, n: int,
                   fetched_step) -> None:
        """Push the mean of an accumulated gradient window of n batches."""
        self._push(worker_id, _window_mean(accum_tree, n), fetched_step)

    def _gradient_scales(self) -> dict:
        """The server-published per-layer absmax table (shared-scale
        quantization, docs/WIRE_PROTOCOL.md): read directly off in-process
        stores, from the registration/fetch-refreshed cache on a
        RemoteStore. Empty ({}) degrades to per-push scales."""
        fn = getattr(self.store, "gradient_scales", None)
        if not callable(fn):
            return {}
        try:
            scales, _ = fn()
            return scales
        except Exception:  # noqa: BLE001 — scales are an optimization hint
            return {}

    def _note_d2h_overlap(self, seconds: float) -> None:
        """Record device->host gradient-pull seconds that ran on the comms
        pipeline thread — pull time the training thread did NOT block on
        (the double-buffered-transfer win). Serial pulls block the trainer
        and are not 'saved'."""
        pipe = self._pipe
        if pipe is not None and threading.current_thread() is pipe._thread:
            self._tm_d2h_saved.observe(seconds)

    def _maybe_encode_device(self, grads_tree):
        """Device-resident encode of a push, if it applies: returns a
        DevicePayload (quantize/pack dispatched on the accelerator, packed
        bytes copying to the host in the background) or None when the
        NumPy reference path in ``_push`` should handle it (codec off,
        non-quantized codec, or a host-resident tree)."""
        if self._device_codec is None \
                or isinstance(grads_tree, DevicePayload):
            return None
        flat = flatten_params(grads_tree, as_numpy=False)
        if not is_device_tree(flat):
            return None
        plan = self._bitwidth.plan(flat) if self._bitwidth else None
        return self._device_codec.encode(
            flat, plan=plan, scales=self._gradient_scales())

    @property
    def _span_attrs(self) -> dict:
        return getattr(self._span, "attrs", {})

    @_span_attrs.setter
    def _span_attrs(self, attrs: dict) -> None:
        self._span.attrs = attrs

    @contextmanager
    def _codec(self, stage: str):
        """The ``worker.codec`` span and the goodput bracket around codec
        work. The device store runs no codec (it is handed device arrays
        and hands them back) and gets neither."""
        if getattr(self.store, "keeps_device_arrays", False):
            yield
            return
        with trace_span("worker.codec", always=True, stage=stage,
                        **self._span_attrs), self._gp("codec"):
            yield

    def _push(self, worker_id, grads_tree, fetched_step) -> None:
        with self._codec("encode"):
            if getattr(self.store, "keeps_device_arrays", False):
                # Device-resident store: hand over the device arrays
                # untouched — no host round-trip, no wire, no codec.
                flat = flatten_params(grads_tree, as_numpy=False)
                pre_bytes = 0
            else:
                payload = grads_tree \
                    if isinstance(grads_tree, DevicePayload) \
                    else self._maybe_encode_device(grads_tree)
                if payload is not None:
                    # Device codec: the quantize/pack already ran on the
                    # accelerator (at dispatch time when pipelined);
                    # finalize pulls ONLY the packed wire bytes.
                    t0 = _tnow()
                    flat = self._device_codec.finalize(payload)
                    pull_s = _tnow() - t0
                    self._note_d2h_overlap(pull_s)
                    self._tm_codec_s.observe(
                        payload.encode_seconds + pull_s)
                    pre_bytes = payload.pre_bytes
                else:
                    t0 = _tnow()
                    flat = flatten_params(jax.device_get(grads_tree))
                    self._note_d2h_overlap(_tnow() - t0)
                    pre_bytes = sum(int(v.nbytes) for v in flat.values())
                    # Worker-side compression (worker.py:264-268): the
                    # store/service advertises its codec; the encode
                    # happens here, once, before the wire (fp16 = the
                    # reference's cast; the quantized family — int8/int4/
                    # topk/adaptive — quantizes per the bitwidth
                    # controller's per-layer plan, against the server's
                    # shared scales when published, with error feedback
                    # carrying the residual).
                    codec = getattr(self.store, "push_codec", "none")
                    t1 = _tnow()
                    if codec == "fp16":
                        flat = fp16_compress(flat)
                        self._tm_codec_s.observe(_tnow() - t1)
                    elif codec in QUANTIZED_PUSH_CODECS:
                        plan = self._bitwidth.plan(flat) if self._bitwidth \
                            else None
                        flat = compress_push(
                            flat, plan, scales=self._gradient_scales(),
                            ef=self._ef, topk_frac=self.config.topk_frac)
                        self._tm_codec_s.observe(_tnow() - t1)
                wire_bytes = sum(int(v.nbytes) for v in flat.values())
                self._tm_push_pre.inc(pre_bytes)
                self._tm_push_wire.inc(wire_bytes)
                self._tm_push_saved.inc(max(0, pre_bytes - wire_bytes))
                if pre_bytes:
                    # Effective bits per gradient VALUE this push (fp32
                    # payload carries pre_bytes/4 values).
                    self._tm_push_bits.set(
                        round(wire_bytes * 32.0 / pre_bytes, 3))
        t0 = _tnow()
        if self.store.push(worker_id, flat, fetched_step):
            self.result.pushes_accepted += 1
        else:
            self.result.pushes_rejected += 1
        done = _tnow()
        if self._bitwidth is not None and self._prev_push_done is not None:
            # Link pressure = push RPC seconds over the window since the
            # previous push completed (adaptive codec only).
            self._bitwidth.note_push(done - t0, done - self._prev_push_done)
        self._prev_push_done = done

    def evaluate(self, params, batch_stats) -> float:
        """Full test-set top-1 (worker.py:313-331)."""
        from ..train.train_state import TrainState  # light TrainState shim
        state = TrainState.create(
            apply_fn=self.model.apply, params=params,
            batch_stats=batch_stats, tx=_eval_tx())
        # Device-resident test set, shared by every worker in the process:
        # uploaded once instead of ~30 MB per eval. Benign create race:
        # last wins.
        cache = getattr(self.dataset, "_device_test_cache", None)
        if cache is None:
            import jax.numpy as jnp
            cache = (jnp.asarray(self.dataset.x_test),
                     jnp.asarray(self.dataset.y_test.astype(np.int32)))
            self.dataset._device_test_cache = cache
        x_te, y_te = cache
        correct = total = 0
        for xb, yb in make_batches(x_te, y_te,
                                   self.config.eval_batch_size,
                                   shuffle=False, drop_remainder=False):
            c, t = self._eval_step(state, xb, yb)
            correct += int(c)
            total += int(t)
        return correct / max(total, 1)


def run_workers(store: ParameterStore, model, dataset: Dataset,
                n_workers: int, config: WorkerConfig | None = None,
                timeout: float | None = None,
                devices=None) -> list[WorkerResult]:
    """Spawn N worker threads sharing one compiled step; join them all.

    The in-process equivalent of launching N Fargate worker tasks
    (terraform/main.tf:387-435). Worker ``i`` computes on
    ``devices[i % len(devices)]`` — by default every chip of this host, so
    four workers keep four chips busy instead of queueing on chip 0.
    Virtual CPU devices share the same cores, so on the CPU backend the
    default is one device: spreading there only multiplies compiles.
    """
    config = config or WorkerConfig()
    if devices is None:
        devices = jax.local_devices()
        if jax.default_backend() == "cpu":
            devices = devices[:1]
    grad_step = make_grad_step(model, augment=config.augment)
    eval_step = jax.jit(make_eval_step())
    # local_sgd workers share ONE donated fused compile too (same shapes
    # => one executable; each call donates its own buffers).
    fused_step = make_fused_local_step(model, augment=config.augment) \
        if config.k_step_mode == "local_sgd" else None
    workers = [
        PSWorker(store, model, dataset, config, grad_step=grad_step,
                 eval_step=eval_step, fused_step=fused_step,
                 worker_name=f"worker-{i}",
                 device=devices[i % len(devices)])
        for i in range(n_workers)
    ]
    for w in workers:
        w.start()
    # Failure-detection reaper: with a worker_timeout configured, expire
    # silent workers periodically so elastic rounds shrink instead of
    # wedging on a dead worker (the capability behind --worker-timeout).
    reaper_stop = threading.Event()
    wt = getattr(store.config, "worker_timeout", None)
    if wt:
        def _reap():
            while not reaper_stop.wait(wt / 2):
                expired = store.expire_stale_workers()
                if expired:
                    print(f"expired silent workers: {expired}")
        threading.Thread(target=_reap, daemon=True).start()
    try:
        for w in workers:
            w.join(timeout)
    finally:
        reaper_stop.set()
    for w in workers:
        if w.result.error is not None:
            raise w.result.error
    return [w.result for w in workers]
