"""Synchronous data parallelism as SPMD over a named ``data`` mesh axis.

This file *is* the reference's sync mode, re-designed for TPU. The whole gRPC
round trip — worker pushes pickled fp16 gradients (worker.py:270-311), server
stashes them per worker under a lock, waits for all N, averages per-parameter
(server.py:145-169, 264-288), applies SGD (server.py:126-143), workers fetch
~45 MB of re-pickled params (server.py:213-237) — collapses into ONE compiled
program per step:

- each mesh slot ("worker") computes gradients on its contiguous shard of the
  batch,
- ``lax.pmean`` over the ``data`` axis is the per-parameter average, executed
  as an XLA all-reduce over ICI (no server process, no serialization, no
  star-topology bandwidth bottleneck),
- the SGD update runs replicated on every worker, so "fetch" is free — the
  updated params are already resident on every device.

Gradient compression: the reference casts fp32->fp16 before the wire
(worker.py:264-268, ~50% bytes). The TPU analogue is reducing in bfloat16 —
``compression='bf16'`` casts gradients before the all-reduce, halving ICI
traffic, and restores fp32 for the update.

Unlike the reference's "sync" (which returns PushReply immediately and lets
workers run ahead on stale params — SURVEY.md appendix quirk 2), this is a
true barrier: the XLA collective synchronizes all workers every step. That is
both more faithful to the *name* and strictly better behaved; the reference's
no-barrier behavior is unreproducible in SPMD and documented as such.
"""

from __future__ import annotations
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.compression import compress_for_allreduce, decompress_from_allreduce
from ..train.tasks import ImageTask
from ..train.train_state import TrainState
from .mesh import DATA_AXIS


def _int8_ring_allreduce_mean(grads, axis: str, axis_size: int, seed):
    """Quantized all-reduce as a reduce-scatter ring + all-gather ring with
    int8 payloads on every hop (EQuARX-style; PAPERS.md prior art).

    The round-3 formulation (quantize once, ``all_gather`` values+scales,
    local mean) moved N x S int8 bytes per device — O(N) in the mesh size,
    already tying bf16-pmean traffic at N=4 and ~2x it at N=8. Quantizing
    *inside* the ring keeps per-device bytes ~N-independent:

    - reduce-scatter phase: N-1 hops; each hop quantizes the running
      partial sum of ONE 1/N-sized chunk (stochastic rounding, per-hop
      seed — requantization noise stays unbiased), ``ppermute``s it to the
      next neighbor, and accumulates the received block into the local
      contribution for the next chunk. After N-1 hops device d holds the
      full sum of chunk (d+1) mod N.
    - all-gather phase: the reduced mean chunk is quantized ONCE and its
      int8+scales payload rotated N-1 hops; every device (owner included)
      applies the SAME dequantized values, so replicas stay bit-identical.

    Per-device ICI bytes: 2 (N-1)/N x S x 1B (+ scales, 4B / 32768 elems)
    vs bf16-pmean's 4 (N-1)/N x S — int8 is ~half bf16 at every N, and
    strictly below it from N=2 up (the round-3 scheme crossed above bf16
    at N>=4). Byte model asserted against compiled HLO by
    tests/test_quantize.py.
    """
    from jax.flatten_util import ravel_pytree

    from ..ops.pallas.quantize import dequantize_int8, quantize_int8

    flat, unravel = ravel_pytree(grads)
    n = axis_size
    my = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    chunk = -(-flat.size // n)
    own = jnp.pad(flat, (0, n * chunk - flat.size)).reshape(n, chunk)

    def quant(x, s):
        # Distinct PRNG stream per hop (and per device/step via ``seed``,
        # already folded with worker index + step by the caller).
        hop_seed = jax.random.randint(jax.random.fold_in(seed, s), (),
                                      0, 2 ** 31 - 1, dtype=jnp.int32)
        return quantize_int8(x, seed=hop_seed, stochastic=True)

    # -- reduce-scatter ring: partial sums travel int8 ---------------------
    part = jnp.take(own, my % n, axis=0)
    for s in range(n - 1):
        v, sc = quant(part, s)
        v = jax.lax.ppermute(v, axis, perm)
        sc = jax.lax.ppermute(sc, axis, perm)
        recv = dequantize_int8(v, sc, (chunk,))
        part = jnp.take(own, (my - s - 1) % n, axis=0) + recv

    # -- all-gather ring: the mean chunk quantized once, rotated N-1 hops --
    v, sc = quant(part / n, n - 1)
    out = jnp.zeros((n, chunk), jnp.float32)
    idx = (my + 1) % n
    out = out.at[idx].set(dequantize_int8(v, sc, (chunk,)))
    for _ in range(n - 1):
        v = jax.lax.ppermute(v, axis, perm)
        sc = jax.lax.ppermute(sc, axis, perm)
        idx = (idx - 1) % n
        out = out.at[idx].set(dequantize_int8(v, sc, (chunk,)))
    return unravel(out.reshape(-1)[:flat.size])


def shard_batch(mesh: Mesh, batch, axis: str = DATA_AXIS):
    """Place host arrays onto the mesh, batch dim split along ``axis``.

    This is the reference's data sharding (worker.py:166-179) done by the
    runtime: contiguous equal slices of the leading dim per worker slot.
    """
    sharding = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), batch)


def make_sync_dp_step(mesh: Mesh, *, axis: str = DATA_AXIS,
                      compression: str = "bf16",
                      augment: bool = True, task=None) -> Callable:
    """Build the sync data-parallel ``step(state, *batch, rng)``.

    ``task`` (train/tasks.py) is the model family's part of the step: the
    forward and backward pass on one worker's shard of ``batch`` and what
    it reports. Without one it is the image task, ``step(state, images_u8,
    labels, rng)``, with ``augment``.

    ``state`` must be built from a model constructed with
    ``axis_name=axis`` so BatchNorm statistics sync across workers (the
    sane resolution of the reference's frozen-BN defect, SURVEY.md §7(b)).
    Returns ``(state, metrics)`` with metrics pmean'd across workers.
    """
    task = task or ImageTask(augment)

    def worker_step(state: TrainState, *args):
        *batch, rng = args
        # Per-worker RNG: fold in the worker index (distinct augmentation
        # per shard) and the global step.
        widx = jax.lax.axis_index(axis)
        rng = jax.random.fold_in(jax.random.fold_in(rng, widx), state.step)

        loss, grads, new_stats, judged, extra = task.forward_backward(
            state, batch, rng, axis)

        # == server.py:145-169 aggregate_gradients_sync, as one all-reduce,
        # with compression on the wire (the reference cast fp16,
        # worker.py:264-268):
        #   bf16/fp16 -> reduced-precision pmean (half the ICI bytes)
        #   int8      -> quantized reduce-scatter + all-gather ring
        #                (~1/2 bf16's bytes, N-independent; EQuARX-style)
        with jax.named_scope("exchange"):
            if compression == "int8":
                # Dedicated PRNG stream: augment_batch consumes split(rng)
                # (= fold_in(rng, 0/1)), so the ring's hop seeds must
                # branch off a tag those small indices can never produce.
                grads = _int8_ring_allreduce_mean(
                    grads, axis, mesh.shape[axis],
                    jax.random.fold_in(rng, 0x7FFFFFFF))
            else:
                grads = compress_for_allreduce(grads, compression)
                grads = jax.lax.pmean(grads, axis)
                grads = decompress_from_allreduce(grads, compression)

        # == server.py:126-143 apply_gradients, replicated on every worker.
        with jax.named_scope("update"):
            state = state.apply_gradients(grads=grads)
            state = state.replace(batch_stats=new_stats)

        acc = task.accuracy(judged)
        metrics = {
            "loss": jax.lax.pmean(loss, axis),
            "accuracy": jax.lax.pmean(acc, axis),
            # Per-slot measurements ([N] when gathered): each logical
            # worker's OWN shard loss/accuracy — the honest basis for
            # per-worker METRICS_JSON rows (round-4 VERDICT item 10; the
            # reference's workers each report their own numbers,
            # worker.py:350-366).
            "worker_loss": loss[None],
            "worker_accuracy": acc[None],
            **extra,    # the task's own, replicated
        }
        return state, metrics

    metric_specs = {"loss": P(), "accuracy": P(),
                    "worker_loss": P(axis), "worker_accuracy": P(axis),
                    **{name: P() for name in task.extra_metrics}}
    sharded = jax.shard_map(
        worker_step,
        mesh=mesh,
        in_specs=(P(),) + (P(axis),) * task.batch_arity + (P(),),
        out_specs=(P(), metric_specs),
        check_vma=False,
    )
    # Donating the state lets XLA update params/opt_state in place instead of
    # holding both generations in HBM (same as train/baseline.py's step).
    return jax.jit(sharded, donate_argnums=0)


def make_sync_dp_eval_step(mesh: Mesh, task=None) -> Callable:
    """Build ``eval_step(state, *batch) -> (correct, total)`` (the image
    task's ``(state, images_u8, labels)`` without a ``task``) for the state
    ``make_sync_dp_step`` leaves replicated on every chip.

    On one chip it is the plain jitted step. On several, every chip
    evaluates the whole batch on its own copy of the state, which is what a
    plain ``jit`` over replicated arrays compiles to as well; but that
    would be a program for GSPMD to partition, and it cannot partition a
    kernel call (the fused attention core, ops/attention.py). Written as a
    ``shard_map`` with nothing sharded, the same program is manual and may
    hold one."""
    task = task or ImageTask()
    eval_step = task.eval_step()
    if mesh.size > 1:
        eval_step = jax.shard_map(eval_step, mesh=mesh,
                                  in_specs=(P(),) * (1 + task.batch_arity),
                                  out_specs=P(), check_vma=False)
    return jax.jit(eval_step)
