"""Jitted train/eval step factories (single-chip; the SPMD and async paths
build on these).

Reference parity: the worker hot loop zero_grad -> forward -> CE loss ->
backward (src/workers/worker.py:333-348) plus the server apply
(server.py:126-143) become ONE compiled XLA program: normalize + augment +
fwd + bwd + update, fused by XLA, bfloat16 on the MXU when the model is so
configured.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import optax

from ..data.cifar import augment_batch, normalize, standardize, to_float
from .train_state import TrainState


def cross_entropy_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean softmax cross-entropy with integer labels (worker.py:131 used
    nn.CrossEntropyLoss)."""
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def _variables(params, batch_stats):
    """BatchNorm-free models (ViT) carry an empty batch_stats collection."""
    v = {"params": params}
    if batch_stats:
        v["batch_stats"] = batch_stats
    return v


def preprocess(images_u8: jax.Array, rng: jax.Array,
               augment: bool) -> jax.Array:
    """Raw uint8 pixels to what a model reads, in torchvision's order
    (worker.py:145-154): RandomCrop/Flip on raw pixels (zero pad = black)
    -> ToTensor -> Normalize. The crop/flip gathers run on the uint8
    pixels — bit-identical floats to casting first (pure index
    permutations, zero pad in either domain) at 1/4 the gather bandwidth;
    the two batched gathers once cost ~45% of the ResNet-18 step."""
    with jax.named_scope("augment"):
        images = images_u8
        if augment:
            images = augment_batch(rng, images)
        return standardize(to_float(images))


def image_forward_backward(apply_fn: Callable, params, batch_stats,
                           images_u8: jax.Array, labels: jax.Array,
                           rng: jax.Array, augment: bool):
    """THE image forward and backward pass, under every trainer: augment ->
    standardize -> apply (train=True, batch statistics mutable) ->
    cross-entropy -> ``value_and_grad``. Returns ``(loss, grads,
    new_batch_stats, logits)``.

    The named scopes (``augment``, ``forward_backward``; ``exchange`` and
    ``update`` are the callers') tag each instruction's metadata with the
    phase it belongs to, for a profile's readers; they cost nothing at run
    time."""
    images = preprocess(images_u8, rng, augment)

    def loss_fn(p):
        outputs, mutated = apply_fn(
            _variables(p, batch_stats),
            images, train=True, mutable=["batch_stats"],
        )
        loss = cross_entropy_loss(outputs, labels)
        return loss, (outputs, mutated.get("batch_stats", {}))

    with jax.named_scope("forward_backward"):
        (loss, (logits, new_stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
    return loss, grads, new_stats, logits


def collect_moe_stats(intermediates: dict) -> list[dict]:
    """All ``moe_stats`` entries sown by SwitchMoEMlp layers
    (models/vit.py), in module-tree order — one dict per MoE layer."""
    found: list[dict] = []

    def walk(node):
        if isinstance(node, dict):
            for k, v in node.items():
                if k == "moe_stats":
                    found.extend(v)   # sow stores a tuple of entries
                else:
                    walk(v)

    walk(intermediates)
    return found


def make_train_step(augment: bool = True,
                    moe_aux_weight: float | None = None) -> Callable:
    """Build ``train_step(state, images_u8, labels, rng) -> (state, metrics)``.

    ``images_u8`` is the raw uint8 batch; normalization and augmentation
    happen on device inside the compiled program.

    ``moe_aux_weight is not None`` (MoE models): routing stats sown by
    each SwitchMoEMlp layer are collected — metrics gain ``moe_aux_loss``,
    ``moe_load_imbalance`` (max/mean expert load) and ``moe_drop_frac`` —
    and the Switch load-balance loss (mean across layers) is weighted into
    the training loss. Weight 0.0 keeps the observability with balancing
    OFF (the recorded contrast runs use it).
    """

    def train_step(state: TrainState, images_u8: jax.Array,
                   labels: jax.Array, rng: jax.Array):
        rng = jax.random.fold_in(rng, state.step)
        moe = None
        if moe_aux_weight is not None:
            images = preprocess(images_u8, rng, augment)

            def loss_fn(p):
                outputs, mutated = state.apply_fn(
                    _variables(p, state.batch_stats), images, train=True,
                    mutable=["batch_stats", "intermediates"],
                )
                layers = collect_moe_stats(
                    mutated.get("intermediates", {}))
                aux = (jnp.mean(jnp.stack([s["aux_loss"] for s in layers]))
                       if layers else jnp.float32(0.0))
                ce = cross_entropy_loss(outputs, labels)
                loss = ce + moe_aux_weight * aux
                return loss, (outputs,
                              mutated.get("batch_stats", {}),
                              {"ce": ce, "aux": aux, "layers": layers})
            grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
            with jax.named_scope("forward_backward"):
                (loss, (logits, new_stats, moe)), grads = grad_fn(
                    state.params)
        else:
            loss, grads, new_stats, logits = image_forward_backward(
                state.apply_fn, state.params, state.batch_stats,
                images_u8, labels, rng, augment)

        # "exchange", the third scope of parallel/sync_dp.py's step, has
        # nothing to hold on one chip
        with jax.named_scope("update"):
            state = state.apply_gradients(grads=grads)
            state = state.replace(batch_stats=new_stats)
        accuracy = jnp.mean(jnp.argmax(logits, -1) == labels)
        metrics = {"loss": loss, "accuracy": accuracy}
        if moe is not None and moe["layers"]:
            load = jnp.stack([s["load"] for s in moe["layers"]])  # [L, E]
            metrics.update({
                "loss": moe["ce"],            # comparable across modes
                "moe_aux_loss": moe["aux"],
                "moe_load_imbalance": jnp.mean(
                    jnp.max(load, axis=1) / jnp.maximum(
                        jnp.mean(load, axis=1), 1e-9)),
                "moe_drop_frac": jnp.mean(jnp.stack(
                    [s["drop_frac"] for s in moe["layers"]])),
            })
        return state, metrics

    return train_step


def make_grad_step(model, augment: bool = True) -> Callable:
    """Build the *worker-local* step: forward/backward WITHOUT the update.

    This is the async-mode analogue of the reference worker's
    ``train_local_batch`` (worker.py:333-348): zero_grad -> forward -> CE
    loss -> backward, with the parameter update left to the parameter store
    (server.py:126-143). Returns
    ``grad_step(params, batch_stats, images_u8, labels, rng, step)
    -> (grads, new_batch_stats, loss, accuracy)``, jit-compiled once and
    shared by all worker threads (same shapes => one executable).
    """

    @jax.jit
    def grad_step(params, batch_stats, images_u8, labels, rng, step):
        loss, grads, new_stats, logits = image_forward_backward(
            model.apply, params, batch_stats, images_u8, labels,
            jax.random.fold_in(rng, step), augment)
        accuracy = jnp.mean(jnp.argmax(logits, -1) == labels)
        return grads, new_stats, loss, accuracy

    return grad_step


def make_fused_local_step(model, augment: bool = True) -> Callable:
    """Build the DONATED fused worker-local step for ``local_sgd`` mode:
    grads + SGD apply + window-accumulator update as ONE compiled program.

    ``fused_step(params, accum, batch_stats, images_u8, labels, rng,
    step, lr) -> (new_params, new_accum, new_batch_stats, loss, accuracy)``
    with ``donate_argnums=(0, 1, 2)``: params, the gradient accumulator,
    and batch_stats are donated, so XLA updates them in place — no
    param-sized allocation and no device->host->device round-trip inside
    the K-step window. The worker trains along its LOCAL trajectory
    (params -= lr * grads each batch, the same plain-SGD apply the server
    runs) and pushes the window's accumulated gradient sum at the
    boundary; with K=1 the accumulator carries exactly one batch's
    gradients at the fetched params, so the pushed payload matches
    'faithful' mode bit-for-bit (up to +0/-0 on exactly-zero gradient
    entries: the accumulator's ``0 + g``). ``lr`` is traced (one
    executable serves any learning rate).
    """

    from functools import partial

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def fused_step(params, accum, batch_stats, images_u8, labels, rng,
                   step, lr):
        loss, grads, new_stats, logits = image_forward_backward(
            model.apply, params, batch_stats, images_u8, labels,
            jax.random.fold_in(rng, step), augment)
        new_params = jax.tree_util.tree_map(
            lambda p, g: p - lr * g, params, grads)
        new_accum = jax.tree_util.tree_map(
            lambda a, g: a + g, accum, grads)
        accuracy = jnp.mean(jnp.argmax(logits, -1) == labels)
        return new_params, new_accum, new_stats, loss, accuracy

    return fused_step


def make_eval_step() -> Callable:
    """Build ``eval_step(state, images_u8, labels) -> (correct, total)``.

    Top-1 over the full test set, matching worker.py:313-331 /
    baseline_training.py:181-199.
    """

    def eval_step(state: TrainState, images_u8: jax.Array, labels: jax.Array):
        images = normalize(images_u8)
        logits = state.apply_fn(
            _variables(state.params, state.batch_stats),
            images, train=False)
        correct = jnp.sum(jnp.argmax(logits, -1) == labels)
        return correct, labels.shape[0]

    return eval_step
