"""Tasks: what a model family trains on, for the sync trainer.

``SyncTrainer`` (train/distributed.py) and the step builders
(parallel/sync_dp.py) own the epoch loop, the spans, the mesh, the gradient
exchange and the donated update, for every model. What differs between an
image classifier and a decoder LM comes with the model's family
(models/registry.py:family_of) as a task:

- how the model, its optimizer and its first state are made,
- how an epoch's batches are cut from the dataset, and the evaluation's,
- the forward and backward pass of one worker's shard of a batch: the loss,
  the model state that is not trained by gradient, the accuracy, and what
  else the step reports,
- the evaluation of a batch: ``(correct, total)``.

:class:`ImageTask` is what ``worker_step`` and ``SyncTrainer`` did before
there was a second family, moved here and not changed: the compiled
ResNet-18 and ViT-B/16 programs are the same HLO
(tests/test_image_task_unmoved.py). :class:`LMTask` brings packed token
rows (data/tokens.py), next-token cross-entropy in float32 plus the
multi-token-prediction loss, AdamW, the router-bias update, and held-out
token accuracy.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from ..data.cifar import make_batches
from .optimizers import adamw, server_sgd
from .steps import image_forward_backward, make_eval_step
from .train_state import TrainState, create_train_state

#: images one evaluation batch of the sync trainer holds
EVAL_BATCH = 1000


class ImageTask:
    """uint8 NHWC images and int32 labels; augmentation, ``standardize``,
    label cross-entropy and top-1, SGD."""

    #: arrays a batch is made of: (images_u8, labels)
    batch_arity = 2
    #: what a step reports beside loss and accuracy
    extra_metrics = ()

    def __init__(self, augment: bool = True):
        self.augment = augment

    # -- model, optimizer, state ------------------------------------------
    def make_model(self, cfg, dataset, dtype, axis_name):
        from ..models import get_model
        return get_model(cfg.model, num_classes=cfg.num_classes,
                         dtype=dtype, axis_name=axis_name,
                         image_size=dataset.x_train.shape[1])

    def make_optimizer(self, cfg):
        return server_sgd(cfg.learning_rate)

    def init_state(self, model, rng, tx, dataset) -> TrainState:
        h, w = dataset.x_train.shape[1:3]
        return create_train_state(model, rng, tx, input_shape=(1, h, w, 3))

    def place_state(self, mesh, state: TrainState) -> TrainState:
        """Where the first step finds the state: left where ``init`` put
        it, as before there were tasks. (The step then compiles twice, for
        this placement and for the replicated one its own output has;
        for these models that is 15-30 s of a cold set-up.)"""
        return state

    # -- batches -----------------------------------------------------------
    def train_batches(self, dataset, global_batch: int, seed: int):
        return make_batches(dataset.x_train, dataset.y_train, global_batch,
                            seed=seed)

    def eval_batches(self, dataset, global_batch: int):
        return make_batches(dataset.x_test, dataset.y_test, EVAL_BATCH,
                            shuffle=False, drop_remainder=False)

    def eval_batch_count(self, dataset, global_batch: int) -> int:
        return -(-len(dataset.x_test) // EVAL_BATCH)

    # -- the step's forward and backward, on one worker's shard -------------
    def forward_backward(self, state: TrainState, batch, rng, axis: str):
        """``(loss, grads, new_model_state, judged, extra)``: ``judged`` is
        what :meth:`accuracy` takes, which the step calls after its update
        (where the accuracy has always been computed); ``extra`` the
        task's own replicated metrics (``extra_metrics``)."""
        images_u8, labels = batch
        loss, grads, new_stats, logits = image_forward_backward(
            state.apply_fn, state.params, state.batch_stats, images_u8,
            labels, rng, self.augment)
        return loss, grads, new_stats, (logits, labels), {}

    def accuracy(self, judged) -> jax.Array:
        logits, labels = judged
        return jnp.mean(jnp.argmax(logits, -1) == labels)

    def eval_step(self):
        return make_eval_step()

    def record_epoch(self, registry, step_metrics: list) -> None:
        """Nothing beside what the trainer counts for every task."""


class LMTask:
    """Packed token rows ``[B, T+2]`` (data/tokens.py); the decoder's
    next-token loss in float32 plus ``mtp_lambda`` x its MTP loss, AdamW,
    the router-bias update from the counted loads, held-out token
    accuracy. What it reads of the decoder's configuration is
    models/decoder.py:DecoderConfig."""

    batch_arity = 1
    #: per step, replicated: assignments given to held / absent experts and
    #: dropped (stays 0), the held experts' max-over-mean load (mean over
    #: the expert layers), tokens trained on, the share of (pass, expert)
    #: slices of the carried weight gradients the backward pass touches
    #: (parallel/moe.py:grad_visits; mean over the expert layers)
    extra_metrics = ("moe_held", "moe_absent", "moe_dropped",
                     "moe_load_max_over_mean", "tokens",
                     "moe_grad_visits_share")

    def __init__(self, model_config=None):
        self.model_config = model_config    # a config object, a preset name
        self._said_routing_kept = False

    def make_model(self, cfg, dataset, dtype, axis_name):
        from ..models import get_model
        from ..models.registry import lm_config
        self.model_config = lm_config(cfg.model, self.model_config)
        if dataset.vocab_size > self.model_config.vocab_size:
            raise ValueError(
                f"the data draws ids below {dataset.vocab_size}, the model "
                f"holds {self.model_config.vocab_size} rows of vocabulary")
        return get_model(cfg.model, dtype=dtype, config=self.model_config)

    def make_optimizer(self, cfg):
        # cfg.optimizer: AdamW's b1, b2, eps, weight_decay where the run
        # states them (a benchmark configuration does); adamw's defaults
        return adamw(cfg.learning_rate, **(cfg.optimizer or {}))

    def init_state(self, model, rng, tx, dataset) -> TrainState:
        mc = self.model_config
        bias = jnp.zeros((mc.expert_layers, mc.n_routed_experts),
                         jnp.float32)
        # parameter shapes do not depend on the sequence length

        @jax.jit
        def init(rng):      # an argument: one program for every seed
            tokens = jnp.zeros((1, 10), jnp.int32)
            return TrainState.create(
                apply_fn=model.apply,
                params=model.init(rng, tokens, bias)["params"],
                batch_stats={"router_bias": bias}, tx=tx)

        return init(rng)

    def place_state(self, mesh, state: TrainState) -> TrainState:
        """Replicated over the mesh, which is how the step returns it: the
        step then compiles once (75 s a compile at the published widths),
        not once for the initial placement and once for its own output's."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(state, NamedSharding(mesh, P()))

    def train_batches(self, dataset, global_batch: int, seed: int):
        from ..data.tokens import make_token_batches
        return ((rows,) for rows in make_token_batches(
            dataset.train, global_batch, seed=seed))

    def eval_batches(self, dataset, global_batch: int):
        from ..data.tokens import make_token_batches
        return ((rows,) for rows in make_token_batches(
            dataset.test, min(global_batch, len(dataset.test)),
            shuffle=False))

    def eval_batch_count(self, dataset, global_batch: int) -> int:
        return len(dataset.test) // min(global_batch, len(dataset.test))

    def forward_backward(self, state: TrainState, batch, rng, axis: str):
        from ..parallel import moe
        (tokens,) = batch
        mc = self.model_config
        bias = state.batch_stats["router_bias"]

        def loss_fn(params):
            out = state.apply_fn({"params": params}, tokens, bias)
            return out["loss"], out

        with jax.named_scope("forward_backward"):
            (loss, out), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)

        first, held = mc.held_experts
        mine = out["loads"][:, first:first + held]          # this worker's
        loads = jax.lax.psum(out["loads"], axis)            # every worker's
        with jax.named_scope("update"):
            new_bias = jax.vmap(
                lambda b, l: moe.bias_update(b, l, mc.bias_update_gamma))(
                    bias, loads)
        held_loads = loads[:, first:first + held].astype(jnp.float32)
        extra = {
            "moe_held": jnp.sum(held_loads),
            "moe_absent": (jnp.sum(loads) - jnp.sum(held_loads)).astype(
                jnp.float32),
            "moe_dropped": jax.lax.psum(
                jnp.sum(mine) - out["processed"], axis).astype(jnp.float32),
            "moe_load_max_over_mean": jnp.mean(
                jnp.max(held_loads, axis=1)
                / jnp.maximum(jnp.mean(held_loads, axis=1), 1e-9)),
            "tokens": jax.lax.psum(out["count"], axis).astype(jnp.float32),
            "moe_grad_visits_share": jax.lax.pmean(
                _grad_visits_share(mc, mine, tokens), axis),
        }
        return (loss, grads, {"router_bias": new_bias},
                (out["correct"], out["count"]), extra)

    def accuracy(self, judged) -> jax.Array:
        correct, count = judged
        return correct / count

    def eval_step(self):
        def eval_step(state: TrainState, tokens: jax.Array):
            out = state.apply_fn({"params": state.params}, tokens,
                                 state.batch_stats["router_bias"])
            return out["correct"], out["count"]
        return eval_step

    def record_epoch(self, registry, step_metrics: list) -> None:
        """The epoch's step metrics into the registry's counters
        (docs/OBSERVABILITY.md, "The decoder LM"). Called at the epoch's
        sync, when the host fetches the steps' results anyway."""
        if not step_metrics:
            return
        # one small program and one fetch an epoch, not forty fetches
        # while the device waits for the evaluation
        rows = np.asarray(jnp.stack(
            [jnp.stack([m[k] for k in self.extra_metrics])
             for m in step_metrics]), np.float32)
        held, absent, dropped, ratio, tokens, visits_share = rows.T
        registry.counter("dps_moe_tokens_routed_total",
                         where="held").inc(float(held.sum()))
        registry.counter("dps_moe_tokens_routed_total",
                         where="absent").inc(float(absent.sum()))
        registry.counter("dps_moe_tokens_dropped_total").inc(
            float(dropped.sum()))
        registry.gauge("dps_moe_load_max_over_mean").set(float(ratio[-1]))
        registry.gauge("dps_moe_grad_visits_share").set(
            float(visits_share[-1]))
        registry.counter("dps_trainer_tokens_total", mode="sync").inc(
            float(tokens.sum()))
        if not self._said_routing_kept:
            # once, at the first epoch's sync: the step has been traced
            self._said_routing_kept = True
            layers = registry.counter("dps_moe_routing_kept_layers_total")
            kept = registry.counter("dps_moe_routing_kept_bytes_total")
            sys.stdout.write(
                f"[lm] routing kept for the backward pass: {layers.value:.0f} "
                f"expert layers traced in all programs so far, "
                f"{kept.value:.0f} bytes of integers named "
                f"(parallel/moe.py:KEEP_ROUTING)\n")
            sys.stdout.flush()


def _grad_visits_share(mc, held_loads, tokens):
    """Mean over the expert layers of ``visits / (passes x C)`` for this
    worker's ``held_loads`` ``[layers, C]`` (parallel/moe.py:grad_visits):
    what the backward pass reads and writes of the carried expert
    gradients, as a share of a whole-carry add every pass."""
    from ..parallel import moe
    rows, min_passes = mc.pass_plan(tokens.shape[0] * (tokens.shape[1] - 2))
    visits, passes = jax.vmap(
        lambda sizes: moe.grad_visits(sizes, rows, min_passes))(held_loads)
    return jnp.mean(visits / (passes * held_loads.shape[1]))


def task_for(model_name: str, *, augment: bool = True, model_config=None):
    """The task that trains ``model_name``."""
    from ..models.registry import family_of
    if family_of(model_name) == "lm":
        return LMTask(model_config)
    return ImageTask(augment)
