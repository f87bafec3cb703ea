"""Driver ``sync_mesh_lm``: ``SyncTrainer`` with the decoder-LM task for any
decoder of ``models/registry.py``, exactly what ``cli train --mode sync
--model <model>`` builds, in one ``train()`` call that outlives the run.

``drivers/sync_mesh_tokens.py`` is this driver for one model: its
``build_trainer`` imports ``JoyAIConfig`` by name and reads that
configuration file's own keys. Everything else of it is shared and loaded
from it as it loads ``sync_mesh`` (the window, the edges and their counters,
``finish``, the comparison with the plain reference and its readings). What
this file brings:

1. :func:`build_trainer`: the model's configuration object comes from the
   registry (``lm_config_from_file``) and the configuration file's ``model``
   key, with the file saying which of its keys counts the experts
   (``deployment.experts_key``: the published value is the router's width,
   the file's own value the experts held from ``deployment.
   first_expert_held`` on), so the next decoder needs no third driver;
2. the edge also carries ``dps_flash_tiles_total{kind}`` (the tiles the
   flash kernels' programs compute, mask and skip, counted as they are
   traced), which ``attention.band_tile_share`` reads;
3. a second control. ``BENCH_REFERENCE_CONTROL`` (a builder's switch; the
   driver of a check sets nothing) is a dtype, as there: the reference in
   that lower precision; or ``full_attention``: the reference with the
   window taken off every window layer, which a program that forgot the band
   computes. Either is put through the reference's own update in the
   program's place and the limits have to reject it;
4. the host's memory. ``readings`` works its tensors off four at a time,
   each holding a dozen temporaries of its tensor's size, beside two copies
   of the state and the reference's gradients. The first decoder's largest
   tensor is 132 MB; a vocabulary slice of 37,984 x 2,560 is 389 MB, twice,
   and four at a time met the chip machine's 40 GiB (PERF.md, PR 34). The
   configuration file says how many (``reference_check.
   tensors_at_a_time``) and :func:`tensors_at_a_time` holds the pool to it.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import dataclasses
import os
import time

from harness import spec

_BASE = spec.load_module("drivers", "sync_mesh_tokens",
                         os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
ADAMW_VALUES = _BASE.ADAMW_VALUES
FLASH_TILE_KINDS = ("unmasked", "masked", "skipped", "below_band")


@contextlib.contextmanager
def tensors_at_a_time(n: int):
    """While it lasts, a ``ThreadPoolExecutor`` has at most ``n`` workers.
    ``sync_mesh_tokens.readings`` asks ``concurrent.futures`` for its pool
    of four when it is called, and is an accepted file. Set-up only: the
    trainer's thread has not started."""
    real = concurrent.futures.ThreadPoolExecutor

    def capped(max_workers=None, **kw):
        return real(max_workers=min(n, max_workers or n), **kw)

    concurrent.futures.ThreadPoolExecutor = capped
    try:
        yield
    finally:
        concurrent.futures.ThreadPoolExecutor = real


def model_config(config: dict):
    """The decoder's configuration object from a configuration file."""
    from distributed_parameter_server_for_ml_training_tpu.models.registry \
        import lm_config_from_file
    experts = config["deployment"]["experts_key"]
    return lm_config_from_file(
        config["model"], config,
        **{experts: int(config["published"][experts])},
        held_experts=(int(config["deployment"]["first_expert_held"]),
                      int(config[experts])),
        **config["assumed"]["values"])


def build_trainer(cell, seed: int, chips: int):
    """``(trainer, dataset, global_batch)``: the cell's ``SyncTrainer``, as
    ``cli train --mode sync --model <model>`` builds it, on the seed's
    token data."""
    from distributed_parameter_server_for_ml_training_tpu.train \
        .distributed import DistributedConfig, SyncTrainer
    from harness.tokens import make_token_dataset

    traffic, config = cell.traffic, cell.config
    per_chip = int(traffic["per_chip_batch"])
    global_batch = per_chip * chips
    t0 = time.monotonic()
    dataset = make_token_dataset(
        config, traffic, int(traffic["steps_per_epoch"]) * global_batch,
        seed)
    print(f"[bench] token data: {dataset.documents} documents, rows "
          f"{dataset.train.shape} + {dataset.test.shape}, packing waste "
          f"{dataset.packing_waste:.4f}, {time.monotonic() - t0:.1f} s",
          flush=True)
    cfg = DistributedConfig(
        mode="sync", num_workers=chips,
        learning_rate=float(config["optimizer"]["learning_rate"]),
        optimizer={k: float(config["optimizer"][k]) for k in ADAMW_VALUES},
        num_epochs=10 ** 9,  # one train() call; the run leaves it alive
        batch_size=per_chip, compression=traffic["exchange_dtype"],
        dtype=config["compute_dtype"], model=config["model"],
        model_config=model_config(config), seed=seed)
    t0 = time.monotonic()
    trainer = SyncTrainer(dataset, cfg)
    print(f"[bench] trainer and the seed's state: "
          f"{time.monotonic() - t0:.1f} s", flush=True)
    return trainer, dataset, global_batch


def full_attention_control(cell, seed: int, trainer, dataset,
                           global_batch: int) -> dict:
    """The reference with its window left off against the reference, both
    in float32, the first through the reference's own update in the
    program's place: the readings, printed with whether the limits reject
    them. Before the comparison proper, which leaves the state as it found
    it; the state waits on the host meanwhile."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    config, task, mc = cell.config, trainer.task, trainer.task.model_config
    check = config["reference_check"]
    reference = _BASE._load_reference(cell.bench_dir, config["reference"])
    update = _BASE.ReferenceUpdate(reference, config["optimizer"],
                                   mc.bias_update_gamma)
    t0 = time.monotonic()
    start = jax.device_get(trainer.state)
    batch = next(task.train_batches(dataset, global_batch, seed=seed * 997))
    tokens = jnp.asarray(batch[0])
    positions = jnp.asarray(np.sort(np.random.default_rng(
        [seed, 31]).choice(dataset.seq_len, size=int(check["positions"]),
                           replace=False)))
    trainer.state = None        # the reference needs the chip to itself
    jax.clear_caches()

    def side(cfg):
        params = jax.tree_util.tree_map(jnp.asarray, start.params)
        bias = jnp.asarray(start.batch_stats["router_bias"])
        head_block = int(check["head_block"])
        loss, aux, grads = reference.loss_and_grads(
            params, bias, tokens, cfg, rows_per_block=1, remat=True,
            dtype=jnp.float32, head_block=head_block)
        at = reference.logits_at(params, bias, tokens, cfg, positions,
                                 dtype=jnp.float32, head_block=head_block)
        out = jax.device_get({"loss": loss, "grads": grads, "logits": at,
                              "loads": aux["loads"]})
        del params, bias, loss, aux, grads, at
        jax.clear_caches()
        return dict(out, loss=float(out["loss"]))

    origin = {"params": start.params,
              "bias": start.batch_stats["router_bias"]}
    without = dataclasses.replace(
        mc, sliding_window_layout=(0,) * len(mc.sliding_window_layout))
    with tensors_at_a_time(int(check.get("tensors_at_a_time", 4))):
        found = _BASE.readings(
            _BASE._updated(origin, side(without), update), side(mc), origin,
            update)
    trainer.state = task.place_state(trainer.mesh, start)
    print(f"[bench] control: the reference without its window against the "
          f"reference, readings {found}; rejected "
          f"{not _BASE.within(found, config['reference_limits'])} (has to "
          f"be True); {time.monotonic() - t0:.1f} s", flush=True)
    return found


class Session(_BASE.Session):
    def __init__(self, ctx):
        from distributed_parameter_server_for_ml_training_tpu.telemetry \
            import get_registry
        from harness import hlo_scopes
        from harness.session import TrainerThread

        traffic = ctx.cell.traffic
        self.ctx = ctx
        self.chips = len(ctx.devices)
        self.images_per_device_step = int(traffic["per_chip_batch"])
        self.steps_per_epoch = int(traffic["steps_per_epoch"])
        self.trainer, dataset, self.global_batch = build_trainer(
            ctx.cell, ctx.seed, self.chips)
        self.packing_waste = dataset.packing_waste
        if _BASE._traced(ctx):
            # the executable the trainer runs, compiled at its first call
            # (the comparison's), gives the scope readers its text
            self.trainer._step = hlo_scopes.KeptStep(
                "jit_worker_step", self.trainer._step)
        control = os.environ.get("BENCH_REFERENCE_CONTROL")
        if control == "full_attention":
            full_attention_control(ctx.cell, ctx.seed, self.trainer, dataset,
                                   self.global_batch)
            control = None
        check = ctx.cell.config["reference_check"]
        with tensors_at_a_time(int(check.get("tensors_at_a_time", 4))):
            self.matches_reference, _ = _BASE.compare_with_reference(
                ctx.cell, ctx.seed, self.trainer, dataset,
                self.global_batch, devices=ctx.devices, control=control)
        reg = get_registry()
        self._steps = reg.counter("dps_trainer_steps_total", mode="sync")
        self._dispatch = reg.histogram("dps_trainer_step_seconds",
                                       mode="sync")
        self._registry = reg
        self._thread = TrainerThread(self.trainer.train, "sync-trainer")

    def edge(self, not_before: float, deadline: float) -> dict:
        edge = super().edge(not_before, deadline)
        edge["flash_tiles"] = {
            kind: self._registry.counter("dps_flash_tiles_total",
                                         kind=kind).value
            for kind in FLASH_TILE_KINDS}
        return edge


def start(ctx) -> Session:
    return Session(ctx)
