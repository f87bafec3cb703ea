"""Driver ``sync_mesh_tokens``: ``SyncTrainer`` with the decoder-LM task
over a mesh of the cell's chips, exactly what ``cli train --mode sync
--model joyai_llm_flash`` builds, in one ``train()`` call that outlives the
run. An "image" of such a cell is one packed sequence.

Everything about the window is ``drivers/sync_mesh.py``'s, whose ``Session``
this one extends: the edge is an epoch end (``trainer.test_accuracies``
grows), ``finish`` reads the epoch lines, and ``learned`` is the same
group. What differs is set-up:

1. token data from the seed (``harness/tokens.py``) and the trainer, as the
   CLI builds it (:func:`build_trainer`);
2. **the comparison with the plain reference** (``matches_reference``,
   :func:`compare_with_reference`), of the step that the window then times:
   the trainer's own compiled ``_step`` is called once, from the seed's
   state on the run's first batch, and what it leaves is fetched: the loss,
   AdamW's first moment (the gradient the update was given, after the
   exchange), its second moment, the parameters' change and the router
   bias's. ``reference/<name>.py`` computes loss and gradients a sequence at
   a time in float32 and puts them through its own AdamW and bias rule, on
   the host. Both sides at once do not fit beside the state, so the state
   waits on the host while the reference holds the chip, and the trainer
   starts the run from a copy of the seed's state. The logits at sampled
   positions and the counted loads come from the model's own functions
   (``logits_at``). The limits are the configuration's ``reference_limits``;
3. in a traced run, the text of the executable the trainer runs, for the
   scope readers (``harness/hlo_scopes.py``).

``BENCH_REFERENCE_CONTROL=<dtype>`` in the environment (a builder's switch,
``bfloat16``; the driver of a check sets nothing) also computes the
reference in that lower precision and prints its readings against the
float32 reference and whether the limits reject them, which they have to.
"""

from __future__ import annotations

import importlib.util
import math
import os
import time

from harness import spec

_BASE = spec.load_module("drivers", "sync_mesh",
                         os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))

#: AdamW's values beside the learning rate: the configuration states each
ADAMW_VALUES = ("b1", "b2", "eps", "weight_decay")


def _load_reference(bench_dir: str, name: str):
    path = os.path.join(bench_dir, "reference", name + ".py")
    s = importlib.util.spec_from_file_location("bench_reference_" + name,
                                               path)
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


def _traced(ctx) -> bool:
    """``RunContext`` does not say whether the run is traced; its log's
    directory does (``runner.main``: ``<cell>.seed<n>.trace<0|1>``)."""
    return os.path.dirname(ctx.log_path).endswith(".trace1")


def _leaves(tree) -> dict:
    import jax
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_leaves_with_path(tree)}


def _rel_l2(a, b) -> float:
    """The norm of ``a - b`` over the norm of ``b`` (0 where both are 0)."""
    import numpy as np
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm((a - b).ravel())
                 / max(np.linalg.norm(b.ravel()), 1e-30))


def _rel_max(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def readings(program: dict, reference: dict, start: dict, update) -> dict:
    """The numbers ``matches_reference`` compares. Host arrays throughout.

    ``program``: what the step left (``loss``, ``params``, ``mu``, ``nu``,
    ``bias``) and the model's ``logits`` (main, mtp) and ``loads``.
    ``reference``: ``loss``, ``grads``, ``logits``, ``loads``. ``start``:
    the ``params`` and ``bias`` both began from. ``update(param, grad)`` is
    the reference's first AdamW step, ``update.bias(bias, loads)`` its bias
    rule, ``update.b1`` the first moment's decay.

    Two views of the update. ``update_l2`` holds the parameters' change to
    the reference's gradients through the reference's AdamW: the whole
    step, and coarse, because Adam's first step is ``sign(g)`` wherever
    ``|g|`` is well above ``eps``, so an entry whose rounding error exceeds
    its size moves the other way; an unchanged state reads 1.
    ``rule_l2_worst`` gives the reference's AdamW the *program's* own
    gradient (its first moment over ``1 - b1``) and so holds the rule
    alone, moment and parameter, tensor by tensor, to rounding: a wrong
    ``b2``, bias correction, ``eps``, rate, or decay on a gain shows here.
    The gradient itself is held through the first moment (``grad_*``)."""
    import numpy as np
    from concurrent.futures import ThreadPoolExecutor
    p0, p1 = _leaves(start["params"]), _leaves(program["params"])
    mu, nu = _leaves(program["mu"]), _leaves(program["nu"])
    want = _leaves(reference["grads"])

    def tensor(key):
        """(first moment's error, the rule's, |change - reference's|^2,
        |reference's change|^2) of one tensor."""
        p1_ref, mu_ref, _nu = update(p0[key], want[key])
        moved, moved_ref = p1[key] - p0[key], p1_ref - p0[key]
        p1_rule, _mu, nu_rule = update(p0[key], mu[key] / (1.0 - update.b1))
        return (_rel_l2(mu[key], mu_ref),
                max(_rel_l2(moved, p1_rule - p0[key]),
                    _rel_l2(nu[key], nu_rule)),
                float(np.sum((moved - moved_ref) ** 2, dtype=np.float64)),
                float(np.sum(moved_ref ** 2, dtype=np.float64)))

    # 680M parameters at the published widths, some forty passes each:
    # numpy releases the interpreter in them, so tensors go side by side,
    # four at a time: each holds a dozen temporaries of its tensor's size,
    # and eight at a time met the chip machine's 40 GiB (PERF.md, PR 28)
    with ThreadPoolExecutor(max_workers=4) as pool:
        rows = dict(zip(want, pool.map(tensor, want)))
    grad = {key: row[0] for key, row in rows.items()}
    rule = {key: row[1] for key, row in rows.items()}
    change = sum(row[2] for row in rows.values())
    ours = sum(row[3] for row in rows.values())
    bias_ref = np.stack([update.bias(b, l) for b, l in
                         zip(start["bias"], reference["loads"])])
    loads_p = np.asarray(program["loads"], np.float64)
    loads_r = np.asarray(reference["loads"], np.float64)
    worst = max(grad, key=grad.get)
    return {
        "loss_rel": abs(program["loss"] - reference["loss"])
        / abs(reference["loss"]),
        "logits_max": max(_rel_max(a, b) for a, b in
                          zip(program["logits"], reference["logits"])),
        "grad_l2_worst": grad[worst],
        "grad_l2_median": float(np.median(list(grad.values()))),
        "update_l2": math.sqrt(change / max(ours, 1e-60)),
        "rule_l2_worst": max(rule.values()),
        # entries of the router bias that went the other way, or nowhere
        "bias_moved": float(np.mean(
            np.abs(np.asarray(program["bias"]) - bias_ref)
            > 0.5 * update.gamma)),
        # assignments that went to another expert, of all assignments
        "routing_moved": float(np.abs(loads_p - loads_r).sum() / 2.0
                               / loads_r.sum()),
        "worst_tensor": worst,
        "worst_rule_tensor": max(rule, key=rule.get),
    }


def within(found: dict, limits: dict) -> bool:
    unknown = set(limits) - set(found)
    if unknown or not limits:
        raise ValueError(f"'reference_limits' names readings this driver "
                         f"does not make: {sorted(unknown)}")
    return all(math.isfinite(found[k]) and found[k] <= limits[k]
               for k in limits)


class ReferenceUpdate:
    """The reference's first step on one tensor, with the configuration's
    values: ``self(param, grad) -> (param, mu, nu)``; ``bias`` its router
    bias rule for one expert layer."""

    def __init__(self, reference, optimizer: dict, gamma: float):
        self._reference = reference
        self._values = {k: float(optimizer[k]) for k in
                        ("learning_rate",) + ADAMW_VALUES}
        self.b1, self.gamma = self._values["b1"], float(gamma)

    def __call__(self, param, grad):
        import numpy as np
        zero = np.zeros(param.shape, np.float32)
        return self._reference.adamw_step(param, grad, zero, zero, 1,
                                          **self._values)

    def bias(self, bias, loads):
        import numpy as np
        return self._reference.bias_step(
            np.asarray(bias, np.float32), np.asarray(loads, np.float32),
            self.gamma)


def _updated(origin: dict, side: dict, update: ReferenceUpdate) -> dict:
    """What the reference's update leaves from ``origin`` given a side's
    gradients and loads, in the shape :func:`readings` takes a program's."""
    import jax
    import numpy as np
    leaves, tree = jax.tree_util.tree_flatten(origin["params"])
    stepped = [update(p, np.asarray(g, np.float32)) for p, g in
               zip(leaves, jax.tree_util.tree_leaves(side["grads"]))]
    params, mu, nu = (tree.unflatten([s[i] for s in stepped])
                      for i in range(3))
    return {"loss": side["loss"], "logits": side["logits"],
            "loads": side["loads"], "params": params, "mu": mu, "nu": nu,
            "bias": np.stack([update.bias(b, l) for b, l in
                              zip(origin["bias"], side["loads"])])}


def build_trainer(cell, seed: int, chips: int):
    """``(trainer, dataset, global_batch)``: the cell's ``SyncTrainer``, as
    ``cli train --mode sync --model <model>`` builds it, on the seed's
    token data."""
    from distributed_parameter_server_for_ml_training_tpu.models.joyai \
        import JoyAIConfig
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
    model_config = JoyAIConfig.from_hf(
        config,
        n_routed_experts=int(config["published"]["n_routed_experts"]),
        held_experts=(int(config["deployment"]["first_expert_held"]),
                      int(config["n_routed_experts"])),
        **config["assumed"]["values"])
    cfg = DistributedConfig(
        mode="sync", num_workers=chips,
        learning_rate=float(config["optimizer"]["learning_rate"]),
        optimizer={k: float(config["optimizer"][k]) for k in ADAMW_VALUES},
        num_epochs=10 ** 9,  # one train() call; the run leaves it alive
        batch_size=per_chip, compression=traffic["exchange_dtype"],
        dtype=config["compute_dtype"], model=config["model"],
        model_config=model_config, seed=seed)
    t0 = time.monotonic()
    trainer = SyncTrainer(dataset, cfg)
    print(f"[bench] trainer and the seed's state: "
          f"{time.monotonic() - t0:.1f} s", flush=True)
    return trainer, dataset, global_batch


def first_step(trainer, start, batch, step=None) -> dict:
    """Call the trainer's step once, as ``train()`` calls it, from a copy
    of the host state ``start`` on ``batch``; what it leaves, on the host.
    ``step`` stands in for ``trainer._step`` in the tests that plant a
    fault."""
    import jax
    step = step or trainer._step
    state = trainer.task.place_state(trainer.mesh, start)
    placed = trainer._shard(batch)
    rng = jax.random.PRNGKey(trainer.config.seed + 1)
    state, metrics = step(state, *placed, rng)
    left = jax.device_get({
        "loss": metrics["loss"], "dropped": metrics["moe_dropped"],
        "params": state.params, "mu": state.opt_state[0].mu,
        "nu": state.opt_state[0].nu,
        "bias": state.batch_stats["router_bias"]})
    return dict(left, loss=float(left["loss"]),
                dropped=float(left["dropped"]))


def compare_with_reference(cell, seed: int, trainer, dataset,
                           global_batch: int, *, devices=(), step=None,
                           control: str | None = None) -> tuple[bool, dict]:
    """Step 2 of the module docstring. Returns ``(matches_reference, the
    readings)`` and leaves ``trainer.state`` a fresh copy of the state it
    found."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    config, task, mc = cell.config, trainer.task, trainer.task.model_config
    check = config["reference_check"]
    reference = _load_reference(cell.bench_dir, config["reference"])
    update = ReferenceUpdate(reference, config["optimizer"],
                             mc.bias_update_gamma)
    t0 = time.monotonic()
    start = jax.device_get(trainer.state)          # the seed's state
    batch = next(task.train_batches(dataset, global_batch, seed=seed * 997))
    tokens = jnp.asarray(batch[0])
    positions = jnp.asarray(np.sort(np.random.default_rng(
        [seed, 31]).choice(dataset.seq_len, size=int(check["positions"]),
                           replace=False)))
    bias = trainer.state.batch_stats["router_bias"]
    # one program for both (the two forward passes are one after CSE), with
    # the seed's positions an argument: the same program for every seed
    model = trainer.model
    logits, loads = jax.device_get(jax.jit(lambda p, t, b, at: (
        model.apply({"params": p}, t, b, at, method="logits_at"),
        model.apply({"params": p}, t, b)["loads"]))(
            trainer.state.params, tokens, bias, positions))
    trainer.state = None        # the reference needs the chip to itself
    jax.clear_caches()
    t1 = time.monotonic()

    def reference_side(dtype):
        params = jax.tree_util.tree_map(jnp.asarray, start.params)
        bias = jnp.asarray(start.batch_stats["router_bias"])
        head_block = int(check["head_block"])
        loss, aux, grads = reference.loss_and_grads(
            params, bias, tokens, mc, rows_per_block=1, remat=True,
            dtype=dtype, head_block=head_block)
        at = reference.logits_at(params, bias, tokens, mc, positions,
                                 dtype=dtype, head_block=head_block)
        side = jax.device_get({"loss": loss, "grads": grads, "logits": at,
                               "loads": aux["loads"]})
        del params, bias, loss, aux, grads, at
        jax.clear_caches()
        return dict(side, loss=float(side["loss"]))

    want = reference_side(jnp.float32)
    t2 = time.monotonic()
    origin = {"params": start.params,
              "bias": start.batch_stats["router_bias"]}
    limits = config["reference_limits"]
    if control:
        # the reference in a lower precision, through the reference's own
        # update, in the program's place: the limits have to reject it
        found = readings(_updated(origin, reference_side(jnp.dtype(control)),
                                  update), want, origin, update)
        print(f"[bench] control: the reference in {control} against the "
              f"reference, readings {found}; rejected "
              f"{not within(found, limits)} (has to be True); "
              f"{time.monotonic() - t2:.1f} s", flush=True)
    t3 = time.monotonic()

    left = first_step(trainer, start, batch, step)
    t4 = time.monotonic()
    found = readings(dict(left, logits=logits, loads=loads), want, origin,
                     update)
    trainer.state = task.place_state(trainer.mesh, start)
    stats = [d.memory_stats() or {} for d in devices]
    peak = max([s.get("peak_bytes_in_use", 0)
                + s.get("peak_bytes_reserved", 0) for s in stats] or [0])
    ok = within(found, limits) and left["dropped"] == 0.0
    print(f"[bench] matches_reference {ok}: readings {found} against limits "
          f"{limits}; dropped {left['dropped']}; loss program "
          f"{left['loss']:.6f} reference {want['loss']:.6f}; the model's "
          f"logits and the state to the host {t1 - t0:.1f} s, reference "
          f"{t2 - t1:.1f} s, the trainer's step (its compile or load) and "
          f"what it left {t4 - t3:.1f} s, readings on the host "
          f"{time.monotonic() - t4:.1f} s; memory peak after it "
          f"{peak / 1e9:.3f} GB", flush=True)
    return ok, found


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
        if _traced(ctx):
            # the executable the trainer runs, compiled at its first call
            # (the comparison's), gives the scope readers its text
            self.trainer._step = hlo_scopes.KeptStep(
                "jit_worker_step", self.trainer._step)
        self.matches_reference, _ = compare_with_reference(
            ctx.cell, ctx.seed, self.trainer, dataset, self.global_batch,
            devices=ctx.devices,
            control=os.environ.get("BENCH_REFERENCE_CONTROL"))
        reg = get_registry()
        self._steps = reg.counter("dps_trainer_steps_total", mode="sync")
        self._dispatch = reg.histogram("dps_trainer_step_seconds",
                                       mode="sync")
        self._registry = reg
        self._thread = TrainerThread(self.trainer.train, "sync-trainer")

    def edge(self, not_before: float, deadline: float) -> dict:
        edge = super().edge(not_before, deadline)
        reg = self._registry
        edge.update(
            tokens=reg.counter("dps_trainer_tokens_total",
                               mode="sync").value,
            moe_held=reg.counter("dps_moe_tokens_routed_total",
                                 where="held").value,
            moe_absent=reg.counter("dps_moe_tokens_routed_total",
                                   where="absent").value,
            moe_dropped=reg.counter("dps_moe_tokens_dropped_total").value,
            moe_load_max_over_mean=reg.gauge(
                "dps_moe_load_max_over_mean").value,
            packing_waste=self.packing_waste)
        return edge

    def finish(self, first: dict, last: dict) -> tuple[dict, int]:
        checks, failed = super().finish(first, last)
        window_s = last["t"] - first["t"]
        tokens = last["tokens"] - first["tokens"]
        print(f"[bench] tokens/s/chip {tokens / window_s / self.chips:.1f} "
              f"({tokens:.0f} tokens in the window); routed to held "
              f"experts {last['moe_held'] - first['moe_held']:.0f}, to "
              f"absent {last['moe_absent'] - first['moe_absent']:.0f}, "
              f"dropped {last['moe_dropped']:.0f}; held experts' load max "
              f"over mean {last['moe_load_max_over_mean']:.3f}", flush=True)
        checks["matches_reference"] = self.matches_reference
        checks["no_token_dropped"] = last["moe_dropped"] == 0
        # the trainer's token count agrees with the epochs it has ended
        per_epoch = (self.steps_per_epoch * self.global_batch
                     * int(self.ctx.cell.traffic["seq_len"]))
        checks["tokens_reconcile"] = all(
            0 <= e["tokens"] - e["epochs"] * per_epoch <= per_epoch
            for e in (first, last))
        return checks, failed


def start(ctx) -> Session:
    return Session(ctx)
