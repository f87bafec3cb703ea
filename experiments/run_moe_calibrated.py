"""Calibrated MoE run: Switch-MoE ViT with balanced routing, recorded.

Round-4 VERDICT item 3 'done' bar: a committed ``calibrated/`` MoE run
demonstrating balanced routing. Trains ``--mode moe`` (8 experts,
registry vit_tiny, Switch aux loss at the default weight) on the
calibrated compositional dataset, plus a short aux-weight=0 contrast run,
and records per-epoch expert-load imbalance + drop rate.

The MoE trainer needs one device per expert; this host has ONE TPU chip,
so the run uses the 8-device virtual CPU mesh (same collectives, honest
provenance in the record — the on-chip story for EP is the driver's
``dryrun_multichip``).

Run:  python experiments/run_moe_calibrated.py [--epochs N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
from distributed_parameter_server_for_ml_training_tpu.utils.compile_cache \
    import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np  # noqa: E402


def run(aux_weight: float, epochs: int, ds) -> dict:
    from distributed_parameter_server_for_ml_training_tpu.train.model_parallel import (
        ModelParallelConfig, MoETrainer)

    cfg = ModelParallelConfig(
        model="vit_tiny", num_workers=8, num_epochs=epochs, batch_size=128,
        augment=False, num_classes=100, learning_rate=0.1,
        moe_aux_weight=aux_weight)
    trainer = MoETrainer(ds, cfg)
    t0 = time.time()
    metrics = trainer.train()
    metrics["wall_seconds"] = round(time.time() - t0, 1)

    # Per-epoch routing health from the per-step metric stream.
    steps = len(trainer._moe_step_metrics)
    spe = max(1, steps // epochs)
    per_epoch = []
    for e in range(epochs):
        chunk = trainer._moe_step_metrics[e * spe:(e + 1) * spe]
        if not chunk:
            break
        per_epoch.append({
            "epoch": e + 1,
            "load_imbalance": round(float(np.mean(
                [float(m["moe_load_imbalance"]) for m in chunk])), 3),
            "drop_frac": round(float(np.mean(
                [float(m["moe_drop_frac"]) for m in chunk])), 4),
            "aux_loss": round(float(np.mean(
                [float(m["moe_aux_loss"]) for m in chunk])), 4),
        })
    metrics["per_epoch_routing"] = per_epoch
    return metrics


def run_dense(epochs: int, ds) -> dict:
    """Dense-FFN vit_tiny under the IDENTICAL recipe (same optimizer,
    lr, batch size, batch order seed, step budget, eval) — the contrast
    that shows whether the MoE's 8x FFN parameters at equal per-token
    FLOPs buy quality (round-4 VERDICT weak 4: 'the MoE demonstration
    never shows MoE is worth having')."""
    import jax
    import jax.numpy as jnp

    from distributed_parameter_server_for_ml_training_tpu.data.cifar import (
        make_batches)
    from distributed_parameter_server_for_ml_training_tpu.models.vit import (
        ViT)
    from distributed_parameter_server_for_ml_training_tpu.train import (
        create_train_state, make_eval_step, make_train_step, server_sgd)
    from distributed_parameter_server_for_ml_training_tpu.train.model_parallel \
        import VIT_SHAPES, ModelParallelConfig

    # Build the dense arm FROM the same registry shape and the same
    # config defaults the MoE arm uses (dtype included) — matched by
    # construction, so an accuracy gap can't be an fp32-vs-bf16 or
    # shape-drift artifact.
    cfg = ModelParallelConfig()
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    model = ViT(**VIT_SHAPES["vit_tiny"], num_classes=100, dtype=dtype,
                pool="gap")
    state = create_train_state(model, jax.random.PRNGKey(cfg.seed),
                               server_sgd(0.1), input_shape=(1, 32, 32, 3))
    step = jax.jit(make_train_step(augment=False), donate_argnums=0)
    eval_step = jax.jit(make_eval_step())
    t0 = time.time()
    accs, steps_done = [], 0
    for epoch in range(epochs):
        # Same batch-order seed expression as the one epoch loop's (train/loop.py).
        for xb, yb in make_batches(ds.x_train, ds.y_train, 128,
                                   seed=cfg.seed * 997 + epoch):
            state, _ = step(state, xb, yb.astype(np.int32),
                            jax.random.PRNGKey(steps_done))
            steps_done += 1
        correct = total = 0
        for i in range(0, len(ds.x_test), 256):
            xb = ds.x_test[i:i + 256]
            yb = ds.y_test[i:i + 256].astype(np.int32)
            c, n = eval_step(state, xb, yb)
            correct += float(c)
            total += int(n)
        accs.append(round(correct / total, 4))
        print(f"dense epoch {epoch + 1}: test_acc={accs[-1]}", flush=True)
    return {"final_test_accuracy": accs[-1], "all_test_accuracies": accs,
            "local_steps_completed": steps_done,
            "wall_seconds": round(time.time() - t0, 1),
            "arch": "vit_tiny dense MLP", "optimizer": "server_sgd(0.1)"}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--contrast-epochs", type=int, default=None,
                    help="aux-weight=0 contrast run length "
                         "(default: same as --epochs — full-run contrast)")
    ap.add_argument("--skip-dense", action="store_true")
    ap.add_argument("--out", default="moe_8experts.json",
                    help="output filename under experiments/results/"
                         "calibrated/ (a longer-budget rerun must not "
                         "overwrite the default record)")
    ap.add_argument("--train-size", type=int, default=8192,
                    help="subset of the calibrated dataset (CPU-mesh host)")
    args = ap.parse_args()

    from distributed_parameter_server_for_ml_training_tpu.data.cifar import (
        compositional_cifar100)

    ds = compositional_cifar100(n_train=args.train_size, n_test=2048)
    record = {
        "experiment_name": args.out.rsplit(".", 1)[0],
        "dataset": {"generator": "compositional_cifar100",
                    "synthetic": True, "n_train": args.train_size,
                    "n_test": 2048},
        "provenance": ("8-device virtual CPU mesh "
                       "(xla_force_host_platform_device_count; the single "
                       "attached TPU chip cannot host 8 experts)"),
        "config": {"model": "vit_tiny", "n_experts": 8, "batch_size": 128,
                   "learning_rate": 0.1, "capacity_factor": 2.0},
    }
    out = os.path.join(REPO, "experiments", "results", "calibrated",
                       os.path.basename(args.out))

    def save():
        with open(out, "w") as f:
            json.dump(record, f, indent=2, default=float)
            f.write("\n")

    # Validate the output path BEFORE the first ~40-minute cell: a bad
    # --out must fail in seconds, not after the training finishes.
    save()
    # Save after EVERY cell: a crash in a later cell must not lose a
    # 40-minute run (it did once).
    record["balanced_aux_0.01"] = run(0.01, args.epochs, ds)
    save()
    if not args.skip_dense:
        # Matched-recipe dense arm: same optimizer/lr/batch/steps; wall
        # clock reported separately (the MoE pays all_to_all + routing).
        record["dense_reference"] = run_dense(args.epochs, ds)
        save()
        moe_acc = record["balanced_aux_0.01"].get("final_test_accuracy")
        dense_acc = record["dense_reference"]["final_test_accuracy"]
        record["moe_vs_dense"] = {
            "matched": "registry shape, dtype, optimizer, lr, global "
                       "batch, batch-order seed, step budget, dataset",
            "moe_final_acc": moe_acc, "dense_final_acc": dense_acc,
            "moe_beats_or_matches_dense":
                (moe_acc is not None and dense_acc is not None
                 and float(moe_acc) >= float(dense_acc) - 0.005),
            "moe_wall_seconds":
                record["balanced_aux_0.01"].get("wall_seconds"),
            "dense_wall_seconds":
                record["dense_reference"]["wall_seconds"],
        }
        save()
    n_contrast = (args.contrast_epochs if args.contrast_epochs is not None
                  else args.epochs)
    if n_contrast > 0:
        record["contrast_aux_0"] = run(0.0, n_contrast, ds)
        save()
    print(f"wrote {out}")
    print("balanced per-epoch routing:",
          record["balanced_aux_0.01"]["per_epoch_routing"])
    if "moe_vs_dense" in record:
        print("moe vs dense:", record["moe_vs_dense"])
    if "contrast_aux_0" in record:
        print("contrast (aux off) routing:",
              record["contrast_aux_0"]["per_epoch_routing"])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
