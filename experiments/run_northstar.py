"""North-star convergence runs on the attached TPU chip.

Produces the recorded experiment artifacts the reference ships
(/root/reference/experiment_results/{sync_4workers,async_4workers,
async_8workers}.json + charts) for THIS framework, plus runs the reference's
own comparison points to plateau:

1. the sync/async x {4,8} worker matrix at the reference's 3-epoch config
   (EXPERIMENT_GUIDE.md:95-111) -> experiments/results/<cell>.json + plots,
2. the single-machine baseline recipe (baseline_training.py:201-260) to
   plateau (past both MultiStepLR milestones) -> baseline_convergence.json,
3. a long sync run to plateau -> sync_4workers_long.json.

Real CIFAR-100 is NOT available in this environment (no network egress);
every run uses the deterministic class-structured synthetic stand-in
(data/cifar.py:synthetic_cifar100) and every artifact records that
provenance. The comparison against the reference's recorded curves is
therefore about *relative shapes* (sync vs async vs baseline, staleness
rejection behavior), written up in experiments/results/ACCURACY.md.

Run:  python experiments/run_northstar.py [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# XLA compiles on the host CPU; the persistent cache makes every re-run and
# every identical cell a disk hit.
import jax  # noqa: E402

from distributed_parameter_server_for_ml_training_tpu.utils.compile_cache \
    import enable_compile_cache  # noqa: E402

enable_compile_cache()

OUT = os.path.join(REPO, "experiments", "results")


def run_baseline_convergence(ds, epochs: int, out_dir: str) -> dict:
    from distributed_parameter_server_for_ml_training_tpu.train.baseline import (
        BaselineConfig, BaselineTrainer)

    # device_loop: one compiled program per epoch over the device-resident
    # dataset, so no per-batch host dispatch or upload.
    cfg = BaselineConfig(num_epochs=epochs, device_loop=True)
    trainer = BaselineTrainer(ds, cfg)
    t0 = time.time()
    metrics = trainer.train(
        plot_path=os.path.join(out_dir, "baseline_convergence.png"))
    total = time.time() - t0
    record = {
        "experiment_name": "baseline_convergence",
        "dataset": {
            "synthetic": bool(ds.synthetic),
            "num_classes": int(ds.num_classes),
            "n_train": int(len(ds.x_train)),
            "n_test": int(len(ds.x_test)),
        },
        "device": str(jax.devices()[0]),
        "config": {
            "batch_size": cfg.batch_size,
            "num_epochs": cfg.num_epochs,
            "learning_rate": cfg.learning_rate,
            "momentum": cfg.momentum,
            "weight_decay": cfg.weight_decay,
            "milestones": list(cfg.milestones),
            "gamma": cfg.gamma,
            "dtype": cfg.dtype,
        },
        "total_training_time_seconds": round(total, 2),
        "epochs": metrics.epochs,
        "train_losses": metrics.train_losses,
        "train_accuracies_pct": metrics.train_accuracies,
        "test_accuracies_pct": metrics.test_accuracies,
        "epoch_times_seconds": [round(t, 3) for t in metrics.epoch_times],
    }
    with open(os.path.join(out_dir, "baseline_convergence.json"), "w") as f:
        json.dump(record, f, indent=2)
    return record


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true",
                        help="tiny shapes for a smoke test of this script")
    parser.add_argument("--variant", choices=["easy", "hard", "calibrated"],
                        default="calibrated",
                        help="synthetic difficulty: 'calibrated' (default; "
                             "compositional dataset matched to the "
                             "reference's learning curve — ep1 ~8%%, 65%% "
                             "crossed at epoch 11, plateau ~70%%), 'easy' "
                             "(class templates; ~100%% in 1-2 epochs — "
                             "fast convergence checks) or 'hard' "
                             "(low-amplitude templates + heavy noise; "
                             "superseded by 'calibrated')")
    args = parser.parse_args()

    global OUT
    if args.variant != "easy":
        OUT = os.path.join(OUT, args.variant)
    os.makedirs(OUT, exist_ok=True)

    from distributed_parameter_server_for_ml_training_tpu.analysis import (
        run_matrix)
    from distributed_parameter_server_for_ml_training_tpu.analysis.runner import (
        run_cell)
    from distributed_parameter_server_for_ml_training_tpu.data import (
        compositional_cifar100, synthetic_cifar100)

    # 'hard' difficulty tuned so ResNet-18 shows a gradual CIFAR-like curve
    # instead of instant 100%; 'calibrated' goes further — the compositional
    # generator whose knobs were swept (experiments/calibrate_dataset.py)
    # until the baseline recipe reproduces the reference's curve SHAPE.
    make_ds = (compositional_cifar100 if args.variant == "calibrated"
               else partial(synthetic_cifar100,
                            **(dict(template_amp=0.06, noise=0.45)
                               if args.variant == "hard" else {})))
    if args.quick:
        ds = make_ds(n_train=2048, n_test=512)
        matrix_epochs, base_epochs, long_epochs = 1, 2, 1
        counts = (2,)
    else:
        ds = make_ds()   # 50k/10k, the reference's sizes
        matrix_epochs, base_epochs, long_epochs = 3, 20, 12
        counts = (4, 8)

    with open(os.path.join(OUT, "MANIFEST.json"), "w") as f:
        json.dump({
            "variant": args.variant,
            "dataset": {"generator": make_ds.func.__name__
                          if isinstance(make_ds, partial)
                          else make_ds.__name__,
                        "synthetic": True,
                        "n_train": len(ds.x_train),
                        "n_test": len(ds.x_test)},
            "note": "Real CIFAR-100 is unavailable in this environment "
                    "(no network egress); runs use the deterministic "
                    "synthetic stand-in (data/cifar.py).",
        }, f, indent=2)

    t0 = time.time()

    # 1) The reference's experiment matrix (3 epochs, per its recorded runs).
    #    backend='device': store tensors stay in HBM; the host-numpy store
    #    would copy ~90 MB per worker step between host and device.
    print(f"== matrix: sync/async x {counts} ({matrix_epochs} epochs) ==",
          flush=True)
    run_matrix(ds, OUT, modes=("sync", "async"), worker_counts=counts,
               epochs=matrix_epochs, backend="device")

    # 2) Baseline recipe to plateau (README.md:138 trained 20 epochs).
    print(f"== baseline convergence ({base_epochs} epochs) ==", flush=True)
    rec = run_baseline_convergence(ds, base_epochs, OUT)
    print(f"   final test acc {rec['test_accuracies_pct'][-1]:.2f}% "
          f"in {rec['total_training_time_seconds']:.0f}s", flush=True)

    # 3) Long sync run to plateau.
    print(f"== long sync x {counts[0]} ({long_epochs} epochs) ==", flush=True)
    cell = run_cell(ds, "sync", counts[0], epochs=long_epochs,
                    backend="device")
    cell["experiment_name"] = f"sync_{counts[0]}workers_long"
    with open(os.path.join(OUT, cell["experiment_name"] + ".json"), "w") as f:
        json.dump(cell, f, indent=2)
    agg = cell["worker_metrics_aggregated"]
    print(f"   total {agg['total_training_time_seconds']:.1f}s, "
          f"final acc {agg['average_final_accuracy']:.4f}", flush=True)

    print(f"all north-star runs done in {time.time() - t0:.0f}s", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
