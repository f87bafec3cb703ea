"""Calibrate compositional_cifar100 difficulty to the reference curve.

Target (round-2 VERDICT item 1), from /root/reference/baseline/results/
baseline_summary.json and README.md:446:
  - epoch-1 test acc ~ 12%
  - 65% crossed only mid-training (>5 epochs, realistically after the
    first MultiStepLR drop at epoch 10)
  - plateau ~ 70%

Runs the exact baseline recipe (batch 128, SGD m=0.9 wd=5e-4,
MultiStepLR([10,15], 0.1), 20 epochs, device epoch loop) over a grid of
generator knobs; all configs share one compiled executable (identical
shapes), so each extra config costs dataset-gen + ~35 s of training.

Run:  python experiments/calibrate_dataset.py [--configs i,j,...]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

from distributed_parameter_server_for_ml_training_tpu.utils.compile_cache \
    import enable_compile_cache  # noqa: E402

enable_compile_cache()

GRID = [
    # name, generator kwargs. Round-1 finding: the original defaults
    # (motif_amp .22, template .035, bg .22, lbl .22) give ep1 44.9%,
    # cross65 @ 4, final 76.7% — too easy on every axis.
    ("base", dict()),
    ("hard_a", dict(template_amp=0.015, motif_amp=0.15, bg_noise=0.28,
                    label_noise=0.28)),
    ("hard_b", dict(template_amp=0.0, motif_amp=0.16, bg_noise=0.28,
                    label_noise=0.28)),
    ("hard_c", dict(template_amp=0.02, motif_amp=0.12, bg_noise=0.30,
                    label_noise=0.25, n_distractors=3)),
    ("hard_d", dict(template_amp=0.015, motif_amp=0.18, bg_noise=0.35,
                    label_noise=0.28, amp_jitter=0.7)),
    # Round 2: hard_* overshot (ep1 2.5-4.8%, never cross 65, final 41-51);
    # interpolate between base and hard_a.
    ("mid_a", dict(template_amp=0.022, motif_amp=0.18, bg_noise=0.25,
                   label_noise=0.25)),
    ("mid_b", dict(template_amp=0.020, motif_amp=0.19, bg_noise=0.25,
                   label_noise=0.22)),
    ("mid_c", dict(template_amp=0.025, motif_amp=0.17, bg_noise=0.26,
                   label_noise=0.25)),
    # Round 3: mid_b (ep1 8.8, cross65 @11, final 68.0) is nearly the
    # reference curve (ep1 11.95, ~65 @ 20); nudge ep1 up a touch.
    ("mid_d", dict(template_amp=0.024, motif_amp=0.20, bg_noise=0.25,
                   label_noise=0.22)),
]


def run_config(name: str, kw: dict, epochs: int = 20) -> dict:
    from distributed_parameter_server_for_ml_training_tpu.data import (
        compositional_cifar100)
    from distributed_parameter_server_for_ml_training_tpu.train.baseline import (
        BaselineConfig, BaselineTrainer)

    t0 = time.time()
    ds = compositional_cifar100(**kw)
    gen_s = time.time() - t0
    trainer = BaselineTrainer(ds, BaselineConfig(num_epochs=epochs,
                                                 device_loop=True))
    t0 = time.time()
    m = trainer.train()
    train_s = time.time() - t0
    rec = {"name": name, "kwargs": kw, "gen_seconds": round(gen_s, 1),
           "train_seconds": round(train_s, 1),
           "test_accuracies_pct": [round(a, 2) for a in m.test_accuracies],
           "train_accuracies_pct": [round(a, 2) for a in m.train_accuracies]}
    te = m.test_accuracies
    cross = next((i + 1 for i, a in enumerate(te) if a >= 65.0), None)
    rec["epoch1_test"] = round(te[0], 2)
    rec["cross65_epoch"] = cross
    rec["final_test"] = round(te[-1], 2)
    print(f"== {name}: ep1 {te[0]:.1f}%  cross65 @ {cross}  "
          f"final {te[-1]:.1f}%  ({train_s:.0f}s)", flush=True)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default=None,
                    help="comma-separated indices into GRID (default: all)")
    ap.add_argument("--epochs", type=int, default=20)
    args = ap.parse_args()
    sel = (range(len(GRID)) if args.configs is None
           else [int(i) for i in args.configs.split(",")])
    out = []
    for i in sel:
        name, kw = GRID[i]
        out.append(run_config(name, kw, epochs=args.epochs))
        path = os.path.join(REPO, "experiments", "results",
                            "calibration_sweep.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=2)
    for r in out:
        print(f"{r['name']:>16}: ep1 {r['epoch1_test']:5.1f}  "
              f"cross65 {str(r['cross65_epoch']):>4}  "
              f"final {r['final_test']:5.1f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
