"""Fetch-codec accuracy parity: bf16-compressed fetches vs fp32 fetches.

Round-4 VERDICT weak 3 'done' bar: the dominant wire term (fp32 parameter
fetches — the reference's own hot spot, server.py:222's ~45 MB re-pickle)
halves under ``serve --fetch-codec bf16`` *with curves unchanged*. The
byte halving is recorded by the wire matrix's ``*_fetchbf16`` cells; THIS
script records the numerics half: two identical PS training runs (same
model/seed/shards/recipe, 2 workers against an in-process store) differing
ONLY in the store's fetch codec, loss/accuracy curves side by side.

bf16 keeps fp32's exponent range and drops 16 mantissa bits; workers hold
the decompressed weights only for the K-step window before refetching, so
rounding does not accumulate — the curves should track within noise.

Run:  python experiments/run_fetch_codec_parity.py [--epochs 4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
from distributed_parameter_server_for_ml_training_tpu.utils.compile_cache \
    import enable_compile_cache  # noqa: E402

enable_compile_cache()


def run_arm(fetch_codec: str, epochs: int, n_train: int) -> dict:
    import numpy as np

    from distributed_parameter_server_for_ml_training_tpu.data.cifar import (
        compositional_cifar100)
    from distributed_parameter_server_for_ml_training_tpu.models import (
        get_model)
    from distributed_parameter_server_for_ml_training_tpu.ps import (
        ParameterStore, StoreConfig)
    from distributed_parameter_server_for_ml_training_tpu.ps.worker import (
        PSWorker, WorkerConfig)
    from distributed_parameter_server_for_ml_training_tpu.utils.pytree \
        import flatten_params

    ds = compositional_cifar100(n_train=n_train, n_test=1024)
    model = get_model("vit_tiny", num_classes=ds.num_classes,
                      image_size=32)
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 32, 32, 3), np.float32),
                           train=False)
    store = ParameterStore(
        flatten_params(variables["params"]),
        StoreConfig(mode="async", total_workers=2, learning_rate=0.1,
                    push_codec="fp16", fetch_codec=fetch_codec))
    cfg = WorkerConfig(batch_size=64, num_epochs=epochs, augment=False,
                       seed=0)
    t0 = time.time()
    workers = [PSWorker(store, model, ds, cfg, worker_name=f"w{i}")
               for i in range(2)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    for w in workers:
        if w.result.error is not None:
            raise w.result.error
    return {
        "fetch_codec": fetch_codec,
        "wall_seconds": round(time.time() - t0, 1),
        "per_worker_accuracy_curves": {
            w.worker_name: w.result.test_accuracies for w in workers},
        "final_accuracy_mean": round(float(np.mean(
            [w.result.test_accuracies[-1] for w in workers])), 4),
        "server_metrics": store.metrics(),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--num-train", type=int, default=4096)
    args = ap.parse_args()

    out = os.path.join(REPO, "experiments", "results", "calibrated",
                       "fetch_codec_parity.json")
    record = {
        "experiment_name": "fetch_codec_parity",
        "setup": "2 in-process PSWorkers, async store, push fp16 (the "
                 "reference default); ONLY the fetch codec differs. "
                 "Byte effect recorded separately by the wire matrix "
                 "(async_4w_fp16_*_fetchbf16 cells: params-in halves).",
    }
    for codec in ("none", "bf16"):
        record[f"fetch_{codec}"] = run_arm(codec, args.epochs,
                                           args.num_train)
        with open(out, "w") as f:
            json.dump(record, f, indent=2, default=float)
            f.write("\n")
        print(f"fetch_codec={codec}: "
              f"{record[f'fetch_{codec}']['final_accuracy_mean']} "
              f"final acc", flush=True)
    a = record["fetch_none"]["final_accuracy_mean"]
    b = record["fetch_bf16"]["final_accuracy_mean"]
    record["parity"] = {
        "final_acc_fp32_fetch": a, "final_acc_bf16_fetch": b,
        "abs_delta": round(abs(a - b), 4),
        # Async-store runs are order-dependent (thread interleaving), so
        # exact equality is not expected even at fetch_codec=none; the
        # bar is "within run-to-run noise".
        "within_noise": abs(a - b) < 0.02,
    }
    with open(out, "w") as f:
        json.dump(record, f, indent=2, default=float)
        f.write("\n")
    print("parity:", record["parity"])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
