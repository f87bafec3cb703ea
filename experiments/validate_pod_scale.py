"""Pod-scale config validation: ResNet-50 sync-SGD over 32 workers.

BASELINE.json configs[3] is "ResNet-50 / ImageNet-1k sync-SGD, 32 workers
(pod-scale allreduce)" — every other config has recorded evidence at its
worker count, but 32-way sync had only the 8-device dryrun. This compiles
and executes the REAL sync train step (parallel/sync_dp.py shard_map +
pmean; bf16 wire and the int8 ring) for ResNet-50 with the ImageNet stem
and 1000 classes over a 32-device virtual mesh — the driver's
`xla_force_host_platform_device_count` technique at the pod-scale worker
count (and the store bound: MAX_WORKERS is 32, ps/store.py).

Host-sized shapes (112px, global batch 32 = 1 image/worker) keep the
single-core CPU run tractable; the sharding/collective structure is
identical at 224px — the per-device program only scales.

Run:  python experiments/validate_pod_scale.py
Writes experiments/results/pod_scale_dryrun.json.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_WORKERS = 32

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={N_WORKERS}")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
from distributed_parameter_server_for_ml_training_tpu.utils.compile_cache \
    import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np  # noqa: E402


def main() -> int:
    import jax.numpy as jnp

    from distributed_parameter_server_for_ml_training_tpu.models import (
        ResNet50)
    from distributed_parameter_server_for_ml_training_tpu.parallel import (
        make_mesh, make_sync_dp_step, shard_batch)
    from distributed_parameter_server_for_ml_training_tpu.train import (
        create_train_state, server_sgd)

    assert jax.device_count() == N_WORKERS, jax.devices()
    mesh = make_mesh(N_WORKERS)
    size = 112
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                     axis_name="data", imagenet_stem=True, s2d_stem=True)
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (N_WORKERS, size, size, 3),
                          dtype=np.uint8)
    labels = (np.arange(N_WORKERS) % 1000).astype(np.int32)
    bi, bl = shard_batch(mesh, (images, labels))

    record = {"n_workers": N_WORKERS,
              "provenance": ("32-device virtual CPU mesh "
                             "(xla_force_host_platform_device_count) on a "
                             "single host — collective structure, not pod "
                             "timing"),
              "model": "resnet50_imagenet_stem",
              "num_classes": 1000, "image_size": size,
              "global_batch": N_WORKERS, "cells": {}}
    for comp in ("bf16", "int8"):
        state = create_train_state(model, jax.random.PRNGKey(0),
                                   server_sgd(0.1),
                                   input_shape=(1, size, size, 3))
        step = make_sync_dp_step(mesh, compression=comp, augment=False)
        t0 = time.time()
        state, m = step(state, bi, bl, jax.random.PRNGKey(1))
        jax.block_until_ready(state)
        loss0 = float(m["loss"])
        state, m2 = step(state, bi, bl, jax.random.PRNGKey(2))
        jax.block_until_ready(state)
        record["cells"][comp] = {
            "compile_plus_2_steps_seconds": round(time.time() - t0, 1),
            "loss_step1": round(loss0, 4),
            "loss_step2": round(float(m2["loss"]), 4),
            "per_worker_loss_count": int(
                np.asarray(m2["worker_loss"]).shape[0]),
        }
        print(f"{comp}: {record['cells'][comp]}", flush=True)
        assert record["cells"][comp]["per_worker_loss_count"] == N_WORKERS

    out = os.path.join(REPO, "experiments", "results",
                       "pod_scale_dryrun.json")
    with open(out, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
