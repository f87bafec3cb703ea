"""Experiment matrix runner: the reference's §6 tables, in-process.

The reference produced its sync/async x {4,8,16} worker results by deploying
Fargate clusters per cell (EXPERIMENT_GUIDE.md:95-111) and scraping
CloudWatch. Here one process runs the full matrix: each cell is a
ParameterStore (sync or async aggregation) + N worker threads sharing the
accelerator, and the output is one experiment JSON per cell in the recorded
``experiment_results/*.json`` schema, plus the comparison/scaling figures.

(The SPMD sync path is the *performance* story and is benchmarked by
bench.py; this runner exists to reproduce the reference's experiment
semantics — logical workers, staleness, aggregated metrics — at any worker
count on any device count.)
"""

from __future__ import annotations

import json
import os

import jax
import numpy as np

from ..data.cifar import Dataset
from ..models import ResNet18
from ..ps.store import ParameterStore, StoreConfig
from ..ps.worker import WorkerConfig, run_workers
from ..utils.pytree import flatten_params
from .parse_logs import aggregate_worker_metrics


def run_cell(dataset: Dataset, mode: str, n_workers: int, *,
             epochs: int = 3, batch_size: int = 128, lr: float = 0.1,
             staleness_bound: int = 5, num_classes: int = 100,
             model=None, seed: int = 0, backend: str = "python",
             augment: bool = True) -> dict:
    """One experiment cell -> experiment record (reference JSON schema)."""
    import jax.numpy as jnp

    model = model or ResNet18(num_classes=num_classes, dtype=jnp.bfloat16)
    variables = model.init(jax.random.PRNGKey(seed),
                           np.zeros((1, 32, 32, 3), np.float32), train=False)
    flat = flatten_params(variables["params"])
    cfg = StoreConfig(mode=mode, total_workers=n_workers, learning_rate=lr,
                      staleness_bound=staleness_bound)
    # 'device' keeps tensors in HBM: no host<->device copy of the ~45 MB
    # parameter and gradient payloads on every worker step.
    from ..ps import make_store
    store = make_store(backend, flat, cfg)

    results = run_workers(
        store, model, dataset, n_workers,
        WorkerConfig(batch_size=batch_size, num_epochs=epochs,
                     augment=augment, seed=seed))
    wc = WorkerConfig(batch_size=batch_size, num_epochs=epochs)
    worker_dicts = [r.metrics(n_workers, lr, wc) for r in results]
    return {
        "experiment_name": f"{mode}_{n_workers}workers",
        # Provenance: the reference's records came from real CIFAR-100 on
        # Fargate; ours must say what data (and device) produced them.
        "dataset": {
            "synthetic": bool(dataset.synthetic),
            "num_classes": int(dataset.num_classes),
            "n_train": int(len(dataset.x_train)),
            "n_test": int(len(dataset.x_test)),
        },
        "device": str(jax.devices()[0]),
        "server_metrics": store.metrics(),
        "worker_metrics_aggregated": aggregate_worker_metrics(worker_dicts),
        "raw_worker_metrics": worker_dicts,
    }


def run_matrix(dataset: Dataset, out_dir: str, *,
               modes=("sync", "async"), worker_counts=(4, 8),
               epochs: int = 3, batch_size: int = 128, lr: float = 0.1,
               num_classes: int = 100, backend: str = "python",
               plots: bool = True, **cell_kw) -> list[dict]:
    os.makedirs(out_dir, exist_ok=True)
    records = []
    for mode in modes:
        for n in worker_counts:
            print(f"=== cell: {mode} x {n} workers ===", flush=True)
            rec = run_cell(dataset, mode, n, epochs=epochs,
                           batch_size=batch_size, lr=lr,
                           num_classes=num_classes, backend=backend,
                           **cell_kw)
            records.append(rec)
            path = os.path.join(out_dir, rec["experiment_name"] + ".json")
            with open(path, "w") as f:
                json.dump(rec, f, indent=2)
            agg = rec["worker_metrics_aggregated"]
            print(f"    total {agg['total_training_time_seconds']:.1f}s, "
                  f"final acc {agg['average_final_accuracy']:.4f}")
    if plots:
        from .visualize import ExperimentVisualizer
        viz = ExperimentVisualizer(out_dir)
        viz.plot_sync_vs_async(os.path.join(out_dir, "sync_vs_async.png"))
        viz.plot_scaling_analysis(os.path.join(out_dir, "scaling.png"))
        print(viz.summary_table())
    return records
