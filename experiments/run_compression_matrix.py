"""Recorded compression wire-matrix (ISSUE 6 acceptance evidence).

Two recorded cells, PR-2/PR-4 demo format (explicit PASS/FAIL checks, one
JSON artifact):

1. **Codec matrix** — the same 2-worker sync training run (tiny ResNet,
   synthetic CIFAR, fixed seed) under each push codec
   (fp32 control / fp16 / int8 / int4+EF / topk+EF / adaptive). Per cell:
   final accuracy, exact wire-payload bytes from the per-worker telemetry
   counters (precodec vs wire), effective bits/value, server-side
   compressed-domain engagement. Acceptance: **int4+EF moves ≥4× fewer
   push bytes than fp32 at final-accuracy parity within tolerance**.
2. **Server apply microbench, 8 workers sync** — the same int8 push
   stream against `compressed_domain=True` (homomorphic int32 accumulate,
   dequantize once per round) vs `False` (the legacy decode-per-push
   path). Acceptance: **measured per-push latency drop (the fp32 decode
   eliminated) and end-to-end round-wall speedup**.

Topology note: cells run in-process (worker threads against the python
store) — the byte counters count exactly the payload bytes the gRPC wire
would carry (the codec runs in `PSWorker._push` either way), and the
gRPC-specific negotiation/degradation matrix is pinned by tier-1 tests
(`tests/test_comms.py::TestCompressedDomainWire`).

Run:  python experiments/run_compression_matrix.py [--quick]
Artifact: experiments/results/compression/compression_matrix.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

OUT = os.path.join(REPO, "experiments", "results", "compression")

CODEC_CELLS = ["none", "fp16", "int8", "int4", "topk", "adaptive"]


def _counter_value(name, **labels):
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        get_registry)
    return get_registry().counter(name, **labels).value


def run_codec_cell(codec: str, model, dataset, epochs: int,
                   workers: int = 2) -> dict:
    import numpy as np

    from distributed_parameter_server_for_ml_training_tpu.ps import (
        ParameterStore, StoreConfig, WorkerConfig, run_workers)
    from distributed_parameter_server_for_ml_training_tpu.utils import (
        flatten_params)
    import jax

    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 32, 32, 3), np.float32),
                           train=False)
    store = ParameterStore(
        flatten_params(variables["params"]),
        StoreConfig(mode="sync", total_workers=workers,
                    learning_rate=0.05, push_codec=codec))
    # Byte counters are process-cumulative (worker ids repeat across
    # cells) — snapshot before/after and diff.
    wids = [str(i) for i in range(workers)]
    before = {
        w: (_counter_value("dps_worker_push_bytes_total",
                           stage="precodec", worker=w),
            _counter_value("dps_worker_push_bytes_total",
                           stage="wire", worker=w))
        for w in wids}
    compressed_before = store._tm_compressed.value
    t0 = time.time()
    results = run_workers(store, model, dataset, n_workers=workers,
                          config=WorkerConfig(batch_size=32,
                                              num_epochs=epochs,
                                              augment=False, seed=0))
    wall = time.time() - t0
    pre = wire = 0
    for w in wids:
        b = before[w]
        pre += _counter_value("dps_worker_push_bytes_total",
                              stage="precodec", worker=w) - b[0]
        wire += _counter_value("dps_worker_push_bytes_total",
                               stage="wire", worker=w) - b[1]
    pushes = sum(r.pushes_accepted for r in results)
    accs = [r.test_accuracies[-1] for r in results if r.test_accuracies]
    return {
        "push_codec": codec,
        "workers": workers,
        "epochs": epochs,
        "wall_seconds": round(wall, 2),
        "global_step": store.global_step,
        "pushes_accepted": pushes,
        "final_accuracy": round(float(sum(accs) / max(len(accs), 1)), 4),
        "push_mb_precodec": round(pre / 1e6, 3),
        "push_mb_wire": round(wire / 1e6, 3),
        "byte_reduction_vs_fp32": round(pre / wire, 2) if wire else None,
        "effective_bits_per_value": round(wire * 32.0 / pre, 3)
        if pre else None,
        "server_compressed_accum_pushes": int(
            store._tm_compressed.value - compressed_before),
        "qscale_version": store.gradient_scales()[1],
    }


def run_apply_bench(workers: int = 8, rounds: int = 30,
                    n_tensors: int = 32, tensor_size: int = 32768) -> dict:
    """Server-side A/B at 8 workers sync: identical int8 push streams
    against the homomorphic path vs the legacy decode-per-push path.
    Reports per-push latency (non-round-final pushes: pure stash/decode,
    no apply) and total wall."""
    import numpy as np

    from distributed_parameter_server_for_ml_training_tpu.ops.compression \
        import compress_push
    from distributed_parameter_server_for_ml_training_tpu.ps import (
        ParameterStore, StoreConfig)

    def bench(compressed: bool):
        rng = np.random.default_rng(0)
        params = {f"p{i}": rng.normal(size=tensor_size).astype(np.float32)
                  for i in range(n_tensors)}
        store = ParameterStore(params, StoreConfig(
            mode="sync", total_workers=workers, learning_rate=0.01,
            push_codec="int8", compressed_domain=compressed))
        payloads = [compress_push(
            {k: rng.normal(size=v.shape).astype(np.float32)
             for k, v in params.items()}) for _ in range(workers)]
        push_s = []
        t0 = time.perf_counter()
        for r in range(rounds):
            for w in range(workers):
                t1 = time.perf_counter()
                store.push(w, payloads[w], r)
                push_s.append(time.perf_counter() - t1)
        wall = time.perf_counter() - t0
        per_round = np.array(push_s).reshape(rounds, workers)
        return {
            "wall_seconds": round(wall, 3),
            # Non-final pushes carry no apply: their latency IS the
            # per-push decode/stash cost the tentpole removes.
            "per_push_ms": round(float(per_round[:, :-1].mean()) * 1e3, 4),
            # The round-completing push runs the aggregation + apply.
            "round_apply_ms": round(float(per_round[:, -1].mean()) * 1e3,
                                    4),
            "compressed_accum_pushes": int(store._tm_compressed.value),
        }

    n_params = n_tensors * tensor_size
    legacy = bench(False)
    homomorphic = bench(True)
    return {
        "workers": workers,
        "rounds": rounds,
        "model_params": n_params,
        "payload": "int8 + per-tensor scales",
        "legacy_decode_per_push": legacy,
        "compressed_domain": homomorphic,
        "per_push_speedup": round(
            legacy["per_push_ms"] / homomorphic["per_push_ms"], 2),
        "round_wall_speedup": round(
            legacy["wall_seconds"] / homomorphic["wall_seconds"], 2),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="1 training epoch, fewer bench rounds")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--acc-tolerance", type=float, default=0.06,
                    help="final-accuracy parity band vs the fp32 control")
    args = ap.parse_args()
    epochs = 1 if args.quick else args.epochs
    bench_rounds = 10 if args.quick else 30

    import jax
    jax.config.update("jax_platforms", "cpu")
    from distributed_parameter_server_for_ml_training_tpu.utils.compile_cache \
        import enable_compile_cache  # noqa: E402

    enable_compile_cache()

    from distributed_parameter_server_for_ml_training_tpu.data import (
        synthetic_cifar100)
    from distributed_parameter_server_for_ml_training_tpu.models import (
        ResNet)

    dataset = synthetic_cifar100(n_train=640, n_test=128, num_classes=10,
                                 seed=1)
    model = ResNet(stage_sizes=(1, 1), num_filters=8, num_classes=10)

    cells = []
    for codec in CODEC_CELLS:
        cell = run_codec_cell(codec, model, dataset, epochs)
        cells.append(cell)
        print(f"cell {codec}: acc={cell['final_accuracy']} "
              f"wire={cell['push_mb_wire']}MB "
              f"({cell['byte_reduction_vs_fp32']}x under fp32, "
              f"{cell['effective_bits_per_value']} bits/value)", flush=True)

    bench = run_apply_bench(rounds=bench_rounds)
    print(f"apply bench (8w sync): per-push "
          f"{bench['legacy_decode_per_push']['per_push_ms']}ms -> "
          f"{bench['compressed_domain']['per_push_ms']}ms "
          f"({bench['per_push_speedup']}x), wall "
          f"{bench['round_wall_speedup']}x", flush=True)

    by_codec = {c["push_codec"]: c for c in cells}
    control = by_codec["none"]
    int4 = by_codec["int4"]
    checks = []

    def check(name, ok, detail):
        checks.append({"check": name, "pass": bool(ok), "detail": detail})
        print(f"[{'PASS' if ok else 'FAIL'}] {name}: {detail}", flush=True)

    check("int4_byte_reduction_ge_4x",
          int4["byte_reduction_vs_fp32"] is not None
          and int4["byte_reduction_vs_fp32"] >= 4.0,
          f"{int4['byte_reduction_vs_fp32']}x vs fp32 "
          f"({int4['push_mb_wire']} vs {control['push_mb_wire']} MB)")
    acc_gap = abs(int4["final_accuracy"] - control["final_accuracy"])
    check("int4_accuracy_parity",
          acc_gap <= args.acc_tolerance,
          f"|{int4['final_accuracy']} - {control['final_accuracy']}| = "
          f"{round(acc_gap, 4)} <= {args.acc_tolerance}")
    check("every_quantized_push_stayed_compressed",
          all(by_codec[c]["server_compressed_accum_pushes"]
              >= by_codec[c]["pushes_accepted"]
              for c in ("int8", "int4", "topk", "adaptive")),
          "dps_store_compressed_accum_total covered all accepted pushes "
          "in every quantized cell")
    check("shared_scales_published",
          all(by_codec[c]["qscale_version"] >= 1
              for c in ("int8", "int4", "topk", "adaptive")),
          "gradient_scales() versioned >= 1 after training in every "
          "quantized cell")
    check("apply_per_push_speedup_ge_3x",
          bench["per_push_speedup"] >= 3.0,
          f"{bench['per_push_speedup']}x (decode-per-push eliminated)")
    check("apply_round_wall_speedup",
          bench["round_wall_speedup"] >= 1.2,
          f"{bench['round_wall_speedup']}x end-to-end at 8 workers")

    os.makedirs(OUT, exist_ok=True)
    artifact = {
        "experiment": "compression_matrix",
        "topology": "in-process: N worker threads against the python "
                    "store; byte columns are exact codec-payload bytes "
                    "(the same bytes a gRPC push would carry)",
        "cells": cells,
        "apply_bench_8w_sync": bench,
        "checks": checks,
        "all_pass": all(c["pass"] for c in checks),
    }
    out_path = os.path.join(OUT, "compression_matrix.json")
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print(f"\n{sum(c['pass'] for c in checks)}/{len(checks)} checks PASS "
          f"-> {out_path}", flush=True)
    return 0 if artifact["all_pass"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
