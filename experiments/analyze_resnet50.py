"""Per-stage time/FLOPs breakdown of ResNet-50 @224 on the attached chip.

Round-4 VERDICT item 6: the 224px ResNet-50 sits near ~27% MFU while the
other families reach 44-47%. This measures WHERE the step goes: fwd+bwd
wall time and XLA-counted FLOPs of model PREFIXES (stem, +stage0, ...,
full), so per-stage deltas give each stage's achieved TF/s — the
trace-backed ceiling analysis PERF.md records.

Run:  python experiments/analyze_resnet50.py [--batch 256]
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

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REPS = 10  # chained iterations per dispatch (amortizes host dispatch)
TRIALS_MIN = 5  # median-of-5 minimum: best-of-N lets one outlier corrupt
                # prefix deltas (same fix as measure_mfu's bench)


def device_peak_tflops() -> float:
    """bf16 peak (TFLOP/s) of the chip this process runs on; fails on a
    device kind the table does not know."""
    from distributed_parameter_server_for_ml_training_tpu.telemetry.profiler \
        import require_peak_flops

    return require_peak_flops(jax.devices()[0].device_kind) / 1e12


def measure_prefix(n_stages: int, batch: int, trials: int) -> dict:
    # The REAL registry architecture truncated in place (max_stages) —
    # not a re-implementation that could drift from models/resnet.py.
    from distributed_parameter_server_for_ml_training_tpu.models.resnet import (
        Bottleneck, ResNet)

    model = ResNet(stage_sizes=(3, 4, 6, 3), block_cls=Bottleneck,
                   num_classes=1000, dtype=jnp.bfloat16,
                   imagenet_stem=True, s2d_stem=True,
                   max_stages=n_stages)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(batch, 224, 224, 3)), jnp.float32)
    vs = model.init(jax.random.PRNGKey(0), x[:1], train=False)

    def loss(params, x):
        y, _ = model.apply({"params": params,
                            "batch_stats": vs["batch_stats"]}, x,
                           train=True, mutable=["batch_stats"])
        return jnp.sum(y.astype(jnp.float32) ** 2) * 1e-6

    grad = jax.grad(loss)

    def chain(params, x):
        def body(p, _):
            g = grad(p, x)
            return jax.tree_util.tree_map(
                lambda a, b: a - 1e-6 * b.astype(a.dtype), p, g), ()
        out, _ = jax.lax.scan(body, params, None, length=REPS)
        return jax.tree_util.tree_reduce(
            lambda a, b: a + jnp.sum(jnp.abs(b).astype(jnp.float32)), out,
            0.0)

    jitted = jax.jit(chain)
    single = jax.jit(grad).lower(vs["params"], x).compile()
    flops = float(single.cost_analysis().get("flops", 0.0))
    _ = float(jitted(vs["params"], x))          # compile + warm
    times = []
    for _t in range(max(trials, TRIALS_MIN)):
        t0 = time.perf_counter()
        _ = float(jitted(vs["params"], x))
        times.append(time.perf_counter() - t0)
    med = float(np.median(times))
    ms = med / REPS * 1e3
    return {"prefix_stages": n_stages, "ms_fwd_bwd": round(ms, 2),
            "gflops": round(flops / 1e9, 1),
            "tf_per_s": round(flops / (med / REPS) / 1e12, 1),
            "mfu_pct": round(100 * flops / (med / REPS) / 1e12
                             / device_peak_tflops(), 1)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--trials", type=int, default=3)
    args = ap.parse_args()

    rows = []
    for n in range(5):
        rows.append(measure_prefix(n, args.batch, args.trials))
        print(rows[-1], flush=True)
    # per-stage deltas
    deltas = []
    for i in range(1, len(rows)):
        dms = rows[i]["ms_fwd_bwd"] - rows[i - 1]["ms_fwd_bwd"]
        dfl = rows[i]["gflops"] - rows[i - 1]["gflops"]
        deltas.append({
            "stage": i - 1,
            "ms": round(dms, 2),
            "gflops": round(dfl, 1),
            "tf_per_s": round(dfl / max(dms, 1e-9), 1),  # GF/ms == TF/s
            "mfu_pct": round(100 * (dfl / max(dms, 1e-9))
                             / device_peak_tflops(), 1),
        })
        print(deltas[-1], flush=True)
    out = os.path.join(REPO, "experiments", "results",
                       "resnet50_stage_breakdown.json")
    with open(out, "w") as f:
        json.dump({"batch": args.batch, "reps_per_dispatch": REPS,
                   "prefixes": rows, "stage_deltas": deltas}, f, indent=2)
        f.write("\n")
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
