"""Pipeline schedule efficiency: GPipe vs 1F1B, quantified (round-4 VERDICT
weak 5).

Three measurements per (schedule, M) at S=4 stages, each from an exact
artifact rather than a wall clock this 1-chip host cannot produce (a real
stage mesh needs S chips; CPU "timing" of a virtual mesh on one core would
measure nothing but the host):

- **tick-table occupancy** — useful units / (ticks x stages), computed from
  the actual schedule table the SPMD program unrolls (build_1f1b_schedule
  verifies its own tables; GPipe's occupancy is closed-form M/(S+M-1) per
  phase). This IS the bubble: 1 - occupancy = idle tick fraction.
- **XLA memory_analysis** — per-device peak allocation of the AOT-compiled
  train step (the number that decides an OOM; same method as
  measure_pp_memory.py).
- **XLA cost_analysis FLOPs** — total program FLOPs, exposing each
  schedule's recompute overhead (GPipe remat vs 1F1B's vjp-per-unit).

Key facts the recorded table shows (see the JSON's "conclusions"):
- at EQUAL (S, M), non-interleaved 1F1B and GPipe have the SAME tick count
  2(S+M-1) and bubble (S-1)/(S+M-1) — 1F1B's schedule-level win is its
  O(S) in-flight activation cap (vs GPipe's O(M) stash; stash_gb column);
- the MEASURED program peak goes the other way: the 1F1B body's per-tick
  lax.cond units and dynamically indexed buffers defeat XLA's aliasing,
  costing more than the stash cap saves — a real, recorded negative
  result for single-program 1F1B on TPU;
- the bubble reduction itself comes from raising M: the GPipe M=32 row
  fits v5e at an 8.6% bubble (vs 27.3% at M=8) thanks to remat+sharded
  IO — the TPU-idiomatic route the trainers take. Megatron-style 1F1B
  pays off under per-stage asynchronous controllers, not inside one
  lockstep XLA program (the per-tick ring collectives synchronize
  stages, so a mixed fwd/bwd tick costs max(t_fwd, t_bwd) for all).

Run:  python experiments/measure_pp_schedule.py [--batch 512]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

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

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

V5E_HBM_GB = 16.0
STAGES = 4
TOKENS = 197
HIDDEN = 768


def schedule_occupancy(schedule: str, s: int, m: int) -> dict:
    from distributed_parameter_server_for_ml_training_tpu.parallel.pipeline \
        import build_1f1b_schedule

    if schedule == "1f1b":
        t = build_1f1b_schedule(s, m)
        ticks = int(t["ticks"])
        useful = int((t["act"] != 0).sum())
        # max in-flight fwd-done-not-bwd-done units (stashed activations)
        stash = 0
        for stage in range(s):
            run = np.cumsum((t["act"][:, stage] == 1).astype(int)
                            - (t["act"][:, stage] == 2).astype(int))
            stash = max(stash, int(run.max()))
    else:
        ticks = 2 * (s + m - 1)      # fwd unroll + autodiff replay
        useful = 2 * m * s
        stash = m                    # one stashed input per microbatch
    return {
        "ticks": ticks,
        "useful_units": useful,
        "occupancy": round(useful / (ticks * s), 4),
        "bubble_fraction": round(1 - useful / (ticks * s), 4),
        "max_inflight_activations_per_stage": stash,
    }


def build_step(schedule: str, m: int, batch: int):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_parameter_server_for_ml_training_tpu.models.vit import (
        EncoderStage)
    from distributed_parameter_server_for_ml_training_tpu.parallel.pipeline \
        import make_pipeline_train_step, stack_stage_params

    mesh = Mesh(np.array(jax.devices()[:STAGES]), ("stage",))
    stage = EncoderStage(num_blocks=12 // STAGES, num_heads=12,
                         dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    tok = jnp.zeros((1, TOKENS, HIDDEN), jnp.float32)
    stage_ps = [stage.init(jax.random.fold_in(rng, 100 + s), tok)["params"]
                for s in range(STAGES)]
    stacked = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P("stage"))),
        stack_stage_params(stage_ps))

    def loss_fn(y_mb, t_mb):
        # l2 head stand-in: the cotangent entering the ring backward has
        # the real [mb, T, D] shape; identical across both schedules.
        return jnp.mean((y_mb.astype(jnp.float32) - t_mb) ** 2)

    step = make_pipeline_train_step(
        mesh, lambda p, x: stage.apply({"params": p}, x), loss_fn, m,
        schedule=schedule)
    x = jax.ShapeDtypeStruct((batch, TOKENS, HIDDEN), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    y = jax.ShapeDtypeStruct((batch, TOKENS, HIDDEN), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    return step, stacked, x, y


def measure(schedule: str, m: int, batch: int) -> dict:
    occ = schedule_occupancy(schedule, STAGES, m)
    step, stacked, x, y = build_step(schedule, m, batch)
    compiled = step.lower(stacked, x, y).compile()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    flops = (ca or {}).get("flops", 0.0)
    rec = {
        "schedule": schedule, "stages": STAGES, "microbatches": m,
        **occ,
        # schedule-level stash: max in-flight microbatch inputs x bytes
        "stash_gb": round(occ["max_inflight_activations_per_stage"]
                          * (batch // m) * TOKENS * HIDDEN * 4 / 1e9, 3),
        "temp_gb": round(ma.temp_size_in_bytes / 1e9, 3),
        "argument_gb": round(ma.argument_size_in_bytes / 1e9, 3),
        "peak_estimate_gb": round(
            (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes) / 1e9, 3),
        # NOTE: XLA cost_analysis sums BOTH lax.cond branches (static
        # accounting); the EXECUTED flops follow the tick tables and are
        # equal for both schedules up to the loss head. Recorded anyway —
        # it bounds program size, not runtime.
        "program_tflops_static": round(flops / 1e12, 3),
    }
    rec["fits_v5e"] = rec["peak_estimate_gb"] < V5E_HBM_GB
    print(rec, flush=True)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--microbatches", default="8,32")
    args = ap.parse_args()
    ms = [int(v) for v in args.microbatches.split(",")]

    rows = []
    for m in ms:
        for schedule in ("gpipe", "1f1b"):
            rows.append(measure(schedule, m, args.batch))
        _write(rows, args)  # incremental
    return 0


def _write(rows, args) -> None:
    out = os.path.join(REPO, "experiments", "results", "pp_schedule.json")
    with open(out, "w") as f:
        json.dump({
            "config": {"model": "vit_b16 encoder pipeline (3 blocks/stage)",
                       "tokens": TOKENS, "hidden": HIDDEN,
                       "batch": args.batch, "stages": STAGES,
                       "dtype": "bfloat16 params, fp32 boundaries",
                       "method": "tick-table occupancy (exact) + AOT "
                                 "memory_analysis + cost_analysis, "
                                 "4-stage virtual mesh; equal-numerics "
                                 "asserted in tests/test_pipeline.py"},
            "lockstep_caveat": "single-program SPMD: per-tick ring "
                               "collectives synchronize stages, so a "
                               "mixed fwd/bwd tick costs max(t_fwd, "
                               "t_bwd) for every stage; tick counts "
                               "price both schedules in the same units",
            "conclusions": [
                "At equal (S, M) both schedules have the same tick count "
                "2(S+M-1) and bubble (S-1)/(S+M-1); 1F1B's schedule-level "
                "win is the O(S) in-flight stash (stash_gb column: capped "
                "at S microbatches vs GPipe's M).",
                "MEASURED program peak goes the OTHER way: the 1F1B "
                "body's per-tick lax.cond units and dynamically indexed "
                "buffers defeat XLA's liveness/aliasing analysis, costing "
                "more than the stash cap saves — GPipe+remat lets XLA "
                "free each microbatch's residuals optimally.",
                "TPU-idiomatic conclusion, adopted by the trainers: keep "
                "GPipe+remat+sharded-IO and raise M — the M=32 GPipe row "
                "fits v5e with an 8.6% bubble (vs 27.3% at M=8), which is "
                "the bubble reduction 1F1B's memory headroom is FOR, "
                "without fighting the compiler. Megatron-style 1F1B "
                "pays off under per-stage asynchronous controllers, not "
                "inside one lockstep XLA program (pipeline.py module "
                "comment).",
            ],
            "v5e_hbm_gb": V5E_HBM_GB,
            "rows": rows}, f, indent=2)
        f.write("\n")
    print(f"wrote {out}", flush=True)
    print("\n| schedule | M | ticks | bubble | max stash/stage | "
          "peak GB | TFLOPs |")
    print("|---|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['schedule']} | {r['microbatches']} | {r['ticks']} | "
              f"{r['bubble_fraction']} | "
              f"{r['max_inflight_activations_per_stage']} | "
              f"{r['peak_estimate_gb']} | {r['program_tflops_static']} |")


if __name__ == "__main__":
    raise SystemExit(main())
