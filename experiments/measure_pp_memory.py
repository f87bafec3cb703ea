"""Per-device pipeline memory: round-3 replicating schedule vs round-4
sharded-IO + remat.

Round-4 VERDICT item 5 'done' bar: a recorded peak-HBM table showing pp
fits where the replicating scheme OOMs. Compiles the pipeline's
forward+backward (ViT-B/16 encoder stages at 224px token shapes,
batch 512, 4 stages x 8 microbatches) ahead-of-time for each
(shard_io, remat) combination and reads XLA's per-device
``memory_analysis`` — the compiler's own peak-allocation accounting,
which is what determines an OOM on a real chip (v5e: 16 GB HBM/chip).

Scope note: the measured program is the PIPELINE segment (the stage ring
+ its backward), which dominates the step's activation memory — the
replicated prologue/epilogue add one [B, T, D] boundary tensor each.
The full train step cannot be AOT-compiled on the virtual CPU mesh:
XLA:CPU's SPMD partitioner check-fails ("Invalid binary instruction
opcode copy") on the auto-sharded patch-embed conv composed with the
manually-partitioned shard_map; the TPU backend compiles the identical
composition fine (tests/test_model_parallel.py trains it), but AOT for
a 4-device TPU mesh needs 4 physical chips this host lacks.

No execution happens (batch 512 would not fit the CPU host); the SPMD
program is what a TPU stage mesh runs.

Run:  python experiments/measure_pp_memory.py [--batch 512]
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
MICROBATCHES = 8
TOKENS = 197          # 224px / patch 16 -> 196 patches + CLS
HIDDEN = 768


def build_and_measure(batch: int, shard_io: bool, remat: bool) -> dict:
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_parameter_server_for_ml_training_tpu.models.vit import (
        EncoderStage)
    from distributed_parameter_server_for_ml_training_tpu.parallel.pipeline import (
        make_pipeline_apply, stack_stage_params)

    mesh = Mesh(np.array(jax.devices()[:STAGES]).reshape(1, STAGES),
                ("data", "stage"))
    stage = EncoderStage(num_blocks=12 // STAGES, num_heads=12,
                         dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    tok = jnp.zeros((1, TOKENS, HIDDEN), jnp.float32)
    stage_ps = [stage.init(jax.random.fold_in(rng, 100 + s), tok)["params"]
                for s in range(STAGES)]
    stacked = jax.tree_util.tree_map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P("stage"))),
        stack_stage_params(stage_ps))

    pipe = make_pipeline_apply(
        mesh, lambda p, x: stage.apply({"params": p}, x),
        num_microbatches=MICROBATCHES, data_axis=None,
        shard_io=shard_io, remat=remat)

    def loss_fn(stages, x):
        # sum over the pipeline output: the cotangent entering the ring's
        # backward has the same [B, T, D] shape the real CE loss feeds it.
        return jnp.sum(pipe(stages, x).astype(jnp.float32) ** 2)

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    # fp32 boundary tensors: a bf16 pipeline input check-fails the XLA:CPU
    # compiler (same "opcode copy" bug class as the full-step composition;
    # the TPU backend runs bf16 pipelines fine — the trainers do). This
    # overstates the IO tensors 2x, identically across all four
    # combinations, so the comparison stands.
    x = jax.ShapeDtypeStruct((batch, TOKENS, HIDDEN), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    compiled = grad_fn.lower(stacked, x).compile()
    ma = compiled.memory_analysis()
    rec = {
        "shard_io": shard_io, "remat": remat,
        "temp_gb": round(ma.temp_size_in_bytes / 1e9, 3),
        "argument_gb": round(ma.argument_size_in_bytes / 1e9, 3),
        "output_gb": round(ma.output_size_in_bytes / 1e9, 3),
        "peak_estimate_gb": round(
            (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes) / 1e9, 3),
    }
    rec["fits_v5e"] = rec["peak_estimate_gb"] < V5E_HBM_GB
    print(rec, flush=True)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=512)
    args = ap.parse_args()

    rows = []
    for shard_io, remat in ((False, False), (True, False), (False, True),
                            (True, True)):
        rows.append(build_and_measure(args.batch, shard_io, remat))
    out = os.path.join(REPO, "experiments", "results", "pp_memory.json")
    with open(out, "w") as f:
        json.dump({
            "config": {"model": "vit_b16 encoder pipeline",
                       "tokens": TOKENS, "hidden": HIDDEN,
                       "batch": args.batch, "stages": STAGES,
                       "microbatches": MICROBATCHES,
                       "dtype": "bfloat16",
                       "method": "AOT compile + XLA memory_analysis of "
                                 "the pipeline fwd+bwd, per device, "
                                 "4-stage virtual mesh"},
            "v5e_hbm_gb": V5E_HBM_GB,
            "rows": rows}, f, indent=2)
        f.write("\n")
    print(f"wrote {out}")
    print("\n| shard_io | remat | temp GB | peak est GB | fits v5e 16GB |")
    print("|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['shard_io']} | {r['remat']} | {r['temp_gb']} | "
              f"{r['peak_estimate_gb']} | {r['fits_v5e']} |")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
