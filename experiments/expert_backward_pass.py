"""One expert layer's forward and backward at the two LM cells' shapes: the
parent's weight-gradient accumulate against the in-place kernel (PR 35).

    JAX_PLATFORMS=cpu python experiments/expert_backward_pass.py compile
    chiprun -- python experiments/expert_backward_pass.py chip [options]

``compile`` needs no chip: it compiles the layer (``held_expert_ffn`` and
its gradient, as the cells call it) for a *described* v5e, as
``tests/test_compile_v5e.py`` does, once with the parent's backward pass
(``jax.vjp`` of the whole pass, then ``carry + dW`` over all three ``[C, D,
F]`` arrays) and once with the change's, and prints what the backward loop's
body holds: every instruction that produces an array of a carry's shape, the
grouped kernels, and the compiler's ``estimated_cycles`` where it gives
them. Nothing it prints is a device time.

``chip`` runs both on the chip it is given and prints one ``RESULT_JSON:``
line: ms a call of the layer's forward + backward (host clock around
``block_until_ready``, the median of ``--calls`` calls) for each shape and
variant, ms a pass of the accumulate alone (the three weight gradients of a
pass added to their carries, eight passes in a loop), and how far the
change's gradients are from the parent's. ``--acc-tile``, ``--block-rows``
and ``--chunk-k`` override the kernel's tile constants for a sweep (15360
makes a visit's matmul one). Not imported by anything a cell runs.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

#: the two cells' expert layers: tokens a step, width, expert width, experts
#: a token, experts in all, held, capacity factor, activation, combine
SHAPES = {
    "smallthinker": dict(n=16384, d=2560, f=768, k=6, e=64, held=16,
                         capacity=4.0, activation="relu", combine="gather"),
    "joyai": dict(n=16384, d=2048, f=768, k=8, e=256, held=16,
                  capacity=2.5, activation="silu", combine="scatter"),
}


def parent_pass_grads(moe):
    """The parent's backward of a pass (PR 34's ``_work_off_bwd`` body):
    ``jax.vjp`` of the whole pass, the weights' gradients in the rows' dtype,
    then the whole carry read, added to and written."""
    import jax

    def pass_grads(rows_x, rows_w, experts, group, valid, activation,
                   d_part, de, impl):
        _out, vjp = jax.vjp(
            lambda rx, rw, ex: moe._pass_out(rx, rw, ex, group, valid,
                                             activation),
            rows_x, rows_w, experts)
        d_rows_x, d_rows_w, d_experts = vjp(d_part)
        return d_rows_x, d_rows_w, jax.tree_util.tree_map(
            jax.numpy.add, de, d_experts)
    return pass_grads


def layer(shape: dict, variant: str):
    """``f(x, experts, weights, idx, cot) -> (dx, d_experts, d_weights)`` of
    the cell's expert layer, under the parent's or the change's backward."""
    import jax
    import jax.numpy as jnp

    from distributed_parameter_server_for_ml_training_tpu.ops import (
        attention)
    from distributed_parameter_server_for_ml_training_tpu.parallel import moe

    rows, min_passes = moe.pass_plan(shape["n"], shape["k"], shape["held"],
                                     shape["e"], shape["capacity"])
    change = moe._pass_grads
    parent = parent_pass_grads(moe)

    def loss(x, experts, weights, idx, cot):
        y, _done = moe.held_expert_ffn(
            x, idx, weights, experts, 0, rows=rows, min_passes=min_passes,
            activation=shape["activation"], combine=shape["combine"])
        return jnp.sum(y.astype(jnp.float32) * cot)

    def f(*args):
        # the choice is made while the function is traced
        was = moe._pass_grads, attention._on_tpu
        moe._pass_grads = parent if variant == "parent" else change
        attention._on_tpu = lambda: True
        try:
            return jax.grad(loss, argnums=(0, 1, 2))(*args)
        finally:
            moe._pass_grads, attention._on_tpu = was
    return f, rows, min_passes


def arguments(shape: dict, make):
    """The layer's arguments through ``make(shape, dtype, kind)``."""
    import jax.numpy as jnp
    n, d, f, k, held = (shape[key] for key in ("n", "d", "f", "k", "held"))
    experts = {"gate": make((held, d, f), jnp.float32, "weight"),
               "up": make((held, d, f), jnp.float32, "weight"),
               "down": make((held, f, d), jnp.float32, "weight")}
    return (make((n, d), jnp.bfloat16, "rows"), experts,
            make((n, k), jnp.float32, "gates"), make((n, k), jnp.int32, "idx"),
            make((n, d), jnp.float32, "rows"))


# -- compile: what the backward loop's body holds ----------------------------

def _while_bodies(text: str) -> list[str]:
    names = set(re.findall(r"body=%?([\w.\-]+)", text))
    bodies = []
    for name in names:
        at = text.find(f"\n%{name} ")
        if at < 0:
            at = text.find(f"\n{name} ")
        if at >= 0:
            bodies.append(text[at:text.index("\n}\n", at)])
    return bodies


def compile_report() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    for name, shape in SHAPES.items():
        carries = {f"f32[{shape['held']},{shape['d']},{shape['f']}]",
                   f"f32[{shape['held']},{shape['f']},{shape['d']}]"}
        for variant in ("parent", "change"):
            f, rows, passes = layer(shape, variant)
            compiled = jax.jit(f).lower(*arguments(
                shape, lambda dims, dtype, _kind: jax.ShapeDtypeStruct(
                    dims, dtype, sharding=chip))).compile()
            memory = compiled.memory_analysis()
            print(f"{name} {variant}: rows {rows} x {passes} passes; "
                  f"temporaries {memory.temp_size_in_bytes / 1e6:.1f} MB")
            for body in _while_bodies(compiled.as_text()):
                for line in body.splitlines():
                    made = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) (\w[\w\-]*)\(",
                                    line)
                    if not made:
                        continue
                    out, kind = made.group(2), made.group(3)
                    grouped = ("ragged-dot" in made.group(1)
                               or "grouped_grad" in made.group(1))
                    if not grouped and not (
                            out.split("{")[0] in carries
                            and kind in ("fusion", "custom-call")):
                        continue
                    cycles = re.search(r'"estimated_cycles":"?(\d+)', line)
                    scope = re.search(r'op_name="([^"]*)"', line)
                    print(f"    %{made.group(1)} = {out.split('{')[0]} {kind}"
                          f" cycles={cycles.group(1) if cycles else '-'}"
                          f" op_name=...{scope.group(1)[-60:] if scope else ''}")
    return 0


# -- chip: times --------------------------------------------------------------

def _timed(fn, args, calls: int) -> float:
    import jax
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def accumulate_alone(shape: dict, variant: str, passes: int, rows: int):
    """``f(de, rows_x, d_gate, d_up, hidden, d_out, sizes)``: ``passes``
    passes of the three weight gradients added to their carries and nothing
    else, pass ``p``'s groups being ``sizes[p]``."""
    import jax
    import jax.numpy as jnp

    from distributed_parameter_server_for_ml_training_tpu.parallel import moe

    dims = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])

    def add(acc, lhs, rhs, group):
        if variant == "parent":     # dW in the rows' dtype, then the add
            return acc + jax.lax.ragged_dot_general(lhs, rhs, group, dims)
        return moe._add_weight_grads(acc, lhs, rhs, group, "in_place")

    def f(de, rows_x, d_gate, d_up, hidden, d_out, sizes):
        def one_pass(p, de):
            return {"gate": add(de["gate"], rows_x, d_gate, sizes[p]),
                    "up": add(de["up"], rows_x, d_up, sizes[p]),
                    "down": add(de["down"], hidden, d_out, sizes[p])}
        return jax.lax.fori_loop(0, passes, one_pass, de)
    return f


def pass_sizes(shape: dict, rows: int, passes: int, seed: int):
    """``[passes, held]`` int32: the groups of each pass for uniformly drawn
    routing (the held experts' share even), slack on the last expert."""
    import numpy as np
    r = np.random.default_rng(seed)
    idx = r.integers(0, shape["e"], size=(shape["n"], shape["k"]))
    loads = np.bincount(idx.reshape(-1), minlength=shape["e"])[:shape["held"]]
    ends = np.cumsum(loads)
    starts = ends - loads
    sizes = np.zeros((passes, shape["held"]), np.int32)
    for p in range(passes):
        lo, hi = p * rows, (p + 1) * rows
        sizes[p] = np.clip(ends, lo, hi) - np.clip(starts, lo, hi)
        sizes[p, -1] += rows - sizes[p].sum()
    return sizes


def chip_report(args) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_parameter_server_for_ml_training_tpu.ops.pallas import (
        grouped_grad)
    from distributed_parameter_server_for_ml_training_tpu.utils.compile_cache \
        import enable_compile_cache

    enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"found no TPU: {device}", file=sys.stderr)
        return 1
    if args.acc_tile:
        grouped_grad.MAX_ACC_TILE = args.acc_tile
    if args.block_rows:
        grouped_grad.BLOCK_ROWS = args.block_rows
    if args.chunk_k:
        grouped_grad.CHUNK_K = args.chunk_k
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "acc_tile": grouped_grad.MAX_ACC_TILE,
              "block_rows": grouped_grad.BLOCK_ROWS,
              "chunk_k": grouped_grad.CHUNK_K, "shapes": {}}
    for name in args.shapes.split(","):
        shape = SHAPES[name]
        keys = iter(jax.random.split(jax.random.PRNGKey(args.seed), 16))

        def make(dims, dtype, kind):
            if kind == "idx":
                return jax.random.randint(next(keys), dims, 0, shape["e"])
            if kind == "gates":
                return jax.nn.softmax(jax.random.normal(next(keys), dims))
            scale = 0.02 if kind == "weight" else 1.0
            return (jax.random.normal(next(keys), dims) * scale).astype(dtype)

        inputs = arguments(shape, make)
        row = result["shapes"][name] = {}
        grads = {}
        for variant in args.variants.split(","):
            f, rows, passes = layer(shape, variant)
            fn = jax.jit(f)
            row[f"layer_ms.{variant}"] = _timed(fn, inputs, args.calls)
            grads[variant] = jax.tree_util.tree_map(
                lambda g: np.asarray(g, np.float32), fn(*inputs))
        row["rows"], row["passes"] = rows, passes
        if len(grads) == 2:
            # relative l2 distance a gradient, the change from the parent
            row["grad_rel_l2"] = {
                jax.tree_util.keystr(path): float(
                    np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
                for (path, a), (_p, b) in zip(
                    jax.tree_util.tree_leaves_with_path(grads["change"]),
                    jax.tree_util.tree_leaves_with_path(grads["parent"]))}
        # the accumulate alone
        d, f_, held = shape["d"], shape["f"], shape["held"]
        sizes = jnp.asarray(pass_sizes(shape, rows, passes, args.seed))

        def bf(dims):
            return jax.random.normal(next(keys), dims).astype(jnp.bfloat16)

        operands = (bf((rows, d)), bf((rows, f_)), bf((rows, f_)),
                    bf((rows, f_)), bf((rows, d)), sizes)
        for variant in args.variants.split(","):
            de = {"gate": jnp.zeros((held, d, f_), jnp.float32),
                  "up": jnp.zeros((held, d, f_), jnp.float32),
                  "down": jnp.zeros((held, f_, d), jnp.float32)}
            fn = jax.jit(accumulate_alone(shape, variant, passes, rows))
            row[f"accumulate_ms_a_pass.{variant}"] = _timed(
                fn, (de, *operands), args.calls) / passes
        row["visited_pairs"] = int((np.asarray(sizes) > 0).sum())
    print("RESULT_JSON:", json.dumps(result))
    return 0


def main(argv: list[str]) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("mode", choices=("compile", "chip"))
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--variants", default="parent,change")
    parser.add_argument("--calls", type=int, default=10)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--acc-tile", type=int, default=0)
    parser.add_argument("--block-rows", type=int, default=0)
    parser.add_argument("--chunk-k", type=int, default=0)
    args = parser.parse_args(argv)
    return compile_report() if args.mode == "compile" else chip_report(args)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
