"""Per-device collective wire-byte accounting from compiled HLO.

The reference measured its communication cost at the wire (pickled payload
sizes through gRPC, server.py logs); the SPMD analogue is the set of
collective ops XLA actually emitted. This module parses a compiled
executable's HLO text and applies the standard per-device traffic model of
each collective, giving a comparable "bytes over ICI per step per device"
number for the compression modes (parallel/sync_dp.py) without needing a
hardware profiler. Used by tests/test_quantize.py (asserts the int8 ring
moves fewer bytes than bf16 pmean) and experiments/measure_comm_bytes.py
(records the bytes-vs-N model in PERF.md).

Traffic model (ring algorithms, the TPU/ICI default):
- collective-permute: result bytes (one neighbor send per device)
- all-reduce:        2 x (N-1)/N x result bytes (reduce-scatter + all-gather)
- all-gather:        (N-1)/N x result bytes (each device receives all
                     other shards)
- reduce-scatter:    (N-1) x result bytes ((N-1)/N of the N-x-larger input)
"""

from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_SHAPE_RE = re.compile(r"(pred|[suf]\d+|bf16)\[([\d,]*)\]")
# Lazy match after '=' up to the op keyword: tuple result shapes may
# contain '/*index=5*/' comments, so the shape text itself can hold '='.
_OP_RE = re.compile(
    r"=\s+(.*?)\s*"
    r"(collective-permute|all-reduce|all-gather|reduce-scatter)"
    r"(-start)?\(")


def _shape_bytes(shape_text: str, pick: str = "sum") -> int:
    """Bytes of all typed shapes in ``shape_text`` (or just the largest).

    ``pick`` handles async ``-start`` forms of collectives, whose result
    tuple aliases the operand alongside the result buffer — summing both
    would double-count the wire bytes. All four collective kinds can lower
    to ``-start``/``-done`` pairs on TPU: for collective-permute /
    all-gather / all-reduce the RESULT is the largest member
    (``pick='largest'``); for reduce-scatter the result is 1/N of the
    operand, so the result is the SMALLEST member (``pick='smallest'``) —
    the (N-1) ring factor in :func:`collective_wire_bytes` is calibrated
    for result bytes. Scalar tuple members (``u32[]`` context handles some
    start forms carry) are excluded from the pick so 'smallest' lands on
    the result, not a 4-byte handle. Scope: single-tensor collectives (the
    forms this codebase emits); a variadic start would undercount.
    """
    sizes, scalars = [], []
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        (scalars if dims == "" else sizes).append(n * _DTYPE_BYTES[dtype])
    if pick == "sum":
        return sum(sizes) + sum(scalars)
    if not sizes:
        return 0
    return max(sizes) if pick == "largest" else min(sizes)


def collective_wire_bytes(hlo_text: str, n_devices: int) -> dict:
    """Sum per-device wire bytes of every collective in ``hlo_text``.

    Returns ``{"total": int, "by_op": {op: bytes}, "count": {op: int}}``.
    ``-done`` halves of async pairs are skipped (the ``-start`` carries
    the shape); small scalar reductions count like any other.
    """
    by_op: dict[str, int] = defaultdict(int)
    count: dict[str, int] = defaultdict(int)
    frac = (n_devices - 1) / n_devices
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        shape_text, op, is_start = m.group(1), m.group(2), bool(m.group(3))
        if is_start:
            pick = "smallest" if op == "reduce-scatter" else "largest"
        else:
            pick = "sum"
        b = _shape_bytes(shape_text, pick=pick)
        if op == "collective-permute":
            moved = b
        elif op == "all-reduce":
            moved = 2 * frac * b
        elif op == "all-gather":
            moved = frac * b
        else:  # reduce-scatter: result is 1/N of the reduced input
            moved = (n_devices - 1) * b
        by_op[op] += int(moved)
        count[op] += 1
    return {"total": sum(by_op.values()), "by_op": dict(by_op),
            "count": dict(count)}


def sync_grad_mean_bytes(n_devices: int, size: int,
                         modes=("none", "bf16", "int8")) -> dict:
    """Per-device wire bytes of the sync-DP gradient mean per compression
    mode, measured from compiled HLO on an ``n_devices`` mesh.

    The single measurement harness behind tests/test_quantize.py and
    experiments/measure_comm_bytes.py. CPU XLA widens bf16 collectives to
    f32; when detected, the bf16 number is bounded by half the f32
    measurement (same op, half-width dtype on TPU) and
    ``bf16_widened_on_cpu`` is set.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..ops.compression import (compress_for_allreduce,
                                   decompress_from_allreduce)
    from ..parallel import make_mesh
    from ..parallel.sync_dp import _int8_ring_allreduce_mean

    mesh = make_mesh(n_devices)
    g = jnp.ones((size,), jnp.float32)
    key = jax.random.PRNGKey(0)

    def mean_none(g, key):
        return jax.lax.pmean(g, "data")

    def mean_bf16(g, key):
        c = compress_for_allreduce(g, "bf16")
        return decompress_from_allreduce(jax.lax.pmean(c, "data"), "bf16")

    def mean_int8(g, key):
        return _int8_ring_allreduce_mean(g, "data", n_devices, key)

    fns = {"none": mean_none, "bf16": mean_bf16, "int8": mean_int8}
    out: dict = {}
    for name in modes:
        sm = jax.shard_map(fns[name], mesh=mesh, in_specs=(P(), P()),
                           out_specs=P(), check_vma=False)
        hlo = jax.jit(sm).lower(g, key).compile().as_text()
        out[name] = collective_wire_bytes(hlo, n_devices)
    if ("bf16" in out and "none" in out
            and out["bf16"]["total"] > 0.9 * out["none"]["total"]):
        total = out["none"]["total"] // 2
        out["bf16"] = {"total": total, "by_op": {"all-reduce": total},
                       "count": out["bf16"]["count"],
                       "widened_on_cpu": True}
    return out
