"""Grouped weight gradients added into their accumulator, as a Pallas TPU
kernel: ``acc[g] += lhs[rows of g]^T @ rhs[rows of g]``.

``lhs`` ``[M, K]`` and ``rhs`` ``[M, N]`` are the two sides of a grouped
matmul's weight gradient (the rows that went in, the cotangents that came
back), their rows sorted by group: ``group`` ``[C]`` int32 says how many
consecutive rows each of the ``C`` groups has, summing to ``M``. ``acc``
``[C, K, N]`` float32 is the running sum the caller carries (the held
experts' gradients over the passes of parallel/moe.py:_work_off_bwd); it is
aliased to the result, so the kernel reads and writes only the ``[K, N]``
slices of groups that have a row. A group without one is not visited and
its slice is not touched: that is the point. XLA's own route
(``ragged_dot_general`` then ``acc + dw``) writes a ``[C, K, N]`` array of
the rows' dtype and then reads and rewrites the whole of ``acc``, whichever
few groups the rows belong to.

Design (the idea of jax.experimental.pallas.ops.tpu.megablox's ``tgmm``
with ``existing_out``; not imported: it visits empty groups to zero them,
and its tiles are not ours):

- grid ``(N tiles, K tiles, visits)``, visits innermost. A *visit* is one
  (group, row tile) pair that has a row: the row tiles of ``block_rows`` a
  group's rows lie in, group by group (``visit_plan``, computed by XLA
  from ``group`` and handed over as prefetched scalars). A row tile that
  holds a group boundary is visited once a group, its rows of other groups
  masked to zero. There are at most ``M / block_rows + C - 1`` visits, the
  grid's static size; the ones past the plan's end repeat the last and add
  nothing.
- the block of ``acc`` (and of the aliased result) a visit maps to is its
  group's ``[block_k, block_n]`` tile. Consecutive visits of one group map
  to one block, which Pallas fetches when the group's first visit starts
  and writes back after its last; in between the products are summed into
  it in VMEM, in float32.
- ``lhs`` enters the MXU transposed (the contraction is over rows on both
  sides), as in the flash kernels' dK/dV, 256 of its columns at a time: the
  compiler unrolls a matmul, and a visit's whole product as one was 8,300
  bundles of code a call site (twelve and fifteen in the LM cells' steps,
  4.5 MB of device memory); as a loop over column chunks it is 3,300.

Off TPU the caller takes XLA's route with the same float32 sum
(parallel/moe.py:_add_weight_grads); tests run this kernel in interpret
mode at small shapes (``INTERPRET``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

#: rows a visit multiplies: a tile of lhs and rhs (256 and 1,024 read the
#: same to 7% on the chip)
BLOCK_ROWS = 512
#: the widest ``[block_k, block_n]`` float32 tile of ``acc``, in elements
#: (3.75 MiB; it is held four times: fetched and written, double-buffered).
#: On the chip a tile of 512 x 768 read 1.454 and 0.890 ms a pass at the two
#: LM cells' shapes, this one 1.317 and 0.808 (PERF.md section 6, PR 35)
MAX_ACC_TILE = 1280 * 768
#: scoped VMEM the kernel may use; a v5e core has 128 MiB
VMEM_LIMIT_BYTES = 64 << 20

#: columns of lhs a matmul inside a visit takes (``_kernel``)
CHUNK_K = 256

# Run the kernel in interpreter mode (tests, off the chip); never on a TPU.
INTERPRET = False


def pick_blocks(m: int, k: int, n: int):
    """``(block_rows, block_k, block_n)`` from the shapes alone, or None
    where the kernel does not take them (a width that is no multiple of 128
    lanes, rows that are no multiple of bf16's 16 sublanes). lhs is read
    once an N tile and rhs once a K tile, ``2 M K N (1 / block_n + 1 /
    block_k)`` bytes in bf16: the pair of 128-multiples that divide ``K``
    and ``N``, fit ``MAX_ACC_TILE`` and make that sum least (the wider
    ``block_n`` of two that tie: lhs is also transposed once a read)."""
    if k % 128 or n % 128 or m % 16:
        return None

    def divisors(size: int):
        return [b for b in range(128, size + 1, 128) if size % b == 0]

    block_k, block_n = min(
        ((bk, bn) for bk in divisors(k) for bn in divisors(n)
         if bk * bn <= MAX_ACC_TILE),
        key=lambda t: (1 / t[0] + 1 / t[1], -t[1]))
    block_rows = max(b for b in range(16, min(m, BLOCK_ROWS) + 1, 16)
                     if m % b == 0)
    return block_rows, block_k, block_n


def visit_plan(group: jax.Array, m: int, block_rows: int):
    """The (group, row tile) pairs that have a row, in the rows' order.

    ``(offsets [C + 1], group_of [V], tile_of [V], visits [1])``, all
    int32, ``V = m / block_rows + C - 1``: group ``g``'s rows are ``offsets[g]
    .. offsets[g + 1]``; visit ``v < visits`` is row tile ``tile_of[v]`` for
    group ``group_of[v]``; entries from ``visits`` on repeat the last."""
    c = group.shape[0]
    ends = jnp.cumsum(group)
    starts = ends - group
    tiles = jnp.where(group > 0,
                      (ends - 1) // block_rows - starts // block_rows + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    visits = visit_ends[-1]
    v = jnp.minimum(jnp.arange(m // block_rows + c - 1), visits - 1)
    group_of = jnp.searchsorted(visit_ends, v, side="right").astype(
        jnp.int32)
    tile_of = (starts // block_rows)[group_of] + v - (visit_ends - tiles)[
        group_of]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets.astype(jnp.int32), group_of, tile_of.astype(jnp.int32),
            visits.reshape(1).astype(jnp.int32))


def _kernel(offsets_ref, group_ref, tile_ref, visits_ref, lhs_ref, rhs_ref,
            acc_ref, out_ref, masked_ref, *, block_rows: int, chunk_k: int):
    import jax.experimental.pallas as pl

    v = pl.program_id(2)
    g = group_ref[v]

    # a group's first visit brings its tile of the running sum
    @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != g))
    def _():
        out_ref[...] = acc_ref[...]

    @pl.when(v < visits_ref[0])
    def _():
        # rows of other groups in a tile that holds a boundary: zero on one
        # side is zero in the product
        row = tile_ref[v] * block_rows + jax.lax.broadcasted_iota(
            jnp.int32, (block_rows, 1), 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        masked_ref[...] = jnp.where(mine, rhs_ref[...], 0)

        # ``chunk_k`` columns of lhs at a time: the compiler unrolls a
        # matmul, and one of the whole tile is 8,300 bundles of code a call
        def chunk(c, carry):
            at = pl.multiple_of(c * chunk_k, chunk_k)
            out_ref[pl.ds(at, chunk_k), :] += jax.lax.dot_general(
                lhs_ref[:, pl.ds(at, chunk_k)], masked_ref[...],
                (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, lhs_ref.shape[1] // chunk_k, chunk, 0)


def grouped_grad_accumulate(acc: jax.Array, lhs: jax.Array, rhs: jax.Array,
                            group: jax.Array) -> jax.Array:
    """``acc`` with ``lhs[rows of g]^T @ rhs[rows of g]`` added to ``acc[g]``
    for every group ``g`` that has a row; ``acc`` is aliased to the result."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (m, k), n = lhs.shape, rhs.shape[1]
    if acc.shape != (group.shape[0], k, n) or rhs.shape[0] != m:
        raise ValueError(f"acc {acc.shape} for lhs {lhs.shape}, rhs "
                         f"{rhs.shape} and {group.shape[0]} groups")
    blocks = pick_blocks(m, k, n)
    if blocks is None or acc.dtype != jnp.float32:
        raise ValueError(f"no tiles for lhs {lhs.shape}, rhs {rhs.shape}, "
                         f"acc {acc.dtype}: see pick_blocks")
    block_rows, block_k, block_n = blocks
    plan = visit_plan(group, m, block_rows)

    # index maps: the grid's (N tile j, K tile i, visit v), then the plan
    def lhs_tile(j, i, v, offsets, group_of, tile_of, visits):
        return tile_of[v], i

    def rhs_tile(j, i, v, offsets, group_of, tile_of, visits):
        return tile_of[v], j

    def acc_tile(j, i, v, offsets, group_of, tile_of, visits):
        return group_of[v], i, j

    acc_spec = pl.BlockSpec((None, block_k, block_n), acc_tile)
    return pl.pallas_call(
        partial(_kernel, block_rows=block_rows,
                chunk_k=math.gcd(block_k, CHUNK_K)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(plan),
            grid=(n // block_n, k // block_k, plan[1].shape[0]),
            in_specs=[
                pl.BlockSpec((block_rows, block_k), lhs_tile),
                pl.BlockSpec((block_rows, block_n), rhs_tile),
                acc_spec,
            ],
            out_specs=acc_spec,
            scratch_shapes=[pltpu.VMEM((block_rows, block_n), rhs.dtype)]),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        input_output_aliases={len(plan) + 2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * (n // block_n) + m * n * (k // block_k))
            * lhs.dtype.itemsize),
        interpret=INTERPRET, name="grouped_grad_accumulate",
    )(*plan, lhs, rhs, acc)
