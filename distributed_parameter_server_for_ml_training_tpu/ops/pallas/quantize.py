"""Block-wise int8 gradient quantization as Pallas TPU kernels.

The reference's gradient compression is an fp16 cast (worker.py:264-268,
~50% bytes). This is the stronger TPU-native analogue: symmetric int8 with a
per-block scale (~75% fewer bytes than fp32), quantized/dequantized on device
so only int8 + scales cross HBM/ICI/host boundaries. Used by

- the ``compression='int8'`` sync all-reduce mode (parallel/sync_dp.py):
  int8 payloads on every hop of a reduce-scatter + all-gather ring
  (EQuARX-style quantized collective; PAPERS.md prior art),
- the async wire path (ops/compression.py int8 tree codec is the host-side
  equivalent for store payloads).

Kernel layout: input is flattened and viewed as [rows, 128] (VPU lanes),
grid over row-blocks of BLOCK_ROWS; each block gets one fp32 scale computed
from its abs-max. On TPU, stochastic rounding uses the on-core PRNG
(pltpu.prng_random_bits); round-to-nearest is the deterministic default.
Both kernels fall back to identical-math jnp implementations off-TPU (and
power the unit tests via interpret-free CPU execution).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128
BLOCK_ROWS = 256  # 256x128 fp32 = 128 KiB per block in VMEM


def block_rows_for(rows_padded: int) -> int:
    """Quantization block height for a [rows_padded, 128] view.

    Large inputs tile in BLOCK_ROWS blocks; inputs at or below one block
    are a SINGLE block of their own (32-row-aligned: the int8 native TPU
    tile is (32, 128)) — padding a 1/N-sized ring chunk up to 32768
    elements would otherwise dominate the wire bytes for small models
    (parallel/sync_dp.py int8 ring). Both quantize and dequantize derive
    the layout from this rule, so the pair stays consistent without
    shipping the block size. Empty inputs (rows_padded == 0) get the
    minimum 32-row block so callers' ``rows // br`` stays well-defined
    (0 blocks) instead of dividing by zero."""
    if rows_padded == 0:
        return 32
    return rows_padded if rows_padded <= BLOCK_ROWS else BLOCK_ROWS


def _pad_to_blocks(x: jax.Array) -> tuple[jax.Array, int, int]:
    """Flatten to [rows, 128]; rows 32-aligned (single block) for small
    inputs, a BLOCK_ROWS multiple otherwise."""
    n = x.size
    rows = -(-n // LANES)
    if rows <= BLOCK_ROWS:
        rows_padded = -(-rows // 32) * 32
    else:
        rows_padded = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    flat = jnp.zeros((rows_padded * LANES,), jnp.float32)
    flat = flat.at[:n].set(x.reshape(-1).astype(jnp.float32))
    return flat.reshape(rows_padded, LANES), n, rows_padded


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# -- kernels ------------------------------------------------------------------

def _quantize_kernel(x_ref, values_ref, scales_ref, *, stochastic: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block = x_ref[:]
    abs_max = jnp.max(jnp.abs(block))
    scale = jnp.where(abs_max > 0, abs_max / 127.0, 1.0)
    # scales live whole in SMEM (scalar-per-block outputs can't be tiled);
    # each grid step writes its own slot.
    scales_ref[pl.program_id(0), 0] = scale
    scaled = block / scale
    if stochastic:
        # floor(x + u), u ~ U[0,1): rounds k+f up with probability f —
        # unbiased. (pltpu.stochastic_round targets only bf16/fp8 dtypes in
        # this JAX, so int8 needs the manual form.)
        # Mosaic can't cast uint32->f32; go via int32 with a mask to keep
        # the value in [0, 2^24).
        random_bits = pltpu.bitcast(
            pltpu.prng_random_bits(scaled.shape), jnp.int32)
        u = ((random_bits >> 8) & 0x00FFFFFF).astype(jnp.float32) \
            * (1.0 / (1 << 24))
        values_ref[:] = jnp.clip(jnp.floor(scaled + u),
                                 -127, 127).astype(jnp.int8)
    else:
        values_ref[:] = jnp.clip(jnp.rint(scaled), -127, 127).astype(jnp.int8)


def _quantize_seed_kernel(seed_ref, x_ref, values_ref, scales_ref):
    from jax.experimental.pallas import tpu as pltpu

    pltpu.prng_seed(seed_ref[0])
    _quantize_kernel(x_ref, values_ref, scales_ref, stochastic=True)


def _dequantize_kernel(values_ref, scales_ref, out_ref):
    import jax.experimental.pallas as pl

    out_ref[:] = (values_ref[:].astype(jnp.float32)
                  * scales_ref[pl.program_id(0), 0])


# -- public ops ---------------------------------------------------------------

@partial(jax.jit, static_argnames=("stochastic", "use_pallas"))
def quantize_int8(x: jax.Array, seed: jax.Array | int = 0, *,
                  stochastic: bool = False,
                  use_pallas: bool | None = None):
    """x (any shape) -> (values int8 [rows,128], scales fp32 [blocks]).

    The caller keeps ``x.shape`` to reconstruct (dequantize_int8 takes it
    statically).
    """
    if x.size == 0:  # empty gradients quantize to empty wire payloads
        return (jnp.zeros((0, LANES), jnp.int8), jnp.zeros((0,), jnp.float32))
    xb, n, rows = _pad_to_blocks(x)
    br = block_rows_for(rows)
    n_blocks = rows // br
    if use_pallas is None:
        use_pallas = _on_tpu()

    if use_pallas:
        import jax.experimental.pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        out_shapes = (
            jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.float32),
        )
        block_in = pl.BlockSpec((br, LANES), lambda i: (i, 0),
                                memory_space=pltpu.VMEM)
        block_vals = pl.BlockSpec((br, LANES), lambda i: (i, 0),
                                  memory_space=pltpu.VMEM)
        # whole scales array in SMEM for every step (untiled scalar slots)
        block_scale = pl.BlockSpec((n_blocks, 1), lambda i: (0, 0),
                                   memory_space=pltpu.SMEM)
        if stochastic:
            values, scales = pl.pallas_call(
                _quantize_seed_kernel,
                grid=(n_blocks,),
                in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), block_in],
                out_specs=(block_vals, block_scale),
                out_shape=out_shapes,
            )(jnp.atleast_1d(jnp.asarray(seed, jnp.int32)), xb)
        else:
            values, scales = pl.pallas_call(
                partial(_quantize_kernel, stochastic=False),
                grid=(n_blocks,),
                in_specs=[block_in],
                out_specs=(block_vals, block_scale),
                out_shape=out_shapes,
            )(xb)
        return values, scales.reshape(n_blocks)

    # jnp fallback: identical deterministic math (stochastic ignored).
    blocks = xb.reshape(n_blocks, br * LANES)
    abs_max = jnp.max(jnp.abs(blocks), axis=1)
    scales = jnp.where(abs_max > 0, abs_max / 127.0, 1.0)
    q = jnp.clip(jnp.rint(blocks / scales[:, None]), -127, 127)
    return q.astype(jnp.int8).reshape(rows, LANES), scales


@partial(jax.jit, static_argnames=("shape", "use_pallas"))
def dequantize_int8(values: jax.Array, scales: jax.Array,
                    shape: tuple, *, use_pallas: bool | None = None):
    """Inverse of :func:`quantize_int8`; ``shape`` is the original
    (static) array shape."""
    n = int(np.prod(shape, dtype=np.int64)) if shape else 1
    if n == 0:
        return jnp.zeros(shape, jnp.float32)
    rows = values.shape[0]
    br = block_rows_for(rows)
    n_blocks = rows // br
    if use_pallas is None:
        use_pallas = _on_tpu()

    if use_pallas:
        import jax.experimental.pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        out = pl.pallas_call(
            _dequantize_kernel,
            grid=(n_blocks,),
            in_specs=[
                pl.BlockSpec((br, LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((n_blocks, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((br, LANES), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        )(values, scales.reshape(n_blocks, 1))
    else:
        blocks = values.reshape(n_blocks, br * LANES)
        out = (blocks.astype(jnp.float32)
               * scales.reshape(n_blocks, 1)).reshape(rows, LANES)

    flat = out.reshape(-1)[:n]
    return flat.reshape(shape)


def quantize_dequantize_int8(x: jax.Array, *, stochastic: bool = False,
                             seed: int = 0,
                             use_pallas: bool | None = None) -> jax.Array:
    """Round-trip (the quantization error a gradient would incur)."""
    v, s = quantize_int8(x, seed, stochastic=stochastic,
                         use_pallas=use_pallas)
    return dequantize_int8(v, s, tuple(x.shape), use_pallas=use_pallas)


# -- fused wire-codec kernels (device-resident push codec) --------------------
#
# The wire codec family (ops/compression.py int8/int4/topk) is the NumPy
# host reference: every quantized push starts with a full fp32 device_get
# BEFORE the bytes shrink. These kernels run the SAME math on device, bit
# identical to the reference (true division — never a reciprocal multiply,
# which double-rounds; jnp.rint == np.rint round-half-even; identical clip
# bounds), so only the already-quantized wire buffers cross the link. Tree
# orchestration (host-computed scales, error feedback, the single packed
# bytes pull) lives in ops/device_codec.py; these are the per-tensor
# primitives it traces into its phase programs. Only the quantize runs as
# a Pallas kernel — the nibble pack and top-k select stay jnp inside the
# same jit program (XLA fuses them; Mosaic has no win for lane-pair bit
# twiddling), which also serves as the CPU tier-1 fallback.

# Below ~64k elements the pallas_call launch costs more than the fused XLA
# elementwise it replaces; small tensors stay on the jnp path even on TPU.
PALLAS_WIRE_MIN_SIZE = 65536


def _wire_quantize_kernel(scale_ref, x_ref, values_ref, *, levels: int):
    # One fp32 block / one shared SMEM scale -> int8 codes in [-levels,
    # levels]. The divide must stay a true divide for bit-identity with
    # the NumPy reference codec.
    values_ref[:] = jnp.clip(jnp.rint(x_ref[:] / scale_ref[0]),
                             -levels, levels).astype(jnp.int8)


def wire_quantize_flat(x2d: jax.Array, scale: jax.Array, levels: int,
                       use_pallas: bool) -> jax.Array:  # dpslint: hot-path device
    """[rows,128] fp32 + scalar scale -> [rows,128] int8 codes.

    Traced inside the device codec's phase programs (and the jitted
    :func:`wire_quantize` wrapper) — not jitted itself. ``levels`` is 127
    for int8 wire codes, 7 for int4 nibble codes.
    """
    rows = x2d.shape[0]
    scale = jnp.asarray(scale, jnp.float32)
    if use_pallas and rows:
        import jax.experimental.pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        br = block_rows_for(rows)
        return pl.pallas_call(
            partial(_wire_quantize_kernel, levels=levels),
            grid=(rows // br,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec((br, LANES), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((br, LANES), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
        )(scale.reshape(1), x2d)
    return jnp.clip(jnp.rint(x2d / scale), -levels, levels).astype(jnp.int8)


def pack_nibbles_device(q: jax.Array) -> jax.Array:  # dpslint: hot-path device
    """int8 codes in [-8, 7] (any shape) -> packed uint8, flat ceil(n/2).

    Bit-identical to ops/packed.py:pack_nibbles: low nibble = even flat
    index, odd length padded with a zero code. Traced (not jitted) so the
    device codec fuses it into the quantize program.
    """
    flat = q.reshape(-1)
    if flat.size % 2:
        flat = jnp.concatenate([flat, jnp.zeros((1,), jnp.int8)])
    pairs = flat.reshape(-1, 2)
    lo = pairs[:, 0].astype(jnp.uint8) & 0x0F
    hi = (pairs[:, 1].astype(jnp.uint8) & 0x0F) << 4
    return lo | hi


def topk_select_flat(x: jax.Array, k: int):  # dpslint: hot-path device
    """Flat top-k by |value|: (sorted int32 indices, fp32 values).

    jax.lax.top_k + ascending index sort — identical to the NumPy
    reference's argpartition+sort selection whenever the k-th magnitude
    is unique (equal-magnitude ties at the boundary tie-break by index
    here, unspecified there; continuous gradients don't tie). Traced,
    not jitted.
    """
    flat = x.reshape(-1).astype(jnp.float32)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    idx = jnp.sort(idx).astype(jnp.int32)
    return idx, jnp.take(flat, idx)


@partial(jax.jit, static_argnames=("levels", "use_pallas"))
def wire_quantize(x: jax.Array, scale, *, levels: int = 127,
                  use_pallas: bool | None = None) -> jax.Array:
    """Tensor + scalar scale -> int8 wire codes with the tensor's shape.

    Jitted per-tensor convenience surface over :func:`wire_quantize_flat`
    (tests, microbench). The device codec uses the flat form directly so
    a whole gradient tree compiles as one program.
    """
    if use_pallas is None:
        use_pallas = _on_tpu() and x.size >= PALLAS_WIRE_MIN_SIZE
    if x.size == 0:
        return jnp.zeros(x.shape, jnp.int8)
    xb, n, _ = _pad_to_blocks(x)
    q = wire_quantize_flat(xb, scale, levels, use_pallas)
    return q.reshape(-1)[:n].reshape(x.shape)
