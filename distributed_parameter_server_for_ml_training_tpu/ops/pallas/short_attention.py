"""Fused softmax attention for SHORT sequences as two Pallas TPU kernels.

The regime is the one ``flash_attention.py`` was not built for: a sequence
so short (ViT-B/16 at 224 px: 197 tokens) that one head's whole score
matrix fits in VMEM (197 x 197 x 4 B = 155 KB). No online softmax and no
key blocks are needed; what costs time at this length is everything
*around* the scores: XLA's dense formulation writes them to HBM (seven
bf16 and five f32 passes over ``[B, H, T, T]`` a block, forward and
backward) and copies q, k, v and o between layouts. Here the scores live
and die in VMEM and the operands are the arrays the neighbouring matmuls
already produce and consume:

- in: the fused activation ``qkv`` ``[B, T, 3*H*D]`` exactly as the ``qkv``
  Dense writes it (columns in (3, H, D) order);
- out: ``[B, T, H*D]`` exactly as the ``out`` Dense reads it;
- backward: ``d qkv`` ``[B, T, 3*H*D]`` in one array.

No slice, transpose, pad or concatenate of q, k, v, o or their gradients
exists outside the kernels.

Blocks are whole rows of images: a grid step takes ``(Bblk, Tk, 3*H*D)`` of
``qkv``, where ``Tk`` is T rounded up to the 128 lanes the scores' key axis
needs. The block overhangs the array's T rows (nothing is padded in HBM);
the rows beyond T hold whatever was in VMEM, so the kernel masks them:
key columns beyond T go to -inf before the softmax, and every operand
whose padded rows meet a contraction is zeroed there. Inside, a loop over
the ``H*D/128`` groups of ``128/D`` heads reads q, k and v as 128-lane
column slices at ``g*128``, ``H*D + g*128`` and ``2*H*D + g*128``, so no
load is narrower than a vector register. A head inside a group is picked by
zeroing the other heads' lanes of q (or dO): the contraction over all 128
lanes then sums that head's D alone, and the MXU's 128-deep pass costs the
same as a 64-deep one would.

Precision is ``dense_core``'s or better: every MXU operand bf16 (the input
dtype), every accumulation and every softmax statistic f32. The logits stay
f32 (``dense_core`` rounds them to bf16 before its softmax). Saved for the
backward: the row log-sum-exp, ``f32[B, T, H]``, and nothing score-shaped.
The backward is one kernel with one recomputation of s and p.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128
_NEG_INF = -1e30

#: Longest sequence the kernels take. A head's f32 scores are 4 Tk^2 bytes;
#: the backward keeps three score-shaped arrays live for a head (p, dP, dS)
#: while the group's other head is scheduled: 2 heads x 3 copies = 24 Tk^2
#: bytes, 6.3 MB at Tk = 512 and 25 MB at 1024, against a default scoped
#: VMEM limit of 16 MB. Compiled for a described v5e (PR 27): at 512 both
#: kernels fit the ``vmem_limit_bytes`` that ``_vmem_bytes`` asks for
#: (20.6 MB with one image's double-buffered blocks at H*D = 768); at 1024
#: the forward alone wants 57 MB. A sequence that long wants key blocks
#: and an online softmax: ``flash_attention.py``.
MAX_T = 512

# Run the kernels in interpreter mode (CPU emulation of the kernel code).
# Tests flip this; never set on a TPU.
INTERPRET = False


def supports(t: int, num_heads: int, head_dim: int) -> bool:
    """The static shapes the kernels are written for."""
    return (head_dim <= LANES and LANES % head_dim == 0
            and (num_heads * head_dim) % LANES == 0 and 0 < t <= MAX_T)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _dot(a, b, contract):
    """f32-accumulated MXU matmul of bf16 operands; ``contract`` names the
    contracted dimension of each operand."""
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _head_lanes(j: int, head_dim: int):
    """[1, 128] mask of head ``j``'s lanes inside its group."""
    lane = _iota((1, LANES), 1)
    return (lane >= j * head_dim) & (lane < (j + 1) * head_dim)


def _zero_rows(x, t: int):
    """``x`` with its rows from ``t`` on zeroed (they overhang the array
    and hold whatever VMEM held)."""
    if x.shape[0] == t:
        return x
    return jnp.where(_iota((x.shape[0], 1), 0) < t, x, jnp.zeros_like(x))


def _fwd_kernel(qkv_ref, o_ref, lse_ref, *, t: int, num_heads: int,
                head_dim: int, scale: float):
    hd = num_heads * head_dim
    per_group = LANES // head_dim
    tq, tk = o_ref.shape[1], qkv_ref.shape[1]
    keys = _iota((1, tk), 1) < t

    def image(b, carry):
        for g in range(hd // LANES):
            c = g * LANES
            # q's padded rows only make padded rows of o, which the
            # write-back drops; k's make padded columns, masked below; v's
            # meet the contraction of p.v and must be zero.
            q = qkv_ref[b, 0:tq, c:c + LANES]
            k = qkv_ref[b, :, hd + c:hd + c + LANES]
            v = _zero_rows(qkv_ref[b, :, 2 * hd + c:2 * hd + c + LANES], t)
            o = None
            for j in range(per_group):
                head = _head_lanes(j, head_dim)
                qj = q if per_group == 1 else jnp.where(
                    head, q, jnp.zeros_like(q))
                s = _dot(qj, k, (1, 1)) * scale                # [tq, tk] f32
                if tk != t:
                    s = jnp.where(keys, s, _NEG_INF)
                m = jnp.max(s, axis=-1, keepdims=True)
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=-1, keepdims=True)
                # lanes of the group's other heads hold p.v_other: dropped
                oj = _dot(p.astype(v.dtype), v, (1, 0)) * (1.0 / l)
                o = oj if o is None else jnp.where(head, oj, o)
                h = g * per_group + j
                lse_ref[b, :, h:h + 1] = m + jnp.log(l)
            o_ref[b, :, c:c + LANES] = o.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, qkv_ref.shape[0], image, 0)


def _bwd_kernel(qkv_ref, lse_ref, do_ref, dqkv_ref, *, t: int,
                num_heads: int, head_dim: int, scale: float):
    hd = num_heads * head_dim
    per_group = LANES // head_dim
    tk = qkv_ref.shape[1]
    keys = _iota((1, tk), 1) < t
    rows = _iota((tk, 1), 0) < t

    def image(b, carry):
        for g in range(hd // LANES):
            c = g * LANES
            # Every operand's padded rows meet a contraction here (q's and
            # dO's in dK and dV, k's in dQ), so all four are zeroed.
            q = _zero_rows(qkv_ref[b, :, c:c + LANES], t)
            k = _zero_rows(qkv_ref[b, :, hd + c:hd + c + LANES], t)
            v = _zero_rows(qkv_ref[b, :, 2 * hd + c:2 * hd + c + LANES], t)
            do = _zero_rows(do_ref[b, :, c:c + LANES], t)
            dq = dk = dv = None
            for j in range(per_group):
                head = _head_lanes(j, head_dim)
                if per_group == 1:
                    qj, doj = q, do
                else:
                    qj = jnp.where(head, q, jnp.zeros_like(q))
                    doj = jnp.where(head, do, jnp.zeros_like(do))
                h = g * per_group + j
                lse = lse_ref[b, :, h:h + 1]
                if tk != t:
                    # a padded row's p is exp(0 - 1e30) = 0, whatever the
                    # overhang held
                    lse = jnp.where(rows, lse, -_NEG_INF)
                s = _dot(qj, k, (1, 1)) * scale                # [tk, tk] f32
                p = jnp.exp(s - lse)
                if tk != t:
                    p = jnp.where(keys, p, 0.0)
                dp = _dot(doj, v, (1, 1))
                delta = jnp.sum(dp * p, axis=-1, keepdims=True)
                ds = (p * (dp - delta)).astype(q.dtype)
                # the other heads' lanes of each product are dropped below
                dvj = _dot(p.astype(do.dtype), do, (0, 0))
                dqj = _dot(ds, k, (1, 0)) * scale
                dkj = _dot(ds, q, (0, 0)) * scale
                if dq is None:
                    dq, dk, dv = dqj, dkj, dvj
                else:
                    dq = jnp.where(head, dqj, dq)
                    dk = jnp.where(head, dkj, dk)
                    dv = jnp.where(head, dvj, dv)
            dqkv_ref[b, :, c:c + LANES] = dq.astype(dqkv_ref.dtype)
            dqkv_ref[b, :, hd + c:hd + c + LANES] = dk.astype(dqkv_ref.dtype)
            dqkv_ref[b, :, 2 * hd + c:2 * hd + c + LANES] = dv.astype(
                dqkv_ref.dtype)
        return carry

    jax.lax.fori_loop(0, qkv_ref.shape[0], image, 0)


#: Images a grid step takes. One: at 197 tokens a step then carries 12 heads
#: of work (6 us forward, 15 backward) against 0.35 us of grid overhead,
#: 3,072 steps a ViT-B/16 train step, and its double-buffered blocks stay
#: small; 2 and 4 measured no faster (PERF.md section 6, PR 27).
IMAGES_PER_STEP = 1


def _call(kernel, name: str, b: int, in_blocks, out_blocks, out_shapes,
          vmem_bytes: int, cost):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def spec(shape):
        return pl.BlockSpec(shape, lambda i: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        kernel,
        grid=(b // in_blocks[0][0],),
        in_specs=[spec(s) for s in in_blocks],
        out_specs=tuple(spec(s) for s in out_blocks),
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_bytes),
        cost_estimate=cost,
        name=name,
        interpret=INTERPRET,
    )


def _vmem_bytes(blocks, tk: int) -> int:
    """What a call asks for: its blocks, double-buffered (bf16 but for the
    small lse), and the scores' live copies (MAX_T's comment), with room
    for the compiler's own temporaries."""
    block_bytes = sum(2 * 2 * int(np.prod(s)) for s in blocks)
    return int(block_bytes + 24 * tk * tk + (8 << 20))


def _geometry(qkv, num_heads: int):
    b, t, width = qkv.shape
    hd = width // 3
    head_dim = hd // num_heads
    if width != 3 * num_heads * head_dim or not supports(
            t, num_heads, head_dim):
        raise ValueError(
            f"short_attention takes qkv [B, T, 3*H*D] with T <= {MAX_T}, "
            f"128 % D == 0 and H*D % 128 == 0; got {qkv.shape} with "
            f"H={num_heads}")
    return b, t, hd, head_dim


# jit: the twelve blocks of a model (and its train and evaluation programs)
# then share one trace of each kernel and, inside a program, one lowering
# to Mosaic; unjitted, every call site traced and lowered its own copy,
# which a warm-cache start paid for with 20 s of host time.
@partial(jax.jit, static_argnames=("num_heads",))
def _forward(qkv, num_heads: int):
    import jax.experimental.pallas as pl

    b, t, hd, head_dim = _geometry(qkv, num_heads)
    bblk = IMAGES_PER_STEP
    tq, tk = _round_up(t, 16), _round_up(t, LANES)
    blocks_in = [(bblk, tk, 3 * hd)]
    blocks_out = [(bblk, tq, hd), (bblk, tq, num_heads)]
    kernel = partial(_fwd_kernel, t=t, num_heads=num_heads,
                     head_dim=head_dim, scale=1.0 / np.sqrt(head_dim))
    cost = pl.CostEstimate(
        flops=4 * b * num_heads * t * t * head_dim,
        transcendentals=b * num_heads * t * t,
        bytes_accessed=2 * b * t * 4 * hd + 4 * b * t * num_heads)
    return _call(
        kernel, "short_attention_fwd", b, blocks_in, blocks_out,
        (jax.ShapeDtypeStruct((b, t, hd), qkv.dtype),
         jax.ShapeDtypeStruct((b, t, num_heads), jnp.float32)),
        _vmem_bytes(blocks_in + blocks_out, tk), cost)(qkv)


@partial(jax.jit, static_argnames=("num_heads",))
def _backward(qkv, lse, do, num_heads: int):
    import jax.experimental.pallas as pl

    b, t, hd, head_dim = _geometry(qkv, num_heads)
    bblk = IMAGES_PER_STEP
    # the transposed products (dV = p^T.dO, dK = dS^T.q) want the query
    # axis lane-aligned too, so both axes are padded to Tk here
    tk = _round_up(t, LANES)
    blocks_in = [(bblk, tk, 3 * hd), (bblk, tk, num_heads), (bblk, tk, hd)]
    blocks_out = [(bblk, tk, 3 * hd)]
    kernel = partial(_bwd_kernel, t=t, num_heads=num_heads,
                     head_dim=head_dim, scale=1.0 / np.sqrt(head_dim))
    cost = pl.CostEstimate(
        flops=10 * b * num_heads * t * t * head_dim,
        transcendentals=b * num_heads * t * t,
        bytes_accessed=2 * b * t * 7 * hd + 4 * b * t * num_heads)
    return _call(
        kernel, "short_attention_bwd", b, blocks_in, blocks_out,
        (jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),),
        _vmem_bytes(blocks_in + blocks_out, tk), cost)(qkv, lse, do)[0]


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def short_attention(qkv: jax.Array, num_heads: int) -> jax.Array:
    """``[B, T, 3*H*D]`` (columns in (3, H, D) order) -> ``[B, T, H*D]``
    non-causal softmax attention, scale ``1/sqrt(D)``. Differentiable: the
    backward returns ``d qkv`` in one array. Shapes as :func:`supports`
    says; the caller (``ops.attention.attention_core``) decides when."""
    return _forward(qkv, num_heads=num_heads)[0]


def _vjp_fwd(qkv, num_heads):
    o, lse = _forward(qkv, num_heads=num_heads)
    return o, (qkv, lse)


def _vjp_bwd(num_heads, res, do):
    qkv, lse = res
    return (_backward(qkv, lse, do.astype(qkv.dtype),
                      num_heads=num_heads),)


short_attention.defvjp(_vjp_fwd, _vjp_bwd)
