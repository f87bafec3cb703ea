"""Ring attention: sequence/context parallelism over a mesh axis.

Net-new capability (the reference has no sequence parallelism of any kind —
SURVEY.md §5.7). Long sequences are sharded along a ``seq`` mesh axis; each
device holds a [B, T/N, H, D] slice of q/k/v. K/V blocks rotate around the
ring via ``lax.ppermute`` (one ICI hop per step, overlapping compute with the
neighbor transfer) while each device accumulates attention for its resident
queries with the online-softmax (flash-attention) merge, so the full [T, T]
score matrix never materializes anywhere.

Equivalent math to dense softmax attention (tests assert allclose); memory
per device is O(T/N) instead of O(T^2).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


_NEG_INF = -1e30


def _merge(m, l, o, logits, v_blk):
    """Online-softmax merge of one K/V block into the running (m, l, o)."""
    m_blk = jnp.max(logits, axis=-1)                      # [B,H,Tq]
    m_new = jnp.maximum(m, m_blk)
    p = jnp.exp(logits - m_new[..., None])                # [B,H,Tq,Tk]
    alpha = jnp.exp(m - m_new)                            # [B,H,Tq]
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_blk)
    return m_new, l_new, o_new


def ring_attention_local(q, k, v, *, axis_name: str, axis_size: int,
                         causal: bool = False):
    """Per-shard body; call inside ``shard_map`` over ``axis_name``.

    q/k/v: [B, T_local, H, D] (this shard's slice). Returns [B, T_local, H, D].
    """
    b, t_local, h, d = q.shape
    scale = 1.0 / np.sqrt(d)
    my = jax.lax.axis_index(axis_name)

    qf = q.astype(jnp.float32)
    m = jnp.full((b, h, t_local), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, t_local), jnp.float32)
    o = jnp.zeros((b, h, t_local, d), jnp.float32)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    kk, vv = k.astype(jnp.float32), v.astype(jnp.float32)

    for step in range(axis_size):
        # After `step` rotations we hold the block that started on shard
        # (my - step) mod N.
        src = (my - step) % axis_size
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kk) * scale
        if causal:
            q_pos = my * t_local + jnp.arange(t_local)        # global rows
            k_pos = src * t_local + jnp.arange(t_local)       # global cols
            mask = q_pos[:, None] >= k_pos[None, :]           # [Tq,Tk]
            logits = jnp.where(mask[None, None], logits, _NEG_INF)
        m, l, o = _merge(m, l, o, logits, vv)
        if step != axis_size - 1:
            kk = jax.lax.ppermute(kk, axis_name, perm)
            vv = jax.lax.ppermute(vv, axis_name, perm)

    out = o / jnp.maximum(l, 1e-30)[..., None]                # [B,H,Tq,D]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)   # [B,Tq,H,D]


def make_ring_attention(mesh: Mesh, axis: str = "data",
                        causal: bool = False) -> Callable:
    """Jitted ``fn(q, k, v) -> out`` over sequence-sharded [B, T, H, D]."""
    axis_size = mesh.shape[axis]
    body = partial(ring_attention_local, axis_name=axis,
                   axis_size=axis_size, causal=causal)
    spec = P(None, axis)  # shard the T dimension
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Ring x flash: the Pallas flash kernels as the per-hop block core
# ---------------------------------------------------------------------------

def _to3(x):
    """[B, T, H, D] -> [B*H, T, D] (the flash kernels' layout)."""
    b, t, h, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, t, d)


def _to4(x3, b, h):
    """[B*H, T, D] -> [B, T, H, D] (inverse of _to3)."""
    _, t, d = x3.shape
    return jnp.transpose(x3.reshape(b, h, t, d), (0, 2, 1, 3))


def _hop_fwd(q4, k4, v4, use_pallas: bool, causal=False,
             q_offset=0, k_offset=0):
    """One hop's flash forward on [B, Tq, H, D] q against a [B, Tk, H, D]
    K/V block -> (normalized fp32 partial out [B,Tq,H,D], lse [B*H,Tq,1]).
    Partials stay fp32: the ring accumulators merge N of them, and rounding
    each hop to the input dtype would stack N quantization errors."""
    from ..ops.pallas.flash_attention import _flash_fwd_impl, pick_block

    b, tq, h, d = q4.shape
    tk = k4.shape[1]
    o3, lse3 = _flash_fwd_impl(_to3(q4), _to3(k4), _to3(v4), tk,
                               pick_block(tq), pick_block(tk), use_pallas,
                               out_dtype=jnp.float32, causal=causal,
                               q_offset=q_offset, k_offset=k_offset)
    return _to4(o3, b, h), lse3


def _hop_bwd(q4, k4, v4, do4, lse_tot, delta, use_pallas: bool,
             causal=False, q_offset=0, k_offset=0):
    """One hop's flash backward: fp32 (dq_partial, dk_block, dv_block)
    given the TOTAL logsumexp and delta — the flash backward never
    differentiates through the merge (p_i = exp(s_i - lse_total) directly;
    shared impl in ops/pallas/flash_attention._flash_bwd_impl)."""
    from ..ops.pallas.flash_attention import _flash_bwd_impl, pick_block

    b, tq, h, d = q4.shape
    tk = k4.shape[1]
    dq3, dk3, dv3 = _flash_bwd_impl(
        _to3(q4), _to3(k4), _to3(v4), _to3(do4), lse_tot, delta,
        kv_len=tk, block_q=pick_block(tq), block_k=pick_block(tk),
        use_pallas=use_pallas, out_dtype=jnp.float32, causal=causal,
        q_offset=q_offset, k_offset=k_offset)
    return _to4(dq3, b, h), _to4(dk3, b, h), _to4(dv3, b, h)


def make_ring_flash_attention(mesh: Mesh, axis: str = "seq",
                              causal: bool = False,
                              use_pallas: bool | None = None) -> Callable:
    """Ring attention whose per-hop block core is the Pallas flash kernel.

    Composition of the two long-context mechanisms: the sequence is sharded
    T/N per device (ring hops via ``ppermute`` over ``axis``), and within
    each hop the resident [Tq_local, Tk_block] attention runs as the fused
    flash kernel (ops/pallas/flash_attention.py) instead of a dense einsum
    — neither the [T, T] nor even a [T/N, T/N] score matrix reaches HBM.

    Forward: each hop's flash fwd yields a normalized partial (o_i, lse_i);
    partials merge associatively (out = sum_i exp(lse_i - M) o_i /
    sum_i exp(lse_i - M), lse = M + log-sum). Backward (custom VJP): the
    flash backward never differentiates the merge — with the TOTAL lse and
    delta = rowsum(dO * O), each hop's dq/dk/dv come from the same flash
    backward kernels, with dK/dV accumulators rotating in lockstep with
    their K/V blocks so each block's gradient arrives home after a full
    cycle (standard ring-attention backward).

    Off TPU (CPU tests) the hops run the identical-math jnp fallback; the
    kernels themselves are validated on-chip by tests/test_flash_attention.
    ``causal=True`` masks in GLOBAL positions: each hop passes its shard's
    q offset and the rotating block's k offset down to the kernels; a hop
    whose block is entirely in the future degenerates to lse ~ -1e30 and
    the merge weights it to zero. T/N must be a multiple of 128.
    """
    axis_size = mesh.shape[axis]
    if use_pallas is None:
        from ..ops.attention import _on_tpu
        use_pallas = _on_tpu()
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    @jax.custom_vjp
    def local_ring(q, k, v):
        out, _ = _ring_fwd(q, k, v)
        return out

    def _ring_fwd(q, k, v):
        b, tl, h, d = q.shape
        bh = b * h
        m = jnp.full((bh, tl, 1), _NEG_INF, jnp.float32)
        l = jnp.zeros((bh, tl, 1), jnp.float32)
        acc = jnp.zeros((b, tl, h, d), jnp.float32)
        my = jax.lax.axis_index(axis)
        kk, vv = k, v
        for step in range(axis_size):
            src = (my - step) % axis_size  # home shard of the resident block
            if causal:
                # A block entirely in the future (src > my) contributes
                # nothing — skip its FLOPs instead of computing a hop the
                # merge will weight to zero ((N-1)/2 hops per shard).
                o_i, lse_i = jax.lax.cond(
                    src <= my,
                    lambda ops: _hop_fwd(*ops, use_pallas, True,
                                         my * tl, src * tl),
                    lambda ops: (jnp.zeros((b, tl, h, d), jnp.float32),
                                 jnp.full((bh, tl, 1), _NEG_INF,
                                          jnp.float32)),
                    (q, kk, vv))
            else:
                o_i, lse_i = _hop_fwd(q, kk, vv, use_pallas, False,
                                      my * tl, src * tl)
            m_new = jnp.maximum(m, lse_i)
            w_prev = jnp.exp(m - m_new)
            w_i = jnp.exp(lse_i - m_new)
            l = l * w_prev + w_i
            # [BH, T, 1] weights -> [B, T, H, 1] to scale the partials.
            def w4(w):
                return jnp.transpose(w.reshape(b, h, tl, 1), (0, 2, 1, 3))
            acc = acc * w4(w_prev) + o_i * w4(w_i)  # o_i already fp32
            m = m_new
            if step != axis_size - 1:
                kk = jax.lax.ppermute(kk, axis, perm)
                vv = jax.lax.ppermute(vv, axis, perm)
        l = jnp.maximum(l, 1e-30)
        lse_tot = m + jnp.log(l)
        out = (acc / jnp.transpose(l.reshape(b, h, tl, 1), (0, 2, 1, 3))
               ).astype(q.dtype)
        return out, lse_tot

    def fwd_rule(q, k, v):
        out, lse_tot = _ring_fwd(q, k, v)
        return out, (q, k, v, out, lse_tot)

    def bwd_rule(res, do):
        q, k, v, out, lse_tot = res
        b, tl, h, d = q.shape
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)                       # [B, T, H]
        delta = jnp.transpose(delta, (0, 2, 1)).reshape(b * h, tl, 1)
        dq = jnp.zeros_like(q, jnp.float32)
        my = jax.lax.axis_index(axis)
        kk, vv = k, v
        dkk = jnp.zeros_like(k, jnp.float32)
        dvv = jnp.zeros_like(v, jnp.float32)
        for step in range(axis_size):
            src = (my - step) % axis_size
            if causal:
                dq_i, dk_i, dv_i = jax.lax.cond(
                    src <= my,
                    lambda ops: _hop_bwd(*ops, use_pallas, True,
                                         my * tl, src * tl),
                    lambda ops: (jnp.zeros((b, tl, h, d), jnp.float32),) * 3,
                    (q, kk, vv, do, lse_tot, delta))
            else:
                dq_i, dk_i, dv_i = _hop_bwd(q, kk, vv, do, lse_tot, delta,
                                            use_pallas, False,
                                            my * tl, src * tl)
            dq = dq + dq_i
            dkk = dkk + dk_i
            dvv = dvv + dv_i
            # Rotate blocks AND their gradient accumulators together; the
            # accumulators always rotate (N hops bring each one home with
            # every shard's contribution), the K/V blocks skip the final
            # rotation — they are never read again.
            if step != axis_size - 1:
                kk = jax.lax.ppermute(kk, axis, perm)
                vv = jax.lax.ppermute(vv, axis, perm)
            dkk = jax.lax.ppermute(dkk, axis, perm)
            dvv = jax.lax.ppermute(dvv, axis, perm)
        return (dq.astype(q.dtype), dkk.astype(k.dtype),
                dvv.astype(v.dtype))

    local_ring.defvjp(fwd_rule, bwd_rule)

    spec = P(None, axis)
    fn = jax.shard_map(local_ring, mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=spec,
                       check_vma=False)
    return jax.jit(fn)


def dense_attention(q, k, v, causal: bool = False):
    """Reference dense softmax attention (for tests / single-device)."""
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk",
                        q.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(d)
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)
