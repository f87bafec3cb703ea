"""Fused (flash) attention as Pallas TPU kernels, with a custom VJP.

Net-new TPU capability (round-2 VERDICT item 5): the reference has no
attention anywhere (its model layer is a CNN, SURVEY.md §2.6); this is the
fused core for the framework's transformer path — the same
``[B, T, H, D] x3 -> [B, T, H, D]`` contract as
parallel/ring_attention.dense_attention, so it drops into
models/vit.py:SelfAttention via ``attention_fn`` and serves as the per-hop
block kernel inside ring attention.

Design (standard flash attention, TPU-shaped):

- forward: grid over (batch*heads, T/BLOCK_Q); each program streams K/V
  through VMEM in BLOCK_K tiles, keeping the online-softmax running
  (max, sum, acc) in VMEM scratch — the [T, T] score matrix never
  materializes. Saves the per-row logsumexp for the backward.
- backward: two kernels re-using the saved LSE (no softmax recompute
  ambiguity): dQ tiles over query blocks, dK/dV tiles over key blocks,
  each streaming the opposite operand. delta = rowsum(dO * O) is a cheap
  elementwise precompute.
- what a program sums over its tiles (the forward's max, sum and acc, dQ,
  dK and dV) is VMEM scratch that a tile reads and writes where it uses it;
  the loops carry nothing. As loop-carried values the same arrays, up to
  three times the register file, were copied between spill slots at both
  ends of every iteration while the MXU stood still. The forward's max and
  sum are ``[BLOCK_Q, 128]`` (a row's max in every lane, its sum as 128
  partial sums that meet after the loop).
- a program visits only the tiles its loop bounds leave (``_k_ranges``,
  ``_q_ranges``: not the padding, not the causal future; ``tile_plan``
  counts them) and masks every tile it visits: on a v5e the kernels wait
  for the MXU, and a loop of their own for the tiles the mask cannot touch
  made them slower (PERF.md section 6, PR 33).
- ``v`` (and so ``o``) may have a head width of its own: latent attention
  (models/joyai.py) has q/k heads of 192 and v heads of 128. The softmax
  scale is 1/sqrt of the q/k width.
- two doors to one set of kernels: ``flash_attention`` takes ``[B, T, H,
  D]`` and transposes to the kernels' ``[B*H, T, D]`` and back;
  ``flash_attention_heads_major`` takes what a caller that projects per
  head holds (q, k ``[B, H, T, D]``; v, ``o`` ``[B, T, H*Dv]``) and moves
  nothing: the kernel bodies see the same blocks, and the block specs of v,
  ``o``, ``dO`` and ``dv`` pick a head's lanes (``v_heads`` below).
- sequence lengths that aren't block multiples are zero-padded; padded KEY
  positions are masked to -inf in every kernel, padded QUERY rows fall out
  of the backward because their dO/delta are zero.
- ``window`` (static; causal calls): row ``i`` sees columns ``i - window <
  j <= i``. Tiles wholly below the band are skipped like tiles above the
  diagonal, and where the band is shorter than the sequence a program is
  handed the band alone: its query block's ``window + block`` keys (the
  dK/dV kernel: its K block's queries) through an element-indexed block
  spec, not the sequence (models/smallthinker.py: 4,608 of 16,384 rows).
- grouped queries: ``k`` and ``v`` may hold fewer heads than ``q``
  (``[B*G, T, D]`` beside ``[B*H, T, D]``; query head ``h`` reads key/value
  head ``h // (H/G)``). The grid then has the group as its innermost axis:
  a group's query heads run one after the other on one K/V block, which is
  fetched once, and the dK/dV kernel sums over them in its scratch. With
  ``H == G`` and no window every call is what it was before either existed.

Off TPU the same math runs as a jnp fallback (exact dense formulation with
identical masking), which is what the CPU test suite exercises; kernel-vs-
fallback parity on real hardware is asserted by tests/test_flash_attention.py
when a TPU is attached (and by experiments/ on-chip runs).

VMEM sizing: without a window each program holds full K and V for one
(batch, head), double-buffered (the dK/dV kernel: full Q, dO, LSE and
delta, the ``[T, 1]`` rows padded to 128 lanes): at D=128 bf16 and 16,384
tokens 16 MiB and 48 MiB of ``VMEM_LIMIT_BYTES``; beyond that, shard the
sequence with ring attention (parallel/ring_attention.py), which calls this
kernel per hop on T/N-sized blocks.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = -1e30
#: lanes of a vector register: the width of the forward's running max and sum
_LANES = 128
# Measured on-chip (experiments/measure_mfu.py block sweep): 512-wide tiles
# nearly halve the backward at T>=2048 vs 128 (bigger serial-loop bodies
# keep the MXU fed); short sequences clamp down so padding stays small.
MAX_BLOCK = 512
#: scoped VMEM the kernels may use (``_compiler_params``)
VMEM_LIMIT_BYTES = 64 << 20

# Run the Pallas kernels in interpreter mode (CPU emulation of the exact
# kernel code, loop bounds and SMEM scalars included). Tests flip this to
# exercise the kernel-side logic without a chip; never set on TPU.
INTERPRET = False


# -- which tiles a program visits ---------------------------------------------
#
# A tile is one (query block, K block) pair. The helpers below compute from
# the same facts: the static shapes, ``kv_len``, the window, and the (q, k)
# offsets. ``xp`` is ``jnp`` in the kernels, where the offsets are SMEM
# scalars and the grid position is traced (a ring hop has non-zero offsets
# and block_q may differ from block_k), and ``np`` in ``tile_plan``. Not
# causal, every bound is a Python int.

def _k_ranges(xp, pid_q, shift, n_k: int, block_q: int, block_k: int,
              kv_len: int, causal: bool):
    """``(n_full, hi)`` for query block ``pid_q``: the K-block loop ends at
    ``hi``; blocks ``[hi, n_k)`` lie in the padding (static) or, under
    causal masking, wholly in the future of the block's last GLOBAL row
    (dynamic) and are skipped. Blocks ``[0, n_full)`` are wholly at or
    before the diagonal and wholly inside ``kv_len`` (``tile_plan`` counts
    them; the kernels mask every tile they visit, PERF.md section 6, PR
    33). ``shift`` is ``q_offset - k_offset``. Where the loop *starts*
    under a window: :func:`_k_band`."""
    hi = min(n_k, -(-kv_len // block_k))           # static: skip padding
    n_full = kv_len // block_k                     # static
    if causal:
        row0 = shift + pid_q * block_q             # first row, from k's 0
        hi = xp.clip((row0 + block_q - 1) // block_k + 1, 0, hi)
        n_full = xp.clip((row0 + 1) // block_k, 0, n_full)
    return n_full, hi


def _k_band(xp, pid_q, block_q: int, block_k: int, window: int):
    """``(lo, full_lo)`` for query block ``pid_q`` under a window, offsets
    zero: K blocks before ``lo`` lie wholly below the band of the block's
    FIRST row (row ``i`` sees columns above ``i - window``) and are
    skipped; blocks from ``full_lo`` on lie wholly inside the band of its
    LAST row, so only ``[lo, full_lo)`` cross the band's lower edge."""
    row0 = pid_q * block_q
    lo = xp.maximum(row0 - window + 1, 0) // block_k
    full_lo = -(-xp.maximum(row0 + block_q - window, 0) // block_k)
    return lo, full_lo


def _q_ranges(xp, pid_k, shift, n_q: int, block_q: int, block_k: int,
              kv_len: int, q_len: int, t_k: int, causal: bool):
    """``(lo, hi)`` for K block ``pid_k``: the query-block loop runs
    ``[lo, hi)``. Blocks from ``hi`` on are padded query rows (zero dO and
    delta; static); under causal masking blocks before ``lo`` lie wholly
    before the K block's first GLOBAL column (dynamic); a K block wholly in
    the padding visits none. ``shift`` is ``k_offset - q_offset``. Where
    the loop *ends* under a window: :func:`_q_band`."""
    hi = min(n_q, -(-q_len // block_q))            # static
    lo = 0
    if causal:
        col0 = shift + pid_k * block_k             # first column, from q's 0
        lo = xp.clip(col0 // block_q, 0, hi)
    if kv_len < t_k:                               # static: padded keys
        lo = xp.where(pid_k * block_k >= kv_len, hi, lo)
    return lo, hi


def _q_band(xp, pid_k, block_q: int, block_k: int, window: int):
    """For K block ``pid_k`` under a window, offsets zero: query blocks
    from this one on lie wholly below the band of the block's LAST column
    (column ``j`` is seen by rows below ``j + window``) and are skipped."""
    return (pid_k * block_k + block_k + window - 2) // block_q + 1


def _band_blocks(n_own: int, n_other: int, block_own: int, block_other: int,
                 window: int | None, k_side: bool) -> int:
    """How many blocks of the *other* operand a program's loop can visit
    under the window (the forward and dQ: K blocks a query block; dK/dV:
    query blocks a K block), offsets zero. A program is handed that many
    blocks, not the sequence (``_held_k``, ``_held_q``); 0 where that is
    no fewer than the sequence's, or there is no window: the sequence it
    is."""
    if window is None:
        return 0
    own = np.arange(n_own)
    if k_side:      # K blocks [lo, hi) of query block ``own``
        lo, _ = _k_band(np, own, block_own, block_other, window)
        hi = (own * block_own + block_own - 1) // block_other + 1
    else:           # query blocks [lo, hi) of K block ``own``
        lo = own * block_own // block_other
        hi = _q_band(np, own, block_other, block_own, window)
    band = int((np.minimum(hi, n_other) - lo).max())
    return band if band < n_other else 0


def _held_k(pid_q, block_q: int, block_k: int, n_k: int, band: int):
    """The first of the ``band`` K blocks query block ``pid_q`` holds: they
    end with the block its last row lies in, moved where they would pass
    either end of K. The block specs' index maps and the kernels compute it
    alike."""
    last = jnp.minimum((pid_q * block_q + block_q - 1) // block_k + 1, n_k)
    return jnp.clip(last - band, 0, n_k - band)


def _held_q(pid_k, block_q: int, block_k: int, n_q: int, band: int):
    """The first of the ``band`` query blocks K block ``pid_k`` holds: they
    start with the block its first column lies in."""
    return jnp.clip(pid_k * block_k // block_q, 0, n_q - band)


def tile_plan(t_q: int, t_k: int, kv_len: int, block_q: int, block_k: int,
              causal: bool, window: int | None = None) -> dict:
    """Tiles of one (batch, head) of one kernel call, offsets zero, from the
    loop bounds the kernels themselves use: ``skipped`` (never computed:
    above the diagonal or in the padding), ``masked`` (computed; the
    diagonal, the end of the keys or the band's lower edge crosses them)
    and ``unmasked`` (computed, wholly visible); with a ``window`` also
    ``below_band`` (never computed: wholly below the band). At the decoder
    LM's shape (4,096 causal tokens, 512-wide blocks): 28 unmasked, 8
    masked, 28 skipped. At 16,384 causal tokens, 512-wide blocks: 496 / 32 /
    496 of 1,024, and under a window of 4,096: 196 / 56 / 496 with 276
    below the band, 252 of the triangle's 528."""
    n_q, n_k = t_q // block_q, t_k // block_k
    n_full, hi = (np.broadcast_to(x, (n_q,)) for x in _k_ranges(
        np, np.arange(n_q), 0, n_k, block_q, block_k, kv_len, causal))
    plan = {}
    lo = full_lo = np.zeros((n_q,), np.int64)
    if window is not None:
        lo, full_lo = _k_band(np, np.arange(n_q), block_q, block_k, window)
        lo = np.minimum(lo, hi)
        plan["below_band"] = int(lo.sum())
    unmasked = int(np.maximum(n_full - np.maximum(full_lo, lo), 0).sum())
    visited = int((hi - lo).sum())
    return {"unmasked": unmasked, "masked": visited - unmasked,
            "skipped": n_q * n_k - int(hi.sum()), **plan}


def _count_tiles(programs: int, plan: dict) -> None:
    """``dps_flash_tiles_total{kind}``, at trace time: ``plan`` for each of
    one kernel call's ``programs`` (batch, head)s."""
    from ...telemetry import get_registry
    for kind, n in plan.items():
        get_registry().counter("dps_flash_tiles_total", kind=kind).inc(
            programs * n)


def _keep(shape, pos_ref, row0, col0, kv_len: int, pad_k: bool,
          causal: bool, window: int | None = None):
    """A tile's ``[BQ, BK]`` keep-mask (None where nothing can be masked):
    local columns under ``kv_len``, compared only where the keys are padded
    at all (``pad_k``, static), and under causal masking GLOBAL column <=
    GLOBAL row (``pos_ref`` holds (q_offset, k_offset), non-zero when the
    call is one hop of a sharded ring), under a ``window`` also GLOBAL row
    - GLOBAL column < ``window``. ``row0`` / ``col0``: the tile's first
    local row / column."""
    keep = None
    if pad_k or causal:
        col = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if pad_k:
        keep = col < kv_len
    if causal:
        row_g = pos_ref[0, 0] + row0 \
            + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        col_g = pos_ref[0, 1] + col
        visible = col_g <= row_g
        if window is not None:
            visible &= row_g - col_g < window
        keep = visible if keep is None else keep & visible
    return keep


def _lanes(x, width: int):
    """``x`` ``[BQ, 128]`` with every lane of a row equal -> ``[BQ, width]``
    of the same: whole vregs side by side, no cross-lane move."""
    if width % _LANES == 0:
        return jnp.tile(x, (1, width // _LANES))
    if width < _LANES:
        return x[:, :width]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], width))


# -- the kernels --------------------------------------------------------------
#
# What a program sums over its tiles lives in VMEM scratch, read and written
# where a tile uses it, and the loop carries nothing. Carried as loop values
# the same arrays (192 vregs in the forward and in dK/dV, twice the register
# file) were copied from spill slot to spill slot at every iteration's two
# ends, 370-520 bundles of a 1,850-3,300 bundle tile in which the MXU stood
# still (PERF.md section 6, PR 33).
#
# ``band`` (static; 0 without a window, or where the band is the sequence):
# the blocks of the streamed operand the program holds. Its loop then runs
# over GLOBAL block numbers as before and reads block ``i`` at row ``(i -
# first resident block) * block`` of what it holds.

def _k_loop(pos_ref, pid_q, n_k: int, block_q: int, block_k: int,
            kv_len: int, causal: bool, window, band: int):
    """``(lo, hi, row)`` of the forward's and dQ's K-block loop: it runs
    ``[lo, hi)`` and ``row(i)`` is block ``i``'s first row in the K and V
    the program holds."""
    _n_full, hi = _k_ranges(jnp, pid_q, pos_ref[0, 0] - pos_ref[0, 1], n_k,
                            block_q, block_k, kv_len, causal)
    if window is None:
        return 0, hi, lambda i: i * block_k
    lo, _full_lo = _k_band(jnp, pid_q, block_q, block_k, window)
    if not band:
        return jnp.minimum(lo, hi), hi, lambda i: i * block_k
    held = _held_k(pid_q, block_q, block_k, n_k, band)
    return jnp.minimum(lo, hi), hi, lambda i: (i - held) * block_k


def _fwd_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *,
                scale: float, block_q: int, block_k: int, n_k: int,
                kv_len: int, causal: bool, window=None, band: int = 0):
    import jax.experimental.pallas as pl  # noqa: F401 (pl.ds below)

    q = q_ref[0]                                   # [BQ, D]
    pad_k = kv_len < n_k * block_k
    # program_id is read OUTSIDE the loop body: the interpret-mode lowering
    # can't substitute it inside fori_loop sub-jaxprs (and hoisting is free
    # on the TPU path).
    pid_q = pl.program_id(1)
    lo, hi, row = _k_loop(pos_ref, pid_q, n_k, block_q, block_k, kv_len,
                          causal, window, band)
    # Running max and sum a row as [BQ, 128]: the max with every lane of a
    # row equal, the sum as 128 partial sums a row (column c of a tile goes
    # to lane c % 128) that meet in one cross-lane sum after the loop. A
    # [BQ, 1] array costs the same 64 vregs and a masked store a vreg.
    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)   # v's own width

    def tile(i, carry):
        at = row(i)
        kb = k_ref[0, pl.ds(at, block_k), :]               # [BK, D]
        vb = v_ref[0, pl.ds(at, block_k), :]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [BQ, BK]
        keep = _keep(s.shape, pos_ref, pid_q * block_q, i * block_k,
                     kv_len, pad_k, causal, window)
        if keep is not None:
            s = jnp.where(keep, s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, block_k))            # [BQ, BK]
        alpha = jnp.exp(m - m_new)
        m_ref[...] = m_new
        p_lanes = p[:, :_LANES]
        for c in range(_LANES, block_k, _LANES):
            p_lanes = p_lanes + p[:, c:c + _LANES]
        l_ref[...] = l_ref[...] * alpha + p_lanes
        pv = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * _lanes(alpha, pv.shape[1]) + pv
        return carry

    jax.lax.fori_loop(lo, hi, tile, 0)
    l = jnp.maximum(jnp.sum(l_ref[...], axis=-1, keepdims=True), 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
    lse_ref[0] = m_ref[:, :1] + jnp.log(l)


def _bwd_dq_kernel(pos_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_acc, *,
                   scale: float, block_q: int, block_k: int, n_k: int,
                   kv_len: int, causal: bool, window=None, band: int = 0):
    import jax.experimental.pallas as pl  # noqa: F401

    q = q_ref[0]
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]                                       # [BQ, 1]
    delta = delta_ref[0]
    pad_k = kv_len < n_k * block_k
    pid_q = pl.program_id(1)       # hoisted: see _fwd_kernel
    lo, hi, row = _k_loop(pos_ref, pid_q, n_k, block_q, block_k, kv_len,
                          causal, window, band)
    dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    def tile(i, carry):
        at = row(i)
        kb = k_ref[0, pl.ds(at, block_k), :]
        vb = v_ref[0, pl.ds(at, block_k), :]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)                               # [BQ, BK]
        keep = _keep(s.shape, pos_ref, pid_q * block_q, i * block_k,
                     kv_len, pad_k, causal, window)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        dp = jax.lax.dot_general(
            do.astype(vb.dtype), vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        return carry

    jax.lax.fori_loop(lo, hi, tile, 0)
    dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(pos_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale: float, block_q: int, n_q: int, kv_len: int,
                    q_len: int, t_k: int, causal: bool, window=None,
                    band: int = 0, group: int = 1):
    import jax.experimental.pallas as pl

    kb = k_ref[0]                                          # [BK, D]
    vb = v_ref[0]
    bk = kb.shape[0]
    pid_k = pl.program_id(1)       # hoisted: see _fwd_kernel
    pad_k = kv_len < t_k
    lo, hi = _q_ranges(
        jnp, pid_k, pos_ref[0, 1] - pos_ref[0, 0], n_q, block_q, bk, kv_len,
        q_len, t_k, causal)
    if window is not None:
        hi = jnp.clip(_q_band(jnp, pid_k, block_q, bk, window), lo, hi)
    held = _held_q(pid_k, block_q, bk, n_q, band) if band else 0

    def start():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    def finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    # a group's query heads are the grid's innermost axis: dK and dV sum
    # over them in the scratch, which the first zeroes and the last writes
    if group == 1:
        start()
    else:
        member = pl.program_id(2)
        pl.when(member == 0)(start)

    def tile(j, carry):
        at = (j - held) * block_q if band else j * block_q
        qb = q_ref[0, pl.ds(at, block_q), :]               # [BQ, D]
        dob = do_ref[0, pl.ds(at, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(at, block_q), :]            # [BQ, 1]
        delta = delta_ref[0, pl.ds(at, block_q), :]
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [BQ, BK]
        p = jnp.exp(s - lse)
        keep = _keep(s.shape, pos_ref, j * block_q, pid_k * bk, kv_len,
                     pad_k, causal, window)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [BK, Dv]
        dp = jax.lax.dot_general(
            dob.astype(vb.dtype), vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [BQ, BK]
        ds = p * (dp - delta)
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [BK, D]
        return carry

    jax.lax.fori_loop(lo, hi, tile, 0)
    if group == 1:
        finish()
    else:
        pl.when(member == group - 1)(finish)


# -- jnp fallback (identical masked math, dense) ------------------------------

def _position_mask(tq, tk, kv_len, causal, q_offset, k_offset, window=None):
    """[Tq, Tk] keep-mask combining the kv_len bound with (optionally) the
    causal constraint in GLOBAL positions (offsets are nonzero when the
    call is one hop of a sharded ring) and the window's."""
    keep = (jnp.arange(tk) < kv_len)[None, :]
    if causal:
        rows = q_offset + jnp.arange(tq)
        cols = k_offset + jnp.arange(tk)
        keep = keep & (cols[None, :] <= rows[:, None])
        if window is not None:
            keep = keep & (rows[:, None] - cols[None, :] < window)
    return keep


def _dense_fwd(q, k, v, kv_len, scale, out_dtype=None,
               causal=False, q_offset=0, k_offset=0, window=None):
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = _position_mask(q.shape[1], k.shape[1], kv_len, causal,
                          q_offset, k_offset, window)
    s = jnp.where(mask[None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bqk,bkd->bqd", p / l, v.astype(jnp.float32))
    lse = m + jnp.log(l)           # [BH, T, 1]
    return o.astype(out_dtype or q.dtype), lse


def pick_block(t: int) -> int:
    """Largest 128-multiple <= MAX_BLOCK dividing ``t`` (kernel grids
    floor-divide, so the block must divide the length exactly)."""
    if t % 128:
        raise ValueError(
            f"sequence block length {t} must be a multiple of 128 (TPU "
            f"tile); pad the sequence or pick a shard count that divides "
            f"it into 128-multiples")
    return max(b for b in range(128, MAX_BLOCK + 1, 128) if t % b == 0)


def _compiler_params():
    """Mosaic parameters of the three kernels. Each program keeps one
    (batch, head)'s whole K and V (the dK/dV kernel: whole Q, dO, LSE and
    delta) in VMEM, double-buffered; the ``[T, 1]`` float32 rows pad to 128
    lanes. At T = 4096 with 192-wide q/k that passes the compiler's default
    scoped limit of 16 MiB (the dK/dV kernel asks for about 24), so the
    limit is raised to what the kernels are sized for; a v5e core has
    128 MiB."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


# -- core op on [BH, T_pad, D] with custom VJP --------------------------------

def _static_zeros(*offsets) -> bool:
    """Whether the call's offsets are known to be zero as it is traced (a
    ring hop's are traced values)."""
    return all(isinstance(x, (int, np.integer)) and x == 0 for x in offsets)


def _pos_scalars(q_offset, k_offset):
    """(1, 2) int32 SMEM payload carrying the global (q, k) offsets."""
    return jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(k_offset, jnp.int32)]).reshape(1, 2)


def _split_heads(x, heads: int):
    """``[B, T, H*W]`` -> ``[B*H, T, W]`` (the jnp fallback's view of a
    ``v_heads`` array; the kernels read the array as it is)."""
    b, t, width = x.shape
    return x.reshape(b, t, heads, width // heads).transpose(
        0, 2, 1, 3).reshape(b * heads, t, width // heads)


def _merge_heads(x, heads: int):
    """The inverse of ``_split_heads``."""
    bh, t, w = x.shape
    return x.reshape(bh // heads, heads, t, w).transpose(
        0, 2, 1, 3).reshape(bh // heads, t, heads * w)


class _Grid:
    """One kernel call's grid and the block specs on it.

    The grid is ``(key/value heads, blocks)`` and, where a key/value head
    serves ``group`` > 1 query heads, ``(key/value heads, blocks, group)``:
    the group innermost, so that consecutive programs share their K/V
    blocks (fetched once) and the dK/dV kernel's sums. An index map is
    written once, as a function of ``(kv, block, member)``; ``head`` says
    whose blocks an operand's are (:meth:`query` head ``kv * group +
    member``, or the key/value head itself)."""

    def __init__(self, kv_programs: int, blocks: int, group: int):
        self.group = group
        self.grid = ((kv_programs, blocks) if group == 1
                     else (kv_programs, blocks, group))

    def query(self, kv, member):
        return kv if self.group == 1 else kv * self.group + member

    def block(self, rows: int, width: int, head, row, *, lanes_of: int = 0,
              elements: bool = False):
        """A ``[rows, width]`` block of program ``head(kv, member)`` at
        ``row(block)``: of a ``[programs, T, width]`` array, or with
        ``lanes_of`` = the heads a batch has there, of a ``[B, T, heads *
        width]`` array that head's ``width`` lanes (whole 128-lane tiles,
        so the block is lane-aligned). ``row`` gives a block number, or
        with ``elements`` the first row itself (a band that starts where
        its program needs it, not on a multiple of its length)."""
        import jax.experimental.pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def index(kv, i, member=0):
            p, at = head(kv, member), row(i)
            if lanes_of:
                lane = p % lanes_of
                return (p // lanes_of, at, lane * width if elements else lane)
            return (p, at, 0)

        shape = (1, rows, width)
        if elements:
            shape = tuple(pl.Element(n) for n in shape)
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)


def _whole(_i):
    return 0


def _own(i):
    return i


def _kv(kv, _member):
    return kv


def _held_kv(g: _Grid, n_q: int, n_k: int, block_q: int, block_k: int,
             d: int, dv: int, kv_heads: int, window):
    """``(band, K's block spec, V's)`` of the forward and dQ: what a query
    block's program holds of its key/value head, the whole of K and V
    (``band`` 0) or under a window the ``band`` blocks that end with its
    diagonal block (``_held_k``), wherever they start."""
    band = _band_blocks(n_q, n_k, block_q, block_k, window, k_side=True)
    if band:
        rows = band * block_k

        def row(i):
            return _held_k(i, block_q, block_k, n_k, band) * block_k
    else:
        rows, row = n_k * block_k, _whole
    return (band, g.block(rows, d, _kv, row, elements=bool(band)),
            g.block(rows, dv, _kv, row, lanes_of=kv_heads,
                    elements=bool(band)))


def _repeat_group(x, group: int):
    """``[B*G, T, D]`` -> ``[B*H, T, D]``: each key/value head once for
    each query head of its group (the jnp fallback's view)."""
    return jnp.repeat(x, group, axis=0) if group > 1 else x


def _sum_group(x, group: int):
    """The gradient's way back through ``_repeat_group``."""
    if group == 1:
        return x
    return x.reshape(x.shape[0] // group, group, *x.shape[1:]).sum(axis=1)


def _check_window(window, causal, *offsets) -> None:
    if window is not None and not (causal and _static_zeros(*offsets)):
        raise ValueError("a window needs causal attention and offsets that "
                         "are zero as the call is traced")


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_core(q, k, v, kv_len, block_q, block_k, use_pallas, causal,
                v_heads=0, window=None):
    o, _ = _flash_fwd_impl(q, k, v, kv_len, block_q, block_k, use_pallas,
                           causal=causal, v_heads=v_heads, window=window)
    return o


def _flash_fwd_impl(q, k, v, kv_len, block_q, block_k, use_pallas,
                    out_dtype=None, causal=False, q_offset=0, k_offset=0,
                    v_heads=0, window=None):
    """``q`` ``[B*H, T, D]``, ``k`` ``[B*G, T, D]`` (G key/value heads a
    batch, G dividing H). ``v`` ``[B*G, T, Dv]`` and the ``o`` returned
    ``[B*H, T, Dv]`` or, with ``v_heads`` = H, ``[B, T, G*Dv]`` and ``[B,
    T, H*Dv]`` as a Dense writes and reads them: the kernel bodies are the
    same, the block specs pick a head's lanes of its batch."""
    bh, tp, d = q.shape
    group = bh // k.shape[0]
    kv_heads = v_heads // group
    # v (and o) may be narrower than q and k (MLA)
    dv = v.shape[2] // kv_heads if v_heads else v.shape[2]
    scale = 1.0 / np.sqrt(d)
    _check_window(window, causal, q_offset, k_offset)
    if not use_pallas:
        # out_dtype reaches the FINAL cast — an intermediate round-trip
        # through q.dtype would quantize the fp32 partials the ring merge
        # depends on.
        if v_heads:
            v = _split_heads(v, kv_heads)
        o, lse = _dense_fwd(q, _repeat_group(k, group),
                            _repeat_group(v, group), kv_len, scale,
                            out_dtype, causal, q_offset, k_offset, window)
        return (_merge_heads(o, v_heads) if v_heads else o), lse

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if _static_zeros(q_offset, k_offset):
        _count_tiles(bh, tile_plan(tp, k.shape[1], kv_len, block_q, block_k,
                                   causal, window))
    n_q, n_k = tp // block_q, k.shape[1] // block_k
    g = _Grid(k.shape[0], n_q, group)
    band, blk_k, blk_v = _held_kv(g, n_q, n_k, block_q, block_k, d, dv,
                                  kv_heads, window)
    blk_pos = pl.BlockSpec(memory_space=pltpu.SMEM)
    blk_q = g.block(block_q, d, g.query, _own)
    blk_o = g.block(block_q, dv, g.query, _own, lanes_of=v_heads)
    # LSE rides as [BH, T, 1]: a (1, BLOCK_Q, 1) block keeps the last
    # two dims tileable ((BLOCK_Q, 1): sublanes % 8 == 0, lane dim == array).
    blk_lse = g.block(block_q, 1, g.query, _own)
    o, lse = pl.pallas_call(
        partial(_fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
                n_k=n_k, kv_len=kv_len, causal=causal, window=window,
                band=band),
        grid=g.grid,
        in_specs=[blk_pos, blk_q, blk_k, blk_v],
        out_specs=(blk_o, blk_lse),
        out_shape=(jax.ShapeDtypeStruct(
            (v.shape[0], tp, group * v.shape[2]) if v_heads
            else (bh, tp, dv), out_dtype or q.dtype),
                   jax.ShapeDtypeStruct((bh, tp, 1), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, dv), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=INTERPRET, name="flash_attention_fwd",
    )(_pos_scalars(q_offset, k_offset), q, k, v)
    return o, lse


def _flash_core_fwd(q, k, v, kv_len, block_q, block_k, use_pallas, causal,
                    v_heads=0, window=None):
    o, lse = _flash_fwd_impl(q, k, v, kv_len, block_q, block_k, use_pallas,
                             causal=causal, v_heads=v_heads, window=window)
    return o, (q, k, v, o, lse)


def _flash_bwd_impl(q, k, v, do, lse, delta, kv_len, block_q, block_k,
                    use_pallas, out_dtype=None,
                    causal=False, q_offset=0, k_offset=0, q_len=None,
                    v_heads=0, window=None):
    """Flash backward given EXTERNAL (lse, delta) — shared by the custom
    VJP below and by ring attention's per-hop backward
    (parallel/ring_attention.py), where lse/delta come from the MERGED
    softmax over the whole ring. ``out_dtype`` overrides the gradient
    dtype (the ring accumulates partials in fp32). ``q_len`` is the
    UNPADDED query length (padded query rows carry zero dO/delta, so the
    dK/dV kernel skips those blocks); defaults to the padded length,
    i.e. no skipping. Shapes as ``_flash_fwd_impl``'s: ``do`` is ``o``'s,
    the ``dk`` and ``dv`` returned are ``k``'s and ``v``'s, summed over
    each key/value head's group of query heads."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    group = bh // k.shape[0]
    kv_heads = v_heads // group
    dv = v.shape[2] // kv_heads if v_heads else v.shape[2]
    q_len = tq if q_len is None else q_len
    scale = 1.0 / np.sqrt(d)
    dts = [out_dtype or x.dtype for x in (q, k, v)]
    _check_window(window, causal, q_offset, k_offset)
    if not use_pallas:
        if v_heads:
            dq, dk, dv = _flash_bwd_impl(
                q, k, _split_heads(v, kv_heads), _split_heads(do, v_heads),
                lse, delta, kv_len, block_q, block_k, False, out_dtype,
                causal, q_offset, k_offset, q_len, window=window)
            return dq, dk, _merge_heads(dv, kv_heads)
        qf, kf, vf = (x.astype(jnp.float32) for x in (
            q, _repeat_group(k, group), _repeat_group(v, group)))
        dof = do.astype(jnp.float32)
        s = jnp.einsum("bqd,bkd->bqk", qf, kf) * scale
        mask = _position_mask(tq, tk, kv_len, causal, q_offset, k_offset,
                              window)
        p = jnp.where(mask[None], jnp.exp(s - lse), 0.0)
        dv = jnp.einsum("bqk,bqd->bkd", p, dof)
        dp = jnp.einsum("bqd,bkd->bqk", dof, vf)
        ds = p * (dp - delta)
        dq = jnp.einsum("bqk,bkd->bqd", ds, kf) * scale
        dk = jnp.einsum("bqk,bqd->bkd", ds, qf) * scale
        return (dq.astype(dts[0]), _sum_group(dk, group).astype(dts[1]),
                _sum_group(dv, group).astype(dts[2]))

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if _static_zeros(q_offset, k_offset):
        # dQ visits every query block, dK/dV none that is all padded rows
        for rows in (tq, min(tq, -(-q_len // block_q) * block_q)):
            _count_tiles(bh, tile_plan(rows, tk, kv_len, block_q, block_k,
                                       causal, window))
    n_q, n_k = tq // block_q, tk // block_k
    blk_pos = pl.BlockSpec(memory_space=pltpu.SMEM)
    pos = _pos_scalars(q_offset, k_offset)

    # -- dQ: a query block a program, K and V (or their band) held
    g = _Grid(k.shape[0], n_q, group)
    band, blk_kheld, blk_vheld = _held_kv(g, n_q, n_k, block_q, block_k, d,
                                          dv, kv_heads, window)
    blk_q = g.block(block_q, d, g.query, _own)
    blk_row = g.block(block_q, 1, g.query, _own)
    dq = pl.pallas_call(
        partial(_bwd_dq_kernel, scale=scale, block_q=block_q,
                block_k=block_k, n_k=n_k, kv_len=kv_len, causal=causal,
                window=window, band=band),
        grid=g.grid,
        in_specs=[blk_pos, blk_q, blk_kheld, blk_vheld,
                  g.block(block_q, dv, g.query, _own, lanes_of=v_heads),
                  blk_row, blk_row],
        out_specs=blk_q,
        out_shape=jax.ShapeDtypeStruct(q.shape, dts[0]),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=INTERPRET, name="flash_attention_bwd_dq",
    )(pos, q, k, v, do, lse, delta)

    # -- dK/dV: a K block a program, Q, dO, LSE and delta (or their band)
    # held, one query head of the group after the other
    g = _Grid(k.shape[0], n_k, group)
    band = _band_blocks(n_k, n_q, block_k, block_q, window, k_side=False)

    def held_q(j):
        return _held_q(j, block_q, block_k, n_q, band) * block_q

    rows, row = (band * block_q, held_q) if band else (tq, _whole)
    blk_k = g.block(block_k, d, _kv, _own)
    blk_v = g.block(block_k, dv, _kv, _own, lanes_of=kv_heads)
    blk_rows = g.block(rows, 1, g.query, row, elements=bool(band))
    dk, dv = pl.pallas_call(
        partial(_bwd_dkv_kernel, scale=scale, block_q=block_q, n_q=n_q,
                kv_len=kv_len, q_len=q_len, t_k=tk, causal=causal,
                window=window, band=band, group=group),
        grid=g.grid,
        in_specs=[blk_pos,
                  g.block(rows, d, g.query, row, elements=bool(band)),
                  blk_k, blk_v,
                  g.block(rows, dv, g.query, row, lanes_of=v_heads,
                          elements=bool(band)),
                  blk_rows, blk_rows],
        out_specs=(blk_k, blk_v),
        out_shape=(jax.ShapeDtypeStruct(k.shape, dts[1]),
                   jax.ShapeDtypeStruct(v.shape, dts[2])),
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=INTERPRET, name="flash_attention_bwd_dkv",
    )(pos, q, k, v, do, lse, delta)
    return dq, dk, dv


def _delta_kernel(do_ref, o_ref, delta_ref):
    delta_ref[0] = jnp.sum(do_ref[0].astype(jnp.float32)
                           * o_ref[0].astype(jnp.float32),
                           axis=-1, keepdims=True)


def _delta_of_heads(do, o, heads: int, block_q: int, use_pallas: bool):
    """``delta = rowsum(dO * O)`` a head, ``[B, T, H*Dv]`` x2 -> ``[B*H, T,
    1]`` float32, as the backward kernels read it. A kernel of its own on
    the chip: XLA sums a head's 128 lanes out of a ``[B, T, H*128]`` array
    only after writing the whole product in float32 and copying it to
    another tiling (three passes and 0.8 GB a block at latent attention's
    sizes where the kernels' own ``[B*H, T, Dv]`` layout takes one); this
    reads dO and O once, through the block specs the other kernels use."""
    b, t, width = do.shape
    dv = width // heads
    if not use_pallas:
        prod = do.astype(jnp.float32) * o.astype(jnp.float32)
        return jnp.sum(prod.reshape(b, t, heads, dv), axis=-1).transpose(
            0, 2, 1).reshape(b * heads, t, 1)

    import jax.experimental.pallas as pl

    g = _Grid(b * heads, t // block_q, 1)
    blk = g.block(block_q, dv, _kv, _own, lanes_of=heads)
    return pl.pallas_call(
        _delta_kernel, grid=g.grid, in_specs=[blk, blk],
        out_specs=g.block(block_q, 1, _kv, _own),
        out_shape=jax.ShapeDtypeStruct((b * heads, t, 1), jnp.float32),
        interpret=INTERPRET, name="flash_attention_bwd_delta",
    )(do, o)


def _flash_core_bwd(kv_len, block_q, block_k, use_pallas, causal, v_heads,
                    window, res, do):
    q, k, v, o, lse = res
    if v_heads:
        delta = _delta_of_heads(do, o, v_heads, block_q, use_pallas)
    else:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)        # [BH, T, 1]
    # Self-attention: q and k share the unpadded length, so q_len=kv_len.
    return _flash_bwd_impl(q, k, v, do, lse, delta, kv_len, block_q,
                           block_k, use_pallas, causal=causal,
                           q_len=kv_len, v_heads=v_heads, window=window)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# -- public op ----------------------------------------------------------------

def _check_blocks(block_q, block_k) -> None:
    for name, blk in (("block_q", block_q), ("block_k", block_k)):
        if blk is not None and (blk <= 0 or blk % 128):
            raise ValueError(
                f"{name}={blk} must be a positive multiple of 128 (TPU "
                f"tile constraint; defaults via pick_block satisfy it)")


def _flash_heads_first(q3, k3, v3, block_q, block_k, use_pallas, causal,
                       v_heads=0, window=None):
    """``[B*H, T, D]``, ``[B*G, T, D]`` and ``[B*G, T, Dv]`` -> ``[B*H, T,
    Dv]`` (with ``v_heads`` = H: ``[B, T, G*Dv]`` -> ``[B, T, H*Dv]``): the
    block sizes, the padding of T and the core op, shared by both public
    entries below."""
    t = q3.shape[1]
    # Default blocks: the largest 128-multiple <= MAX_BLOCK that DIVIDES the
    # 128-rounded sequence length — a bare min() would pad e.g. T=768 up to
    # 1024 (1.78x the attention FLOPs); 384 divides it exactly.
    tp128 = -(-t // 128) * 128
    if block_q is None:
        block_q = pick_block(tp128)
    if block_k is None:
        block_k = pick_block(tp128)
    # Pad to a multiple of BOTH block sizes — the kernels floor-divide the
    # padded length by each, so a non-divisible combination would silently
    # skip trailing blocks.
    block = np.lcm(block_q, block_k)
    tp = -(-t // block) * block

    def pad(x):
        return jnp.pad(x, ((0, 0), (0, tp - t), (0, 0))) if tp != t else x

    o3 = _flash_core(pad(q3), pad(k3), pad(v3), t, block_q, block_k,
                     bool(use_pallas), bool(causal), v_heads, window)
    return o3[:, :t] if tp != t else o3


def flash_attention_heads_major(q: jax.Array, k: jax.Array, v: jax.Array, *,
                                causal: bool = False,
                                block_q: int | None = None,
                                block_k: int | None = None,
                                use_pallas: bool = True,
                                window: int | None = None) -> jax.Array:
    """Fused attention for a caller that projects per head: q and k
    heads-major ``[B, H, T, D]`` and ``[B, G, T, D]`` (G key/value heads, G
    dividing H: query head ``h`` reads head ``h // (H/G)``), ``v`` as its
    Dense writes it, ``[B, T, G*Dv]``, and ``o`` returned as the output
    Dense reads it, ``[B, T, H*Dv]``. ``window``: a causal row sees itself
    and the ``window - 1`` positions before it.

    The kernels read ``[B*H, T, D]``, which is heads-major q/k with the two
    leading axes taken as one, and (``Dv`` whole 128-lane tiles) head
    ``h``'s ``Dv`` lanes of ``v``, ``o``, ``dO`` and ``dv`` through their
    block specs: no array is transposed between a projection and a kernel,
    where ``flash_attention``'s ``[B, T, H, D]`` contract costs a pass over
    each of q, k, v and o, forward and backward. A ``Dv`` that is not whole
    tiles is split into heads here first. Same kernels, custom VJP, block
    sizes and padding as ``flash_attention``; no dispatch: the caller
    (``ops.attention.heads_attention_core``) has already chosen, and
    ``use_pallas=False`` is the kernel-identical jnp fallback the CPU tests
    compare with."""
    b, h, t, d = q.shape
    kv_heads = k.shape[1]
    dv = v.shape[-1] // kv_heads
    _check_blocks(block_q, block_k)
    q3, k3 = q.reshape(b * h, t, d), k.reshape(b * kv_heads, t, d)
    if dv % 128:
        o3 = _flash_heads_first(q3, k3, _split_heads(v, kv_heads), block_q,
                                block_k, use_pallas, causal, window=window)
        return _merge_heads(o3, h).astype(q.dtype)
    return _flash_heads_first(q3, k3, v, block_q, block_k, use_pallas,
                              causal, v_heads=h,
                              window=window).astype(q.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    use_pallas: bool | None = None,
                    window: int | None = None) -> jax.Array:
    """Fused attention over ``[B, T, H, D]`` q/k/v (causal optional; k and
    v may hold fewer heads, ``[B, T, G, D]``; ``window`` as
    ``flash_attention_heads_major``'s): the layout a Dense writes. The
    kernels read heads-major ``[B*H, T, D]``, so this entry transposes q, k
    and v on the way in and ``o`` on the way out; a caller that can hold
    ``[B, H, T, D]`` uses ``flash_attention_heads_major`` and moves nothing.

    Same contract as parallel/ring_attention.dense_attention — plug into
    models/vit.py:SelfAttention via ``attention_fn=flash_attention`` (or
    partial(...) to pin block sizes). Differentiable (custom VJP, flash
    backward). T is padded to a block multiple internally; default block
    sizes adapt to T (128-tile-rounded, capped at MAX_BLOCK).

    ``use_pallas=None`` (the default) asks the one rule,
    ``ops.attention.select_core``, and where it does not say ``flash`` runs
    the PLAIN dense formulation under native XLA autodiff, ``dense_core`` —
    what models/vit.py:SelfAttention runs with no ``attention_fn`` off a TPU,
    so there ``attention_fn=flash_attention`` is the identical program
    (asserted bitwise by the CPU tests). ``fused_short`` reads ``dense``
    here: that kernel needs the packed ``[B, T, 3*H*D]`` activation
    ``attention_core`` is given. Explicit True/False force the Pallas
    kernels / the custom-VJP fallback (the CPU tests exercise the latter's
    kernel-identical math; the ring's per-hop calls force theirs).
    """
    b, t, h, d = q.shape
    _check_blocks(block_q, block_k)
    if use_pallas is None:
        from .. import attention
        if attention.core_for_separate_qkv(
                causal, q.dtype, t, h, d, v.shape[-1], window=window,
                num_kv_heads=k.shape[2]) == "dense":
            return attention.dense_core(q, k, v, causal=causal,
                                        window=window)
        use_pallas = True

    def to3(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(-1, t, x.shape[-1])

    o3 = _flash_heads_first(to3(q), to3(k), to3(v), block_q, block_k,
                            use_pallas, causal, window=window)
    o = o3.reshape(b, h, t, v.shape[-1])
    return jnp.transpose(o, (0, 2, 1, 3)).astype(q.dtype)
