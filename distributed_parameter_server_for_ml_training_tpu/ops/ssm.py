"""Mamba-2's state-space scan, computed in chunks, and the causal depthwise
convolution that feeds it.

The recurrence, for head ``h`` of group ``g`` (``S`` is ``[P, N]``)::

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

``x`` ``[B, T, H, P]``, ``dt`` ``[B, T, H]`` (positive: after the softplus),
``A`` ``[H]`` (negative), ``B`` and ``C`` ``[B, T, G, N]``, one pair a group
of ``H / G`` heads. Token by token that is ``T`` dependent steps on a ``[H,
P, N]`` state, and a backward pass that keeps ``[T, H, P, N]`` of them (8.6
GB at 2 x 8,192 tokens, 16 heads of 64 on a state of 128).

:func:`ssm_scan` is the state-space-duality form (Dao and Gu, "Transformers
are SSMs", section 6): the sequence in chunks of ``L`` tokens, and with ``a_t
= dt_t A`` and ``cum`` its running sum inside a chunk

- inside a chunk ``y_l += sum_{s <= l} (C_l . B_s) exp(cum_l - cum_s) dt_s
  x_s``: the masked ``C B^T`` product against the decays' segment sums, two
  matmuls a chunk;
- a chunk's own contribution to the state at its end, ``sum_s exp(cum_L -
  cum_s) dt_s x_s B_s^T``, one matmul a chunk;
- between chunks the carried state, ``S <- exp(cum_L) S + (the chunk's
  own)``: ``T / L`` dependent steps on ``[H, P, N]``;
- ``y_l += exp(cum_l) S_before C_l``, one matmul a chunk.

A decay is only ever ``exp`` of a *difference* of running sums that is <= 0
(the mask is applied to the difference, before the ``exp``), so a long run of
strong decays underflows to the 0 it is and a quotient of two underflowed
products never appears. The decays, their sums and the carried state are
float32; the matmuls take operands of ``x``'s dtype and sum in float32.

The backward pass is JAX's own through this form. What it keeps is a chunk's
``[L, L]`` decays a head and one ``[H, P, N]`` state a *chunk*, 1 / L of the
token-by-token scan's; the states inside a chunk never exist, forward or
backward.

``dps_ssm_scan_total{impl}`` counts, at trace time, which scan a program
holds (``xla_chunked``; a Pallas kernel would be a second value).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the values of ``dps_ssm_scan_total``'s ``impl`` label
SSM_SCAN_IMPLS = ("xla_chunked",)


def causal_conv1d(x: jax.Array, kernel: jax.Array, bias: jax.Array):
    """Depthwise causal convolution along ``T``: ``y_t = bias + sum_k
    kernel[k] x_{t - (K-1) + k}`` a channel, tokens before the first
    reading 0. ``x`` ``[B, T, C]``, ``kernel`` ``[K, C]``, ``bias`` ``[C]``;
    float32 inside, ``x``'s dtype out. ``K`` shifted multiply-adds (4 in
    Mamba-2), which XLA fuses into one pass."""
    t, k = x.shape[1], kernel.shape[0]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    y = bias.astype(jnp.float32)
    for i in range(k):
        y = y + padded[:, i:i + t] * kernel[i].astype(jnp.float32)
    return y.astype(x.dtype)


def ssm_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int) -> jax.Array:
    """``y`` ``[B, T, H, P]`` of the module docstring's recurrence from a
    zero state, in chunks of ``chunk`` tokens. ``T`` need not be a multiple
    of ``chunk``: the tail is padded with ``dt = 0`` tokens, which neither
    decay the state nor add to it."""
    from ..telemetry import get_registry
    get_registry().counter("dps_ssm_scan_total",
                           impl=SSM_SCAN_IMPLS[0]).inc()
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    hg, f32 = h // g, jnp.float32
    pad = -t % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b, c))
    z = (t + pad) // chunk
    x = x.reshape(bsz, z, chunk, g, hg, p)
    b = b.reshape(bsz, z, chunk, g, n)
    c = c.reshape(bsz, z, chunk, g, n)
    # heads before the chunk's tokens: [B, Z, G, Hg, L]
    dt = dt.astype(f32).reshape(bsz, z, chunk, g, hg).transpose(0, 1, 3, 4, 2)
    cum = jnp.cumsum(dt * a.astype(f32).reshape(g, hg, 1), axis=-1)

    # inside a chunk: (C_l . B_s) exp(cum_l - cum_s) dt_s, s <= l
    cb = jnp.einsum("bzlgn,bzsgn->bzgls", c, b, preferred_element_type=f32)
    # the diagonal's decay is exp(0): a constant 1, so that cum_l - cum_l
    # hands its two equal and opposite cotangents to nobody (summed into a
    # row's and a column's they would cancel to rounding noise of their size)
    below = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)
    decay = jnp.exp(jnp.where(below, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf)) + jnp.eye(chunk, dtype=f32)
    m = cb[:, :, :, None] * decay * dt[..., None, :]    # [B, Z, G, Hg, L, L]
    y = jnp.einsum("bzghls,bzsghp->bzlghp", m.astype(x.dtype), x,
                   preferred_element_type=f32)

    # a chunk's own contribution to the state at its end: [B, Z, G, Hg, P, N]
    to_end = (jnp.exp(cum[..., -1:] - cum) * dt).transpose(0, 1, 4, 2, 3)
    own = jnp.einsum("bzsghp,bzsgn->bzghpn",
                     (x * to_end[..., None]).astype(x.dtype), b,
                     preferred_element_type=f32)

    # between chunks: the state each chunk starts from
    def carry(state, step):
        chunk_decay, chunk_own = step
        return chunk_decay[..., None, None] * state + chunk_own, state

    _, before = jax.lax.scan(
        carry, jnp.zeros((bsz, g, hg, p, n), f32),
        (jnp.exp(cum[..., -1]).swapaxes(0, 1), own.swapaxes(0, 1)))
    before = before.swapaxes(0, 1)                      # [B, Z, G, Hg, P, N]
    y = y + jnp.einsum("bzlgn,bzghpn->bzlghp", c, before.astype(x.dtype),
                       preferred_element_type=f32) \
        * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
    return y.reshape(bsz, t + pad, h, p)[:, :t].astype(x.dtype)
