"""The framework's attention cores and the one rule that picks between them.

``dense_core`` is the softmax-attention formulation every dense path
shares: logits in the INPUT dtype (bf16 matmuls stay on the fast MXU
path; fp32 upcasts cost 7-10% of a ViT-B/16 @224 step: 740-753 against
813-823 images/s on the round-4 chip, an earlier installation than the
ledger's), softmax in fp32, probabilities cast back. Users:

- ``attention_core`` below, wherever the fused short-sequence kernel
  (ops/pallas/short_attention.py) does not apply,
- ops/pallas/flash_attention.flash_attention under its own
  ``[B, T, H, D]`` contract, wherever the rule below does not say ``flash``,
- train/model_parallel.py:TPTrainer (GSPMD cannot partition a kernel call),
- experiments/measure_mfu.py's attention bench dense arm (the baseline
  the Pallas kernel must beat is the core the dispatch actually runs,
  not the fp32-upcast test reference in parallel/ring_attention).

``attention_core`` is what models/vit.py:SelfAttention runs when no
``attention_fn`` is set, ``heads_attention_core`` what the decoder LMs'
attention runs (models/joyai.py: separate q, k and v, v narrower than q
and k; models/smallthinker.py: 28 query heads on 4 key/value heads, a
sliding window on three layers of four): one softmax attention, three
ways to schedule it, chosen by the backend and static shapes alone
(``select_core``; the window and the number of key/value heads are among
them).

Two layouts come in at the door. ``[B, T, H, D]`` is what a fused or
per-tensor Dense writes and what ``dense_core``, ``flash_attention`` and
``attention_core``'s callers hold; the flash kernels read ``[B*H, T, D]``,
so that door transposes q, k, v and ``o``. models/joyai.py:MLA, the one
heads-major caller, hands ``heads_attention_core`` the arrays the kernels
read as they are: q and k heads-major ``[B, H, T, D]`` (projected per head
and concatenated straight into it), v as ``[B, T, H*Dv]`` the way its
matmul writes it, and takes ``o`` back as ``[B, T, H*Dv]`` the way the
output matmul reads it (a head's ``Dv`` lanes are a block of the kernels'
block specs): nothing is transposed on the way to a kernel or back.

Kept light (jnp, the telemetry registry; the kernel module is imported
only when chosen) so models, ops and experiments can all import it without
cycles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = -1e30


def dense_core(q: jax.Array, k: jax.Array, v: jax.Array, *,
               causal: bool = False, heads_major: bool = False,
               window: int | None = None) -> jax.Array:
    """[B, T, H, D] x3 -> [B, T, H, D] softmax attention in the input
    dtype (fp32 softmax); with ``heads_major`` the arrays the caller holds
    are ``[B, H, T, D]``, in and out: the same einsums with their letters
    in that order. ``v`` may have a width of its own; the scale is 1/sqrt
    of q's. ``k`` and ``v`` may hold fewer heads than ``q`` (grouped
    queries: query head ``h`` reads head ``h // (H/G)``). With a ``window``
    (causal only) row ``i`` sees columns ``i - window < j <= i``."""
    if window is not None and not causal:
        raise ValueError("a window needs causal attention")
    d = q.shape[-1]
    scale = 1.0 / np.sqrt(d)
    qk, pv = (("bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd") if heads_major
              else ("bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd"))
    heads = 1 if heads_major else 2
    group = q.shape[heads] // k.shape[heads]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=heads) for x in (k, v))
    logits = jnp.einsum(qk, q, k) * scale
    if causal:
        t = logits.shape[-1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((t, t), bool), -window)
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum(pv, probs.astype(q.dtype), v)


#: ``impl`` label of ``dps_attention_core_total`` -> one-line meaning
#: (docs/OBSERVABILITY.md documents exactly these rows; tools/dpslint's
#: catalog-drift check pins the two to each other both directions).
ATTENTION_CORE_IMPLS = {
    "fused_short": "ops/pallas/short_attention.py: one fused Pallas "
                   "kernel each way, scores in VMEM, fed by the qkv "
                   "activation",
    "dense": "dense_core: XLA's einsum / softmax / einsum",
    "flash": "ops/pallas/flash_attention.py: the streaming Pallas kernels "
             "(one forward, two backward), scores in VMEM, causal blocks "
             "and blocks below a window's band skipped, grouped queries "
             "on their group's K/V blocks; long sequences",
}

#: from this many tokens on, a bf16 sequence on a TPU goes to the flash
#: kernels: a head's [T, T] scores no longer fit the fused short kernel's
#: VMEM (``short_attention.MAX_T``) and, written to HBM, are 32 MiB a head
#: in bf16 at 4,096 tokens
FLASH_MIN_T = 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def select_core(*, on_tpu: bool, causal: bool, dtype, t: int,
                num_heads: int, head_dim: int,
                v_head_dim: int | None = None, window: int | None = None,
                num_kv_heads: int | None = None) -> str:
    """Which core a call compiles to; a key of ``ATTENTION_CORE_IMPLS``.
    ``v_head_dim`` is given by callers whose v is not as wide as q and k,
    ``window`` and ``num_kv_heads`` by callers that have a sliding window
    or fewer key/value heads than query heads: the fused short-sequence
    kernel computes neither, the flash kernels and ``dense_core`` both.

    The fused short-sequence kernel when the backend is a TPU, the
    attention is not causal, the operands are bf16 (its MXU operands;
    an fp32 model keeps its fp32 einsums), and the static shapes are the
    ones it is written for: 128 % D == 0, H*D % 128 == 0 (whole 128-lane
    groups of heads) and T <= short_attention.MAX_T (one head's scores
    in VMEM; the constant's comment derives it). The flash kernels
    under the same backend and dtype, causal or not, when
    ``T >= FLASH_MIN_T`` and T is whole 128-row tiles (they would pad it
    otherwise). ``dense_core``
    otherwise. Nothing else is consulted: no flag, no environment
    variable, no measured-crossover file."""
    from .pallas.short_attention import supports
    v_head_dim = head_dim if v_head_dim is None else v_head_dim
    if not (on_tpu and dtype == jnp.bfloat16):
        return "dense"
    if (not causal and v_head_dim == head_dim and window is None
            and num_kv_heads in (None, num_heads)
            and supports(t, num_heads, head_dim)):
        return "fused_short"
    if t >= FLASH_MIN_T and t % 128 == 0:
        return "flash"
    return "dense"


def _count(impl: str, window: int | None = None, group: int = 1) -> None:
    """``dps_attention_core_total{impl}``; a call with a sliding window or
    grouped queries also says which (``window``: its length; ``group``:
    query heads a key/value head), so a snapshot tells a model's window
    layers from its global ones."""
    from ..telemetry import get_registry
    labels = {"impl": impl}
    if window is not None:
        labels["window"] = str(window)
    if group > 1:
        labels["group"] = str(group)
    get_registry().counter("dps_attention_core_total", **labels).inc()


def core_for_separate_qkv(causal: bool, dtype, t: int, num_heads: int,
                          head_dim: int, v_head_dim: int, *,
                          window: int | None = None,
                          num_kv_heads: int | None = None) -> str:
    """``select_core``'s answer, counted, for a caller that holds q, k and
    v apart: ``fused_short`` needs the packed qkv activation and reads
    ``dense`` there."""
    impl = select_core(on_tpu=_on_tpu(), causal=causal, dtype=dtype, t=t,
                       num_heads=num_heads, head_dim=head_dim,
                       v_head_dim=v_head_dim, window=window,
                       num_kv_heads=num_kv_heads)
    if impl == "fused_short":
        impl = "dense"
    _count(impl, window, num_heads // (num_kv_heads or num_heads))
    return impl


def heads_attention_core(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = False,
                         window: int | None = None) -> jax.Array:
    """For a caller that projects per head (models/joyai.py:MLA,
    models/decoder.py:GroupedAttention): q and k heads-major ``[B, H,
    T, Dqk]`` and ``[B, G, T, Dqk]`` (G = H, or fewer key/value heads), ``v``
    ``[B, T, G*Dv]`` as its Dense writes it -> ``o`` ``[B, T, H*Dv]`` as the
    output Dense reads it; by the same rule and counted in the same counter
    as ``attention_core``.

    These are the arrays the flash kernels read as they are (heads-major
    q/k is ``[B*H, T, D]`` with its leading axes taken as one; a head's
    ``Dv`` lanes of ``v`` and ``o`` are a block of theirs), so nothing is
    transposed on the way to a kernel or back; ``dense_core`` gets ``v``
    split into heads, which on the CPU costs nothing that matters."""
    b, num_heads, t, head_dim = q.shape
    kv_heads = k.shape[1]
    v_head_dim = v.shape[-1] // kv_heads
    impl = core_for_separate_qkv(causal, q.dtype, t, num_heads, head_dim,
                                 v_head_dim, window=window,
                                 num_kv_heads=kv_heads)
    if impl == "flash":
        from .pallas.flash_attention import flash_attention_heads_major
        return flash_attention_heads_major(q, k, v, causal=causal,
                                           window=window)
    o = dense_core(q, k, v.reshape(b, t, kv_heads, v_head_dim).transpose(
        0, 2, 1, 3), causal=causal, heads_major=True, window=window)
    return o.transpose(0, 2, 1, 3).reshape(b, t, num_heads * v_head_dim)


def attention_core(qkv: jax.Array, num_heads: int, *,
                   causal: bool = False) -> jax.Array:
    """``[B, T, 3*H*D]`` as the fused ``qkv`` Dense writes it (columns in
    (3, H, D) order) -> ``[B, T, H*D]`` as the ``out`` Dense reads it.

    Counts the choice in ``dps_attention_core_total{impl}`` at trace time
    (12 a compile of the ViT-B/16 step), so a run's snapshot says which
    core its program holds."""
    b, t, width = qkv.shape
    head_dim = width // (3 * num_heads)
    impl = select_core(on_tpu=_on_tpu(), causal=causal, dtype=qkv.dtype,
                       t=t, num_heads=num_heads, head_dim=head_dim)
    _count(impl)
    if impl == "fused_short":
        from .pallas.short_attention import short_attention
        return short_attention(qkv, num_heads)
    qkv = qkv.reshape(b, t, 3, num_heads, head_dim)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if impl == "flash":
        from .pallas.flash_attention import flash_attention
        out = flash_attention(q, k, v, causal=causal, use_pallas=True)
    else:
        out = dense_core(q, k, v, causal=causal)
    return out.reshape(b, t, num_heads * head_dim)
