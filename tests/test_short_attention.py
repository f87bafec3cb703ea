"""Fused short-sequence attention (ops/pallas/short_attention.py) and the
rule that picks it (ops/attention.py:select_core / attention_core).

The kernels run here in Pallas' interpreter, which fills what a block
overhangs of its array with NaN: a padded row or column that reached a
result would show as NaN. The reference is written here, plain ``jnp``
softmax attention on inputs upcast to float32: not ``dense_core``, not the
flash kernel's reference. The Mosaic-compiled kernels meet the true-fp32
reference on the chip in ``chip_smoke.py`` (``short_attn_bf16_T197``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_parameter_server_for_ml_training_tpu.ops import (
    attention as at)
from distributed_parameter_server_for_ml_training_tpu.ops.pallas import (
    short_attention as sa)
from distributed_parameter_server_for_ml_training_tpu.telemetry import (
    get_registry)

#: bf16 outputs of magnitude up to ~4: one bf16 rounding is 2**-8 relative.
#: tests/test_flash_attention.py::test_forward_bf16 allows 3e-2 + 3e-2 |ref|.
TOL = dict(atol=2e-2, rtol=2e-2)

#: (B, T, H, D): the published ViT-B/16 head geometry at 224 px (197 tokens
#: overhang the 256-row blocks), an aligned T (no padding anywhere), a short
#: T with one group of two heads, and D = 128 (one head a group).
GEOMETRIES = [(2, 197, 12, 64), (1, 256, 2, 64), (2, 65, 2, 64),
              (1, 100, 2, 128)]


def reference(qkv, num_heads):
    """[B, T, 3*H*D] -> [B, T, H*D] in float32, columns in (3, H, D) order."""
    b, t, width = qkv.shape
    d = width // (3 * num_heads)
    x = qkv.astype(jnp.float32).reshape(b, t, 3, num_heads, d)
    q, k, v = x[:, :, 0], x[:, :, 1], x[:, :, 2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, num_heads * d)


def _inputs(b, t, h, d):
    qkv = jax.random.normal(jax.random.PRNGKey(t), (b, t, 3 * h * d),
                            jnp.float32).astype(jnp.bfloat16)
    cot = jax.random.normal(jax.random.PRNGKey(t + 1), (b, t, h * d),
                            jnp.float32)
    return qkv, cot


@pytest.fixture()
def interpreted(monkeypatch):
    monkeypatch.setattr(sa, "INTERPRET", True)


@pytest.mark.parametrize("b,t,h,d", GEOMETRIES)
def test_forward_matches_float32_reference(b, t, h, d, interpreted):
    qkv, _cot = _inputs(b, t, h, d)
    out = sa.short_attention(qkv, h)
    assert out.shape == (b, t, h * d) and out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(reference(qkv, h)), **TOL)


@pytest.mark.parametrize("b,t,h,d", GEOMETRIES)
def test_dqkv_matches_float32_reference(b, t, h, d, interpreted):
    qkv, cot = _inputs(b, t, h, d)
    got = jax.grad(lambda x: jnp.sum(
        sa.short_attention(x, h).astype(jnp.float32) * cot))(qkv)
    want = jax.grad(lambda x: jnp.sum(reference(x, h) * cot))(
        qkv.astype(jnp.float32))
    assert got.shape == qkv.shape and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), **TOL)


def test_unsupported_geometry_is_refused():
    with pytest.raises(ValueError, match="short_attention takes"):
        sa.short_attention(jnp.zeros((1, 8, 3 * 3 * 64), jnp.bfloat16), 3)


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("on_tpu,causal,dtype,t,h,d,want", [
    (True, False, BF16, 197, 12, 64, "fused_short"),    # ViT-B/16 @224
    (True, False, BF16, sa.MAX_T, 12, 64, "fused_short"),
    (True, False, BF16, sa.MAX_T + 1, 12, 64, "dense"),  # scores leave VMEM
    (True, False, BF16, 197, 2, 128, "fused_short"),    # one head a group
    (True, False, BF16, 197, 4, 32, "fused_short"),     # four heads a group
    (False, False, BF16, 197, 12, 64, "dense"),         # every CPU run
    (True, True, BF16, 197, 12, 64, "dense"),           # causal
    (True, False, F32, 197, 12, 64, "dense"),           # an fp32 model
    (True, False, BF16, 64, 3, 64, "dense"),    # ViT_Tiny: H*D = 192
    (True, False, BF16, 197, 8, 48, "dense"),           # 128 % D != 0
    (True, False, BF16, 197, 1, 256, "dense"),          # D > 128
])
def test_select_core(on_tpu, causal, dtype, t, h, d, want):
    assert want in at.ATTENTION_CORE_IMPLS
    assert at.select_core(on_tpu=on_tpu, causal=causal, dtype=dtype, t=t,
                          num_heads=h, head_dim=d) == want


@pytest.mark.parametrize("on_tpu,want", [(False, "dense"),
                                         (True, "fused_short")])
def test_attention_core_counts_its_choice(on_tpu, want, monkeypatch,
                                          interpreted):
    """The counter's label for each outcome, once a trace; and both cores
    give the reference's answer under the [B, T, 3*H*D] contract."""
    monkeypatch.setattr(at, "_on_tpu", lambda: on_tpu)
    qkv, _cot = _inputs(1, 40, 2, 64)
    counters = {impl: get_registry().counter("dps_attention_core_total",
                                             impl=impl)
                for impl in at.ATTENTION_CORE_IMPLS}
    before = {impl: c.value for impl, c in counters.items()}
    fn = jax.jit(lambda x: at.attention_core(x, 2))
    out = fn(qkv)
    fn(qkv)  # a cached call traces nothing and counts nothing
    after = {impl: c.value - before[impl] for impl, c in counters.items()}
    assert after == {impl: float(impl == want)
                     for impl in at.ATTENTION_CORE_IMPLS}
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(reference(qkv, 2)), **TOL)


def test_default_vit_core_is_dense_core_off_tpu():
    """On CPU the default model computes exactly what it did when
    SelfAttention called dense_core itself."""
    from distributed_parameter_server_for_ml_training_tpu.models import (
        ViT_Tiny)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3))
    default = ViT_Tiny(num_classes=10)
    explicit = default.clone(attention_fn=at.dense_core)
    params = default.init(jax.random.PRNGKey(1), x, train=False)
    np.testing.assert_array_equal(
        np.asarray(default.apply(params, x, train=False)),
        np.asarray(explicit.apply(params, x, train=False)))
