"""Flash-attention kernel: parity with dense attention, fwd and bwd.

On the CPU suite these run the jnp fallback path (identical masked math).
The Pallas kernels themselves compile and run only on a TPU: their parity
with the dense reference, forward and backward, is asserted on the chip by
``chip_smoke.py``'s kernel phase (the former test_pallas_path_on_tpu).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_parameter_server_for_ml_training_tpu.ops.pallas.flash_attention import (
    flash_attention)
from distributed_parameter_server_for_ml_training_tpu.parallel.ring_attention import (
    dense_attention)


def _qkv(b, t, h, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, d), dtype) for k in ks)


@pytest.mark.parametrize("t", [64, 100, 128, 257])
def test_forward_matches_dense(t):
    q, k, v = _qkv(2, t, 3, 64)
    out = flash_attention(q, k, v, use_pallas=False)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_forward_bf16():
    q, k, v = _qkv(2, 96, 2, 64, jnp.bfloat16)
    out = flash_attention(q, k, v, use_pallas=False)
    ref = dense_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("t", [64, 100])
def test_gradients_match_dense(t):
    """Custom-VJP flash backward == autodiff through dense attention."""
    q, k, v = _qkv(1, t, 2, 64, seed=3)
    cot = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, use_pallas=False) * cot)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v) * cot)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name} mismatch")


def test_vit_attention_fn_contract():
    """flash_attention drops into models/vit.py:SelfAttention via
    attention_fn and produces the same logits as the default einsum core."""
    from functools import partial

    from distributed_parameter_server_for_ml_training_tpu.models.vit import ViT

    kw = dict(patch_size=4, hidden_dim=64, depth=2, num_heads=2,
              num_classes=10, dtype=jnp.float32)
    dense_vit = ViT(**kw)
    flash_vit = ViT(**kw, attention_fn=partial(flash_attention,
                                               use_pallas=False))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3))
    params = dense_vit.init(jax.random.PRNGKey(1), x, train=False)
    out_d = dense_vit.apply(params, x, train=False)
    out_f = flash_vit.apply(params, x, train=False)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_f),
                               atol=1e-4, rtol=1e-4)


def test_explicit_block_override_validated():
    """ADVICE r3: a non-128-multiple block override must raise a clear
    ValueError instead of an opaque Mosaic lowering error."""
    q, k, v = _qkv(1, 128, 1, 64)
    with pytest.raises(ValueError, match="block_q=100"):
        flash_attention(q, k, v, block_q=100)
    with pytest.raises(ValueError, match="block_k=-128"):
        flash_attention(q, k, v, block_k=-128)


@pytest.mark.parametrize("t,causal", [(197, False), (197, True), (300, True)])
def test_kernels_interpret_mode(t, causal, monkeypatch):
    """The ACTUAL Pallas kernels (loop bounds, SMEM scalars, padding
    masks) emulated on CPU via interpret mode — the only CPU-side check
    that exercises kernel code rather than the jnp fallback. Covers the
    padded final block (197->256) and the causal dynamic loop bounds."""
    import distributed_parameter_server_for_ml_training_tpu.ops.pallas.flash_attention as fa

    monkeypatch.setattr(fa, "INTERPRET", True)
    q, k, v = _qkv(2, t, 2, 64, seed=11)
    out = flash_attention(q, k, v, causal=causal, use_pallas=True)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)
    cot = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    g_p = jax.grad(lambda a, b, c: jnp.sum(
        flash_attention(a, b, c, causal=causal, use_pallas=True) * cot),
        argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(lambda a, b, c: jnp.sum(
        dense_attention(a, b, c, causal=causal) * cot),
        argnums=(0, 1, 2))(q, k, v)
    for gp, gd, name in zip(g_p, g_d, "qkv"):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gd),
                                   atol=5e-3, rtol=5e-3,
                                   err_msg=f"d{name} mismatch")


def _value_and_grads(fn, q, k, v, cot):
    return jax.value_and_grad(
        lambda a, b, c: jnp.sum(fn(a, b, c).astype(jnp.float32)
                                * cot.astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)


#: (window, query heads, key/value heads) of the cases before there were any
PLAIN = (None, 2, 2)


@pytest.mark.parametrize("door,dtype,t,causal,block_q,block_k,grouping", [
    ("bthd", jnp.float32, 512, True, 128, 128, PLAIN),
    ("bthd", jnp.float32, 512, False, 128, 128, PLAIN),
    ("bthd", jnp.float32, 600, True, 128, 128, PLAIN),    # padded to 640: the
    ("bthd", jnp.float32, 600, False, 128, 128, PLAIN),   # last K block is masked
    ("bthd", jnp.float32, 512, True, 256, 128, PLAIN),
    ("bthd", jnp.float32, 512, True, 128, 256, PLAIN),
    ("bthd", jnp.float32, 600, True, 256, 128, PLAIN),    # 768: a K block all pad
    ("bthd", jnp.float32, 600, False, 128, 256, PLAIN),
    ("bthd", jnp.bfloat16, 512, True, 128, 128, PLAIN),
    ("bthd", jnp.bfloat16, 600, False, 128, 128, PLAIN),
    ("heads_major", jnp.float32, 512, True, 128, 128, PLAIN),
    ("heads_major", jnp.bfloat16, 600, True, 128, 128, PLAIN),
    ("heads_major", jnp.bfloat16, 512, True, 256, 128, PLAIN),
    # a window x grouped queries. 256: whole blocks; 200 and 130: the band's
    # lower edge inside a block (at 130 no tile is wholly visible); each
    # program holds a band of its operand, not the sequence
    # (``_band_blocks``: 3 of 4 blocks at 512 tokens and a window of 200)
    ("bthd", jnp.float32, 512, True, 128, 128, (256, 4, 2)),
    ("bthd", jnp.float32, 512, True, 128, 128, (200, 4, 2)),
    ("heads_major", jnp.float32, 512, True, 128, 128, (200, 4, 1)),
    ("heads_major", jnp.bfloat16, 512, True, 128, 128, (200, 6, 2)),
    ("bthd", jnp.float32, 600, True, 128, 256, (200, 6, 2)),   # and padding
    ("bthd", jnp.float32, 768, True, 256, 128, (130, 2, 2)),   # window alone
    ("heads_major", jnp.float32, 640, True, 128, 128, (130, 4, 2)),
    ("heads_major", jnp.float32, 512, True, 128, 128, (None, 4, 2)),  # groups
    ("bthd", jnp.float32, 512, False, 128, 128, (None, 6, 3)),        # alone
])
def test_kernels_sum_over_several_tiles(door, dtype, t, causal, block_q,
                                        block_k, grouping, monkeypatch):
    """Several tiles a program (interpret mode; the cases above are one
    tile a program): every kernel sums into its VMEM scratch over wholly
    visible tiles, tiles the diagonal crosses and, at 600 tokens, a last K
    block that is partly padding; forward and all three gradients against
    plain float32 attention. ``heads_major`` is the decoder LM's door at
    its widths, q/k heads of 192 and v heads of 128."""
    import distributed_parameter_server_for_ml_training_tpu.ops.pallas.flash_attention as fa

    monkeypatch.setattr(fa, "INTERPRET", True)
    from distributed_parameter_server_for_ml_training_tpu.ops.attention import (
        dense_core)
    window, heads, kv_heads = grouping
    plan = fa.tile_plan(-(-t // block_q) * block_q, -(-t // block_k) * block_k,
                        t, block_q, block_k, causal, window)
    assert plan["unmasked"] or window is not None
    assert plan["masked"] or not (causal or t % 128)
    assert window is None or plan["below_band"]
    d, dv = (192, 128) if door == "heads_major" else (64, 64)
    ks = jax.random.split(jax.random.PRNGKey(t + block_q), 4)
    q = jax.random.normal(ks[0], (1, t, heads, d), dtype)
    k = jax.random.normal(ks[1], (1, t, kv_heads, d), dtype)
    v = jax.random.normal(ks[2], (1, t, kv_heads, dv), dtype)
    cot = jax.random.normal(ks[3], (1, t, heads, dv), dtype)
    extra = {} if window is None else {"window": window}

    def kernels(q, k, v):
        if door == "bthd":
            return flash_attention(q, k, v, causal=causal, use_pallas=True,
                                   block_q=block_q, block_k=block_k, **extra)
        return fa.flash_attention_heads_major(
            *_heads_major(q, k, v), causal=causal, block_q=block_q,
            block_k=block_k, **extra).reshape(cot.shape)

    def plain(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        if grouping == PLAIN:
            return _dense_f32(q, k, v, causal)
        # the CPU path's core with the same mask, in float32
        return dense_core(q, k, v, causal=causal, window=window)

    out = kernels(q, k, v)
    assert out.dtype == dtype and out.shape == cot.shape
    fwd_tol, bwd_tol = (2e-3, 5e-3) if dtype == jnp.float32 else (3e-2, 3e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(plain(q, k, v)),
                               atol=fwd_tol, rtol=fwd_tol)
    (_l, got), (_l2, want) = (_value_and_grads(fn, q, k, v, cot)
                              for fn in (kernels, plain))
    for g, w, name in zip(got, want, "qkv"):
        w = np.asarray(w, np.float32)
        # bf16: of the gradient's largest value, as chip_smoke.py measures
        tol = bwd_tol * (1.0 if dtype == jnp.float32 else np.abs(w).max())
        np.testing.assert_allclose(np.asarray(g, np.float32), w, atol=tol,
                                   rtol=bwd_tol, err_msg=f"d{name}")


# -- which tiles a program visits (tile_plan, dps_flash_tiles_total) ----------

def test_tile_plan_at_the_decoder_lms_shape():
    import distributed_parameter_server_for_ml_training_tpu.ops.pallas.flash_attention as fa

    assert fa.tile_plan(4096, 4096, 4096, 512, 512, True) == {
        "unmasked": 28, "masked": 8, "skipped": 28}
    assert fa.tile_plan(4096, 4096, 4096, 512, 512, False) == {
        "unmasked": 64, "masked": 0, "skipped": 0}
    # a window as long as the sequence skips nothing more
    assert fa.tile_plan(4096, 4096, 4096, 512, 512, True, 4096) == {
        "unmasked": 28, "masked": 8, "skipped": 28, "below_band": 0}


def test_tile_plan_for_a_band_at_the_window_models_shape():
    """16,384 causal tokens in 512-wide blocks: 528 tiles in the triangle;
    under a window of 4,096 a query block visits its own block, the 7 before
    it and the one the band's lower edge crosses: 36 + 24 x 9 = 252, with 24
    + 32 of them masked, and holds 9 of the 32 K blocks (dK/dV: 9 of the 32
    query blocks)."""
    import distributed_parameter_server_for_ml_training_tpu.ops.pallas.flash_attention as fa

    assert fa.tile_plan(16384, 16384, 16384, 512, 512, True) == {
        "unmasked": 496, "masked": 32, "skipped": 496}
    assert fa.tile_plan(16384, 16384, 16384, 512, 512, True, 4096) == {
        "unmasked": 196, "masked": 56, "skipped": 496, "below_band": 276}
    assert fa._band_blocks(32, 32, 512, 512, 4096, k_side=True) == 9
    assert fa._band_blocks(32, 32, 512, 512, 4096, k_side=False) == 9
    assert fa._band_blocks(32, 32, 512, 512, None, k_side=True) == 0
    # two positions more and a query block's first row sees into a tenth
    assert fa._band_blocks(32, 32, 512, 512, 4097, k_side=True) == 9
    assert fa._band_blocks(32, 32, 512, 512, 4098, k_side=True) == 10
    # at 4,096 tokens a window of 4,096 is the sequence: nothing is cut
    assert fa._band_blocks(8, 8, 512, 512, 4096, k_side=True) == 0


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t_pad,kv_len,block_q,block_k", [
    (512, 512, 128, 128), (640, 600, 128, 128), (768, 600, 256, 128),
    (768, 600, 128, 256), (1024, 1024, 256, 128), (1024, 1000, 128, 256),
    (384, 300, 128, 384), (1024, 520, 512, 256), (768, 512, 384, 256)])
def test_loop_bounds_agree_with_the_mask(t_pad, kv_len, block_q, block_k,
                                         causal):
    """Both kernels' loop bounds (the K-block loop of the forward and dQ,
    the query-block loop of dK/dV) against a count over the dense mask,
    offsets zero: a tile is skipped exactly when the mask keeps none of it,
    and counted unmasked exactly when it keeps all of it."""
    import distributed_parameter_server_for_ml_training_tpu.ops.pallas.flash_attention as fa

    n_q, n_k = t_pad // block_q, t_pad // block_k
    mask = np.broadcast_to(np.asarray(
        fa._position_mask(t_pad, t_pad, kv_len, causal, 0, 0)),
        (t_pad, t_pad)).reshape(n_q, block_q, n_k, block_k)
    every, some = mask.all((1, 3)), mask.any((1, 3))
    want = {"unmasked": int(every.sum()), "masked": int((some & ~every).sum()),
            "skipped": int((~some).sum())}
    assert fa.tile_plan(t_pad, t_pad, kv_len, block_q, block_k,
                        causal) == want
    # tile by tile
    n_full, hi = (np.broadcast_to(x, (n_q,)) for x in fa._k_ranges(
        np, np.arange(n_q), 0, n_k, block_q, block_k, kv_len, causal))
    lo, hi_q = (np.broadcast_to(x, (n_k,)) for x in fa._q_ranges(
        np, np.arange(n_k), 0, n_q, block_q, block_k, kv_len, t_pad, t_pad,
        causal))
    for i in range(n_q):
        for j in range(n_k):
            assert (j < hi[i]) == some[i, j] == (lo[j] <= i < hi_q[j])
            assert (j < n_full[i]) == every[i, j]


@pytest.mark.parametrize("window", [130, 200, 256, 300, 512])
@pytest.mark.parametrize("t_pad,kv_len,block_q,block_k", [
    (512, 512, 128, 128), (640, 600, 128, 128), (768, 600, 256, 128),
    (768, 600, 128, 256), (1024, 1024, 256, 128), (1024, 1000, 128, 256),
    (1024, 520, 512, 256), (768, 512, 384, 256)])
def test_loop_bounds_under_a_window_agree_with_the_mask(t_pad, kv_len,
                                                        block_q, block_k,
                                                        window):
    """As ``test_loop_bounds_agree_with_the_mask``, causal with a window:
    below the band a tile is skipped exactly when the mask keeps none of
    it, the four kinds of ``tile_plan`` are a count over the dense mask,
    and the blocks a program holds (``_held_k``, ``_held_q`` and
    ``_band_blocks``) cover every tile its loop visits."""
    import distributed_parameter_server_for_ml_training_tpu.ops.pallas.flash_attention as fa

    n_q, n_k = t_pad // block_q, t_pad // block_k
    mask = np.asarray(fa._position_mask(
        t_pad, t_pad, kv_len, True, 0, 0, window)).reshape(
            n_q, block_q, n_k, block_k)
    causal = np.asarray(fa._position_mask(
        t_pad, t_pad, kv_len, True, 0, 0)).reshape(n_q, block_q, n_k, block_k)
    every, some = mask.all((1, 3)), mask.any((1, 3))
    in_triangle = causal.any((1, 3))
    want = {"unmasked": int(every.sum()),
            "masked": int((some & ~every).sum()),
            "skipped": int((~in_triangle).sum()),
            "below_band": int((in_triangle & ~some).sum())}
    assert fa.tile_plan(t_pad, t_pad, kv_len, block_q, block_k, True,
                        window) == want
    _n_full, hi = (np.broadcast_to(x, (n_q,)) for x in fa._k_ranges(
        np, np.arange(n_q), 0, n_k, block_q, block_k, kv_len, True))
    lo, _full_lo = fa._k_band(np, np.arange(n_q), block_q, block_k, window)
    lo_q, hi_q = (np.broadcast_to(x, (n_k,)) for x in fa._q_ranges(
        np, np.arange(n_k), 0, n_q, block_q, block_k, kv_len, t_pad, t_pad,
        True))
    hi_q = np.clip(fa._q_band(np, np.arange(n_k), block_q, block_k, window),
                   lo_q, hi_q)
    band_k = fa._band_blocks(n_q, n_k, block_q, block_k, window, True) or n_k
    band_q = fa._band_blocks(n_k, n_q, block_k, block_q, window, False) or n_q
    for i in range(n_q):
        held = int(fa._held_k(i, block_q, block_k, n_k, band_k))
        for j in range(n_k):
            visited = min(lo[i], hi[i]) <= j < hi[i]
            assert visited == some[i, j] == (lo_q[j] <= i < hi_q[j]), (i, j)
            if visited:
                assert held <= j < held + band_k
                held_q = int(fa._held_q(j, block_q, block_k, n_q, band_q))
                assert held_q <= i < held_q + band_q


def test_tracing_latent_attention_counts_its_tiles(monkeypatch):
    """``dps_flash_tiles_total{kind}`` at trace time: the decoder LM's tiny
    latent attention at 1,024 bf16 tokens, told it is on a TPU, traces the
    three kernels over 4 (batch, head)s of 2 x 2 tiles each (1 unmasked, 2
    masked, 1 skipped), forward and backward."""
    from distributed_parameter_server_for_ml_training_tpu.models import joyai
    from distributed_parameter_server_for_ml_training_tpu.ops import (
        attention as at)
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        get_registry)

    monkeypatch.setattr(at, "_on_tpu", lambda: True)
    cfg = joyai.PRESETS["tiny"]
    mla = joyai.MLA(cfg, jnp.bfloat16)
    u = jax.ShapeDtypeStruct((1, 1024, cfg.hidden_size), jnp.bfloat16)
    params = jax.eval_shape(mla.init, jax.random.PRNGKey(0), u)
    kinds = {kind: get_registry().counter("dps_flash_tiles_total", kind=kind)
             for kind in ("unmasked", "masked", "skipped")}
    before = {kind: c.value for kind, c in kinds.items()}
    jax.eval_shape(jax.grad(lambda p, u: jnp.sum(
        mla.apply(p, u).astype(jnp.float32))), params, u)
    heads = cfg.num_attention_heads
    assert {kind: c.value - before[kind] for kind, c in kinds.items()} == {
        "unmasked": 3 * heads * 1, "masked": 3 * heads * 2,
        "skipped": 3 * heads * 1}


@pytest.mark.parametrize("t", [64, 100, 257])
def test_causal_forward_matches_dense(t):
    q, k, v = _qkv(2, t, 3, 64, seed=5)
    out = flash_attention(q, k, v, causal=True, use_pallas=False)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_causal_gradients_match_dense():
    q, k, v = _qkv(1, 100, 2, 64, seed=7)
    cot = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    g_f = jax.grad(lambda a, b, c: jnp.sum(
        flash_attention(a, b, c, causal=True, use_pallas=False) * cot),
        argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(lambda a, b, c: jnp.sum(
        dense_attention(a, b, c, causal=True) * cot),
        argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_f, g_d, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name} mismatch")


# -- v narrower than q and k (latent attention) -------------------------------

def _dense_f32(q, k, v, causal):
    """Softmax attention in float32, the scale from q's width."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        t = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s,
                      -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("use_pallas,t", [(True, 256), (True, 200),
                                          (False, 200)])
def test_v_may_have_a_width_of_its_own(use_pallas, t, monkeypatch):
    """q/k heads of 48 and v heads of 32 (the LM's are 192 and 128), causal:
    the kernels in interpret mode and the fallback, forward and all three
    gradients; o and dv have v's width."""
    import distributed_parameter_server_for_ml_training_tpu.ops.pallas.flash_attention as fa

    monkeypatch.setattr(fa, "INTERPRET", True)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k = (jax.random.normal(x, (2, t, 2, 48)) for x in ks[:2])
    v = jax.random.normal(ks[2], (2, t, 2, 32))
    cot = jax.random.normal(ks[3], v.shape)

    def run(fn):
        return jax.value_and_grad(
            lambda a, b, c: jnp.sum(fn(a, b, c) * cot),
            argnums=(0, 1, 2))(q, k, v)

    out = flash_attention(q, k, v, causal=True, use_pallas=use_pallas)
    assert out.shape == v.shape
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense_f32(q, k, v, True)),
                               atol=2e-3, rtol=2e-3)
    (_l, got), (_l2, want) = (
        run(lambda a, b, c: flash_attention(a, b, c, causal=True,
                                            use_pallas=use_pallas)),
        run(lambda a, b, c: _dense_f32(a, b, c, True)))
    for g, w, name in zip(got, want, "qkv"):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-3,
                                   rtol=5e-3, err_msg=f"d{name}")


@pytest.mark.parametrize("on_tpu,dtype,t,causal,dv,want", [
    (True, jnp.bfloat16, 4096, True, 128, "flash"),     # the LM's cell
    (True, jnp.bfloat16, 4096, False, 192, "flash"),
    (True, jnp.bfloat16, 1024, True, 128, "flash"),
    (True, jnp.bfloat16, 1000, True, 128, "dense"),     # not whole tiles
    (True, jnp.bfloat16, 512, True, 128, "dense"),      # short and causal
    (True, jnp.float32, 4096, True, 128, "dense"),      # an fp32 model
    (False, jnp.bfloat16, 4096, True, 128, "dense"),    # every CPU run
    (True, jnp.bfloat16, 197, False, 32, "dense"),      # v narrower: not
])                                                      # the fused kernel
def test_select_core_sends_long_bf16_sequences_to_flash(on_tpu, dtype, t,
                                                        causal, dv, want):
    from distributed_parameter_server_for_ml_training_tpu.ops import (
        attention as at)
    assert at.select_core(on_tpu=on_tpu, causal=causal, dtype=dtype, t=t,
                          num_heads=32, head_dim=64 if dv == 32 else 192,
                          v_head_dim=dv) == want


@pytest.mark.parametrize("on_tpu,t,want", [
    (False, 1024, "dense"),     # every CPU run, whatever the length
    (True, 576, "dense"),       # under FLASH_MIN_T
    (True, 1024, "flash"),
    (True, 2040, "dense"),      # not whole tiles
])
def test_the_bthd_door_asks_the_same_rule(on_tpu, t, want, monkeypatch):
    """``flash_attention(use_pallas=None)`` takes what ``select_core`` says
    for its shapes (``fused_short`` cannot be held under ``[B, T, H, D]``
    and reads ``dense``): bitwise ``dense_core``, or the kernels' path."""
    from distributed_parameter_server_for_ml_training_tpu.ops import (
        attention as at)
    from distributed_parameter_server_for_ml_training_tpu.ops.pallas import (
        flash_attention as fa)
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        get_registry)

    q, k, v = _qkv(1, t, 2, 64, jnp.bfloat16)
    assert at.select_core(on_tpu=on_tpu, causal=False, dtype=q.dtype, t=t,
                          num_heads=2, head_dim=64) == want
    kernels = []

    def heads_first(q3, k3, v3, block_q, block_k, use_pallas, causal,
                    window=None):
        kernels.append(use_pallas)
        return jnp.zeros_like(q3)

    monkeypatch.setattr(at, "_on_tpu", lambda: on_tpu)
    monkeypatch.setattr(fa, "_flash_heads_first", heads_first)
    counted = get_registry().counter("dps_attention_core_total", impl=want)
    before = counted.value
    out = flash_attention(q, k, v)
    assert counted.value == before + 1
    if want == "flash":
        assert kernels == [True]
    else:
        assert kernels == []
        np.testing.assert_array_equal(
            np.asarray(out, np.float32),
            np.asarray(at.dense_core(q, k, v), np.float32))


def _heads_major(q, k, v):
    """``[B, T, H, D]`` x3 as the heads-major entry takes them: q and k
    ``[B, H, T, D]``, v ``[B, T, H*Dv]``."""
    b, t, h, dv = v.shape
    return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.reshape(b, t, h * dv))


@pytest.mark.parametrize("use_pallas,t,dv", [
    (False, 200, 32),    # the jnp fallback: bitwise
    (False, 256, 128),
    (True, 256, 128),    # the kernels, v/o/dO/dv a head's lanes of [B,T,H*Dv]
    (True, 256, 32),     # Dv not whole lane tiles: split into heads first
])
def test_heads_major_entry_equals_the_bthd_entry(use_pallas, t, dv,
                                                 monkeypatch):
    """``flash_attention_heads_major`` (q, k ``[B, H, T, D]``; v, o ``[B,
    T, H*Dv]``) against ``flash_attention`` on the same numbers as ``[B,
    T, H, D]``: forward and all three gradients, causal, v narrower than q
    and k. Both reach the same core, so the fallback is equal bit for bit;
    through the kernels (interpret mode) the v side is read by other block
    specs."""
    import distributed_parameter_server_for_ml_training_tpu.ops.pallas.flash_attention as fa

    monkeypatch.setattr(fa, "INTERPRET", True)
    b, h, d = 2, 2, 48
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k = (jax.random.normal(x, (b, t, h, d)) for x in ks[:2])
    v = jax.random.normal(ks[2], (b, t, h, dv))
    cot = jax.random.normal(ks[3], v.shape)

    def bthd(q, k, v):
        return flash_attention(q, k, v, causal=True, use_pallas=use_pallas)

    def heads_major(q, k, v):
        o = fa.flash_attention_heads_major(*_heads_major(q, k, v),
                                           causal=True,
                                           use_pallas=use_pallas)
        assert o.shape == (b, t, h * dv)
        return o.reshape(b, t, h, dv)

    want, got = (jax.value_and_grad(
        lambda a, b_, c: jnp.sum(fn(a, b_, c) * cot),
        argnums=(0, 1, 2))(q, k, v) for fn in (bthd, heads_major))
    np.testing.assert_array_equal(np.asarray(heads_major(q, k, v)),
                                  np.asarray(bthd(q, k, v)))
    for g, w, name in zip(got[1], want[1], "qkv"):
        if use_pallas:     # dv sums dO blocks fetched in another order
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-5, rtol=1e-5,
                                       err_msg=f"d{name}")
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=f"d{name}")


def test_heads_attention_core_counts_and_computes(monkeypatch):
    from distributed_parameter_server_for_ml_training_tpu.ops import (
        attention as at)
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        get_registry)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k = (jax.random.normal(x, (1, 40, 2, 24)) for x in ks[:2])
    v = jax.random.normal(ks[2], (1, 40, 2, 16))
    counter = get_registry().counter("dps_attention_core_total",
                                     impl="dense")
    before = counter.value
    out = jax.jit(lambda a, b, c: at.heads_attention_core(
        a, b, c, causal=True))(*_heads_major(q, k, v))
    assert counter.value == before + 1
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(_dense_f32(q, k, v, True)).reshape(1, 40, 2 * 16),
        atol=1e-5)


# -- a window, grouped queries: the rule, the CPU core, the counters -----------

@pytest.mark.parametrize("t,causal,window,kv_heads,want", [
    (16384, True, 4096, 4, "flash"),      # the window model's window layers
    (16384, True, None, 4, "flash"),      # and its global layer
    (4096, True, 4096, 28, "flash"),
    (197, False, None, 12, "fused_short"),
    (197, False, None, 4, "dense"),       # the short kernel has no groups
    (1000, True, 512, 4, "dense"),        # not whole tiles
])
def test_select_core_takes_the_window_and_the_key_value_heads(t, causal,
                                                              window,
                                                              kv_heads, want):
    from distributed_parameter_server_for_ml_training_tpu.ops import (
        attention as at)
    heads, width = (12, 64) if t == 197 else (28, 128)
    assert at.select_core(on_tpu=True, causal=causal, dtype=jnp.bfloat16,
                          t=t, num_heads=heads, head_dim=width,
                          window=window, num_kv_heads=kv_heads) == want
    assert at.select_core(on_tpu=False, causal=causal, dtype=jnp.bfloat16,
                          t=t, num_heads=heads, head_dim=width,
                          window=window, num_kv_heads=kv_heads) == "dense"


@pytest.mark.parametrize("heads_major", [False, True])
@pytest.mark.parametrize("window,kv_heads", [(None, 2), (5, 4), (5, 2),
                                             (1, 1), (40, 2)])
def test_dense_core_masks_the_band_and_repeats_the_key_value_heads(
        window, kv_heads, heads_major):
    """``dense_core`` against a loop over rows: row ``i`` of query head
    ``h`` is a softmax over columns ``i - window < j <= i`` of key/value
    head ``h // (H/G)``."""
    from distributed_parameter_server_for_ml_training_tpu.ops import (
        attention as at)
    t, heads, d = 12, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(window or 0), 3)
    q = jax.random.normal(ks[0], (1, t, heads, d))
    k, v = (jax.random.normal(x, (1, t, kv_heads, d)) for x in ks[1:])
    args = [x.transpose(0, 2, 1, 3) for x in (q, k, v)] if heads_major \
        else [q, k, v]
    out = at.dense_core(*args, causal=True, window=window,
                        heads_major=heads_major)
    out = out.transpose(0, 2, 1, 3) if heads_major else out
    for h in range(heads):
        g = h // (heads // kv_heads)
        for i in range(t):
            first = 0 if window is None else max(0, i - window + 1)
            s = (k[0, first:i + 1, g] @ q[0, i, h]) / np.sqrt(d)
            want = jax.nn.softmax(s) @ v[0, first:i + 1, g]
            np.testing.assert_allclose(np.asarray(out[0, i, h]),
                                       np.asarray(want), atol=1e-5)
    with pytest.raises(ValueError, match="causal"):
        at.dense_core(*args, causal=False, window=4, heads_major=heads_major)


def test_a_window_needs_causal_attention_and_static_offsets():
    import distributed_parameter_server_for_ml_training_tpu.ops.pallas.flash_attention as fa
    q, k, v = _qkv(1, 128, 1, 64)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, window=64, use_pallas=False)
    q3 = q.reshape(1, 128, 64)
    with pytest.raises(ValueError, match="window"):
        fa._flash_fwd_impl(q3, q3, q3, 128, 128, 128, False, causal=True,
                           q_offset=jnp.int32(0), window=64)
    out = flash_attention(q, k, v, causal=True, window=64, use_pallas=False)
    from distributed_parameter_server_for_ml_training_tpu.ops.attention import (
        dense_core)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_core(q, k, v, causal=True,
                                               window=64)), atol=1e-5)


def test_tracing_window_attention_counts_its_core_and_its_tiles(monkeypatch):
    """The window model's tiny attention at 1,024 bf16 tokens, told it is
    on a TPU: a window layer (window 300) and the global layer each trace
    the three kernels over 4 query heads of 2 x 2 tiles (no tile lies
    wholly below a band of 300, but its lower edge crosses the one tile
    under the diagonal, which is then masked), counted under ``impl=flash``
    with the layer's ``window`` and ``group``."""
    from distributed_parameter_server_for_ml_training_tpu.models import (
        smallthinker)
    from distributed_parameter_server_for_ml_training_tpu.ops import (
        attention as at)
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        get_registry)
    from dataclasses import replace

    monkeypatch.setattr(at, "_on_tpu", lambda: True)
    cfg = replace(smallthinker.PRESETS["tiny"], sliding_window_size=300,
                  head_dim=128)
    reg = get_registry()
    kinds = {kind: reg.counter("dps_flash_tiles_total", kind=kind)
             for kind in ("unmasked", "masked", "skipped", "below_band")}
    cores = {layer: reg.counter("dps_attention_core_total", impl="flash",
                                group="2", **extra)
             for layer, extra in ((0, {}), (1, {"window": "300"}))}
    u = jax.ShapeDtypeStruct((1, 1024, cfg.hidden_size), jnp.bfloat16)
    for layer in (0, 1):
        attn = smallthinker.GroupedAttention(
            cfg, jnp.bfloat16, rope=bool(cfg.rope_layout[layer]),
            window=cfg.window(layer))
        params = jax.eval_shape(attn.init, jax.random.PRNGKey(0), u)
        before = {kind: c.value for kind, c in kinds.items()}
        traced = cores[layer].value
        jax.eval_shape(jax.grad(lambda p, u: jnp.sum(
            attn.apply(p, u).astype(jnp.float32))), params, u)
        assert cores[layer].value == traced + 1
        heads = cfg.num_attention_heads
        got = {kind: c.value - before[kind] for kind, c in kinds.items()}
        under = 0 if layer else 1       # the tile under the diagonal
        assert got == {"unmasked": 3 * heads * under,
                       "masked": 3 * heads * (3 - under),
                       "skipped": 3 * heads * 1, "below_band": 0}
