"""Ring-attention (sequence parallelism) correctness tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_parameter_server_for_ml_training_tpu.parallel import (
    dense_attention, make_mesh, make_ring_attention)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    shape = (2, 64, 4, 16)  # [B, T, H, D], T sharded 8 ways -> 8 per shard
    q = jnp.asarray(rng.normal(size=shape), jnp.float32)
    k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    return q, k, v


def test_ring_equals_dense(devices, qkv):
    q, k, v = qkv
    mesh = make_mesh(8)
    ring = make_ring_attention(mesh, axis="data")
    out_ring = ring(q, k, v)
    out_dense = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_dense),
                               rtol=2e-5, atol=2e-5)


def test_ring_causal_equals_dense(devices, qkv):
    q, k, v = qkv
    mesh = make_mesh(8)
    ring = make_ring_attention(mesh, axis="data", causal=True)
    out_ring = ring(q, k, v)
    out_dense = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_dense),
                               rtol=2e-5, atol=2e-5)


def test_ring_bf16_inputs(devices, qkv):
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)
    mesh = make_mesh(8)
    out = make_ring_attention(mesh)(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32),
                               rtol=0.05, atol=0.05)


def test_ring_gradients_flow(devices, qkv):
    """Differentiability: ring attention must be trainable end-to-end."""
    q, k, v = qkv
    mesh = make_mesh(8)
    ring = make_ring_attention(mesh)

    def loss(q):
        return jnp.sum(ring(q, k, v) ** 2)

    g = jax.grad(loss)(q)
    assert g.shape == q.shape
    assert np.isfinite(np.asarray(g)).all()

    def loss_dense(q):
        return jnp.sum(dense_attention(q, k, v) ** 2)

    g_ref = jax.grad(loss_dense)(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_interpreted_kernels(devices, causal, monkeypatch):
    """Ring x flash with the ACTUAL Pallas kernels per hop (interpret
    mode): exercises the nonzero SMEM (q_offset, k_offset) scalars and
    the causal dynamic loop bounds the jnp-fallback hops never touch."""
    import distributed_parameter_server_for_ml_training_tpu.ops.pallas.flash_attention as fa
    from distributed_parameter_server_for_ml_training_tpu.parallel.ring_attention import (
        make_ring_flash_attention)
    from distributed_parameter_server_for_ml_training_tpu.parallel import make_mesh

    monkeypatch.setattr(fa, "INTERPRET", True)
    mesh = make_mesh(4)
    ring = make_ring_flash_attention(mesh, axis="data", causal=causal,
                                     use_pallas=True)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (1, 512, 2, 64), jnp.float32)
               for kk in ks)
    out = ring(q, k, v)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)
    cot = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    gr = jax.grad(lambda a, b, c: jnp.sum(ring(a, b, c) * cot),
                  argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda a, b, c: jnp.sum(
        dense_attention(a, b, c, causal=causal) * cot),
        argnums=(0, 1, 2))(q, k, v)
    for g1, g2, name in zip(gr, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=5e-3, rtol=5e-3,
                                   err_msg=f"d{name}")


def test_ring_flash_interpreted_kernels_two_blocks_a_hop(devices,
                                                         monkeypatch):
    """As above, causal, with two blocks a hop (1,024 tokens over 4 devices
    in 128-row blocks; ``pick_block`` would give a hop one block): a hop on
    the diagonal sums in one program over a wholly visible tile and a tile
    the diagonal crosses, and the loop bounds are computed from non-zero
    (q_offset, k_offset)."""
    import distributed_parameter_server_for_ml_training_tpu.ops.pallas.flash_attention as fa
    from distributed_parameter_server_for_ml_training_tpu.parallel.ring_attention import (
        make_ring_flash_attention)

    monkeypatch.setattr(fa, "INTERPRET", True)
    monkeypatch.setattr(fa, "MAX_BLOCK", 128)
    assert fa.pick_block(1024 // 4) == 128
    ring = make_ring_flash_attention(make_mesh(4), axis="data", causal=True,
                                     use_pallas=True)
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q, k, v, cot = (jax.random.normal(kk, (1, 1024, 2, 64), jnp.float32)
                    for kk in ks)
    np.testing.assert_allclose(
        np.asarray(ring(q, k, v)),
        np.asarray(dense_attention(q, k, v, causal=True)),
        atol=2e-3, rtol=2e-3)
    gr = jax.grad(lambda a, b, c: jnp.sum(ring(a, b, c) * cot),
                  argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda a, b, c: jnp.sum(
        dense_attention(a, b, c, causal=True) * cot),
        argnums=(0, 1, 2))(q, k, v)
    for g1, g2, name in zip(gr, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=5e-3, rtol=5e-3, err_msg=f"d{name}")


class TestRingFlash:
    """Ring x flash composition: flash kernels as the per-hop block core
    (CPU runs the identical-math jnp hop fallback; the Pallas hop path is
    validated on-chip)."""

    def test_matches_dense_fwd_and_grads(self, devices):
        from distributed_parameter_server_for_ml_training_tpu.parallel.ring_attention import (
            make_ring_flash_attention)

        mesh = make_mesh(4)
        ring = make_ring_flash_attention(mesh, axis="data")
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q, k, v = (jax.random.normal(kk, (2, 512, 2, 64), jnp.float32)
                   for kk in ks)
        out = ring(q, k, v)
        ref = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

        cot = jax.random.normal(jax.random.PRNGKey(9), q.shape)
        gr = jax.grad(lambda a, b, c: jnp.sum(ring(a, b, c) * cot),
                      argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(lambda a, b, c: jnp.sum(dense_attention(a, b, c)
                                              * cot),
                      argnums=(0, 1, 2))(q, k, v)
        for g1, g2, name in zip(gr, gd, "qkv"):
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       atol=1e-4, rtol=1e-4,
                                       err_msg=f"d{name}")

    def test_bf16(self, devices):
        from distributed_parameter_server_for_ml_training_tpu.parallel.ring_attention import (
            make_ring_flash_attention)

        mesh = make_mesh(2)
        ring = make_ring_flash_attention(mesh, axis="data")
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q, k, v = (jax.random.normal(kk, (1, 256, 2, 64), jnp.bfloat16)
                   for kk in ks)
        out = ring(q, k, v)
        assert out.dtype == jnp.bfloat16
        ref = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=0.05, atol=0.05)

    def test_causal_matches_dense(self, devices):
        """Causal ring x flash: hop-local kernels mask in GLOBAL positions
        (q offset = shard start, k offset = rotating block's home);
        all-future hops vanish through the lse merge."""
        from distributed_parameter_server_for_ml_training_tpu.parallel.ring_attention import (
            make_ring_flash_attention)

        mesh = make_mesh(4)
        ring = make_ring_flash_attention(mesh, axis="data", causal=True)
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        q, k, v = (jax.random.normal(kk, (2, 512, 2, 64), jnp.float32)
                   for kk in ks)
        out = ring(q, k, v)
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

        cot = jax.random.normal(jax.random.PRNGKey(9), q.shape)
        gr = jax.grad(lambda a, b, c: jnp.sum(ring(a, b, c) * cot),
                      argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(lambda a, b, c: jnp.sum(
            dense_attention(a, b, c, causal=True) * cot),
            argnums=(0, 1, 2))(q, k, v)
        for g1, g2, name in zip(gr, gd, "qkv"):
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       atol=1e-4, rtol=1e-4,
                                       err_msg=f"d{name}")
