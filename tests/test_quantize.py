"""int8 quantization kernel + quantized all-reduce tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_parameter_server_for_ml_training_tpu.ops.pallas.quantize import (
    BLOCK_ROWS, LANES, dequantize_int8, quantize_dequantize_int8,
    quantize_int8)


class TestQuantizeKernel:
    def test_roundtrip_error_bound(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(1000, 37)), jnp.float32)
        y = quantize_dequantize_int8(x)
        # per-block scale = absmax/127 -> error <= scale/2 per element
        err = np.abs(np.asarray(y - x))
        assert err.max() <= float(jnp.max(jnp.abs(x))) / 127.0

    def test_shapes_and_dtypes(self):
        x = jnp.ones((513,), jnp.float32)  # forces padding
        v, s = quantize_int8(x)
        assert v.dtype == jnp.int8 and v.shape[1] == LANES
        # small inputs stay one 32-row-aligned block (int8 native tile;
        # no 32768-element padding that would dominate ring-chunk bytes)
        assert v.shape == (32, LANES)
        assert s.shape == (1,)
        y = dequantize_int8(v, s, (513,))
        assert y.shape == (513,)
        np.testing.assert_allclose(np.asarray(y), 1.0, rtol=0.01)

    def test_shapes_large_input_tiles_in_blocks(self):
        n = 3 * BLOCK_ROWS * LANES + 5
        x = jnp.ones((n,), jnp.float32)
        v, s = quantize_int8(x)
        assert v.shape[0] % BLOCK_ROWS == 0
        assert s.shape == (v.shape[0] // BLOCK_ROWS,)
        y = dequantize_int8(v, s, (n,))
        np.testing.assert_allclose(np.asarray(y), 1.0, rtol=0.01)

    def test_zeros_safe(self):
        x = jnp.zeros((256,), jnp.float32)
        y = quantize_dequantize_int8(x)
        np.testing.assert_array_equal(np.asarray(y), 0.0)

    def test_empty_input_roundtrips(self):
        # Round-4 ADVICE: rows=0 divided by block_rows_for(0)=0.
        v, s = quantize_int8(jnp.zeros((0,), jnp.float32))
        assert v.shape == (0, 128) and s.shape == (0,)
        y = dequantize_int8(v, s, (0,))
        assert y.shape == (0,)
        assert quantize_dequantize_int8(jnp.zeros((0, 3))).shape == (0, 3)

    def test_preserves_extremes(self):
        x = jnp.asarray([127.0, -127.0, 0.0, 1.0], jnp.float32)
        y = np.asarray(quantize_dequantize_int8(x))
        np.testing.assert_allclose(y[:2], [127.0, -127.0], rtol=1e-6)

    def test_per_block_scales_isolate_outliers(self):
        """A huge value in one block must not destroy precision in others."""
        n = 2 * BLOCK_ROWS * LANES
        x = np.full(n, 0.01, np.float32)
        x[0] = 1000.0  # outlier in block 0 only
        y = np.asarray(quantize_dequantize_int8(jnp.asarray(x)))
        # block 1 keeps fine resolution
        np.testing.assert_allclose(y[BLOCK_ROWS * LANES:], 0.01, rtol=0.05)


class TestInt8Ring:
    """The quantized reduce-scatter + all-gather ring
    (parallel/sync_dp._int8_ring_allreduce_mean)."""

    def _ring_outputs(self, n, values):
        """Run the ring over an n-device mesh; returns [n, S] per-device
        results (out_specs stacks them) for replica-consistency checks."""
        from jax.sharding import PartitionSpec as P

        from distributed_parameter_server_for_ml_training_tpu.parallel import make_mesh
        from distributed_parameter_server_for_ml_training_tpu.parallel.sync_dp import (
            _int8_ring_allreduce_mean)

        mesh = make_mesh(n)

        def body(vals, key):
            # vals: [1, S] this device's gradient contribution
            out = _int8_ring_allreduce_mean(vals[0], "data", n, key[0])
            return out[None]

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P("data"), P("data")),
                           out_specs=P("data"), check_vma=False)
        keys = jax.random.split(jax.random.PRNGKey(7), n)
        return np.asarray(fn(values, keys))

    @pytest.mark.parametrize("n", [4, 8])
    def test_mean_and_replica_consistency(self, devices, n):
        rng = np.random.default_rng(0)
        size = 5000  # not divisible by n: exercises chunk padding
        vals = jnp.asarray(rng.normal(size=(n, size)), jnp.float32)
        outs = self._ring_outputs(n, vals)
        true_mean = np.asarray(vals).mean(axis=0)
        # every replica must hold BIT-IDENTICAL results (the all-gather
        # phase ships one quantization of each chunk to everyone)
        for d in range(1, n):
            np.testing.assert_array_equal(outs[d], outs[0])
        # and the mean must be close to exact (N-1 requantizations of
        # running partials + one of the mean)
        scale = np.abs(true_mean).max() / 127.0
        np.testing.assert_allclose(outs[0], true_mean,
                                   atol=(n + 1) * scale, rtol=0.05)

    def test_async_start_forms_counted_once(self):
        """Round-4 ADVICE: every '-start' op returns an (operand, result)
        tuple; bytes must come from the RESULT buffer only — the largest
        member for all-reduce/all-gather/permute, the SMALLEST for
        reduce-scatter (its result is 1/N of the operand)."""
        from distributed_parameter_server_for_ml_training_tpu.utils.hlo_bytes \
            import collective_wire_bytes

        n = 4
        hlo = "\n".join([
            # sync forms: result shape only
            "  x = f32[1024] all-reduce(f32[1024] a), replica_groups={}",
            "  y = f32[256] reduce-scatter(f32[1024] a), dimensions={0}",
            # async forms: (operand, result) tuples
            "  ars = (f32[1024], f32[1024]) all-reduce-start(f32[1024] a)",
            # context scalar (u32[]) must not be picked as the "result"
            "  rss = (f32[1024], f32[256], u32[]) "
            "reduce-scatter-start(f32[1024] a)",
            "  ags = (f32[256], f32[1024]) all-gather-start(f32[256] a)",
            "  cps = (f32[512], f32[512]) collective-permute-start(f32[512] a)",
        ])
        out = collective_wire_bytes(hlo, n)
        frac = (n - 1) / n
        # sync all-reduce == async all-reduce (same 1024-elem result)
        assert out["by_op"]["all-reduce"] == 2 * int(2 * frac * 1024 * 4)
        assert out["count"]["all-reduce"] == 2
        # sync rs == async rs: (N-1) x 256-elem result each
        assert out["by_op"]["reduce-scatter"] == 2 * (n - 1) * 256 * 4
        assert out["by_op"]["all-gather"] == int(frac * 1024 * 4)
        assert out["by_op"]["collective-permute"] == 512 * 4

    @pytest.mark.parametrize("n", [4, 8])
    def test_wire_bytes_below_bf16(self, devices, n):
        """VERDICT r3 item 2 'done' bar: int8 strictly below bf16 bytes at
        N>=4, measured from the compiled HLO's collective ops on an
        isolated gradient-sized all-reduce (no BN/metric psums mixed in);
        shared harness with experiments/measure_comm_bytes.py."""
        from distributed_parameter_server_for_ml_training_tpu.utils.hlo_bytes import (
            sync_grad_mean_bytes)

        size = 2 ** 20          # 1M-element gradient (4 MB fp32)
        stats = sync_grad_mean_bytes(n, size)

        # pmean must show the expected 2 (N-1)/N x S bytes (sanity of the
        # HLO parser itself)
        expect_none = 2 * (n - 1) / n * size * 4
        assert abs(stats["none"]["total"] - expect_none) < 0.1 * expect_none
        assert stats["int8"]["total"] < stats["bf16"]["total"], stats
        # and the ring should be ~half of bf16, not a marginal win
        assert stats["int8"]["total"] < 0.7 * stats["bf16"]["total"], stats


class TestWireQuantCodecs:
    """Property tests for the push wire codecs (ops/compression.py;
    ISSUE 6 satellite): round-trip error bounds per codec, shared-scale
    int32 accumulation vs dequantize-then-sum, and error-feedback
    convergence on a quadratic toy problem."""

    def _rand(self, shape, seed=0):
        return np.random.default_rng(seed).normal(size=shape) \
            .astype(np.float32)

    def test_int8_roundtrip_error_bound(self):
        from distributed_parameter_server_for_ml_training_tpu.ops.compression import (
            int8_dequantize, int8_quantize)
        x = self._rand((257, 3))
        q, s = int8_quantize(x)
        err = np.abs(int8_dequantize(q, s) - x)
        assert err.max() <= float(s) / 2 + 1e-7

    def test_int4_roundtrip_error_bound(self):
        from distributed_parameter_server_for_ml_training_tpu.ops.compression import (
            int4_dequantize, int4_quantize)
        x = self._rand((33, 7))  # odd element count exercises nibble pad
        packed, s = int4_quantize(x)
        y = int4_dequantize(packed, s)
        assert y.shape == x.shape
        # scale = absmax/7 -> half-step error bound per element
        assert np.abs(y - x).max() <= float(s) / 2 + 1e-7
        # symmetric levels: extremes survive exactly
        ext = np.asarray([7.0, -7.0, 0.0], np.float32)
        p2, s2 = int4_quantize(ext)
        np.testing.assert_allclose(int4_dequantize(p2, s2), ext, rtol=1e-6)

    def test_int4_wire_roundtrip(self):
        """PackedInt4 survives encode/decode (the wire's int4 dtype) and
        dequantizes from the zero-copy view."""
        from distributed_parameter_server_for_ml_training_tpu.comms import (
            wire)
        from distributed_parameter_server_for_ml_training_tpu.ops.compression import (
            compress_push, wire_decompress)
        x = {"a": self._rand((513,)), "b": self._rand((8, 9))}
        payload = compress_push(x, plan={"a": "int4", "b": "int4"})
        out = wire.decode_tensor_dict(wire.encode_tensor_dict(payload))
        dec = wire_decompress(out)
        for k in x:
            assert dec[k].shape == x[k].shape
            scale = float(payload[k + "::int4scale"][0])
            assert np.abs(dec[k] - x[k]).max() <= scale / 2 + 1e-7

    def test_topk_keeps_largest_and_bounds_error(self):
        from distributed_parameter_server_for_ml_training_tpu.ops.compression import (
            compress_push, wire_decompress)
        x = np.zeros(1000, np.float32)
        x[[3, 500, 999]] = [10.0, -20.0, 5.0]
        x += self._rand(1000, seed=1) * 0.01
        payload = compress_push({"g": x}, plan={"g": "topk"},
                                topk_frac=0.003)
        dec = wire_decompress(payload)["g"]
        assert np.count_nonzero(dec) == 3
        # the three spikes survive (to int8 resolution), noise is dropped
        np.testing.assert_allclose(dec[[3, 500, 999]], x[[3, 500, 999]],
                                   rtol=0.02, atol=0.2)

    def test_shared_scale_accumulate_matches_dequantize_then_sum(self):
        """The homomorphic path: int32 accumulation of shared-scale
        payloads must equal dequantize-then-mean within float rounding."""
        from distributed_parameter_server_for_ml_training_tpu.ops.compression import (
            compress_push, homomorphic_mean, wire_decompress)
        scales = {"w": 3.0, "v": 1.7}
        dicts = [compress_push(
            {"w": self._rand((64, 3), seed=i), "v": self._rand(129, seed=i + 9)},
            plan={"w": "int8", "v": "int4"}, scales=scales)
            for i in range(4)]
        got = homomorphic_mean(dicts)
        want = {k: np.mean([wire_decompress(d)[k] for d in dicts], axis=0)
                for k in ("w", "v")}
        for k in want:
            np.testing.assert_allclose(got[k], want[k], rtol=1e-5,
                                       atol=1e-6)

    def test_homomorphic_mean_mixed_scales_and_codecs(self):
        """Entries that DON'T share a scale (or aren't quantized at all)
        land in separate accumulator groups — same mean either way."""
        from distributed_parameter_server_for_ml_training_tpu.ops.compression import (
            compress_push, homomorphic_mean, wire_decompress)
        g0 = compress_push({"w": self._rand(200, seed=0)},
                           plan={"w": "int8"}, scales={"w": 2.0})
        g1 = compress_push({"w": self._rand(200, seed=1)},
                           plan={"w": "int8"})  # own per-push scale
        g2 = compress_push({"w": self._rand(200, seed=2)},
                           plan={"w": "topk"}, topk_frac=0.1)
        g3 = {"w": self._rand(200, seed=3)}  # dense fp32 (legacy worker)
        dicts = [g0, g1, g2, g3]
        got = homomorphic_mean(dicts)["w"]
        want = np.mean([wire_decompress(d)["w"] for d in dicts], axis=0)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_error_feedback_residual_converges_quadratic(self):
        """EF-SGD on f(x) = 0.5||x - t||^2 with top-k compression: with
        error feedback the iterates reach the optimum; without it the
        dropped coordinates stall (the classic EF property the int4/topk
        codecs rely on)."""
        from distributed_parameter_server_for_ml_training_tpu.ops.compression import (
            ErrorFeedback, compress_push, wire_decompress)
        rng = np.random.default_rng(0)
        target = rng.normal(size=16).astype(np.float32)

        def run(ef):
            # k=1 of 16 coordinates per step -> effective update delay of
            # ~16 steps; the EF stability bound wants lr·delay < 1.
            x = np.zeros(16, np.float32)
            for _ in range(600):
                g = x - target
                payload = compress_push({"x": g}, plan={"x": "topk"},
                                        ef=ef, topk_frac=0.07)  # k=1
                x = x - 0.05 * wire_decompress(payload)["x"]
            return float(np.abs(x - target).max())

        with_ef = run(ErrorFeedback())
        without_ef = run(None)
        assert with_ef < 1e-3, with_ef
        assert with_ef < without_ef

    def test_int4_nonfinite_raises(self):
        from distributed_parameter_server_for_ml_training_tpu.ops.compression import (
            int4_quantize)
        with pytest.raises(ValueError, match="non-finite"):
            int4_quantize(np.asarray([1.0, np.nan], np.float32))


class TestDeviceCodecBitIdentity:
    """The device codec's contract (ops/device_codec.py; ISSUE 14): for
    the same gradients, plan, shared scales, EF history, and topk_frac,
    the device-encoded payload is BYTE-FOR-BYTE what compress_push emits
    — keys, key order, dtypes, and frame bytes — so the server side
    cannot tell which codec a worker ran."""

    # Shapes chosen to hit every packing corner: odd flat lengths (nibble
    # pad), 1-element tensors, non-contiguous-in-rows 2D/4D, and a size
    # above the Pallas engagement floor's padding logic.
    SHAPES = [(7,), (1,), (33, 5), (257, 3), (4, 3, 3, 8), (1024,)]

    def _flat(self, seed=0, scale=1.0):
        rng = np.random.default_rng(seed)
        return {f"t{i}": (rng.normal(size=s) * scale).astype(np.float32)
                for i, s in enumerate(self.SHAPES)}

    def _assert_identical(self, dev: dict, ref: dict):
        from distributed_parameter_server_for_ml_training_tpu.comms import (
            wire)
        assert list(dev) == list(ref)  # key ORDER is part of the frame
        for k in ref:
            assert np.asarray(dev[k]).dtype == np.asarray(ref[k]).dtype, k
            np.testing.assert_array_equal(np.asarray(dev[k]),
                                          np.asarray(ref[k]), err_msg=k)
        assert wire.encode_tensor_dict(dict(dev)) \
            == wire.encode_tensor_dict(dict(ref))

    @pytest.mark.parametrize("kind", ["int8", "int4", "topk"])
    def test_wire_bytes_match_numpy_reference(self, kind):
        from distributed_parameter_server_for_ml_training_tpu.ops.compression import (
            compress_push)
        from distributed_parameter_server_for_ml_training_tpu.ops.device_codec import (
            DeviceCodec)
        flat = self._flat(seed=3)
        plan = {k: kind for k in flat}
        codec = DeviceCodec(error_feedback=False, use_pallas=False)
        dev = codec.encode_now(
            {k: jnp.asarray(v) for k, v in flat.items()}, plan=plan)
        ref = compress_push(dict(flat), plan=plan)
        self._assert_identical(dev, ref)

    def test_mixed_plan_and_shared_scales_match(self):
        from distributed_parameter_server_for_ml_training_tpu.ops.compression import (
            compress_push)
        from distributed_parameter_server_for_ml_training_tpu.ops.device_codec import (
            DeviceCodec)
        flat = self._flat(seed=11, scale=2.5)
        names = list(flat)
        plan = {names[0]: "none", names[1]: "int8", names[2]: "int4",
                names[3]: "int8", names[4]: "int4", names[5]: "topk"}
        # Server-published absmax table for a subset (the rest fall back
        # to per-push scales), including a degenerate 0 entry.
        scales = {names[1]: 3.25, names[2]: 0.0, names[4]: 1.125}
        codec = DeviceCodec(error_feedback=False, use_pallas=False)
        dev = codec.encode_now(
            {k: jnp.asarray(v) for k, v in flat.items()},
            plan=plan, scales=scales)
        ref = compress_push(dict(flat), plan=plan, scales=dict(scales))
        self._assert_identical(dev, ref)

    def test_error_feedback_residuals_track_numpy_over_pushes(self):
        """Multi-push sequence: EF residuals feed back into each encode,
        so a single-ulp drift anywhere would compound and break the byte
        match by push 2. Also pins the residual carry itself."""
        from distributed_parameter_server_for_ml_training_tpu.ops.compression import (
            ErrorFeedback, compress_push)
        from distributed_parameter_server_for_ml_training_tpu.ops.device_codec import (
            DeviceCodec)
        plan = {f"t{i}": k for i, k in enumerate(
            ["int8", "int4", "topk", "int8", "int4", "int8"])}
        ef = ErrorFeedback()
        codec = DeviceCodec(error_feedback=True, use_pallas=False)
        for push in range(4):
            flat = self._flat(seed=100 + push)
            dev = codec.encode_now(
                {k: jnp.asarray(v) for k, v in flat.items()}, plan=plan)
            ref = compress_push(dict(flat), plan=plan, ef=ef)
            self._assert_identical(dev, ref)
        for name, res in ef._residual.items():
            np.testing.assert_array_equal(
                np.asarray(codec._residual[name]), res,
                err_msg=f"EF residual diverged for {name}")

    def test_topk_frac_and_k_sizing_match(self):
        from distributed_parameter_server_for_ml_training_tpu.ops.compression import (
            compress_push)
        from distributed_parameter_server_for_ml_training_tpu.ops.device_codec import (
            DeviceCodec)
        x = {"g": np.random.default_rng(7).normal(size=1000)
             .astype(np.float32)}
        for frac in (0.003, 0.01, 0.25, 1.0):
            codec = DeviceCodec(error_feedback=False, topk_frac=frac,
                                use_pallas=False)
            dev = codec.encode_now({"g": jnp.asarray(x["g"])},
                                   plan={"g": "topk"})
            ref = compress_push(dict(x), plan={"g": "topk"},
                                topk_frac=frac)
            self._assert_identical(dev, ref)

    def test_server_aggregation_cannot_tell_codecs_apart(self):
        """homomorphic_mean over a mixed round (half the pushes device-
        encoded, half NumPy) equals the all-NumPy round exactly."""
        from distributed_parameter_server_for_ml_training_tpu.ops.compression import (
            compress_push, homomorphic_mean)
        from distributed_parameter_server_for_ml_training_tpu.ops.device_codec import (
            DeviceCodec)
        plan = {"w": "int8", "v": "int4"}
        scales = {"w": 2.0, "v": 1.5}
        rng = np.random.default_rng(5)
        grads = [{"w": rng.normal(size=(64, 3)).astype(np.float32),
                  "v": rng.normal(size=129).astype(np.float32)}
                 for _ in range(4)]
        codec = DeviceCodec(error_feedback=False, use_pallas=False)
        mixed = [
            codec.encode_now({k: jnp.asarray(v) for k, v in g.items()},
                             plan=plan, scales=scales)
            if i % 2 else compress_push(dict(g), plan=plan,
                                        scales=dict(scales))
            for i, g in enumerate(grads)]
        ref = [compress_push(dict(g), plan=plan, scales=dict(scales))
               for g in grads]
        got, want = homomorphic_mean(mixed), homomorphic_mean(ref)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])

    def test_nonfinite_raises_like_reference(self):
        from distributed_parameter_server_for_ml_training_tpu.ops.device_codec import (
            DeviceCodec)
        codec = DeviceCodec(error_feedback=False, use_pallas=False)
        bad = {"g": jnp.asarray([1.0, np.nan], jnp.float32)}
        with pytest.raises(ValueError, match="non-finite"):
            codec.encode_now(bad, plan={"g": "int8"})

    def test_is_device_tree_gates_the_fast_path(self):
        from distributed_parameter_server_for_ml_training_tpu.ops.device_codec import (
            is_device_tree)
        assert is_device_tree({"a": jnp.zeros(3)})
        assert not is_device_tree({"a": np.zeros(3)})
        assert not is_device_tree({"a": jnp.zeros(3), "b": np.zeros(3)})
        assert not is_device_tree({})


def test_int8_sync_allreduce_trains(devices, tiny_model):
    """compression='int8' end-to-end: the quantized all-reduce must stay
    close to fp32 for one step and still learn over a short run."""
    from distributed_parameter_server_for_ml_training_tpu.data import (
        make_batches, synthetic_cifar100)
    from distributed_parameter_server_for_ml_training_tpu.parallel import (
        make_mesh, make_sync_dp_step, shard_batch)
    from distributed_parameter_server_for_ml_training_tpu.train import (
        create_train_state, server_sgd)

    mesh = make_mesh(8)
    m = tiny_model(axis_name="data")

    # Fresh state per call: the sync-DP step donates its state argument.
    def st0():
        return create_train_state(m, jax.random.PRNGKey(0), server_sgd(0.1))

    rng = np.random.default_rng(3)
    images = rng.integers(0, 255, (32, 32, 32, 3), dtype=np.uint8)
    labels = (np.arange(32) % 10).astype(np.int32)
    bi, bl = shard_batch(mesh, (images, labels))

    exact, _ = make_sync_dp_step(mesh, compression="none", augment=False)(
        st0(), bi, bl, jax.random.PRNGKey(1))
    quant, _ = make_sync_dp_step(mesh, compression="int8", augment=False)(
        st0(), bi, bl, jax.random.PRNGKey(1))
    for a, b in zip(jax.tree_util.tree_leaves(exact.params),
                    jax.tree_util.tree_leaves(quant.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0.05, atol=1e-3)

    # short training run still learns
    d = synthetic_cifar100(n_train=512, n_test=64, num_classes=10, seed=5)
    step = make_sync_dp_step(mesh, compression="int8", augment=False)
    st = st0()
    losses = []
    for epoch in range(6):
        for xb, yb in make_batches(d.x_train, d.y_train, 64, seed=epoch):
            sb = shard_batch(mesh, (xb, yb))
            st, metrics = step(st, sb[0], sb[1], jax.random.PRNGKey(0))
            losses.append(float(metrics["loss"]))
    assert np.mean(losses[-4:]) < np.mean(losses[:4])
