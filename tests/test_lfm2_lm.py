"""The LFM2 decoder (models/lfm2.py) against its plain reference
(benchmarks/reference/lfm2_reference.py) at a small size on the CPU, and what
it asked of the pieces it brings and shares: the gated short convolution and
``causal_conv1d`` (ops/ssm.py), the window model's attention module with its
q/k norms on, the flash kernels at a head width of 64, the sigmoid router,
the LM task through ``SyncTrainer`` and ``cli train``.

Small size (the ``tiny`` preset): 5 layers read from the published
``layer_types`` (conv, conv, full_attention, conv, conv), the first dense, 4
query heads on 2 key/value heads of 16, 16 experts of 32 with 4 a token,
convolution 3, vocabulary 512, 40 tokens.
"""

import ast
import importlib.util
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_parameter_server_for_ml_training_tpu.models import (
    decoder, get_model, lfm2)
from distributed_parameter_server_for_ml_training_tpu.models.registry import (
    MODEL_NAMES, family_of, lm_config, lm_config_from_file)
from distributed_parameter_server_for_ml_training_tpu.ops import ssm
from distributed_parameter_server_for_ml_training_tpu.parallel import moe

HERE = os.path.dirname(os.path.abspath(__file__))
REFERENCE = os.path.join(HERE, "..", "benchmarks", "reference",
                         "lfm2_reference.py")
TINY = lfm2.PRESETS["tiny"]
B, T = 2, 40


def _load_reference():
    spec = importlib.util.spec_from_file_location("lfm2_reference",
                                                  REFERENCE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()


def _setup(cfg=TINY, dtype=jnp.float32, seed=0, init_std=0.08):
    """Seeded weights (wider than the model's 0.02 so that every path
    carries signal at this depth; the norms' gains moved off 1 so that a
    gain in the wrong place shows) and one batch."""
    cfg = replace(cfg, init_std=init_std)
    model = get_model("lfm2_moe", dtype=dtype, config=cfg)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (B, T + 2)), jnp.int32)
    bias = jnp.asarray(np.random.default_rng(seed + 1).normal(
        size=(cfg.expert_layers, cfg.num_experts)) * 0.05, jnp.float32)
    params = model.init(jax.random.PRNGKey(seed), tokens[:1, :10],
                        bias)["params"]
    r = np.random.default_rng(seed + 2)
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.asarray(r.uniform(0.5, 1.5, a.shape), a.dtype)
        if "scale" in jax.tree_util.keystr(path) else a, params)
    return cfg, model, params, bias, tokens


def _worst(grads, want):
    """The largest over the tensors of max |a - b| over the tensor's
    largest |b|, and where."""
    rows = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30)),
        grads, want)
    path, value = max(jax.tree_util.tree_leaves_with_path(rows),
                      key=lambda kv: kv[1])
    return value, jax.tree_util.keystr(path)


#: float32 program against the float32 reference: the same terms summed in
#: other orders (sorted groups against a loop over experts, a chunked loss,
#: fused norms, RoPE on halves against rotate_half): 1e-6 to 1e-5 of a
#: tensor's largest value; 1e-4 leaves ten times that and is a hundred
#: times under what bf16 compute gives.
TIGHT = 1e-4


@pytest.fixture(scope="module")
def f32_case():
    cfg, model, params, bias, tokens = _setup()
    want = ref.loss_and_grads(params, bias, tokens, cfg)
    return cfg, model, params, bias, tokens, want


def _activations(seed, scale=1.0, width=None):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(1, T, width or TINY.hidden_size)) * scale, jnp.float32)


# -- the whole step ---------------------------------------------------------------

def test_float32_step_matches_the_reference(f32_case):
    """Loss, every gradient, the loads."""
    cfg, model, params, bias, tokens, (want_loss, aux, want) = f32_case

    def loss_fn(p):
        out = model.apply({"params": p}, tokens, bias)
        return out["loss"], out
    (loss, out), grads = jax.jit(jax.value_and_grad(
        loss_fn, has_aux=True))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    np.testing.assert_array_equal(np.asarray(out["loads"]),
                                  np.asarray(aux["loads"]))
    assert out["loads"].shape == (4, 16)
    assert int(out["processed"]) == B * T * 4 * 4      # all held, none lost
    worst, where = _worst(grads, want)
    assert worst < TIGHT, (worst, where)
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(want)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.abs(g).max()) > 0, jax.tree_util.keystr(path)
    # and bf16 compute lies outside the tight tolerance
    low = get_model("lfm2_moe", dtype=jnp.bfloat16, config=cfg)
    grads_bf16 = jax.jit(jax.grad(lambda p: low.apply(
        {"params": p}, tokens, bias)["loss"]))(params)
    assert _worst(grads_bf16, want)[0] > TIGHT * 10


def test_logits_at_sampled_positions(f32_case):
    cfg, model, params, bias, tokens, _want = f32_case
    positions = jnp.asarray([0, 7, T - 1])
    got = model.apply({"params": params}, tokens, bias, positions,
                      method="logits_at")
    want = ref.logits_at(params, bias, tokens, cfg, positions)
    assert len(got) == len(want) == 1
    assert got[0].shape == (B, 3, cfg.vocab_size)
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[0]),
        atol=1e-4 * float(jnp.max(jnp.abs(want[0]))))


@pytest.mark.parametrize("case", ["bf16", "blocked"])
def test_the_reference_in_bf16_is_not_the_reference_and_blocking_is(
        f32_case, monkeypatch, case):
    """The reference computed in bf16 is far from it; computed a row at a
    time, two heads and 8 queries a block, recomputed, it is itself."""
    cfg, _model, params, bias, tokens, (want_loss, _aux, want) = f32_case
    if case == "bf16":
        _l, _a, low = ref.loss_and_grads(params, bias, tokens, cfg,
                                         dtype=jnp.bfloat16)
        low = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), low)
        assert _worst(low, want)[0] > TIGHT * 10
        return
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    loss, _a, grads = ref.loss_and_grads(
        params, bias, tokens, cfg, rows_per_block=1, remat=True,
        head_block=2)
    assert abs(float(loss) - float(want_loss)) < 1e-6
    assert _worst(grads, want)[0] < 1e-5


# -- the pieces -------------------------------------------------------------------

def test_the_gated_short_convolution_against_a_token_by_token_loop():
    """``y_t = C_t * sum_k w[k] B_{t-2+k} x_{t-2+k}``, one token at a time
    with a window of the last three gated inputs; forward and gradients;
    and the counter says which implementation a program holds."""
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        get_registry)
    r = np.random.default_rng(0)
    d = 6
    bcx = jnp.asarray(r.normal(size=(2, 12, 3 * d)), jnp.float32)
    kernel = jnp.asarray(r.normal(size=(3, d)), jnp.float32)
    w = jnp.asarray(r.normal(size=(2, 12, d)), jnp.float32)

    def loop(bcx, kernel):
        b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
        window = jnp.zeros((2, 3, d))
        out = []
        for t in range(12):
            window = jnp.concatenate([window[:, 1:], (b * x)[:, t:t + 1]],
                                     axis=1)
            out.append(c[:, t] * jnp.sum(window * kernel[None], axis=1))
        return jnp.stack(out, axis=1)

    counted = get_registry().counter("dps_short_conv_total",
                                     impl=ssm.SHORT_CONV_IMPLS[0])
    before = counted.value
    got = ssm.short_conv(bcx, kernel)
    assert counted.value == before + 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(loop(bcx, kernel)),
                               atol=1e-5)
    grads = [jax.grad(lambda a, k: jnp.sum(fn(a, k) * w), argnums=(0, 1))(
        bcx, kernel) for fn in (ssm.short_conv, loop)]
    for g, want in zip(*grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   atol=1e-5)
    # token t reads nothing after t, channel c nothing of another channel
    moved = ssm.short_conv(bcx.at[:, 7, 2].add(1.0), kernel) - got
    assert not np.asarray(moved[:, :7]).any()
    assert not np.asarray(moved[..., [0, 1, 3, 4, 5]]).any()
    assert np.asarray(moved[:, 7:10, 2]).all()
    assert not np.asarray(moved[:, 10:]).any()


def test_causal_conv1d_with_four_taps_and_a_bias_gives_what_it_gave():
    """Mamba-2's use (models/nemotron_h.py): ``K`` = 4 and a bias, as
    before the bias became optional; without one it is the same sum less
    the bias."""
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(2, 12, 6)), jnp.float32)
    kernel = jnp.asarray(r.normal(size=(4, 6)), jnp.float32)
    bias = jnp.asarray(r.normal(size=(6,)), jnp.float32)
    want = np.zeros((2, 12, 6), np.float32) + np.asarray(bias)
    for t in range(12):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += np.asarray(kernel[k]) * np.asarray(
                    x[:, t - 3 + k])
    np.testing.assert_allclose(np.asarray(ssm.causal_conv1d(x, kernel, bias)),
                               want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ssm.causal_conv1d(x, kernel)),
                               want - np.asarray(bias), atol=1e-5)


@pytest.mark.parametrize("layer", ["layer_0", "layer_2"])
def test_a_mixer_against_the_reference(f32_case, layer):
    """The conv mixer (layer 0) and the attention layer with its q/k norms
    and RoPE (layer 2): output and every gradient, of the input and of
    each parameter."""
    cfg, _model, params, _bias, _tokens, _want = f32_case
    p, u, w = params[layer], _activations(1), _activations(2)
    if layer == "layer_0":
        p = p["conv"]
        module = lfm2.ShortConv(cfg, jnp.float32)

        def reference(p, u):
            return jnp.sum(ref.short_conv(p, u[0], cfg) * w[0])
    else:
        p = p["self_attn"]
        module = decoder.GroupedAttention(
            cfg, jnp.float32, rope=True, window=None, qk_norm=cfg.norm_eps)

        def reference(p, u):
            return jnp.sum(ref.attention(p, u[0], cfg, None) * w[0])

    def program(p, u):
        return jnp.sum(module.apply({"params": p}, u) * w)

    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1)))(p, u)
        want, want_grads = jax.jit(jax.value_and_grad(
            reference, argnums=(0, 1)))(p, u)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    worst, where = _worst(got_grads, want_grads)
    assert worst < TIGHT, (worst, where)


def test_the_router_is_the_published_formula_and_the_bias_breaks_ties():
    """``sigmoid(u W_r)``, the top 4 of ``s + bias``, weights ``s`` over
    their sum (``norm_topk_prob``) times ``routed_scaling_factor``, as
    transformers' ``Lfm2MoeSparseMoeBlock`` (less its 1e-6 in the sum: the
    reference's docstring). Experts 5 and 9 tie for the fourth place in
    every row: the bias decides which is chosen, and the weight is the
    score, not the score plus the bias."""
    r = np.random.default_rng(4)
    logits = -1.0 - np.abs(r.normal(size=(T, 16))).astype(np.float32)
    logits[:, :3] = r.uniform(2.0, 4.0, size=(T, 3))
    logits[:, 5] = logits[:, 9] = 1.0
    scores = jax.nn.sigmoid(jnp.asarray(logits))
    s = np.asarray(scores)
    for winner, loser in ((5, 9), (9, 5)):
        bias = np.zeros(16, np.float32)
        bias[winner] = 1e-3
        idx, weights = moe.route_top_k(scores, jnp.asarray(bias), 4,
                                       scaling=TINY.routed_scaling_factor,
                                       normalize=TINY.norm_topk_prob)
        idx, weights = np.asarray(idx), np.asarray(weights)
        for t in range(T):
            assert set(idx[t]) == {0, 1, 2, winner}
            want = s[t, idx[t]] / s[t, idx[t]].sum()
            np.testing.assert_allclose(weights[t], want, rtol=1e-6)
        assert not (idx == loser).any()
    # the scaling multiplies the weights after the normalisation
    _idx, scaled = moe.route_top_k(scores, jnp.zeros(16), 4, scaling=2.5)
    np.testing.assert_allclose(np.asarray(scaled).sum(axis=1), 2.5,
                               rtol=1e-6)


def test_four_shares_of_eight_experts_add_up_to_the_whole_layer():
    """32 experts in the shares ``(0,8) (8,8) (16,8) (24,8)``, the cut's
    (8 of 32 a chip): each share routes over all 32 and computes its own
    experts' part, the reference given the same share gives the same part,
    and the four add up to the uncut reference's layer."""
    cfg = replace(TINY, num_experts=32, held_experts=(0, 32), init_std=0.08)
    u = _activations(5, 2.0)
    bias = jnp.asarray(np.random.default_rng(6).normal(size=32) * 0.05,
                       jnp.float32)
    p = decoder.ExpertLayer(cfg, jnp.float32).init(
        jax.random.PRNGKey(3), u, bias)["params"]
    with jax.default_matmul_precision("highest"):
        whole, loads = ref.expert_layer(p, u[0], bias, cfg)
        total = jnp.zeros_like(whole)
        for first in (0, 8, 16, 24):
            share = replace(cfg, held_experts=(first, 8))
            mine = dict(p, **{f"experts_{n}": p[f"experts_{n}"][first:
                                                                 first + 8]
                              for n in ("gate", "up", "down")})
            y, share_loads, processed = decoder.ExpertLayer(
                share, jnp.float32).apply({"params": mine}, u, bias)
            np.testing.assert_array_equal(np.asarray(share_loads),
                                          np.asarray(loads))
            assert int(processed) == int(loads[first:first + 8].sum())
            part, _ = ref.expert_layer(mine, u[0], bias, share)
            np.testing.assert_allclose(np.asarray(y[0]), np.asarray(part),
                                       atol=2e-5)
            total = total + y[0]
    assert int(loads.sum()) == T * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5)


def test_the_flash_kernels_take_heads_of_64(monkeypatch):
    """The three kernels (interpret mode) at D = 64, causal, 4 query heads
    on 2 key/value heads, as the attention layer hands them over (q, k
    heads-major, v ``[B, T, G*64]``, o ``[B, T, H*64]``), against
    ``dense_core``: forward and all three gradients."""
    import distributed_parameter_server_for_ml_training_tpu.ops.pallas \
        .flash_attention as fa
    from distributed_parameter_server_for_ml_training_tpu.ops import (
        attention as at)
    monkeypatch.setattr(fa, "INTERPRET", True)
    b, h, g, t, d = 1, 4, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (b, h, t, d))
    k = jax.random.normal(ks[1], (b, g, t, d))
    v = jax.random.normal(ks[2], (b, t, g * d))
    cot = jax.random.normal(ks[3], (b, t, h * d))

    def kernels(q, k, v):
        return fa.flash_attention_heads_major(q, k, v, causal=True,
                                              block_q=128, block_k=128)

    def dense(q, k, v):
        o = at.dense_core(q, k, v.reshape(b, t, g, d).transpose(0, 2, 1, 3),
                          causal=True, heads_major=True)
        return o.transpose(0, 2, 1, 3).reshape(b, t, h * d)

    got, want = (jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a) * cot), argnums=(0, 1, 2))(q, k, v)
        for fn in (kernels, dense))
    np.testing.assert_allclose(np.asarray(kernels(q, k, v)),
                               np.asarray(dense(q, k, v)), atol=2e-5)
    for a, w, name in zip(got[1], want[1], "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=1e-4,
                                   err_msg=f"d{name}")
    # on a TPU, bf16 and 8,192 tokens, the rule picks the kernels for it
    assert at.select_core(on_tpu=True, causal=True, dtype=jnp.bfloat16,
                          t=8192, num_heads=32, head_dim=64,
                          num_kv_heads=8) == "flash"


# -- the configuration object, the registry, the task ---------------------------

def test_the_registry_builds_the_configuration_from_published_keys():
    published = {
        "vocab_size": 512, "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_hidden_layers": 5,
        "num_dense_layers": 1, "layer_types": list(TINY.layer_types),
        "conv_L_cache": 3, "conv_bias": False, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_experts": 16,
        "num_experts_per_tok": 4, "norm_topk_prob": True,
        "use_expert_bias": True, "routed_scaling_factor": 1,
        "norm_eps": 1e-05, "rope_theta": 1000000, "model_type": "lfm2_moe",
        "max_position_embeddings": 128000}
    assert family_of("lfm2_moe") == "lm" and "lfm2_moe" in MODEL_NAMES
    assert lm_config_from_file("lfm2_moe", published,
                               held_experts=(0, 16)) == TINY
    assert lm_config("lfm2_moe") is TINY
    assert TINY.layers == ("conv", "conv", "full_attention", "conv", "conv")
    assert TINY.head_dim == 16 and TINY.expert_layers == 4
    for key, value in (("conv_bias", True), ("use_expert_bias", False),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            lm_config_from_file("lfm2_moe", dict(published, **{key: value}))
    with pytest.raises(ValueError, match="layer_types"):
        lm_config_from_file("lfm2_moe", dict(published, num_hidden_layers=30))
    with pytest.raises(ValueError, match="layer_types"):
        lm_config_from_file("lfm2_moe", dict(
            published, layer_types=["conv", "sliding_attention"] * 3))


def test_the_ep4_preset_is_the_cut_the_issue_reckons():
    """541,374,592 trainable elements: layers 0-4 (conv dense, conv,
    full_attention, conv, conv), experts 0..7 of 32, a quarter of the
    vocabulary, every head."""
    cfg = lfm2.PRESETS["ep4"]
    assert cfg.layers == ("conv", "conv", "full_attention", "conv", "conv")
    assert cfg.expert_layers == 4 and cfg.head_dim == 64
    model = get_model("lfm2_moe", config="ep4")
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 10), jnp.int32),
                           jnp.zeros((4, 32))))["params"]
    sizes = {jax.tree_util.keystr(path): int(np.prod(s.shape)) for path, s
             in jax.tree_util.tree_leaves_with_path(shapes)}

    def layer(i):
        return sum(n for key, n in sizes.items() if f"['layer_{i}']" in key)
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    experts = 2048 * 32 + 8 * 3 * 2048 * 1792
    assert conv == 16_783_360
    assert layer(0) == conv + 3 * 2048 * 7168 + 2 * 2048 == 60_827_648
    assert layer(1) == conv + experts + 2 * 2048 == 104_933_376
    assert layer(2) == 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64 \
        + experts + 2 * 2048 == 98_635_904
    assert sum(sizes.values()) == layer(0) + 3 * layer(1) + layer(2) \
        + 2 * 16384 * 2048 + 2048 == 541_374_592
    assert cfg.held_experts == (0, 8) and cfg.vocab_size * 4 == 65_536
    assert cfg.train_seq_len == 8192
    assert cfg.pass_plan(4 * 8192) == (16384, 3)


def test_sync_trainer_and_cli_train_the_tiny_preset(tmp_path):
    """``cli train --mode sync --model lfm2_moe`` on the CPU, a process of
    its own: the normal path; the loss falls."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(HERE, ".."))
    out = subprocess.run(
        [sys.executable, "-m",
         "distributed_parameter_server_for_ml_training_tpu.cli", "train",
         "--mode", "sync", "--model", "lfm2_moe", "--workers", "2",
         "--batch-size", "2", "--epochs", "3", "--lr", "3e-3", "--dtype",
         "float32", "--num-train", "16", "--num-test", "4"],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    losses = [float(l.split("loss ")[1].split()[0])
              for l in out.stdout.splitlines()
              if l.startswith("[sync x2] epoch")]
    assert len(losses) == 3 and losses[-1] < losses[0] - 0.3, \
        out.stdout[-2000:]
    assert "expert layers traced" in out.stdout


def test_the_reference_imports_nothing_from_the_package():
    with open(REFERENCE) as f:
        source = f.read()
    imported = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0
            imported.add(node.module.split(".")[0])
    assert imported == {"__future__", "jax"}
    assert 'default_matmul_precision("highest")' in source
    assert "pallas" not in source and "causal_conv1d" not in source
    assert "rotate_half" in source and "1e-6" in source
