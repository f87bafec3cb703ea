"""The Nemotron-H decoder (models/nemotron_h.py) against its plain reference
(benchmarks/reference/nemotron_h_reference.py) at a small size on the CPU,
and what it asked of the pieces it brings and shares: the chunked
state-space scan and the convolution (ops/ssm.py), the attention module of
the window model without its positions, the ungated experts in a latent,
the LM task through ``SyncTrainer``.

Small size (the ``tiny`` preset): 4 layers ``ME*E``, 40 tokens in chunks of
16 (the last chunk padded), 4 Mamba-2 heads of 8 in 2 groups on a state of
16, 4 query heads on 2 key/value heads of 16, 16 experts of 48 in a latent
of 32 with 4 a token, a shared expert of 96, vocabulary 512.
"""

import ast
import importlib.util
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_parameter_server_for_ml_training_tpu.data import tokens as tk
from distributed_parameter_server_for_ml_training_tpu.models import (
    get_model, nemotron_h)
from distributed_parameter_server_for_ml_training_tpu.models.registry import (
    family_of, lm_config, lm_config_from_file)
from distributed_parameter_server_for_ml_training_tpu.ops import ssm

HERE = os.path.dirname(os.path.abspath(__file__))
REFERENCE = os.path.join(HERE, "..", "benchmarks", "reference",
                         "nemotron_h_reference.py")
TINY = nemotron_h.PRESETS["tiny"]
B, T = 2, 40


def _load_reference():
    spec = importlib.util.spec_from_file_location("nemotron_h_reference",
                                                  REFERENCE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()


def _setup(cfg=TINY, dtype=jnp.float32, seed=0, init_std=0.08):
    """Seeded weights (wider than the model's 0.02 so that every path
    carries signal at this depth) and one batch."""
    cfg = replace(cfg, init_std=init_std)
    model = get_model("nemotron_h", dtype=dtype, config=cfg)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (B, T + 2)), jnp.int32)
    bias = jnp.asarray(np.random.default_rng(seed + 1).normal(
        size=(cfg.expert_layers, cfg.n_routed_experts)) * 0.05, jnp.float32)
    params = model.init(jax.random.PRNGKey(seed), tokens[:1, :10],
                        bias)["params"]
    return cfg, model, params, bias, tokens


def _program(model, params, bias, tokens):
    def loss_fn(p):
        out = model.apply({"params": p}, tokens, bias)
        return out["loss"], out
    (loss, out), grads = jax.jit(jax.value_and_grad(
        loss_fn, has_aux=True))(params)
    return loss, out, grads


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
#: other orders (chunks and segment sums against a token-by-token scan,
#: sorted groups against a loop over experts, a chunked loss, fused norms):
#: 1e-6 to 1e-5 of a tensor's largest value; 1e-4 leaves ten times that and
#: is a hundred times under what bf16 compute gives.
TIGHT = 1e-4


# -- the scan and the convolution ------------------------------------------------

def _scan_inputs(t, strength, seed=0, bsz=2, h=4, p=8, g=2, n=16):
    """Seeded inputs of a scan; ``strength`` scales ``dt`` (at 30 a token's
    decay is exp(-30) and less, and a product of a chunk's underflows)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (bsz, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (bsz, t, h))) * strength
    a = -jnp.exp(jax.random.normal(k[2], (h,)))
    b, c = (jax.random.normal(k[i], (bsz, t, g, n)) for i in (3, 4))
    return (x, dt, a, b, c), jax.random.normal(k[5], (bsz, t, h, p))


def _token_by_token(x, dt, a, b, c):
    """The reference's recurrence, a sequence at a time, a group's B and C
    repeated for its heads."""
    rep = x.shape[2] // b.shape[2]
    return jnp.stack([
        ref.recurrence(x[i], dt[i], a, jnp.repeat(b[i], rep, axis=1),
                       jnp.repeat(c[i], rep, axis=1))
        for i in range(x.shape[0])])


@pytest.mark.parametrize("t,strength", [
    (32, 1.0),      # whole chunks of 8
    (37, 1.0),      # the last chunk padded
    (5, 1.0),       # shorter than a chunk
    (40, 30.0),     # a chunk's product of decays underflows float32
    (40, 300.0),    # a single token's does
])
def test_the_chunked_scan_against_the_token_by_token_recurrence(t, strength):
    """Forward and all five gradients, float32, true-float32 products."""
    args, w = _scan_inputs(t, strength)

    def value_and_grads(scan):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(scan(*a) * w), argnums=(0, 1, 2, 3, 4)))(*args)

    with jax.default_matmul_precision("highest"):
        got, got_grads = value_and_grads(
            lambda *a: ssm.ssm_scan(*a, chunk=8))
        want, want_grads = value_and_grads(_token_by_token)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    for name, a, b in zip("x dt a b c".split(), got_grads, want_grads):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert float(jnp.max(jnp.abs(a - b))) \
            < 2e-5 * float(jnp.max(jnp.abs(b))), name


def test_the_scan_keeps_no_state_a_token():
    """Neither pass holds an array of ``T x H x P x N`` elements (the states
    a token-by-token scan's backward pass keeps): the largest is a chunk's
    decays, ``T x H x L``, and the carried states are one a chunk."""
    t, chunk = 64, 8
    (x, dt, a, b, c), w = _scan_inputs(t, 1.0)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *args: jnp.sum(ssm.ssm_scan(*args, chunk=chunk) * w),
        argnums=(0, 1, 2, 3, 4)))(x, dt, a, b, c)
    bsz, _t, h, p = x.shape
    n = b.shape[-1]
    sizes = []

    def walk(jp):
        for eqn in jp.eqns:
            sizes.extend(int(np.prod(v.aval.shape)) for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    assert max(sizes) < bsz * t * h * p * n
    assert max(sizes) == bsz * (t // chunk) * h * p * n   # a state a chunk


def test_the_scan_counts_its_implementation():
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        get_registry)
    counted = get_registry().counter("dps_ssm_scan_total",
                                     impl=ssm.SSM_SCAN_IMPLS[0])
    before = counted.value
    args, _w = _scan_inputs(16, 1.0)
    ssm.ssm_scan(*args, chunk=8)
    assert counted.value == before + 1


def test_the_convolution_is_causal_and_depthwise():
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(2, 12, 6)), jnp.float32)
    kernel = jnp.asarray(r.normal(size=(4, 6)), jnp.float32)
    bias = jnp.asarray(r.normal(size=(6,)), jnp.float32)
    y = ssm.causal_conv1d(x, kernel, bias)
    want = np.zeros((2, 12, 6), np.float32) + np.asarray(bias)
    for t in range(12):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += np.asarray(kernel[k]) * np.asarray(
                    x[:, t - 3 + k])
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    # token t reads nothing after t, channel c nothing of another channel
    moved = ssm.causal_conv1d(x.at[:, 7, 2].add(1.0), kernel, bias) - y
    assert not np.asarray(moved[:, :7]).any()
    assert not np.asarray(moved[..., [0, 1, 3, 4, 5]]).any()
    assert np.asarray(moved[:, 7:11, 2]).all()


# -- the mixers ------------------------------------------------------------------

@pytest.fixture(scope="module")
def f32_case():
    cfg, model, params, bias, tokens = _setup()
    want = ref.loss_and_grads(params, bias, tokens, cfg)
    return cfg, model, params, bias, tokens, want


def _activations(seed, scale=1.0):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(1, T, TINY.hidden_size)) * scale, jnp.float32)


def test_the_mamba_mixer_against_the_reference(f32_case):
    """Output and gradients, of the input and of every parameter."""
    cfg, _model, params, _bias, _tokens, _want = f32_case
    p, u = params["layer_0"]["mixer"], _activations(1)
    w = _activations(2)

    def program(p, u):
        return jnp.sum(nemotron_h.Mamba2Mixer(cfg, jnp.float32).apply(
            {"params": p}, u) * w)

    def reference(p, u):
        return jnp.sum(ref.mamba(p, u[0], cfg) * w[0])

    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1)))(p, u)
        want, want_grads = jax.jit(jax.value_and_grad(
            reference, argnums=(0, 1)))(p, u)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    worst, where = _worst(got_grads, want_grads)
    assert worst < TIGHT, (worst, where)
    for path, g in jax.tree_util.tree_leaves_with_path(got_grads):
        assert float(jnp.abs(g).max()) > 0, jax.tree_util.keystr(path)


def test_the_latent_expert_layer_against_the_reference(f32_case):
    cfg, _model, params, bias, _tokens, _want = f32_case
    p, u = params["layer_1"]["mixer"], _activations(3, 2.0)
    w = _activations(4)

    def program(p, u):
        y, loads, done = nemotron_h.LatentExpertLayer(
            cfg, jnp.float32).apply({"params": p}, u, bias[0])
        return jnp.sum(y * w), (loads, done)

    def reference(p, u):
        y, loads = ref.expert_layer(p, u[0], bias[0], cfg)
        return jnp.sum(y * w[0]), loads

    with jax.default_matmul_precision("highest"):
        (got, (loads, done)), got_grads = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1), has_aux=True))(p, u)
        (want, want_loads), want_grads = jax.jit(jax.value_and_grad(
            reference, argnums=(0, 1), has_aux=True))(p, u)
    np.testing.assert_array_equal(np.asarray(loads), np.asarray(want_loads))
    assert int(done) == T * cfg.num_experts_per_tok
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    worst, where = _worst(got_grads, want_grads)
    assert worst < TIGHT, (worst, where)
    # the bias steers the choice and takes part in no weight: with another
    # bias other experts are chosen, weighted by their own scores
    other = nemotron_h.LatentExpertLayer(cfg, jnp.float32).apply(
        {"params": p}, u, -bias[0])
    assert not np.array_equal(np.asarray(other[1]), np.asarray(loads))


def test_float32_step_matches_the_reference(f32_case):
    """Loss, every gradient, the loads."""
    cfg, model, params, bias, tokens, (want_loss, aux, want) = f32_case
    loss, out, grads = _program(model, params, bias, tokens)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    np.testing.assert_array_equal(np.asarray(out["loads"]),
                                  np.asarray(aux["loads"]))
    assert out["loads"].shape == (2, 16)
    assert int(out["processed"]) == B * T * 4 * 2      # all held, none lost
    worst, where = _worst(grads, want)
    assert worst < TIGHT, (worst, where)
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(want)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.abs(g).max()) > 0, jax.tree_util.keystr(path)


def test_bf16_compute_is_outside_the_tight_tolerance(f32_case):
    cfg, _model, params, bias, tokens, (want_loss, _aux, want) = f32_case
    model = get_model("nemotron_h", dtype=jnp.bfloat16, config=cfg)
    loss, _out, grads = _program(model, params, bias, tokens)
    assert _worst(grads, want)[0] > TIGHT * 10
    assert abs(float(loss) - float(want_loss)) < 5e-3 * float(want_loss)


def test_the_reference_in_bf16_is_not_the_reference(f32_case):
    cfg, _model, params, bias, tokens, (_loss, _aux, want) = f32_case
    _l, _a, low = ref.loss_and_grads(params, bias, tokens, cfg,
                                     dtype=jnp.bfloat16)
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), low)
    assert _worst(low, want)[0] > TIGHT * 10


def test_reference_blocking_and_remat_change_nothing(f32_case, monkeypatch):
    """Rows a block, heads a block, queries a block (8 of the 40), the
    recurrence in checkpointed blocks of 8 tokens, recomputation."""
    cfg, _model, params, bias, tokens, (want_loss, _aux, want) = f32_case
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    monkeypatch.setattr(ref, "SCAN_BLOCK", 8)
    loss, _a, grads = ref.loss_and_grads(
        params, bias, tokens, cfg, rows_per_block=1, remat=True,
        head_block=2)
    assert abs(float(loss) - float(want_loss)) < 1e-6
    assert _worst(grads, want)[0] < 1e-5


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


@pytest.mark.parametrize("pattern", ["MM*E", "E*ME", "M*EE"])
def test_a_layer_is_the_mixer_its_pattern_character_names(pattern):
    """Another pattern is another model, the reference given the same
    pattern follows it, and the bias rows go to the expert layers in
    order."""
    cfg, model, params, bias, tokens = _setup(replace(
        TINY, hybrid_override_pattern=pattern))
    bias = bias[:1].repeat(cfg.expert_layers, axis=0) * jnp.arange(
        1, cfg.expert_layers + 1)[:, None]
    assert cfg.expert_layers == pattern.count("E")
    loss = model.apply({"params": params}, tokens, bias)["loss"]
    follows, aux = ref.batch_loss(params, bias, tokens, cfg)
    assert abs(float(loss) - float(follows)) < 3e-6 * float(loss)
    assert aux["loads"].shape == (cfg.expert_layers, 16)
    kinds = {"M": "in_proj", "*": "q", "E": "router"}
    for i, kind in enumerate(pattern):
        assert kinds[kind] in params[f"layer_{i}"]["mixer"]


def test_the_attention_layer_has_no_positions(f32_case):
    """With a causal mask alone the layer's output at the last position does
    not change when the earlier tokens are shuffled."""
    cfg, _model, params, _bias, _tokens, _want = f32_case
    a = _activations(2)
    shuffled = a.at[:, :T - 1].set(a[:, np.random.default_rng(3).permutation(
        T - 1)])
    from distributed_parameter_server_for_ml_training_tpu.models import decoder

    def last(x):
        attn = decoder.GroupedAttention(cfg, jnp.float32, rope=False,
                                        window=None)
        return attn.apply({"params": params["layer_2"]["mixer"]}, x)[0, -1]

    np.testing.assert_allclose(np.asarray(last(a)), np.asarray(last(shuffled)),
                               atol=1e-5)
    want = ref.attention(params["layer_2"]["mixer"], a[0], cfg, None)
    np.testing.assert_allclose(np.asarray(last(a)), np.asarray(want[-1]),
                               atol=1e-5)


# -- the test that ties the share to the model -----------------------------------

def _columns(width_of, shares, share):
    """Column indices of one share of a matrix whose columns are runs of
    ``width_of[i]`` channels, each run divided evenly over ``shares``."""
    out, lo = [], 0
    for width in width_of:
        part = width // shares
        out.append(np.arange(lo + share * part, lo + (share + 1) * part))
        lo += width
    return np.concatenate(out)


def test_eight_head_shares_of_a_mamba_layer_add_up_to_the_whole_layer():
    """16 heads in 8 groups, a group a share: each share's in-projection is
    its columns of ``[z | x | B | C | dt]``, its convolution its channels,
    its scan its two heads on its group's B and C, its norm its group, and
    its out-projection its rows; the eight partial outputs add up to the
    uncut reference's layer."""
    whole = replace(TINY, mamba_num_heads=16, n_groups=8, init_std=0.08)
    h, p_, g, n = 16, whole.mamba_head_dim, 8, whole.ssm_state_size
    inner = h * p_
    u = _activations(6)
    p = nemotron_h.Mamba2Mixer(whole, jnp.float32).init(
        jax.random.PRNGKey(0), u)["params"]
    p = dict(p, D=jnp.asarray(np.random.default_rng(1).normal(size=h),
                              jnp.float32),
             norm=jnp.asarray(np.random.default_rng(2).uniform(
                 0.5, 1.5, size=inner), jnp.float32))
    share_cfg = replace(whole, mamba_num_heads=2, n_groups=1)
    # one program a side for the eight shares
    program = jax.jit(lambda mine: nemotron_h.Mamba2Mixer(
        share_cfg, jnp.float32).apply({"params": mine}, u)[0])
    reference = jax.jit(lambda mine: ref.mamba(mine, u[0], share_cfg))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: ref.mamba(p, u[0], whole))(p)
        total = jnp.zeros_like(want)
        for s in range(8):
            proj = _columns((inner, inner, g * n, g * n, h), 8, s)
            conv = _columns((inner, g * n, g * n), 8, s)
            heads, chans = _columns((h,), 8, s), _columns((inner,), 8, s)
            mine = {
                "in_proj": {"kernel": p["in_proj"]["kernel"][:, proj]},
                "conv_kernel": p["conv_kernel"][:, conv],
                "conv_bias": p["conv_bias"][conv],
                "dt_bias": p["dt_bias"][heads], "A_log": p["A_log"][heads],
                "D": p["D"][heads], "norm": p["norm"][chans],
                "out_proj": {"kernel": p["out_proj"]["kernel"][chans]}}
            part = program(mine)
            np.testing.assert_allclose(np.asarray(part),
                                       np.asarray(reference(mine)),
                                       atol=2e-5)
            total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)


def test_four_head_shares_of_the_attention_layer_add_up(f32_case):
    """4 query heads on 2 key/value heads in four shares of one query head,
    each with the key/value head it reads (two shares hold the same one, as
    two chips of the deployment both hold a key/value head)."""
    cfg, _model, params, _bias, _tokens, _want = f32_case
    from distributed_parameter_server_for_ml_training_tpu.models import decoder
    p, u, hd = params["layer_2"]["mixer"], _activations(7), cfg.head_dim
    want = ref.attention(p, u[0], cfg, None)
    share_cfg = replace(cfg, num_attention_heads=1, num_key_value_heads=1)
    total = jnp.zeros_like(want)
    for head in range(4):
        q = slice(head * hd, (head + 1) * hd)
        kv = slice(head // 2 * hd, (head // 2 + 1) * hd)
        mine = {"q": {"kernel": p["q"]["kernel"][:, q]},
                "k": {"kernel": p["k"]["kernel"][:, kv]},
                "v": {"kernel": p["v"]["kernel"][:, kv]},
                "o": {"kernel": p["o"]["kernel"][q]}}
        total = total + decoder.GroupedAttention(
            share_cfg, jnp.float32, rope=False, window=None).apply(
                {"params": mine}, u)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)


def test_four_shares_of_four_experts_add_up_to_the_whole_layer(f32_case):
    """16 experts in the shares ``(0,4) (4,4) (8,4) (12,4)``: each share's
    output is ``W_up`` on ITS partial sum plus the shared expert; the four
    routed parts and the shared expert counted once add up to the uncut
    reference's layer."""
    cfg, _model, params, bias, _tokens, _want = f32_case
    p, u = params["layer_3"]["mixer"], _activations(5, 2.0)
    whole, loads = ref.expert_layer(p, u[0], bias[1], cfg)
    shared = ref.relu2(u[0] @ p["shared"]["up"]["kernel"]) \
        @ p["shared"]["down"]["kernel"]
    total = shared
    for first in (0, 4, 8, 12):
        share = replace(cfg, held_experts=(first, 4))
        mine = dict(p, **{f"experts_{n}": p[f"experts_{n}"][first:first + 4]
                          for n in ("up", "down")})
        y, share_loads, processed = nemotron_h.LatentExpertLayer(
            share, jnp.float32).apply({"params": mine}, u, bias[1])
        np.testing.assert_array_equal(np.asarray(share_loads),
                                      np.asarray(loads))
        assert int(processed) == int(loads[first:first + 4].sum())
        # and the reference given the same share gives the same part
        part, _ = ref.expert_layer(mine, u[0], bias[1], share)
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(part),
                                   atol=2e-5)
        total = total + (y[0] - shared)
    assert int(loads.sum()) == T * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5)


# -- the configuration object, the registry, the task ---------------------------

def test_the_registry_builds_the_configuration_from_published_keys():
    published = {
        "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 4,
        "hybrid_override_pattern": "ME*E", "mamba_num_heads": 4,
        "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
        "chunk_size": 16, "conv_kernel": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "n_routed_experts": 16,
        "num_experts_per_tok": 4, "moe_intermediate_size": 48,
        "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96,
        "routed_scaling_factor": 5, "norm_topk_prob": True,
        "layer_norm_epsilon": 1e-05, "mlp_hidden_act": "relu2",
        "expand": 2, "model_type": "nemotron_h",
        "num_nextn_predict_layers": 0, "max_position_embeddings": 262144}
    assert family_of("nemotron_h") == "lm"
    assert lm_config_from_file("nemotron_h", published,
                               held_experts=(0, 16)) == TINY
    assert lm_config("nemotron_h", "tiny") is TINY
    assert lm_config("nemotron_h") is TINY
    # the Mamba width is heads x head width, never expand x hidden
    assert TINY.mamba_inner == 32 != published["expand"] * 64
    for key, value in (("mlp_hidden_act", "silu"), ("n_group", 2),
                       ("use_conv_bias", False), ("attention_bias", True),
                       ("tie_word_embeddings", True),
                       ("num_nextn_predict_layers", 1)):
        with pytest.raises(ValueError, match=key):
            lm_config_from_file("nemotron_h", dict(published,
                                                   **{key: value}))
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        lm_config_from_file("nemotron_h", dict(published,
                                               num_hidden_layers=5))
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        lm_config_from_file("nemotron_h", dict(
            published, hybrid_override_pattern="M-*E"))
    with pytest.raises(ValueError, match="groups"):
        lm_config_from_file("nemotron_h", dict(published, n_groups=3))


def test_the_tp8_ep64_preset_is_the_cut_the_issue_reckons():
    """700,862,960 trainable elements: the first 11 layers ``MEMEMEM*EME``,
    16 of 128 Mamba-2 heads in 1 of 8 groups, 4 of 32 query heads on 1 of 2
    key/value heads, experts 0..7 of 512, an eighth of the vocabulary."""
    cfg = nemotron_h.PRESETS["tp8_ep64"]
    assert cfg.pattern == "MEMEMEM*EME" and cfg.expert_layers == 5
    model = get_model("nemotron_h", config="tp8_ep64")
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 10), jnp.int32),
                           jnp.zeros((5, 512))))["params"]
    sizes = {jax.tree_util.keystr(path): int(np.prod(s.shape)) for path, s
             in jax.tree_util.tree_leaves_with_path(shapes)}

    def layer(i):
        return sum(n for key, n in sizes.items() if f"['layer_{i}']" in key)
    # in_proj 4,096 x 2,320, out_proj 1,024 x 4,096, the convolution 4 x
    # 1,280 + 1,280, three vectors a head, the two norms
    assert layer(0) == 4096 * 2320 + 1024 * 4096 + 5 * 1280 + 3 * 16 \
        + 1024 + 4096 == 13_708_592
    assert layer(7) == 2 * 4096 * 512 + 2 * 4096 * 128 + 4096 == 5_246_976
    assert layer(1) == 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 \
        + 8 * 2 * 1024 * 2688 + 4096 == 98_570_240
    assert sum(sizes.values()) == 5 * layer(0) + layer(7) + 5 * layer(1) \
        + 2 * 16384 * 4096 + 4096 == 700_862_960
    assert cfg.held_experts == (0, 8) and cfg.vocab_size * 8 == 131_072
    assert cfg.mamba_inner == 1024 and cfg.train_seq_len == 8192
    assert cfg.pass_plan(2 * 8192) == (3072, 3)


def _tiny_trainer(epochs=3, seed=0):
    from distributed_parameter_server_for_ml_training_tpu.train.distributed \
        import DistributedConfig, SyncTrainer
    data = tk.synthetic_documents(vocab_size=512, seq_len=T, n_train=32,
                                  n_test=4, seed=seed, median_len=20)
    return SyncTrainer(data, DistributedConfig(
        num_workers=2, batch_size=2, learning_rate=3e-3, num_epochs=epochs,
        model="nemotron_h", dtype="float32", seed=seed))


def test_sync_trainer_trains_the_tiny_model_and_the_loss_falls(devices,
                                                               capsys):
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        get_registry)
    reg = get_registry()
    before = {w: reg.counter("dps_moe_tokens_routed_total", where=w).value
              for w in ("held", "absent")}
    scans = reg.counter("dps_ssm_scan_total", impl="xla_chunked")
    cores = reg.counter("dps_attention_core_total", impl="dense", group="2")
    counted = (scans.value, cores.value)
    trainer = _tiny_trainer(epochs=4)
    metrics = trainer.train()
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("[sync x2] epoch")]
    losses = [float(l.split("loss ")[1].split()[0]) for l in lines]
    assert len(losses) == 4 and losses[-1] < losses[0] - 0.3, losses
    assert metrics["global_steps_completed"] == 4 * 8
    tokens = 32 * T * 4
    held = reg.counter("dps_moe_tokens_routed_total",
                       where="held").value - before["held"]
    assert held == tokens * 4 * 2            # top-4, 2 expert layers, all held
    assert reg.counter("dps_moe_tokens_routed_total",
                       where="absent").value == before["absent"]
    assert reg.counter("dps_moe_tokens_dropped_total").value == 0
    assert reg.gauge("dps_moe_load_max_over_mean").value >= 1.0
    # one Mamba-2 layer and one attention layer a program, as many programs
    assert scans.value - counted[0] == cores.value - counted[1] > 0
    # the balancing bias moved: gamma 0.001 a step, one row an expert layer
    bias = np.asarray(trainer.state.batch_stats["router_bias"])
    assert bias.shape == (2, 16) and np.abs(bias).max() > 0.0009


def test_cli_train_trains_the_tiny_preset(tmp_path):
    """``cli train --mode sync --model nemotron_h`` on the CPU: the normal
    path, a process of its own."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(HERE, ".."))
    out = subprocess.run(
        [sys.executable, "-m",
         "distributed_parameter_server_for_ml_training_tpu.cli", "train",
         "--mode", "sync", "--model", "nemotron_h", "--workers", "2",
         "--batch-size", "2", "--epochs", "2", "--lr", "3e-3", "--dtype",
         "float32", "--num-train", "16", "--num-test", "4"],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    losses = [float(l.split("loss ")[1].split()[0])
              for l in out.stdout.splitlines()
              if l.startswith("[sync x2] epoch")]
    assert len(losses) == 2 and losses[1] < losses[0], out.stdout[-2000:]


def test_the_reference_imports_nothing_from_the_package():
    with open(REFERENCE) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0
            imported.add(node.module.split(".")[0])
    assert imported == {"__future__", "jax"}
    source = open(REFERENCE).read()
    assert 'default_matmul_precision("highest")' in source
    assert "pallas" not in source and "cumsum" not in source
    # the recurrence is a scan over tokens, not the program's chunked form
    assert "ssm_scan" not in source and "segment sums" in source
