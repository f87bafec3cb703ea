"""The SmallThinker decoder (models/smallthinker.py) against its plain
reference (benchmarks/reference/smallthinker_reference.py) at a small size
on the CPU, and what it asked of the pieces it shares: the window and the
grouped queries in the attention door, the router, the ReLU gate, the LM
task through ``SyncTrainer``.

Small size (the ``tiny`` preset): 4 layers ``[0, 1, 1, 1]`` (one global
layer without positions, three with RoPE and a window of 8), 32 tokens, 4
query heads on 2 key/value heads of 16, 8 experts top 2, vocabulary 512.
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
    get_model, smallthinker)
from distributed_parameter_server_for_ml_training_tpu.models.registry import (
    family_of, lm_config, lm_config_from_file)

HERE = os.path.dirname(os.path.abspath(__file__))
REFERENCE = os.path.join(HERE, "..", "benchmarks", "reference",
                         "smallthinker_reference.py")
TINY = smallthinker.PRESETS["tiny"]
B, T = 2, 32


def _load_reference():
    spec = importlib.util.spec_from_file_location("smallthinker_reference",
                                                  REFERENCE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()


def _setup(cfg=TINY, dtype=jnp.float32, seed=0, init_std=0.08):
    """Seeded weights (wider than the model's 0.02 so that every path
    carries signal at this depth) and one batch."""
    cfg = replace(cfg, init_std=init_std)
    model = get_model("smallthinker", dtype=dtype, config=cfg)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (B, T + 2)), jnp.int32)
    bias = jnp.zeros((cfg.expert_layers, cfg.n_routed_experts), jnp.float32)
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
#: other orders (sorted groups against a loop over experts, a chunked loss,
#: fused norms): 1e-6 to 1e-5 of a tensor's largest value; 1e-4 leaves ten
#: times that and is a hundred times under what bf16 compute gives.
TIGHT = 1e-4


@pytest.fixture(scope="module")
def f32_case():
    cfg, model, params, bias, tokens = _setup()
    want = ref.loss_and_grads(params, bias, tokens, cfg)
    return cfg, model, params, bias, tokens, want


def test_float32_step_matches_the_reference(f32_case):
    """Loss, every gradient, the loads."""
    cfg, model, params, bias, tokens, (want_loss, aux, want) = f32_case
    loss, out, grads = _program(model, params, bias, tokens)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    np.testing.assert_array_equal(np.asarray(out["loads"]),
                                  np.asarray(aux["loads"]))
    assert out["loads"].shape == (4, 8)
    assert int(out["processed"]) == B * T * 2 * 4      # all held, none lost
    worst, where = _worst(grads, want)
    assert worst < TIGHT, (worst, where)
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(want)
    # every tensor takes gradient: the router through its softmax weights
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.abs(g).max()) > 0, jax.tree_util.keystr(path)


def test_bf16_compute_is_outside_the_tight_tolerance(f32_case):
    cfg, _model, params, bias, tokens, (want_loss, _aux, want) = f32_case
    model = get_model("smallthinker", dtype=jnp.bfloat16, config=cfg)
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
    """Rows a block, heads a block, queries a block (16 of the 32, through
    ``jax.lax.map`` with the mask's rows cut to match), recomputation."""
    cfg, _model, params, bias, tokens, (want_loss, _aux, want) = f32_case
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
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


# -- the layer pattern ---------------------------------------------------------

def _loss_with(cfg, params, bias, tokens):
    model = get_model("smallthinker", dtype=jnp.float32, config=cfg)
    return float(model.apply({"params": params}, tokens, bias)["loss"])


@pytest.mark.parametrize("field,changed", [
    ("rope_layout", (1, 1, 1, 1)),              # positions on the global layer
    ("rope_layout", (0, 1, 1, 0)),              # none on a window layer
    ("sliding_window_layout", (0, 0, 0, 0)),    # the window forgotten
    ("sliding_window_layout", (1, 1, 1, 1)),    # a window on the global layer
    ("sliding_window_size", 9),                 # i - j <= W in place of <
])
def test_rope_and_the_window_only_where_the_layouts_say(f32_case, field,
                                                        changed):
    """The model with one layout entry changed is another model, and the
    reference given the same change follows it: each layer reads its own
    entry, in both."""
    cfg, _model, params, bias, tokens, (want_loss, _aux, _want) = f32_case
    other = replace(cfg, **{field: changed})
    loss = _loss_with(other, params, bias, tokens)
    assert abs(loss - float(want_loss)) > 3e-5 * float(want_loss)
    follows, _aux = ref.batch_loss(params, bias, tokens, other)
    assert abs(loss - float(follows)) < 3e-6 * loss


def test_the_global_layer_has_no_positions(f32_case):
    """Layer 0 (``rope_layout`` 0, no window) with a causal mask alone is
    equivariant under a permutation of the *earlier* tokens: its output at
    the last position does not change when the prefix is shuffled. A window
    layer's does (RoPE, and what falls out of the window)."""
    cfg, _model, params, _bias, _tokens, _want = f32_case
    a = jnp.asarray(np.random.default_rng(2).normal(size=(1, T, 64)),
                    jnp.float32)
    shuffled = a.at[:, :T - 1].set(a[:, np.random.default_rng(3).permutation(
        T - 1)])

    def last(layer, x):
        attn = smallthinker.GroupedAttention(
            cfg, jnp.float32, rope=bool(cfg.rope_layout[layer]),
            window=cfg.window(layer))
        return attn.apply({"params": params[f"layer_{layer}"]["attn"]},
                          x)[0, -1]

    np.testing.assert_allclose(np.asarray(last(0, a)),
                               np.asarray(last(0, shuffled)), atol=1e-5)
    assert float(jnp.abs(last(1, a) - last(1, shuffled)).max()) > 1e-3


def test_the_router_reads_the_blocks_input(f32_case):
    """A fault planted by norming the router's input first must fail: a
    block that hands the router ``RMSNorm(x)`` weighs the experts
    otherwise."""
    cfg, _model, params, _bias, _tokens, _want = f32_case
    p = params["layer_1"]
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, T, 64)) * 3.0,
                    jnp.float32)
    want, want_loads = ref.block(p, x[0], cfg, 1, None)
    got, loads, _n = smallthinker.Block(cfg, jnp.float32, layer=1).apply(
        {"params": p}, x)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(loads), np.asarray(want_loads))

    # the plant: the same layer with the router fed the normed x
    normed = smallthinker.rms_norm(x, p["attn_norm"]["scale"],
                                   cfg.rms_norm_eps)
    u = jnp.asarray(np.random.default_rng(5).normal(size=(1, T, 64)),
                    jnp.float32)
    layer = smallthinker.ExpertLayer(cfg, jnp.float32)
    right, right_loads, _ = layer.apply({"params": p["moe"]}, x, u)
    wrong, _loads, _ = layer.apply({"params": p["moe"]}, normed, u)
    ref_out, ref_loads = ref.expert_layer(p["moe"], x[0], u[0], cfg)
    np.testing.assert_allclose(np.asarray(right[0]), np.asarray(ref_out),
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(right_loads),
                                  np.asarray(ref_loads))
    # (a norm with gains of 1 scales a token's logits by one positive
    # number: the choice stays, the softmax over the chosen logits does not)
    assert float(jnp.abs(wrong[0] - ref_out).max()) > 1e-2


def test_four_shares_of_two_experts_add_up_to_the_whole_layer(f32_case):
    """The test that ties the share to the model: 8 experts in the shares
    ``(0,2) (2,2) (4,2) (6,2)``; the four partial results add up to the
    uncut reference's layer output (there is no shared expert to count
    once)."""
    cfg, _model, params, _bias, _tokens, _want = f32_case
    p = params["layer_2"]["moe"]
    r = np.random.default_rng(5)
    x, u = (jnp.asarray(r.normal(size=(1, T, 64)), jnp.float32)
            for _ in range(2))
    whole, loads = ref.expert_layer(p, x[0], u[0], cfg)
    total = jnp.zeros_like(whole)
    for first in (0, 2, 4, 6):
        share = replace(cfg, held_experts=(first, 2))
        mine = dict(p, **{f"experts_{n}": p[f"experts_{n}"][first:first + 2]
                          for n in ("gate", "up", "down")})
        y, share_loads, processed = smallthinker.ExpertLayer(
            share, jnp.float32).apply({"params": mine}, x, u)
        np.testing.assert_array_equal(np.asarray(share_loads),
                                      np.asarray(loads))
        assert int(processed) == int(loads[first:first + 2].sum())
        # and the reference given the same share gives the same part
        part, _ = ref.expert_layer(mine, x[0], u[0], share)
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(part),
                                   atol=2e-5)
        total = total + y[0]
    assert int(loads.sum()) == T * 2
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5)


# -- the configuration object, the registry, the task ---------------------------

def test_the_registry_builds_the_configuration_from_published_keys():
    published = {
        "head_dim": 16, "hidden_size": 64, "moe_ffn_hidden_size": 32,
        "moe_num_active_primary_experts": 2, "moe_num_primary_experts": 8,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_hidden_layers": 4,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-06,
        "max_position_embeddings": 64,
        "rope_layout": [0, 1, 1, 1], "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1],
        "sliding_window_size": 8, "tie_word_embeddings": False,
        "vocab_size": 512, "model_name": "not a field"}
    assert family_of("smallthinker") == "lm"
    assert lm_config_from_file("smallthinker", published,
                               held_experts=(0, 8)) == TINY
    assert lm_config("smallthinker", "tiny") is TINY
    assert lm_config("smallthinker") is TINY
    for key, value in (("moe_primary_router_apply_softmax", False),
                       ("norm_topk_prob", False),
                       ("tie_word_embeddings", True),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            lm_config_from_file("smallthinker", dict(published,
                                                     **{key: value}))
    with pytest.raises(ValueError, match="layout"):
        lm_config_from_file("smallthinker", dict(published,
                                                 num_hidden_layers=5))
    # the other decoder's goes the same way
    from distributed_parameter_server_for_ml_training_tpu.models import joyai
    assert lm_config_from_file(
        "joyai_llm_flash", {"vocab_size": 16160, "num_hidden_layers": 5},
        held_experts=(0, 16), bias_update_gamma=0.01,
        expert_capacity_factor=2.5) == joyai.PRESETS["ep16"]


def test_the_task_keeps_its_contract_without_a_balancing_bias():
    assert TINY.bias_update_gamma == 0.0
    assert TINY.expert_layers == 4 and TINY.n_routed_experts == 8
    assert [TINY.window(i) for i in range(4)] == [None, 8, 8, 8]


def test_the_ep4_preset_is_the_cut_the_issue_reckons():
    """656,529,920 trainable elements: one period of 4 layers, experts 0..15
    of 64, a quarter of the vocabulary, every head held."""
    cfg = smallthinker.PRESETS["ep4"]
    model = get_model("smallthinker", config="ep4")
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 10), jnp.int32),
                           jnp.zeros((4, 64))))["params"]
    sizes = {jax.tree_util.keystr(path): int(np.prod(s.shape)) for path, s
             in jax.tree_util.tree_leaves_with_path(shapes)}
    assert sum(sizes.values()) == 656_529_920
    layer = sum(n for key, n in sizes.items() if "layer_0" in key)
    assert layer == 115_512_320
    assert sum(n for key, n in sizes.items()
               if "layer_0" in key and "['attn']" in key) == 20_971_520
    assert cfg.held_experts == (0, 16) and cfg.vocab_size * 4 == 151_936
    assert [cfg.window(i) for i in range(4)] == [None, 4096, 4096, 4096]
    assert cfg.max_position_embeddings == 16384


def _tiny_trainer(epochs=3, seed=0):
    from distributed_parameter_server_for_ml_training_tpu.train.distributed \
        import DistributedConfig, SyncTrainer
    data = tk.synthetic_documents(vocab_size=512, seq_len=T, n_train=32,
                                  n_test=4, seed=seed, median_len=20)
    return SyncTrainer(data, DistributedConfig(
        num_workers=2, batch_size=2, learning_rate=3e-3, num_epochs=epochs,
        model="smallthinker", dtype="float32", seed=seed))


def test_sync_trainer_trains_the_tiny_model_and_the_loss_falls(devices,
                                                               capsys):
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        get_registry)
    reg = get_registry()
    before = {w: reg.counter("dps_moe_tokens_routed_total", where=w).value
              for w in ("held", "absent")}
    tokens_before = reg.counter("dps_trainer_tokens_total",
                                mode="sync").value
    windows = reg.counter("dps_attention_core_total", impl="dense",
                          window="8", group="2")
    fulls = reg.counter("dps_attention_core_total", impl="dense", group="2")
    cores = (windows.value, fulls.value)
    trainer = _tiny_trainer(epochs=4)
    metrics = trainer.train()
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("[sync x2] epoch")]
    losses = [float(l.split("loss ")[1].split()[0]) for l in lines]
    assert len(losses) == 4 and losses[-1] < losses[0] - 0.3, losses
    assert metrics["global_steps_completed"] == 4 * 8
    tokens = 32 * T * 4
    assert reg.counter("dps_trainer_tokens_total",
                       mode="sync").value - tokens_before == tokens
    held = reg.counter("dps_moe_tokens_routed_total",
                       where="held").value - before["held"]
    assert held == tokens * 2 * 4            # top-2, 4 layers, all held
    assert reg.counter("dps_moe_tokens_routed_total",
                       where="absent").value == before["absent"]
    assert reg.counter("dps_moe_tokens_dropped_total").value == 0
    # the backward pass's (pass, expert) slices, of passes x 8 held experts
    rows, passes = trainer.task.model_config.pass_plan(2 * T)
    assert 1 / 8 <= reg.gauge("dps_moe_grad_visits_share").value <= (
        (8 + passes - 1) / (passes * 8))
    assert reg.counter("dps_moe_grad_accumulate_total",
                       impl="xla").value >= 4
    # no balancing bias: the task's array stays what it was
    assert not np.asarray(trainer.state.batch_stats["router_bias"]).any()
    # the counter tells the window layers from the global one, three to one
    assert windows.value - cores[0] == 3 * (fulls.value - cores[1]) > 0


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
    assert "pallas" not in source.replace("no kernel", "")
