"""Expert-parallel MoE tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_parameter_server_for_ml_training_tpu.parallel import make_mesh
from distributed_parameter_server_for_ml_training_tpu.parallel.moe import (
    dense_reference, init_moe_params, make_moe_ffn)

E = 8   # experts == mesh size
D = 16
H = 32


@pytest.fixture(scope="module")
def params():
    return init_moe_params(jax.random.PRNGKey(0), D, H, E)


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh(E, axis_names=("expert",))


def test_moe_matches_dense_reference(devices, mesh8, params):
    """With generous capacity (no drops), distributed EP must equal the
    dense per-token computation."""
    tokens = jnp.asarray(
        np.random.default_rng(1).normal(size=(64, D)), jnp.float32)
    moe = make_moe_ffn(mesh8, capacity=64)
    out, stats = moe(params, tokens)
    ref = dense_reference(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    assert float(stats["drop_frac"]) == 0.0


def test_capacity_drops_tokens(devices, mesh8, params):
    """capacity=1: at most one token per expert per shard survives; dropped
    tokens produce exactly zero output (the residual carries them)."""
    tokens = jnp.asarray(
        np.random.default_rng(2).normal(size=(64, D)), jnp.float32)
    out, stats = make_moe_ffn(mesh8, capacity=1)(params, tokens)
    out = np.asarray(out)
    ref = np.asarray(dense_reference(params, tokens))
    zero_rows = np.all(out == 0.0, axis=1)
    assert zero_rows.any()  # something got dropped at capacity 1
    kept = ~zero_rows
    np.testing.assert_allclose(out[kept], ref[kept], rtol=1e-4, atol=1e-5)
    # drop_frac must agree with the observed zero rows
    np.testing.assert_allclose(float(stats["drop_frac"]),
                               zero_rows.mean(), atol=1e-6)


def test_moe_gradients_flow(devices, mesh8, params):
    tokens = jnp.asarray(
        np.random.default_rng(3).normal(size=(32, D)), jnp.float32)
    moe = make_moe_ffn(mesh8, capacity=32)

    def loss(params):
        return jnp.sum(moe(params, tokens)[0] ** 2)

    grads = jax.grad(loss)(params)
    # experts that received tokens get nonzero grads; router always does
    assert float(jnp.sum(jnp.abs(grads["router"]))) > 0
    assert float(jnp.sum(jnp.abs(grads["w1"]))) > 0
    for leaf in jax.tree_util.tree_leaves(grads):
        assert np.isfinite(np.asarray(leaf)).all()


def test_routing_stats_and_aux_loss(devices, mesh8, params):
    """Stats semantics: load/importance sum to 1, aux_loss >= 1 with
    equality only at perfectly uniform routing, and the aux loss is
    differentiable w.r.t. the ROUTER (through P_e; f_e is stop-graded)."""
    tokens = jnp.asarray(
        np.random.default_rng(5).normal(size=(128, D)), jnp.float32)
    moe = make_moe_ffn(mesh8, capacity=128)
    _, stats = moe(params, tokens)
    np.testing.assert_allclose(float(jnp.sum(stats["load"])), 1.0,
                               atol=1e-5)
    np.testing.assert_allclose(float(jnp.sum(stats["importance"])), 1.0,
                               atol=1e-5)
    assert float(stats["aux_loss"]) >= 1.0 - 1e-5

    g = jax.grad(lambda p: moe(p, tokens)[1]["aux_loss"])(params)
    assert float(jnp.sum(jnp.abs(g["router"]))) > 0
    # expert FFN weights don't feed the router distribution
    assert float(jnp.sum(jnp.abs(g["w1"]))) == 0.0


def test_aux_loss_balances_routing(devices, mesh8):
    """Minimizing the aux loss alone must drive a skewed router toward
    uniform load — the mechanism the MoE trainer relies on."""
    rng = np.random.default_rng(7)
    params = init_moe_params(jax.random.PRNGKey(1), D, H, E)
    # skew: bias the router strongly toward expert 0
    params["router"] = params["router"].at[:, 0].add(2.0)
    tokens = jnp.asarray(rng.normal(size=(256, D)), jnp.float32)
    moe = make_moe_ffn(mesh8, capacity=256)

    def imbalance(p):
        s = moe(p, tokens)[1]
        return float(jnp.max(s["load"]) / jnp.mean(s["load"])), s

    before, s0 = imbalance(params)
    grad_fn = jax.jit(jax.grad(lambda p: moe(p, tokens)[1]["aux_loss"]))
    p = params
    for _ in range(120):
        g = grad_fn(p)
        p = jax.tree_util.tree_map(lambda a, b: a - 2.0 * b, p, g)
    after, s1 = imbalance(p)
    assert before > 3.0          # the skew was real
    assert after < 1.5, (before, after)
    assert float(s1["aux_loss"]) < float(s0["aux_loss"])


def test_load_distribution_counted(devices, mesh8, params):
    """Routing statistics: every expert id in range; aggregate token count
    preserved."""
    tokens = jnp.asarray(
        np.random.default_rng(4).normal(size=(128, D)), jnp.float32)
    logits = tokens @ params["router"]
    expert_idx = np.asarray(jnp.argmax(logits, axis=-1))
    assert expert_idx.min() >= 0 and expert_idx.max() < E
    counts = np.bincount(expert_idx, minlength=E)
    assert counts.sum() == 128


# ---------------------------------------------------------------------------
# dp x ep composition (round-4 VERDICT weak 4)
# ---------------------------------------------------------------------------

def test_dp_ep_matches_dense_reference(devices):
    """(data=2, expert=4) mesh: with generous capacity the composed
    dp x ep MoE equals the dense per-token computation — routing and
    combine are per-token, so data-grouping must not change the math."""
    n_exp, dp = 4, 2
    mesh = make_mesh(dp, axis_names=("data", "expert"))
    params = init_moe_params(jax.random.PRNGKey(0), D, H, n_exp)
    tokens = jnp.asarray(
        np.random.default_rng(2).normal(size=(64, D)), jnp.float32)
    moe = make_moe_ffn(mesh, capacity=64, data_axis="data")
    out, stats = moe(params, tokens)
    ref = dense_reference(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    assert float(stats["drop_frac"]) == 0.0
    # stats are replicated across the WHOLE mesh and averaged over groups
    assert stats["load"].shape == (n_exp,)
    np.testing.assert_allclose(float(jnp.sum(stats["load"])), 1.0,
                               rtol=1e-5)


def test_dp_ep_gradients_include_data_psum(devices):
    """Expert-weight gradients must aggregate over the data axis: the
    dp x ep gradient equals the single-group gradient on the same global
    token batch (generous capacity)."""
    n_exp = 4
    params = init_moe_params(jax.random.PRNGKey(0), D, H, n_exp)
    tokens = jnp.asarray(
        np.random.default_rng(3).normal(size=(64, D)), jnp.float32)

    mesh_dp = make_mesh(2, axis_names=("data", "expert"))
    mesh_ep = make_mesh(n_exp, axis_names=("expert",),
                        devices=jax.devices()[:n_exp])
    moe_dp = make_moe_ffn(mesh_dp, capacity=64, data_axis="data")
    moe_ep = make_moe_ffn(mesh_ep, capacity=64)

    def loss(fn):
        def f(p):
            out, _ = fn(p, tokens)
            return jnp.sum(out ** 2)
        return f

    g_dp = jax.grad(loss(moe_dp))(params)
    g_ep = jax.grad(loss(moe_ep))(params)
    for k in params:
        np.testing.assert_allclose(np.asarray(g_dp[k]), np.asarray(g_ep[k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)


def test_dp_ep_trainer_smoke(devices):
    """MoETrainer with --dp-degree 2: (data=2, expert=4) mesh trains and
    reports routing stats."""
    from distributed_parameter_server_for_ml_training_tpu.data.cifar import (
        synthetic_cifar100)
    from distributed_parameter_server_for_ml_training_tpu.train.model_parallel \
        import ModelParallelConfig, MoETrainer

    ds = synthetic_cifar100(n_train=128, n_test=64, seed=0)
    cfg = ModelParallelConfig(model="vit_tiny", num_workers=4, dp_degree=2,
                              num_epochs=1, batch_size=64, augment=False,
                              num_classes=ds.num_classes, dtype="float32")
    trainer = MoETrainer(ds, cfg)
    assert trainer.mesh.shape == {"data": 2, "expert": 4}
    metrics = trainer.train()
    assert metrics["moe_dp_degree"] == 2
    assert metrics["n_experts"] == 4
    assert "moe_load_imbalance" in metrics


# -- the held-experts layer's gate and the softmax router ----------------------

def _held_layer_inputs(n=96, d=16, f=8, e=8, seed=0):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(n, d)), jnp.float32)
    logits = jnp.asarray(r.normal(size=(n, e)), jnp.float32)
    experts = {name: jnp.asarray(r.normal(size=shape) * 0.3, jnp.float32)
               for name, shape in (("gate", (e, d, f)), ("up", (e, d, f)),
                                   ("down", (e, f, d)))}
    return x, logits, experts


@pytest.mark.parametrize("k", [1, 2, 6])
def test_softmax_of_the_top_k_against_a_loop(k):
    """``route_top_k_softmax``: a token's ``k`` largest logits, weighted by
    a softmax over those ``k`` alone."""
    from distributed_parameter_server_for_ml_training_tpu.parallel import moe
    _x, logits, _experts = _held_layer_inputs()
    idx, weights = moe.route_top_k_softmax(logits, k)
    assert idx.shape == weights.shape == (96, k) and idx.dtype == jnp.int32
    for token in range(0, 96, 7):
        row = np.asarray(logits[token])
        chosen = np.argsort(-row)[:k]
        assert sorted(np.asarray(idx[token]).tolist()) == sorted(
            chosen.tolist())
        e = np.exp(row[chosen] - row[chosen].max())
        want = dict(zip(chosen.tolist(), (e / e.sum()).tolist()))
        for j, w in zip(np.asarray(idx[token]).tolist(),
                        np.asarray(weights[token]).tolist()):
            assert abs(w - want[j]) < 1e-6
    np.testing.assert_allclose(np.asarray(weights.sum(axis=1)), 1.0,
                               atol=1e-6)
    # the weights take gradient, and a shift of a token's logits none
    g = jax.grad(lambda l: jnp.sum(
        moe.route_top_k_softmax(l, k)[1][:, 0]))(logits)
    assert (k == 1) == (float(jnp.abs(g).max()) == 0.0)


def _unit_of(activation):
    """``(the activation's name, whether the experts have a gate)`` of a
    test's ``activation`` parameter: ``ungated:<name>`` is the two-matrix
    expert ``down(act(up u))``."""
    return activation.rpartition(":")[2], not activation.startswith("ungated")


@pytest.mark.parametrize("activation", ["relu", "silu", "ungated:relu2",
                                        "ungated:silu"])
def test_the_gates_activation_against_a_loop(activation):
    """``held_expert_ffn(activation=...)``: each held expert on the tokens
    that chose it, ``down(act(gate u) * (up u))`` or, of experts without a
    ``gate``, ``down(act(up u))``, forward and gradients, with experts 2..5
    of 8 held."""
    from distributed_parameter_server_for_ml_training_tpu.parallel import moe
    x, logits, experts = _held_layer_inputs()
    idx, weights = moe.route_top_k_softmax(logits, 2)
    first, count = 2, 4
    activation, gated = _unit_of(activation)
    held = {name: w[first:first + count] for name, w in experts.items()
            if gated or name != "gate"}
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu,
           "relu2": lambda u: jnp.maximum(u, 0) ** 2}[activation]

    def layer(x, held, weights):
        return moe.held_expert_ffn(x, idx, weights, held, first, rows=32,
                                   activation=activation)

    def loop(x, held, weights):
        out = jnp.zeros_like(x)
        for c in range(count):
            w = jnp.sum(jnp.where(idx == first + c, weights, 0.0), axis=1)
            hidden = act(x @ held["gate"][c]) * (x @ held["up"][c]) \
                if gated else act(x @ held["up"][c])
            out = out + w[:, None] * (hidden @ held["down"][c])
        return out

    y, processed = layer(x, held, weights)
    loads = moe.expert_loads(idx, 8)
    assert int(processed) == int(loads[first:first + count].sum())
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(loop(x, held, weights)), atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(layer(*a)[0] ** 2),
                   argnums=(0, 1, 2))(x, held, weights)
    want = jax.grad(lambda *a: jnp.sum(loop(*a) ** 2),
                    argnums=(0, 1, 2))(x, held, weights)
    assert set(got[1]) == set(held)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=1e-4)
    # the default is the gate it always had
    default, _ = moe.held_expert_ffn(x, idx, weights, held, first, rows=32)
    assert (activation == "silu") == bool(jnp.allclose(default, y))
    with pytest.raises(KeyError):
        moe.held_expert_ffn(x, idx, weights, held, first, rows=32,
                            activation="gelu")


def test_the_window_models_pass_plan():
    """16,384 tokens, 6 experts a token, 16 of 64 held: an even load of
    24,576 assignments in passes of 12,288 rows; the cell's stated capacity
    of 4.0 always runs all eight (98,304 rows: every assignment the
    sequence can give the held experts, and the deployment's even load)."""
    from distributed_parameter_server_for_ml_training_tpu.parallel import moe
    assert moe.pass_plan(16384, 6, 16, 64) == (12288, 1)
    assert moe.pass_plan(16384, 6, 16, 64, 4.0) == (12288, 8)
    assert moe.pass_plan(16384, 6, 16, 64, 9.0) == (12288, 8)


def test_the_latent_models_pass_plan():
    """16,384 tokens, 22 experts a token, 8 of 512 held: an even load of
    5,632 assignments in passes of 3,072 rows; the cell's stated capacity of
    1.5 always runs three (9,216 rows); a token can give the held experts 8
    assignments at most, 43 passes."""
    from distributed_parameter_server_for_ml_training_tpu.parallel import moe
    assert moe.pass_plan(16384, 22, 8, 512) == (3072, 1)
    assert moe.pass_plan(16384, 22, 8, 512, 1.5) == (3072, 3)
    assert moe.pass_plan(16384, 22, 8, 512, 99.0) == (3072, 43)


@pytest.mark.parametrize("min_passes", [1, 5])
def test_the_gather_combine_is_the_scatter_combine(min_passes):
    """``combine="gather"`` (a pass writes its rows where the sort put them,
    a token gathers its k rows at the end) against the default (a pass adds
    its rows into the tokens' sums): output, the count and every gradient,
    with fewer passes than the stated floor and with more."""
    from distributed_parameter_server_for_ml_training_tpu.parallel import moe
    x, logits, experts = _held_layer_inputs()
    idx, weights = moe.route_top_k_softmax(logits, 3)
    held = {name: w[1:6] for name, w in experts.items()}

    def layer(combine):
        def f(x, held, weights):
            y, done = moe.held_expert_ffn(
                x, idx, weights, held, 1, rows=32, min_passes=min_passes,
                activation="relu", combine=combine)
            return jnp.sum(y ** 2), (y, done)
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
            x, held, weights)

    (_l, (y, done)), grads = layer("gather")
    (_l2, (want, want_done)), want_grads = layer("scatter")
    assert int(done) == int(want_done) == int(
        moe.expert_loads(idx, 8)[1:6].sum())
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=1e-4)
    with pytest.raises(ValueError, match="combine"):
        moe.held_expert_ffn(x, idx, weights, held, 1, rows=32,
                            combine="sort")


# -- the backward pass's weight gradients, added in place (PR 35) --------------

#: rows a held expert has in one pass of 96 rows, tiles of 32 rows
GROUPED_GRAD_CASES = {
    "boundaries_off_the_tile_edges": (10, 3, 37, 17, 29),
    "experts_with_no_row": (0, 5, 0, 60, 0, 31),
    "boundaries_on_the_tile_edges": (32, 32, 0, 32),
    "only_the_first_expert": (96, 0, 0, 0, 0, 0),
    "slack_rows_on_the_last_expert": (7, 0, 9, 0, 0, 80),
    "only_slack_on_the_last_expert": (0, 0, 0, 0, 0, 96),
    "one_row_an_expert": (1, 1, 1, 1, 1, 91),
}


@pytest.mark.parametrize("case", sorted(GROUPED_GRAD_CASES))
def test_the_grouped_grad_kernel_against_a_loop(case, monkeypatch):
    """``grouped_grad_accumulate`` in interpret mode: ``acc[g] += lhs[rows of
    g]^T @ rhs[rows of g]`` in float32 into a non-zero carry, against a loop
    over the experts; an expert without a row comes back bit for bit."""
    from distributed_parameter_server_for_ml_training_tpu.ops.pallas import (
        grouped_grad as gg)
    monkeypatch.setattr(gg, "INTERPRET", True)
    monkeypatch.setattr(gg, "BLOCK_ROWS", 32)
    group = GROUPED_GRAD_CASES[case]
    m, k, n = sum(group), 128, 256
    r = np.random.default_rng(len(case))
    lhs = jnp.asarray(r.normal(size=(m, k)), jnp.bfloat16)
    rhs = jnp.asarray(r.normal(size=(m, n)), jnp.bfloat16)
    acc = jnp.asarray(r.normal(size=(len(group), k, n)), jnp.float32)
    assert gg.pick_blocks(m, k, n) == (32, 128, 256)
    got = np.asarray(jax.jit(gg.grouped_grad_accumulate)(
        acc, lhs, rhs, jnp.asarray(group, jnp.int32)))
    at = 0
    for g, size in enumerate(group):
        mine = slice(at, at + size)
        want = np.asarray(acc[g]) + (
            np.asarray(lhs[mine], np.float32).T
            @ np.asarray(rhs[mine], np.float32))
        if size:
            np.testing.assert_allclose(got[g], want, atol=2e-5, rtol=1e-6)
            assert not np.array_equal(got[g], np.asarray(acc[g]))
        else:
            assert np.array_equal(got[g], np.asarray(acc[g])), g
        at += size
    # the plan: every (group, tile) pair with a row once, in the rows' order
    _offsets, group_of, tile_of, visits = (
        np.asarray(a) for a in gg.visit_plan(
            jnp.asarray(group, jnp.int32), m, 32))
    ends = np.cumsum(group)
    pairs = [(g, t) for g, (lo, hi) in enumerate(zip(ends - group, ends))
             for t in range(m // 32) if max(lo, t * 32) < min(hi, t * 32 + 32)]
    assert list(zip(group_of[:visits[0]], tile_of[:visits[0]])) == pairs
    assert len(group_of) == m // 32 + len(group) - 1 >= visits[0]
    assert all((g, t) == pairs[-1] for g, t in
               zip(group_of[visits[0]:], tile_of[visits[0]:]))


def test_the_grouped_grad_kernels_tiles_follow_the_shapes():
    from distributed_parameter_server_for_ml_training_tpu.ops.pallas import (
        grouped_grad as gg)
    # the two cells' passes: SmallThinker 12,288 x 2,560 x 768, JoyAI 4,096
    # x 2,048 x 768, and the down projection's transposes of both
    assert gg.pick_blocks(12288, 2560, 768) == (512, 1280, 768)
    assert gg.pick_blocks(12288, 768, 2560) == (512, 768, 1280)
    assert gg.pick_blocks(4096, 2048, 768) == (512, 1024, 768)
    assert gg.pick_blocks(4096, 768, 2048) == (512, 768, 1024)
    # the latent experts' two matrices: 3,072 x 1,024 x 2,688 (21 x 128)
    assert gg.pick_blocks(3072, 1024, 2688) == (512, 1024, 896)
    assert gg.pick_blocks(3072, 2688, 1024) == (512, 896, 1024)
    # no tiles: a width off the 128 lanes, rows off bf16's 16 sublanes
    assert gg.pick_blocks(32, 16, 8) is None
    assert gg.pick_blocks(40, 128, 128) is None
    with pytest.raises(ValueError, match="no tiles"):
        gg.grouped_grad_accumulate(
            jnp.zeros((2, 16, 8)), jnp.zeros((32, 16)), jnp.zeros((32, 8)),
            jnp.asarray([16, 16], jnp.int32))
    with pytest.raises(ValueError, match="groups"):
        gg.grouped_grad_accumulate(
            jnp.zeros((3, 128, 128)), jnp.zeros((32, 128)),
            jnp.zeros((32, 128)), jnp.asarray([16, 16], jnp.int32))


def _parent_pass_grads(moe):
    """The parent's backward of a pass (PR 34): ``jax.vjp`` of the whole
    pass, then the whole carry added to; the reference the change is held
    to."""
    def pass_grads(rows_x, rows_w, experts, group, valid, activation,
                   d_part, de, impl):
        _out, vjp = jax.vjp(
            lambda rx, rw, ex: moe._pass_out(rx, rw, ex, group, valid,
                                             activation),
            rows_x, rows_w, experts)
        d_rows_x, d_rows_w, d_experts = vjp(d_part)
        return d_rows_x, d_rows_w, jax.tree_util.tree_map(
            jnp.add, de, d_experts)
    return pass_grads


@pytest.mark.parametrize("impl", ["xla", "in_place"])
@pytest.mark.parametrize("min_passes", [1, 3])
@pytest.mark.parametrize("activation", ["silu", "relu", "ungated:relu2"])
@pytest.mark.parametrize("combine", ["scatter", "gather"])
def test_the_backward_pass_against_the_parents(combine, activation,
                                               min_passes, impl,
                                               monkeypatch):
    """``_work_off``'s gradients (``dx``, ``dw`` and the expert leaves,
    three of a gated unit and two of an ungated one)
    with a pass's weight gradients added by ``_add_weight_grads``
    (XLA's route off the TPU; the kernel, here in interpret mode, on it)
    against the parent's formulation, in float32, for a list of 163
    assignments that ends in the middle of a pass of 64 rows."""
    from distributed_parameter_server_for_ml_training_tpu.ops import (
        attention)
    from distributed_parameter_server_for_ml_training_tpu.ops.pallas import (
        grouped_grad as gg)
    from distributed_parameter_server_for_ml_training_tpu.parallel import moe
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        get_registry)
    monkeypatch.setattr(gg, "INTERPRET", True)
    monkeypatch.setattr(gg, "BLOCK_ROWS", 32)
    monkeypatch.setattr(attention, "_on_tpu", lambda: impl == "in_place")
    x, logits, experts = _held_layer_inputs(n=80, d=128, f=128, e=8, seed=3)
    idx, weights = moe.route_top_k_softmax(logits, 3)
    activation, gated = _unit_of(activation)
    held = {name: w[2:7] for name, w in experts.items()
            if gated or name != "gate"}
    assert int(moe.expert_loads(idx, 8)[2:7].sum()) % 64

    def grads():
        def f(x, held, weights):
            y, _done = moe.held_expert_ffn(
                x, idx, weights, held, 2, rows=64, min_passes=min_passes,
                activation=activation, combine=combine)
            return jnp.sum(y ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(x, held, weights)

    counted = get_registry().counter("dps_moe_grad_accumulate_total",
                                     impl=impl)
    before = counted.value
    got = grads()
    assert counted.value == before + 1
    monkeypatch.setattr(moe, "_pass_grads", _parent_pass_grads(moe))
    want = grads()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=1e-4)


def _count_grad_visits(sizes, rows, min_passes):
    """(pass, expert) pairs with a row, pass by pass, as ``_pass_rows`` cuts
    them: the slack of a pass goes to the last expert."""
    ends = np.cumsum(sizes)
    starts, total = ends - sizes, int(ends[-1])
    passes = max(-(-total // rows), min_passes)
    visits = 0
    for p in range(passes):
        lo, hi = p * rows, (p + 1) * rows
        group = np.clip(ends, lo, hi) - np.clip(starts, lo, hi)
        group[-1] += rows - group.sum()
        visits += int((group > 0).sum())
    return visits, passes


@pytest.mark.parametrize("min_passes", [1, 3, 8])
@pytest.mark.parametrize("sizes", [
    (10, 3, 37, 17, 29),        # ends inside a pass
    (0, 5, 0, 60, 0, 31),
    (32, 32, 0, 32),            # ends on a pass's edge
    (7, 0, 9, 0, 0, 0),         # the last expert has slack alone
    (0, 0, 0, 0),               # nothing held: slack passes only
    (200, 1, 1, 1, 1, 1),       # more passes than the floor
    (31, 1, 0, 0),              # the list ends on an edge, the last empty
], ids=str)
def test_grad_visits_against_a_count(sizes, min_passes):
    from distributed_parameter_server_for_ml_training_tpu.parallel import moe
    sizes = np.asarray(sizes)
    visits, passes = jax.jit(moe.grad_visits, static_argnums=(1, 2))(
        jnp.asarray(sizes), 32, min_passes)
    assert (int(visits), int(passes)) == _count_grad_visits(
        sizes, 32, min_passes)
    assert int(passes) <= int(visits) <= len(sizes) + int(passes) - 1
