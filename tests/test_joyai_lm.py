"""The decoder LM (models/joyai.py) against its plain reference
(benchmarks/reference/joyai_reference.py) at a small size on the CPU, and the
pieces it brought: the held-experts layer, AdamW, token data, the LM task
through ``SyncTrainer``.

Small size: hidden 64, 4 heads of 24 + 8 / 16, 16 experts top-4, 3 layers
(1 dense + 2 expert) and the MTP module, vocabulary 512, sequences of 64.
"""

import importlib.util
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_parameter_server_for_ml_training_tpu.data import tokens as tk
from distributed_parameter_server_for_ml_training_tpu.models import (
    decoder, get_model, joyai)
from distributed_parameter_server_for_ml_training_tpu.parallel import moe
from distributed_parameter_server_for_ml_training_tpu.train.optimizers import (
    adamw)

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = joyai.PRESETS["tiny"]
B, T = 2, 64


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "joyai_reference", os.path.join(HERE, "..", "benchmarks",
                                        "reference", "joyai_reference.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()


def _setup(cfg=TINY, dtype=jnp.float32, seed=0, init_std=0.08):
    """Seeded weights (wider than the model's 0.006 so that every path
    carries signal at this depth), a seeded router bias, one batch."""
    cfg = replace(cfg, init_std=init_std)
    model = get_model("joyai_llm_flash", dtype=dtype, config=cfg)
    r = np.random.default_rng(seed)
    tokens = jnp.asarray(r.integers(0, cfg.vocab_size, (B, T + 2)),
                         jnp.int32)
    bias = jnp.asarray(r.normal(size=(cfg.expert_layers,
                                      cfg.n_routed_experts)) * 0.02,
                       jnp.float32)
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


def _worst(grads, want, l2=False, median=False):
    """Over the tensors, the largest (with ``median`` the median) of: max
    |a - b| over the tensor's largest |b|; with ``l2`` the norm of a - b
    over the norm of b."""
    def error(a, b):
        if l2:
            return float(jnp.linalg.norm((a - b).ravel())
                         / jnp.linalg.norm(b.ravel()))
        return float(jnp.max(jnp.abs(a - b))
                     / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30))
    rows = jax.tree_util.tree_map(error, grads, want)
    flat = jax.tree_util.tree_leaves_with_path(rows)
    if median:
        return float(np.median([v for _p, v in flat])), "median"
    path, value = max(flat, key=lambda kv: kv[1])
    return value, jax.tree_util.keystr(path)


#: float32 program against the float32 reference: both sum the same terms
#: in different orders (sorted groups against a dense one-hot, a chunked
#: loss, fused norms), so they differ by float32 rounding through 4 blocks:
#: 1e-6 to 1e-5 of a tensor's largest value measured; 1e-4 leaves ten times
#: that and is a hundred times under what bf16 compute gives.
TIGHT = 1e-4
#: bf16 compute (float32 router, softmax, norms and loss) against the same,
#: by the norm of the difference over the norm of the tensor: 8 bits of
#: mantissa through 4 blocks give 0.02 to 0.06 for most tensors. A few
#: tokens' k-th and (k+1)-th experts change places under that rounding, and
#: each moves a whole token's term from one expert's rows to another's:
#: expert and router tensors read 0.08 to 0.3 at 32 tokens an expert (and
#: 0.45 by the largest element, which is why the norm is compared), and
#: which tokens flip changes with any change in summation order. So the
#: worst tensor gets a wide limit and the median tensor the telling one.
LOOSE = 0.6
LOOSE_MEDIAN = 0.1


@pytest.fixture(scope="module")
def f32_case():
    cfg, model, params, bias, tokens = _setup()
    want = ref.loss_and_grads(params, bias, tokens, cfg)
    return cfg, model, params, bias, tokens, want


def test_float32_step_matches_the_reference(f32_case):
    cfg, model, params, bias, tokens, (want_loss, aux, want) = f32_case
    loss, out, grads = _program(model, params, bias, tokens)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert abs(float(out["mtp_loss"]) - float(aux["mtp_loss"])) < 1e-4
    np.testing.assert_array_equal(np.asarray(out["loads"]),
                                  np.asarray(aux["loads"]))
    worst, where = _worst(grads, want)
    assert worst < TIGHT, (worst, where)
    assert set(jax.tree_util.tree_structure(grads).node_data()[1]) \
        == set(jax.tree_util.tree_structure(want).node_data()[1])


def test_bf16_compute_passes_the_loose_tolerance_and_fails_the_tight(
        f32_case):
    """Lower precision than stated fails the stated tolerance: the same
    weights in bf16 compute are inside LOOSE and far outside TIGHT."""
    cfg, _model, params, bias, tokens, (want_loss, _aux, want) = f32_case
    model = get_model("joyai_llm_flash", dtype=jnp.bfloat16, config=cfg)
    loss, _out, grads = _program(model, params, bias, tokens)
    worst, where = _worst(grads, want, l2=True)
    assert TIGHT * 10 < worst < LOOSE, (worst, where)
    middle = _worst(grads, want, l2=True, median=True)[0]
    assert TIGHT * 10 < middle < LOOSE_MEDIAN, middle
    assert _worst(grads, want)[0] > TIGHT * 10
    assert abs(float(loss) - float(want_loss)) < 2e-3 * float(want_loss)


def test_the_reference_in_bf16_is_not_the_reference(f32_case):
    cfg, _model, params, bias, tokens, (_loss, _aux, want) = f32_case
    _l, _a, low = ref.loss_and_grads(params, bias, tokens, cfg,
                                     dtype=jnp.bfloat16)
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), low)
    assert _worst(low, want)[0] > TIGHT * 10


def test_reference_blocking_and_remat_change_nothing(f32_case):
    cfg, _model, params, bias, tokens, (want_loss, _aux, want) = f32_case
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
    for a, b in zip(got, want):
        assert a.shape == (B, 3, cfg.vocab_size)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4 * float(jnp.max(jnp.abs(b))))


def test_rope_is_interleaved_not_half_split():
    """Pairs are (x[2i], x[2i+1]); the half-split convention, pairs
    (x[i], x[i + R/2]), gives other numbers."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 5, 2, 8)),
                    jnp.float32)
    got = decoder.rope_interleaved(x, 32e6)
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(ref.rope(x[0], 32e6)), atol=1e-6)
    theta = 32e6 ** (-np.arange(0, 8, 2) / 8)
    angle = np.arange(5)[:, None] * theta[None]
    lo, hi = np.asarray(x[0, ..., :4]), np.asarray(x[0, ..., 4:])
    cos, sin = np.cos(angle)[:, None], np.sin(angle)[:, None]
    half = np.concatenate([lo * cos - hi * sin, lo * sin + hi * cos], -1)
    assert np.abs(np.asarray(got[0]) - half).max() > 0.1
    np.testing.assert_allclose(np.asarray(got[0, 0]), np.asarray(x[0, 0]),
                               atol=1e-7)     # position 0 is not rotated


@pytest.mark.parametrize("shape", [(2, 9, 4, 8), (2, 9, 1, 64)],
                         ids=["q_rope[B,T,H,R]", "k_rope[B,T,1,R]"])
def test_half_split_rotation_is_the_interleaved_one_on_permuted_lanes(shape):
    """``rope_half_split`` of lanes in ``half_split_lanes`` order is that
    permutation of ``rope_interleaved``: the same products and angles.
    The model holds the array with T next to the lanes (heads-major q, the
    shared k_rope without a head axis)."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=shape), jnp.float32)
    lanes = decoder.half_split_lanes(shape[-1])
    assert sorted(lanes) == list(range(shape[-1]))
    np.testing.assert_array_equal(lanes[:4], [0, 2, 4, 6])
    want = decoder.rope_interleaved(x, 32e6)[..., lanes]       # [B, T, H, R]
    got = decoder.rope_half_split(
        jnp.swapaxes(x[..., lanes], 1, 2), 32e6)             # [B, H, T, R]
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(got, 1, 2)),
                               np.asarray(want), atol=1e-6)
    assert float(jnp.max(jnp.abs(want - x[..., lanes]))) > 0.1


def test_mla_and_its_gradients_match_the_reference(f32_case):
    """``MLA`` in float32 against ``ref.mla``: the output to 1e-5 and the
    gradient with respect to every parameter, the four whose weights are
    cut and reordered inside (``q_b``, ``kv_a``, ``kv_b``, ``o``) to every
    column, rotary ones included."""
    cfg, _model, params, _bias, _tokens, _want = f32_case
    p = params["layer_1"]["attn"]
    r = np.random.default_rng(4)
    u = jnp.asarray(r.normal(size=(2, T, 64)), jnp.float32)
    cot = jnp.asarray(r.normal(size=(2, T, 64)), jnp.float32)
    mla = joyai.MLA(cfg, jnp.float32)

    def program(p):
        return jnp.sum(mla.apply({"params": p}, u) * cot)

    def reference(p):
        return jnp.sum(jnp.stack([ref.mla(p, x, cfg, None) for x in u]) * cot)

    np.testing.assert_allclose(
        np.asarray(mla.apply({"params": p}, u)),
        np.asarray(jnp.stack([ref.mla(p, x, cfg, None) for x in u])),
        atol=1e-5)
    got, want = jax.grad(program)(p), jax.grad(reference)(p)
    assert set(got) == {"q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
                        "kv_b", "o"}
    for name in got:
        (g,), (w,) = (jax.tree_util.tree_leaves(t[name])
                      for t in (got, want))
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), err_msg=name,
            atol=1e-5 * float(jnp.max(jnp.abs(w))))
    rope = cfg.qk_rope_head_dim           # the rotary columns carry signal
    assert float(jnp.min(jnp.max(jnp.abs(
        want["kv_a"]["kernel"][:, -rope:]), axis=0))) > 0
    assert float(jnp.min(jnp.max(jnp.abs(want["q_b"]["kernel"]),
                                 axis=0))) > 0


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.mark.parametrize("core", ["flash", "dense"])
def test_mla_forward_moves_no_activation_between_layouts(core, monkeypatch):
    """What ``MLA``'s forward holds between its matmuls and its attention
    core, read off the jaxpr at the tiny preset's widths (v heads of 128 so
    that the kernels' block specs apply, 1,024 tokens so that
    ``select_core`` says ``flash`` for bf16 once the backend probe is
    steered): no reshape of anything to a trailing axis of 2 (the
    interleaved rotation's lane shuffle), and no transpose but the three
    per-head projections' own (``einsum("btc,chd->bhtd")`` is a
    ``dot_general`` and the transpose that names its output heads-major:
    the compiler writes the matmul's result in that order). q and k as
    concatenated, v and o are never transposed or cut. On the dense path
    (``core="dense"``, what the CPU runs) ``dense_core`` is handed v split
    into heads and hands o back: two more, on the way into and out of its
    einsums."""
    from distributed_parameter_server_for_ml_training_tpu.ops import (
        attention as at)
    cfg = replace(TINY, v_head_dim=128)
    dtype = jnp.bfloat16 if core == "flash" else jnp.float32
    monkeypatch.setattr(at, "_on_tpu", lambda: core == "flash")
    mla = joyai.MLA(cfg, dtype)
    u = jnp.zeros((1, 1024, cfg.hidden_size), dtype)
    params = jax.eval_shape(lambda: mla.init(jax.random.PRNGKey(0), u))
    eqns = list(_equations(jax.make_jaxpr(
        lambda p, u: mla.apply(p, u))(params, u).jaxpr))
    names = [e.primitive.name for e in eqns]
    assert ("pallas_call" in names) == (core == "flash")
    by_output = {id(v): e for e in eqns for v in e.outvars}
    def activation(v):       # T rows; no weight of this size has 1,024
        return hasattr(v, "aval") and 1024 in v.aval.shape

    for e in eqns:
        if e.primitive.name == "reshape" and activation(e.invars[0]):
            assert e.outvars[0].aval.shape[-1] != 2, e
    transposed = [e for e in eqns if e.primitive.name == "transpose"
                  and activation(e.invars[0])]
    from_matmul = [e for e in transposed if by_output.get(
        id(e.invars[0])) is not None and by_output[
            id(e.invars[0])].primitive.name == "dot_general"]
    h, width = cfg.num_attention_heads, cfg.qk_nope_head_dim \
        + cfg.qk_rope_head_dim
    from_matmul = [e for e in from_matmul if e.outvars[0].aval.shape in (
        (1, h, 1024, cfg.qk_nope_head_dim),
        (1, h, 1024, cfg.qk_rope_head_dim))]
    assert len(from_matmul) == 3                 # q_nope, q_rope, k_nope
    rest = [e for e in transposed if e not in from_matmul]
    if core == "flash":
        assert not rest, rest
    else:
        # v into dense_core's heads-major einsum, the einsum's own output
        # order, o back
        assert len(rest) <= 3 and all(
            e.invars[0].aval.shape[-1] == cfg.v_head_dim for e in rest), rest
    sliced = [e for e in eqns if e.primitive.name == "slice"
              and activation(e.invars[0])
              and e.invars[0].aval.shape[-1] in (
                  width, cfg.qk_nope_head_dim + cfg.v_head_dim)]
    assert not sliced, sliced            # weights are cut, not q or kv


#: the parameter tree of ``JoyAILM`` at the tiny preset as PR 28 wrote it
#: (sorted ``path shape dtype`` lines, sha256): a checkpoint of that tree
#: restores into this model
TINY_TREE = (67, "72c3edbf679dd0f557ba8727124a52fe3c4ad86adaeb00e9d44d6a1c"
                 "48018ad5")


def test_the_parameter_tree_is_what_checkpoints_hold(f32_case):
    import hashlib
    cfg, _model, params, _bias, _tokens, _want = f32_case
    rows = sorted(f"{jax.tree_util.keystr(path)} {tuple(leaf.shape)} "
                  f"{leaf.dtype}" for path, leaf in
                  jax.tree_util.tree_leaves_with_path(params))
    assert (len(rows), hashlib.sha256(
        "\n".join(rows).encode()).hexdigest()) == TINY_TREE
    h, nope, rope, vd = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    attn = {name: tuple(jax.tree_util.tree_leaves(leaf)[0].shape)
            for name, leaf in params["layer_0"]["attn"].items()}
    assert attn == {
        "q_a": (cfg.hidden_size, cfg.q_lora_rank),
        "q_a_norm": (cfg.q_lora_rank,),
        "q_b": (cfg.q_lora_rank, h * (nope + rope)),
        "kv_a": (cfg.hidden_size, cfg.kv_lora_rank + rope),
        "kv_a_norm": (cfg.kv_lora_rank,),
        "kv_b": (cfg.kv_lora_rank, h * (nope + vd)),
        "o": (h * vd, cfg.hidden_size)}
    # a state dict of that tree (what a checkpoint holds) restores
    from flax import serialization
    state = serialization.to_state_dict(
        jax.tree_util.tree_map(np.asarray, params))
    restored = serialization.from_state_dict(params, state)
    assert jax.tree_util.tree_structure(restored) \
        == jax.tree_util.tree_structure(params)


def test_k_rope_is_one_vector_shared_by_the_heads(f32_case):
    """The program against a variant of the reference in which each head
    takes its own slice of a wider ``k_rope``: the shared one matches, and
    feeding the heads different vectors does not."""
    cfg, model, params, bias, tokens, _want = f32_case
    p = params["layer_0"]["attn"]
    u = jnp.asarray(np.random.default_rng(3).normal(size=(T, 64)),
                    jnp.float32)
    want = ref.mla(p, u, cfg, None)
    got = joyai.MLA(cfg, jnp.float32).apply({"params": p}, u[None])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)
    # per-head k_rope: rotate a different vector for every head
    kv_a = u @ p["kv_a"]["kernel"]
    k_rope = kv_a[:, cfg.kv_lora_rank:]
    per_head = jnp.stack([jnp.roll(k_rope, h, axis=-1)
                          for h in range(cfg.num_attention_heads)], axis=1)
    assert float(jnp.max(jnp.abs(per_head[:, 1] - per_head[:, 0]))) > 0.01

    def mla_per_head(u):
        # the reference's mla with k_rope[T, R] replaced by [T, H, R]
        t = u.shape[0]
        h, nope, rp, vd = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                           cfg.qk_rope_head_dim, cfg.v_head_dim)
        c_q = ref.rms_norm(u @ p["q_a"]["kernel"], p["q_a_norm"]["scale"],
                           cfg.rms_norm_eps)
        q = (c_q @ p["q_b"]["kernel"]).reshape(t, h, nope + rp)
        c_kv = ref.rms_norm(kv_a[:, :cfg.kv_lora_rank],
                            p["kv_a_norm"]["scale"], cfg.rms_norm_eps)
        kv = (c_kv @ p["kv_b"]["kernel"]).reshape(t, h, nope + vd)
        s = (jnp.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope])
             + jnp.einsum("qhr,khr->hqk", ref.rope(q[..., nope:], 32e6),
                          ref.rope(per_head, 32e6)))
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None],
                      s / np.sqrt(nope + rp), -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                       kv[..., nope:])
        return o.reshape(t, h * vd) @ p["o"]["kernel"]

    other = mla_per_head(u)
    assert float(jnp.max(jnp.abs(other - want))) \
        > 100 * float(jnp.max(jnp.abs(got - want)))


def _layer_inputs(n=96, d=16, f=8, e=16, seed=0):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(n, d)), jnp.float32)
    router = jnp.asarray(r.normal(size=(d, e)), jnp.float32)
    experts = {name: jnp.asarray(r.normal(size=shape) * 0.3, jnp.float32)
               for name, shape in (("gate", (e, d, f)), ("up", (e, d, f)),
                                   ("down", (e, f, d)))}
    return x, router, experts


def test_no_token_is_dropped_when_one_expert_takes_half_the_tokens():
    """A bias that sends every token to expert 0 first: it gets N of the
    N * k assignments, eight times an even share, and computes all of them
    in passes of 32 rows."""
    x, router, experts = _layer_inputs()
    n, e, k = x.shape[0], 16, 2
    scores = jax.nn.sigmoid(x @ router)
    bias = jnp.zeros((e,)).at[0].set(10.0)
    idx, weights = moe.route_top_k(scores, bias, k, scaling=2.5)
    loads = moe.expert_loads(idx, e)
    assert int(loads[0]) == n and int(loads.sum()) == n * k
    held = {name: w[:4] for name, w in experts.items()}
    y, processed = moe.held_expert_ffn(x, idx, weights, held, 0, rows=32)
    assert int(processed) == int(loads[:4].sum()) >= n
    want = jnp.zeros_like(x)
    for c in range(4):
        w = jnp.sum(jnp.where(idx == c, weights, 0.0), axis=1)
        out = (jax.nn.silu(x @ held["gate"][c]) * (x @ held["up"][c])) \
            @ held["down"][c]
        want = want + w[:, None] * out
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    # a stated capacity's slack rows (zero rows given to the last held
    # expert) change nothing: the same output and gradients with 1 or 9
    # passes always run
    def total(x, experts, passes):
        return jnp.sum(moe.held_expert_ffn(
            x, idx, weights, experts, 0, rows=32, min_passes=passes)[0] ** 2)
    for passes in (1, 9):
        got = jax.grad(total, argnums=(0, 1))(x, held, passes)
        ref_g = jax.grad(total, argnums=(0, 1))(x, held, 1)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(ref_g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
    y9, _ = moe.held_expert_ffn(x, idx, weights, held, 0, rows=32,
                                min_passes=9)
    np.testing.assert_allclose(np.asarray(y9), np.asarray(want), atol=1e-5)
    # the plan at the cell's sizes: passes of 4,096 rows, as many as the
    # routing needs; with the cell's stated capacity (2.5 x the even load of
    # 8,192) five always
    assert moe.pass_plan(16384, 8, 16, 256) == (4096, 1)
    assert moe.pass_plan(16384, 8, 16, 256, 2.5) == (4096, 5)
    rows, passes = moe.pass_plan(n, k, 4, e, 2.5)
    assert rows <= n * min(k, 4) and rows * passes <= n * min(k, 4)


def test_a_trip_count_that_stops_short_shows_as_dropped(monkeypatch):
    """``processed`` is counted pass by pass, not taken from the routing:
    with one pass too few it falls short of the held experts' loads, which
    is what ``moe_dropped`` (train/tasks.py) reports."""
    n, d, f, k, e = 64, 16, 8, 2, 8
    key = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(key[0], (n, d))
    idx = jax.random.randint(key[1], (n, k), 0, 4)      # all to held experts
    weights = jnp.ones((n, k)) / k
    held = {"gate": jax.random.normal(key[2], (4, d, f)),
            "up": jax.random.normal(key[3], (4, d, f)),
            "down": jax.random.normal(key[4], (4, f, d))}
    _, processed = moe.held_expert_ffn(x, idx, weights, held, 0, rows=32)
    assert int(processed) == n * k
    passes = moe._passes
    monkeypatch.setattr(moe, "_passes",
                        lambda total, rows, floor: passes(total, rows,
                                                          floor) - 1)
    _, short = moe.held_expert_ffn(x, idx, weights, held, 0, rows=32)
    assert int(short) == n * k - 32


def test_the_bias_update_raises_the_starved_and_lowers_the_crowded():
    loads = jnp.asarray([10, 0, 5, 5])
    new = moe.bias_update(jnp.zeros((4,)), loads, 0.001)
    np.testing.assert_allclose(np.asarray(new),
                               [-0.001, 0.001, 0.0, 0.0], atol=1e-9)
    # and the bias steers the choice without entering the weights
    scores = jnp.asarray([[0.6, 0.5, 0.4, 0.3]])
    idx, w = moe.route_top_k(scores, jnp.asarray([0.0, 0.0, 0.0, 1.0]), 2,
                             normalize=True)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 3]
    np.testing.assert_allclose(float(w.sum()), 1.0, atol=1e-6)
    np.testing.assert_allclose(sorted(np.asarray(w[0]).tolist()),
                               [0.3 / 0.9, 0.6 / 0.9], atol=1e-6)


def test_four_shares_of_four_experts_add_up_to_the_whole_layer(f32_case):
    """The test that ties the share to the model: 16 experts in shares of
    4; the four partial results, the shared expert counted once, are the
    uncut reference's layer output."""
    cfg, _model, params, bias, _tokens, _want = f32_case
    p = params["layer_1"]["moe"]
    u = jnp.asarray(np.random.default_rng(5).normal(size=(1, T, 64)),
                    jnp.float32)
    whole, loads = ref.expert_layer(p, u[0], bias[0], cfg)
    shared = ref.swiglu(p["shared"], u[0])
    total = jnp.zeros_like(whole)
    for first in range(0, 16, 4):
        share = replace(cfg, held_experts=(first, 4))
        mine = dict(p, **{f"experts_{n}": p[f"experts_{n}"][first:first + 4]
                          for n in ("gate", "up", "down")})
        y, share_loads, processed = decoder.ExpertLayer(
            share, jnp.float32, shared_experts=1).apply(
                {"params": mine}, u, bias[0])
        np.testing.assert_array_equal(np.asarray(share_loads),
                                      np.asarray(loads))
        assert int(processed) == int(loads[first:first + 4].sum())
        total = total + (y[0] - shared)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole), atol=2e-5)


def test_adamw_is_optaxs_on_one_tensor():
    r = np.random.default_rng(0)
    params = {"w": jnp.asarray(r.normal(size=(4, 3)), jnp.float32)}
    ours, theirs = adamw(1e-2), optax.adamw(
        1e-2, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
    a, b = params, params
    sa, sb = ours.init(a), theirs.init(b)
    for i in range(3):
        g = {"w": jnp.asarray(r.normal(size=(4, 3)), jnp.float32)}
        ua, sa = ours.update(g, sa, a)
        ub, sb = theirs.update(g, sb, b)
        a, b = optax.apply_updates(a, ua), optax.apply_updates(b, ub)
    np.testing.assert_allclose(np.asarray(a["w"]), np.asarray(b["w"]),
                               rtol=1e-6)
    assert sa[0].mu["w"].dtype == jnp.float32
    # vectors (norm gains) are not decayed
    gain = {"g": jnp.ones((3,))}
    u, _ = ours.update({"g": jnp.zeros((3,))}, ours.init(gain), gain)
    assert float(jnp.abs(u["g"]).max()) == 0.0


def test_the_references_update_is_the_programs_on_numpy_arrays():
    """``adamw_step`` and ``bias_step`` (plain arithmetic, the comparison on
    the chip runs them on the host) against the program's optimizer and
    bias rule over three steps: a matrix is decayed, a vector is not."""
    r = np.random.default_rng(1)
    values = dict(b1=0.8, b2=0.9, eps=1e-6, weight_decay=0.2)
    tx = adamw(1e-2, **values)
    params = {"w": jnp.asarray(r.normal(size=(4, 3)), jnp.float32),
              "g": jnp.ones((5,), jnp.float32)}
    state = tx.init(params)
    mine = {k: (np.asarray(v), np.zeros(v.shape, np.float32),
                np.zeros(v.shape, np.float32)) for k, v in params.items()}
    for count in (1, 2, 3):
        grads = {k: jnp.asarray(r.normal(size=v.shape), jnp.float32)
                 for k, v in params.items()}
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        mine = {k: ref.adamw_step(p, np.asarray(grads[k]), mu, nu, count,
                                  learning_rate=1e-2, **values)
                for k, (p, mu, nu) in mine.items()}
    for k in params:
        assert isinstance(mine[k][0], np.ndarray)
        np.testing.assert_allclose(mine[k][0], np.asarray(params[k]),
                                   rtol=2e-6)
        np.testing.assert_allclose(mine[k][1], np.asarray(state[0].mu[k]),
                                   rtol=2e-6)
        np.testing.assert_allclose(mine[k][2], np.asarray(state[0].nu[k]),
                                   rtol=2e-6)
    loads = np.asarray([10, 0, 5, 5])
    np.testing.assert_allclose(
        ref.bias_step(np.zeros(4, np.float32), loads, 0.001),
        np.asarray(moe.bias_update(jnp.zeros((4,)), jnp.asarray(loads),
                                   0.001)), atol=1e-9)


def test_the_lm_task_builds_adamw_from_the_runs_values():
    """``DistributedConfig.optimizer`` reaches the optimizer: a benchmark
    configuration's b1, b2, eps and weight decay are what trains."""
    from distributed_parameter_server_for_ml_training_tpu.train.distributed \
        import DistributedConfig
    from distributed_parameter_server_for_ml_training_tpu.train.tasks \
        import LMTask
    params = {"w": jnp.ones((2, 2), jnp.float32)}
    grads = {"w": jnp.full((2, 2), 0.5, jnp.float32)}

    def first_update(cfg):
        tx = LMTask().make_optimizer(cfg)
        return np.asarray(tx.update(grads, tx.init(params), params)[0]["w"])

    default = first_update(DistributedConfig(learning_rate=1e-2))
    stated = first_update(DistributedConfig(
        learning_rate=1e-2, optimizer={"weight_decay": 0.0}))
    np.testing.assert_allclose(default, -1e-2 * (1.0 + 0.1), rtol=1e-5)
    np.testing.assert_allclose(stated, -1e-2, rtol=1e-5)


def test_token_data_is_seeded_heavy_tailed_and_packed():
    a = tk.synthetic_documents(vocab_size=512, seq_len=256, n_train=16,
                               n_test=2, seed=2**31 + 3, median_len=60)
    b = tk.synthetic_documents(vocab_size=512, seq_len=256, n_train=16,
                               n_test=2, seed=2**31 + 3, median_len=60)
    np.testing.assert_array_equal(a.train, b.train)
    assert a.train.shape == (16, 258) and a.test.shape == (2, 258)
    assert a.train.dtype == np.int32
    assert 0 <= a.train.min() and a.train.max() < 512
    # a row's two extra tokens are the next row's first two
    np.testing.assert_array_equal(a.train[0, 256:], a.train[1, :2])
    assert 0.0 < a.packing_waste < 0.2
    stream, docs, _text = tk.generate_stream(200_000, 512, 1)
    lengths = np.diff(np.flatnonzero(np.concatenate(
        [[True], stream == tk.EOD])))
    assert np.median(lengths) < 600 < 4096 < lengths.max()
    batches = list(tk.make_token_batches(a.train, 4, seed=0))
    assert len(batches) == 4 and batches[0].shape == (4, 258)


def _tiny_trainer(tmp_path=None, epochs=3, seed=0):
    from distributed_parameter_server_for_ml_training_tpu.train.distributed \
        import DistributedConfig, SyncTrainer
    data = tk.synthetic_documents(vocab_size=512, seq_len=64, n_train=32,
                                  n_test=4, seed=seed, median_len=40)
    return SyncTrainer(data, DistributedConfig(
        num_workers=2, batch_size=2, learning_rate=3e-3, num_epochs=epochs,
        model="joyai_llm_flash", dtype="float32", seed=seed))


def test_sync_trainer_trains_the_tiny_lm_and_the_loss_falls(devices, capsys):
    from distributed_parameter_server_for_ml_training_tpu.telemetry import (
        get_registry)
    reg = get_registry()
    before = {w: reg.counter("dps_moe_tokens_routed_total", where=w).value
              for w in ("held", "absent")}
    tokens_before = reg.counter("dps_trainer_tokens_total",
                                mode="sync").value
    trainer = _tiny_trainer(epochs=4)
    metrics = trainer.train()
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("[sync x2] epoch")]
    losses = [float(l.split("loss ")[1].split()[0]) for l in lines]
    assert len(losses) == 4 and losses[-1] < losses[0] - 0.3, losses
    assert metrics["global_steps_completed"] == 4 * 8
    assert len(trainer.test_accuracies) == 4
    assert 0.0 <= trainer.test_accuracies[-1] <= 1.0
    # every assignment is counted once: tokens x k a step, all held here
    tokens = 32 * 64 * 4
    assert reg.counter("dps_trainer_tokens_total",
                       mode="sync").value - tokens_before == tokens
    held = reg.counter("dps_moe_tokens_routed_total",
                       where="held").value - before["held"]
    assert held == tokens * 4 * 3        # top-4, 2 expert layers + MTP's
    assert reg.counter("dps_moe_tokens_routed_total",
                       where="absent").value == before["absent"]
    assert reg.counter("dps_moe_tokens_dropped_total").value == 0
    assert reg.gauge("dps_moe_load_max_over_mean").value >= 1.0
    # one pass a layer here, so every held expert with a row is a visit
    assert 1 / 16 <= reg.gauge("dps_moe_grad_visits_share").value <= 1.0
    # the bias moved, by steps of gamma
    bias = np.asarray(trainer.state.batch_stats["router_bias"])
    assert np.abs(bias).max() > 0 and np.abs(bias).max() <= 0.001 * 32 + 1e-9


def test_resume_restores_the_moments_and_the_bias(devices, tmp_path):
    first = _tiny_trainer(epochs=1)
    first.train(checkpoint_dir=str(tmp_path))
    want = jax.device_get((first.state.opt_state, first.state.batch_stats,
                           first.state.params))
    second = _tiny_trainer(epochs=1)
    second.train(checkpoint_dir=str(tmp_path), resume=True)   # nothing left
    got = jax.device_get((second.state.opt_state, second.state.batch_stats,
                          second.state.params))
    assert int(second.state.step) == 8
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mu = jax.tree_util.tree_leaves(got[0][0].mu)
    assert max(float(np.abs(m).max()) for m in mu) > 0


def test_the_two_reference_copies_are_byte_identical():
    with open(os.path.join(HERE, "reference", "joyai_reference.py"),
              "rb") as a, open(os.path.join(
                  HERE, "..", "benchmarks", "reference",
                  "joyai_reference.py"), "rb") as b:
        assert a.read() == b.read()


def test_the_ep16_preset_is_the_cut_the_issue_reckons():
    """680,441,088 trainable elements with the router bias (5 x 256) counted
    as the issue counts it: 1 dense + 4 expert layers, the MTP module, an
    eighth of the vocabulary, experts 0..15 of 256."""
    cfg = joyai.PRESETS["ep16"]
    model = get_model("joyai_llm_flash", config="ep16")
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 10), jnp.int32),
                           jnp.zeros((cfg.expert_layers, 256))))["params"]
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert n + cfg.expert_layers * cfg.n_routed_experts == 680_441_088
    assert cfg.held_experts == (0, 16) and cfg.vocab_size * 8 == 129_280
