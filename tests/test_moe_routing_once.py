"""An expert layer routes once a step (parallel/moe.py:KEEP_ROUTING).

A decoder recomputes a block at a time in its backward pass. What an expert
layer decides in integers (the chosen experts, the assignments' sorted
order, the held experts' bounds in it) takes no gradient and would be
rebuilt identically, so the block's ``nn.remat`` keeps what is named
``moe.ROUTING`` and the recomputation neither selects nor sorts: the
gradient of a model holds one ``top_k`` and one ``sort`` an expert layer,
and is the same gradient to the bit.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_parameter_server_for_ml_training_tpu.models import get_model
from distributed_parameter_server_for_ml_training_tpu.models.registry import (
    lm_config)
from distributed_parameter_server_for_ml_training_tpu.parallel import moe
from distributed_parameter_server_for_ml_training_tpu.telemetry import (
    get_registry)


def _primitives(jaxpr) -> collections.Counter:
    """How often each primitive stands in ``jaxpr`` and in the jaxprs its
    equations hold (a ``checkpoint``'s, a loop's body), each counted as
    written, once."""
    counts = collections.Counter(e.primitive.name for e in jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for inner in jax.core.jaxprs_in_params(eqn.params):
            counts += _primitives(inner)
    return counts


def _tiny(name: str, remat: bool):
    """``(loss of the parameters, parameters, expert layers)`` of decoder
    ``name``'s ``tiny`` preset in float32 on two rows of 32 tokens."""
    mc = lm_config(name)
    model = get_model(name, dtype=jnp.float32, config=mc).clone(remat=remat)
    bias = jnp.zeros((mc.expert_layers, mc.n_routed_experts), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 34), 0,
                                mc.vocab_size)
    params = jax.jit(lambda rng: model.init(rng, tokens, bias)["params"])(
        jax.random.PRNGKey(0))
    # expert_layers: the blocks with a bias row, JoyAI's MTP block among them
    return (lambda p: model.apply({"params": p}, tokens, bias)["loss"],
            params, mc.expert_layers)


@pytest.mark.parametrize("name", ["smallthinker", "nemotron_h",
                                  "joyai_llm_flash"])
def test_a_recomputed_decoder_selects_and_sorts_once_a_layer(name):
    """The gradient's jaxpr of each decoder's ``tiny`` preset with
    ``remat=True`` holds one ``sort`` and one ``top_k`` an expert block, as
    many as with ``remat=False`` (the parent: two of each), and the two
    gradients' loops are what they were: the passes are recomputed where a
    weight gradient needs their sum (Nemotron's ``latent_up``) and nowhere
    else."""
    loss, params, blocks = _tiny(name, remat=True)
    counts = _primitives(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    assert counts["sort"] == blocks and counts["top_k"] == blocks, counts
    plain, plain_params, _ = _tiny(name, remat=False)
    whole = _primitives(jax.make_jaxpr(jax.grad(plain))(plain_params).jaxpr)
    assert whole["sort"] == blocks and whole["top_k"] == blocks, whole
    recomputed = blocks if name == "nemotron_h" else 0
    assert counts["while"] == 2 * blocks + recomputed, counts
    assert whole["while"] == 2 * blocks


def _router_layer(kind: str):
    """``f(x, router, experts) -> (sum of y^2, processed)``: a router, the
    selection of ``kind`` and the held experts' part, on 96 tokens of 16,
    8 experts of which 5 are held, 3 a token; and its arguments."""
    r = np.random.default_rng(5)
    x = jnp.asarray(r.normal(size=(96, 16)), jnp.float32)
    router = jnp.asarray(r.normal(size=(16, 8)), jnp.float32)
    experts = {name: jnp.asarray(r.normal(size=shape) * 0.3, jnp.float32)
               for name, shape in (("gate", (5, 16, 8)), ("up", (5, 16, 8)),
                                   ("down", (5, 8, 16)))}

    def f(x, router, experts):
        logits = jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST)
        if kind == "softmax":
            idx, weights = moe.route_top_k_softmax(logits, 3)
        else:
            idx, weights = moe.route_top_k(
                jax.nn.sigmoid(logits), jnp.linspace(-0.1, 0.1, 8), 3,
                scaling=2.5)
        y, processed = moe.held_expert_ffn(x, idx, weights, experts, 2,
                                           rows=64, min_passes=2)
        return jnp.sum(y ** 2), processed

    return f, (x, router, experts)


@pytest.mark.parametrize("kind", ["sigmoid_bias", "softmax"])
def test_keeping_the_routing_changes_no_bit_and_halves_the_sorts(kind):
    """A router, ``route_top_k`` / ``route_top_k_softmax`` and
    ``held_expert_ffn`` under ``jax.checkpoint`` and under
    ``jax.checkpoint(policy=moe.KEEP_ROUTING)``: the same outputs and the
    same gradients of the tokens, the router and the experts bit for bit;
    two ``sort`` and two ``top_k`` against one of each (the softmax reads
    its ``k`` logits through the kept choice, or its selection would stay
    alive in the recomputation)."""
    f, args = _router_layer(kind)

    def grad_of(g):
        return jax.value_and_grad(g, argnums=(0, 1, 2), has_aux=True)

    again = grad_of(jax.checkpoint(f))
    once = grad_of(jax.checkpoint(f, policy=moe.KEEP_ROUTING))
    for a, b in zip(jax.tree_util.tree_leaves(jax.jit(again)(*args)),
                    jax.tree_util.tree_leaves(jax.jit(once)(*args))):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(jax.jit(once)(*args)[0][1]) > 64       # a second pass ran
    counts = {name: _primitives(jax.make_jaxpr(g)(*args).jaxpr)
              for name, g in (("again", again), ("once", once))}
    assert counts["again"]["sort"] == counts["again"]["top_k"] == 2
    assert counts["once"]["sort"] == counts["once"]["top_k"] == 1
    # the recomputation's count of the held experts' rows goes as well
    assert counts["once"]["scatter-add"] < counts["again"]["scatter-add"]


def test_the_counter_reads_the_bytes_the_shapes_give():
    """``dps_moe_routing_kept_bytes_total`` grows, once a traced expert
    layer, by what the layer names to be kept: ``idx [N, k]``, the padded
    ``order [N k + rows x min_passes]``, ``starts`` and ``ends [C]`` and
    ``total``, all int32; ``dps_moe_routing_kept_layers_total`` by one."""
    registry = get_registry()
    kept = registry.counter("dps_moe_routing_kept_bytes_total")
    layers = registry.counter("dps_moe_routing_kept_layers_total")
    f, args = _router_layer("softmax")
    before, before_layers = kept.value, layers.value
    jax.make_jaxpr(jax.grad(
        lambda *a: jax.checkpoint(f, policy=moe.KEEP_ROUTING)(*a)[0]))(*args)
    n, k, c, rows, min_passes = 96, 3, 5, 64, 2
    assert kept.value - before == 4 * (
        n * k + (n * k + rows * min_passes) + 2 * c + 1)
    assert layers.value - before_layers == 1
