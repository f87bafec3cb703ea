"""Expert parallelism, two layers.

**The Switch layer** (``make_moe_ffn``): top-1 over an ``expert`` mesh axis,
one expert a mesh slot, capacity buffers that drop, two ``all_to_all`` hops.
``MoETrainer`` (--mode moe) runs it inside a ViT. Described next.

**The held-experts layer** (``route_top_k`` + ``held_expert_ffn``, further
down): top-k over the router's whole published width, many experts a chip,
the layer *told which experts it holds*, no token dropped whatever the
imbalance. It returns the partial sum the held experts give; on one chip it
runs without its exchange (the other shares' partial sums live on the chips
that hold them). The decoder LMs run it: models/joyai.py (sigmoid scores
and a balancing bias, SwiGLU experts) and models/smallthinker.py (a softmax
over the chosen logits, :func:`route_top_k_softmax`, ReLU-gated experts).

The Switch layer: net-new capability (the reference has no MoE — SURVEY.md §2 checklist, EP
row). One expert per mesh slot; each device routes its resident tokens,
packs them into capacity-limited per-expert buffers, and two
``lax.all_to_all`` hops move tokens to their expert and back:

    route (local) -> dispatch [E, C, D] -> all_to_all -> my expert's FFN on
    [N, C, D] -> all_to_all back -> gate * combine (dropped tokens -> 0)

Capacity C bounds memory and keeps shapes static (XLA requirement); tokens
beyond an expert's capacity are dropped, which is standard Switch behavior —
in a transformer the residual connection carries them through unchanged.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P


EXPERT_AXIS = "expert"


def init_moe_params(rng, d_model: int, d_hidden: int, n_experts: int):
    """Router + stacked per-expert FFN params ([E, ...]; shard on 'expert')."""
    k1, k2, k3 = jax.random.split(rng, 3)
    scale1 = 1.0 / jnp.sqrt(d_model)
    scale2 = 1.0 / jnp.sqrt(d_hidden)
    return {
        "router": jax.random.normal(k1, (d_model, n_experts)) * scale1,
        "w1": jax.random.normal(k2, (n_experts, d_model, d_hidden)) * scale1,
        "b1": jnp.zeros((n_experts, d_hidden)),
        "w2": jax.random.normal(k3, (n_experts, d_hidden, d_model)) * scale2,
        "b2": jnp.zeros((n_experts, d_model)),
    }


def _moe_body(params, tokens, *, axis_name: str, axis_size: int,
              capacity: int, data_axis: str | None = None):
    """shard_map body. params: router replicated + my expert's slice [1,...].
    tokens: [n_local, D]. Returns ``([n_local, D], stats)`` where stats are
    GLOBAL routing statistics (pmean'd over the expert axis — and the data
    axis when composing dp x ep — replicated):

    - ``aux_loss``: the Switch load-balance loss E * sum_e f_e * P_e
      (f_e = fraction of tokens routed to e, hard counts; P_e = mean router
      probability). Differentiable through P_e; minimized (=1) at uniform
      routing — trainers weight it into the total loss.
    - ``load``: [E] f_e, ``importance``: [E] P_e,
    - ``drop_frac``: fraction of tokens dropped by the capacity limit.
    """
    n, d = tokens.shape
    e = axis_size

    # -- route locally (top-1 / Switch) --------------------------------------
    logits = tokens @ params["router"]          # [n, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)     # [n]
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=1)[:, 0]

    # position of each token within its expert's send buffer
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)      # [n, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1                # [n, E]
    pos = jnp.max(pos, axis=1)                                   # [n]
    keep = pos < capacity

    # -- routing stats + Switch auxiliary load-balance loss ------------------
    # dp x ep: each data-parallel group routes its own tokens; f_e/P_e
    # additionally pmean over the data axis BEFORE the product, so the
    # aux loss is the Switch loss of the GLOBALLY pooled statistics —
    # invariant to how tokens are grouped across dp (a dp x ep step sees
    # the same aux loss/grads as ep-only on the same global batch, which
    # test_dp_ep_gradients_include_data_psum asserts), and replicated
    # across the whole mesh for the P() out_spec.
    stat_axes = (axis_name,) if data_axis is None else (axis_name, data_axis)
    load = jax.lax.pmean(jnp.mean(onehot.astype(jnp.float32), axis=0),
                         stat_axes)                              # [E] f_e
    importance = jax.lax.pmean(jnp.mean(probs, axis=0),
                               stat_axes)                        # [E] P_e
    # f_e is constant w.r.t. params (argmax); gradients flow through P_e —
    # exactly the Switch Transformer formulation (eq. 4).
    aux_loss = e * jnp.sum(jax.lax.stop_gradient(load) * importance)
    drop_frac = jax.lax.pmean(
        1.0 - jnp.mean(keep.astype(jnp.float32)), stat_axes)
    stats = {"aux_loss": aux_loss, "load": load,
             "importance": importance, "drop_frac": drop_frac}

    # -- dispatch [E, C, D] --------------------------------------------------
    safe_pos = jnp.clip(pos, 0, capacity - 1)
    dispatch = jnp.zeros((e, capacity, d), tokens.dtype)
    dispatch = dispatch.at[expert_idx, safe_pos].add(
        tokens * keep[:, None].astype(tokens.dtype))

    # -- to experts, compute, and back ---------------------------------------
    recv = jax.lax.all_to_all(dispatch, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)        # [N, C, D]
    w1 = params["w1"][0]
    b1 = params["b1"][0]
    w2 = params["w2"][0]
    b2 = params["b2"][0]
    h = jax.nn.gelu(recv @ w1 + b1)
    out = h @ w2 + b2                                            # [N, C, D]
    back = jax.lax.all_to_all(out, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)        # [E, C, D]

    # -- combine -------------------------------------------------------------
    gathered = back[expert_idx, safe_pos]                        # [n, D]
    mask = (keep.astype(tokens.dtype) * gate.astype(tokens.dtype))[:, None]
    return gathered * mask, stats


def make_moe_ffn(mesh: Mesh, capacity: int,
                 axis: str = EXPERT_AXIS,
                 data_axis: str | None = None) -> Callable:
    """Build ``fn(params, tokens[B, D]) -> ([B, D], stats)`` with tokens
    sharded on the expert axis and experts one-per-slot. Differentiable;
    ``stats`` (replicated) carries the Switch aux loss + routing
    observability — see ``_moe_body``.

    dp x ep (round-4 VERDICT weak 4): with ``data_axis`` set, the mesh is
    ``(data, expert)`` — tokens shard over BOTH axes, each data group
    routes its tokens over ITS experts' slice of the mesh (the two
    ``all_to_all`` hops stay within the group's expert ring), and expert
    weights replicate across the data axis, so the shard_map transpose
    inserts the data-axis gradient psum — exactly how Switch Transformer
    composes EP with DP at pod scale."""
    axis_size = mesh.shape[axis]
    body = partial(_moe_body, axis_name=axis, axis_size=axis_size,
                   capacity=capacity, data_axis=data_axis)
    # Expert-stacked leaves shard their leading [E] dim on the expert axis
    # and replicate across data; the router replicates everywhere.
    param_specs = {
        "router": P(),
        "w1": P(axis), "b1": P(axis),
        "w2": P(axis), "b2": P(axis),
    }
    stats_specs = {"aux_loss": P(), "load": P(), "importance": P(),
                   "drop_frac": P()}
    tok_spec = P(axis) if data_axis is None else P((data_axis, axis))
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(param_specs, tok_spec),
        out_specs=(tok_spec, stats_specs),
        check_vma=False,
    )
    return jax.jit(sharded)


def dense_reference(params, tokens, capacity: int | None = None):
    """Single-device reference: every token through its top-1 expert (no
    capacity drops unless ``capacity`` given per-expert-per-shard semantics
    are not modeled — use generous capacity in comparisons)."""
    logits = tokens @ params["router"]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=1)[:, 0]
    h = jax.nn.gelu(jnp.einsum("nd,ndh->nh", tokens,
                               params["w1"][expert_idx])
                    + params["b1"][expert_idx])
    out = jnp.einsum("nh,nhd->nd", h, params["w2"][expert_idx]) \
        + params["b2"][expert_idx]
    return out * gate[:, None].astype(tokens.dtype)


# -- the held-experts layer ---------------------------------------------------

#: the name of a layer's integer routing decisions: the chosen experts
#: ``idx [N, k]``, the assignments' sorted ``order`` and the held experts'
#: bounds in it (``starts``, ``ends``, ``total``). None takes a gradient
#: and a recomputation would rebuild each identically, so a block that is
#: recomputed in the backward pass keeps them: a layer routes once a step.
ROUTING = "moe_routing"

#: the ``policy`` of a decoder's ``nn.remat``: everything in a block is
#: recomputed but what is named ``ROUTING`` (integers, ``N k`` x 8 bytes and
#: change a layer, counted in ``dps_moe_routing_kept_bytes_total``)
KEEP_ROUTING = jax.checkpoint_policies.save_only_these_names(ROUTING)


def _keep(a: jax.Array) -> jax.Array:
    """``a`` named ``ROUTING``: inert but under ``KEEP_ROUTING``. Named
    flat: a TPU pads the last axis of what it keeps to 128 lanes, so ``idx
    [N, k]`` kept as it stands would be ``128 / k`` times its bytes."""
    return checkpoint_name(a.reshape(-1), ROUTING).reshape(a.shape)


def route_top_k(scores: jax.Array, bias: jax.Array, k: int, *,
                scaling: float = 1.0, normalize: bool = True):
    """Choose ``k`` experts a token and weigh them.

    ``scores`` ``[N, E]`` float32 are the router's affinities (the caller's
    sigmoid or softmax over the *whole* published width ``E``), ``bias``
    ``[E]`` the balancing bias that only steers the choice (``noaux_tc``:
    it takes no gradient and is updated from the counted loads, see
    :func:`bias_update`). Returns ``(idx [N, k] int32, weights [N, k]
    float32)``: the ``k`` largest of ``scores + bias``, weighted by their
    ``scores`` (without the bias), divided by their sum if ``normalize``,
    times ``scaling``.
    """
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias)[None], k)
    idx = _keep(idx.astype(jnp.int32))
    weights = jnp.take_along_axis(scores, idx, axis=1)
    if normalize:
        weights = weights / jnp.sum(weights, axis=1, keepdims=True)
    return idx, weights * scaling


def route_top_k_softmax(logits: jax.Array, k: int):
    """Choose the ``k`` largest of a token's router ``logits`` ``[N, E]``
    (float32, the router's whole published width) and weigh them by a
    softmax over those ``k`` logits alone: ``(idx [N, k] int32, weights [N,
    k] float32)``, the weights of a token summing to 1. No bias steers the
    choice."""
    _, idx = jax.lax.top_k(logits, k)
    idx = _keep(idx.astype(jnp.int32))
    # the k logits read through the kept choice (``top_k``'s values), so
    # that a recomputation selects nothing; by comparison, not by a gather:
    # on a TPU ``take_along_axis`` fetches the N k floats one by one, 1 ms
    # at 16,384 x 6 where the values used to come with the selection
    # (PERF.md, PR 37), and a compare, select and sum over [N, k, E] is
    # fused elementwise work, forward and backward
    chosen = idx[:, :, None] == jnp.arange(logits.shape[1], dtype=jnp.int32)
    top = jnp.sum(jnp.where(chosen, logits[:, None, :], 0), axis=-1)
    return idx, jax.nn.softmax(top, axis=-1)


def expert_loads(idx: jax.Array, n_experts: int) -> jax.Array:
    """``[E]`` int32: assignments each of the ``E`` experts was given."""
    return jnp.zeros((n_experts,), jnp.int32).at[idx.reshape(-1)].add(1)


def bias_update(bias: jax.Array, loads: jax.Array, gamma: float):
    """``b_e += gamma * sign(mean load - load_e)``: an expert with less than
    its share is made likelier, one with more less likely."""
    loads = loads.astype(jnp.float32)
    return bias + gamma * jnp.sign(jnp.mean(loads) - loads)


def pass_plan(n_tokens: int, k: int, held: int, n_experts: int,
              capacity_factor: float = 0.0):
    """``(rows, min_passes)`` for :func:`held_expert_ffn`: a pass is half of
    what the held experts get under even routing, in whole 512-row tiles and
    never more than the most they can get. ``capacity_factor`` is the layer's
    static capacity in units of the even load, as a deployment with
    fixed-shape exchange buffers states one: that many rows are computed in
    every step, slack as zero rows, so that a step's time does not follow
    the routing while the load is within it (``min_passes``); 0, the default,
    computes what the routing needs and no more. Beyond the capacity the
    layer runs more passes; it never drops."""
    most = n_tokens * min(k, held)
    even = n_tokens * k * held // n_experts
    rows = min(most, max(512, -(-even // 1024) * 512))
    floor = round(capacity_factor * even / rows) if capacity_factor else 1
    return rows, min(-(-most // rows), max(1, floor))


def _pass_rows(p, order, starts, ends, total, rows: int):
    """Pass ``p`` of the sorted assignment list: ``(at [rows], valid
    [rows], group [C])``: the assignments' indices into the flat ``[N * k]``
    list, which of them lie before the list's end, and how many rows of the
    pass each held expert has. Rows past the list's end (the last pass's, or
    a stated capacity's slack) are zero rows given to the last held expert,
    so that a pass is the same work wherever the list ends."""
    lo = p * rows
    at = jax.lax.dynamic_slice(order, (lo,), (rows,))
    valid = lo + jnp.arange(rows) < total
    group = jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows)
    return at, valid, group.at[-1].add(rows - jnp.sum(group))


def relu2(u):
    """``relu(u)^2``."""
    return jnp.square(jax.nn.relu(u))


#: an expert's activation, by the name :func:`held_expert_ffn` takes: the
#: gate's of a gated unit, the hidden units' own of an ungated one
GATE_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
                    "relu2": relu2}


def _inputs(experts) -> tuple:
    """The names of an expert's input matrices: ``gate`` and ``up`` of a
    gated unit (``down(act(gate u) * (up u))``), ``up`` alone of an ungated
    one (``down(act(up u))``). What ``experts`` holds says which."""
    return ("gate", "up") if "gate" in experts else ("up",)


def _pre(rows_x, weights_in, group):
    """The pass's rows through each input matrix of their own expert:
    ``(gate u, up u)`` or ``(up u,)``."""
    return tuple(jax.lax.ragged_dot(rows_x, w, group) for w in weights_in)


def _unit(pre, activation: str):
    """An expert's hidden units from :func:`_pre`'s products."""
    hidden = GATE_ACTIVATIONS[activation](pre[0])
    return hidden * pre[1] if len(pre) == 2 else hidden


def _weighted(out, rows_w, valid):
    # rows past the last group's end hold whatever the kernel left
    return jnp.where(valid[:, None], out, 0) * rows_w[:, None].astype(
        out.dtype)


def _pass_out(rows_x, rows_w, experts, group, valid, activation: str):
    """The pass's rows through their experts' unit, weighted: a grouped
    matmul an expert matrix, three of a gated unit and two of an ungated one
    (``jax.lax.ragged_dot``; on the TPU XLA's own grouped kernel, which
    visits only the tiles a group fills)."""
    with jax.named_scope("moe_experts"):
        weights_in = [experts[name].astype(rows_x.dtype)
                      for name in _inputs(experts)]
        hidden = _unit(_pre(rows_x, weights_in, group), activation)
        out = jax.lax.ragged_dot(
            hidden, experts["down"].astype(rows_x.dtype), group)
    return _weighted(out, rows_w, valid)


def _grad_accumulate_impl(rows: int, d: int, f: int) -> str:
    """Which way a layer's passes add their weight gradients to the carry,
    chosen from the backend and the shapes alone and counted in
    ``dps_moe_grad_accumulate_total{impl}`` at trace time: ``in_place``
    (ops/pallas/grouped_grad.py: summed in float32 into the slices of the
    experts a pass has rows of, the carry aliased in and out) on a TPU where
    the kernel has tiles for the shapes, else ``xla`` (``ragged_dot_general``
    into float32, then the whole carry read, added to and written)."""
    from ..ops import attention
    from ..ops.pallas import grouped_grad
    from ..telemetry import get_registry
    # whether there are tiles is the same for [d, f] and the down
    # projection's [f, d]
    impl = ("in_place" if attention._on_tpu()
            and grouped_grad.pick_blocks(rows, d, f) else "xla")
    get_registry().counter("dps_moe_grad_accumulate_total", impl=impl).inc()
    return impl


def _add_weight_grads(acc, lhs, rhs, group, impl: str):
    """``acc [C, K, N]`` float32 with ``lhs[rows of c]^T @ rhs[rows of c]``
    added to each expert ``c``'s slice: the products of the rows' dtype
    summed in float32, nothing rounded before it is added."""
    if impl == "in_place":
        from ..ops.pallas.grouped_grad import grouped_grad_accumulate
        return grouped_grad_accumulate(acc, lhs, rhs, group)
    return acc + jax.lax.ragged_dot_general(
        lhs, rhs, group, jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[]),
        preferred_element_type=jnp.float32)


def _pass_grads(rows_x, rows_w, experts, group, valid, activation: str,
                d_part, de, impl: str):
    """The backward pass of :func:`_pass_out` for the cotangent ``d_part``
    of its result: ``(d_rows_x, d_rows_w, de)``, ``de`` being the carried
    float32 gradients of ``experts`` with this pass's added
    (:func:`_add_weight_grads`). The forward is computed again stage by
    stage and each stage's inputs differentiated by ``jax.vjp`` with the
    weights held constant; the weight gradients, one an expert matrix, are
    the one thing not left to it."""
    names = _inputs(experts)
    with jax.named_scope("moe_experts"):
        weights_in = [experts[name].astype(rows_x.dtype) for name in names]
        wd = experts["down"].astype(rows_x.dtype)
        pre, vjp_in = jax.vjp(lambda rx: _pre(rx, weights_in, group), rows_x)
        hidden, vjp_unit = jax.vjp(lambda *pre: _unit(pre, activation), *pre)
        out, vjp_down = jax.vjp(
            lambda h: jax.lax.ragged_dot(h, wd, group), hidden)
    _part, vjp_weigh = jax.vjp(lambda o, rw: _weighted(o, rw, valid), out,
                               rows_w)
    d_out, d_rows_w = vjp_weigh(d_part)
    with jax.named_scope("moe_experts"):
        (d_hidden,) = vjp_down(d_out)
        d_pre = vjp_unit(d_hidden)
        (d_rows_x,) = vjp_in(d_pre)
        de = dict(
            {name: _add_weight_grads(de[name], rows_x, d, group, impl)
             for name, d in zip(names, d_pre)},
            down=_add_weight_grads(de["down"], hidden, d_out, group, impl))
    return d_rows_x, d_rows_w, de


def _passes(total, rows: int, min_passes: int):
    return jnp.maximum(-(-total // rows), min_passes)


#: how a token's rows are summed (``held_expert_ffn(combine=...)``)
COMBINES = ("scatter", "gather")


class _TokenSums:
    """Where the passes' rows go to be summed a token: ``[N, D]`` float32
    sums of the rows' ``[rows, D]`` results, row ``i`` of pass ``p`` being
    assignment ``order[p * rows + i]`` of token ``order[...] // k``.

    ``scatter``: each pass adds its rows into the sums (a read-modify-write
    of a float32 row a row: only the sums and one pass's rows are live).
    ``gather``: each pass writes its rows where they stand in the sorted
    list, a ``[N * k, D]`` buffer in the rows' dtype, and at the end every
    token gathers its ``k`` rows from where the sort put them and sums
    them. On a TPU a scatter-add costs by the distinct rows it touches
    (7.9 ms for 12,288 distinct rows of 2,560, 3.1 ms where four in a row
    are one token's, as a pass's slack rows are; PERF.md, PR 34), so its
    time follows the routing, and a gather does not (1.0 ms either way);
    the price is the buffer, 0.5 GB at 16,384 tokens x 6."""

    def __init__(self, combine: str, n: int, k: int, rows: int,
                 passes: int, order):
        if combine not in COMBINES:
            raise ValueError(f"combine={combine!r}: one of {COMBINES}")
        self.gather, self.n, self.k, self.rows = (combine == "gather", n, k,
                                                  rows)
        self.slots = max(n * k, passes * rows)
        self.order = order

    def zeros(self, d: int, dtype):
        return (jnp.zeros((self.slots, d), dtype) if self.gather
                else jnp.zeros((self.n, d), jnp.float32))

    def add(self, sums, p, tokens, part):
        if self.gather:
            return jax.lax.dynamic_update_slice(
                sums, part.astype(sums.dtype), (p * self.rows, 0))
        return sums.at[tokens].add(part.astype(jnp.float32))

    def done(self, sums):
        if not self.gather:
            return sums
        n_k = self.n * self.k
        # where the sort put assignment a = token * k + j
        at = jnp.zeros((n_k,), jnp.int32).at[self.order[:n_k]].set(
            jnp.arange(n_k, dtype=jnp.int32))
        return jnp.sum(sums[at].reshape(self.n, self.k, -1).astype(
            jnp.float32), axis=1)


def _most_passes(n: int, k: int, held: int, rows: int, min_passes: int):
    """The most passes the loop can run (``_passes``' largest value)."""
    return max(min_passes, -(-n * min(k, held) // rows))


def grad_visits(sizes: jax.Array, rows: int, min_passes: int):
    """``(visits, passes)``, int32: the (pass, expert) pairs of a layer's
    backward pass whose slice of the carried weight gradients is read and
    written (:func:`_add_weight_grads` in place), out of ``passes x C``
    that a whole-carry add touches, and the passes the loop runs. ``sizes``
    ``[C]`` are the held experts' loads, as :func:`held_expert_ffn` sorts
    them: expert ``c``'s rows lie in ``floor((end - 1) / rows) - floor(start
    / rows) + 1`` passes, and the last held expert is also visited in every
    pass that has slack rows. ``passes <= visits <= C + passes - 1``."""
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts, total = ends - sizes, ends[-1]
    passes = _passes(total, rows, min_passes)
    own = jnp.sum(jnp.where(sizes > 0,
                            (ends - 1) // rows - starts // rows + 1, 0))
    # passes from the one the list ends in (or just before) hold slack; the
    # last expert's own rows reach into the first of them unless the list
    # ends on a pass's edge
    slack = passes - total // rows
    shared = (sizes[-1] > 0) & (total % rows != 0)
    return own + slack - shared.astype(jnp.int32), passes


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _work_off(x, flat_weights, experts, order, starts, ends, total,
              k: int, rows: int, min_passes: int, activation: str,
              combine: str):
    """``(y [N, D] float32, processed)``: the sorted list worked off
    ``rows`` at a time, ``min_passes`` passes or as many as the list is long
    (a loop with a dynamic trip count, hence the hand-written backward pass
    below: the same loop, each pass's forward computed again and
    differentiated, its weight gradients added to their float32 sums in
    place: ``_pass_grads``), and the assignments the passes computed,
    counted pass by pass: a trip count that stops short shows as fewer than
    ``total``. Under ``combine="scatter"`` only one pass's rows and the
    ``[N, D]`` sums are live, whatever the imbalance (``_TokenSums``), and a
    pass beyond both costs nothing."""
    n, d = x.shape
    sums = _TokenSums(combine, n, k, rows, _most_passes(
        n, k, starts.shape[0], rows, min_passes), order)

    def one_pass(p, carry):
        y, processed = carry
        at, valid, group = _pass_rows(p, order, starts, ends, total, rows)
        with jax.named_scope("moe_route"):
            tokens = at // k
            rows_x = jnp.where(valid[:, None], x[tokens], 0)
            rows_w = flat_weights[at]
        part = _pass_out(rows_x, rows_w, experts, group, valid, activation)
        with jax.named_scope("moe_route"):
            return (sums.add(y, p, tokens, part),
                    processed + jnp.sum(valid, dtype=jnp.int32))

    y, processed = jax.lax.fori_loop(
        0, _passes(total, rows, min_passes), one_pass,
        (sums.zeros(d, x.dtype), jnp.int32(0)))
    with jax.named_scope("moe_route"):
        return sums.done(y), processed


def _work_off_fwd(x, flat_weights, experts, order, starts, ends, total,
                  k, rows, min_passes, activation, combine):
    out = _work_off(x, flat_weights, experts, order, starts, ends, total,
                    k, rows, min_passes, activation, combine)
    return out, (x, flat_weights, experts, order, starts, ends, total)


def _work_off_bwd(k, rows, min_passes, activation, combine, residuals,
                  cotangents):
    x, flat_weights, experts, order, starts, ends, total = residuals
    dy, _ = cotangents          # the count takes none
    sums = _TokenSums(combine, x.shape[0], k, rows, _most_passes(
        x.shape[0], k, starts.shape[0], rows, min_passes), order)
    impl = _grad_accumulate_impl(rows, x.shape[1], experts["up"].shape[2])

    def one_pass(p, carry):
        dx, dw, de = carry
        at, valid, group = _pass_rows(p, order, starts, ends, total, rows)
        with jax.named_scope("moe_route"):
            tokens = at // k
            rows_x = jnp.where(valid[:, None], x[tokens], 0)
            rows_w = flat_weights[at]
            d_part = dy[tokens].astype(x.dtype)
        d_rows_x, d_rows_w, de = _pass_grads(
            rows_x, rows_w, experts, group, valid, activation, d_part, de,
            impl)
        with jax.named_scope("moe_route"):
            dx = sums.add(dx, p, tokens,
                          jnp.where(valid[:, None], d_rows_x, 0))
            dw = dw.at[at].add(jnp.where(valid, d_rows_w, 0))
        return dx, dw, de

    dx, dw, de = jax.lax.fori_loop(
        0, _passes(total, rows, min_passes), one_pass,
        (sums.zeros(x.shape[1], x.dtype), jnp.zeros_like(flat_weights),
         {name: jnp.zeros(w.shape, jnp.float32)
          for name, w in experts.items()}))
    with jax.named_scope("moe_route"):
        dx = sums.done(dx)
    de = {name: de[name].astype(w.dtype) for name, w in experts.items()}
    return dx.astype(x.dtype), dw, de, None, None, None, None


_work_off.defvjp(_work_off_fwd, _work_off_bwd)


def _count_kept(*routing):
    """At trace time, once an expert layer: the bytes of what the layer
    names ``ROUTING`` into ``dps_moe_routing_kept_bytes_total``, the layer
    into ``dps_moe_routing_kept_layers_total``."""
    from ..telemetry import get_registry
    registry = get_registry()
    registry.counter("dps_moe_routing_kept_bytes_total").inc(
        sum(a.size * a.dtype.itemsize for a in routing))
    registry.counter("dps_moe_routing_kept_layers_total").inc()


def held_expert_ffn(x: jax.Array, idx: jax.Array, weights: jax.Array,
                    experts: dict, first: int, *, rows: int,
                    min_passes: int = 1, activation: str = "silu",
                    combine: str = "scatter"):
    """The held experts' part of a top-k expert layer's output.

    ``x`` ``[N, D]`` tokens, ``idx`` / ``weights`` ``[N, k]`` from
    :func:`route_top_k` (or :func:`route_top_k_softmax`), ``experts`` the
    stacked weights of the experts held here (``gate`` / ``up`` ``[C, D,
    F]``, ``down`` ``[C, F, D]``; an expert is ``down(activation(gate u) *
    (up u))``, ``activation`` a key of ``GATE_ACTIVATIONS``: SwiGLU by
    default; without a ``gate`` it is the ungated ``down(activation(up
    u))``, two matrices), which are the experts ``first .. first + C - 1``
    of the router's numbering. Returns ``(y [N, D], processed)``: for each
    token the weighted sum over the held experts among its ``k``, zero for a
    token that chose none of them, and the number of assignments the passes
    computed, counted as they ran: it equals the number given to held
    experts, because nothing is dropped.

    How: the assignments are sorted by held expert (absent experts' last),
    and the sorted list, at most ``N * min(k, C)`` long, is worked off in
    passes of ``rows`` rows (:func:`_work_off`): gather the rows' tokens,
    a grouped matmul an expert matrix over the held experts, weigh, add into
    the tokens' sums. As many passes run as the list needs, found at run time,
    so only the worst case pays for the worst case; ``min_passes`` is the
    floor a stated capacity sets (:func:`pass_plan`), 1 without one.
    ``combine`` (``COMBINES``) says how the rows' results reach the tokens'
    sums: added into them pass by pass (``scatter``, the default: least
    memory) or written where the sort put them and gathered at the end
    (``gather``: a time that does not follow the routing; ``_TokenSums``).
    """
    k, c = idx.shape[1], experts["down"].shape[0]
    with jax.named_scope("moe_route"):
        local = idx - first
        held = (local >= 0) & (local < c)
        flat = jnp.where(held, local, c).reshape(-1)     # absent sort last
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        sizes = jnp.zeros((c + 1,), jnp.int32).at[flat].add(1)[:c]
        ends = jnp.cumsum(sizes)
        starts, total = ends - sizes, ends[-1]
        # the last pass, or a capacity's floor, may reach past the end
        order, starts, ends, total = map(_keep, (
            jnp.pad(order, (0, rows * min_passes)), starts, ends, total))
    _count_kept(idx, order, starts, ends, total)
    y, processed = _work_off(x, weights.reshape(-1), experts, order, starts,
                             ends, total, k, rows, min_passes, activation,
                             combine)
    return y.astype(x.dtype), processed
