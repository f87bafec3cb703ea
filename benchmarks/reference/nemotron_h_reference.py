"""Plain reference of the NVIDIA-Nemotron-3-Super (``nemotron_h``) training
step: forward, loss and gradients in ``jax.numpy``, and the update they go
through: AdamW in plain arithmetic and the balancing bias's sign rule.

Written from the source's ``config.json`` keys and the family's published
description, not from the program: the state-space recurrence **token by
token** (a ``lax.scan`` over the sequence on a ``[H, P, N]`` state: no
chunks, no segment sums, no masked ``C B^T`` product), the convolution as
four shifted products, attention with an explicit ``[Tq, T]`` mask and every
score materialised, the key/value heads repeated for their groups, no sort
and no grouped matmul (a loop over the held experts, each applied to every
token and weighted by a dense ``[T, E]`` array that is zero where the token
did not choose it), no chunked loss. Float32 with true-float32 matrix
products (``jax.default_matmul_precision("highest")``: on a TPU a float32
product otherwise runs in bf16 passes). Nothing is imported from the
program; it reads the program's parameter tree (names below) and a
configuration object's numbers (``cfg.<published key>``,
``cfg.held_experts``).

Per token ``x`` of width D; no projection has a bias; ``RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * g``, eps ``layer_norm_epsilon``. Layer ``l`` of kind
``hybrid_override_pattern[l]``: ``x <- x + Mixer(RMSNorm(x))``.

``M``, Mamba-2 (``H`` heads of ``P``, ``G`` groups, state ``N``, ``d_inner =
H P``)::

    [z | xBC | dt] = u W_in         # widths d_inner, d_inner + 2 G N, H
    xBC  = silu(conv(xBC) + b)      # depthwise, causal, width 4
    [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    head h of group g:  S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T
                        y_t = S_t C_t + D_h x_t
    y    = GroupRMSNorm(y * silu(z))    # over each group's d_inner / G
    out  = y W_out

``*``, attention: ``q, k, v = u W_q [T,Hq,hd], u W_k [T,Gk,hd], u W_v
[T,Gk,hd]``; no positions; ``o = softmax(q k_g^T / sqrt(hd) + causal mask)
v_g``, query head ``h`` on key/value head ``h // (Hq / Gk)``; ``out = o W_o``.

``E``, latent experts::

    s    = sigmoid(u W_router)              # [T, E], all 512
    top  = k largest of s + bias;  w = s[top] / sum(s[top]) * scaling
    v    = u W_latent_down                  # D -> latent
    r    = sum over e in top held here: w_e W2_e relu(W1_e v)^2
    out  = r W_latent_up + W_d relu(u W_u)^2    # the shared expert, whole

Loss: mean next-token cross-entropy over the vocabulary slice the embedding
and head hold, every position counted; final RMSNorm, untied head.

**The share.** The configuration counts what is held here: the Mamba-2 and
attention heads (a group's heads, a key/value head's queries) and
``cfg.held_experts`` = (first, count). A mixer's output is the held heads'
or experts' partial sum; what the absent ones would add is left out, here
as in the program.

Assumed, each also under ``assumed`` in the benchmark's configuration file:
the ``in_proj`` column order ``[z | x | B | C | dt]``; the gate before the
group norm; no positions in attention (``rope_theta`` unread); the router
reads the full-width normed input, its scores float32; the bias steers the
choice only; no auxiliary loss; no cross-document mask or state reset; no
multi-token-prediction module.

Departures, each for a stated reason and none changing a value:

- ``remat=True`` recomputes a layer at a time in the backward pass; inside a
  Mamba-2 layer the token scan runs in blocks of ``SCAN_BLOCK`` tokens (an
  outer scan over blocks whose body is checkpointed: the backward pass then
  keeps one state a block and one block's states, 96 MB, where the plain
  scan keeps 8,192 states of 0.5 MB, 4.3 GB a layer); inside attention one
  block of queries' scores at a time; one expert's hidden units at a time;
  the loss summed over ``QUERY_BLOCK`` rows of logits at a time. For the
  chip alone; the CPU tests run without it and one holds the two equal.
- ``head_block`` computes attention for that many heads at a time.
- ``loss_and_grads`` adds a block of rows' gradients into the running sum
  inside the jitted call, the sum donated.
- ``dtype`` other than float32 is not the reference: it is the reference
  *computed in a lower precision* (parameters, activations, the scan's
  state, router, softmax and loss all in that type), which the comparison
  must reject.

The functions' names and arguments are those
``benchmarks/drivers/sync_mesh_tokens.py:compare_with_reference`` calls.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: queries a block where ``head_block`` is given and the sequence is longer;
#: rows of logits a block under ``remat``
QUERY_BLOCK = 512
#: tokens a checkpointed block of the recurrence under ``remat``
SCAN_BLOCK = 128


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(x.dtype)


def relu2(u):
    return jnp.maximum(u, 0) ** 2


def recurrence(x, dt, a, b, c, remat=False):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``
    from ``S = 0``, token by token. ``x`` ``[T, H, P]``, ``dt`` ``[T, H]``,
    ``a`` ``[H]``, ``b`` / ``c`` ``[T, H, N]`` (a group's pair already
    repeated for its heads) -> ``[T, H, P]``."""
    def token(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    t = x.shape[0]
    state = jnp.zeros(x.shape[1:] + (b.shape[-1],), x.dtype)
    if not remat or t <= SCAN_BLOCK or t % SCAN_BLOCK:
        return jax.lax.scan(token, state, (x, dt, b, c))[1]
    blocks = jax.tree_util.tree_map(
        lambda v: v.reshape((t // SCAN_BLOCK, SCAN_BLOCK) + v.shape[1:]),
        (x, dt, b, c))
    y = jax.lax.scan(jax.checkpoint(
        lambda state, block: jax.lax.scan(token, state, block)),
        state, blocks)[1]
    return y.reshape(x.shape)


def mamba(p, u, cfg, remat=False):
    """``u`` ``[T, D]`` (normed) -> ``[T, D]``; one sequence."""
    t = u.shape[0]
    h, hp, g, n = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                   cfg.ssm_state_size)
    inner = h * hp
    zxbcdt = u @ p["in_proj"]["kernel"]
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * g * n],
                  zxbcdt[:, 2 * inner + 2 * g * n:])
    k = cfg.conv_kernel
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype),
                              xbc])
    xbc = jax.nn.silu(p["conv_bias"] + sum(
        padded[i:i + t] * p["conv_kernel"][i] for i in range(k)))
    x = xbc[:, :inner].reshape(t, h, hp)
    b, c = (jnp.repeat(v.reshape(t, g, n), h // g, axis=1) for v in
            (xbc[:, inner:inner + g * n], xbc[:, inner + g * n:]))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), b, c, remat) \
        + p["D"][:, None] * x
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                     + cfg.layer_norm_epsilon)
    return (y.reshape(t, inner) * p["norm"]) @ p["out_proj"]["kernel"]


def attention(p, u, cfg, head_block, remat=False):
    """``u`` ``[T, D]`` (normed) -> ``[T, D]``; one sequence, causal, no
    positions."""
    t = u.shape[0]
    h, g, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim)
    q = (u @ p["q"]["kernel"]).reshape(t, h, hd)
    k = (u @ p["k"]["kernel"]).reshape(t, g, hd)
    v = (u @ p["v"]["kernel"]).reshape(t, g, hd)
    # each key/value head once for every query head of its group
    k, v = (jnp.repeat(x, h // g, axis=1) for x in (k, v))

    def heads(q, k, v, rows):
        """The queries ``rows`` ``[Tq]``, ``q`` ``[Tq, n, hd]``, against
        every key."""
        s = jnp.einsum("qhd,khd->hqk", q, k) \
            / jnp.sqrt(jnp.asarray(hd, q.dtype))
        s = jnp.where(jnp.arange(t)[None, :] <= rows[:, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    if remat:       # one block of queries' score matrices at a time
        heads = jax.checkpoint(heads)
    rows = QUERY_BLOCK if head_block and t > QUERY_BLOCK \
        and t % QUERY_BLOCK == 0 else t
    outs = []
    for lo in range(0, h, head_block or h):
        hi = min(h, lo + (head_block or h))
        if rows == t:
            outs.append(heads(q[:, lo:hi], k[:, lo:hi], v[:, lo:hi],
                              jnp.arange(t)))
            continue
        outs.append(jax.lax.map(
            lambda block: heads(block[0], k[:, lo:hi], v[:, lo:hi],
                                block[1]),
            (q[:, lo:hi].reshape(t // rows, rows, hi - lo, hd),
             jnp.arange(t).reshape(t // rows, rows))).reshape(
                 t, hi - lo, hd))
    return jnp.concatenate(outs, axis=1).reshape(t, h * hd) \
        @ p["o"]["kernel"]


def expert_layer(p, u, bias, cfg, remat=False):
    """``u`` ``[T, D]`` (normed), ``bias`` ``[E]`` -> (``[T, D]``, loads
    ``[E]``)."""
    first, held = cfg.held_experts
    e, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    scores = jax.nn.sigmoid(u @ p["router"].astype(u.dtype))        # [T, E]
    chosen = jnp.argsort(-(scores + jax.lax.stop_gradient(
        bias.astype(u.dtype))), axis=-1)[:, :k]                     # [T, k]
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * cfg.routed_scaling_factor
    onehot = jax.nn.one_hot(chosen, e, dtype=scores.dtype)       # [T, k, E]
    dense = jnp.einsum("tk,tke->te", weights, onehot)   # 0 where not chosen
    v = u @ p["latent_down"]["kernel"]

    def expert(v, w, up, down):
        """One expert on every token, weighted (``w`` ``[T]``)."""
        return w[:, None] * (relu2(v @ up) @ down)

    if remat:       # one expert's hidden units and output at a time
        expert = jax.checkpoint(expert)
    # every held expert on every token, one after the other (a scan, so
    # that an expert's gradient is written into its row of the stacked
    # weights' and not padded to their size eight times over)
    routed, _ = jax.lax.scan(
        lambda out, e: (out + expert(v, *e), None), jnp.zeros_like(v),
        (dense[:, first:first + held].T, p["experts_up"],
         p["experts_down"]))
    shared = relu2(u @ p["shared"]["up"]["kernel"]) \
        @ p["shared"]["down"]["kernel"]
    return (routed @ p["latent_up"]["kernel"] + shared,
            jnp.sum(onehot, axis=(0, 1)))


def layer(p, x, bias, cfg, kind, head_block, remat=False):
    """``x + Mixer(RMSNorm(x))``; ``(x, loads or None)``."""
    u = rms_norm(x, p["norm"]["scale"], cfg.layer_norm_epsilon)
    if kind == "M":
        return x + mamba(p["mixer"], u, cfg, remat), None
    if kind == "*":
        return x + attention(p["mixer"], u, cfg, head_block, remat), None
    m, loads = expert_layer(p["mixer"], u, bias, cfg, remat)
    return x + m, loads


def sequence_hidden(params, router_bias, tokens, cfg, *, remat=False,
                    head_block=None):
    """One sequence ``tokens`` ``[T+2]`` (the last two are not read) -> the
    last layer's output ``[T, D]`` and the loads ``[expert layers, E]``."""
    def run(p, x, bias, kind):
        return layer(p, x, bias, cfg, kind, head_block, remat)
    if remat:
        run = jax.checkpoint(run, static_argnums=(3,))
    x, loads = params["embed"][tokens[:-2]], []
    pattern = cfg.hybrid_override_pattern[:cfg.num_hidden_layers]
    for i, kind in enumerate(pattern):
        bias = router_bias[len(loads)] if kind == "E" else None
        x, load = run(params[f"layer_{i}"], x, bias, kind)
        if kind == "E":
            loads.append(load)
    return x, jnp.stack(loads)


def logits_of(params, x, cfg):
    """Final RMSNorm and the untied head on ``x`` ``[N, D]`` -> ``[N, V]``."""
    return rms_norm(x, params["final_norm"], cfg.layer_norm_epsilon) \
        @ params["head"]


def _cross_entropy_sum(params, x, targets, cfg, remat=False):
    """Sum of the next-token cross-entropies of ``x`` ``[T, D]`` against
    ``targets`` ``[T]``."""
    def rows(block):
        logp = jax.nn.log_softmax(logits_of(params, block[0], cfg), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, block[1][:, None], axis=1))

    t = x.shape[0]
    if not remat or t <= QUERY_BLOCK or t % QUERY_BLOCK:
        return rows((x, targets))
    return jnp.sum(jax.lax.map(jax.checkpoint(rows), (
        x.reshape(t // QUERY_BLOCK, QUERY_BLOCK, -1),
        targets.reshape(t // QUERY_BLOCK, QUERY_BLOCK))))


def batch_loss(params, router_bias, tokens, cfg, *, positions_total=None,
               dtype=jnp.float32, remat=False, head_block=None):
    """``tokens`` ``[B, T+2]`` -> ``(loss, aux)``: the sum over the rows'
    positions of next-token cross-entropy over ``positions_total`` (the
    whole batch's positions when the rows are one block of it; default
    these rows')."""
    cast = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    total = positions_total or tokens.shape[0] * (tokens.shape[1] - 2)
    next_sum, loads = 0.0, 0
    for row in tokens:
        x, load = sequence_hidden(cast, router_bias, row, cfg, remat=remat,
                                  head_block=head_block)
        next_sum = next_sum + _cross_entropy_sum(cast, x, row[1:-1], cfg,
                                                 remat)
        loads = loads + load
    loss = (next_sum / total).astype(jnp.float32)
    return loss, {"next_loss": loss, "loads": loads}


def block_grads(params, router_bias, rows, cfg, *, positions_total,
                dtype=jnp.float32, remat=False, head_block=None):
    """``((loss, aux), grads)`` of one block of rows: its share of the
    batch's loss. The parameters are an argument of the jitted call, not
    2.8 GB of constants in its program."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: batch_loss(p, router_bias, rows, cfg,
                                 positions_total=positions_total,
                                 dtype=dtype, remat=remat,
                                 head_block=head_block),
            has_aux=True)(params)


def loss_and_grads(params, router_bias, tokens, cfg, *, rows_per_block=None,
                   dtype=jnp.float32, remat=False, head_block=None):
    """Loss, aux and the gradient of every parameter tensor over the batch
    ``tokens`` ``[B, T+2]``, computed ``rows_per_block`` sequences at a time
    (all at once by default) so that the activations fit, and summed."""
    b, t = tokens.shape[0], tokens.shape[1] - 2
    step = rows_per_block or b
    if step >= b:       # one block: no running sum
        (loss, aux), grads = jax.jit(
            lambda params, router_bias, rows: block_grads(
                params, router_bias, rows, cfg, positions_total=b * t,
                dtype=dtype, remat=remat, head_block=head_block))(
                    params, router_bias, tokens)
        return loss, aux, grads

    def add_block(total, params, router_bias, rows):
        part = block_grads(params, router_bias, rows, cfg,
                           positions_total=b * t, dtype=dtype, remat=remat,
                           head_block=head_block)
        return jax.tree_util.tree_map(jnp.add, total, part)

    total = jax.tree_util.tree_map(
        jnp.zeros_like, jax.eval_shape(
            lambda: block_grads(params, router_bias, tokens[:step], cfg,
                                positions_total=b * t, dtype=dtype)))
    add_block = jax.jit(add_block, donate_argnums=0)
    for lo in range(0, b, step):
        total = add_block(total, params, router_bias, tokens[lo:lo + step])
    (loss, aux), grads = total
    return loss, aux, grads


def logits_at(params, router_bias, tokens, cfg, positions, *,
              dtype=jnp.float32, head_block=None):
    """Logits at ``positions`` of every row of ``tokens`` ``[B, T+2]``, a
    row at a time, as the one-element tuple ``(main [B, P, V],)``."""
    @jax.jit
    def one(params, router_bias, row, positions):
        cast = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
        with jax.default_matmul_precision("highest"):
            x, _ = sequence_hidden(cast, router_bias, row, cfg,
                                   head_block=head_block)
            return logits_of(cast, x[positions], cfg)

    return (jnp.stack([one(params, router_bias, row, positions)
                       for row in tokens]),)


def adamw_step(param, grad, mu, nu, count, *, learning_rate, b1, b2, eps,
               weight_decay):
    """One AdamW step on one tensor, float32 throughout: ``(param, mu, nu)``
    after step number ``count`` (1 for the first). Decoupled weight decay on
    matrices only (a tensor of two or more axes); bias-corrected moments.
    Operators alone, so that it takes ``numpy`` arrays on the host."""
    mu = b1 * mu + (1.0 - b1) * grad
    nu = b2 * nu + (1.0 - b2) * grad * grad
    m_hat = mu / (1.0 - b1 ** count)
    v_hat = nu / (1.0 - b2 ** count)
    step = m_hat / (v_hat ** 0.5 + eps)
    if param.ndim >= 2:
        step = step + weight_decay * param
    return param - learning_rate * step, mu, nu


def bias_step(router_bias, loads, gamma):
    """``b_e += gamma * sign(mean load - load_e)`` for one layer."""
    gap = loads.mean() - loads
    return router_bias + gamma * ((gap > 0) * 1.0 - (gap < 0) * 1.0)
