"""Plain reference of the SmallThinker-21BA3B-Instruct training step: forward,
loss and gradients in ``jax.numpy``, and the update they go through: AdamW in
plain arithmetic (the model has no balancing bias; :func:`bias_step` is the
rule the program applies with gamma 0).

Written from the source's ``config.json`` keys and its published modelling
code's equations, not from the program: no kernel (an explicit mask built
from ``keep(i, j)``, ``[T, T]`` where all the queries go at once, the key/value heads repeated for their
groups, every score matrix materialised), no sort and no grouped matmul (a
loop over the held experts, each applied to every token and weighted by a dense
``[T, E]`` array that is zero where the token did not choose it), no chunked
loss. Float32 with true-float32 matrix products
(``jax.default_matmul_precision("highest")``: on a TPU a float32 product
otherwise runs in bf16 passes). Nothing is imported from the program; it
reads the program's parameter tree (names below) and a configuration
object's numbers (``cfg.<published key>``, ``cfg.held_experts``).

Per token ``x`` of width D; no projection has a bias;
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``. Block ``l``, input ``x``
``[T, D]``::

    r      = x W_router                       # [T, E], the block's INPUT
    top    = k largest of r per token;  w = softmax over those k logits
    a      = RMSNorm(x)
    q,k,v  = a W_q [T,H,hd], a W_k [T,G,hd], a W_v [T,G,hd]
    if rope_layout[l] == 1: q, k = RoPE(q, k; theta, pairs (i, i + hd/2))
    keep(i,j) = j <= i  and  (sliding_window_layout[l] == 0 or i - j < W)
    o      = softmax(q k_g^T / sqrt(hd) + mask) v_g,   g = h // (H/G)
    y      = x + o W_o
    u      = RMSNorm(y)
    m      = sum over e in top held here:
                 w_e * W_down,e( relu(W_gate,e u) * (W_up,e u) )
    out    = y + m

Loss: mean next-token cross-entropy over the vocabulary slice the embedding
and head hold, every position counted; final RMSNorm, untied head. The
expert layer is given the range of experts held (``cfg.held_experts`` =
first, count) and returns the held experts' terms only: one share of an
expert-parallel deployment.

Assumed, each also under ``assumed`` in the benchmark's configuration file:
the router reads the block's input before any norm and its logits are
float32; top-k first, then a softmax over the chosen logits
(``moe_primary_router_apply_softmax``, ``norm_topk_prob``); no projection
bias; RoPE in half-split pairs; the window counts the position itself (``i -
j < W``); no auxiliary balance loss; no cross-document mask. The config has
no ``secondary`` expert keys, so none are computed.

Departures, each for a stated reason and none changing a value:

- ``remat=True`` recomputes a block at a time in the backward pass, and
  inside a block one group of heads' and one block of queries' scores, and
  one expert's hidden units, at a time; the loss is then summed over
  ``QUERY_BLOCK`` rows of logits at a time. For the chip alone: at 16,384
  tokens one head's float32 scores are 1.07 GB, the logits 2.5 GB (three
  such arrays live in a backward pass) and 16 experts' hidden units 4 GB,
  beside 7.9 GB of parameters, gradients and their sum; the first version
  peaked at 16.3 GB, above the step it is there to check (PERF.md, PR 34).
  The CPU tests run without it and one of them holds the two equal.
- ``head_block`` computes attention for that many heads at a time (a loop)
  and, where the sequence is longer than ``QUERY_BLOCK``, for
  ``QUERY_BLOCK`` queries at a time against every key (``jax.lax.map``, the
  mask built for those rows): the same numbers.
- ``loss_and_grads`` adds a block of rows' gradients into the running sum
  inside the jitted call, the sum donated: a third copy of the gradients
  (2.6 GB) does not fit beside the first two. A batch that is one block
  (the cell's: one sequence) has no sum at all.
- ``dtype`` other than float32 is not the reference: it is the reference
  *computed in a lower precision* (parameters, activations, router, softmax
  and loss all in that type), which the comparison must reject.
- ``cfg`` with another ``sliding_window_layout`` is not the reference
  either: all zeros is the model with its window left off, the second
  control the comparison must reject.

The functions' names and arguments are those
``benchmarks/drivers/sync_mesh_tokens.py:compare_with_reference`` calls.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: queries a block where ``head_block`` is given and the sequence is longer;
#: rows of logits a block under ``remat``
QUERY_BLOCK = 512


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(x.dtype)


def rope(x, theta):
    """``x`` ``[T, heads, R]``: pair ``(x[i], x[i + R/2])`` as the complex
    number ``x[i] + i x[i + R/2]``, multiplied by
    ``exp(i pos theta^(-2i/R))``."""
    t, r = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv  # [T,1,R/2]
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    re, im = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([re * cos - im * sin, re * sin + im * cos],
                           axis=-1)


def keep_mask(rows, t: int, window):
    """``keep(i, j) = j <= i and (window is None or i - j < window)`` for
    the queries ``rows`` ``[Tq]`` against every key: a ``[Tq, T]`` array of
    booleans (``[T, T]`` for all the queries at once)."""
    i, j = rows[:, None], jnp.arange(t)[None, :]
    keep = j <= i
    return keep if window is None else keep & (i - j < window)


def attention(p, a, cfg, layer, head_block, remat=False):
    """``a`` ``[T, D]`` (normed) -> ``[T, D]``; one sequence."""
    t = a.shape[0]
    h, g, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim)
    q = (a @ p["q"]["kernel"]).reshape(t, h, hd)
    k = (a @ p["k"]["kernel"]).reshape(t, g, hd)
    v = (a @ p["v"]["kernel"]).reshape(t, g, hd)
    if cfg.rope_layout[layer]:
        q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
    # each key/value head once for every query head of its group
    k, v = (jnp.repeat(x, h // g, axis=1) for x in (k, v))
    window = cfg.sliding_window_size \
        if cfg.sliding_window_layout[layer] else None

    def heads(q, k, v, rows):
        """The queries ``rows`` ``[Tq]``, ``q`` ``[Tq, n, hd]``, against
        every key."""
        s = jnp.einsum("qhd,khd->hqk", q, k) \
            / jnp.sqrt(jnp.asarray(hd, q.dtype))
        s = jnp.where(keep_mask(rows, t, window)[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    if remat:       # one group's, one query block's score matrices at a time
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


def expert_layer(p, x, u, cfg, remat=False):
    """The router on ``x`` ``[T, D]``, the held experts on ``u`` ``[T, D]``
    -> (``[T, D]``, loads ``[E]``)."""
    first, held = cfg.held_experts
    e, k = cfg.moe_num_primary_experts, cfg.moe_num_active_primary_experts
    logits = x @ p["router"].astype(x.dtype)                        # [T, E]
    chosen = jnp.argsort(-logits, axis=-1)[:, :k]                   # [T, k]
    weights = jax.nn.softmax(
        jnp.take_along_axis(logits, chosen, axis=-1), axis=-1)      # [T, k]
    onehot = jax.nn.one_hot(chosen, e, dtype=logits.dtype)       # [T, k, E]
    dense = jnp.einsum("tk,tke->te", weights, onehot)   # 0 where not chosen
    def expert(u, w, gate, up, down):
        """One expert on every token, weighted (``w`` ``[T]``)."""
        return w[:, None] * ((jax.nn.relu(u @ gate) * (u @ up)) @ down)

    if remat:       # one expert's hidden units and output at a time
        expert = jax.checkpoint(expert)
    # every held expert on every token, one after the other (a scan, so
    # that an expert's gradient is written into its row of the stacked
    # weights' and not padded to their size sixteen times over)
    out, _ = jax.lax.scan(
        lambda out, e: (out + expert(u, *e), None), jnp.zeros_like(u),
        (dense[:, first:first + held].T, p["experts_gate"],
         p["experts_up"], p["experts_down"]))
    return out, jnp.sum(onehot, axis=(0, 1))


def block(p, x, cfg, layer, head_block, remat=False):
    eps = cfg.rms_norm_eps
    y = x + attention(p["attn"], rms_norm(x, p["attn_norm"]["scale"], eps),
                      cfg, layer, head_block, remat)
    m, loads = expert_layer(p["moe"], x,
                            rms_norm(y, p["ffn_norm"]["scale"], eps), cfg,
                            remat)
    return y + m, loads


def sequence_hidden(params, tokens, cfg, *, remat=False, head_block=None):
    """One sequence ``tokens`` ``[T+2]`` (the last is not read) -> the last
    block's output ``[T, D]`` and the loads ``[layers, E]``."""
    def run(p, x, layer):
        return block(p, x, cfg, layer, head_block, remat)
    if remat:
        run = jax.checkpoint(run, static_argnums=(2,))
    x, loads = params["embed"][tokens[:-2]], []
    for layer in range(cfg.num_hidden_layers):
        x, load = run(params[f"layer_{layer}"], x, layer)
        loads.append(load)
    return x, jnp.stack(loads)


def logits_of(params, x, cfg):
    """Final RMSNorm and the untied head on ``x`` ``[N, D]`` -> ``[N, V]``."""
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps) \
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
    these rows'). ``router_bias`` is not read: the model has none."""
    cast = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    total = positions_total or tokens.shape[0] * (tokens.shape[1] - 2)
    next_sum, loads = 0.0, 0
    for row in tokens:
        x, load = sequence_hidden(cast, row, cfg, remat=remat,
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
    2.6 GB of constants in its program."""
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
    if step >= b:       # one block: no running sum, and no room for one
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
    def one(params, row, positions):
        cast = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
        with jax.default_matmul_precision("highest"):
            x, _ = sequence_hidden(cast, row, cfg, head_block=head_block)
            return logits_of(cast, x[positions], cfg)

    return (jnp.stack([one(params, row, positions) for row in tokens]),)


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
    """``b_e += gamma * sign(mean load - load_e)`` for one layer: the rule
    the program's task applies to the array it hands every decoder; this
    model states gamma 0 and the array stays what it was."""
    gap = loads.mean() - loads
    return router_bias + gamma * ((gap > 0) * 1.0 - (gap < 0) * 1.0)
