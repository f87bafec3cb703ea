"""Plain reference of the JoyAI-LLM-Flash training step: forward, loss (with
the multi-token-prediction loss) and gradients, in ``jax.numpy``, and the
update they go through: AdamW and the router-bias rule, in plain arithmetic.

Written from the equations of the model's public description (DeepSeek-V3's
report, whose ``config.json`` keys JoyAI-LLM-Flash shares one for one), not
from the program: no kernel, no sort (a dense one-hot over the experts), no
chunked loss, every score matrix materialised. Float32 with true-float32
matrix products (``jax.default_matmul_precision("highest")``: on a TPU a
float32 product otherwise runs in bf16 passes). The program's tests and the
benchmark's ``matches_reference`` clause hold models/joyai.py to this file;
``tests/reference/`` and ``benchmarks/reference/`` hold byte-identical
copies (a test says so).

It reads the program's parameter tree (names below) and a configuration
object's numbers (``cfg.<published key>``), nothing else of the program.

Per token ``x`` of width D; no projection has a bias;
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.

- Block: ``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; FFN is
  a dense SwiGLU in the leading ``first_k_dense_replace`` layers and the
  expert layer after them.
- The expert layer is given the range of experts held (``cfg.held_experts``
  = first, count) and returns ``shared(u)`` plus the held experts' terms
  only: one share of an expert-parallel deployment. The vocabulary slice is
  what the embedding and head parameters hold.

- The update (:func:`adamw_step`, :func:`bias_step`) is written with
  operators alone, so that it takes ``numpy`` arrays on the host as well as
  ``jax`` arrays: at the published widths the comparison holds the program's
  state and the reference's side by side, which no chip does.

Departures, each for a stated reason:

- ``remat=True`` recomputes a block at a time in the backward pass, and
  inside a block one group of heads' scores at a time. It changes no value
  (the same operations run twice) and is for the chip alone: one
  4,096-token sequence's float32 probabilities are 2.1 GB a block, 12.9 GB
  for six. The CPU tests run without it and one of them holds the two
  equal.
- ``head_block`` computes attention for that many heads at a time (a loop;
  the same numbers), for the same reason.
- ``loss_and_grads`` adds a block of rows' gradients into the running sum
  inside the jitted call, the sum donated: a third copy of the gradients
  (2.7 GB) does not fit beside the first two.
- ``dtype`` other than float32 is not the reference: it is the reference
  *computed in a lower precision* (parameters, activations, router, softmax
  and loss all in that type), which the comparison must reject.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain.astype(x.dtype)


def rope(x, theta):
    """``x`` ``[T, ..., R]``: interleaved pairs ``(x[2i], x[2i+1])`` as the
    complex number ``x[2i] + i x[2i+1]``, multiplied by
    ``exp(i pos theta^(-2i/R))``."""
    t, r = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]   # [T,R/2]
    angle = angle.reshape((t,) + (1,) * (x.ndim - 2) + (r // 2,))
    re, im = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    out_re, out_im = re * cos - im * sin, re * sin + im * cos
    return jnp.stack([out_re, out_im], axis=-1).reshape(x.shape)


def swiglu(p, u):
    return (jax.nn.silu(u @ p["gate"]["kernel"]) * (u @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def mla(p, u, cfg, head_block, remat=False):
    """``u`` ``[T, D]`` -> ``[T, D]``; one sequence."""
    t = u.shape[0]
    h, nope, rp, vd, rank = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim,
                             cfg.kv_lora_rank)
    c_q = rms_norm(u @ p["q_a"]["kernel"], p["q_a_norm"]["scale"],
                   cfg.rms_norm_eps)
    q = (c_q @ p["q_b"]["kernel"]).reshape(t, h, nope + rp)
    kv_a = u @ p["kv_a"]["kernel"]
    c_kv = rms_norm(kv_a[:, :rank], p["kv_a_norm"]["scale"],
                    cfg.rms_norm_eps)
    k_rope = rope(kv_a[:, rank:], cfg.rope_theta)          # [T, R], shared
    kv = (c_kv @ p["kv_b"]["kernel"]).reshape(t, h, nope + vd)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], cfg.rope_theta)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def heads(q_nope, q_rope, k_nope, k_rope, v):
        s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
             + jnp.einsum("qhr,kr->hqk", q_rope, k_rope))
        s = s / jnp.sqrt(jnp.asarray(nope + rp, s.dtype))
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    if remat:       # one group's score matrices live at a time
        heads = jax.checkpoint(heads)
    outs = []
    for lo in range(0, h, head_block or h):
        hi = min(h, lo + (head_block or h))
        outs.append(heads(q_nope[:, lo:hi], q_rope[:, lo:hi],
                          k_nope[:, lo:hi], k_rope, v[:, lo:hi]))
    return jnp.concatenate(outs, axis=1).reshape(t, h * vd) @ p["o"]["kernel"]


def expert_layer(p, u, bias, cfg):
    """``u`` ``[T, D]`` -> (``[T, D]``, loads ``[E]``): the shared expert
    plus the held experts' weighted terms, by a dense one-hot."""
    first, held = cfg.held_experts
    e, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    scores = jax.nn.sigmoid(u @ p["router"].astype(u.dtype))        # [T, E]
    chosen = jnp.argsort(-(scores + bias.astype(scores.dtype)),
                         axis=-1)[:, :k]                            # [T, k]
    onehot = jax.nn.one_hot(chosen, e, dtype=scores.dtype)      # [T, k, E]
    picked = jnp.sum(onehot, axis=1)                   # [T, E] of 0 and 1
    weights = picked * scores
    if cfg.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * cfg.routed_scaling_factor
    mine = weights[:, first:first + held]                           # [T, C]
    hidden = (jax.nn.silu(jnp.einsum("td,cdf->ctf", u, p["experts_gate"]))
              * jnp.einsum("td,cdf->ctf", u, p["experts_up"]))
    each = jnp.einsum("ctf,cfd->ctd", hidden, p["experts_down"])
    routed = jnp.einsum("tc,ctd->td", mine, each)
    return swiglu(p["shared"], u) + routed, jnp.sum(picked, axis=0)


def block(p, x, bias, cfg, dense, head_block, remat=False):
    h = x + mla(p["attn"], rms_norm(x, p["attn_norm"]["scale"],
                                    cfg.rms_norm_eps), cfg, head_block,
                remat)
    u = rms_norm(h, p["ffn_norm"]["scale"], cfg.rms_norm_eps)
    if dense:
        return h + swiglu(p["mlp"], u), None
    y, loads = expert_layer(p["moe"], u, bias, cfg)
    return h + y, loads


def sequence_outputs(params, router_bias, tokens, cfg, *, remat=False,
                     head_block=None):
    """One sequence ``tokens`` ``[T+2]`` -> float32-or-``dtype`` logits of
    the main head ``[T, V]`` and of the MTP module ``[T, V]``, and the loads
    ``[expert layers, E]``."""
    def run(p, x, bias, dense):
        return block(p, x, bias, cfg, dense, head_block, remat)
    if remat:
        run = jax.checkpoint(run, static_argnums=(3,))
    emb = params["embed"][tokens[:-1]]                           # [T+1, D]
    x, loads, layer = emb[:-1], [], 0
    for i in range(cfg.num_hidden_layers):
        dense = i < cfg.first_k_dense_replace
        x, load = run(params[f"layer_{i}"], x, router_bias[layer], dense)
        if not dense:
            loads.append(load)
            layer += 1
    eps = cfg.rms_norm_eps
    z = jnp.concatenate(
        [rms_norm(x, params["mtp_h_norm"]["scale"], eps),
         rms_norm(emb[1:], params["mtp_e_norm"]["scale"], eps)], axis=-1) \
        @ params["mtp_proj"]["kernel"]
    z, load = run(params["mtp_block"], z, router_bias[layer], False)
    loads.append(load)
    main = rms_norm(x, params["final_norm"], eps) @ params["head"]
    mtp = rms_norm(z, params["mtp_out_norm"], eps) @ params["head"]
    return main, mtp, jnp.stack(loads)


def _cross_entropy_sum(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=1))


def batch_loss(params, router_bias, tokens, cfg, *, positions_total=None,
               dtype=jnp.float32, remat=False, head_block=None):
    """``tokens`` ``[B, T+2]`` -> ``(loss, aux)``: the sum over the rows'
    positions of next-token cross-entropy + ``mtp_lambda`` x the MTP
    cross-entropy, over ``positions_total`` (the whole batch's positions
    when the rows are one block of it; default these rows')."""
    cast = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    total = positions_total or tokens.shape[0] * (tokens.shape[1] - 2)
    next_sum = mtp_sum = 0.0
    loads = 0
    for row in tokens:
        main, mtp, load = sequence_outputs(
            cast, router_bias, row, cfg, remat=remat, head_block=head_block)
        next_sum = next_sum + _cross_entropy_sum(main, row[1:-1])
        mtp_sum = mtp_sum + _cross_entropy_sum(mtp, row[2:])
        loads = loads + load
    next_loss, mtp_loss = next_sum / total, mtp_sum / total
    loss = (next_loss + cfg.mtp_lambda * mtp_loss).astype(jnp.float32)
    return loss, {"next_loss": next_loss, "mtp_loss": mtp_loss,
                  "loads": loads}


def block_grads(params, router_bias, rows, cfg, *, positions_total,
                dtype=jnp.float32, remat=False, head_block=None):
    """``((loss, aux), grads)`` of one block of rows: its share of the
    batch's loss. The parameters are an argument of the jitted call, not
    2.7 GB of constants in its program."""
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
    """Logits of both heads at ``positions`` of every row of ``tokens``
    ``[B, T+2]``: ``(main [B, P, V], mtp [B, P, V])``, a row at a time."""
    @jax.jit
    def one(params, router_bias, row, positions):
        cast = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
        with jax.default_matmul_precision("highest"):
            main, mtp, _ = sequence_outputs(cast, router_bias, row, cfg,
                                            head_block=head_block)
        return main[positions], mtp[positions]

    rows = [one(params, router_bias, row, positions) for row in tokens]
    return (jnp.stack([r[0] for r in rows]),
            jnp.stack([r[1] for r in rows]))


def adamw_step(param, grad, mu, nu, count, *, learning_rate, b1, b2, eps,
               weight_decay):
    """One AdamW step on one tensor, float32 throughout: ``(param, mu, nu)``
    after step number ``count`` (1 for the first). Decoupled weight decay on
    matrices only (a tensor of two or more axes); bias-corrected moments."""
    mu = b1 * mu + (1.0 - b1) * grad
    nu = b2 * nu + (1.0 - b2) * grad * grad
    m_hat = mu / (1.0 - b1 ** count)
    v_hat = nu / (1.0 - b2 ** count)
    step = m_hat / (v_hat ** 0.5 + eps)
    if param.ndim >= 2:
        step = step + weight_decay * param
    return param - learning_rate * step, mu, nu


def bias_step(router_bias, loads, gamma):
    """``b_e += gamma * sign(mean load - load_e)`` for one expert layer:
    ``router_bias`` and ``loads`` ``[E]``, the loads counted over all E."""
    gap = loads.mean() - loads
    return router_bias + gamma * ((gap > 0) * 1.0 - (gap < 0) * 1.0)
