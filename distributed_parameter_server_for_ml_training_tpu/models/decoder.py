"""What the decoder LMs share: their blocks, one LM skeleton, one sigmoid
router, one routed-SwiGLU expert layer, and what a decoder's configuration
gives train/tasks.py:LMTask.

The decoders (models/joyai.py, smallthinker.py, nemotron_h.py, lfm2.py)
import from here, from ``ops/`` and from ``parallel/``, and never from one
another; models/registry.py is the one module that imports them.

Parameters are float32, compute is ``dtype``; RMSNorm, RoPE, the router and
the loss compute in float32 whatever ``dtype`` is. An expert layer is **told
which experts it holds** (``held_experts``: first id and count): it routes
over the router's whole published width and returns the held experts'
terms, the partial sum that one chip of an expert-parallel deployment
computes (parallel/moe.py:held_expert_ffn). The balancing bias is not a
parameter: the LM reads it as an argument (``router_bias`` ``[expert
layers, E]``) and the task applies ``parallel/moe.py:bias_update`` to the
loads the layers count.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import heads_attention_core
from ..parallel import moe

Dtype = Any


@dataclass(frozen=True)
class DecoderConfig:
    """What a decoder's configuration gives train/tasks.py:LMTask and this
    module. A decoder's configuration is a frozen dataclass of this class
    whose fields are the source's published keys under their own names;
    where the source spells one of the names below otherwise, the
    configuration gives it as a property beside the published field:

    - ``vocab_size``, ``hidden_size``, ``init_std`` (the initialisers'
      standard deviation);
    - ``norm_eps``: the final norm's eps;
    - ``n_routed_experts`` (the router's width), ``num_experts_per_tok``,
      ``held_experts`` (first id and count of the experts held here);
    - ``expert_layers``: the layers that read a row of ``router_bias``;
    - ``bias_update_gamma``: the balancing bias's step (0: no bias).
    """
    # -- the deployment's: rows an expert layer always computes, in units of
    # the even load (parallel/moe.py:pass_plan); 0: what the routing needs
    expert_capacity_factor: float = 0.0

    def pass_plan(self, n_tokens: int):
        """``(rows, min_passes)`` of an expert layer over ``n_tokens``
        tokens (parallel/moe.py:pass_plan)."""
        return moe.pass_plan(
            n_tokens, self.num_experts_per_tok, self.held_experts[1],
            self.n_routed_experts, self.expert_capacity_factor)

    @classmethod
    def from_published(cls, config: dict, unsupported: dict, **overrides):
        """From a dict with the published keys (``config.json`` or the
        benchmark's configuration file), lists as tuples, then
        ``overrides``; refuses a key of ``unsupported`` whose value is not
        the one the model computes."""
        for key, want in unsupported.items():
            if key in config and config[key] != want:
                raise ValueError(f"{key}={config[key]!r}: this model "
                                 f"computes {want!r} only")
        fields = {f: tuple(config[f]) if isinstance(config[f], list)
                  else config[f]
                  for f in cls.__dataclass_fields__ if f in config}
        return replace(cls(**fields), **overrides)


def normal_init(cfg):
    return nn.initializers.normal(cfg.init_std)


def fan_in_uniform(width: int):
    """Uniform on ``+- 1 / sqrt(K)``: the library default of a depthwise
    convolution of width ``K`` (its fan-in)."""
    def init(key, shape, dtype=jnp.float32):
        bound = 1.0 / math.sqrt(width)
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def rms_norm(x, gain, eps):
    """``x / sqrt(mean(x^2) + eps) * g`` in float32, cast back."""
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale * gain).astype(x.dtype)


def _rope_cos_sin(t: int, r: int, theta: float):
    """``cos`` and ``sin`` of ``pos * theta^(-2i/R)``, each ``[T, R/2]``
    float32: pair ``i``'s angle at every position."""
    freqs = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None]
    return jnp.cos(angles), jnp.sin(angles)


def rope_interleaved(x, theta: float):
    """RoPE on ``[B, T, ..., R]``, position = index along axis 1, pairs
    ``(x[2i], x[2i+1])`` rotated by ``pos * theta^(-2i/R)``
    (``rope_interleave``), no scaling; float32 inside."""
    t, r = x.shape[1], x.shape[-1]
    shape = (1, t) + (1,) * (x.ndim - 3) + (r // 2,)
    cos, sin = (a.reshape(shape) for a in _rope_cos_sin(t, r, theta))
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


def half_split_lanes(r: int) -> np.ndarray:
    """The permutation of ``r`` rotary lanes from interleaved to half-split
    order, ``(0, 2, 4, ..., 1, 3, 5, ...)``: after it the pair ``(x[2i],
    x[2i+1])`` that ``rope_interleaved`` rotates is ``(x'[i], x'[i +
    r/2])``."""
    return np.concatenate([np.arange(0, r, 2), np.arange(1, r, 2)])


def rope_half_split(x, theta: float):
    """``rope_interleaved`` on lanes that ``half_split_lanes`` has permuted:
    ``x`` is ``[..., T, R]``, position = index along axis -2, and pair ``i``
    is ``(x[i], x[i + R/2])``, rotated by the same ``pos * theta^(-2i/R)``;
    float32 inside. The same products and angles, lane for lane, with the
    two halves contiguous: no ``(R/2, 2)`` reshape and no ``stack``, which
    on a TPU are a stride-2 shuffle across lanes."""
    t, r = x.shape[-2], x.shape[-1]
    cos, sin = _rope_cos_sin(t, r, theta)
    x32 = x.astype(jnp.float32)
    lo, hi = x32[..., :r // 2], x32[..., r // 2:]
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos],
                           axis=-1).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        gain = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                          jnp.float32)
        return rms_norm(x, gain, self.eps)


class Linear(nn.Module):
    """``x @ W`` without bias; float32 parameter, ``dtype`` operands."""
    features: int
    cfg: DecoderConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, x):
        w = self.param("kernel", normal_init(self.cfg),
                       (x.shape[-1], self.features), jnp.float32)
        return x.astype(self.dtype) @ w.astype(self.dtype)


class Kernel(nn.Module):
    """A ``Linear``'s parameter (same name, shape, float32 storage and
    initialiser) as ``dtype`` operand, for a caller that cuts it before the
    matmul."""
    shape: tuple
    cfg: DecoderConfig
    dtype: Dtype

    @nn.compact
    def __call__(self):
        return self.param("kernel", normal_init(self.cfg), self.shape,
                          jnp.float32).astype(self.dtype)


class SwiGLU(nn.Module):
    """``W_down(silu(W_gate u) * (W_up u))``."""
    width: int
    cfg: DecoderConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, u):
        gate = Linear(self.width, self.cfg, self.dtype, name="gate")(u)
        up = Linear(self.width, self.cfg, self.dtype, name="up")(u)
        return Linear(u.shape[-1], self.cfg, self.dtype,
                      name="down")(jax.nn.silu(gate) * up)


class GroupedAttention(nn.Module):
    """``q, k, v = a W_q [T, H, D], a W_k [T, G, D], a W_v [T, G, D]`` (no
    bias), RoPE on q and k where ``rope`` says (half-split pairs ``(x[i],
    x[i + D/2])``), ``o = softmax(q k_g^T / sqrt(D) + mask) v_g`` with query
    head ``h`` on key/value head ``g = h // (H/G)`` and ``mask`` causal
    and, with a ``window``, ``i - j < window``; ``W_o`` on the heads.

    **Layouts**, as models/joyai.py:MLA's: q and k are projected per head
    straight to heads-major ``[B, H, T, D]`` and ``[B, G, T, D]``, v by a
    plain matmul to ``[B, T, G*D]``, and ``o`` comes back ``[B, T, H*D]``
    into ``W_o``: the arrays the flash kernels read and write as they are.
    The parameters keep their published shapes (``[hidden, H*D]``, ``[hidden,
    G*D]``, ``[H*D, hidden]``).

    ``cfg`` is any decoder's configuration that states ``hidden_size``,
    ``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
    ``rope_theta`` and ``init_std``. ``qk_norm``, where given, is the eps of
    an RMSNorm over each q and each k head (a gain of ``D`` a side,
    ``q_norm`` and ``k_norm``), applied before RoPE."""
    cfg: DecoderConfig
    dtype: Dtype
    rope: bool
    window: int | None
    qk_norm: float | None = None

    @nn.compact
    def __call__(self, a):
        cfg, dt = self.cfg, self.dtype
        d = a.shape[-1]
        h, g, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        w_q = Kernel((d, h * hd), cfg, dt, name="q")().reshape(d, h, hd)
        w_k = Kernel((d, g * hd), cfg, dt, name="k")().reshape(d, g, hd)
        a = a.astype(dt)
        q = jnp.einsum("btc,chd->bhtd", a, w_q)
        k = jnp.einsum("btc,chd->bhtd", a, w_k)
        v = Linear(g * hd, cfg, dt, name="v")(a)              # [B, T, G*D]
        if self.qk_norm is not None:
            q = RMSNorm(self.qk_norm, name="q_norm")(q)
            k = RMSNorm(self.qk_norm, name="k_norm")(k)
        if self.rope:
            q = rope_half_split(q, cfg.rope_theta)
            k = rope_half_split(k, cfg.rope_theta)
        o = heads_attention_core(q, k, v, causal=True, window=self.window)
        return Linear(cfg.hidden_size, cfg, dt, name="o")(o)


def expert_params(module: nn.Module, cfg, d: int, f: int,
                  names=("gate", "up", "down")) -> dict:
    """``{name: experts_{name}}``, the held experts' weights created on
    ``module`` in the order of ``names``: ``[held, f, d]`` for ``down``,
    ``[held, d, f]`` for every other."""
    held = cfg.held_experts[1]
    return {name: module.param(f"experts_{name}", normal_init(cfg),
                               (held, f, d) if name == "down"
                               else (held, d, f), jnp.float32)
            for name in names}


def sigmoid_route(x, router, bias, cfg):
    """``s = sigmoid(x W_r)`` over all E (float32, true-float32 matmul); the
    ``k`` largest of ``s + b`` are selected, their weights the selected
    ``s`` over their sum (``norm_topk_prob``), times
    ``routed_scaling_factor``. Traced under ``moe_route``. Returns ``(idx
    [N, k], weights [N, k], loads [E] int32)``."""
    with jax.named_scope("moe_route"):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router,
            precision=jax.lax.Precision.HIGHEST))
        idx, weights = moe.route_top_k(
            scores, bias, cfg.num_experts_per_tok,
            scaling=cfg.routed_scaling_factor,
            normalize=cfg.norm_topk_prob)
        loads = moe.expert_loads(idx, cfg.n_routed_experts)
    return idx, weights, loads


class ExpertLayer(nn.Module):
    """``shared(u) + sum over the selected e held here of w_e *
    W_down,e(silu(W_gate,e u) * (W_up,e u))``, routed by
    :func:`sigmoid_route`, experts of ``moe_intermediate_size``;
    ``shared`` is a SwiGLU of ``shared_experts`` times that width (traced
    under ``moe_shared``), and absent where ``shared_experts`` is 0.
    Returns ``(y, loads [E] int32, processed)``."""
    cfg: DecoderConfig
    dtype: Dtype
    shared_experts: int = 0

    @nn.compact
    def __call__(self, u, bias):
        cfg, dt = self.cfg, self.dtype
        b, t, d = u.shape
        f = cfg.moe_intermediate_size
        router = self.param("router", normal_init(cfg),
                            (d, cfg.n_routed_experts), jnp.float32)
        experts = expert_params(self, cfg, d, f)
        x = u.reshape(b * t, d).astype(dt)
        idx, weights, loads = sigmoid_route(x, router, bias, cfg)
        rows, min_passes = cfg.pass_plan(b * t)
        y, processed = moe.held_expert_ffn(
            x, idx, weights, experts, cfg.held_experts[0], rows=rows,
            min_passes=min_passes)
        if self.shared_experts:
            with jax.named_scope("moe_shared"):
                shared = SwiGLU(f * self.shared_experts, cfg, dt,
                                name="shared")(x)
            y = shared + y
        return y.reshape(b, t, d), loads, processed


def chunked_cross_entropy(hidden, gain, head, targets, eps, dtype,
                          chunk: int):
    """Sum of the next-token cross-entropies and the count of correct
    arg-max predictions over ``hidden`` ``[N, D]`` against ``targets``
    ``[N]``: final RMSNorm, head matmul in ``dtype``, log-softmax in
    float32. Worked off ``chunk`` rows at a time under ``jax.checkpoint``
    so that only one chunk's ``[chunk, V]`` float32 logits are live (the
    whole batch's would be 1.06 GB a head at the benchmark's sizes)."""
    n, d = hidden.shape
    chunks = n // chunk
    w = head.astype(dtype)

    @jax.checkpoint
    def one(args):
        h, t = args
        logits = (rms_norm(h, gain, eps).astype(dtype) @ w).astype(
            jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, t[:, None], axis=1)[:, 0]
        return (jnp.sum(logz - picked),
                jnp.sum(jnp.argmax(logits, axis=-1) == t))

    losses, hits = jax.lax.map(one, (hidden.reshape(chunks, chunk, d),
                                     targets.reshape(chunks, chunk)))
    return jnp.sum(losses), jnp.sum(hits)


def loss_rows(n: int, most: int) -> int:
    """The most rows, up to ``most``, that divide ``n``: the chunk that
    :func:`chunked_cross_entropy` works off ``n`` rows in."""
    return max(c for c in range(1, min(most, n) + 1) if n % c == 0)


def head_logits(h, gain, w, eps, dtype):
    """Float32 logits of ``h`` through a final norm of gain ``gain`` and
    the head ``w``, already cast to ``dtype``."""
    return (rms_norm(h, gain, eps).astype(dtype) @ w).astype(jnp.float32)


def remat_layer(layer, remat: bool):
    """``layer``, where ``remat`` says recomputed a layer at a time in
    backward but for its routing decisions (parallel/moe.py:KEEP_ROUTING)."""
    return nn.remat(layer, policy=moe.KEEP_ROUTING) if remat else layer


def run_layers(layers, x, router_bias, experts):
    """``x`` through ``layers``, where ``experts[i]`` says whether layer
    ``i`` is an expert layer: the ``j``-th of those is handed
    ``router_bias[j]``, every other layer ``None``. Returns ``(x, the
    expert layers' loads as a list, the assignments their held experts
    computed)``."""
    loads, processed = [], jnp.int32(0)
    for layer, expert in zip(layers, experts):
        x, load, done = layer(x, router_bias[len(loads)] if expert else None)
        if expert:
            loads.append(load)
            processed += done
    return x, loads, processed


class DecoderLM(nn.Module):
    """A decoder LM's skeleton: ``embed`` ``[V, D]``, the layers,
    ``final_norm``, an untied ``head`` ``[D, V]``; the three parameters are
    the LM module's own, so their paths stay top-level.

    ``__call__(tokens [B, T+2], router_bias [expert layers, E])`` returns
    the training quantities of a packed batch: position ``i < T`` of a row
    reads tokens ``0..i`` and predicts token ``i+1``. Rows are ``T+2`` long
    because that is what train/tasks.py:LMTask hands every decoder
    (models/joyai.py's second head predicts token ``i+2``); here the last
    token is not read.

    A decoder's ``setup`` calls this one, then sets ``layers`` (their
    class through :func:`remat_layer`) and ``experts`` (one flag a layer:
    an expert layer reads a row of ``router_bias``). A layer is called
    ``layer(x, bias)`` and returns ``(x, loads, processed)``. A decoder with
    a second head (models/joyai.py) overrides ``hidden`` to return each
    head's input before the loads, sets ``head_norms`` to the heads' final
    gains and ``ahead`` to the tokens past ``T`` its ``hidden`` reads, and
    has a ``__call__`` of its own."""
    cfg: DecoderConfig
    dtype: Dtype = jnp.float32
    remat: bool = True          # remat_layer
    loss_chunk: int = 4096      # rows of logits live at once
    ahead = 0

    def setup(self):
        cfg = self.cfg
        self.embed = self.param("embed", normal_init(cfg),
                                (cfg.vocab_size, cfg.hidden_size),
                                jnp.float32)
        self.final_norm = self.param("final_norm", nn.initializers.ones,
                                     (cfg.hidden_size,), jnp.float32)
        self.head = self.param("head", normal_init(cfg),
                               (cfg.hidden_size, cfg.vocab_size),
                               jnp.float32)
        self.head_norms = (self.final_norm,)

    def hidden(self, tokens, router_bias):
        """``tokens`` ``[B, T]`` -> the last layer's output ``[B, T, D]``,
        the loads ``[expert layers, E]`` and the assignments the held
        experts computed."""
        with jax.named_scope("embed"):
            x = self.embed.astype(self.dtype)[tokens]
        x, loads, processed = run_layers(self.layers, x, router_bias,
                                         self.experts)
        return x, jnp.stack(loads), processed

    def __call__(self, tokens, router_bias):
        b, t = tokens.shape[0], tokens.shape[1] - 2
        x, loads, processed = self.hidden(tokens[:, :-2], router_bias)
        chunk = loss_rows(b * t, self.loss_chunk)
        with jax.named_scope("head_loss"):
            next_sum, hits = chunked_cross_entropy(
                x.reshape(b * t, -1), self.final_norm, self.head,
                tokens[:, 1:-1].reshape(-1), self.cfg.norm_eps, self.dtype,
                chunk)
        loss = next_sum / (b * t)
        return {"loss": loss, "next_loss": loss, "correct": hits,
                "count": jnp.int32(b * t), "loads": loads,
                "processed": processed}

    def logits_at(self, tokens, router_bias, positions):
        """Float32 logits at ``positions`` ``[P]`` of every row, a ``[B, P,
        V]`` a head: ``(main,)``, or ``(main, mtp)`` of models/joyai.py."""
        *outs, _loads, _n = self.hidden(
            tokens[:, :tokens.shape[1] - 2 + self.ahead], router_bias)
        w = self.head.astype(self.dtype)
        return tuple(head_logits(h[:, positions], gain, w, self.cfg.norm_eps,
                                 self.dtype)
                     for h, gain in zip(outs, self.head_norms))
