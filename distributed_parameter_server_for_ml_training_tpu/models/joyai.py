"""JoyAI-LLM-Flash: a DeepSeek-V3-shaped decoder LM in flax.linen.

Source: ``https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/
config.json`` (``model_type`` ``joyai_llm_flash``; 48B-A2.7B). What the
config makes a trainer handle: latent attention (MLA) with q/k heads of
128 + 64 rotary and v heads of 128, one dense SwiGLU layer and then expert
layers (256 routed experts, top-8 of sigmoid scores steered by a
``noaux_tc`` bias, one shared expert), and one multi-token-prediction
module. The equations, block by block, are the module docstrings below; the
plain float32 reference that the tests and the benchmark hold this file to
is ``tests/reference/joyai_reference.py`` (a byte-identical copy lives under
``benchmarks/reference/``).

Training path only, computed as published: MLA unabsorbed, every position
of a packed sequence counted, plain causal attention over the whole
sequence. Parameters are float32, compute is ``dtype`` (bf16 in the
benchmark's cell); RMSNorm, RoPE, the router and the loss compute in
float32 whatever ``dtype`` is.

**The expert layer is told which experts it holds** (``held_experts``:
first id and count). It routes over the router's whole published width and
returns the shared expert's output plus the held experts' terms: the partial
sum that one chip of an expert-parallel deployment computes
(parallel/moe.py:held_expert_ffn). With all experts held it is the whole
layer.

The balancing bias is not a parameter: it takes no gradient, the model
reads it as an argument (``router_bias`` ``[expert layers + MTP, E]``), and
the task (train/tasks.py) applies ``parallel/moe.py:bias_update`` to the
loads this module counts.
"""

from __future__ import annotations

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
class JoyAIConfig:
    """The published ``config.json`` keys this file reads, under their own
    names, plus what the config lacks (``assumed`` in the benchmark's
    configuration file) and the share held here."""
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256        # the router's width, as published
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 32e6
    # -- the share held here: experts first .. first + count - 1
    held_experts: tuple = (0, 256)
    # -- assumed (DeepSeek-V3's report, whose keys this config shares)
    mtp_lambda: float = 0.3
    bias_update_gamma: float = 0.001
    init_std: float = 0.006
    # -- the deployment's: rows an expert layer always computes, in units of
    # the even load (parallel/moe.py:pass_plan); 0: what the routing needs
    expert_capacity_factor: float = 0.0

    def pass_plan(self, n_tokens: int):
        """``(rows, min_passes)`` of an expert layer over ``n_tokens``
        tokens (parallel/moe.py:pass_plan)."""
        return moe.pass_plan(
            n_tokens, self.num_experts_per_tok, self.held_experts[1],
            self.n_routed_experts, self.expert_capacity_factor)

    @property
    def expert_layers(self) -> int:
        """Expert layers that read a router bias: the main model's and the
        MTP module's."""
        return (self.num_hidden_layers - self.first_k_dense_replace
                + self.num_nextn_predict_layers)

    @classmethod
    def from_hf(cls, config: dict, **overrides) -> "JoyAIConfig":
        """From a dict with the published keys (``config.json`` or the
        benchmark's configuration file); refuses what this file does not
        compute."""
        unsupported = {
            "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "hidden_act": "silu",
            "attention_bias": False, "rope_interleave": True,
            "rope_scaling": None, "tie_word_embeddings": False,
            "moe_layer_freq": 1, "n_shared_experts": 1,
            "num_nextn_predict_layers": 1}
        for key, want in unsupported.items():
            if key in config and config[key] != want:
                raise ValueError(f"{key}={config[key]!r}: this model "
                                 f"computes {want!r} only")
        fields = {f: config[f] for f in cls.__dataclass_fields__
                  if f in config}
        return replace(cls(**fields), **overrides)


#: ``--model-preset`` names -> configuration. ``tiny`` is for CPU runs and
#: the tests; ``ep16`` is the benchmark's cut of the published model (one of
#: 16 chips sharing each layer: 1 dense + 4 expert layers and the MTP
#: module, experts 0..15 of 256, an eighth of the vocabulary;
#: benchmarks/configs/joyai-llm-flash-ep16.json holds the same numbers).
PRESETS = {
    "tiny": JoyAIConfig(
        vocab_size=512, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=16, num_experts_per_tok=4, held_experts=(0, 16)),
    # The one-chip cut's two choices (its configuration file says why; PERF.md
    # section 6, PR 28): only the held experts' terms reach the loss, so the
    # router learns toward them and the report's gamma of 0.001 does not
    # hold the loads; and how many of its tokens choose a held expert is a
    # lottery of the seed, which a stated capacity keeps out of the step time.
    "ep16": JoyAIConfig(vocab_size=16160, num_hidden_layers=5,
                        held_experts=(0, 16), bias_update_gamma=0.01,
                        expert_capacity_factor=2.5),
}


def _normal(cfg: JoyAIConfig):
    return nn.initializers.normal(cfg.init_std)


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
    cfg: JoyAIConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, x):
        w = self.param("kernel", _normal(self.cfg),
                       (x.shape[-1], self.features), jnp.float32)
        return x.astype(self.dtype) @ w.astype(self.dtype)


class SwiGLU(nn.Module):
    """``W_down(silu(W_gate u) * (W_up u))``."""
    width: int
    cfg: JoyAIConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, u):
        gate = Linear(self.width, self.cfg, self.dtype, name="gate")(u)
        up = Linear(self.width, self.cfg, self.dtype, name="up")(u)
        return Linear(u.shape[-1], self.cfg, self.dtype,
                      name="down")(jax.nn.silu(gate) * up)


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


class Kernel(nn.Module):
    """A ``Linear``'s parameter (same name, shape, float32 storage and
    initialiser) as ``dtype`` operand, for a caller that cuts it before the
    matmul."""
    shape: tuple
    cfg: JoyAIConfig
    dtype: Dtype

    @nn.compact
    def __call__(self):
        return self.param("kernel", _normal(self.cfg), self.shape,
                          jnp.float32).astype(self.dtype)


class MLA(nn.Module):
    """Latent attention, unabsorbed. ``c_q = RMSNorm(W_qa u)``; ``q = W_qb
    c_q`` as H heads of ``[q_nope; q_rope]``. ``[c_kv; k_rope] = W_kva u``;
    ``c_kv = RMSNorm(c_kv)``; ``W_kvb c_kv`` as H heads of ``[k_nope; v]``;
    ``k_rope`` is ONE vector shared by all heads. RoPE on ``q_rope`` and
    ``k_rope``. Scores ``q.k / sqrt(nope + rope)``, causal, softmax in
    float32; ``W_o`` on the heads' ``P v``.

    **Layouts.** ``u`` and the result are ``[B, T, hidden]``. Between the
    projections and the attention core every array is written once, in the
    layout the flash kernels read (``ops.attention.heads_attention_core``):
    q and k heads-major ``[B, H, T, nope+rope]``, v and ``o`` ``[B, T,
    H*v]`` as their matmuls write and read them (the kernels take a head's
    128 lanes of those as a block). The parameters keep their published
    shapes (``q_b`` ``[q_lora, H*(nope+rope)]``, ``kv_a`` ``[hidden,
    kv_lora+rope]``, ``kv_b`` ``[kv_lora, H*(nope+v)]``); what is cut and
    reordered is the cast weight, a few million elements, never an
    activation of ``B*T`` rows:

    - ``q_b`` and ``kv_b`` are viewed as ``[C, H, D]`` and cut into their
      parts (``q_nope | q_rope``, ``k_nope | v``); ``q_nope``, ``q_rope``
      and ``k_nope`` are projected per head straight to ``[B, H, T, D]``,
      and ``v`` by a plain matmul to ``[B, T, H*v]``, from where it goes to
      the kernel as it is; ``o`` comes back the same way into ``W_o``;
    - scores sum ``q_rope . k_rope`` over the rotary lanes, so one
      permutation of those lanes on both sides changes nothing: the rotary
      columns of ``q_b`` (per head) and of ``kv_a`` are put in half-split
      order (``half_split_lanes``) and the rotation is
      ``rope_half_split``, the products and angles of
      ``rope_interleaved`` (the definition the tests and the reference hold
      this to) on two contiguous halves;
    - ``q = [q_nope; rot(q_rope)]`` and ``k = [k_nope; rot(k_rope) for
      every head]`` are one fusion each, which writes the kernel's operand;
      ``k_rope`` is rotated once as ``[B, T, rope]`` and never exists per
      head on its own."""
    cfg: JoyAIConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, u):
        cfg, dt = self.cfg, self.dtype
        d = u.shape[-1]
        h, nope, rope, vd = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
        lanes = half_split_lanes(rope)
        c_q = RMSNorm(cfg.rms_norm_eps, name="q_a_norm")(
            Linear(cfg.q_lora_rank, cfg, dt, name="q_a")(u))
        w_q = Kernel((cfg.q_lora_rank, h * (nope + rope)), cfg, dt,
                     name="q_b")().reshape(cfg.q_lora_rank, h, nope + rope)
        # cut, then permute the cut: one gather over the uncut lanes
        # (``nope + lanes``) has XLA re-lay ``q_b`` and both its moments
        q_nope = jnp.einsum("btc,chd->bhtd", c_q, w_q[..., :nope])
        q_rope = jnp.einsum("btc,chd->bhtd", c_q, w_q[..., nope:][..., lanes])
        w_kv_a = Kernel((d, cfg.kv_lora_rank + rope), cfg, dt, name="kv_a")()
        u = u.astype(dt)
        c_kv = RMSNorm(cfg.rms_norm_eps, name="kv_a_norm")(
            u @ w_kv_a[:, :cfg.kv_lora_rank])
        k_rope = rope_half_split(
            u @ w_kv_a[:, cfg.kv_lora_rank:][:, lanes],
            cfg.rope_theta)                                  # [B, T, R]
        w_kv = Kernel((cfg.kv_lora_rank, h * (nope + vd)), cfg, dt,
                      name="kv_b")().reshape(cfg.kv_lora_rank, h, nope + vd)
        k_nope = jnp.einsum("btc,chd->bhtd", c_kv, w_kv[..., :nope])
        v = c_kv @ w_kv[..., nope:].reshape(cfg.kv_lora_rank, h * vd)
        q = jnp.concatenate(
            [q_nope, rope_half_split(q_rope, cfg.rope_theta)], axis=-1)
        # [k_nope; k_rope for every head] as a sum of two zero-padded terms:
        # the broadcast over heads then happens inside the one fusion that
        # writes k (a concatenate's operands are materialised first, and
        # k_rope per head is 67 MB a block)
        k = (jnp.pad(k_nope, ((0, 0),) * 3 + ((0, rope),))
             + jnp.pad(k_rope, ((0, 0), (0, 0), (nope, 0)))[:, None])
        o = heads_attention_core(q, k, v, causal=True)       # [B, T, H*vd]
        return Linear(cfg.hidden_size, cfg, dt, name="o")(o)


class ExpertLayer(nn.Module):
    """``shared(u) + sum over the selected e held here of w_e *
    expert_e(u)``. ``s = sigmoid(W_r u)`` over all E (float32, true-float32
    matmul); the ``k`` largest of ``s + b`` are selected; their weights are
    the selected ``s`` over their sum, times ``routed_scaling_factor``.
    Returns ``(y, loads [E] int32, processed)``."""
    cfg: JoyAIConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, u, bias):
        cfg, dt = self.cfg, self.dtype
        b, t, d = u.shape
        first, held = cfg.held_experts
        f, e = cfg.moe_intermediate_size, cfg.n_routed_experts
        router = self.param("router", _normal(cfg), (d, e), jnp.float32)
        experts = {
            name: self.param(f"experts_{name}", _normal(cfg), shape,
                             jnp.float32)
            for name, shape in (("gate", (held, d, f)), ("up", (held, d, f)),
                                ("down", (held, f, d)))}
        x = u.reshape(b * t, d).astype(dt)
        with jax.named_scope("moe_route"):
            scores = jax.nn.sigmoid(jnp.dot(
                x.astype(jnp.float32), router,
                precision=jax.lax.Precision.HIGHEST))
            idx, weights = moe.route_top_k(
                scores, bias, cfg.num_experts_per_tok,
                scaling=cfg.routed_scaling_factor,
                normalize=cfg.norm_topk_prob)
            loads = moe.expert_loads(idx, e)
        rows, min_passes = cfg.pass_plan(b * t)
        routed, processed = moe.held_expert_ffn(
            x, idx, weights, experts, first, rows=rows,
            min_passes=min_passes)
        with jax.named_scope("moe_shared"):
            shared = SwiGLU(f * cfg.n_shared_experts, cfg, dt,
                            name="shared")(x)
        return (shared + routed).reshape(b, t, d), loads, processed


class Block(nn.Module):
    """``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; FFN is the
    dense SwiGLU in the first ``first_k_dense_replace`` layers and the
    expert layer in every other."""
    cfg: JoyAIConfig
    dtype: Dtype
    dense: bool

    @nn.compact
    def __call__(self, x, bias):
        cfg, dt = self.cfg, self.dtype
        with jax.named_scope("mla"):
            h = x + MLA(cfg, dt, name="attn")(
                RMSNorm(cfg.rms_norm_eps, name="attn_norm")(x))
        norm = RMSNorm(cfg.rms_norm_eps, name="ffn_norm")
        if self.dense:
            with jax.named_scope("dense_mlp"):
                y = h + SwiGLU(cfg.intermediate_size, cfg, dt,
                               name="mlp")(norm(h))
            loads = jnp.zeros((cfg.n_routed_experts,), jnp.int32)
            processed = jnp.int32(0)
        else:
            with jax.named_scope("moe_shared"):   # the norm and the sum
                u = norm(h)
            y, loads, processed = ExpertLayer(cfg, dt, name="moe")(u, bias)
            with jax.named_scope("moe_shared"):
                y = h + y
        return y, loads, processed


def _chunked_cross_entropy(hidden, gain, head, targets, eps, dtype,
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


class JoyAILM(nn.Module):
    """The decoder. ``__call__(tokens [B, T+2], router_bias)`` returns the
    training quantities of a packed batch: position ``i < T`` of a row
    reads tokens ``0..i``, predicts token ``i+1`` (the main head) and, from
    the MTP module, token ``i+2``.

    MTP (depth 1): ``z_i = W_eh [RMSNorm(h_i); RMSNorm(Emb(t_{i+1}))]``
    with ``h_i`` the last block's output before the final norm, one more
    block (an expert layer) on ``z``, the module's own output norm, the
    main model's head, cross-entropy against ``t_{i+2}``. Embedding and
    head are the main model's."""
    cfg: JoyAIConfig
    dtype: Dtype = jnp.float32
    # recompute a block at a time in backward, but for its routing
    # decisions (parallel/moe.py:KEEP_ROUTING)
    remat: bool = True
    loss_chunk: int = 4096      # rows of logits live at once

    def setup(self):
        cfg, dt = self.cfg, self.dtype
        block = (nn.remat(Block, policy=moe.KEEP_ROUTING) if self.remat
                 else Block)
        self.embed = self.param("embed", _normal(cfg),
                                (cfg.vocab_size, cfg.hidden_size),
                                jnp.float32)
        self.layers = [
            block(cfg, dt, dense=i < cfg.first_k_dense_replace,
                  name=f"layer_{i}")
            for i in range(cfg.num_hidden_layers)]
        self.final_norm = self.param("final_norm", nn.initializers.ones,
                                     (cfg.hidden_size,), jnp.float32)
        self.head = self.param("head", _normal(cfg),
                               (cfg.hidden_size, cfg.vocab_size),
                               jnp.float32)
        self.mtp_h_norm = RMSNorm(cfg.rms_norm_eps, name="mtp_h_norm")
        self.mtp_e_norm = RMSNorm(cfg.rms_norm_eps, name="mtp_e_norm")
        self.mtp_proj = Linear(cfg.hidden_size, cfg, dt, name="mtp_proj")
        self.mtp_block = block(cfg, dt, dense=False, name="mtp_block")
        self.mtp_out_norm = self.param("mtp_out_norm", nn.initializers.ones,
                                       (cfg.hidden_size,), jnp.float32)

    def hidden(self, tokens, router_bias):
        """``tokens`` ``[B, T+1]`` (inputs and the MTP module's next
        tokens) -> the last block's output ``[B, T, D]``, the MTP block's
        output, the loads ``[expert layers, E]`` and the assignments the
        held experts computed."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            emb = self.embed.astype(self.dtype)[tokens]
        x, loads, processed = emb[:, :-1], [], jnp.int32(0)
        layer = 0
        for i, block in enumerate(self.layers):
            dense = i < cfg.first_k_dense_replace
            x, load, done = block(x, router_bias[layer])
            if not dense:
                loads.append(load)
                processed += done
                layer += 1
        with jax.named_scope("mtp"):
            z = self.mtp_proj(jnp.concatenate(
                [self.mtp_h_norm(x), self.mtp_e_norm(emb[:, 1:])], axis=-1))
            z, load, done = self.mtp_block(z, router_bias[layer])
        loads.append(load)
        return x, z, jnp.stack(loads), processed + done

    def __call__(self, tokens, router_bias):
        cfg = self.cfg
        b, t = tokens.shape[0], tokens.shape[1] - 2
        x, z, loads, processed = self.hidden(tokens[:, :-1], router_bias)
        # the most rows, up to ``loss_chunk``, that divide the batch
        chunk = max(c for c in range(1, min(self.loss_chunk, b * t) + 1)
                    if (b * t) % c == 0)
        with jax.named_scope("head_loss"):
            next_sum, hits = _chunked_cross_entropy(
                x.reshape(b * t, -1), self.final_norm, self.head,
                tokens[:, 1:-1].reshape(-1), cfg.rms_norm_eps, self.dtype,
                chunk)
            mtp_sum, _ = _chunked_cross_entropy(
                z.reshape(b * t, -1), self.mtp_out_norm, self.head,
                tokens[:, 2:].reshape(-1), cfg.rms_norm_eps, self.dtype,
                chunk)
        next_loss, mtp_loss = next_sum / (b * t), mtp_sum / (b * t)
        return {"loss": next_loss + cfg.mtp_lambda * mtp_loss,
                "next_loss": next_loss, "mtp_loss": mtp_loss,
                "correct": hits, "count": jnp.int32(b * t),
                "loads": loads, "processed": processed}

    def logits_at(self, tokens, router_bias, positions):
        """Float32 logits of both heads at ``positions`` ``[P]`` of every
        row: ``(main [B, P, V], mtp [B, P, V])``."""
        cfg = self.cfg
        x, z, _loads, _n = self.hidden(tokens[:, :-1], router_bias)
        w = self.head.astype(self.dtype)
        return tuple(
            (rms_norm(h[:, positions], gain, cfg.rms_norm_eps).astype(
                self.dtype) @ w).astype(jnp.float32)
            for h, gain in ((x, self.final_norm), (z, self.mtp_out_norm)))
