"""JoyAI-LLM-Flash: a DeepSeek-V3-shaped decoder LM in flax.linen.

Source: ``https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/
config.json`` (``model_type`` ``joyai_llm_flash``; 48B-A2.7B). What the
config makes a trainer handle: latent attention (MLA) with q/k heads of
128 + 64 rotary and v heads of 128, one dense SwiGLU layer and then expert
layers (256 routed experts, top-8 of sigmoid scores steered by a
``noaux_tc`` bias, one shared expert), and one multi-token-prediction
module. The equations, block by block, are the module docstrings below; the
plain float32 reference that the tests and the benchmark hold this file to
is ``benchmarks/reference/joyai_reference.py``.

Training path only, computed as published: MLA unabsorbed, every position
of a packed sequence counted, plain causal attention over the whole
sequence. The blocks, the skeleton, the loss and the expert layer (here
with its one shared expert) are models/decoder.py's; the balancing bias is
``[expert layers + MTP, E]``.
"""

from __future__ import annotations

from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import heads_attention_core
from .decoder import (DecoderConfig, DecoderLM, Dtype, ExpertLayer, Kernel,
                      Linear, RMSNorm, SwiGLU, chunked_cross_entropy,
                      half_split_lanes, loss_rows, remat_layer,
                      rope_half_split, run_layers)


@dataclass(frozen=True)
class JoyAIConfig(DecoderConfig):
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

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    @property
    def expert_layers(self) -> int:
        """Expert layers that read a router bias: the main model's and the
        MTP module's."""
        return (self.num_hidden_layers - self.first_k_dense_replace
                + self.num_nextn_predict_layers)

    @classmethod
    def from_hf(cls, config: dict, **overrides) -> "JoyAIConfig":
        """``from_published``, refusing what this file does not compute."""
        return cls.from_published(config, {
            "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "hidden_act": "silu",
            "attention_bias": False, "rope_interleave": True,
            "rope_scaling": None, "tie_word_embeddings": False,
            "moe_layer_freq": 1, "n_shared_experts": 1,
            "num_nextn_predict_layers": 1}, **overrides)


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
            y, loads, processed = ExpertLayer(
                cfg, dt, shared_experts=cfg.n_shared_experts, name="moe")(
                    u, bias)
            with jax.named_scope("moe_shared"):
                y = h + y
        return y, loads, processed


class JoyAILM(DecoderLM):
    """The decoder: models/decoder.py's skeleton with a second head. Position
    ``i < T`` of a row also predicts token ``i+2``, from the MTP module.

    MTP (depth 1): ``z_i = W_eh [RMSNorm(h_i); RMSNorm(Emb(t_{i+1}))]``
    with ``h_i`` the last block's output before the final norm, one more
    block (an expert layer) on ``z``, the module's own output norm, the
    main model's head, cross-entropy against ``t_{i+2}``. Embedding and
    head are the main model's."""
    cfg: JoyAIConfig
    ahead = 1       # the MTP module reads token i+1

    def setup(self):
        super().setup()
        cfg, dt = self.cfg, self.dtype
        block = remat_layer(Block, self.remat)
        self.layers = [
            block(cfg, dt, dense=i < cfg.first_k_dense_replace,
                  name=f"layer_{i}")
            for i in range(cfg.num_hidden_layers)]
        self.experts = [i >= cfg.first_k_dense_replace
                        for i in range(cfg.num_hidden_layers)]
        self.mtp_h_norm = RMSNorm(cfg.rms_norm_eps, name="mtp_h_norm")
        self.mtp_e_norm = RMSNorm(cfg.rms_norm_eps, name="mtp_e_norm")
        self.mtp_proj = Linear(cfg.hidden_size, cfg, dt, name="mtp_proj")
        self.mtp_block = block(cfg, dt, dense=False, name="mtp_block")
        self.mtp_out_norm = self.param("mtp_out_norm", nn.initializers.ones,
                                       (cfg.hidden_size,), jnp.float32)
        self.head_norms = (self.final_norm, self.mtp_out_norm)

    def hidden(self, tokens, router_bias):
        """``tokens`` ``[B, T+1]`` (inputs and the MTP module's next
        tokens) -> the last block's output ``[B, T, D]``, the MTP block's
        output, the loads ``[expert layers, E]`` and the assignments the
        held experts computed."""
        with jax.named_scope("embed"):
            emb = self.embed.astype(self.dtype)[tokens]
        x, loads, processed = run_layers(self.layers, emb[:, :-1],
                                         router_bias, self.experts)
        with jax.named_scope("mtp"):
            z = self.mtp_proj(jnp.concatenate(
                [self.mtp_h_norm(x), self.mtp_e_norm(emb[:, 1:])], axis=-1))
            z, load, done = self.mtp_block(z, router_bias[len(loads)])
        loads.append(load)
        return x, z, jnp.stack(loads), processed + done

    def __call__(self, tokens, router_bias):
        cfg = self.cfg
        b, t = tokens.shape[0], tokens.shape[1] - 2
        x, z, loads, processed = self.hidden(tokens[:, :-1], router_bias)
        chunk = loss_rows(b * t, self.loss_chunk)
        with jax.named_scope("head_loss"):
            next_sum, hits = chunked_cross_entropy(
                x.reshape(b * t, -1), self.final_norm, self.head,
                tokens[:, 1:-1].reshape(-1), cfg.rms_norm_eps, self.dtype,
                chunk)
            mtp_sum, _ = chunked_cross_entropy(
                z.reshape(b * t, -1), self.mtp_out_norm, self.head,
                tokens[:, 2:].reshape(-1), cfg.rms_norm_eps, self.dtype,
                chunk)
        next_loss, mtp_loss = next_sum / (b * t), mtp_sum / (b * t)
        return {"loss": next_loss + cfg.mtp_lambda * mtp_loss,
                "next_loss": next_loss, "mtp_loss": mtp_loss,
                "correct": hits, "count": jnp.int32(b * t),
                "loads": loads, "processed": processed}
