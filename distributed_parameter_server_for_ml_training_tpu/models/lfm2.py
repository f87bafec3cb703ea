"""LFM2-8B-A1B: a hybrid decoder LM whose layers mix with a gated short
convolution or with grouped attention, and whose feed-forward half is a
dense SwiGLU in the leading layers and a sigmoid-routed mixture of experts
in every later one.

Source: ``https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json``
(``model_type`` ``lfm2_moe``; 24 layers, hidden 2,048). ``layer_types``
names each layer's mixer: ``conv`` (18 of 24), the gated short convolution
(``[B | C | x] = u W_in``, ``y = C * conv(B * x)``, depthwise and causal
over ``conv_L_cache`` = 3 tokens, no bias, ``y W_out``), or
``full_attention`` (6 of 24: 32 query heads on 8 key/value heads of 64 =
2,048 / 32, an RMSNorm on every q and k head, then RoPE with theta 1e6,
causal softmax). Layers ``< num_dense_layers`` (2) have a SwiGLU of 7,168;
every later one an expert layer (``s = sigmoid(u W_r)`` over 32 experts,
the 4 largest of ``s + expert_bias`` chosen, the bias steering the choice
only, weights ``s`` over their sum, SwiGLU experts of 1,792, no shared
expert). The equations are :class:`Layer`'s docstring; the plain float32
reference the tests and the benchmark hold this file to is
``benchmarks/reference/lfm2_reference.py``.

Training path only, as the other decoders: every position of a packed
sequence counted, no cross-document mask (neither the convolution nor the
attention resets at a document boundary), no auxiliary loss. The gates and
the convolution compute in float32 whatever ``dtype`` is.

**What is held here is what the configuration says**: every head (a conv
mixer is 16.8M parameters and an attention layer 10.5M; the chips that
share a layer run them data parallel), and ``held_experts`` of the
router's ``num_experts``; an expert layer's output is the held experts'
partial sum, which a deployment would add up across the chips that share
the layer (tests/test_lfm2_lm.py adds them up).

The blocks (``GroupedAttention`` with its q/k norm on), the expert layer
(without a shared expert), the skeleton and the loss are
models/decoder.py's; the gates and the convolution are ops/ssm.py's.
"""

from __future__ import annotations

from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.ssm import short_conv
from .decoder import (DecoderConfig, DecoderLM, Dtype, ExpertLayer,
                      GroupedAttention, Linear, RMSNorm, SwiGLU,
                      fan_in_uniform, remat_layer)

#: ``layer_types``' values
CONV, ATTENTION = "conv", "full_attention"


@dataclass(frozen=True)
class Lfm2MoeConfig(DecoderConfig):
    """The published ``config.json`` keys this file reads, under their own
    names, plus what the config lacks (``assumed`` in the benchmark's
    configuration file) and the share held here."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168          # the dense layers' SwiGLU
    moe_intermediate_size: int = 1792      # an expert's
    num_hidden_layers: int = 24
    num_dense_layers: int = 2
    # one entry a layer; a cut in depth reads the first
    # ``num_hidden_layers`` of them
    layer_types: tuple = ((CONV, CONV, ATTENTION) + (CONV, CONV, CONV,
                                                     ATTENTION) * 4
                          + (CONV, CONV, ATTENTION, CONV, CONV))
    conv_L_cache: int = 3
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_experts: int = 32                  # the router's width, as published
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    # -- the share held here: experts first .. first + count - 1
    held_experts: tuple = (0, 32)
    # -- assumed
    bias_update_gamma: float = 0.001
    init_std: float = 0.02
    # -- sequences ``cli train`` packs at the published widths
    # (``max_position_embeddings`` is the context, not a training length)
    train_seq_len: int = 8192

    @property
    def head_dim(self) -> int:
        """The config states none: ``hidden_size / num_attention_heads``,
        the family's convention (64)."""
        return self.hidden_size // self.num_attention_heads

    @property
    def layers(self) -> tuple:
        """The mixers of the layers run here, one entry each."""
        return self.layer_types[:self.num_hidden_layers]

    # -- models/decoder.py:DecoderConfig's names
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    @classmethod
    def from_hf(cls, config: dict, **overrides) -> "Lfm2MoeConfig":
        """``from_published``, refusing what this file does not compute."""
        cfg = cls.from_published(
            config, {"conv_bias": False, "use_expert_bias": True,
                     "tie_word_embeddings": False}, **overrides)
        if len(cfg.layers) < cfg.num_hidden_layers or \
                set(cfg.layers) - {CONV, ATTENTION}:
            raise ValueError(f"layer_types {cfg.layers!r} for "
                             f"{cfg.num_hidden_layers} layers of {CONV}, "
                             f"{ATTENTION}")
        if cfg.num_attention_heads % cfg.num_key_value_heads or \
                cfg.hidden_size % cfg.num_attention_heads:
            raise ValueError("heads that do not divide into their groups "
                             "or the width")
        return cfg


#: ``--model-preset`` names -> configuration. ``tiny`` is for CPU runs and
#: the tests (both mixers, both feed-forward kinds); ``ep4`` is the
#: benchmark's cut of the published model (one of the 4 chips that share
#: each layer: experts 0..7 of 32, a quarter of the vocabulary, every head,
#: the first 5 layers with 1 of the 2 dense ones;
#: benchmarks/configs/lfm2-8b-a1b-ep4.json holds the same numbers and says
#: where each comes from).
PRESETS = {
    "tiny": Lfm2MoeConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, num_experts=16,
        num_experts_per_tok=4, held_experts=(0, 16)),
    "ep4": Lfm2MoeConfig(
        vocab_size=16384, num_hidden_layers=5, num_dense_layers=1,
        num_experts=32, held_experts=(0, 8), bias_update_gamma=0.01,
        expert_capacity_factor=1.5),
}


class ShortConv(nn.Module):
    """``[B | C | x] = u W_in`` (three runs of ``D``), ``y_t = C_t * sum_k
    w[k] (B * x)_{t - (K-1) + k}`` (depthwise, causal, ``K`` =
    ``conv_L_cache``, no bias), ``out = y W_out``. The gates and the
    convolution are ops/ssm.py:short_conv, traced under ``short_conv``."""
    cfg: Lfm2MoeConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, u):
        cfg, dt = self.cfg, self.dtype
        d = cfg.hidden_size
        bcx = Linear(3 * d, cfg, dt, name="in_proj")(u)
        kernel = self.param("conv", fan_in_uniform(cfg.conv_L_cache),
                            (cfg.conv_L_cache, d), jnp.float32)
        with jax.named_scope("short_conv"):
            y = short_conv(bcx, kernel)
        return Linear(d, cfg, dt, name="out_proj")(y)


class Layer(nn.Module):
    """Layer ``l``::

        h   = x + Mixer(RMSNorm(x))     # layer_types[l]: ShortConv or
                                        # GroupedAttention (q/k norm, RoPE)
        out = h + FFN(RMSNorm(h))       # SwiGLU if l < num_dense_layers,
                                        # else models/decoder.py's
                                        # ExpertLayer

    Returns ``(out, loads, processed)``; the last two are None of a dense
    layer. Scopes: the conv mixer whole (norm, projections, residual) under
    ``conv_mixer`` with ``short_conv`` inside it, the attention half under
    ``attn_full``, a dense feed-forward half under ``dense_mlp``, an expert
    layer's norm and sum under ``moe_experts`` beside what
    parallel/moe.py traces there and under ``moe_route``."""
    cfg: Lfm2MoeConfig
    dtype: Dtype
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, x, bias):
        cfg, dt = self.cfg, self.dtype
        mixer_norm = RMSNorm(cfg.norm_eps, name="operator_norm")
        if self.kind == CONV:
            with jax.named_scope("conv_mixer"):
                h = x + ShortConv(cfg, dt, name="conv")(mixer_norm(x))
        else:
            with jax.named_scope("attn_full"):
                h = x + GroupedAttention(
                    cfg, dt, rope=True, window=None, qk_norm=cfg.norm_eps,
                    name="self_attn")(mixer_norm(x))
        ffn_norm = RMSNorm(cfg.norm_eps, name="ffn_norm")
        if self.dense:
            with jax.named_scope("dense_mlp"):
                return h + SwiGLU(cfg.intermediate_size, cfg, dt,
                                  name="feed_forward")(ffn_norm(h)), \
                    None, None
        with jax.named_scope("moe_experts"):
            u = ffn_norm(h)
        y, loads, processed = ExpertLayer(cfg, dt, name="feed_forward")(
            u, bias)
        with jax.named_scope("moe_experts"):
            return h + y, loads, processed


class Lfm2MoeLM(DecoderLM):
    """The decoder: models/decoder.py's skeleton over the layers of
    ``layer_types``, those past ``num_dense_layers`` the expert layers; the
    final norm, then the untied head."""
    cfg: Lfm2MoeConfig

    def setup(self):
        super().setup()
        cfg = self.cfg
        layer = remat_layer(Layer, self.remat)
        self.layers = [layer(cfg, self.dtype, kind=kind,
                             dense=i < cfg.num_dense_layers,
                             name=f"layer_{i}")
                       for i, kind in enumerate(cfg.layers)]
        self.experts = [i >= cfg.num_dense_layers
                        for i in range(len(cfg.layers))]
