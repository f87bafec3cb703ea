"""SmallThinker-21BA3B-Instruct: a decoder LM with window and full attention
mixed, grouped queries, and a router that reads the layer's input.

Source: ``https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/
blob/main/config.json`` (52 layers, hidden 2560, 28 query heads on 4
key/value heads of 128, 64 experts of width 768 with 6 a token, no shared
expert). What the config makes a trainer handle: three layers of every four
attend within a sliding window of 4,096 and carry RoPE, the fourth is global
and has no positions at all (``sliding_window_layout``, ``rope_layout``);
the router takes the 6 largest of 64 logits *of the block's input*, before
the attention norm, and a softmax over those 6; the experts gate with ReLU.
The equations are :class:`Block`'s docstring; the plain float32 reference
the tests and the benchmark hold this file to is
``benchmarks/reference/smallthinker_reference.py``.

Training path only: every position of a packed sequence counted, no
cross-document mask, no auxiliary balance loss. The blocks, the skeleton
and the loss are models/decoder.py's (its ``GroupedAttention`` told the
window); the experts are parallel/moe.py's ``held_expert_ffn``.

The model has no balancing bias. ``train/tasks.py:LMTask`` hands every
decoder a ``router_bias`` and updates it by ``bias_update_gamma``; this one
states gamma 0, so the array stays what it was, and the model does not read
it.
"""

from __future__ import annotations

from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..parallel import moe
from .decoder import (DecoderConfig, DecoderLM, Dtype, GroupedAttention,
                      RMSNorm, expert_params, normal_init, remat_layer)


@dataclass(frozen=True)
class SmallThinkerConfig(DecoderConfig):
    """The published ``config.json`` keys this file reads, under their own
    names, plus what the config lacks (``assumed`` in the benchmark's
    configuration file) and the share held here."""
    vocab_size: int = 151936
    hidden_size: int = 2560
    moe_ffn_hidden_size: int = 768
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_num_primary_experts: int = 64     # the router's width, as published
    moe_num_active_primary_experts: int = 6
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    sliding_window_size: int = 4096
    max_position_embeddings: int = 16384
    # one entry a layer; the published 52 repeat (0, 1, 1, 1), and a cut in
    # depth reads the first ``num_hidden_layers`` of them
    rope_layout: tuple = (0, 1, 1, 1) * 13
    sliding_window_layout: tuple = (0, 1, 1, 1) * 13
    # -- the share held here: experts first .. first + count - 1
    held_experts: tuple = (0, 64)
    # -- assumed
    init_std: float = 0.02

    # -- models/decoder.py:DecoderConfig's names
    @property
    def n_routed_experts(self) -> int:
        return self.moe_num_primary_experts

    @property
    def num_experts_per_tok(self) -> int:
        return self.moe_num_active_primary_experts

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def bias_update_gamma(self) -> float:
        """No balancing bias: the update is the identity."""
        return 0.0

    def window(self, layer: int) -> int | None:
        """The layer's sliding window, ``None`` on a global layer."""
        return (self.sliding_window_size
                if self.sliding_window_layout[layer] else None)

    @classmethod
    def from_hf(cls, config: dict, **overrides) -> "SmallThinkerConfig":
        """``from_published``, refusing what this file does not compute."""
        cfg = cls.from_published(
            config, {"moe_primary_router_apply_softmax": True,
                     "norm_topk_prob": True, "rope_scaling": None,
                     "tie_word_embeddings": False}, **overrides)
        for layout in (cfg.rope_layout, cfg.sliding_window_layout):
            if len(layout) < cfg.num_hidden_layers:
                raise ValueError(f"a layout of {len(layout)} entries for "
                                 f"{cfg.num_hidden_layers} layers")
        return cfg


#: ``--model-preset`` names -> configuration. ``tiny`` is for CPU runs and
#: the tests; ``ep4`` is the benchmark's cut of the published model (one of 4
#: chips sharing each layer: one period of 4 layers, experts 0..15 of 64, a
#: quarter of the vocabulary, and as its stated capacity the expert-parallel
#: degree: all 98,304 rows a layer that the deployment's even load would be;
#: benchmarks/configs/smallthinker-21b-a3b-ep4.json holds the same numbers
#: and says where each comes from).
PRESETS = {
    "tiny": SmallThinkerConfig(
        vocab_size=512, hidden_size=64, moe_ffn_hidden_size=32,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, moe_num_primary_experts=8,
        moe_num_active_primary_experts=2, sliding_window_size=8,
        max_position_embeddings=64,
        rope_layout=(0, 1, 1, 1), sliding_window_layout=(0, 1, 1, 1),
        held_experts=(0, 8)),
    "ep4": SmallThinkerConfig(vocab_size=37984, num_hidden_layers=4,
                              held_experts=(0, 16),
                              expert_capacity_factor=4.0),
}


class ExpertLayer(nn.Module):
    """``sum over the selected e held here of w_e * W_down,e(relu(W_gate,e
    u) * (W_up,e u))``: ``r = x W_r`` over all E (float32, true-float32
    matmul) from the block's INPUT ``x``, the ``k`` largest logits are
    selected, their weights a softmax over those ``k``. Returns ``(m, loads
    [E] int32, processed)``."""
    cfg: SmallThinkerConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, x, u):
        cfg, dt = self.cfg, self.dtype
        b, t, d = u.shape
        router = self.param("router", normal_init(cfg),
                            (d, cfg.n_routed_experts), jnp.float32)
        experts = expert_params(self, cfg, d, cfg.moe_ffn_hidden_size)
        with jax.named_scope("moe_route"):
            logits = jnp.dot(x.reshape(b * t, d).astype(jnp.float32), router,
                             precision=jax.lax.Precision.HIGHEST)
            idx, weights = moe.route_top_k_softmax(
                logits, cfg.num_experts_per_tok)
            loads = moe.expert_loads(idx, cfg.n_routed_experts)
        rows, min_passes = cfg.pass_plan(b * t)
        routed, processed = moe.held_expert_ffn(
            u.reshape(b * t, d).astype(dt), idx, weights, experts,
            cfg.held_experts[0], rows=rows, min_passes=min_passes,
            activation="relu",
            # 6 x 16,384 rows a layer: the scatter-add's read-modify-write
            # is the layer's largest cost there and follows the routing
            combine="gather")
        return routed.reshape(b, t, d), loads, processed


class Block(nn.Module):
    """Block ``l``, input ``x``::

        r, top, w = the router on x (ExpertLayer)      # before any norm
        y   = x + Attention(RMSNorm(x))                # GroupedAttention
        out = y + Experts(RMSNorm(y); top, w)

    with RoPE where ``rope_layout[l]`` is 1 and the window where
    ``sliding_window_layout[l]`` is 1. The attention's instructions are
    traced under the scope ``attn_window`` or ``attn_full``. ``bias`` is
    the row of ``router_bias`` every expert layer is handed, and not
    read."""
    cfg: SmallThinkerConfig
    dtype: Dtype
    layer: int

    @nn.compact
    def __call__(self, x, bias=None):
        cfg, dt = self.cfg, self.dtype
        window = cfg.window(self.layer)
        with jax.named_scope("attn_full" if window is None
                             else "attn_window"):
            y = x + GroupedAttention(
                cfg, dt, rope=bool(cfg.rope_layout[self.layer]),
                window=window, name="attn")(
                    RMSNorm(cfg.rms_norm_eps, name="attn_norm")(x))
        u = RMSNorm(cfg.rms_norm_eps, name="ffn_norm")(y)
        m, loads, processed = ExpertLayer(cfg, dt, name="moe")(x, u)
        return y + m, loads, processed


class SmallThinkerLM(DecoderLM):
    """The decoder: models/decoder.py's skeleton over the blocks, every one
    an expert layer."""
    cfg: SmallThinkerConfig

    def setup(self):
        super().setup()
        cfg = self.cfg
        block = remat_layer(Block, self.remat)
        self.layers = [block(cfg, self.dtype, layer=i, name=f"layer_{i}")
                       for i in range(cfg.num_hidden_layers)]
        self.experts = [True] * cfg.num_hidden_layers
