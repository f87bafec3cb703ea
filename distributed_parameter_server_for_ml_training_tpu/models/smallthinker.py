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
cross-document mask, no auxiliary balance loss. Parameters are float32,
compute is ``dtype`` (bf16 in the benchmark's cell); RMSNorm, RoPE, the
router and the loss compute in float32 whatever ``dtype`` is. The pieces
that the two decoders share are models/joyai.py's (``RMSNorm``, ``Linear``,
``Kernel``, ``rope_half_split``, the chunked cross-entropy), the expert
layer is parallel/moe.py's ``held_expert_ffn``, **told which experts it
holds** (``held_experts``), and the attention core is
``ops.attention.heads_attention_core``, told the window and handed 4
key/value heads beside 28 query heads.

The model has no balancing bias. ``train/tasks.py:LMTask`` hands every
decoder a ``router_bias`` and updates it by ``bias_update_gamma``; this one
states gamma 0, so the array stays what it was, and the model does not read
it.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import heads_attention_core
from ..parallel import moe
from .joyai import (Kernel, Linear, RMSNorm, _chunked_cross_entropy,
                    _normal, rms_norm, rope_half_split)

Dtype = Any


@dataclass(frozen=True)
class SmallThinkerConfig:
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
    # -- the deployment's: rows an expert layer always computes, in units of
    # the even load (parallel/moe.py:pass_plan); 0: what the routing needs
    expert_capacity_factor: float = 0.0

    # -- what train/tasks.py:LMTask asks of every decoder's configuration
    @property
    def n_routed_experts(self) -> int:
        return self.moe_num_primary_experts

    def pass_plan(self, n_tokens: int):
        """``(rows, min_passes)`` of an expert layer over ``n_tokens``
        tokens (parallel/moe.py:pass_plan)."""
        return moe.pass_plan(
            n_tokens, self.moe_num_active_primary_experts, self.held_experts[1],
            self.moe_num_primary_experts, self.expert_capacity_factor)

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
        """From a dict with the published keys (``config.json`` or the
        benchmark's configuration file); refuses what this file does not
        compute."""
        unsupported = {"moe_primary_router_apply_softmax": True,
                       "norm_topk_prob": True, "rope_scaling": None,
                       "tie_word_embeddings": False}
        for key, want in unsupported.items():
            if key in config and config[key] != want:
                raise ValueError(f"{key}={config[key]!r}: this model "
                                 f"computes {want!r} only")
        fields = {f: tuple(config[f]) if isinstance(config[f], list)
                  else config[f]
                  for f in cls.__dataclass_fields__ if f in config}
        cfg = replace(cls(**fields), **overrides)
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
    ``rope_theta`` and ``init_std`` (models/nemotron_h.py's attention layer
    is this module with ``rope=False`` and no window)."""
    cfg: Any
    dtype: Dtype
    rope: bool
    window: int | None

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
        if self.rope:
            q = rope_half_split(q, cfg.rope_theta)
            k = rope_half_split(k, cfg.rope_theta)
        o = heads_attention_core(q, k, v, causal=True, window=self.window)
        return Linear(cfg.hidden_size, cfg, dt, name="o")(o)


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
        first, held = cfg.held_experts
        f, e = cfg.moe_ffn_hidden_size, cfg.moe_num_primary_experts
        k = cfg.moe_num_active_primary_experts
        router = self.param("router", _normal(cfg), (d, e), jnp.float32)
        experts = {
            name: self.param(f"experts_{name}", _normal(cfg), shape,
                             jnp.float32)
            for name, shape in (("gate", (held, d, f)), ("up", (held, d, f)),
                                ("down", (held, f, d)))}
        with jax.named_scope("moe_route"):
            logits = jnp.dot(x.reshape(b * t, d).astype(jnp.float32), router,
                             precision=jax.lax.Precision.HIGHEST)
            idx, weights = moe.route_top_k_softmax(logits, k)
            loads = moe.expert_loads(idx, e)
        rows, min_passes = cfg.pass_plan(b * t)
        routed, processed = moe.held_expert_ffn(
            u.reshape(b * t, d).astype(dt), idx, weights, experts, first,
            rows=rows, min_passes=min_passes, activation="relu",
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
    traced under the scope ``attn_window`` or ``attn_full``."""
    cfg: SmallThinkerConfig
    dtype: Dtype
    layer: int

    @nn.compact
    def __call__(self, x):
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


class SmallThinkerLM(nn.Module):
    """The decoder. ``__call__(tokens [B, T+2], router_bias)`` returns the
    training quantities of a packed batch: position ``i < T`` of a row
    reads tokens ``0..i`` and predicts token ``i+1``. Rows are ``T+2`` long
    and ``router_bias`` is an argument because that is what
    train/tasks.py:LMTask hands every decoder (the other has a second head
    that predicts token ``i+2``, and a bias); the last token and the bias
    are not read."""
    cfg: SmallThinkerConfig
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
        self.layers = [block(cfg, dt, layer=i, name=f"layer_{i}")
                       for i in range(cfg.num_hidden_layers)]
        self.final_norm = self.param("final_norm", nn.initializers.ones,
                                     (cfg.hidden_size,), jnp.float32)
        self.head = self.param("head", _normal(cfg),
                               (cfg.hidden_size, cfg.vocab_size),
                               jnp.float32)

    def hidden(self, tokens):
        """``tokens`` ``[B, T]`` -> the last block's output ``[B, T, D]``,
        the loads ``[layers, E]`` and the assignments the held experts
        computed."""
        with jax.named_scope("embed"):
            x = self.embed.astype(self.dtype)[tokens]
        loads, processed = [], jnp.int32(0)
        for block in self.layers:
            x, load, done = block(x)
            loads.append(load)
            processed += done
        return x, jnp.stack(loads), processed

    def __call__(self, tokens, router_bias=None):
        cfg = self.cfg
        b, t = tokens.shape[0], tokens.shape[1] - 2
        x, loads, processed = self.hidden(tokens[:, :-2])
        # the most rows, up to ``loss_chunk``, that divide the batch
        chunk = max(c for c in range(1, min(self.loss_chunk, b * t) + 1)
                    if (b * t) % c == 0)
        with jax.named_scope("head_loss"):
            next_sum, hits = _chunked_cross_entropy(
                x.reshape(b * t, -1), self.final_norm, self.head,
                tokens[:, 1:-1].reshape(-1), cfg.rms_norm_eps, self.dtype,
                chunk)
        loss = next_sum / (b * t)
        return {"loss": loss, "next_loss": loss, "correct": hits,
                "count": jnp.int32(b * t), "loads": loads,
                "processed": processed}

    def logits_at(self, tokens, router_bias, positions):
        """Float32 logits at ``positions`` ``[P]`` of every row, as the
        one-element tuple ``(main [B, P, V],)`` (the other decoder's holds
        its second head's too)."""
        x, _loads, _n = self.hidden(tokens[:, :-2])
        w = self.head.astype(self.dtype)
        return ((rms_norm(x[:, positions], self.final_norm,
                          self.cfg.rms_norm_eps).astype(self.dtype)
                 @ w).astype(jnp.float32),)
