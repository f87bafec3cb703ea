"""NVIDIA-Nemotron-3-Super-120B-A12B: a hybrid decoder LM whose every layer
is ONE mixer: a Mamba-2 state-space layer, softmax attention, or a latent
mixture of experts.

Source: ``https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-
BF16/blob/main/config.json`` (``model_type`` ``nemotron_h``; 88 layers,
hidden 4096). ``hybrid_override_pattern`` spells the layers out, one
character each: ``M`` Mamba-2 (128 heads of 64 on a state of 128, 8 groups,
a causal depthwise convolution of width 4, the scan in chunks of 128), ``*``
attention (32 query heads on 2 key/value heads of 128, no positions), ``E``
experts (512 ungated ``relu(u)^2`` experts of width 2,688 *in a latent of
1,024* the layer projects into and out of, 22 a token by sigmoid scores and
a steering bias, one shared expert of 5,376 on the full width). 40 / 40 / 8
of the 88. The equations are the modules' docstrings; the plain float32
reference the tests and the benchmark hold this file to is
``benchmarks/reference/nemotron_h_reference.py`` (the recurrence token by
token).

Training path only, as the other decoders: every position of a packed
sequence counted, no cross-document mask (the scan's state and the
attention both run across a boundary), no auxiliary loss, and the
multi-token-prediction module (``num_nextn_predict_layers``) is not built.
Parameters are float32, compute is ``dtype``; the norms, the router, the
scan's decays and carried state and the loss compute in float32 whatever
``dtype`` is.

**What is held here is what the configuration says.** ``mamba_num_heads``,
``n_groups``, ``num_attention_heads`` and ``num_key_value_heads`` count the
heads this chip holds (a group's heads are self-contained up to
``out_proj``'s sum, as an attention head is up to ``o``'s), and
``held_experts`` the routed experts; the layer's output is then the held
heads' or experts' partial sum, which a deployment would add up across the
chips that share the layer (tests/test_nemotron_h_lm.py adds them up).
The Mamba width is ``mamba_num_heads x mamba_head_dim``, never ``expand x
hidden_size``.

Shared with the other decoders: ``RMSNorm``, ``Linear``, ``Kernel`` and the
chunked cross-entropy (models/joyai.py), ``GroupedAttention``
(models/smallthinker.py), ``held_expert_ffn`` and ``route_top_k``
(parallel/moe.py), the balancing bias as an argument that the task updates.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.ssm import causal_conv1d, ssm_scan
from ..parallel import moe
from .joyai import (Linear, RMSNorm, _chunked_cross_entropy, _normal,
                    rms_norm)
from .smallthinker import GroupedAttention

Dtype = Any

#: ``hybrid_override_pattern``'s characters
MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


@dataclass(frozen=True)
class NemotronHConfig:
    """The published ``config.json`` keys this file reads, under their own
    names, plus what the config lacks (``assumed`` in the benchmark's
    configuration file) and the share held here."""
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    # one character a layer; a cut in depth reads the first
    # ``num_hidden_layers`` of them
    hybrid_override_pattern: str = (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    layer_norm_epsilon: float = 1e-5
    # -- Mamba-2
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # -- attention (no positions: ``rope_theta`` is published and unread)
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    rope_theta: float = 10000.0
    # -- experts
    n_routed_experts: int = 512        # the router's width, as published
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    # -- the share held here: experts first .. first + count - 1
    held_experts: tuple = (0, 512)
    # -- assumed
    bias_update_gamma: float = 0.001
    init_std: float = 0.02
    a_init_range: tuple = (1.0, 16.0)
    # -- sequences ``cli train`` packs at the published widths: the
    # family's pre-training length (``max_position_embeddings`` is the
    # 262,144 of its long-context stage)
    train_seq_len: int = 8192
    # -- the deployment's: rows an expert layer always computes, in units of
    # the even load (parallel/moe.py:pass_plan); 0: what the routing needs
    expert_capacity_factor: float = 0.0

    @property
    def pattern(self) -> str:
        """The layers run here, one character each."""
        return self.hybrid_override_pattern[:self.num_hidden_layers]

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    # -- what train/tasks.py:LMTask asks of every decoder's configuration
    @property
    def expert_layers(self) -> int:
        return self.pattern.count(EXPERTS)

    def pass_plan(self, n_tokens: int):
        """``(rows, min_passes)`` of an expert layer over ``n_tokens``
        tokens (parallel/moe.py:pass_plan)."""
        return moe.pass_plan(
            n_tokens, self.num_experts_per_tok, self.held_experts[1],
            self.n_routed_experts, self.expert_capacity_factor)

    @classmethod
    def from_hf(cls, config: dict, **overrides) -> "NemotronHConfig":
        """From a dict with the published keys (``config.json`` or the
        benchmark's configuration file); refuses what this file does not
        compute."""
        unsupported = {
            "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
            "n_group": 1, "topk_group": 1, "n_shared_experts": 1,
            "attention_bias": False, "mamba_proj_bias": False,
            "mlp_bias": False, "use_bias": False, "use_conv_bias": True,
            "tie_word_embeddings": False, "sliding_window": None,
            "num_nextn_predict_layers": 0}
        for key, want in unsupported.items():
            if key in config and config[key] != want:
                raise ValueError(f"{key}={config[key]!r}: this model "
                                 f"computes {want!r} only")
        fields = {f: config[f] for f in cls.__dataclass_fields__
                  if f in config}
        cfg = replace(cls(**fields), **overrides)
        if set(cfg.pattern) - {MAMBA, ATTENTION, EXPERTS} or \
                len(cfg.pattern) < cfg.num_hidden_layers:
            raise ValueError(f"hybrid_override_pattern {cfg.pattern!r} for "
                             f"{cfg.num_hidden_layers} layers of "
                             f"{MAMBA}, {ATTENTION}, {EXPERTS}")
        if cfg.mamba_num_heads % cfg.n_groups or \
                cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError("heads that do not divide into their groups")
        return cfg


#: ``--model-preset`` names -> configuration. ``tiny`` is for CPU runs and
#: the tests (every kind of layer; a sequence that is no multiple of the
#: chunk); ``tp8_ep64`` is the benchmark's cut of the published model (one
#: of the 64 chips that share each layer: the mixers' heads over 8, the
#: experts 8 a chip, an eighth of the vocabulary, the first 11 layers;
#: benchmarks/configs/nemotron3-super-120b-tp8-ep64.json holds the same
#: numbers and says where each comes from).
PRESETS = {
    "tiny": NemotronHConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=4,
        hybrid_override_pattern="ME*E", mamba_num_heads=4, mamba_head_dim=8,
        n_groups=2, ssm_state_size=16, chunk_size=16,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        n_routed_experts=16, num_experts_per_tok=4, moe_intermediate_size=48,
        moe_latent_size=32, moe_shared_expert_intermediate_size=96,
        held_experts=(0, 16)),
    "tp8_ep64": NemotronHConfig(
        vocab_size=16384, num_hidden_layers=11, mamba_num_heads=16,
        n_groups=1, num_attention_heads=4, num_key_value_heads=1,
        held_experts=(0, 8), bias_update_gamma=0.01,
        expert_capacity_factor=1.5),
}


def _dt_bias_init(cfg: NemotronHConfig):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform on
    ``[time_step_min, time_step_max]``, floored at ``time_step_floor``
    (the family's code)."""
    def init(key, shape, dtype=jnp.float32):
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, dtype, lo,
                                                    hi)),
                         cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus's inverse
    return init


def _a_log_init(cfg: NemotronHConfig):
    def init(key, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(key, shape, dtype,
                                          *cfg.a_init_range))
    return init


def _conv_init(cfg: NemotronHConfig):
    """Uniform on ``+- 1 / sqrt(K)``: the library default of a depthwise
    convolution of width ``K`` (its fan-in)."""
    def init(key, shape, dtype=jnp.float32):
        bound = 1.0 / math.sqrt(cfg.conv_kernel)
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def group_rms_norm(y, gain, groups: int, eps: float):
    """RMSNorm over each of ``groups`` equal runs of the last axis, one
    learned gain a channel; float32 inside."""
    y32 = y.astype(jnp.float32).reshape(y.shape[:-1] + (groups, -1))
    y32 = y32 * jax.lax.rsqrt(jnp.mean(y32 * y32, axis=-1, keepdims=True)
                              + eps)
    return (y32.reshape(y.shape) * gain).astype(y.dtype)


class Mamba2Mixer(nn.Module):
    """``[z | xBC | dt] = u W_in`` (widths ``d_inner``, ``d_inner + 2 G N``,
    ``H``); ``xBC <- silu(conv(xBC) + b)``, depthwise, causal, width 4; ``[x
    | B | C] = xBC``; ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)``
    a head; head ``h`` of group ``g``: ``S_t = exp(dt_t A) S_{t-1} + dt_t
    x_t B_t^T``, ``y_t = S_t C_t + D x_t``; ``y <- GroupRMSNorm(y *
    silu(z))`` over each group's ``d_inner / G`` channels; ``out = y
    W_out``. The scan is ops/ssm.py:ssm_scan, traced under ``ssm_scan``."""
    cfg: NemotronHConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, u):
        cfg, dt_ = self.cfg, self.dtype
        bsz, t, _d = u.shape
        h, p, g, n = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                      cfg.ssm_state_size)
        inner, conv_dim = h * p, h * p + 2 * g * n
        zxbcdt = Linear(inner + conv_dim + h, cfg, dt_, name="in_proj")(u)
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
        kernel = self.param("conv_kernel", _conv_init(cfg),
                            (cfg.conv_kernel, conv_dim), jnp.float32)
        conv_bias = self.param("conv_bias", nn.initializers.zeros,
                               (conv_dim,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (h,),
                             jnp.float32)
        a_log = self.param("A_log", _a_log_init(cfg), (h,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
        gain = self.param("norm", nn.initializers.ones, (inner,),
                          jnp.float32)
        xbc = jax.nn.silu(causal_conv1d(xbc, kernel, conv_bias))
        x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
        x = x.reshape(bsz, t, h, p)
        with jax.named_scope("ssm_scan"):
            y = ssm_scan(x, jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                         -jnp.exp(a_log), b.reshape(bsz, t, g, n),
                         c.reshape(bsz, t, g, n), chunk=cfg.chunk_size)
        y = y + (skip[:, None] * x.astype(jnp.float32)).astype(dt_)
        y = group_rms_norm(y.reshape(bsz, t, inner) * jax.nn.silu(z), gain,
                           g, cfg.layer_norm_epsilon)
        return Linear(cfg.hidden_size, cfg, dt_, name="out_proj")(y)


class Relu2MLP(nn.Module):
    """``W_down relu(W_up u)^2``: the family's ungated unit."""
    width: int
    cfg: NemotronHConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, u):
        up = Linear(self.width, self.cfg, self.dtype, name="up")(u)
        return Linear(u.shape[-1], self.cfg, self.dtype,
                      name="down")(moe.relu2(up))


class LatentExpertLayer(nn.Module):
    """``s = sigmoid(x W_r)`` over all E (float32, true-float32 matmul) of
    the full-width ``x``; the ``k`` largest of ``s + b`` are selected, their
    weights the selected ``s`` over their sum, times
    ``routed_scaling_factor``. ``u = x W_down`` into the latent; ``r = sum
    over the selected e held here of w_e W2_e relu(W1_e u)^2``; ``out = r
    W_up + shared(x)``, the shared expert on the full width. Returns ``(out,
    loads [E] int32, processed)``.

    ``W_up`` is linear, so applying it to the held experts' partial sum
    gives this chip's share of the layer; the shared expert is whole here,
    as on every chip."""
    cfg: NemotronHConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, x, bias):
        cfg, dt = self.cfg, self.dtype
        b, t, d = x.shape
        first, held = cfg.held_experts
        f, e, latent = (cfg.moe_intermediate_size, cfg.n_routed_experts,
                        cfg.moe_latent_size)
        router = self.param("router", _normal(cfg), (d, e), jnp.float32)
        experts = {
            name: self.param(f"experts_{name}", _normal(cfg), shape,
                             jnp.float32)
            for name, shape in (("up", (held, latent, f)),
                                ("down", (held, f, latent)))}
        x = x.reshape(b * t, d).astype(dt)
        with jax.named_scope("moe_route"):
            scores = jax.nn.sigmoid(jnp.dot(
                x.astype(jnp.float32), router,
                precision=jax.lax.Precision.HIGHEST))
            idx, weights = moe.route_top_k(
                scores, bias, cfg.num_experts_per_tok,
                scaling=cfg.routed_scaling_factor,
                normalize=cfg.norm_topk_prob)
            loads = moe.expert_loads(idx, e)
        with jax.named_scope("moe_latent"):
            u = Linear(latent, cfg, dt, name="latent_down")(x)
        rows, min_passes = cfg.pass_plan(b * t)
        routed, processed = moe.held_expert_ffn(
            u, idx, weights, experts, first, rows=rows,
            min_passes=min_passes, activation="relu2")
        with jax.named_scope("moe_latent"):
            routed = Linear(d, cfg, dt, name="latent_up")(routed)
        with jax.named_scope("moe_shared"):
            out = routed + Relu2MLP(cfg.moe_shared_expert_intermediate_size,
                                    cfg, dt, name="shared")(x)
        return out.reshape(b, t, d), loads, processed


#: the scope a layer's instructions are traced under, by its kind; an
#: expert layer's own scopes are inside it, its norm and its residual sum
#: under ``moe_shared``
_LAYER_SCOPE = {MAMBA: "ssm", ATTENTION: "attn_full", EXPERTS: "moe_shared"}


class Layer(nn.Module):
    """Layer ``l`` of kind ``k = hybrid_override_pattern[l]``: ``x + Mixer_k(
    RMSNorm(x))``. Returns ``(x, loads, processed)``; the last two are None
    of a layer without experts."""
    cfg: NemotronHConfig
    dtype: Dtype
    kind: str

    @nn.compact
    def __call__(self, x, bias):
        cfg, dt = self.cfg, self.dtype
        def scope():
            return jax.named_scope(_LAYER_SCOPE[self.kind])

        norm = RMSNorm(cfg.layer_norm_epsilon, name="norm")
        if self.kind == EXPERTS:
            with scope():
                u = norm(x)
            m, loads, processed = LatentExpertLayer(cfg, dt, name="mixer")(
                u, bias)
            with scope():
                return x + m, loads, processed
        with scope():
            mixer = (Mamba2Mixer(cfg, dt, name="mixer")
                     if self.kind == MAMBA else
                     GroupedAttention(cfg, dt, rope=False, window=None,
                                      name="mixer"))
            return x + mixer(norm(x)), None, None


class NemotronHLM(nn.Module):
    """The decoder. ``__call__(tokens [B, T+2], router_bias [expert layers,
    E])`` returns the training quantities of a packed batch: position ``i <
    T`` of a row reads tokens ``0..i`` and predicts token ``i+1``. Rows are
    ``T+2`` long because that is what train/tasks.py:LMTask hands every
    decoder (the first has a second head that predicts token ``i+2``); the
    last token is not read."""
    cfg: NemotronHConfig
    dtype: Dtype = jnp.float32
    # recompute a layer at a time in backward, but for its routing
    # decisions (parallel/moe.py:KEEP_ROUTING)
    remat: bool = True
    loss_chunk: int = 4096      # rows of logits live at once

    def setup(self):
        cfg, dt = self.cfg, self.dtype
        layer = (nn.remat(Layer, policy=moe.KEEP_ROUTING) if self.remat
                 else Layer)
        self.embed = self.param("embed", _normal(cfg),
                                (cfg.vocab_size, cfg.hidden_size),
                                jnp.float32)
        self.layers = [layer(cfg, dt, kind=kind, name=f"layer_{i}")
                       for i, kind in enumerate(cfg.pattern)]
        self.final_norm = self.param("final_norm", nn.initializers.ones,
                                     (cfg.hidden_size,), jnp.float32)
        self.head = self.param("head", _normal(cfg),
                               (cfg.hidden_size, cfg.vocab_size),
                               jnp.float32)

    def hidden(self, tokens, router_bias):
        """``tokens`` ``[B, T]`` -> the last layer's output ``[B, T, D]``,
        the loads ``[expert layers, E]`` and the assignments the held
        experts computed."""
        with jax.named_scope("embed"):
            x = self.embed.astype(self.dtype)[tokens]
        loads, processed = [], jnp.int32(0)
        for layer, kind in zip(self.layers, self.cfg.pattern):
            bias = router_bias[len(loads)] if kind == EXPERTS else None
            x, load, done = layer(x, bias)
            if kind == EXPERTS:
                loads.append(load)
                processed += done
        return x, jnp.stack(loads), processed

    def __call__(self, tokens, router_bias):
        cfg = self.cfg
        b, t = tokens.shape[0], tokens.shape[1] - 2
        x, loads, processed = self.hidden(tokens[:, :-2], router_bias)
        # the most rows, up to ``loss_chunk``, that divide the batch
        chunk = max(c for c in range(1, min(self.loss_chunk, b * t) + 1)
                    if (b * t) % c == 0)
        with jax.named_scope("head_loss"):
            next_sum, hits = _chunked_cross_entropy(
                x.reshape(b * t, -1), self.final_norm, self.head,
                tokens[:, 1:-1].reshape(-1), cfg.layer_norm_epsilon,
                self.dtype, chunk)
        loss = next_sum / (b * t)
        return {"loss": loss, "next_loss": loss, "correct": hits,
                "count": jnp.int32(b * t), "loads": loads,
                "processed": processed}

    def logits_at(self, tokens, router_bias, positions):
        """Float32 logits at ``positions`` ``[P]`` of every row, as the
        one-element tuple ``(main [B, P, V],)``."""
        x, _loads, _n = self.hidden(tokens[:, :-2], router_bias)
        w = self.head.astype(self.dtype)
        return ((rms_norm(x[:, positions], self.final_norm,
                          self.cfg.layer_norm_epsilon).astype(self.dtype)
                 @ w).astype(jnp.float32),)
