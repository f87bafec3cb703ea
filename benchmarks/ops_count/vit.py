"""Operations one ViT training image needs, from the layer shapes.

Multiply-accumulates of the patch embedding, of every encoder layer (fused
qkv projection, the two attention matmuls over all tokens, the output
projection, the two MLP matmuls) and of the classifier; a training image is
3 forward passes of matmul work, 2 operations a multiply-accumulate. Layer
norm, softmax, GELU, augmentation, evaluation and the optimizer are not
counted.
"""

from __future__ import annotations


def forward_macs(arch: dict) -> int:
    """``arch``: image_size, patch_size, hidden_size, mlp_dim, num_heads,
    head_dim, num_layers, num_classes, class_token (bool)."""
    d = int(arch["hidden_size"])
    patch = int(arch["patch_size"])
    patches = (int(arch["image_size"]) // patch) ** 2
    tokens = patches + (1 if arch.get("class_token", True) else 0)
    attn_width = int(arch["num_heads"]) * int(arch["head_dim"])
    layer = (tokens * d * 3 * attn_width          # q, k, v projections
             + 2 * tokens * tokens * attn_width   # QK^T and AV, all heads
             + tokens * attn_width * d            # output projection
             + 2 * tokens * d * int(arch["mlp_dim"]))
    return (patches * patch * patch * 3 * d
            + int(arch["num_layers"]) * layer
            + d * int(arch["num_classes"]))


def train_flops_per_image(arch: dict) -> float:
    return 2.0 * 3.0 * forward_macs(arch)


def parameter_count(arch: dict) -> int:
    """Trainable elements: patch embedding, class token, position
    embedding, the encoder layers (two layer norms, qkv, output projection,
    two MLP matrices, all with biases), the final layer norm, the head."""
    d = int(arch["hidden_size"])
    patch = int(arch["patch_size"])
    patches = (int(arch["image_size"]) // patch) ** 2
    cls = 1 if arch.get("class_token", True) else 0
    attn_width = int(arch["num_heads"]) * int(arch["head_dim"])
    mlp = int(arch["mlp_dim"])
    layer = (4 * d + d * 3 * attn_width + 3 * attn_width
             + attn_width * d + d + d * mlp + mlp + mlp * d + d)
    return (patch * patch * 3 * d + d + cls * d + (patches + cls) * d
            + int(arch["num_layers"]) * layer + 2 * d
            + d * int(arch["num_classes"]) + int(arch["num_classes"]))
