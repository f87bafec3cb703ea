"""Operations one ResNet training image needs, from the layer shapes.

Counts the multiply-accumulates of every convolution and of the classifier
in the forward pass; a training image is forward + backward = 3 forward
passes of matmul work, 2 operations a multiply-accumulate. Batch norm,
activations, pooling, augmentation, evaluation and the optimizer are not
counted (they are not matmul work and the peak is a matmul peak).
"""

from __future__ import annotations


def _conv(out_hw: int, kernel: int, c_in: int, c_out: int) -> int:
    return out_hw * out_hw * kernel * kernel * c_in * c_out


def forward_macs(arch: dict) -> int:
    """``arch``: image_size, stem ('cifar3x3' | 'imagenet7x7'), block
    ('basic' | 'bottleneck'), stage_sizes, stage_widths, num_classes."""
    hw = int(arch["image_size"])
    widths = [int(w) for w in arch["stage_widths"]]
    if arch["stem"] == "cifar3x3":
        macs = _conv(hw, 3, 3, widths[0])
    elif arch["stem"] == "imagenet7x7":
        hw //= 2
        macs = _conv(hw, 7, 3, widths[0])
        hw //= 2  # 3x3/2 max pool
    else:
        raise ValueError(f"unknown stem {arch['stem']!r}")
    expansion = {"basic": 1, "bottleneck": 4}[arch["block"]]
    c_in = widths[0]
    for stage, (blocks, width) in enumerate(zip(arch["stage_sizes"], widths)):
        for block in range(int(blocks)):
            stride = 2 if stage > 0 and block == 0 else 1
            out_hw = hw // stride
            c_out = width * expansion
            if arch["block"] == "basic":
                macs += _conv(out_hw, 3, c_in, width)
                macs += _conv(out_hw, 3, width, width)
            else:
                macs += _conv(hw, 1, c_in, width)
                macs += _conv(out_hw, 3, width, width)
                macs += _conv(out_hw, 1, width, c_out)
            if c_in != c_out or stride != 1:
                macs += _conv(out_hw, 1, c_in, c_out)  # projection shortcut
            c_in, hw = c_out, out_hw
    return macs + c_in * int(arch["num_classes"])


def train_flops_per_image(arch: dict) -> float:
    return 2.0 * 3.0 * forward_macs(arch)


def parameter_count(arch: dict) -> int:
    """Trainable elements: bias-free convolutions, a scale and a bias for
    the batch norm after each, and the classifier with its bias."""
    widths = [int(w) for w in arch["stage_widths"]]
    kernel = {"cifar3x3": 3, "imagenet7x7": 7}[arch["stem"]]
    n = kernel * kernel * 3 * widths[0] + 2 * widths[0]
    expansion = {"basic": 1, "bottleneck": 4}[arch["block"]]
    c_in = widths[0]
    for stage, (blocks, width) in enumerate(zip(arch["stage_sizes"], widths)):
        for block in range(int(blocks)):
            stride = 2 if stage > 0 and block == 0 else 1
            c_out = width * expansion
            if arch["block"] == "basic":
                n += 9 * c_in * width + 9 * width * width + 4 * width
            else:
                n += (c_in * width + 9 * width * width + width * c_out
                      + 4 * width + 2 * c_out)
            if c_in != c_out or stride != 1:
                n += c_in * c_out + 2 * c_out
            c_in = c_out
    return n + c_in * int(arch["num_classes"]) + int(arch["num_classes"])
