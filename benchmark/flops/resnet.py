"""Convolution and dense FLOPs of the CIFAR-style ResNet (He et al., 6n+2 layers).

One multiply-add = 2 FLOPs. A ``k x k`` convolution from ``cin`` to ``cout``
channels over ``h x w`` output pixels costs ``2 * h * w * k * k * cin * cout``.
GroupNorm, ReLU, the residual adds and the mean pool are elementwise and not
counted. Backward is twice the forward, less the stem's input gradient, which
nothing needs (the image is data).
"""

from __future__ import annotations

from typing import Sequence


def conv_flops(h_out: int, w_out: int, k: int, cin: int, cout: int) -> int:
    return 2 * h_out * w_out * k * k * cin * cout


def resnet_cifar_forward_flops(stage_sizes: Sequence[int],
                               stage_filters: Sequence[int],
                               image_hw: int, in_channels: int,
                               num_classes: int) -> int:
    hw = image_hw
    total = conv_flops(hw, hw, 3, in_channels, stage_filters[0])
    cin = stage_filters[0]
    for i, (blocks, cout) in enumerate(zip(stage_sizes, stage_filters)):
        for j in range(blocks):
            if i > 0 and j == 0:
                hw //= 2  # stride-2 first block of every later stage
            total += conv_flops(hw, hw, 3, cin, cout)
            total += conv_flops(hw, hw, 3, cout, cout)
            if cin != cout or (i > 0 and j == 0):
                total += conv_flops(hw, hw, 1, cin, cout)  # projection shortcut
            cin = cout
    return total + 2 * cin * num_classes


def resnet_cifar_train_flops(stage_sizes: Sequence[int],
                             stage_filters: Sequence[int], image_hw: int,
                             in_channels: int, num_classes: int) -> int:
    """Forward + backward FLOPs per image."""
    fwd = resnet_cifar_forward_flops(stage_sizes, stage_filters, image_hw,
                                     in_channels, num_classes)
    stem_dgrad = conv_flops(image_hw, image_hw, 3, in_channels,
                            stage_filters[0])
    return 3 * fwd - stem_dgrad
