"""CIFAR-style ResNet (He et al. 2016, section 4.2) with GroupNorm, forward pass.

3x3 stem, three stages of ``n`` basic blocks at 16/32/64 channels, the first
block of stages two and three at stride 2 with a 1x1 projection shortcut,
global mean pool, one dense layer. Departure from the paper and from FedML's
``model/cv/resnet.py``, which use BatchNorm: GroupNorm with at most 32 groups
(the largest power of two that divides the channels), as the repo builds every
model (``fedml_tpu/models/layers.py``: running statistics break the pure
per-client function a vmapped cohort needs). Projection shortcuts are followed
by a GroupNorm, as the repo's block does.

Parameters arrive in the layout ``flax.linen`` gives the repo's module
(``{"params": {"Conv_0": {"kernel"}, "BasicBlock_i": {"Conv_0", "GroupNorm_0",
"Conv_1", "GroupNorm_1"[, "Conv_2", "GroupNorm_2"]}, "Dense_0"}}``): that
layout is the checkpoint format, not code under test. Float32 throughout.
``precision`` is that of the convolutions and the dense layer: ``"highest"``
(exact float32 products) unless the caller has a stated reason to match the
system's (see ``benchmark/jobs/fedavg.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

GN_EPS = 1e-6  # flax.linen.GroupNorm's default


def conv(x, kernel, stride, precision):
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def group_norm(x, p):
    n, h, w, c = x.shape
    groups = 32
    while c % groups:
        groups //= 2
    g = x.reshape(n, h, w, groups, c // groups)
    mean = g.mean(axis=(1, 2, 4), keepdims=True)
    var = ((g - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    g = (g - mean) * jax.lax.rsqrt(var + GN_EPS)
    return g.reshape(n, h, w, c) * p["scale"] + p["bias"]


def forward(variables, x, stage_sizes, precision="highest"):
    """x: [N, H, W, C] float32 -> logits [N, classes]."""
    p = variables["params"]
    x = conv(x, p["Conv_0"]["kernel"], 1, precision)
    block = 0
    for stage, blocks in enumerate(stage_sizes):
        for j in range(blocks):
            b = p[f"BasicBlock_{block}"]
            stride = 2 if (stage > 0 and j == 0) else 1
            y = jax.nn.relu(group_norm(
                conv(x, b["Conv_0"]["kernel"], stride, precision), b["GroupNorm_0"]))
            y = group_norm(conv(y, b["Conv_1"]["kernel"], 1, precision),
                           b["GroupNorm_1"])
            if "Conv_2" in b:
                x = group_norm(conv(x, b["Conv_2"]["kernel"], stride, precision),
                               b["GroupNorm_2"])
            x = jax.nn.relu(y + x)
            block += 1
    x = x.mean(axis=(1, 2))
    return jnp.matmul(x, p["Dense_0"]["kernel"], precision=precision) + p["Dense_0"]["bias"]
