"""ResNet family — the throughput workhorse (BASELINE configs #2 and #3).

Reference: examples/imagenet/train_imagenet.py trains ResNet-50 under
data-parallel allreduce_grad (SURVEY.md §3.1); the CIFAR config exercises
MultiNodeBatchNormalization. This is a fresh flax implementation, TPU-first:
NHWC layout (the TPU-native conv layout), bfloat16 compute with fp32 params
and batch statistics, and an optional communicator that turns every BN into
a cross-replica MultiNodeBatchNormalization.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

from chainermn_tpu.links import MultiNodeBatchNormalization

ModuleDef = Any


class ResNetBlock(nn.Module):
    """Basic two-conv block (ResNet-18/34 and CIFAR ResNets)."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class BottleneckResNetBlock(nn.Module):
    """1-3-1 bottleneck block (ResNet-50/101/152)."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    """Configurable ResNet.

    ``comm`` switches every norm layer to cross-replica statistics
    (MultiNodeBatchNormalization) — the reference's CIFAR config. ``dtype``
    bfloat16 keeps the MXU fed; params and BN stats stay fp32.
    """

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int
    num_filters: int = 64
    comm: Any = None
    dtype: Any = jnp.float32
    small_inputs: bool = False   # CIFAR stem: 3x3 conv, no maxpool

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype)
        # both branches pin identical momentum/epsilon so toggling
        # cross-replica statistics is the ONLY difference between them
        if self.comm is not None:
            norm = functools.partial(
                MultiNodeBatchNormalization,
                comm=self.comm, use_running_average=not train,
                decay=0.9, eps=1e-5, dtype=self.dtype,
            )
        else:
            norm = functools.partial(
                nn.BatchNorm, use_running_average=not train,
                momentum=0.9, epsilon=1e-5, dtype=self.dtype,
            )

        x = x.astype(self.dtype)
        if self.small_inputs:
            x = conv(self.num_filters, (3, 3), name="conv_init")(x)
        else:
            x = conv(self.num_filters, (7, 7), (2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        if not self.small_inputs:
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_size in enumerate(self.stage_sizes):
            for j in range(block_size):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(
                    self.num_filters * 2 ** i,
                    strides=strides, conv=conv, norm=norm,
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x.astype(jnp.float32)


ResNet18 = functools.partial(ResNet, stage_sizes=[2, 2, 2, 2],
                             block_cls=ResNetBlock)
ResNet34 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3],
                             block_cls=ResNetBlock)
ResNet50 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3],
                             block_cls=BottleneckResNetBlock)
ResNet101 = functools.partial(ResNet, stage_sizes=[3, 4, 23, 3],
                              block_cls=BottleneckResNetBlock)
ResNet152 = functools.partial(ResNet, stage_sizes=[3, 8, 36, 3],
                              block_cls=BottleneckResNetBlock)


def CifarResNet(num_classes: int = 100, depth: int = 20, comm=None,
                dtype=jnp.float32):
    """CIFAR-style ResNet (6n+2 layers, 3 stages) with optional
    cross-replica BN — BASELINE config #3's model."""
    assert (depth - 2) % 6 == 0, "depth must be 6n+2"
    n = (depth - 2) // 6
    return ResNet(
        stage_sizes=[n, n, n], block_cls=ResNetBlock,
        num_classes=num_classes, num_filters=16, comm=comm,
        dtype=dtype, small_inputs=True,
    )
