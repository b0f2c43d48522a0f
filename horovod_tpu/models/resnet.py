"""ResNet family (flax), TPU-first.

The reference's acceptance workloads are ResNet-50/101 + Inception/VGG CNNs
driven through its synthetic benchmark harness (reference:
examples/pytorch_synthetic_benchmark.py:37-100,
examples/pytorch_imagenet_resnet50.py, docs/benchmarks.rst:13-43). This is a
fresh TPU-native implementation, not a port of any torch model code:

* NHWC layout (TPU-native; XLA convs tile NHWC onto the MXU directly).
* bfloat16 compute / float32 parameters and batch statistics — the MXU's
  native mixed-precision recipe.
* Static shapes everywhere; no Python control flow in the forward pass.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


class ResNetBlock(nn.Module):
    """Basic 3x3+3x3 residual block (ResNet-18/34)."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)

        if residual.shape != y.shape:
            residual = self.conv(
                self.filters, (1, 1), self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)

        return self.act(residual + y)


class BottleneckBlock(nn.Module):
    """1x1-3x3-1x1 bottleneck block (ResNet-50/101/152)."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        # zero-init the last norm scale so blocks start as identity
        y = self.norm(scale_init=nn.initializers.zeros)(y)

        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4, (1, 1), self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)

        return self.act(residual + y)


class SpaceToDepthConvInit(nn.Module):
    """The 7x7/2 input conv, reparametrized exactly for the MXU: 2x2
    space-to-depth the image to (112,112,12) and fold the 7x7 stride-2
    kernel into a 4x4 stride-1 kernel over 12 channels with asymmetric
    [(2,1),(2,1)] padding — identical output, 4x the contraction depth
    per MXU pass (the classic TPU MLPerf ResNet transform; measured
    1.43x on this layer, docs/perf_experiments.md). The parameter KEEPS the
    canonical (7,7,3,filters) shape — checkpoints interchange freely
    with the direct path — and the fold is a tiny reshape per step."""

    filters: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        w7 = self.param("kernel", nn.initializers.he_normal(),
                        (7, 7, 3, self.filters), jnp.float32)
        # fold: pad to (8,8), then w4[th,tw, 3*(2uh+uw)+c] =
        # w7[2th+uh-1, 2tw+uw-1, c] (zeros where out of range)
        w8 = jnp.pad(w7, ((1, 0), (1, 0), (0, 0), (0, 0)))
        w4 = w8.reshape(4, 2, 4, 2, 3, self.filters) \
            .transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 12, self.filters)
        n, h, w, c = x.shape
        y = x.reshape(n, h // 2, 2, w // 2, 2, c) \
            .transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2, 4 * c)
        return jax.lax.conv_general_dilated(
            y.astype(self.dtype), w4.astype(self.dtype), (1, 1),
            [(2, 1), (2, 1)], dimension_numbers=("NHWC", "HWIO", "NHWC"))


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    act: Callable = nn.relu
    # exact MXU-friendly reparametrization of the input conv (above);
    # disable to get the textbook direct 7x7/2 convolution
    space_to_depth: bool = True

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype,
                       kernel_init=nn.initializers.he_normal())
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                       param_dtype=jnp.float32)

        x = x.astype(self.dtype)
        if self.space_to_depth and x.shape[1] % 2 == 0 \
                and x.shape[2] % 2 == 0 and x.shape[3] == 3:
            x = SpaceToDepthConvInit(self.num_filters, self.dtype,
                                     name="conv_init")(x)
        else:
            x = conv(self.num_filters, (7, 7), (2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = self.act(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))

        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(
                    self.num_filters * 2 ** i,
                    strides=strides, conv=conv, norm=norm, act=self.act,
                )(x)

        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=jnp.float32)(x)
        return x.astype(jnp.float32)


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=ResNetBlock)
ResNet34 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=ResNetBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BottleneckBlock)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3], block_cls=BottleneckBlock)
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3], block_cls=BottleneckBlock)
