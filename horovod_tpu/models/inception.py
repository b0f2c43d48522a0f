"""Inception V3 (flax), TPU-first.

The reference's other headline scaling model (reference:
docs/benchmarks.rst:13-14 — 90% efficiency at 512 GPUs). Fresh
implementation of the standard Inception-V3 topology (stem + A/B/C/D/E
mixed blocks), NHWC, bf16 compute / f32 params. Canonical input is
(299, 299, 3).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.ops.pallas.conv_bn_act import FusedBatchNormAct


class ConvBN(nn.Module):
    features: int
    kernel: Sequence[int] = (3, 3)
    strides: Sequence[int] = (1, 1)
    padding: Any = "SAME"
    dtype: Any = jnp.bfloat16
    fused: bool = True  # fused BN+ReLU epilogue (same variables/math)

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = nn.Conv(self.features, tuple(self.kernel),
                    strides=tuple(self.strides), padding=self.padding,
                    use_bias=False, dtype=self.dtype,
                    param_dtype=jnp.float32)(x)
        if self.fused:
            return FusedBatchNormAct(momentum=0.9, epsilon=1e-3,
                                     dtype=self.dtype,
                                     name="BatchNorm_0")(
                x, use_running_average=not train)
        x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                         epsilon=1e-3, dtype=self.dtype,
                         param_dtype=jnp.float32)(x)
        return nn.relu(x)


class SpaceToDepthStem(nn.Module):
    """Inception's 3x3/2 VALID stem conv on (299,299,3), reparametrized
    for the MXU like ResNet's (models/resnet.py SpaceToDepthConvInit,
    docs/perf_experiments.md): pad the 299 image one row/col at the END to
    300, 2x2 space-to-depth to (150,150,12), and fold the 3x3 stride-2
    kernel into a 2x2 stride-1 kernel over 12 channels — output is the
    identical 149x149x32 (the folded tap that would read the padded
    row/col carries a zero weight), with 4x the contraction depth per
    MXU pass. The parameter KEEPS the canonical (3,3,3,filters) shape so
    checkpoints interchange with the direct stem."""

    filters: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        w3 = self.param("kernel", nn.initializers.he_normal(),
                        (3, 3, 3, self.filters), jnp.float32)
        # fold: pad to (4,4) at the END, then
        # w2[t,s, 6a+3b+c] = w3[2t+a, 2s+b, c] (u=3 / v=3 taps are zero)
        w4 = jnp.pad(w3, ((0, 1), (0, 1), (0, 0), (0, 0)))
        w2 = w4.reshape(2, 2, 2, 2, 3, self.filters) \
            .transpose(0, 2, 1, 3, 4, 5).reshape(2, 2, 12, self.filters)
        n, h, w, c = x.shape
        if h % 2 or w % 2:  # canonical 299: one zero row/col at the end
            x = jnp.pad(x, ((0, 0), (0, h % 2), (0, w % 2), (0, 0)))
            n, h, w, c = x.shape
        y = x.reshape(n, h // 2, 2, w // 2, 2, c) \
            .transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2, 4 * c)
        return jax.lax.conv_general_dilated(
            y.astype(self.dtype), w2.astype(self.dtype), (1, 1),
            "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _avg_pool_same(x):
    return nn.avg_pool(x, (3, 3), strides=(1, 1), padding="SAME")


class InceptionA(nn.Module):
    pool_features: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        c = partial(ConvBN, dtype=self.dtype)
        b1 = c(64, (1, 1))(x, train)
        b2 = c(64, (5, 5))(c(48, (1, 1))(x, train), train)
        b3 = c(96, (3, 3))(c(96, (3, 3))(c(64, (1, 1))(x, train), train),
                           train)
        b4 = c(self.pool_features, (1, 1))(_avg_pool_same(x), train)
        return jnp.concatenate([b1, b2, b3, b4], axis=-1)


class InceptionB(nn.Module):
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        c = partial(ConvBN, dtype=self.dtype)
        b1 = c(384, (3, 3), strides=(2, 2), padding="VALID")(x, train)
        b2 = c(96, (3, 3), strides=(2, 2), padding="VALID")(
            c(96, (3, 3))(c(64, (1, 1))(x, train), train), train)
        b3 = nn.max_pool(x, (3, 3), strides=(2, 2))
        return jnp.concatenate([b1, b2, b3], axis=-1)


class InceptionC(nn.Module):
    channels_7x7: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        c = partial(ConvBN, dtype=self.dtype)
        c7 = self.channels_7x7
        b1 = c(192, (1, 1))(x, train)
        b2 = c(192, (7, 1))(c(c7, (1, 7))(c(c7, (1, 1))(x, train), train),
                            train)
        b3 = x
        for k, f in (((1, 1), c7), ((7, 1), c7), ((1, 7), c7),
                     ((7, 1), c7), ((1, 7), 192)):
            b3 = c(f, k)(b3, train)
        b4 = c(192, (1, 1))(_avg_pool_same(x), train)
        return jnp.concatenate([b1, b2, b3, b4], axis=-1)


class InceptionD(nn.Module):
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        c = partial(ConvBN, dtype=self.dtype)
        b1 = c(320, (3, 3), strides=(2, 2), padding="VALID")(
            c(192, (1, 1))(x, train), train)
        b2 = c(192, (1, 1))(x, train)
        b2 = c(192, (1, 7))(b2, train)
        b2 = c(192, (7, 1))(b2, train)
        b2 = c(192, (3, 3), strides=(2, 2), padding="VALID")(b2, train)
        b3 = nn.max_pool(x, (3, 3), strides=(2, 2))
        return jnp.concatenate([b1, b2, b3], axis=-1)


class InceptionE(nn.Module):
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        c = partial(ConvBN, dtype=self.dtype)
        b1 = c(320, (1, 1))(x, train)
        b2 = c(384, (1, 1))(x, train)
        b2 = jnp.concatenate([c(384, (1, 3))(b2, train),
                              c(384, (3, 1))(b2, train)], axis=-1)
        b3 = c(384, (3, 3))(c(448, (1, 1))(x, train), train)
        b3 = jnp.concatenate([c(384, (1, 3))(b3, train),
                              c(384, (3, 1))(b3, train)], axis=-1)
        b4 = c(192, (1, 1))(_avg_pool_same(x), train)
        return jnp.concatenate([b1, b2, b3, b4], axis=-1)


class InceptionV3(nn.Module):
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16
    space_to_depth: bool = True

    @nn.compact
    def __call__(self, x, train: bool = True):
        c = partial(ConvBN, dtype=self.dtype)
        x = x.astype(self.dtype)
        # stem
        if self.space_to_depth and x.shape[1] >= 4 and x.shape[3] == 3:
            x = SpaceToDepthStem(32, self.dtype)(x)
            x = FusedBatchNormAct(momentum=0.9, epsilon=1e-3,
                                  dtype=self.dtype)(
                x, use_running_average=not train)
        else:
            x = c(32, (3, 3), strides=(2, 2), padding="VALID")(x, train)
        x = c(32, (3, 3), padding="VALID")(x, train)
        x = c(64, (3, 3))(x, train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        x = c(80, (1, 1), padding="VALID")(x, train)
        x = c(192, (3, 3), padding="VALID")(x, train)
        x = nn.max_pool(x, (3, 3), strides=(2, 2))
        # mixed blocks
        x = InceptionA(32, dtype=self.dtype)(x, train)
        x = InceptionA(64, dtype=self.dtype)(x, train)
        x = InceptionA(64, dtype=self.dtype)(x, train)
        x = InceptionB(dtype=self.dtype)(x, train)
        x = InceptionC(128, dtype=self.dtype)(x, train)
        x = InceptionC(160, dtype=self.dtype)(x, train)
        x = InceptionC(160, dtype=self.dtype)(x, train)
        x = InceptionC(192, dtype=self.dtype)(x, train)
        x = InceptionD(dtype=self.dtype)(x, train)
        x = InceptionE(dtype=self.dtype)(x, train)
        x = InceptionE(dtype=self.dtype)(x, train)
        # head
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=jnp.float32, name="classifier")(x)
        return x.astype(jnp.float32)
