"""ResNets: ResNet-20 (CIFAR, config #2) and ResNet-50 (ImageNet, config #3 —
the headline benchmark model, BASELINE.json metric "ResNet-50/ImageNet
images/sec/chip").

TPU-first choices:
- compute in bfloat16 (MXU native), params and batch-norm stats in float32;
- NHWC layout (XLA TPU's preferred conv layout);
- no data-dependent control flow — the whole net is one traced graph.

Architecture follows the standard He et al. residual recipes (v1.5 bottleneck
for ResNet-50: stride on the 3x3, as in the common benchmark variant).
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp


class ResidualBlock(nn.Module):
    """Basic 3x3+3x3 block (CIFAR ResNet-20)."""

    filters: int
    strides: int = 1
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=self.dtype, param_dtype=jnp.float32,
        )
        residual = x
        y = conv(self.filters, (3, 3), strides=(self.strides, self.strides),
                 padding="SAME")(x)
        y = nn.relu(norm()(y))
        y = conv(self.filters, (3, 3), padding="SAME")(y)
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters, (1, 1),
                            strides=(self.strides, self.strides))(residual)
            residual = norm()(residual)
        return nn.relu(y + residual)


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck (ResNet-50), v1.5: stride on the 3x3."""

    filters: int
    strides: int = 1
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=self.dtype, param_dtype=jnp.float32,
        )
        residual = x
        y = nn.relu(norm()(conv(self.filters, (1, 1))(x)))
        y = conv(self.filters, (3, 3), strides=(self.strides, self.strides),
                 padding="SAME")(y)
        y = nn.relu(norm()(y))
        y = conv(self.filters * 4, (1, 1))(y)
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters * 4, (1, 1),
                            strides=(self.strides, self.strides))(residual)
            residual = norm()(residual)
        return nn.relu(y + residual)


class CifarResNet(nn.Module):
    """ResNet-6n+2 for CIFAR (n=3 -> ResNet-20)."""

    num_classes: int = 10
    n: int = 3
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = x.astype(self.dtype)
        x = nn.Conv(16, (3, 3), padding="SAME", use_bias=False, dtype=self.dtype)(x)
        x = nn.relu(nn.BatchNorm(
            use_running_average=not train, momentum=0.9, epsilon=1e-5,
            dtype=self.dtype, param_dtype=jnp.float32)(x))
        for stage, filters in enumerate((16, 32, 64)):
            for block in range(self.n):
                strides = 2 if stage > 0 and block == 0 else 1
                x = ResidualBlock(filters, strides, self.dtype)(x, train)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes, dtype=jnp.float32)(x)


class ImageNetResNet(nn.Module):
    """Bottleneck ResNet for ImageNet; stage_sizes (3,4,6,3) -> ResNet-50.

    ``space_to_depth`` re-expresses the stem conv the MLPerf-TPU way: the
    C=3 minor dim of the 224x224x3 input defeats the TPU's (8,128) register
    tiling.  Packing 2x2 spatial blocks into channels
    gives a 112x112x12 input, and the 7x7/s2 stem is equivalent to a
    4x4/s1 conv on it: output(i,j) = sum_{di,dj} W[di,dj] x[2i+di-3,
    2j+dj-3]; writing di-3 = 2p+a (a in {0,1}) maps every tap onto kernel
    position p in {-2..1} and packed channel (a,b,c) — a 4x4 kernel with
    asymmetric padding (2,1).  The 4x4x12x64 parameterization is a strict
    superset of the 7x7x3x64 stem (per axis, 1 of the 8 (p,a) pairs maps
    to tap di=-1 outside the 7-tap support — 15 of the 64 2-D combinations
    — and trains as free zeros), so the model class is unchanged up to
    that enlargement — the standard MLPerf treatment.  Equivalence is
    pinned by tests/test_models.py::test_space_to_depth_stem_equivalence.
    """

    num_classes: int = 1000
    stage_sizes: Sequence[int] = (3, 4, 6, 3)
    dtype: jnp.dtype = jnp.bfloat16
    space_to_depth: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):  # x: (B, 224, 224, 3)
        x = x.astype(self.dtype)
        if self.space_to_depth:
            b, h, w, c = x.shape
            x = x.reshape(b, h // 2, 2, w // 2, 2, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)
            x = nn.Conv(64, (4, 4), padding=[(2, 1), (2, 1)],
                        use_bias=False, dtype=self.dtype)(x)
        else:
            x = nn.Conv(64, (7, 7), strides=(2, 2), padding=[(3, 3), (3, 3)],
                        use_bias=False, dtype=self.dtype)(x)
        x = nn.relu(nn.BatchNorm(
            use_running_average=not train, momentum=0.9, epsilon=1e-5,
            dtype=self.dtype, param_dtype=jnp.float32)(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for stage, size in enumerate(self.stage_sizes):
            filters = 64 * 2**stage
            for block in range(size):
                strides = 2 if stage > 0 and block == 0 else 1
                x = BottleneckBlock(filters, strides, self.dtype)(x, train)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes, dtype=jnp.float32)(x)


def ResNet20(**kw) -> CifarResNet:
    return CifarResNet(n=3, **kw)


def ResNet50(**kw) -> ImageNetResNet:
    return ImageNetResNet(stage_sizes=(3, 4, 6, 3), **kw)
