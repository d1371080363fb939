"""ResNet in flax (NHWC, bfloat16-friendly) — the ImageNet flagship workload.

Role parity: reference ``examples/imagenet`` (ResNet-50 over
``CompressedImageCodec`` jpeg Parquet — BASELINE.json north star). TPU-first
choices: NHWC layout (XLA's native conv layout on TPU), bfloat16 compute with
float32 params/batch-stats, and a width that keeps matmuls on the MXU.
"""

from functools import partial
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


class BottleneckBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: int = 1

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides))(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 strides=(self.strides, self.strides),
                                 name='conv_proj')(residual)
            residual = self.norm(name='norm_proj')(residual)
        return self.act(residual + y)


class ResNetBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: int = 1

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1),
                                 strides=(self.strides, self.strides),
                                 name='conv_proj')(residual)
            residual = self.norm(name='norm_proj')(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    # 'conv7' = the classic 7x7/2 stem. 'space_to_depth' rearranges 2x2
    # pixel blocks into channels first ([B,H,W,3] -> [B,H/2,W/2,12]) and
    # applies an equivalent-receptive-field 4x4/1 conv: the contraction dim
    # grows 147 -> 192 taps and C=3 stops starving the MXU's 128-wide lane
    # tiling — the standard MLPerf ResNet-on-TPU stem transform.
    stem: str = 'conv7'
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train=True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = partial(nn.BatchNorm, use_running_average=not train, momentum=0.9,
                       epsilon=1e-5, dtype=self.dtype)
        with jax.named_scope('stem'):       # in no module of their own
            x = x.astype(self.dtype)
        if self.stem == 'space_to_depth':
            b, h, w, c = x.shape
            if h % 2 or w % 2:
                raise ValueError('space_to_depth stem needs even H/W, got '
                                 '{}x{}'.format(h, w))
            with jax.named_scope('stem'):
                x = (x.reshape(b, h // 2, 2, w // 2, 2, c)
                     .transpose(0, 1, 3, 2, 4, 5)
                     .reshape(b, h // 2, w // 2, 4 * c))
            x = conv(self.num_filters, (4, 4), padding='SAME',
                     name='conv_init')(x)
        elif self.stem == 'conv7':
            x = conv(self.num_filters, (7, 7), strides=(2, 2),
                     padding=[(3, 3), (3, 3)], name='conv_init')(x)
        else:
            raise ValueError('unknown stem {!r}'.format(self.stem))
        x = norm(name='bn_init')(x)
        with jax.named_scope('stem'):
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding='SAME')
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = 2 if i > 0 and j == 0 else 1
                x = self.block_cls(self.num_filters * 2 ** i, conv=conv, norm=norm,
                                   act=nn.relu, strides=strides)(x)
        with jax.named_scope('head'):
            x = jnp.mean(x, axis=(1, 2))
            x = nn.Dense(self.num_classes, dtype=self.dtype, name='head')(x)
            return x.astype(jnp.float32)


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=ResNetBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BottleneckBlock)
# A tiny variant for dry-runs / CI (compiles in seconds on CPU).
ResNetTiny = partial(ResNet, stage_sizes=[1, 1], block_cls=ResNetBlock, num_filters=8)
