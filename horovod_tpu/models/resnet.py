"""ResNet model family — CIFAR ResNet v1/v2 (20/56/110) and ImageNet ResNet-50.

Reference parity
----------------
* ``examples/keras-cifar10-resnet.py`` builds ResNet v1 (6n+2) and v2 (9n+2)
  for CIFAR-10 (``keras-cifar10-resnet.py:52-63`` documents the accuracy
  table: 20v1 92.16%, 56v1 92.71%, 110v1 92.65%, 56v2 93.01%, 110v2 93.15%).
* ``examples/keras_imagenet_resnet50.py`` trains stock Keras ResNet-50 with
  the Goyal et al. recipe (``keras_imagenet_resnet50.py:32-37, 113-122``).

TPU-native design
-----------------
flax.linen modules with a ``dtype`` knob (bfloat16 activations by default on
TPU — the MXU's native input type; params stay float32). Convs and matmuls
are left to XLA to tile onto the MXU; BatchNorm uses a mutable ``batch_stats``
collection, and under data parallelism the running stats are synchronized with
a cross-replica mean via ``axis_name`` (the modern equivalent of what the
reference delegates to per-replica Keras BN plus weight broadcast).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

ModuleDef = Any


class BasicBlock(nn.Module):
    """ResNet v1 basic block: conv-bn-relu, conv-bn, add, relu."""

    filters: int
    strides: Tuple[int, int] = (1, 1)
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = nn.BatchNorm
    act: Callable = nn.relu

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides, padding="SAME")(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), padding="SAME")(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides,
                                 name="shortcut")(residual)
            residual = self.norm(name="shortcut_bn")(residual)
        return self.act(y + residual)


class BottleneckBlock(nn.Module):
    """ResNet v1 bottleneck (1x1 -> 3x3 -> 1x1 x4), used by ResNet-50."""

    filters: int
    strides: Tuple[int, int] = (1, 1)
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = nn.BatchNorm
    act: Callable = nn.relu

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides, padding="SAME")(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        # Zero-init the last BN scale so each block starts as identity
        # (Goyal et al. trick used by the reference recipe's lineage).
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                 name="shortcut")(residual)
            residual = self.norm(name="shortcut_bn")(residual)
        return self.act(y + residual)


class PreActBlock(nn.Module):
    """ResNet v2 pre-activation bottleneck (bn-relu-conv ordering),
    the ``resnet_v2`` of ``keras-cifar10-resnet.py``."""

    filters: int
    strides: Tuple[int, int] = (1, 1)
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = nn.BatchNorm
    act: Callable = nn.relu

    @nn.compact
    def __call__(self, x):
        y = self.norm()(x)
        y = self.act(y)
        residual = x
        if self.strides != (1, 1) or x.shape[-1] != self.filters * 4:
            residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                 name="shortcut")(y)
        y = self.conv(self.filters, (1, 1))(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides, padding="SAME")(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        return y + residual


class ResNet(nn.Module):
    """Generic ResNet.

    ``stage_sizes`` counts blocks per stage; ``block_cls`` picks the block
    flavor. ``cifar_stem=True`` uses the 3x3/stride-1 stem (CIFAR, 32x32
    inputs); otherwise the 7x7/stride-2 + maxpool ImageNet stem.
    """

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 10
    num_filters: int = 64
    cifar_stem: bool = False
    # Space-to-depth stem (the standard TPU ResNet optimization, e.g.
    # MLPerf ResNet-50 submissions): fold 2x2 image patches into channels
    # ([N,H,W,3] -> [N,H/2,W/2,12]) and replace the 7x7/stride-2 stem conv
    # with an equivalent-receptive-field 4x4/stride-1 conv. A 3-channel
    # stride-2 conv uses ~2% of the MXU's 128 input lanes and dominates
    # like 15-20% of step time; the s2d form quadruples channel depth and
    # removes the stride. Same downstream network; trains from scratch
    # like the original (the 4x4x12 kernel is the zero-padded 8x8x3
    # reparametrization of the 7x7x3 one).
    stem_space_to_depth: bool = False
    dtype: Any = jnp.bfloat16
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = functools.partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=self.dtype,
            axis_name=self.axis_name if train else None)

        x = x.astype(self.dtype)
        if self.cifar_stem:
            x = conv(self.num_filters, (3, 3), padding="SAME", name="stem")(x)
        else:
            if self.stem_space_to_depth:
                n, h, w, c = x.shape
                if h % 2 or w % 2:
                    raise ValueError(
                        f"stem_space_to_depth folds 2x2 patches and needs "
                        f"even spatial dims; got {h}x{w} (pad or resize "
                        f"the input, or use the standard stem)")
                x = x.reshape(n, h // 2, 2, w // 2, 2, c)
                x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2,
                                                          4 * c)
                x = conv(self.num_filters, (4, 4), (1, 1),
                         padding=[(2, 1), (2, 1)], name="stem_s2d")(x)
            else:
                x = conv(self.num_filters, (7, 7), (2, 2),
                         padding=[(3, 3), (3, 3)], name="stem")(x)
            x = norm(name="stem_bn")(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        if self.cifar_stem and self.block_cls is not PreActBlock:
            x = norm(name="stem_bn")(x)
            x = nn.relu(x)

        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(
                    self.num_filters * 2 ** i, strides=strides,
                    conv=conv, norm=norm)(x)

        if self.block_cls is PreActBlock:
            x = norm(name="final_bn")(x)
            x = nn.relu(x)
        x = jnp.mean(x, axis=(1, 2))
        # Final logits in float32 for numerically stable softmax/loss.
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)
        return x


# ---------------------------------------------------------------------------
# CIFAR ResNet v1 (6n+2) / v2 (9n+2) — keras-cifar10-resnet.py parity.
# depth 20 -> n=3, 56 -> n=9, 110 -> n=18 (v1); v2 uses 9n+2.
# ---------------------------------------------------------------------------

def cifar_resnet_v1(depth: int = 20, num_classes: int = 10, **kw) -> ResNet:
    """ResNet v1 for CIFAR (``keras-cifar10-resnet.py`` resnet_v1,
    depth = 6n+2 ∈ {20, 56, 110})."""
    if (depth - 2) % 6 != 0:
        raise ValueError("v1 depth must be 6n+2 (e.g. 20, 56, 110)")
    n = (depth - 2) // 6
    return ResNet(stage_sizes=[n, n, n], block_cls=BasicBlock,
                  num_classes=num_classes, num_filters=16, cifar_stem=True,
                  **kw)


def cifar_resnet_v2(depth: int = 56, num_classes: int = 10, **kw) -> ResNet:
    """ResNet v2 (pre-activation) for CIFAR (``keras-cifar10-resnet.py``
    resnet_v2, depth = 9n+2 ∈ {56, 110})."""
    if (depth - 2) % 9 != 0:
        raise ValueError("v2 depth must be 9n+2 (e.g. 56, 110)")
    n = (depth - 2) // 9
    return ResNet(stage_sizes=[n, n, n], block_cls=PreActBlock,
                  num_classes=num_classes, num_filters=16, cifar_stem=True,
                  **kw)


def resnet50(num_classes: int = 1000, **kw) -> ResNet:
    """ImageNet ResNet-50 — the reference's north-star workload
    (``keras_imagenet_resnet50.py``)."""
    return ResNet(stage_sizes=[3, 4, 6, 3], block_cls=BottleneckBlock,
                  num_classes=num_classes, num_filters=64, **kw)


def resnet101(num_classes: int = 1000, **kw) -> ResNet:
    """ResNet-101 (the reference's benchmark model, ``docs/benchmarks.md``)."""
    return ResNet(stage_sizes=[3, 4, 23, 3], block_cls=BottleneckBlock,
                  num_classes=num_classes, num_filters=64, **kw)
