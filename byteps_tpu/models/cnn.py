"""CNN model family: ResNet and VGG (flax.linen).

The reference's throughput benchmarks are ResNet-50 and VGG-16
(reference: docs/performance.md:5-26, example/pytorch/benchmark_byteps.py
uses torchvision models).  These are the TPU-native counterparts: NHWC
layout (TPU conv-native), bf16 compute with f32 params/batch-stats, built
with flax.linen so they drop straight into the DistributedOptimizer path.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


class ResNetBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1),
                                 self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class BottleneckResNetBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

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
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=self.dtype)
        with jax.named_scope("cnn.features"):
            x = conv(self.num_filters, (7, 7), (2, 2),
                     padding=[(3, 3), (3, 3)],
                     name="conv_init")(x.astype(self.dtype))
            x = norm(name="bn_init")(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
            for i, block_size in enumerate(self.stage_sizes):
                for j in range(block_size):
                    strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                    x = self.block_cls(self.num_filters * 2 ** i,
                                       conv=conv, norm=norm, act=nn.relu,
                                       strides=strides)(x)
        with jax.named_scope("cnn.classifier"):
            x = jnp.mean(x, axis=(1, 2))
            x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
            return x


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=ResNetBlock)
ResNet34 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=ResNetBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3],
                   block_cls=BottleneckResNetBlock)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3],
                    block_cls=BottleneckResNetBlock)


class VGG(nn.Module):
    """VGG-16/19 (docs/performance.md benchmarks VGG-16)."""
    cfg: Sequence  # ints = conv filters, "M" = maxpool
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        with jax.named_scope("cnn.features"):
            x = x.astype(self.dtype)
            for v in self.cfg:
                if v == "M":
                    x = nn.max_pool(x, (2, 2), strides=(2, 2))
                else:
                    x = nn.Conv(v, (3, 3), padding=[(1, 1), (1, 1)],
                                dtype=self.dtype)(x)
                    x = nn.relu(x)
        with jax.named_scope("cnn.classifier"):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dense(4096, dtype=self.dtype)(x)
            x = nn.relu(x)
            x = nn.Dense(4096, dtype=self.dtype)(x)
            x = nn.relu(x)
            x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
            return x


_VGG16_CFG = [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
              512, 512, 512, "M", 512, 512, 512, "M"]
_VGG19_CFG = [64, 64, "M", 128, 128, "M", 256, 256, 256, 256, "M",
              512, 512, 512, 512, "M", 512, 512, 512, 512, "M"]

VGG16 = partial(VGG, cfg=_VGG16_CFG)
VGG19 = partial(VGG, cfg=_VGG19_CFG)


_CNN_TABLE = {"resnet18": ResNet18, "resnet34": ResNet34,
              "resnet50": ResNet50, "resnet101": ResNet101,
              "vgg16": VGG16, "vgg19": VGG19}
CNN_NAMES = tuple(_CNN_TABLE)


def create_cnn(name: str, num_classes: int = 1000, **kw) -> nn.Module:
    if name not in _CNN_TABLE:
        raise ValueError(
            f"unknown cnn {name!r}; options: {sorted(_CNN_TABLE)}")
    return _CNN_TABLE[name](num_classes=num_classes, **kw)


def cnn_loss_fn(model: nn.Module):
    """Returns loss(variables, batch) for softmax-CE image classification.

    `variables` is the full flax variable dict ({'params': ..., and
    'batch_stats': ... when the model has BatchNorm}).  Inference-mode norm
    (train=False) keeps the loss a pure function of `variables`, which is what
    the DP train-step builder differentiates; models that need train-mode
    batch-stats updates thread the mutable collection explicitly in their
    training script (see example/jax/train_imagenet_resnet_byteps.py).
    """
    def loss(variables, batch):
        images, labels = batch
        logits = model.apply(variables, images, train=False)
        with jax.named_scope("cnn.head"):
            logp = jax.nn.log_softmax(logits)
            return -jnp.take_along_axis(logp, labels[:, None],
                                        axis=-1).mean()
    return loss
