"""Plain float32 reference of VGG's classification loss.

Written from Simonyan & Zisserman (arXiv:1409.1556), section 2.1 and
Table 1: a stack of 3x3 convolutions with stride 1 and 1 pixel of padding,
each followed by ReLU; 2x2 max-pooling with stride 2 where the table says
"maxpool"; then FC-4096, FC-4096, FC-1000 with ReLU between them, and the
softmax cross-entropy of the label.  The table's column is given as the
list of the configuration file: ints are convolution widths, "M" a
max-pool.

Nothing of byteps_tpu or flax is imported.  The one thing shared with the
program is the layout of its parameter tree, which the reference has to
read: `params/Conv_<i>/{kernel [3, 3, in, out], bias}` and
`params/Dense_<i>/{kernel [in, out], bias}`, images NHWC, the last
feature map flattened in (h, w, c) order.

Departures from the paper, each because the program departs alike: no
dropout on the two hidden FC layers (published 0.5).  Everything is
float32 with matmuls and convolutions at `highest` precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def conv3x3_relu(x, kernel, bias):
    y = lax.conv_general_dilated(
        x, kernel, window_strides=(1, 1), padding=((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    return jnp.maximum(y + bias, 0.0)


def max_pool_2x2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                             (1, 2, 2, 1), "VALID")


def logits(variables, images, layers):
    p = jax.tree.map(lambda x: x.astype(jnp.float32), variables["params"])
    x = images.astype(jnp.float32)
    conv = 0
    for width in layers:
        if width == "M":
            x = max_pool_2x2(x)
        else:
            layer = p[f"Conv_{conv}"]
            if layer["kernel"].shape[-1] != width:
                raise ValueError(f"Conv_{conv} is not {width} wide")
            x = conv3x3_relu(x, layer["kernel"], layer["bias"])
            conv += 1
    x = x.reshape(x.shape[0], -1)
    for i in range(3):
        layer = p[f"Dense_{i}"]
        x = x @ layer["kernel"] + layer["bias"]
        if i < 2:
            x = jnp.maximum(x, 0.0)
    return x


def loss(variables, batch, layers):
    """Mean softmax cross-entropy.  batch = (images NHWC, labels int32)."""
    with jax.default_matmul_precision("highest"):
        images, labels = batch
        logp = jax.nn.log_softmax(logits(variables, images, layers), -1)
        return -jnp.take_along_axis(logp, labels[:, None], -1).mean()
