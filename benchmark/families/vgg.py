"""The vgg family: a column of the VGG paper's Table 1 run through the
program's model zoo (`byteps_tpu.models.create_cnn`), with the plain
reference of `benchmark/reference/vgg.py` beside it.  See
`benchmark/families/gpt2.py` for what a family is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from benchmark.reference import vgg as reference
from byteps_tpu import models


def model_flops_per_image(layers, fc, num_classes: int, image_size: int,
                          channels: int) -> float:
    """Model FLOPs to train on one image: 2 per multiply-add of every
    convolution and FC layer in the forward pass, times 3 for forward and
    backward (the gradient of the first layer's input is counted though
    nobody needs it: the usual convention, 0.6% of the total here).
    Biases, ReLU, pooling and the softmax are not counted."""
    macs, size, cin = 0, image_size, channels
    for width in layers:
        if width == "M":
            size //= 2
        else:
            macs += size * size * 9 * cin * width
            cin = width
    fan_in = size * size * cin
    for width in (*fc, num_classes):
        macs += fan_in * width
        fan_in = width
    return 3.0 * 2.0 * macs


class Family:
    unit = "images"
    units_per_sample = 1
    def __init__(self, config: dict, job: dict):
        pub = config["published"]
        self.pub = pub
        self.model = models.create_cnn(pub["name"],
                                       num_classes=pub["num_classes"])
        if list(self.model.cfg) != list(pub["layers"]):
            raise ValueError(
                f"the program's {pub['name']} is {list(self.model.cfg)}, "
                f"the configuration publishes {pub['layers']}")
        self._loss = models.cnn_loss_fn(self.model)
        # how many samples the reference check takes and how far the
        # program may be from the reference, with the reason, are the
        # configuration's own
        self.reference_check = config["reference_check"]
        opt = job["optimizer"]
        if opt["name"] != "sgd":
            raise ValueError(f"vgg family: no optimizer {opt['name']!r}")
        self._opt = opt

    def optimizer(self) -> optax.GradientTransformation:
        return optax.sgd(float(self._opt["learning_rate"]),
                         momentum=float(self._opt["momentum"]))

    def _image_shape(self, n: int):
        p = self.pub
        return (n, p["image_size"], p["image_size"], p["channels"])

    def init(self, key):
        return self.model.init(key, jnp.zeros(self._image_shape(1)),
                               train=False)

    def make_batch(self, key, n_samples: int):
        k_img, k_lab = jax.random.split(key)
        images = jax.random.normal(k_img, self._image_shape(n_samples),
                                   jnp.float32)
        labels = jax.random.randint(k_lab, (n_samples,), 0,
                                    self.pub["num_classes"], jnp.int32)
        return images, labels

    def loss(self, variables, batch):
        return self._loss(variables, batch)

    def reference_loss(self, variables, batch):
        return reference.loss(variables, batch, self.pub["layers"])

    def model_flops_per_sample(self) -> float:
        p = self.pub
        return model_flops_per_image(p["layers"], p["fc"], p["num_classes"],
                                     p["image_size"], p["channels"])
