"""The kimi_linear model WHOLE (every expert, the whole vocabulary)
against its plain reference at tiny widths in float32, beside
`tests/test_kimi_linear.py` (a file of its own so that another worker
runs it); and `short_conv.mamba_conv` without a bias, which this model's
KDA layers call."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.tests import tiny_kimilinear
from byteps_tpu.models import kimi_linear
from byteps_tpu.ops import short_conv
from tests.test_kimi_linear import against_reference


def test_the_whole_model_against_the_reference():
    """A KDA expert layer and a latent-attention expert layer (the
    model's layers 7 and 8) with all of a 16-wide router's experts held
    and the whole vocabulary: nothing is a share, and `hold_held_weight`
    has nothing to hold."""
    config = tiny_kimilinear.config(layers=[7, 8], experts=range(16),
                                    vocab=512)
    config["published"].update(num_experts=16, vocab_size=512)
    from benchmark.families import kimilinear
    family = kimilinear.Family(config, config["job"])
    family.cfg = dataclasses.replace(family.cfg, dtype=jnp.float32)
    assert kimi_linear.stack_plan(family.cfg) == (
        (kimi_linear.KDA, kimi_linear.MOE, 1),
        (kimi_linear.MLA, kimi_linear.MOE, 1))
    assert len(family.cfg.held) == family.cfg.num_experts == 16
    loss_off, worst, name = against_reference(family)
    assert loss_off < 1e-6 and worst < 2e-4, (loss_off, worst, name)


def test_mamba_conv_without_a_bias_is_mamba_conv_with_a_zero_bias():
    """Result and the gradients of x and the taps, three parts in one
    call; the bias's own gradient is not asked for."""
    k = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(k[0], (2, 48, 96))
    w = jax.random.normal(k[1], (4, 96))
    g = jax.random.normal(k[2], (2, 48, 96))
    parts = (32, 32, 32)

    def call(bias):
        out, vjp = jax.vjp(lambda x, w: jnp.concatenate(
            short_conv.mamba_conv(x, w, bias, parts=parts), -1), x, w)
        return (out, *vjp(g))
    for a, b in zip(call(None), call(jnp.zeros((96,)))):
        # the compiler folds a row of zeros its own way: float32's last bit
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=4e-6)


def test_mamba_conv_with_a_bias_traces_what_it_traced():
    """The granite and nemotron cells' call: the jaxpr of `mamba_conv`
    WITH a bias and of its gradient, at a granite layer's width, hashes to
    what the parent tree's did (commit a68a736; the text holds no source
    locations): taking `bias=None` changed nothing of it."""
    x = jax.ShapeDtypeStruct((1, 256, 4352), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((4, 4352), jnp.float32)
    b = jax.ShapeDtypeStruct((4352,), jnp.float32)

    def both(x, w, b):
        def loss(x, w, b):
            return sum(y.astype(jnp.float32).sum() for y in
                       short_conv.mamba_conv(x, w, b, parts=(4096, 128, 128),
                                             interpret=False))
        return jax.grad(loss, (0, 1, 2))(x, w, b)
    text = str(jax.make_jaxpr(both)(x, w, b))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == "5290764a4201364c"
