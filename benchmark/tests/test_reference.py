"""The plain references against the program at tiny widths, the model
FLOPs functions, and that the tolerances bind."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.families import gpt2, vgg
from benchmark.harness import correct, manifest, seeded
from benchmark.tests.tiny import tiny_config
from byteps_tpu import models
from byteps_tpu.models import transformer as tfm


def _config(name):
    with open(os.path.join(manifest.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_gpt2():
    config = tiny_config(_config("gpt2-medium"))
    family = gpt2.Family(config, config["job"])
    return family, seeded.params(family, 0), seeded.batch(family, 0, 2)


def _dropped_mask(q, k, v, causal):
    return tfm.dense_attention(q, k, v, causal=False)


def _bf16_softmax(q, k, v, causal):
    """Dense causal attention with the scores and the softmax left in the
    activations' bfloat16."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype))
    n = q.shape[2]
    scores = jnp.where(jnp.tril(jnp.ones((n, n), bool)), scores,
                       jnp.finfo(q.dtype).min)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("attn_fn,passes", [
    (None, True),                       # the configuration's own: flash
    (tfm.dense_attention, True),
    (_dropped_mask, False),
], ids=["flash", "dense", "dropped_mask"])
def test_gpt2_against_reference(tiny_gpt2, attn_fn, passes):
    family, params, batch = tiny_gpt2

    def loss(p, b):
        return tfm.loss_fn(p, b, family.cfg, attn_fn=attn_fn)

    got = correct.gradient_agreement(loss, family.reference_loss, params,
                                     batch)
    assert correct.agreement_ok(got, family.reference_check) is passes, got
    if not passes:
        # not by a hair: the worst leaf is wrong by its own size
        assert got["worst_grad_rel_diff"] > 10 * family.reference_check["grad_rel_tol"]


def test_gpt2_in_float32_is_the_reference(tiny_gpt2):
    """With float32 activations the program and the reference are the
    same function to rounding: what is left under bfloat16 is rounding
    and not a different model."""
    family, params, batch = tiny_gpt2
    import dataclasses
    cfg = dataclasses.replace(family.cfg, dtype=jnp.float32,
                              attn_impl="dense", ce_chunk_rows=0)
    got = correct.gradient_agreement(
        lambda p, b: tfm.loss_fn(p, b, cfg), family.reference_loss, params,
        batch)
    assert got["loss_rel_diff"] < 1e-6
    assert got["worst_grad_rel_diff"] < 1e-4, got


def test_bf16_softmax_is_seen_at_the_kernel(tiny_gpt2):
    """A softmax taken in bfloat16 moves the model's gradients by about as
    much as bfloat16 activations already do, so the model-level tolerance
    cannot tell it (PERF.md, Open questions).  Where it shows is the
    attention output itself, which this pins: against float32 attention
    on the same inputs, the program's kernel errs by bfloat16's output
    rounding, and a bfloat16 softmax by several times that."""
    from benchmark.reference import gpt2 as reference
    q, k, v = (jax.random.normal(key, (2, 4, 256, 64), jnp.bfloat16)
               for key in jax.random.split(jax.random.key(0), 3))
    exact = reference.attention(*(t.astype(jnp.float32) for t in (q, k, v)))

    def err(fn):
        out = fn(q, k, v, True).astype(jnp.float32)
        return float(jnp.linalg.norm(out - exact) / jnp.linalg.norm(exact))

    kernel, sloppy = err(tfm.flash_attention_fn), err(_bf16_softmax)
    assert kernel < 4e-3, kernel          # 2**-8, bfloat16's rounding step
    assert sloppy > 2 * kernel, (kernel, sloppy)


def test_vgg_against_reference():
    config = tiny_config(_config("vgg16"))
    family = vgg.Family(config, config["job"])
    params, batch = seeded.params(family, 0), seeded.batch(family, 0, 4)
    got = correct.gradient_agreement(family.loss, family.reference_loss,
                                     params, batch)
    assert correct.agreement_ok(got, family.reference_check), got
    # The program's own model in float32 is the reference's function
    # exactly: what bfloat16 leaves is rounding and max-pool ties, not
    # another architecture.
    exact = models.cnn_loss_fn(models.create_cnn(
        "vgg16", num_classes=config["published"]["num_classes"],
        dtype=jnp.float32))
    got = correct.gradient_agreement(exact, family.reference_loss, params,
                                     batch)
    assert got["loss_rel_diff"] < 1e-6
    assert got["worst_grad_rel_diff"] < 1e-4, got

    def no_last_relu(variables, b):     # a wrong architecture must fail
        images, labels = b
        p = dict(variables["params"])
        p["Dense_1"] = jax.tree.map(jnp.zeros_like, p["Dense_1"])
        return family.loss({"params": p}, b)

    got = correct.gradient_agreement(no_last_relu, family.reference_loss,
                                     params, batch)
    assert not correct.agreement_ok(got, family.reference_check)


def test_model_flops():
    pub = _config("gpt2-medium")["published"]
    per_token = gpt2.model_flops_per_token(
        pub["n_layer"], pub["n_embd"], pub["n_inner"], pub["vocab_size"],
        seq_len=1024)
    # 6 * (24 * 12 * 1024**2 + 50257 * 1024) + 12 * 24 * 1024 * 1024
    assert per_token == 6 * (301989888 + 51463168) + 301989888
    family = gpt2.Family(_config("gpt2-medium"),
                         _config("gpt2-medium")["job"])
    # the program's own arithmetic, at the model's full context
    assert per_token == tfm.flops_per_token(family.cfg)
    pub = _config("vgg16")["published"]
    per_image = vgg.model_flops_per_image(
        pub["layers"], pub["fc"], pub["num_classes"], pub["image_size"],
        pub["channels"])
    assert per_image == pytest.approx(92.82e9, rel=1e-3)   # 15.47 GMAC x 6


def test_published_sizes():
    family = vgg.Family(_config("vgg16"), _config("vgg16")["job"])
    shapes = jax.eval_shape(family.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == \
        _config("vgg16")["published"]["parameters"] == 138357544
    family = gpt2.Family(_config("gpt2-medium"),
                         _config("gpt2-medium")["job"])
    shapes = jax.eval_shape(family.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 354823168
    assert len(jax.tree.leaves(shapes)) == 16


def test_losses_checks():
    assert correct.losses_sound([3.0, 2.9, 2.5])
    assert not correct.losses_sound([3.0, 3.1])
    assert not correct.losses_sound([3.0, float("nan"), 2.0])
    assert correct.losses_agree([1.0, 2.0], [1.0, 2.0 + 1e-6],
                                "rounding")["ok"]
    assert not correct.losses_agree([1.0, 2.0], [1.0, 2.001],
                                    "reduction_order")["ok"]
