"""The mellum program broken in ten ways, each of which the cell's
`correct` has to catch (ISSUE 36, Tentpole 5).  A variant is a context
manager over a family: inside it `family.loss` and the routing that
`family.reference_loss` asks the program for are the broken program's;
the reference stays what it is.

Three are built by an option of the program; seven need its code patched,
which is done here and nowhere in the program.  Used by the tests at tiny
widths (`tests/test_mellum.py`) and by `tools/reference_check.py` at the
published widths on the chip.
"""

import contextlib
import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.models import mellum
from byteps_tpu.ops import flash_attention
from byteps_tpu.parallel import dropless_moe


@contextlib.contextmanager
def _option(family, **changed):
    kept = family.cfg
    family.cfg = dataclasses.replace(kept, **changed)
    try:
        yield family
    finally:
        family.cfg = kept


def window_off_by_one_tile(family):
    """Sliding layers see one tile of 512 keys more (a quarter of the
    window at tiny widths)."""
    w = family.cfg.sliding_window
    return _option(family, sliding_window=w + max(w // 4, min(w, 512)))


def norm_topk_prob_off(family):
    """The chosen probabilities as they are, not over their sum."""
    return _option(family, norm_topk_prob=False)


def yarn_amplitude_left_out(family):
    """cos and sin of the full layers times 1."""
    return _option(family, yarn=dataclasses.replace(
        family.cfg.yarn, attention_factor=1.0))


@contextlib.contextmanager
def yarn_ramp_left_out(family):
    """Every pair of a full layer interpolated (frequency over `factor`),
    the fast ones too."""
    def interpolated(head_dim, theta, yarn):
        half = head_dim // 2
        return theta ** (-jnp.arange(half, dtype=jnp.float32) / half) / (
            yarn.factor)
    with mock.patch.object(mellum, "yarn_inv_freq", interpolated):
        yield family


@contextlib.contextmanager
def router_in_bfloat16(family):
    """Scores from a bfloat16 product, softmax in bfloat16; the top-k and
    the weights from those."""
    def route(x, router_w, cfg, expert_bias=None, sel=None):
        scores = jax.nn.softmax(
            x.astype(jnp.bfloat16) @ router_w.astype(jnp.bfloat16),
            axis=-1).astype(jnp.float32)
        if sel is None:
            _, sel = lax.top_k(lax.stop_gradient(scores), cfg.top_k)
        weights = jnp.take_along_axis(scores, sel, axis=-1)
        if cfg.route_norm:
            weights = weights / weights.sum(-1, keepdims=True)
        return sel, weights
    with mock.patch.object(dropless_moe, "route", route):
        yield family


@contextlib.contextmanager
def softmax_statistics_in_bfloat16(family):
    """The flash kernels' running maximum and sum rounded to bfloat16
    after every tile (the log-sum-exp the backward kernels read with
    them)."""
    step = flash_attention._online_step

    def rounded(*args, **kwargs):
        m, l, acc = step(*args, **kwargs)

        def bf16(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return bf16(m), bf16(l), acc
    with mock.patch.object(flash_attention, "_online_step", rounded):
        yield family


@contextlib.contextmanager
def expert_products_in_float8(family):
    """The operands of the experts' three products (the rows, the hidden
    activations, the weights) rounded to float8's three mantissa bits
    (e4m3) at bfloat16's range, which is what a scaled float8 product
    sees: the nearest precision below the bfloat16 the cell states."""
    swiglu = dropless_moe._swiglu_grouped

    def float8(x):
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)

    def rounded(xg, experts, group_sizes, dtype):
        def grouped(lhs, w):
            return lax.ragged_dot(float8(lhs), float8(w.astype(dtype)),
                                  group_sizes)
        h = jax.nn.silu(grouped(xg, experts["gate_w"])) * grouped(
            xg, experts["up_w"])
        return grouped(h, experts["down_w"])
    assert swiglu.__code__.co_varnames[:4] == rounded.__code__.co_varnames[:4]
    with mock.patch.object(dropless_moe, "_swiglu_grouped", rounded):
        yield family


@contextlib.contextmanager
def held_expert_dropped(family):
    """The last held expert of every layer adds nothing."""
    held_experts = dropless_moe.held_experts

    def dropped(x, router_w, experts, cfg, **kwargs):
        keep = (jnp.arange(len(cfg.held)) < len(cfg.held) - 1)
        experts = dict(experts, down_w=experts["down_w"]
                       * keep[:, None, None].astype(experts["down_w"].dtype))
        return held_experts(x, router_w, experts, cfg, **kwargs)
    with mock.patch.object(dropless_moe, "held_experts", dropped):
        yield family


def held_weight_not_held(family):
    """A share's backward pass as it comes: the router's gradient is the
    one that says "send the held experts more"
    (`dropless_moe.MoEConfig.hold_held_weight` off)."""
    moe = mellum.MellumConfig.moe.fget
    return mock.patch.object(
        mellum.MellumConfig, "moe", property(lambda cfg: dataclasses.replace(
            moe(cfg), hold_held_weight=False)))


def top7(family):
    return _option(family, num_experts_per_tok=family.cfg.num_experts_per_tok
                   - 1)


VARIANTS = {
    "router_in_bfloat16": router_in_bfloat16,
    "softmax_statistics_in_bfloat16": softmax_statistics_in_bfloat16,
    "yarn_amplitude_left_out": yarn_amplitude_left_out,
    "yarn_ramp_left_out": yarn_ramp_left_out,
    "window_off_by_one_tile": window_off_by_one_tile,
    "norm_topk_prob_off": norm_topk_prob_off,
    "held_expert_dropped": held_expert_dropped,
    "expert_products_in_float8": expert_products_in_float8,
    "top7": top7,
    "held_weight_not_held": held_weight_not_held,
}
