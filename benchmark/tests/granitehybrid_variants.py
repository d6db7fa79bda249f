"""The granitehybrid program broken in fourteen ways, each of which the
cell's `correct` has to catch (ISSUE 34).  A variant is a context manager
over a family: inside it `family.loss` is the broken program's; the
reference stays what it is.

Five are built by an option of the program (a multiplier left at 1, the
attention's usual scale); nine need its code patched, which is done here
and nowhere in the program.  The two that touch the carried state patch
the `jnp` form of the scan (`ops/ssd.py` `_chunk`) and run the program
with it (`jnp_scan`: `ssd_scan(impl="jnp")`, the model itself has no such
switch): the kernels keep their state in scratch memory no patch reaches.
Two compute part of the scan in bfloat16 where the configuration states
float32 (`ONLY_ROUNDING`): the cumulative sums, which the cell's three
limits tell on the chip, and the carried state, which they do not (a
state rounded once a chunk reads like the program's own bfloat16
products): that one is told by the family's fourth number, the program's
scan alone on float32 operands against the recurrence.
Used by the tests at tiny widths (`tests/test_granite_hybrid.py`,
`benchmark/tests/test_granitehybrid.py`) and by `tools/reference_check.py`
at the published widths on the chip.
"""

import contextlib
import dataclasses
import functools
import math
from unittest import mock

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.models import granite_hybrid
from byteps_tpu.ops import ssd


@contextlib.contextmanager
def _option(family, **changed):
    kept = family.cfg
    family.cfg = dataclasses.replace(kept, **changed)
    try:
        yield family
    finally:
        family.cfg = kept


def embedding_multiplier_left_out(family):
    return _option(family, embedding_multiplier=1.0)


def residual_multiplier_left_out(family):
    return _option(family, residual_multiplier=1.0)


def attention_multiplier_left_out(family):
    return _option(family, attention_multiplier=1.0)


def logits_scaling_left_out(family):
    return _option(family, logits_scaling=1.0)


def attention_scale_sqrt(family):
    """1 / sqrt(head size), every other model's scale."""
    return _option(family,
                   attention_multiplier=1.0 / math.sqrt(family.cfg.head_dim))


def _as_bfloat16(x):
    """x with bfloat16's 8 bits of mantissa, float32 still.  Not a cast
    there and back: the TPU's compiler takes a pair of casts out as excess
    precision it is allowed to keep, and the variant then reads like the
    program to five digits (so it did, on the chip)."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@contextlib.contextmanager
def jnp_scan(family):
    """The program with the `jnp` form of its scan (`ssd_scan(impl=)`):
    not broken, and what the two variants of the carried state patch."""
    with mock.patch.object(ssd, "ssd_scan",
                           functools.partial(ssd.ssd_scan, impl="jnp")):
        yield family


@contextlib.contextmanager
def _patched_chunk(family, wrap):
    with jnp_scan(family), \
            mock.patch.object(ssd, "_chunk", wrap(ssd._chunk)):
        yield family


def state_in_bfloat16(family):
    """The state a chunk hands on is rounded to bfloat16."""
    def wrap(chunk):
        def broken(*args):
            y, state = chunk(*args)
            return y, _as_bfloat16(state)
        return broken
    return _patched_chunk(family, wrap)


def state_dropped_at_chunk_edge(family):
    """Every chunk starts from an empty state."""
    def wrap(chunk):
        def broken(x, dt, a, bm, cm, state):
            return chunk(x, dt, a, bm, cm, jnp.zeros_like(state))
        return broken
    return _patched_chunk(family, wrap)


@contextlib.contextmanager
def cumulative_sum_in_bfloat16(family):
    """The decays' exponents from a cumulative sum kept in bfloat16."""
    def cumsum(a):
        return _as_bfloat16(jnp.cumsum(a, axis=-1))
    with mock.patch.object(ssd, "_cumsum", cumsum):
        yield family


@contextlib.contextmanager
def dt_bias_left_out(family):
    def step_size(raw, dt_bias):
        return jax.nn.softplus(raw.astype(jnp.float32))
    with mock.patch.object(granite_hybrid, "_step_size", step_size):
        yield family


@contextlib.contextmanager
def softplus_left_out(family):
    """dt + dt_bias as it comes, negative where it is: the state then
    grows where it should decay."""
    def step_size(raw, dt_bias):
        return raw.astype(jnp.float32) + dt_bias.astype(jnp.float32)
    with mock.patch.object(granite_hybrid, "_step_size", step_size):
        yield family


@contextlib.contextmanager
def d_left_out(family):
    scan = ssd.ssd_scan

    def without(x, dt, A, B, C, D, **kw):
        return scan(x, dt, A, B, C, jnp.zeros_like(D), **kw)
    with mock.patch.object(ssd, "ssd_scan", without):
        yield family


@contextlib.contextmanager
def conv_bias_left_out(family):
    def conv(xbc, lp):
        return jax.nn.silu(ssd.causal_conv1d(xbc, lp["conv_w"]))
    with mock.patch.object(granite_hybrid, "_conv", conv):
        yield family


@contextlib.contextmanager
def conv_shifted_by_one(family):
    """The taps meet x_{t-4} ... x_{t-1}: the current position is not
    seen."""
    def conv(xbc, lp):
        late = jnp.pad(xbc, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        return jax.nn.silu(ssd.causal_conv1d(late, lp["conv_w"],
                                             lp["conv_b"]))
    with mock.patch.object(granite_hybrid, "_conv", conv):
        yield family


@contextlib.contextmanager
def gate_after_norm(family):
    def gate_norm(y, z, scale, cfg):
        return granite_hybrid._norm(y, scale, cfg) * jax.nn.silu(z)
    with mock.patch.object(granite_hybrid, "_gate_norm", gate_norm):
        yield family


VARIANTS = {
    "state_in_bfloat16": state_in_bfloat16,
    "state_dropped_at_chunk_edge": state_dropped_at_chunk_edge,
    "cumulative_sum_in_bfloat16": cumulative_sum_in_bfloat16,
    "dt_bias_left_out": dt_bias_left_out,
    "softplus_left_out": softplus_left_out,
    "d_left_out": d_left_out,
    "conv_bias_left_out": conv_bias_left_out,
    "conv_shifted_by_one": conv_shifted_by_one,
    "gate_after_norm": gate_after_norm,
    "embedding_multiplier_left_out": embedding_multiplier_left_out,
    "residual_multiplier_left_out": residual_multiplier_left_out,
    "attention_multiplier_left_out": attention_multiplier_left_out,
    "logits_scaling_left_out": logits_scaling_left_out,
    "attention_scale_sqrt": attention_scale_sqrt,
}
# Round as the program's own bfloat16 products do: inside the cell's three
# limits (the carried state) or inside them at tiny widths (the cumulative
# sums), and told by the scan alone in float32, the fourth number of
# `benchmark/families/granitehybrid.py`.
ONLY_ROUNDING = ("state_in_bfloat16", "cumulative_sum_in_bfloat16")
BUILT_BY_AN_OPTION = (
    "embedding_multiplier_left_out", "residual_multiplier_left_out",
    "attention_multiplier_left_out", "logits_scaling_left_out",
    "attention_scale_sqrt")
