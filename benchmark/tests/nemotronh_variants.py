"""The nemotronh program broken in twelve ways, each of which the cell's
`correct` has to catch (ISSUE 47).  A variant is a context manager over a
family: inside it `family.loss`, and what `family.reference_loss` runs of
the program, are the broken program's; the reference stays what it is.

One is built by an option of the program (the route scale left at 1); the
others need its code patched, which is done here and nowhere in the
program.  The one that touches the carried state patches the `jnp` form
of the scan (`ops/ssd.py` `_chunk`) and runs the program with it: the
kernels keep their state in scratch memory no patch reaches.  Three only
ROUND where the configuration states a precision (`ONLY_ROUNDING`): the
carried state in bfloat16 (told by `scan_rel_tol`), the experts' products
in float8 (`experts_rel_tol`, the nearest precision below the cell's
bfloat16) and the softmax's statistics in bfloat16 (`attn_row_tol`); in
float32 at tiny widths they fail the three limits too.
Used by the tests at tiny widths (`tests/test_nemotron_h.py`,
`benchmark/tests/test_nemotronh.py`) and by `tools/reference_check.py` at
the published widths on the chip.
"""

import contextlib
import dataclasses
import functools
from unittest import mock

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.models import granite_hybrid, nemotron_h
from byteps_tpu.models.transformer import _rope
from byteps_tpu.ops import flash_attention, ssd
from byteps_tpu.parallel import dropless_moe


def _as_bfloat16(x):
    """x with bfloat16's 8 bits of mantissa, float32 still (not a cast
    there and back, which the TPU's compiler takes out)."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _relu2(u):
    return jnp.square(jax.nn.relu(u))


def _grouped_form(between=_relu2, operand=lambda x: x):
    """An expert's two grouped products (`lax.ragged_dot`) with `between`
    in place of the squared ReLU and `operand` put on every operand, under
    `dropless_moe._relu2_grouped`'s signature."""
    def form(xg, experts, group_sizes, dtype):
        def grouped(lhs, w):
            return lax.ragged_dot(operand(lhs), operand(w.astype(dtype)),
                                  group_sizes)
        return grouped(between(grouped(xg, experts["up_w"])),
                       experts["down_w"])
    assert (dropless_moe._relu2_grouped.__code__.co_varnames[:4]
            == form.__code__.co_varnames[:4])
    return form


@contextlib.contextmanager
def _experts_between(family, between):
    """Every expert, routed and shared, with `between` for relu(.)^2."""
    def shared(x, up_w, down_w, dt):
        h = jnp.einsum("bsd,df->bsf", x, up_w.astype(dt))
        return jnp.einsum("bsf,fd->bsd", between(h), down_w.astype(dt))
    with mock.patch.object(dropless_moe, "_relu2_grouped",
                           _grouped_form(between)), \
            mock.patch.object(nemotron_h, "_relu2", shared):
        yield family


def silu_gate_for_relu2(family):
    """A silu gate on the up-projection in place of the squared ReLU:
    SwiGLU's form with the one matrix there is, silu(u) * u."""
    return _experts_between(family, lambda u: jax.nn.silu(u) * u)


def relu_without_the_square(family):
    return _experts_between(family, jax.nn.relu)


@contextlib.contextmanager
def route_scale_left_out(family):
    kept = family.cfg
    family.cfg = dataclasses.replace(kept, route_scale=1.0)
    try:
        yield family
    finally:
        family.cfg = kept


@contextlib.contextmanager
def weights_not_normed(family):
    """The chosen scores as they are, not over their sum (times the
    scale still)."""
    moe = nemotron_h.NemotronHConfig.moe.fget
    with mock.patch.object(
            nemotron_h.NemotronHConfig, "moe",
            property(lambda self: dataclasses.replace(moe(self),
                                                      route_norm=False))):
        yield family


@contextlib.contextmanager
def one_group_for_all_heads(family):
    """The first group's B and C given to every head of the scan."""
    scan = ssd.ssd_scan

    def broken(x, dt, A, B, C, D, **kwargs):
        return scan(x, dt, A, jnp.broadcast_to(B[:, :, :1], B.shape),
                    jnp.broadcast_to(C[:, :, :1], C.shape), D, **kwargs)
    with mock.patch.object(ssd, "ssd_scan", broken):
        yield family


@contextlib.contextmanager
def norm_over_the_whole_width(family):
    """The gated norm's mean square over all the inner channels at
    once."""
    def whole(y, z, scale, cfg, groups):
        return granite_hybrid._gate_norm(y, z, scale, cfg)
    with mock.patch.object(granite_hybrid, "_gate_norm_grouped", whole):
        yield family


@contextlib.contextmanager
def norm_before_the_gate(family):
    def before(y, z, scale, cfg, groups):
        split = (*y.shape[:-1], groups, y.shape[-1] // groups)
        normed = granite_hybrid._norm(
            y.reshape(split), scale.reshape(split[-2:]), cfg)
        return normed.reshape(y.shape) * jax.nn.silu(z)
    with mock.patch.object(granite_hybrid, "_gate_norm_grouped", before):
        yield family


@contextlib.contextmanager
def state_in_bfloat16(family):
    """The state a chunk hands on is rounded to bfloat16 (the `jnp` form
    of the scan, which is what the patch reaches)."""
    chunk = ssd._chunk

    def broken(*args):
        y, state = chunk(*args)
        return y, _as_bfloat16(state)
    with mock.patch.object(ssd, "ssd_scan",
                           functools.partial(ssd.ssd_scan, impl="jnp")), \
            mock.patch.object(ssd, "_chunk", broken):
        yield family


@contextlib.contextmanager
def rotary_positions_applied(family):
    """Queries and keys turned by `rope_theta`, which the config states
    and the family's attention does not use."""
    qkv = granite_hybrid._qkv

    def turned(x, lp, cfg):
        q, k, v = qkv(x, lp, cfg)
        return _rope(q, 10000.0), _rope(k, 10000.0), v
    with mock.patch.object(granite_hybrid, "_qkv", turned):
        yield family


@contextlib.contextmanager
def expert_products_in_float8(family):
    """The operands of the routed experts' two products (the rows, the
    hidden activations, the weights) rounded to float8's three mantissa
    bits (e4m3) at bfloat16's range: the nearest precision below the
    bfloat16 the cell states."""
    def float8(x):
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)
    with mock.patch.object(dropless_moe, "_relu2_grouped",
                           _grouped_form(operand=float8)):
        yield family


@contextlib.contextmanager
def last_columns_dropped(family):
    """The last 64 columns of every routed expert's width add nothing:
    what tiles of 128 would leave of 1856 = 14.5 x 128 (at tiny widths the
    last eighth of the width)."""
    def cut(u):
        width = u.shape[-1]
        keep = width - (64 if width > 64 else max(width // 8, 1))
        return jnp.where(jnp.arange(width) < keep, _relu2(u), 0)
    with mock.patch.object(dropless_moe, "_relu2_grouped",
                           _grouped_form(cut)):
        yield family


@contextlib.contextmanager
def softmax_stats_in_bfloat16(family):
    """The flash kernels' running maximum and sum rounded to bfloat16
    after every tile (as `mellum_variants.py` has it)."""
    step = flash_attention._online_step

    def rounded(*args, **kwargs):
        m, l, acc = step(*args, **kwargs)

        def bf16(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return bf16(m), bf16(l), acc
    with mock.patch.object(flash_attention, "_online_step", rounded):
        yield family


VARIANTS = {
    "silu_gate_for_relu2": silu_gate_for_relu2,
    "relu_without_the_square": relu_without_the_square,
    "route_scale_left_out": route_scale_left_out,
    "weights_not_normed": weights_not_normed,
    "one_group_for_all_heads": one_group_for_all_heads,
    "norm_over_the_whole_width": norm_over_the_whole_width,
    "norm_before_the_gate": norm_before_the_gate,
    "state_in_bfloat16": state_in_bfloat16,
    "rotary_positions_applied": rotary_positions_applied,
    "expert_products_in_float8": expert_products_in_float8,
    "last_columns_dropped": last_columns_dropped,
    "softmax_stats_in_bfloat16": softmax_stats_in_bfloat16,
}
# Round where the configuration states a precision: told on the chip by
# the family's own numbers (scan_rel_tol, experts_rel_tol, attn_row_tol)
# and not by the cell's three limits.
ONLY_ROUNDING = ("state_in_bfloat16", "expert_products_in_float8",
                 "softmax_stats_in_bfloat16")
