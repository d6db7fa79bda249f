"""The afmoe program broken in seven ways, each of which the cell's
`correct` has to catch (ISSUE 29, Tentpole 3).  A variant is a context
manager over a family: inside it `family.loss` and the routing that
`family.reference_loss` asks the program for are the broken program's;
the reference stays what it is.

Three are built by an option of the program; four need its code patched,
which is done here and nowhere in the program.  Used by the tests at tiny
widths (`tests/test_afmoe.py`, `benchmark/tests/test_afmoe.py`) and by
`tools/afmoe_check.py` at the published widths on the chip.
"""

import contextlib
import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.models import afmoe
from byteps_tpu.parallel import dropless_moe


@contextlib.contextmanager
def _option(family, **changed):
    kept = family.cfg
    family.cfg = dataclasses.replace(kept, **changed)
    try:
        yield family
    finally:
        family.cfg = kept


def window_dropped(family):
    """Sliding layers attend to every earlier key."""
    return _option(family, sliding_window=1 << 30)


def top7(family):
    return _option(family, num_experts_per_tok=family.cfg.num_experts_per_tok
                   - 1)


def route_scale_left_out(family):
    return _option(family, route_scale=1.0)


@contextlib.contextmanager
def rope_in_full_layers(family):
    """Rotary positions in the full layers too."""
    attn_fn = afmoe._attn_fn

    def broken(cfg, kind):
        fn = attn_fn(cfg, kind)
        if kind == afmoe.SLIDING:
            return fn
        return lambda q, k, v: fn(afmoe._rope(q, cfg.rope_theta),
                                  afmoe._rope(k, cfg.rope_theta), v)
    with mock.patch.object(afmoe, "_attn_fn", broken):
        yield family


@contextlib.contextmanager
def gate_left_out(family):
    with mock.patch.object(afmoe, "_gated", lambda ctx, g: ctx):
        yield family


@contextlib.contextmanager
def router_in_bfloat16(family):
    """Scores from a bfloat16 product, sigmoid in bfloat16; the top-k and
    the weights from those."""
    def route(x, router_w, cfg, expert_bias=None, sel=None):
        scores = jax.nn.sigmoid(
            x.astype(jnp.bfloat16) @ router_w.astype(jnp.bfloat16)
        ).astype(jnp.float32)
        if sel is None:
            biased = scores if expert_bias is None else scores + expert_bias
            _, sel = lax.top_k(lax.stop_gradient(biased), cfg.top_k)
        weights = jnp.take_along_axis(scores, sel, axis=-1)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        return sel, weights * cfg.route_scale
    with mock.patch.object(dropless_moe, "route", route):
        yield family


@contextlib.contextmanager
def held_rows_dropped(family):
    """A buffer of half the even share and no exact path behind it: the
    rows of the held experts that sort last are dropped."""
    def nothing(k, rows, past, x, experts, flat_w, plan):
        return jnp.zeros(x.shape, jnp.float32)

    def half(self, n_tokens):
        even = n_tokens * self.top_k * len(self.held) / self.num_experts
        return max(8, int(even / 2) // 8 * 8)
    with mock.patch.object(dropless_moe, "_past_the_buffer", nothing), \
            mock.patch.object(dropless_moe.MoEConfig, "buffer_rows", half):
        yield family


VARIANTS = {
    "window_dropped": window_dropped,
    "rope_in_full_layers": rope_in_full_layers,
    "gate_left_out": gate_left_out,
    "top7": top7,
    "route_scale_left_out": route_scale_left_out,
    "router_in_bfloat16": router_in_bfloat16,
    "held_rows_dropped": held_rows_dropped,
}
BUILT_BY_AN_OPTION = ("window_dropped", "top7", "route_scale_left_out")
