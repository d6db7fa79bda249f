"""The lfm2 program broken in thirteen ways, each of which the cell's
`correct` has to catch (ISSUE 55 names twelve; the softmax's statistics in
bfloat16 is the control `attn_rel_tol` is set against).  A variant is a
context manager over a family: inside it `family.loss`, and what
`family.reference_loss` runs of the program, are the broken program's; the
reference stays what it is.

One is built by an option of the program (the weights not normed); the
others need its code patched, which is done here and nowhere in the
program.  Six patch `lfm2._gated_conv` with `_broken_conv`, the operator
as jnp operations with one thing about it changed; two patch `lfm2._qkv`
with `_broken_qkv`.  `router_bias_in_weights` needs a bias that is not
zero: it lays one, a CONSTANT, over the tree that program and reference
are both given, and patches the program to read its weights from the
biased scores; `bias_as_it_should_be` lays the same bias and patches
nothing, and has to pass.  Three only ROUND where the configuration states
a precision (`ONLY_ROUNDING`): the convolution's products and sums in
bfloat16 (told by `conv_rel_tol`), the router's scores in bfloat16
(`router_rel_tol`) and the flash kernels' statistics in bfloat16
(`attn_rel_tol`).  `rotary_before_the_norms` is told on seeded weights by
`qk_rel_tol` alone at the cell's tolerances (`benchmark/families/lfm2.py`
says why).
Used by the tests at tiny widths (`tests/test_lfm2.py`,
`benchmark/tests/test_lfm2.py`) and by `tools/reference_check.py` at the
published widths on the chip.
"""

import contextlib
import dataclasses
import functools
from unittest import mock

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.models import lfm2
from byteps_tpu.models.transformer import _rope
from byteps_tpu.ops import flash_attention, ssd
from byteps_tpu.parallel import dropless_moe


@contextlib.contextmanager
def _option(family, **changes):
    kept = family.cfg
    family.cfg = dataclasses.replace(kept, **changes)
    try:
        yield family
    finally:
        family.cfg = kept


def weights_not_normed(family):
    """A token's weights are its chosen scores as they are."""
    return _option(family, route_norm=False)


def _broken_conv(bcx, taps, reverse=False, across=False, gate_b=True,
                 gate_c=True, silu=False, rounded=False):
    """`C * conv(B * X)` as jnp operations (the kernel's oracle,
    `ssd.causal_conv1d` between two products) with one thing changed (the
    defaults are the program's arithmetic)."""
    shape, out_dtype = bcx.shape, bcx.dtype
    if across:
        # the batch's sequences laid end to end as ONE
        bcx = bcx.reshape(1, shape[0] * shape[1], shape[2])
    b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    if not rounded:
        u = b * x if gate_b else x
        if reverse:
            # tap k meets position t + 2 - k: the sequence read backwards
            z = ssd.causal_conv1d(u[:, ::-1], taps)[:, ::-1]
        else:
            z = ssd.causal_conv1d(u, taps)
        if silu:
            z = jax.nn.silu(z)
        y = c * z if gate_c else z
    else:
        # every product and every partial sum rounded to bfloat16, by
        # `reduce_precision`: a cast the chip's compiler is free to drop
        def bf16(t):
            return lax.reduce_precision(t, 8, 7)
        K, S = taps.shape[0], b.shape[1]
        padded = jnp.pad(bf16(b * x), ((0, 0), (K - 1, 0), (0, 0)))
        z = None
        for k in range(K):
            term = bf16(lax.slice_in_dim(padded, k, k + S, axis=1)
                        * bf16(taps[k].astype(jnp.float32)))
            z = term if z is None else bf16(z + term)
        y = c * z
    return y.astype(out_dtype).reshape(*shape[:2], -1)


@contextlib.contextmanager
def _conv(family, **broken):
    with mock.patch.object(lfm2, "_gated_conv",
                           functools.partial(_broken_conv, **broken)):
        yield family


def taps_reversed(family):
    """The taps reach FORWARD in time: not causal."""
    return _conv(family, reverse=True)


def tap_across_a_sequences_start(family):
    """The batch's sequences convolved as one: a sequence's first two
    positions read the one before it."""
    return _conv(family, across=True)


def gate_b_left_out(family):
    return _conv(family, gate_b=False)


def gate_c_left_out(family):
    return _conv(family, gate_c=False)


def silu_after_the_convolution(family):
    """Mamba's convolution, not this model's."""
    return _conv(family, silu=True)


def conv_summed_in_bfloat16(family):
    """The operator's products and sums in bfloat16 (the nearest
    precision below the float32 the configuration states)."""
    return _conv(family, rounded=True)


def _broken_qkv(x, lp, cfg, norms=True, rotary_first=False):
    """`lfm2._qkv` with one line of it changed (the defaults are the
    program's)."""
    B, S, _ = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    u = lfm2._norm(x, lp["operator_norm"], cfg)
    qkv = jnp.einsum("bsd,de->bse", u, lp["qkv_w"].astype(cfg.dtype))
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)

    def heads(t):
        return t.reshape(B, S, -1, Dh).transpose(0, 2, 1, 3)
    q, k = heads(q), heads(k)
    if rotary_first:
        q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    if norms:
        q = lfm2._norm(q, lp["q_norm"], cfg)
        k = lfm2._norm(k, lp["k_norm"], cfg)
    if not rotary_first:
        q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    return q, k, heads(v)


@contextlib.contextmanager
def _qkv(family, **broken):
    with mock.patch.object(lfm2, "_qkv",
                           functools.partial(_broken_qkv, **broken)):
        yield family


def qk_norms_left_out(family):
    return _qkv(family, norms=False)


def rotary_before_the_norms(family):
    """On seeded weights the norms' scales are 1 and the values are the
    program's: what differs is the scales' own gradient."""
    return _qkv(family, rotary_first=True)


def _bias(n, layers):
    """A bias of a tenth of a score's range, the same whenever asked."""
    return 0.1 * jax.random.normal(jax.random.key(55), (layers, n),
                                   jnp.float32)


@contextlib.contextmanager
def bias_as_it_should_be(family):
    """No variant: a bias that is not zero laid over the tree as a
    CONSTANT, for program and reference alike.  Has to pass."""
    def with_bias(fn):
        def wrapped(params, batch):
            E = family.cfg.num_experts
            groups, seen = [], 0
            for group in params["layers"]:
                if "router_w" in group:
                    n = group["router_w"].shape[0]
                    group = {**group,
                             "expert_bias": _bias(E, 64)[seen:seen + n]}
                    seen += n
                groups.append(group)
            return fn({**params, "layers": groups}, batch)
        return wrapped
    kept = family.loss, family.reference_loss
    family.loss, family.reference_loss = map(with_bias, kept)
    try:
        yield family
    finally:
        del family.loss, family.reference_loss


def _route_with(scores_of, biased_weights=False, softmax=False):
    """`dropless_moe.route` written out, its scores `scores_of(x, w)`."""
    def route(x, router_w, cfg, expert_bias=None, sel=None):
        scores = scores_of(x, router_w)
        if softmax:
            scores = jax.nn.softmax(scores, axis=-1)
        else:
            scores = jax.nn.sigmoid(scores)
        scores = scores.astype(jnp.float32)
        biased = scores if expert_bias is None else (
            scores + lax.stop_gradient(expert_bias))
        if sel is None:
            _, sel = lax.top_k(lax.stop_gradient(biased), cfg.top_k)
        weights = jnp.take_along_axis(
            biased if biased_weights else scores, sel, axis=-1)
        if cfg.route_norm:
            weights = weights / (weights.sum(-1, keepdims=True)
                                 + cfg.norm_eps)
        return sel, weights * cfg.route_scale
    return route


def _float32_logits(x, router_w):
    with jax.default_matmul_precision("highest"):
        return x.astype(jnp.float32) @ router_w.astype(jnp.float32)


@contextlib.contextmanager
def router_bias_in_weights(family):
    """The weights read from the scores WITH the bias, which is the
    choice's alone."""
    with bias_as_it_should_be(family), mock.patch.object(
            dropless_moe, "route",
            _route_with(_float32_logits, biased_weights=True)):
        yield family


@contextlib.contextmanager
def softmax_router(family):
    """Scores from a softmax over the experts, not a sigmoid each."""
    with mock.patch.object(dropless_moe, "route",
                           _route_with(_float32_logits, softmax=True)):
        yield family


@contextlib.contextmanager
def router_scores_in_bfloat16(family):
    """Scores from a bfloat16 product, sigmoid in bfloat16; the top-k and
    the weights from those (as `afmoe_variants.py` has it)."""
    with mock.patch.object(dropless_moe, "route", _route_with(
            lambda x, w: x.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16))):
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
    "taps_reversed": taps_reversed,
    "tap_across_a_sequences_start": tap_across_a_sequences_start,
    "gate_b_left_out": gate_b_left_out,
    "gate_c_left_out": gate_c_left_out,
    "silu_after_the_convolution": silu_after_the_convolution,
    "qk_norms_left_out": qk_norms_left_out,
    "rotary_before_the_norms": rotary_before_the_norms,
    "router_bias_in_weights": router_bias_in_weights,
    "weights_not_normed": weights_not_normed,
    "softmax_router": softmax_router,
    "conv_summed_in_bfloat16": conv_summed_in_bfloat16,
    "router_scores_in_bfloat16": router_scores_in_bfloat16,
    "softmax_stats_in_bfloat16": softmax_stats_in_bfloat16,
}
BUILT_BY_AN_OPTION = ("weights_not_normed",)
# Round where the configuration states a precision: told on the chip by
# the family's own numbers (conv_rel_tol; router_rel_tol).
ONLY_ROUNDING = ("conv_summed_in_bfloat16", "router_scores_in_bfloat16",
                 "softmax_stats_in_bfloat16")
