"""The joyai program broken in ten ways, each of which the cell's
`correct` has to catch (ISSUE 50).  A variant is a context manager over a
family: inside it `family.loss`, and what `family.reference_loss` runs of
the program, are the broken program's; the reference stays what it is.

Two are built by an option of the program (the prediction module's loss
left out, the route scale left at 1); the others need its code patched,
which is done here and nowhere in the program.  Four patch `joyai._qkv`
with `_broken_qkv`, the program's own lines with one of them changed.
`router_bias_in_weights` needs a bias that is not zero: it lays one, a
CONSTANT, over the tree that program and reference are both given (no
gradient is asked of it), and patches the program to read its weights
from the biased scores; `bias_as_it_should_be` lays the same bias and
patches nothing, and has to pass.  Two only ROUND where the configuration
states a precision (`ONLY_ROUNDING`): the router's scores in bfloat16
(told by `router_rel_tol`) and the softmax's statistics in bfloat16
(`attn_rel_tol`).  `mtp_fed_the_normed_hidden_state` is told on seeded
weights by `mtp_state_tol` alone (`benchmark/families/joyai.py` says
why).
Used by the tests at tiny widths (`tests/test_joyai.py`,
`benchmark/tests/test_joyai.py`) and by `tools/reference_check.py` at the
published widths on the chip.
"""

import contextlib
import dataclasses
import functools
import math
from unittest import mock

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.models import joyai
from byteps_tpu.models.transformer import _rms_norm, _rope
from byteps_tpu.ops import flash_attention
from byteps_tpu.parallel import dropless_moe


@contextlib.contextmanager
def _option(family, **changes):
    kept = family.cfg
    family.cfg = dataclasses.replace(kept, **changes)
    try:
        yield family
    finally:
        family.cfg = kept


def mtp_loss_left_out(family):
    """The training loss is the main head's alone."""
    return _option(family, mtp_loss_weight=0.0)


def route_scale_left_out(family):
    return _option(family, route_scale=1.0)


def _broken_qkv(x, lp, cfg, key_per_head=False, kv_norm=True,
                scale_by_nope=False, v_from_the_rotary_part=False):
    """`joyai._qkv` with one line of it changed (the defaults are the
    program's)."""
    dt = cfg.dtype
    B, S, D = x.shape
    H, nope, rope = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    norm = functools.partial(_rms_norm, bias=None, eps=cfg.rms_norm_eps)
    a = norm(x, lp["input_ln"])
    down = jnp.einsum("bsd,de->bse", a, lp["down_w"].astype(dt))
    cq, ckv, kr = jnp.split(
        down, [cfg.q_lora_rank, cfg.q_lora_rank + cfg.kv_lora_rank], axis=-1)

    def heads(t):
        return t.reshape(B, S, H, -1).transpose(0, 2, 1, 3)
    q = heads(jnp.einsum("bsr,re->bse", norm(cq, lp["q_a_ln"]),
                         lp["q_up_w"].astype(dt)))
    if kv_norm:
        ckv = norm(ckv, lp["kv_a_ln"])
    kv = heads(jnp.einsum("bsr,re->bse", ckv, lp["kv_up_w"].astype(dt)))
    qr = _rope(q[..., nope:], cfg.rope_theta)
    kr = jnp.broadcast_to(kr[:, None], (B, H, S, rope))
    if key_per_head:
        # head h's rotary key is the token's with its lanes moved on by h
        kr = jnp.stack([jnp.roll(kr[:, h], h, axis=-1) for h in range(H)], 1)
    kr = _rope(kr, cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], qr], axis=-1)
    if scale_by_nope:
        q = q * jnp.asarray(math.sqrt((nope + rope) / nope), q.dtype)
    k = jnp.concatenate([kv[..., :nope], kr], axis=-1)
    v = kv[..., nope:]
    if v_from_the_rotary_part:
        # a head's [kN | kR | v] read for v at `nope`, not `nope + rope`
        v = jnp.concatenate([kr, v], axis=-1)[..., :v.shape[-1]]
    return q, k, v


@contextlib.contextmanager
def _qkv(family, **broken):
    with mock.patch.object(joyai, "_qkv",
                           functools.partial(_broken_qkv, **broken)):
        yield family


def rotary_key_per_head(family):
    """Every head a rotary key of its own, not the one the token has."""
    return _qkv(family, key_per_head=True)


def kv_norm_left_out(family):
    """No RMS norm in the middle of the key-value chain."""
    return _qkv(family, kv_norm=False)


def scale_by_the_nope_width(family):
    """Logits over sqrt(128), the part without positions, not sqrt(192)."""
    return _qkv(family, scale_by_nope=True)


def v_with_the_rotary_part_in_it(family):
    """A value read from where the rotary key lies beside it: its first
    64 lanes are the rotary key's."""
    return _qkv(family, v_from_the_rotary_part=True)


@contextlib.contextmanager
def mtp_fed_the_normed_hidden_state(family):
    """The prediction module reads the main stack's hidden state AFTER
    its final norm."""
    mtp = joyai._mtp

    def normed(params, h, next_tokens, sel, cfg):
        h = _rms_norm(h, params["final_ln"], None, eps=cfg.rms_norm_eps)
        return mtp(params, h, next_tokens, sel, cfg)
    with mock.patch.object(joyai, "_mtp", normed):
        yield family


def _bias(n, layers):
    """A bias of a tenth of a score's range, the same whenever asked."""
    return 0.1 * jax.random.normal(jax.random.key(50), (layers, n),
                                   jnp.float32)


@contextlib.contextmanager
def bias_as_it_should_be(family):
    """No variant: a bias that is not zero laid over the tree as a
    CONSTANT, for program and reference alike.  Has to pass."""
    def with_bias(fn):
        def wrapped(params, batch):
            E = family.cfg.num_experts
            params = dict(params)
            if "moe" in params:
                n = params["moe"]["router_w"].shape[0]
                params["moe"] = {**params["moe"],
                                 "expert_bias": _bias(E, n + 1)[:n]}
            if "mtp" in params:
                params["mtp"] = {**params["mtp"],
                                 "expert_bias": _bias(E, 8)[-1]}
            return fn(params, batch)
        return wrapped
    kept = family.loss, family.reference_loss
    family.loss, family.reference_loss = map(with_bias, kept)
    try:
        yield family
    finally:
        del family.loss, family.reference_loss


@contextlib.contextmanager
def router_bias_in_weights(family):
    """The weights read from the scores WITH the bias, which is the
    choice's alone."""
    def route(x, router_w, cfg, expert_bias=None, sel=None):
        with jax.default_matmul_precision("highest"):
            scores = jax.nn.sigmoid(
                x.astype(jnp.float32) @ router_w.astype(jnp.float32))
        if expert_bias is not None:
            scores = scores + lax.stop_gradient(expert_bias)
        if sel is None:
            _, sel = lax.top_k(lax.stop_gradient(scores), cfg.top_k)
        weights = jnp.take_along_axis(scores, sel, axis=-1)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        return sel, weights * cfg.route_scale
    with bias_as_it_should_be(family), \
            mock.patch.object(dropless_moe, "route", route):
        yield family


@contextlib.contextmanager
def router_scores_in_bfloat16(family):
    """Scores from a bfloat16 product, sigmoid in bfloat16; the top-k and
    the weights from those (as `afmoe_variants.py` has it)."""
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
    "rotary_key_per_head": rotary_key_per_head,
    "kv_norm_left_out": kv_norm_left_out,
    "scale_by_the_nope_width": scale_by_the_nope_width,
    "v_with_the_rotary_part_in_it": v_with_the_rotary_part_in_it,
    "router_bias_in_weights": router_bias_in_weights,
    "mtp_fed_the_normed_hidden_state": mtp_fed_the_normed_hidden_state,
    "mtp_loss_left_out": mtp_loss_left_out,
    "route_scale_left_out": route_scale_left_out,
    "router_scores_in_bfloat16": router_scores_in_bfloat16,
    "softmax_stats_in_bfloat16": softmax_stats_in_bfloat16,
}
BUILT_BY_AN_OPTION = ("mtp_loss_left_out", "route_scale_left_out")
# Round where the configuration states a precision: told on the chip by
# the family's own numbers (router_rel_tol; attn_rel_tol).
ONLY_ROUNDING = ("router_scores_in_bfloat16", "softmax_stats_in_bfloat16")
# What reaches a flash kernel alone: nothing to tell at tiny widths under
# dense attention.
NEEDS_FLASH = ("softmax_stats_in_bfloat16",)
