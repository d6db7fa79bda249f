"""The kimi_linear program broken in twenty-one ways, each of which the
cell's `correct` has to catch (ISSUE 57 names nineteen; the convolution's
sums and the softmax's statistics in bfloat16 are the controls
`conv_rel_tol` and `attn_rel_tol` are set against).  A variant is a
context manager over a family: inside it `family.loss`, and what
`family.reference_loss` runs of the program, are the broken program's; the
reference stays what it is.

Two are built by an option of the program (the weights not normed, the
route scale left at 1); the others need its code patched, which is done
here and nowhere in the program.  The scan's own (`kimi_linear._scan`):
three put a position-by-position recurrence with one line changed in its
place (`_recurrence`: the decay after the correction, q and k not normed;
and unchanged it is the program up to rounding, `recurrence_as_it_is`,
which has to pass), two the chunked `jnp` form with one thing changed (the
state rounded to bfloat16 between chunks; the pairwise decays factored as
`exp(G) * exp(-G)`, which overflows), one scales the kernels' result (q
unscaled).  The gates, the convolution, the gated norm and the latent
attention's operands are the program's own lines with one changed.  The
router's three are `lfm2_variants.py`'s, which patch `dropless_moe.route`
whatever the model.  Four only ROUND where the configuration states a precision
(`ONLY_ROUNDING`): told by `kda_rel_tol`, `router_rel_tol`,
`conv_rel_tol`, `attn_rel_tol`.
Used by the tests at tiny widths (`benchmark/tests/test_kimilinear.py`)
and by `tools/reference_check.py` at the published widths on the chip.
"""

import contextlib
import functools
import math
from unittest import mock

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.tests.lfm2_variants import (  # noqa: F401
    _option, bias_as_it_should_be, router_bias_in_weights,
    router_scores_in_bfloat16, softmax_stats_in_bfloat16, weights_not_normed)
from byteps_tpu.models import kimi_linear
from byteps_tpu.models.transformer import _rope
from byteps_tpu.ops import kda, ssd

_F32 = jnp.float32


def route_scale_left_out(family):
    return _option(family, route_scale=1.0)


# ---------------------------------------------------------------------------
# The scan
# ---------------------------------------------------------------------------
def _recurrence(q, k, v, g, beta, normed=True, decay_after=False,
                block=64, head_block=8):
    """`kimi_linear._scan`'s arguments and result, a position at a time in
    float32, with one thing changed (the defaults are the program's
    arithmetic).  For memory at the cell's length: `head_block` heads at
    a time and blocks of `block` positions, each rematerialised."""
    B, S, W = q.shape
    H = beta.shape[-1]
    K, hb = W // H, min(head_block, H)

    def position(state, xs):
        q, k, v, g, b = xs
        decay = jnp.exp(g)[..., None]
        if not decay_after:
            state = decay * state
        seen = jnp.einsum("bhk,bhkv->bhv", k, state)
        state = state + (b[..., None] * k)[..., None] * (v - seen)[..., None,
                                                                   :]
        if decay_after:
            state = decay * state
        return state, jnp.einsum("bhk,bhkv->bhv", q, state)

    @jax.checkpoint
    def rows(state, xs):
        return lax.scan(position, state, xs)

    @jax.checkpoint
    def some(first):
        def heads(t):                   # [B, S, H w] -> [S, B, hb, w]
            mine = lax.dynamic_slice_in_dim(t.reshape(B, S, H, -1), first,
                                            hb, axis=2)
            return jnp.moveaxis(mine.astype(_F32), 1, 0)
        qh, kh, vh, gh, bh = map(heads, (q, k, v, g, beta))
        if normed:
            qh, kh = kda._normed(qh, kh)
        else:
            qh = qh / math.sqrt(K)

        def cut(t):
            return t.reshape(S // block, block, *t.shape[1:])
        _, o = lax.scan(rows, jnp.zeros((B, hb, K, vh.shape[-1]), _F32),
                        tuple(map(cut, (qh, kh, vh, gh, bh[..., 0]))))
        return o.reshape(S, B, hb, -1)

    with jax.default_matmul_precision("highest"):
        o = lax.map(some, jnp.arange(0, H, hb))     # [H / hb, S, B, hb, V]
    o = jnp.moveaxis(o, 0, 2).reshape(S, B, -1)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


@contextlib.contextmanager
def _scan(family, fn):
    with mock.patch.object(kimi_linear, "_scan", fn):
        yield family


def recurrence_as_it_is(family):
    """No variant: the recurrence unchanged in the scan's place.  Has to
    pass."""
    return _scan(family, _recurrence)


def decay_after_the_correction(family):
    """S_t = Diag(exp(g_t)) ((I - beta k k^T) S_{t-1} + beta k v^T): the
    new pair decays before it is ever read."""
    return _scan(family, functools.partial(_recurrence, decay_after=True))


def qk_not_normed(family):
    """q and k as the convolution left them (q still over sqrt(K))."""
    return _scan(family, functools.partial(_recurrence, normed=False))


def q_unscaled(family):
    """No 1 / sqrt(K) on the queries."""
    def scan(q, k, v, g, beta):
        K = q.shape[-1] // beta.shape[-1]
        o = kda.kda_scan(q, k, v, g, beta)
        return (o.astype(_F32) * math.sqrt(K)).astype(o.dtype)
    return _scan(family, scan)


def state_in_bfloat16(family):
    """The state a chunk hands the next rounded to bfloat16 (the nearest
    precision below the float32 the configuration states for it)."""
    def chunk(q, k, v, g, beta, state):
        o, state = kda._chunk_jnp(q, k, v, g, beta, state)
        return o, lax.reduce_precision(state, 8, 7)
    return _scan(family, functools.partial(kda.kda_scan_jnp, chunk_fn=chunk))


def _factored_pairwise(a, k, G, dtype):
    """`kda._pairwise_jnp` by exp(G_r) * exp(-G_j): the second factor
    overflows where a chunk decays far."""
    C = a.shape[0]
    keep = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
    return jnp.where(keep, ((a * jnp.exp(G)).astype(dtype)
                            @ (k * jnp.exp(-G)).astype(dtype).T).astype(_F32),
                     0.0)


@contextlib.contextmanager
def pairwise_decays_factored(family):
    with mock.patch.object(kda, "_pairwise_jnp", _factored_pairwise), \
            _scan(family, kda.kda_scan_jnp):
        yield family


# ---------------------------------------------------------------------------
# Round the scan: gates, convolution, gated norm
# ---------------------------------------------------------------------------
def one_decay_a_head(family):
    """The scalar-gated delta rule: a head's channels all decay at their
    mean rate."""
    decay = kimi_linear._decay

    def scalar(f, lp, cfg):
        g = decay(f, lp, cfg)
        per = g.reshape(*g.shape[:-1], cfg.kda_heads, cfg.kda_head_dim)
        return jnp.broadcast_to(per.mean(-1, keepdims=True),
                                per.shape).reshape(g.shape)
    return mock.patch.object(kimi_linear, "_decay", scalar)


def softplus_left_out(family):
    """g = -exp(A_log) (f + dt_bias): no softplus, so no sign."""
    def raw(f, lp, cfg):
        rate = jnp.repeat(jnp.exp(lp["A_log"].astype(_F32)),
                          cfg.kda_head_dim)
        return -rate * (f.astype(_F32) + lp["dt_bias"].astype(_F32))
    return mock.patch.object(kimi_linear, "_decay", raw)


def beta_left_out(family):
    """beta = 1: every position overwrites what its key reads."""
    gates = kimi_linear._gates

    def ones(u, lp, cfg):
        g, beta, z = gates(u, lp, cfg)
        return g, jnp.ones_like(beta), z
    return mock.patch.object(kimi_linear, "_gates", ones)


def _broken_conv(qkv, taps, cfg, silu=True, across=False, rounded=False):
    """`kimi_linear._conv` as jnp operations (the kernel's oracle,
    `ssd.causal_conv1d` and a silu) with one thing changed."""
    shape = qkv.shape
    if across:
        # the sequence read as the second half of one twice as long: its
        # first three positions read the rows before them
        qkv = qkv.reshape(1, shape[0] * shape[1], shape[2])
    if rounded:
        # every product and every partial sum rounded to bfloat16, by
        # `reduce_precision`: a cast the chip's compiler is free to drop
        def bf16(t):
            return lax.reduce_precision(t, 8, 7)
        K, S = taps.shape[0], qkv.shape[1]
        padded = jnp.pad(qkv.astype(_F32), ((0, 0), (K - 1, 0), (0, 0)))
        y = None
        for k in range(K):
            term = bf16(lax.slice_in_dim(padded, k, k + S, axis=1)
                        * bf16(taps[k].astype(_F32)))
            y = term if y is None else bf16(y + term)
    else:
        y = ssd.causal_conv1d(qkv.astype(_F32), taps)
    if silu:
        y = jax.nn.silu(y)
    return tuple(jnp.split(y.astype(qkv.dtype).reshape(shape), 3, axis=-1))


def conv_summed_in_bfloat16(family):
    """The convolution's products and sums in bfloat16 (the nearest
    precision below the float32 the configuration states)."""
    return mock.patch.object(
        kimi_linear, "_conv", functools.partial(_broken_conv, rounded=True))


def silu_left_out(family):
    return mock.patch.object(
        kimi_linear, "_conv", functools.partial(_broken_conv, silu=False))


def tap_across_a_sequences_start(family):
    """The batch's sequences convolved as one (`_conv_alone` lays the
    cell's one sequence out as two, so that this shows)."""
    return mock.patch.object(
        kimi_linear, "_conv", functools.partial(_broken_conv, across=True))


def _broken_gate_norm(o, z, scale, cfg, silu=False, gate_first=False):
    B, S, W = o.shape
    gate = (jax.nn.silu if silu else jax.nn.sigmoid)(z)

    def normed(t):
        heads = t.reshape(B, S, cfg.kda_heads, cfg.kda_head_dim)
        return kimi_linear._norm(heads, scale, cfg).reshape(B, S, W)
    return normed(o * gate) if gate_first else normed(o) * gate


def output_gate_a_silu(family):
    return mock.patch.object(
        kimi_linear, "_gate_norm",
        functools.partial(_broken_gate_norm, silu=True))


def gate_before_the_head_norm(family):
    """Mamba-2's gated norm, not this model's."""
    return mock.patch.object(
        kimi_linear, "_gate_norm",
        functools.partial(_broken_gate_norm, gate_first=True))


# ---------------------------------------------------------------------------
# Latent attention
# ---------------------------------------------------------------------------
def _broken_qkv(x, lp, cfg, rotary=False, scale_by_nope=False,
                key_per_head=False):
    """`kimi_linear._qkv` with one line of it changed (the defaults are
    the program's)."""
    dt = cfg.dtype
    B, S, _ = x.shape
    H, nope, rope = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    u = kimi_linear._norm(x, lp["input_ln"], cfg)

    def heads(t):
        return t.reshape(B, S, H, -1).transpose(0, 2, 1, 3)
    q = heads(jnp.einsum("bsd,de->bse", u, lp["q_w"].astype(dt)))
    down = jnp.einsum("bsd,de->bse", u, lp["down_w"].astype(dt))
    c, kr = down[..., :cfg.kv_lora_rank], down[..., cfg.kv_lora_rank:]
    kv = heads(jnp.einsum(
        "bsr,re->bse", kimi_linear._norm(c, lp["kv_a_ln"], cfg),
        lp["kv_up_w"].astype(dt)))
    kr = jnp.broadcast_to(kr[:, None], (B, H, S, rope))
    if key_per_head:
        # head h's shared key part is the token's with its lanes moved on
        kr = jnp.stack([jnp.roll(kr[:, h], h, axis=-1) for h in range(H)], 1)
    if rotary:
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], 10000.0)], axis=-1)
        kr = _rope(kr, 10000.0)
    if scale_by_nope:
        q = q * jnp.asarray(math.sqrt((nope + rope) / nope), q.dtype)
    return q, jnp.concatenate([kv[..., :nope], kr], axis=-1), kv[..., nope:]


def _qkv(family, **broken):
    return mock.patch.object(kimi_linear, "_qkv",
                             functools.partial(_broken_qkv, **broken))


def rotary_turns_in_the_mla_layer(family):
    """`rope_theta` read after all: positions turn the 64-wide parts."""
    return _qkv(family, rotary=True)


def scale_by_the_nope_width(family):
    """Logits over sqrt(128), not sqrt(192)."""
    return _qkv(family, scale_by_nope=True)


def shared_key_per_head(family):
    """Every head a 64-wide key part of its own, not the one a token
    has."""
    return _qkv(family, key_per_head=True)


VARIANTS = {
    "one_decay_a_head": one_decay_a_head,
    "decay_after_the_correction": decay_after_the_correction,
    "beta_left_out": beta_left_out,
    "softplus_left_out": softplus_left_out,
    "qk_not_normed": qk_not_normed,
    "q_unscaled": q_unscaled,
    "state_in_bfloat16": state_in_bfloat16,
    "pairwise_decays_factored": pairwise_decays_factored,
    "silu_left_out": silu_left_out,
    "tap_across_a_sequences_start": tap_across_a_sequences_start,
    "output_gate_a_silu": output_gate_a_silu,
    "gate_before_the_head_norm": gate_before_the_head_norm,
    "rotary_turns_in_the_mla_layer": rotary_turns_in_the_mla_layer,
    "scale_by_the_nope_width": scale_by_the_nope_width,
    "shared_key_per_head": shared_key_per_head,
    "weights_not_normed": weights_not_normed,
    "route_scale_left_out": route_scale_left_out,
    "router_bias_in_weights": router_bias_in_weights,
    "router_scores_in_bfloat16": router_scores_in_bfloat16,
    "conv_summed_in_bfloat16": conv_summed_in_bfloat16,
    "softmax_stats_in_bfloat16": softmax_stats_in_bfloat16,
}
BUILT_BY_AN_OPTION = ("weights_not_normed", "route_scale_left_out")
# Round where the configuration states a precision: told on the chip by
# the family's own numbers (kda_rel_tol; router_rel_tol).
ONLY_ROUNDING = ("state_in_bfloat16", "router_scores_in_bfloat16",
                 "conv_summed_in_bfloat16", "softmax_stats_in_bfloat16")
# What reaches a flash kernel alone: nothing to tell at tiny widths, where
# the interpreter's float32 sums leave bfloat16 statistics inside the limit.
NEEDS_THE_CHIP = ("softmax_stats_in_bfloat16",)
