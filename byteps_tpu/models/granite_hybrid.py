"""The `granitemoehybrid` decoder (IBM's Granite 4.0-H family): a language
model most of whose layers mix the sequence with a Mamba-2 state-space
scan and a few with attention (`layer_types`), here for the members with
no routed experts (`num_local_experts` 0), whose feed-forward is the
shared SwiGLU alone.

    h0 = embed[ids] * embedding_multiplier
    each layer:   x = x + residual_multiplier * mixer(rms(x; input_ln))
                  x = x + residual_multiplier * mlp(rms(x; post_ln))
    mlp(u):       [a, b] = split(u W_in, 2);  (silu(a) * b) W_out
    attention(u): q, k, v = u Wq, u Wk, u Wv, no bias and NO positions
                  (`position_embedding_type` "nope"); causal
                  softmax(q k^T * attention_multiplier) v, a key-value
                  head serving heads / kv_heads query heads; then Wo
    mamba(u):     [z, xBC, dt] = split(u W_in)
                  xBC = silu(conv1d(xBC)): depthwise, causal, with bias
                  [x, B, C] = split(xBC);  x as heads of `mamba_d_head`;
                  B, C shared by the heads of a group
                  dt = softplus(dt + dt_bias);  A = -exp(A_log)
                  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
                  y_t = S_t C_t + D x_t           (`ops/ssd.py`, chunked)
                  y = rms(y * silu(z); gate_norm), over the whole inner
                  width;  then W_out
    logits = rms(x; final_ln) embed^T / logits_scaling        (tied head)

A module beside `afmoe.py`, for that module's reason: the layers differ in
what they hold, so the stack is scanned a period of `layer_types` at a
time, and inside a period a run of layers of one kind is a `lax.scan` of
its own over the run's stacked leaves (`_stack_plan`): compile time is
that of one layer a run, whatever the depth.  Shared with
`transformer.py`, imported and not copied: `_rms_norm`, the flash adapter,
the streamed cross-entropy `fused_nll_sum`; with `ops/`: the flash kernels
and the scan.

No switches.  Attention is the flash kernels at the block their own rule
picks, every layer is rematerialised (`jax.checkpoint`), the head
and the cross-entropy are streamed `ce_chunk_rows` rows at a time and the
scan is `ops/ssd.py`'s default form: one path, the one the benchmark's
cell runs.  A sequence is a multiple of 128 positions (the flash kernels'
tiling).

What a rematerialised layer KEEPS (`KEPT_NAMES`, the policy
`save_only_these_names` of the one `jax.checkpoint` call; no option): an
attention layer the flash call's `o` and `lse`
(`flash_attention.KEPT_NAME`; 34 MB a layer and sequence of 8,192 at the
published widths), so that its recompute calls no forward kernel and the
backward kernels read what the one call wrote; a mamba layer `in_proj`'s
result before the split (`IN_PROJ_NAME`, which `_mamba` lays on it;
[8192, 8512] bfloat16, 139 MB), so that its recompute starts at the
convolution.  The runs are scanned, so a kept value is a stack over the
run's layers from the forward pass to the backward pass
(`bps_remat_kept_bytes{name}`: 1.26 GB for nine mamba layers, which the
cell's 2 GB of room holds; PERF.md, Findings, PR 49).  Everything else is
made again.  `models/nemotron_h.py` calls the same two mixers under a
policy of its own, with its router's name added.

A share of a deployment.  `layer_types` lists the layers that are run (a
pipeline stage's) and `vocab_size` the rows of the TIED embedding held
here, ids `vocab_start ...`: embedding, logits and loss are over the
slice.  With every layer and the whole vocabulary it is the whole model.

Parameters float32, compute `dtype`; the scan's dt, decays, cumulative
sums and carried state are float32 whatever `dtype` is.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..common import telemetry
from ..ops import flash_attention, short_conv, ssd
from .transformer import _rms_norm, flash_attention_fn, fused_nll_sum

PyTree = Any
MAMBA, ATTENTION = "mamba", "attention"
# The name `in_proj`'s result carries for `jax.checkpoint` (`_mamba`): a
# layer whose policy lists it starts its recompute at the convolution.
IN_PROJ_NAME = "mamba.in_proj"
# What this model's rematerialised layers keep from their forward pass, by
# name (the module's docstring says why these and no more).
KEPT_NAMES = (flash_attention.KEPT_NAME, IN_PROJ_NAME)


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int                    # rows of the tied embedding held here
    hidden_size: int
    layer_types: Tuple[str, ...]       # one entry a layer that is run
    intermediate_size: int             # the shared SwiGLU's
    num_heads: int                     # attention
    num_kv_heads: int
    head_dim: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 1.0  # in place of 1 / sqrt(head_dim)
    logits_scaling: float = 1.0
    vocab_start: int = 0               # first token id of the held slice
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16          # of the activations
    ce_chunk_rows: int = 2048          # rows a block of the streamed head

    def __post_init__(self):
        if any(t not in (MAMBA, ATTENTION) for t in self.layer_types):
            raise ValueError(f"layer_types={self.layer_types}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads={self.num_heads} not divisible by "
                             f"num_kv_heads={self.num_kv_heads}")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(f"{self.mamba_n_heads} mamba heads in "
                             f"{self.mamba_n_groups} groups")

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)


def _stack_plan(cfg: GraniteHybridConfig):
    """`(periods, [(kind, layers) of each run of one period])`: the period
    is the shortest that tiles `layer_types`, a run its consecutive layers
    of one kind.  The parameter tree holds one group of leaves a run,
    `params["layers"][i]`, stacked `[periods, layers of the run, ...]`:
    the stack is scanned a period at a time and each run scans its own
    leaves, so no leaf is ever cut or joined, in either pass.  That
    suits a list that a short period tiles (the published 4 x (5 mamba,
    attention, 4 mamba)).  A list that nothing tiles would be one period
    with a run, a group of leaves and a compiled body for every change of
    kind: `models/nemotron_h.py`, whose layers are one part each in an
    order no period tiles, stacks its leaves by KIND instead
    (`nemotron_h.layer_plan`)."""
    kinds = cfg.layer_types
    n = len(kinds)
    p = next(p for p in range(1, n + 1)
             if n % p == 0 and kinds == kinds[:p] * (n // p))
    runs = []
    for kind in kinds[:p]:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return n // p, [tuple(r) for r in runs]


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def init_params(rng: jax.Array, cfg: GraniteHybridConfig) -> PyTree:
    """Normal / sqrt(fan_in) matrices, unit norm scales, and the scan's own
    leaves as the family's initialiser makes them, so that the heads decay
    at different rates: `A_log` the log of a uniform 1-16 a head, `dt_bias`
    the inverse softplus of a log-uniform 0.001-0.1, `D` 1; the
    convolution's taps and bias uniform +-1 / sqrt(taps)."""
    dt = jnp.float32
    D, F = cfg.hidden_size, cfg.intermediate_size
    periods, runs = _stack_plan(cfg)
    keys = iter(jax.random.split(rng, 1 + 10 * len(runs)))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, dt)
                / math.sqrt(fan_in)).astype(dt)

    def conv(shape):
        # a depthwise convolution's default: uniform +-1 / sqrt(taps)
        bound = 1.0 / math.sqrt(cfg.mamba_d_conv)
        return jax.random.uniform(next(keys), shape, dt, -bound, bound)

    def common(lead):
        return {"input_ln": jnp.ones((*lead, D), dt),
                "post_ln": jnp.ones((*lead, D), dt),
                "mlp_in_w": w((*lead, D, 2 * F), D),  # [a | b] side by side
                "mlp_out_w": w((*lead, F, D), F)}

    def mamba(lead):
        H, I, C = cfg.mamba_n_heads, cfg.d_inner, cfg.conv_dim
        step = jnp.exp(jax.random.uniform(
            next(keys), (*lead, H), dt, math.log(1e-3), math.log(1e-1)))
        return {
            **common(lead),
            "in_proj_w": w((*lead, D, I + C + H), D),   # [z | xBC | dt]
            # `conv_w[k]` meets x_{t-(K-1)+k}: the model's [C, 1, K] weight
            # with the taps in front
            "conv_w": conv((*lead, cfg.mamba_d_conv, C)),
            "conv_b": conv((*lead, C)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jax.random.uniform(next(keys), (*lead, H), dt,
                                                1.0, 16.0)),
            "D": jnp.ones((*lead, H), dt),
            "gate_norm": jnp.ones((*lead, I), dt),
            "out_proj_w": w((*lead, I, D), I),
        }

    def attention(lead):
        Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        return {
            **common(lead),
            "qkv_w": w((*lead, D, (Hq + 2 * Hkv) * Dh), D),   # [q | k | v]
            "attn_out_w": w((*lead, Hq * Dh, D), Hq * Dh),
        }

    return {"embed": w((cfg.vocab_size, D), D),
            "final_ln": jnp.ones((D,), dt),
            "layers": [(mamba if kind == MAMBA else attention)((periods, n))
                       for kind, n in runs]}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _norm(x, scale, cfg):
    return _rms_norm(x, scale, None, eps=cfg.rms_norm_eps)


def _conv(xbc, lp):
    """The mixer's convolution, bias and silu, [B, S, C] -> [B, S, C]:
    `ops/short_conv.py`'s kernels (the benchmark's broken variants patch
    this name with the `jnp` form, `ssd.causal_conv1d` and a silu).  The
    call writes x, B and C as three arrays, x as wide as the gated norm's
    scale, and the backward call reads their three cotangents: joined
    here only for `_mamba` to split again, which the compiler folds away,
    so neither the split nor its transpose is a pass over the array."""
    inner = lp["gate_norm"].shape[-1]
    state = (xbc.shape[-1] - inner) // 2
    return jnp.concatenate(short_conv.mamba_conv(
        xbc, lp["conv_w"], lp["conv_b"], parts=(inner, state, state)), -1)


def _step_size(raw, dt_bias):
    """The scan's dt, float32: softplus(dt + dt_bias) a head."""
    return jax.nn.softplus(raw.astype(jnp.float32)
                           + dt_bias.astype(jnp.float32))


def _gate_norm(y, z, scale, cfg):
    """The gated norm: the gate goes on BEFORE the norm, which is taken
    over the whole inner width (one group)."""
    return _norm(y * jax.nn.silu(z), scale, cfg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _stretch_sums(x, groups: int):
    """[..., groups w] -> [..., groups]: the sum over each stretch of w
    neighbours of the last dimension.  Written, with `_spread`, its
    transpose, as slices of whole stretches, each the other's backward
    pass (JAX's own transpose of a slice pads it to the whole width, one
    array a stretch): the same sums as a reshape to [..., groups, w]
    gives, but on the chip that reshape re-tiles the array (lanes become
    rows), and in the backward pass the compiler made two float32 copies
    of the whole width of it a layer once the scan handed the norm its
    result in the mixer's own layout (PERF.md, Findings, PR 48)."""
    w = x.shape[-1] // groups
    return jnp.concatenate(
        [x[..., g * w:(g + 1) * w].sum(-1, keepdims=True)
         for g in range(groups)], axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _spread(r, w: int):
    """[..., groups] -> [..., groups w]: each number over its stretch."""
    return jnp.concatenate(
        [jnp.broadcast_to(r[..., g:g + 1], (*r.shape[:-1], w))
         for g in range(r.shape[-1])], axis=-1)


_stretch_sums.defvjp(
    lambda x, groups: (_stretch_sums(x, groups), x.shape[-1] // groups),
    lambda groups, w, ct: (_spread(ct, w),))
_spread.defvjp(
    lambda r, w: (_spread(r, w), None),
    lambda w, _, ct: (_stretch_sums(ct, ct.shape[-1] // w),))


def _gate_norm_grouped(y, z, scale, cfg, groups: int):
    """The gated norm over each of `groups` stretches of the inner width
    apart, under the one learned scale (`models/nemotron_h.py`): `_norm`
    a stretch, float32, over the array as the mixer has it."""
    gated = y * jax.nn.silu(z)
    g32 = gated.astype(jnp.float32)
    w = gated.shape[-1] // groups
    mean_sq = _stretch_sums(g32 * g32, groups) / w
    normed = g32 * _spread(jax.lax.rsqrt(mean_sq + cfg.rms_norm_eps), w)
    return (normed * scale.astype(jnp.float32)).astype(gated.dtype)


def _mamba(x, lp, cfg, scope: str = "granite.mamba", norm_groups: int = 1):
    """The Mamba-2 mixer, its input norm included.  x [B, S, D] ->
    [B, S, D].  `cfg` is this model's or another's with the same fields
    (`models/nemotron_h.py`, which names its own `scope` and norms the
    gated result by the scan's groups)."""
    dt = cfg.dtype
    B, S, _ = x.shape
    H, P = cfg.mamba_n_heads, cfg.mamba_d_head
    G, N, I = cfg.mamba_n_groups, cfg.mamba_d_state, cfg.d_inner
    with jax.named_scope(scope + ".in_proj"):
        u = _norm(x, lp["input_ln"], cfg)
        zxbcdt = checkpoint_name(
            jnp.einsum("bsd,de->bse", u, lp["in_proj_w"].astype(dt)),
            IN_PROJ_NAME)
        z, xbc, raw = jnp.split(zxbcdt, [I, I + cfg.conv_dim], axis=-1)
    with jax.named_scope(scope + ".conv"):
        xbc = _conv(xbc, lp)
        x, bm, cm = jnp.split(xbc, [I, I + G * N], axis=-1)
    with jax.named_scope(scope + ".scan"):
        y = ssd.ssd_scan(
            x.reshape(B, S, H, P), _step_size(raw, lp["dt_bias"]),
            -jnp.exp(lp["A_log"].astype(jnp.float32)),
            bm.reshape(B, S, G, N), cm.reshape(B, S, G, N), lp["D"],
            chunk=min(cfg.mamba_chunk_size, S))
    with jax.named_scope(scope + ".gate_norm"):
        y = y.reshape(B, S, I)
        if norm_groups == 1:    # this model's, under the name and the
            # arguments its broken variants patch
            y = _gate_norm(y, z, lp["gate_norm"], cfg)
        else:
            y = _gate_norm_grouped(y, z, lp["gate_norm"], cfg, norm_groups)
    with jax.named_scope(scope + ".out_proj"):
        return jnp.einsum("bse,ed->bsd", y, lp["out_proj_w"].astype(dt))


def _attend(q, k, v, cfg):
    """q, k, v [B, H, S, Dh] -> ctx, causal, scores times
    `attention_multiplier`.  The shared flash adapter divides by sqrt(Dh),
    so the model's multiplier goes on q (the published 1/64 at head size
    64 is a factor of 1/8: exact in any dtype)."""
    scale = cfg.attention_multiplier * math.sqrt(q.shape[-1])
    return flash_attention_fn(q * jnp.asarray(scale, q.dtype), k, v, True)


def _qkv(x, lp, cfg):
    """What a layer's attention call is given: x [B, S, D] normed and
    projected, queries [B, H, S, Dh], keys and values [B, Hkv, S, Dh]."""
    B, S, _ = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    u = _norm(x, lp["input_ln"], cfg)
    qkv = jnp.einsum("bsd,de->bse", u, lp["qkv_w"].astype(cfg.dtype))
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)

    def heads(t):
        return t.reshape(B, S, -1, Dh).transpose(0, 2, 1, 3)
    return heads(q), heads(k), heads(v)


def _attention(x, lp, cfg, scope: str = "granite.attn"):
    """The attention mixer, its input norm included.  x [B, S, D] ->
    [B, S, D].  `cfg` and `scope` as `_mamba`'s."""
    B, S, _ = x.shape
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    with jax.named_scope(scope):
        with jax.named_scope(".qkv"):
            q, k, v = _qkv(x, lp, cfg)
            if Hkv != H:
                k = jnp.repeat(k, H // Hkv, axis=1)
                v = jnp.repeat(v, H // Hkv, axis=1)
        # the kernels and the transpose after them stay the half's own
        ctx = _attend(q, k, v, cfg)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, -1)
        with jax.named_scope(".out"):
            return jnp.einsum("bse,ed->bsd", ctx,
                              lp["attn_out_w"].astype(cfg.dtype))


def _mlp(x, lp, cfg: GraniteHybridConfig):
    """The shared SwiGLU, its input norm included."""
    dt = cfg.dtype
    u = _norm(x, lp["post_ln"], cfg)
    ab = jnp.einsum("bsd,df->bsf", u, lp["mlp_in_w"].astype(dt))
    a, b = jnp.split(ab, 2, axis=-1)
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(a) * b,
                      lp["mlp_out_w"].astype(dt))


def _layer(x, lp, cfg: GraniteHybridConfig, kind: str):
    """One layer.  x [B, S, D] -> [B, S, D]."""
    mixer = _mamba if kind == MAMBA else _attention
    r = jnp.asarray(cfg.residual_multiplier, x.dtype)
    x = x + r * mixer(x, lp, cfg)
    with jax.named_scope("granite.mlp"):
        return x + r * _mlp(x, lp, cfg)


def _embed(params, tokens, cfg: GraniteHybridConfig):
    with jax.named_scope("granite.embed"):
        x = params["embed"].astype(cfg.dtype)[tokens - cfg.vocab_start]
        return x * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)


def _record_scan(cfg, batch: int, seq_len: int) -> None:
    chunk = min(cfg.mamba_chunk_size, seq_len)
    telemetry.record_static(
        "ssd_scan", layers=cfg.count(MAMBA), chunk=chunk,
        groups=cfg.mamba_n_groups,
        state_bytes=ssd.state_bytes(batch, cfg.mamba_n_heads, seq_len,
                                    cfg.mamba_d_head, cfg.mamba_d_state,
                                    chunk))


def _record_kept(cfg, batch: int, seq_len: int, names, others=None) -> None:
    """`bps_remat_kept_*`: what the layers' policy keeps under each of
    `names`.  The two mixers' names are reckoned here from `cfg` (this
    model's or `models/nemotron_h.py`'s); `others` is `{name: (layers,
    bytes a layer)}` of a caller's own."""
    rows, item = batch * seq_len, jnp.dtype(cfg.dtype).itemsize
    kept = {
        IN_PROJ_NAME: (cfg.count(MAMBA), rows * item * (
            cfg.d_inner + cfg.conv_dim + cfg.mamba_n_heads)),
        flash_attention.KEPT_NAME: (
            cfg.count(ATTENTION), flash_attention.kept_bytes(
                batch * cfg.num_heads, seq_len, cfg.head_dim, cfg.dtype)),
        **(others or {})}
    for name in names:
        layers, nbytes = kept[name]
        telemetry.record_static("remat_kept", labels={"name": name},
                                layers=layers, bytes=layers * nbytes)


def forward_hidden(params: PyTree, tokens: jax.Array,
                   cfg: GraniteHybridConfig) -> jax.Array:
    """tokens [B, S] int32 (ids of the held slice) -> the final hidden
    states [B, S, D], after the last norm."""
    _record_scan(cfg, *tokens.shape)
    _record_kept(cfg, *tokens.shape, KEPT_NAMES)
    x = _embed(params, tokens, cfg)
    _, runs = _stack_plan(cfg)

    def period(x, run_leaves):
        for (kind, _), lps in zip(runs, run_leaves):
            layer = jax.checkpoint(
                functools.partial(_layer, cfg=cfg, kind=kind),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *KEPT_NAMES))
            x, _ = lax.scan(lambda x, lp, layer=layer: (layer(x, lp), None),
                            x, lps)
        return x, None

    x, _ = lax.scan(period, x, params["layers"])
    with jax.named_scope("granite.head"):
        return _norm(x, params["final_ln"], cfg)


def head_logits(x: jax.Array, embed: jax.Array,
                cfg: GraniteHybridConfig) -> jax.Array:
    """Float32 logits of `x` [..., D] over the held rows of the tied
    embedding [V, D]: the slice's columns of the whole model's logits."""
    return jnp.einsum("...d,vd->...v", x, embed.astype(x.dtype),
                      preferred_element_type=jnp.float32
                      ) / cfg.logits_scaling


def loss_fn(params: PyTree, batch, cfg: GraniteHybridConfig) -> jax.Array:
    """Mean next-token cross-entropy over the held slice of the vocabulary.
    batch = (tokens [B, S], targets [B, S])."""
    tokens, targets = batch
    x = forward_hidden(params, tokens, cfg)
    with jax.named_scope("granite.head"):
        targets = targets - cfg.vocab_start
        scaled = x / jnp.asarray(cfg.logits_scaling, x.dtype)
        return fused_nll_sum(scaled, params["embed"], targets,
                             cfg.ce_chunk_rows) / targets.size


def synthetic_batch(rng: jax.Array, batch_size: int, seq_len: int,
                    cfg: GraniteHybridConfig):
    """Token ids uniform over the held slice of the vocabulary."""
    toks = jax.random.randint(rng, (batch_size, seq_len + 1),
                              cfg.vocab_start,
                              cfg.vocab_start + cfg.vocab_size, jnp.int32)
    return toks[:, :-1], toks[:, 1:]
