"""The `lfm2_moe` decoder (LiquidAI's LFM2 mixture-of-experts models,
`model_type: lfm2_moe`): a language model whose layers differ on TWO axes.
A layer's MIXER is a doubly gated short convolution or grouped attention
(`layer_types`), and its FEED-FORWARD is a dense SwiGLU in the first
`num_dense_layers` layers and sparse experts in the others:

    h0 = embed[ids]
    layer:     x = x + mixer(rms(x; operator_norm))
               x = x + ffn  (rms(x; ffn_norm))
    conv(u):   [B | C | X] = u W_in                    W_in [D, 3 D]
               z_t = sum_k w_k (B * X)_{t-(K-1)+k}     depthwise, causal,
                     K = `conv_L_cache` 3 taps; zeros stand before a
                     sequence's first position; no bias, no activation
               conv(u) = (C * z) W_out
    attn(u):   q, k, v = u Wq, u Wk, u Wv; q and k RMS-normed over each
               head (q_norm, k_norm), THEN rotary over the whole head
               (lane i with i + Dh / 2); causal softmax(q k^T / sqrt(Dh)) v,
               a key-value head serving heads / kv_heads query heads; Wo
    dense(u):  (silu(u W1) * u W3) W2
    experts(u): s = sigmoid(u Wr), float32, over ALL the experts; the top k
               of s + `expert_bias` (a buffer, the leaf where the tree has
               it, else zero, that moves the choice alone);
               w = route_scale * s[choice] / (sum s[choice] + 1e-6);
               sum of w_e (silu(u W1_e) * u W3_e) W2_e.  No shared expert.
    logits = rms(x; final_ln) embed^T                  (the head is tied)

RMS norms everywhere, no bias anywhere.

Why a module beside the others: `afmoe.py` varies the feed-forward and the
attention's mask, `granite_hybrid.py` and `nemotron_h.py` the mixer under
one feed-forward; none stacks layers that differ on both axes, and none
has a mixer that is two projections round an elementwise operator with no
scan, no softmax and no activation function.  What is shared is imported,
not copied: `_rms_norm`, `_rope`, the streamed cross-entropy
`fused_nll_sum`, `afmoe._attn_fn`'s `full_attention` (the flash adapter),
`afmoe._swiglu`, `dropless_moe.held_experts` (with its router), and
`granite_hybrid`'s way of stacking runs.

The plan (`stack_plan`).  A RUN is consecutive layers of one (mixer,
feed-forward) kind; the tree holds one group of leaves a run,
`params["layers"][i]`, stacked on a leading axis over the run's layers,
and a run is one `lax.scan` over its stack: compile time is a layer a run,
no leaf is cut or joined in either pass, and there is no switch on the
kind (`nemotron_h.py` says what a `lax.switch` inside a scan costs its
backward pass).  The published order (conv, conv, then attention and three
convs, nine and a half times) is 21 runs; the benchmark's seven layers are
five.

No option selects a path.  The convolution is `ops/short_conv.py`'s Pallas
kernels (the jnp form, `ssd.causal_conv1d` between two products, is the
tests' oracle: on the chip it took 3.1 times as long, PERF.md, Findings,
PR 55); attention is the flash kernels at the block their own rule picks;
the head and the cross-entropy are streamed `ce_chunk_rows` rows at a
time.  A sequence is a multiple of 128 positions (the flash kernels'
tiling).

What a rematerialised layer KEEPS (`KEPT_NAMES`, the policy
`save_only_these_names` of the one `jax.checkpoint` call every layer is
under; no option).  At the benchmark's shape, 4 sequences of 8,192 at the
published widths, a layer holds from its forward pass to its backward
pass (a run is scanned, so a stack over the run's layers):

    attention  the flash call's `o` and `lse` (`flash_attention.KEPT_NAME`):
               134 + 4 MB; the recompute calls no forward kernel
    experts    the router's logits, choice and weights and the plan's
               sorted list (`dropless_moe.ROUTING_NAME`): 11 MB; no score
               product, top-k or sort a second time
    conv       `in_proj`'s result `[B | C | X]` (`IN_PROJ_NAME`):
               [32768, 6144] bfloat16, 403 MB; the recompute starts at the
               kernel, and the backward kernel reads what the forward
               pass made

2.3 GB over the benchmark's seven layers (`bps_remat_kept_bytes{name}`).
Everything else is made again: the norms, `qkv`, the convolution's own
result (134 MB a layer for 0.84 ms), the gather and the experts' products.

A share of a deployment, as `afmoe.py` says it: `layer_types` lists the
layers that are run (a pipeline stage's) and `num_dense_layers` how many
of THOSE are dense, `held_experts` the experts of every expert layer this
chip holds (the router stays `num_experts` wide and takes
`num_experts_per_tok`), `vocab_size` the rows of the tied embedding held
here, ids `vocab_start ...`: embedding, logits and loss are over the
slice.  With every layer, every expert and the whole vocabulary it is the
whole model.  A share's backward pass holds the weight each token gives
the held experts together constant
(`dropless_moe.MoEConfig.hold_held_weight`, which says why: this layer
adds its branch to the stream un-normed, as mellum's and joyai's do).

Parameters float32, compute `dtype`; the router's scores, top-k and
weights, every norm's statistics, the rotary turn, the flash kernels'
statistics and the convolution's products and sums are float32 whatever
`dtype` is.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..common import telemetry
from ..ops import flash_attention, short_conv
from ..parallel import dropless_moe
from . import afmoe
from .afmoe import FULL
from .transformer import _rms_norm, _rope, fused_nll_sum

PyTree = Any
CONV, ATTENTION = "conv", FULL          # `layer_types`' entries
DENSE, MOE = "dense", "moe"
# The name `in_proj`'s result carries for `jax.checkpoint` (`_conv`).
IN_PROJ_NAME = "lfm2.conv.in_proj"
# What a rematerialised layer keeps from its forward pass, by name (the
# module's docstring says why these).
KEPT_NAMES = (flash_attention.KEPT_NAME, dropless_moe.ROUTING_NAME,
              IN_PROJ_NAME)


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int                    # rows of the tied embedding held here
    hidden_size: int
    layer_types: Tuple[str, ...]       # one entry a layer that is run
    num_dense_layers: int              # of those, the leading dense ones
    intermediate_size: int             # the dense layers' SwiGLU
    moe_intermediate_size: int         # an expert's
    num_experts: int                   # the router's width
    num_experts_per_tok: int
    num_heads: int                     # attention
    num_kv_heads: int
    head_dim: int
    conv_kernel: int = 3               # `conv_L_cache`: the taps
    held_experts: Optional[Tuple[int, ...]] = None   # None: all of them
    vocab_start: int = 0               # first token id of the held slice
    route_scale: float = 1.0
    route_norm: bool = True
    route_norm_eps: float = 1e-6       # beside the chosen scores' sum
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    dtype: Any = jnp.bfloat16          # of the activations
    ce_chunk_rows: int = 2048          # rows a block of the streamed head
    moe_capacity_factor: float = 1.25  # dropless_moe's static buffer
    # what `afmoe._attn_fn` reads of a configuration: the flash kernels,
    # their tiles left to the rule
    attn_impl = "flash"
    attn_block = 0
    attn_block_k = 0

    def __post_init__(self):
        if any(t not in (CONV, ATTENTION) for t in self.layer_types):
            raise ValueError(f"layer_types={self.layer_types}")
        if not 0 <= self.num_dense_layers <= len(self.layer_types):
            raise ValueError(f"num_dense_layers={self.num_dense_layers}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads={self.num_heads} not divisible by "
                             f"num_kv_heads={self.num_kv_heads}")
        if self.head_dim % 2:
            raise ValueError(f"rotary positions need an even head_dim "
                             f"(got {self.head_dim})")

    @property
    def held(self) -> Tuple[int, ...]:
        return (tuple(range(self.num_experts)) if self.held_experts is None
                else tuple(self.held_experts))

    @property
    def moe(self) -> dropless_moe.MoEConfig:
        return dropless_moe.MoEConfig(
            num_experts=self.num_experts, top_k=self.num_experts_per_tok,
            held=self.held, route_scale=self.route_scale,
            route_norm=self.route_norm, norm_eps=self.route_norm_eps,
            score_func="sigmoid", capacity_factor=self.moe_capacity_factor,
            hold_held_weight=True)


def stack_plan(cfg: Lfm2Config) -> Tuple[Tuple[str, str, int], ...]:
    """`(mixer, feed-forward, layers)` of every run, in the order the
    layers run: a run is consecutive layers of one kind on both axes."""
    runs = []
    for i, mixer in enumerate(cfg.layer_types):
        kind = (mixer, DENSE if i < cfg.num_dense_layers else MOE)
        if runs and runs[-1][:2] == kind:
            runs[-1] = (*kind, runs[-1][2] + 1)
        else:
            runs.append((*kind, 1))
    return tuple(runs)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def init_params(rng: jax.Array, cfg: Lfm2Config) -> PyTree:
    """Normal / sqrt(fan_in) matrices, unit norm scales, the convolution's
    taps uniform +-1 / sqrt(taps) (a depthwise convolution's default).
    `params["layers"][i]` holds run i's leaves, stacked over its layers.
    The load balancer's `expert_bias` is no parameter and is not made
    here: a run that has the leaf ([layers, experts]) adds it to the
    scores before the top-k, one that lacks it runs with zero."""
    dt = jnp.float32
    D, Dh = cfg.hidden_size, cfg.head_dim
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    plan = stack_plan(cfg)
    keys = iter(jax.random.split(rng, 1 + 8 * len(plan)))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, dt)
                / math.sqrt(fan_in)).astype(dt)

    def mixer(kind, n):
        if kind == CONV:
            bound = 1.0 / math.sqrt(cfg.conv_kernel)
            return {"in_proj_w": w((n, D, 3 * D), D),       # [B | C | X]
                    # `conv_w[k]` meets position t - (K - 1) + k
                    "conv_w": jax.random.uniform(
                        next(keys), (n, cfg.conv_kernel, D), dt, -bound,
                        bound),
                    "out_proj_w": w((n, D, D), D)}
        return {"qkv_w": w((n, D, (H + 2 * Hkv) * Dh), D),  # [q | k | v]
                "q_norm": jnp.ones((n, Dh), dt),
                "k_norm": jnp.ones((n, Dh), dt),
                "attn_out_w": w((n, H * Dh, D), H * Dh)}

    def swiglu(lead, width, prefix):
        return {prefix + "gate_w": w((*lead, D, width), D),
                prefix + "up_w": w((*lead, D, width), D),
                prefix + "down_w": w((*lead, width, D), width)}

    def feed_forward(kind, n):
        if kind == DENSE:
            return swiglu((n,), cfg.intermediate_size, "mlp_")
        return {"router_w": w((n, D, cfg.num_experts), D),
                **swiglu((n, len(cfg.held)), cfg.moe_intermediate_size,
                         "expert_")}

    return {"embed": w((cfg.vocab_size, D), D),
            "final_ln": jnp.ones((D,), dt),
            "layers": [{"operator_norm": jnp.ones((n, D), dt),
                        "ffn_norm": jnp.ones((n, D), dt),
                        **mixer(m, n), **feed_forward(f, n)}
                       for m, f, n in plan]}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _norm(x, scale, cfg):
    return _rms_norm(x, scale, None, eps=cfg.rms_norm_eps)


def _gated_conv(bcx, taps):
    """The operator between the two projections: [B, S, 3 D] -> [B, S, D]."""
    return short_conv.gated_short_conv(bcx, taps)


def _conv(x, lp, cfg: Lfm2Config):
    """The convolution mixer, its input norm included.  x [B, S, D] ->
    [B, S, D]."""
    dt = cfg.dtype
    with jax.named_scope("lfm2.conv.in_proj"):
        u = _norm(x, lp["operator_norm"], cfg)
        bcx = checkpoint_name(
            jnp.einsum("bsd,de->bse", u, lp["in_proj_w"].astype(dt)),
            IN_PROJ_NAME)
    with jax.named_scope("lfm2.conv.gate_conv"):
        y = _gated_conv(bcx, lp["conv_w"])
    with jax.named_scope("lfm2.conv.out_proj"):
        return jnp.einsum("bsd,de->bse", y, lp["out_proj_w"].astype(dt))


def _qkv(x, lp, cfg: Lfm2Config):
    """What a layer's attention call is given: x [B, S, D] normed and
    projected, q and k normed over each head and then turned; queries
    [B, H, S, Dh], keys and values [B, Hkv, S, Dh]."""
    B, S, _ = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    u = _norm(x, lp["operator_norm"], cfg)
    qkv = jnp.einsum("bsd,de->bse", u, lp["qkv_w"].astype(cfg.dtype))
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)

    def heads(t):
        return t.reshape(B, S, -1, Dh).transpose(0, 2, 1, 3)
    q = _rope(_norm(heads(q), lp["q_norm"], cfg), cfg.rope_theta)
    k = _rope(_norm(heads(k), lp["k_norm"], cfg), cfg.rope_theta)
    return q, k, heads(v)


def _attention(x, lp, cfg: Lfm2Config):
    """The attention mixer, its input norm included."""
    B, S, _ = x.shape
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    with jax.named_scope("lfm2.attn"):
        with jax.named_scope(".qkv"):
            q, k, v = _qkv(x, lp, cfg)
            if Hkv != H:
                k = jnp.repeat(k, H // Hkv, axis=1)
                v = jnp.repeat(v, H // Hkv, axis=1)
        # the kernels and the transpose after them stay the half's own
        ctx = afmoe._attn_fn(cfg, FULL)(q, k, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, -1)
        with jax.named_scope(".out"):
            return jnp.einsum("bse,ed->bsd", ctx,
                              lp["attn_out_w"].astype(cfg.dtype))


_MIXERS = {CONV: _conv, ATTENTION: _attention}


def _ffn_input(x, lp, cfg: Lfm2Config):
    """What a layer's feed-forward, router and experts are given."""
    return _norm(x, lp["ffn_norm"], cfg)


def _feed_forward(x, lp, sel, cfg: Lfm2Config, kind: str):
    """x [B, S, D] -> `(f, routing or None)`."""
    if kind == DENSE:
        with jax.named_scope("lfm2.dense"):
            return afmoe._swiglu(_ffn_input(x, lp, cfg), lp, "mlp_",
                                 cfg.dtype), None
    with jax.named_scope("lfm2.moe"):
        m = _ffn_input(x, lp, cfg).reshape(-1, x.shape[-1])
        experts = {n: lp["expert_" + n] for n in ("gate_w", "up_w", "down_w")}
        routed, routing = dropless_moe.held_experts(
            m, lp["router_w"], experts, cfg.moe,
            expert_bias=lp.get("expert_bias"), sel=sel)
        return routed.reshape(x.shape), routing


def _layer(x, lp, sel, cfg: Lfm2Config, mixer: str, ffn: str):
    """One layer.  x [B, S, D] -> `(x, routing or None)`."""
    x = x + _MIXERS[mixer](x, lp, cfg)
    f, routing = _feed_forward(x, lp, sel, cfg, ffn)
    return x + f, routing


def _embed(params, tokens, cfg: Lfm2Config):
    with jax.named_scope("lfm2.embed"):
        return params["embed"].astype(cfg.dtype)[tokens - cfg.vocab_start]


def _record(cfg: Lfm2Config, batch: int, seq_len: int) -> None:
    plan = stack_plan(cfg)
    telemetry.record_static("layer_plan", stacks=len(plan))
    for mixer in (CONV, ATTENTION):
        telemetry.record_static(
            "layer_plan", labels={"kind": mixer},
            layers=sum(n for m, _, n in plan if m == mixer))
    kept = {
        flash_attention.KEPT_NAME: (ATTENTION, flash_attention.kept_bytes(
            batch * cfg.num_heads, seq_len, cfg.head_dim, cfg.dtype)),
        dropless_moe.ROUTING_NAME: (MOE, cfg.moe.kept_bytes(
            batch * seq_len)),
        IN_PROJ_NAME: (CONV, short_conv.kept_bytes(
            batch, seq_len, cfg.hidden_size, cfg.dtype))}
    for name in KEPT_NAMES:
        kind, nbytes = kept[name]
        layers = sum(n for m, f, n in plan if kind in (m, f))
        telemetry.record_static("remat_kept", labels={"name": name},
                                layers=layers, bytes=layers * nbytes)


def forward_hidden(params: PyTree, tokens: jax.Array, cfg: Lfm2Config,
                   sel=None, with_routing: bool = False):
    """tokens [B, S] int32 (ids of the held slice) -> the final hidden
    states [B, S, D], after the last norm.

    `sel` [expert layers, B*S, k] replaces every router's own top-k (see
    `dropless_moe.route`).  With `with_routing` the result is
    `(hidden, Routing)`, the `Routing`'s leaves stacked over the expert
    layers."""
    _record(cfg, *tokens.shape)
    x = _embed(params, tokens, cfg)
    keep = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)
    routed, seen = [], 0
    for (mixer, ffn, n), lps in zip(stack_plan(cfg), params["layers"]):
        layer = jax.checkpoint(
            functools.partial(_layer, cfg=cfg, mixer=mixer, ffn=ffn),
            policy=keep)
        sels = None
        if ffn == MOE and sel is not None:
            sels = sel[seen:seen + n]
        seen += n if ffn == MOE else 0

        def step(x, xs, layer=layer):
            x, r = layer(x, *xs)
            return x, (r if with_routing else None)

        x, r = lax.scan(step, x, (lps, sels))
        if ffn == MOE and with_routing:
            routed.append(r)
    with jax.named_scope("lfm2.head"):
        x = _norm(x, params["final_ln"], cfg)
    if not with_routing:
        return x
    return x, (jax.tree.map(lambda *a: jnp.concatenate(a), *routed)
               if routed else None)


def head_logits(x: jax.Array, embed: jax.Array) -> jax.Array:
    """Float32 logits of `x` [..., D] over the held rows of the tied
    embedding [V, D]: the slice's columns of the whole model's logits."""
    return jnp.einsum("...d,vd->...v", x, embed.astype(x.dtype),
                      preferred_element_type=jnp.float32)


def loss_fn(params: PyTree, batch, cfg: Lfm2Config, sel=None) -> jax.Array:
    """Mean next-token cross-entropy over the held slice of the vocabulary.
    batch = (tokens [B, S], targets [B, S])."""
    tokens, targets = batch
    x = forward_hidden(params, tokens, cfg, sel=sel)
    with jax.named_scope("lfm2.head"):
        return fused_nll_sum(x, params["embed"], targets - cfg.vocab_start,
                             cfg.ce_chunk_rows) / targets.size


def routing(params: PyTree, tokens: jax.Array, cfg: Lfm2Config):
    """The program's own routing on `tokens`, a `dropless_moe.Routing`
    with leaves stacked over the expert layers."""
    return forward_hidden(params, tokens, cfg, with_routing=True)[1]


synthetic_batch = afmoe.synthetic_batch
