"""The `kimi_linear` decoder (Moonshot's Kimi Linear models, `model_type:
kimi_linear`; arXiv:2510.26692): a language model whose layers differ on
TWO axes.  A layer's MIXER is Kimi Delta Attention (KDA: a delta rule
whose state decays a key channel at a time) or latent attention without
positions (`layer_types`), three of the first to every one of the second
in the published order; its FEED-FORWARD is a dense SwiGLU in the first
`num_dense_layers` layers and experts round a shared one in the others:

    h0 = embed[ids]
    layer:    x = x + mixer(rms(x; input_ln))
              x = x + ffn  (rms(x; post_ln))
    kda(u):   [q | k | v] = silu(conv4(u W_qkv))    H heads of K = 128 each;
                  conv4 depthwise, causal, 4 taps, no bias, zeros before a
                  sequence's first position, its own taps a channel
              q_h = l2norm(q_h) / sqrt(K),  k_h = l2norm(k_h)
              g = -exp(A_log_h) softplus((u W_fa) W_fb + dt_bias)
                  float32, [S, H K]: a log-decay a KEY CHANNEL, <= 0
              beta = sigmoid(u W_b)                        float32, [S, H]
              S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}
                    + beta_t k_t v_t^T;    o_t = S_t^T q_t      (a head)
              y = rms_h(o; o_norm [K]) * sigmoid((u W_ga) W_gb)
              kda(u) = y W_o
    mla(u):   q = u W_q (H heads of nope + rope = 192: no query chain)
              [c | kr] = u W_down;  [kn_h | v_h] = rms(c; kv_a_ln) W_up
              k_h = [kn_h | kr], kr AS IT IS, the same for every head: NO
              rotary turn anywhere (`mla_use_nope`)
              causal softmax(q k^T / sqrt(192)) v;  W_o
    dense(u): (silu(u W1) * u W3) W2
    experts(u): s = sigmoid(u Wr), float32, over ALL the experts; the top
              k of s + `expert_bias` (a buffer, the leaf where the tree has
              it, else zero, that moves the choice alone);
              w = route_scale * s[choice] / (sum s[choice] + 1e-20);
              sum of w_e swiglu_e(u) + swiglu_shared(u), the shared expert
              unweighted on every token
    logits = rms(x; final_ln) head^T                 (the head is untied)

RMS norms everywhere, no bias anywhere.

Why a module beside the others: `joyai.py` has latent attention, but with
a query chain and rotary turns, under one kind of mixer; `lfm2.py` stacks
layers that differ on both axes, but its second mixer is a convolution;
`granite_hybrid.py` has a scanned state, but `ops/ssd.py`'s, which decays
by a scalar and is never corrected.  What is shared is imported, not
copied: `_rms_norm`, the streamed cross-entropy `fused_nll_sum`,
`afmoe._attn_fn`'s `full_attention` (the flash kernels at two widths,
`flash_fwd_d192x128`), `afmoe._swiglu`, `granite_hybrid._stretch_sums` (a
head's norm over the array as the mixer has it),
`dropless_moe.held_experts` with its router, `short_conv.mamba_conv` (the
4-tap convolution and silu of q, k and v in ONE call, `bias=None`), and
`lfm2.stack_plan`'s way of stacking runs.  The scan is `ops/kda.py`.

The plan (`stack_plan`).  A RUN is consecutive layers of one (mixer,
feed-forward) kind; the tree holds one group of leaves a run,
`params["layers"][i]`, stacked over the run's layers, and a run is one
`lax.scan`.  The published 27 layers are 15 runs; the benchmark's five
(KDA dense; KDA, KDA, KDA, MLA with experts) are three.

No option selects a path.  The scan is `kda.kda_scan`, the Pallas kernels
(`kda.kda_scan_jnp` is the tests' oracle: PERF.md, Findings, PR 57, has
both forms' times on the chip); attention is the flash kernels at the
block their own rule picks; the head and the cross-entropy are streamed
`ce_chunk_rows` rows at a time.  A sequence is a multiple of 128
positions (the flash kernels' tiling; the scan's chunk is 64).

What a rematerialised layer KEEPS (`KEPT_NAMES`, the policy
`save_only_these_names` of the one `jax.checkpoint` call every layer is
under; no option).  At the benchmark's shape, ONE sequence of 32,768 at
the published widths, a layer holds from its forward pass to its backward
pass (a run is scanned, so a stack over the run's layers):

    mla        the flash call's `o` and `lse` (`flash_attention.KEPT_NAME`):
               268 + 4 MB; the recompute calls no forward kernel
    experts    the router's logits, choice and weights and the plan's
               sorted list (`dropless_moe.ROUTING_NAME`): 38 MB
    kda        NOTHING.  The three projections' result ([S, 12288]
               bfloat16) is 805 MB a layer, 3.2 GB over the four, and the
               step has 1.1 GB to spare of the chip's 16.9 (compiled for
               a described v5e: peak 15.75 GB; PERF.md, Findings, PR 57),
               so the recompute makes the projection, the convolution and
               the scan again

Everything else is made again: the norms, the gates, the scan's chunk
states (`kda.state_bytes`: 1.07 GB a layer, alive from a layer's
recompute to its backward kernel).

A share of a deployment, as `afmoe.py` says it: `layer_types` lists the
layers that are run (a pipeline stage's) and `num_dense_layers` how many
of THOSE are dense, `held_experts` the experts of every expert layer this
chip holds (the router stays `num_experts` wide and takes
`num_experts_per_tok`; the shared expert whole), `vocab_size` the rows of
embedding and head held here, ids `vocab_start ...`.  With every layer,
every expert and the whole vocabulary it is the whole model.  A share's
backward pass holds the weight each token gives the held experts together
constant (`dropless_moe.MoEConfig.hold_held_weight`).

Parameters float32, compute `dtype`; g, beta, the scan's state and sums,
the router's scores, top-k and weights, every norm's statistics and the
flash kernels' statistics are float32 whatever `dtype` is.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..common import telemetry
from ..ops import flash_attention, kda, short_conv
from ..parallel import dropless_moe
from . import afmoe, granite_hybrid
from .afmoe import FULL
from .transformer import _rms_norm, fused_nll_sum

PyTree = Any
KDA, MLA = "kda", "mla"                 # `layer_types`' entries
DENSE, MOE = "dense", "moe"
# What a rematerialised layer keeps from its forward pass, by name (the
# module's docstring says why these and not the projections' result).
KEPT_NAMES = (flash_attention.KEPT_NAME, dropless_moe.ROUTING_NAME)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int                    # rows of embedding and head held here
    hidden_size: int
    layer_types: Tuple[str, ...]       # one entry a layer that is run
    num_dense_layers: int              # of those, the leading dense ones
    intermediate_size: int             # the dense layers' SwiGLU
    moe_intermediate_size: int         # every expert's, shared or routed
    num_experts: int                   # the router's width
    num_experts_per_tok: int
    num_heads: int                     # latent attention
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kda_heads: int                     # `linear_attn_config.num_heads`
    kda_head_dim: int                  # keys and values alike; the gates'
    #                                    low-rank pairs' width too
    conv_kernel: int = 4               # `short_conv_kernel_size`
    held_experts: Optional[Tuple[int, ...]] = None   # None: all of them
    vocab_start: int = 0               # first token id of the held slice
    route_scale: float = 1.0
    route_norm: bool = True
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16          # of the activations
    ce_chunk_rows: int = 2048          # rows a block of the streamed head
    moe_capacity_factor: float = 1.25  # dropless_moe's static buffer
    # what `afmoe._attn_fn` reads of a configuration: the flash kernels,
    # their tiles left to the rule
    attn_impl = "flash"
    attn_block = 0
    attn_block_k = 0

    def __post_init__(self):
        if any(t not in (KDA, MLA) for t in self.layer_types):
            raise ValueError(f"layer_types={self.layer_types}")
        if not 0 <= self.num_dense_layers <= len(self.layer_types):
            raise ValueError(f"num_dense_layers={self.num_dense_layers}")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def held(self) -> Tuple[int, ...]:
        return (tuple(range(self.num_experts)) if self.held_experts is None
                else tuple(self.held_experts))

    @property
    def moe(self) -> dropless_moe.MoEConfig:
        return dropless_moe.MoEConfig(
            num_experts=self.num_experts, top_k=self.num_experts_per_tok,
            held=self.held, route_scale=self.route_scale,
            route_norm=self.route_norm, score_func="sigmoid",
            capacity_factor=self.moe_capacity_factor, hold_held_weight=True)


def stack_plan(cfg: KimiLinearConfig) -> Tuple[Tuple[str, str, int], ...]:
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
def init_params(rng: jax.Array, cfg: KimiLinearConfig) -> PyTree:
    """Normal / sqrt(fan_in) matrices, unit norm scales, and the scan's own
    leaves as the family's initialiser makes them (`granite_hybrid.py`'s,
    from the same lineage), so that the heads decay at different rates:
    `A_log` the log of a uniform 1-16 a head, `dt_bias` the inverse
    softplus of a log-uniform 0.001-0.1 a channel; the convolution's taps
    uniform +-1 / sqrt(taps).  `params["layers"][i]` holds run i's leaves,
    stacked over its layers.  The load balancer's `expert_bias` is no
    parameter and is not made here."""
    dt = jnp.float32
    D, W, R = cfg.hidden_size, cfg.kda_width, cfg.kda_head_dim
    H, F = cfg.num_heads, cfg.moe_intermediate_size
    plan = stack_plan(cfg)
    keys = iter(jax.random.split(rng, 2 + 24 * len(plan)))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, dt)
                / math.sqrt(fan_in)).astype(dt)

    def mixer(kind, n):
        if kind == KDA:
            bound = 1.0 / math.sqrt(cfg.conv_kernel)
            step = jnp.exp(jax.random.uniform(
                next(keys), (n, W), dt, math.log(1e-3), math.log(1e-1)))
            return {"qkv_w": w((n, D, 3 * W), D),           # [q | k | v]
                    # `conv_w[k]` meets position t - (K - 1) + k
                    "conv_w": jax.random.uniform(
                        next(keys), (n, cfg.conv_kernel, 3 * W), dt, -bound,
                        bound),
                    "A_log": jnp.log(jax.random.uniform(
                        next(keys), (n, cfg.kda_heads), dt, 1.0, 16.0)),
                    "f_a_w": w((n, D, R), D), "f_b_w": w((n, R, W), R),
                    "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                    "beta_w": w((n, D, cfg.kda_heads), D),
                    "g_a_w": w((n, D, R), D), "g_b_w": w((n, R, W), R),
                    "o_norm": jnp.ones((n, R), dt),
                    "out_w": w((n, W, D), W)}
        return {"q_w": w((n, D, H * cfg.qk_head_dim), D),
                "down_w": w((n, D, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                            D),                              # [c | kr]
                "kv_a_ln": jnp.ones((n, cfg.kv_lora_rank), dt),
                # a head's columns side by side: [kn | v]
                "kv_up_w": w((n, cfg.kv_lora_rank,
                              H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                             cfg.kv_lora_rank),
                "attn_out_w": w((n, H * cfg.v_head_dim, D),
                                H * cfg.v_head_dim)}

    def swiglu(lead, width, prefix):
        return {prefix + "gate_w": w((*lead, D, width), D),
                prefix + "up_w": w((*lead, D, width), D),
                prefix + "down_w": w((*lead, width, D), width)}

    def feed_forward(kind, n):
        if kind == DENSE:
            return swiglu((n,), cfg.intermediate_size, "mlp_")
        return {"router_w": w((n, D, cfg.num_experts), D),
                **swiglu((n,), F, "shared_"),
                **swiglu((n, len(cfg.held)), F, "expert_")}

    return {"embed": w((cfg.vocab_size, D), D),
            "head": w((cfg.vocab_size, D), D),
            "final_ln": jnp.ones((D,), dt),
            "layers": [{"input_ln": jnp.ones((n, D), dt),
                        "post_ln": jnp.ones((n, D), dt),
                        **mixer(m, n), **feed_forward(f, n)}
                       for m, f, n in plan]}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _norm(x, scale, cfg):
    return _rms_norm(x, scale, None, eps=cfg.rms_norm_eps)


def _conv(qkv, taps, cfg: KimiLinearConfig):
    """The 4-tap convolution and silu of q, k and v: [B, S, 3 W] -> three
    [B, S, W], each an array the one call wrote."""
    W = cfg.kda_width
    return short_conv.mamba_conv(qkv, taps, None, parts=(W, W, W))


def _decay(f, lp, cfg: KimiLinearConfig):
    """The log-decay a key channel, float32, <= 0: `f` [B, S, W] the
    low-rank pair's result."""
    rate = jnp.repeat(jnp.exp(lp["A_log"].astype(jnp.float32)),
                      cfg.kda_head_dim)
    return -rate * jax.nn.softplus(
        f.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))


def _gates(u, lp, cfg: KimiLinearConfig):
    """`(g, beta, the output gate's argument)` of u [B, S, D]."""
    dt = cfg.dtype

    def pair(a, b):
        low = jnp.einsum("bsd,dr->bsr", u, lp[a].astype(dt))
        return jnp.einsum("bsr,re->bse", low, lp[b].astype(dt))
    beta = jax.nn.sigmoid(jnp.einsum(
        "bsd,dh->bsh", u, lp["beta_w"].astype(dt),
        preferred_element_type=jnp.float32))
    return _decay(pair("f_a_w", "f_b_w"), lp, cfg), beta, pair("g_a_w",
                                                                "g_b_w")


def _scan(q, k, v, g, beta):
    """The delta rule over the sequence (the benchmark's broken variants
    patch this name)."""
    return kda.kda_scan(q, k, v, g, beta)


def _gate_norm(o, z, scale, cfg: KimiLinearConfig):
    """The head norm, THEN the output gate's sigmoid: o, z [B, S, W].
    The heads' statistics are `granite_hybrid._stretch_sums` over the
    array as the mixer has it: a reshape to [B, S, H, K] re-tiles it on
    the chip, three float32 copies of the whole width a layer."""
    K = cfg.kda_head_dim
    o32 = o.astype(jnp.float32)
    mean_sq = granite_hybrid._stretch_sums(o32 * o32, cfg.kda_heads) / K
    normed = o32 * granite_hybrid._spread(
        lax.rsqrt(mean_sq + cfg.rms_norm_eps), K)
    normed = normed * jnp.tile(scale.astype(jnp.float32), cfg.kda_heads)
    return normed.astype(o.dtype) * jax.nn.sigmoid(z)


def _kda(x, lp, cfg: KimiLinearConfig):
    """The KDA mixer, its input norm included.  x [B, S, D] -> [B, S, D]."""
    dt = cfg.dtype
    with jax.named_scope("kimi.kda.proj"):
        u = _norm(x, lp["input_ln"], cfg)
        qkv = jnp.einsum("bsd,de->bse", u, lp["qkv_w"].astype(dt))
    with jax.named_scope("kimi.kda.conv"):
        q, k, v = _conv(qkv, lp["conv_w"], cfg)
    with jax.named_scope("kimi.kda.gates"):
        g, beta, z = _gates(u, lp, cfg)
    with jax.named_scope("kimi.kda.scan"):
        o = _scan(q, k, v, g, beta)
    with jax.named_scope("kimi.kda.gate_norm"):
        y = _gate_norm(o, z, lp["o_norm"], cfg)
    with jax.named_scope("kimi.kda.out_proj"):
        return jnp.einsum("bse,ed->bsd", y, lp["out_w"].astype(dt))


def _qkv(x, lp, cfg: KimiLinearConfig):
    """What a latent-attention layer's call is given: x [B, S, D] normed
    and projected; queries and keys [B, H, S, nope + rope], values
    [B, H, S, v]; the one position-free key part a token laid beside every
    head's own."""
    dt = cfg.dtype
    B, S, _ = x.shape
    H, nope, rope = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    u = _norm(x, lp["input_ln"], cfg)

    def heads(t):
        return t.reshape(B, S, H, -1).transpose(0, 2, 1, 3)
    q = heads(jnp.einsum("bsd,de->bse", u, lp["q_w"].astype(dt)))
    down = jnp.einsum("bsd,de->bse", u, lp["down_w"].astype(dt))
    c, kr = down[..., :cfg.kv_lora_rank], down[..., cfg.kv_lora_rank:]
    kv = heads(jnp.einsum("bsr,re->bse", _norm(c, lp["kv_a_ln"], cfg),
                          lp["kv_up_w"].astype(dt)))
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(kr[:, None], (B, H, S, rope))],
        axis=-1)
    return q, k, kv[..., nope:]


def _mla(x, lp, cfg: KimiLinearConfig):
    """The latent-attention mixer, its input norm included."""
    B, S, _ = x.shape
    with jax.named_scope("kimi.attn"):
        with jax.named_scope(".qkv"):
            q, k, v = _qkv(x, lp, cfg)
        # the kernels and the transpose after them stay the mixer's own
        ctx = afmoe._attn_fn(cfg, FULL)(q, k, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, -1)
        with jax.named_scope(".out"):
            return jnp.einsum("bse,ed->bsd", ctx,
                              lp["attn_out_w"].astype(cfg.dtype))


_MIXERS = {KDA: _kda, MLA: _mla}


def _ffn_input(x, lp, cfg: KimiLinearConfig):
    """What a layer's feed-forward, router and experts are given."""
    return _norm(x, lp["post_ln"], cfg)


def _feed_forward(x, lp, sel, cfg: KimiLinearConfig, kind: str):
    """x [B, S, D] -> `(f, routing or None)`."""
    if kind == DENSE:
        with jax.named_scope("kimi.dense"):
            return afmoe._swiglu(_ffn_input(x, lp, cfg), lp, "mlp_",
                                 cfg.dtype), None
    with jax.named_scope("kimi.moe"):
        m = _ffn_input(x, lp, cfg)
        experts = {n: lp["expert_" + n] for n in ("gate_w", "up_w", "down_w")}
        routed, routing = dropless_moe.held_experts(
            m.reshape(-1, x.shape[-1]), lp["router_w"], experts, cfg.moe,
            expert_bias=lp.get("expert_bias"), sel=sel)
        with jax.named_scope(".shared"):
            shared = afmoe._swiglu(m, lp, "shared_", cfg.dtype)
        return shared + routed.reshape(x.shape), routing


def _layer(x, lp, sel, cfg: KimiLinearConfig, mixer: str, ffn: str):
    """One layer.  x [B, S, D] -> `(x, routing or None)`."""
    x = x + _MIXERS[mixer](x, lp, cfg)
    f, routing = _feed_forward(x, lp, sel, cfg, ffn)
    return x + f, routing


def _embed(params, tokens, cfg: KimiLinearConfig):
    with jax.named_scope("kimi.embed"):
        return params["embed"].astype(cfg.dtype)[tokens - cfg.vocab_start]


def _record(cfg: KimiLinearConfig, batch: int, seq_len: int) -> None:
    plan = stack_plan(cfg)
    telemetry.record_static("layer_plan", stacks=len(plan))
    for mixer in (KDA, MLA):
        telemetry.record_static(
            "layer_plan", labels={"kind": mixer},
            layers=sum(n for m, _, n in plan if m == mixer))
    kda.record(sum(n for m, _, n in plan if m == KDA), batch, cfg.kda_heads,
               seq_len, cfg.kda_head_dim, cfg.kda_head_dim)
    kept = {
        flash_attention.KEPT_NAME: (MLA, flash_attention.kept_bytes(
            batch * cfg.num_heads, seq_len, cfg.v_head_dim, cfg.dtype)),
        dropless_moe.ROUTING_NAME: (MOE, cfg.moe.kept_bytes(
            batch * seq_len))}
    for name in KEPT_NAMES:
        kind, nbytes = kept[name]
        layers = sum(n for m, f, n in plan if kind in (m, f))
        telemetry.record_static("remat_kept", labels={"name": name},
                                layers=layers, bytes=layers * nbytes)


def forward_hidden(params: PyTree, tokens: jax.Array, cfg: KimiLinearConfig,
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
    with jax.named_scope("kimi.head"):
        x = _norm(x, params["final_ln"], cfg)
    if not with_routing:
        return x
    return x, (jax.tree.map(lambda *a: jnp.concatenate(a), *routed)
               if routed else None)


def loss_fn(params: PyTree, batch, cfg: KimiLinearConfig,
            sel=None) -> jax.Array:
    """Mean next-token cross-entropy over the held slice of the vocabulary.
    batch = (tokens [B, S], targets [B, S])."""
    tokens, targets = batch
    x = forward_hidden(params, tokens, cfg, sel=sel)
    with jax.named_scope("kimi.head"):
        return fused_nll_sum(x, params["head"], targets - cfg.vocab_start,
                             cfg.ce_chunk_rows) / targets.size


def routing(params: PyTree, tokens: jax.Array, cfg: KimiLinearConfig):
    """The program's own routing on `tokens`, a `dropless_moe.Routing`
    with leaves stacked over the expert layers."""
    return forward_hidden(params, tokens, cfg, with_routing=True)[1]


synthetic_batch = afmoe.synthetic_batch
