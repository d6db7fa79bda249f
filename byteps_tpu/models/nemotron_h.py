"""The `nemotron_h` decoder (NVIDIA's Nemotron-H family,
`model_type: nemotron_h`): a language model whose layers are ONE PART
each.  `hybrid_override_pattern` names every layer's part by a letter,

    M   a Mamba-2 mixer          E   a sparse-expert feed-forward
    *   grouped attention

and a layer is `x = x + f(rms(x; norm))`, f its part; after the last
layer a final RMS norm and the UNTIED head.

    M:  [z | xBC | dt] = u W_in;  xBC = silu(conv1d(xBC)), causal,
        depthwise, with bias;  [x | B | C] = xBC, x as heads of
        `mamba_d_head`, B and C shared by the heads of a GROUP (8 groups
        of 8 heads in the published model);  dt = softplus(dt + dt_bias),
        A = -exp(A_log);  S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,
        y_t = S_t C_t + D x_t (`ops/ssd.py`, chunked);
        y = rms(y * silu(z); gate_norm) over each group's stretch of the
        inner width apart;  then W_out.  `d_inner` is heads x head size,
        whatever the hidden size.
    E:  s = sigmoid(u W_r), float32;  the top k of s + b, b the
        `e_score_correction_bias`, a buffer (the leaf `expert_bias` where
        the tree has it, else zero) that moves the choice alone;
        w = route_scale * s[choice] / sum s[choice];
        f = sum_j w_j relu(u V_e_j)^2 U_e_j + relu(u V_s)^2 U_s: an expert
        is TWO matrices with a squared ReLU between them, the shared one
        the same form at its own width (`parallel/dropless_moe.py`, which
        computes the part of the experts this chip holds and drops no
        token).
    *:  q, k, v = u Wq, u Wk, u Wv, no bias and NO positions; causal
        softmax(q k^T / sqrt(head_dim)) v, a key-value head serving
        heads / kv_heads query heads; then Wo.

Why a module beside `granite_hybrid.py` and not that one grown: there a
layer is a mixer AND a shared MLP, its tree one group of leaves a run of
a period, its head tied and its four multipliers part of every equation;
here a layer is one part, a third of the layers are expert layers with a
router, and the published order (`MEMEM*EMEMEM*E...`, 52 letters) is
tiled by no period, so the leaves are stacked by KIND (`layer_plan`).
Two model files that each read as their model; what they share is
imported, not copied: the mixer and the attention part themselves
(`granite_hybrid._mamba`, `_attention`, called under this family's scope
names, the mixer told to norm by groups), and through them the scan, the
flash adapter and `_rms_norm`; `afmoe._unstack`; the streamed
cross-entropy `fused_nll_sum`; `dropless_moe.held_experts`.

The plan by kind.  Three stacks of leaves, `params["mamba"]`, `["moe"]`,
`["attention"]`, each stacked on a leading axis over the layers of its
kind in the order they appear; the layers are walked in the published
order, layer i of kind c taking the next set of c's leaves (a `lax.split`
a stack, whose transpose is one concatenate: no leaf is cut or joined
otherwise).  The walk is unrolled, each layer rematerialised
(`jax.checkpoint`): a `lax.scan` over the layers with a `lax.switch` on
the kind would compile three bodies whatever the depth, but its backward
pass adds a gradient the size of EVERY stack at every layer (the
transpose of a dynamic index into a closed-over stack), 2.7 GB a layer
at the published widths.  So compile time grows with the layers that are
run: nine here (a pipeline stage), about a minute.

No switches, as `granite_hybrid.py`: flash attention at the block its own
rule picks (the STREAMING kernels where a head's K and V pass the
resident budget: 16,384 positions at head size 128), every layer
rematerialised, the head streamed `ce_chunk_rows` rows at a time.  A
sequence is a multiple of 128 positions.

What a rematerialised layer KEEPS (`KEPT_NAMES`, the policy
`save_only_these_names` of the one `jax.checkpoint` call; no option).  A
layer's backward pass makes the layer's values again from its input, but
for what costs most to make again a byte, which the forward pass names
and the layer holds until its backward pass, an array a layer (the walk
is unrolled: no scan stacks them).  A layer and sequence of 16,384 at the
published widths:

    *   the flash call's `o` and `lse` (`flash_attention.KEPT_NAME`):
        134 + 2 MB; the recompute calls no forward kernel, the two
        backward kernels read what the one call wrote
    M   `in_proj`'s result before the split (`granite_hybrid.IN_PROJ_NAME`),
        [16384, 10304] bfloat16, 338 MB; the recompute starts at the
        convolution (the input norm is made again: `in_proj_w`'s gradient
        reads it)
    E   the router's logits, `sel`, `weights`, the plan's sorted list and
        each held expert's start and end (`dropless_moe.ROUTING_NAME`):
        9.6 MB; no score product, top-k, sort or count a second time

1.53 GB over the cell's nine layers (`bps_remat_kept_bytes{name}`), for
43 of the 100 ms a step the recompute cost.  The rest is made again: the
convolution, the scan's forward kernel, the gated norm, `qkv`, the
gather and the experts' up products are 120-340 MB each a layer for less
time a byte, in a cell with 2.3 GB left (PERF.md, Findings, PR 49).  A
kept value is the one the forward pass made, where a
recompute makes it from the same operands: same mathematics, and the
same bits wherever the compiler rounds the two alike.

A share of a deployment, as `afmoe.py` says it: `layer_kinds` lists the
layers that are run (a pipeline stage's), `held_experts` the experts of
every expert layer this chip holds (the router stays `num_experts` wide,
the shared expert whole), `vocab_size` the held slice of the vocabulary,
ids `vocab_start ...`.  A share's backward pass holds the weight each
token gives the held experts together constant
(`dropless_moe.MoEConfig.hold_held_weight`, which says why).

Parameters float32, compute `dtype`; the scan's dt, decays, cumulative
sums and carried state and the router's scores are float32 whatever
`dtype` is.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ..common import telemetry
from ..ops import flash_attention
from ..parallel import dropless_moe
from . import granite_hybrid
from .afmoe import _unstack
from .transformer import _rms_norm, fused_nll_sum

PyTree = Any
MAMBA, MOE, ATTENTION = "mamba", "moe", "attention"
# `hybrid_override_pattern`'s letters.  The family's fourth, "-" (a dense
# MLP layer), is in no published pattern this module was written for.
LETTERS = {"M": MAMBA, "E": MOE, "*": ATTENTION}
# What a rematerialised layer keeps from its forward pass, by name (the
# module's docstring says why these and no more).
KEPT_NAMES = (flash_attention.KEPT_NAME, granite_hybrid.IN_PROJ_NAME,
              dropless_moe.ROUTING_NAME)


def kinds_of(pattern: str) -> Tuple[str, ...]:
    """`hybrid_override_pattern` -> one kind a layer."""
    try:
        return tuple(LETTERS[c] for c in pattern)
    except KeyError as e:
        raise ValueError(f"hybrid_override_pattern {pattern!r}: no layer "
                         f"of kind {e.args[0]!r} is written here") from None


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int                    # rows of embedding and head held here
    hidden_size: int
    layer_kinds: Tuple[str, ...]       # one entry a layer that is run
    num_heads: int                     # attention
    num_kv_heads: int
    head_dim: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_n_groups: int
    moe_intermediate_size: int         # a routed expert's width
    moe_shared_intermediate_size: int  # the shared expert's
    num_experts: int                   # the router's width
    num_experts_per_tok: int
    held_experts: Optional[Tuple[int, ...]] = None   # None: all of them
    route_scale: float = 1.0
    route_norm: bool = True
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    vocab_start: int = 0               # first token id of the held slice
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16          # of the activations
    ce_chunk_rows: int = 2048          # rows a block of the streamed head
    moe_capacity_factor: float = 1.25  # dropless_moe's static buffer

    def __post_init__(self):
        if any(t not in (MAMBA, MOE, ATTENTION) for t in self.layer_kinds):
            raise ValueError(f"layer_kinds={self.layer_kinds}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads={self.num_heads} not divisible by "
                             f"num_kv_heads={self.num_kv_heads}")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(f"{self.mamba_n_heads} mamba heads in "
                             f"{self.mamba_n_groups} groups")

    # what `granite_hybrid._mamba` and `_attention` read of a configuration
    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def attention_multiplier(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_kinds)

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


def layer_plan(cfg) -> Tuple[Tuple[str, int], ...]:
    """`(kind, index in that kind's stack)` of every layer, in the order
    the layers run: the plan BY KIND.  The tree holds one stack of leaves
    a kind that occurs, `params[kind]`, `[layers of the kind, ...]`."""
    seen = {}
    plan = []
    for kind in cfg.layer_kinds:
        plan.append((kind, seen.get(kind, 0)))
        seen[kind] = plan[-1][1] + 1
    return tuple(plan)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def init_params(rng: jax.Array, cfg: NemotronHConfig) -> PyTree:
    """Normal / sqrt(fan_in) matrices, unit norm scales, and the scan's own
    leaves as mamba_ssm makes them (`granite_hybrid.init_params` says
    how).  The load balancer's `expert_bias` is no parameter and is not
    made here: a `moe` stack that has the leaf ([layers, experts]) adds it
    to the scores before the top-k, one that lacks it runs with zero."""
    dt = jnp.float32
    D = cfg.hidden_size
    keys = iter(jax.random.split(rng, 24))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, dt)
                / math.sqrt(fan_in)).astype(dt)

    def conv(shape):
        bound = 1.0 / math.sqrt(cfg.mamba_d_conv)
        return jax.random.uniform(next(keys), shape, dt, -bound, bound)

    def mamba(n):
        H, I, C = cfg.mamba_n_heads, cfg.d_inner, cfg.conv_dim
        step = jnp.exp(jax.random.uniform(
            next(keys), (n, H), dt, math.log(1e-3), math.log(1e-1)))
        return {
            "input_ln": jnp.ones((n, D), dt),
            "in_proj_w": w((n, D, I + C + H), D),       # [z | xBC | dt]
            "conv_w": conv((n, cfg.mamba_d_conv, C)),   # taps in front
            "conv_b": conv((n, C)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jax.random.uniform(next(keys), (n, H), dt,
                                                1.0, 16.0)),
            "D": jnp.ones((n, H), dt),
            "gate_norm": jnp.ones((n, I), dt),
            "out_proj_w": w((n, I, D), I),
        }

    def moe(n):
        F, Fs, held = (cfg.moe_intermediate_size,
                       cfg.moe_shared_intermediate_size, len(cfg.held))
        return {
            "input_ln": jnp.ones((n, D), dt),
            "router_w": w((n, D, cfg.num_experts), D),
            "shared_up_w": w((n, D, Fs), D),
            "shared_down_w": w((n, Fs, D), Fs),
            "expert_up_w": w((n, held, D, F), D),
            "expert_down_w": w((n, held, F, D), F),
        }

    def attention(n):
        Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        return {
            "input_ln": jnp.ones((n, D), dt),
            "qkv_w": w((n, D, (Hq + 2 * Hkv) * Dh), D),     # [q | k | v]
            "attn_out_w": w((n, Hq * Dh, D), Hq * Dh),
        }

    out = {"embed": w((cfg.vocab_size, D), D),
           "head": w((cfg.vocab_size, D), D),
           "final_ln": jnp.ones((D,), dt)}
    for kind, make in ((MAMBA, mamba), (MOE, moe), (ATTENTION, attention)):
        if cfg.count(kind):
            out[kind] = make(cfg.count(kind))
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _relu2(x, up_w, down_w, dt):
    h = jnp.einsum("bsd,df->bsf", x, up_w.astype(dt))
    return jnp.einsum("bsf,fd->bsd", jnp.square(jax.nn.relu(h)),
                      down_w.astype(dt))


def _experts_input(x, lp, cfg: NemotronHConfig):
    """What an expert layer's router and experts are given: x [B, S, D]
    normed, [B * S, D]."""
    m = _rms_norm(x, lp["input_ln"], None, eps=cfg.rms_norm_eps)
    return m.reshape(-1, x.shape[-1])


def _moe(x, lp, sel, cfg: NemotronHConfig):
    """An expert layer's part: x [B, S, D] -> `(f, routing)`, f the shared
    expert plus the held routed ones."""
    with jax.named_scope("nemotronh.moe"):
        m = _experts_input(x, lp, cfg)
        routed, routing = dropless_moe.held_experts(
            m, lp["router_w"],
            {"up_w": lp["expert_up_w"], "down_w": lp["expert_down_w"]},
            cfg.moe, expert_bias=lp.get("expert_bias"), sel=sel)
        with jax.named_scope(".shared"):
            shared = _relu2(m.reshape(x.shape), lp["shared_up_w"],
                            lp["shared_down_w"], cfg.dtype)
        return shared + routed.reshape(x.shape), routing


def _layer(x, lp, sel, cfg: NemotronHConfig, kind: str):
    """One layer.  x [B, S, D] -> `(x, routing or None)`."""
    if kind == MOE:
        f, routing = _moe(x, lp, sel, cfg)
        return x + f, routing
    if kind == MAMBA:
        return x + granite_hybrid._mamba(
            x, lp, cfg, "nemotronh.mamba", cfg.mamba_n_groups), None
    return x + granite_hybrid._attention(x, lp, cfg, "nemotronh.attn"), None


def _embed(params, tokens, cfg: NemotronHConfig):
    with jax.named_scope("nemotronh.embed"):
        return params["embed"].astype(cfg.dtype)[tokens - cfg.vocab_start]


def _record_plan(cfg: NemotronHConfig, batch: int, seq_len: int) -> None:
    kinds = [k for k in (MAMBA, MOE, ATTENTION) if cfg.count(k)]
    telemetry.record_static("layer_plan", stacks=len(kinds))
    for kind in kinds:
        telemetry.record_static("layer_plan", labels={"kind": kind},
                                layers=cfg.count(kind))
    if cfg.count(MAMBA):
        granite_hybrid._record_scan(cfg, batch, seq_len)
    granite_hybrid._record_kept(
        cfg, batch, seq_len, KEPT_NAMES,
        {dropless_moe.ROUTING_NAME: (
            cfg.count(MOE), cfg.moe.kept_bytes(batch * seq_len))})


def forward_hidden(params: PyTree, tokens: jax.Array, cfg: NemotronHConfig,
                   sel=None, with_routing: bool = False):
    """tokens [B, S] int32 (ids of the held slice) -> the final hidden
    states [B, S, D], after the last norm.

    `sel` [expert layers, B*S, k] replaces every router's own top-k (see
    `dropless_moe.route`).  With `with_routing` the result is
    `(hidden, Routing)`, the `Routing`'s leaves stacked over the expert
    layers."""
    _record_plan(cfg, *tokens.shape)
    x = _embed(params, tokens, cfg)
    leaves = {kind: _unstack(params[kind], cfg.count(kind))
              for kind in (MAMBA, MOE, ATTENTION) if cfg.count(kind)}
    routed = []
    keep = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)
    for kind, j in layer_plan(cfg):
        one = jax.checkpoint(functools.partial(_layer, cfg=cfg, kind=kind),
                             policy=keep)
        x, r = one(x, leaves[kind][j],
                   sel[j] if kind == MOE and sel is not None else None)
        if kind == MOE:
            routed.append(r)
    with jax.named_scope("nemotronh.head"):
        x = _rms_norm(x, params["final_ln"], None, eps=cfg.rms_norm_eps)
    if not with_routing:
        return x
    return x, jax.tree.map(lambda *a: jnp.stack(a), *routed)


def head_logits(x: jax.Array, head: jax.Array) -> jax.Array:
    """Float32 logits of `x` [..., D] over the rows of `head` [V, D]: the
    held slice's columns of the whole head's logits."""
    return jnp.einsum("...d,vd->...v", x, head.astype(x.dtype),
                      preferred_element_type=jnp.float32)


def loss_fn(params: PyTree, batch, cfg: NemotronHConfig,
            sel=None) -> jax.Array:
    """Mean next-token cross-entropy over the held slice of the vocabulary.
    batch = (tokens [B, S], targets [B, S])."""
    tokens, targets = batch
    x = forward_hidden(params, tokens, cfg, sel=sel)
    with jax.named_scope("nemotronh.head"):
        return fused_nll_sum(x, params["head"], targets - cfg.vocab_start,
                             cfg.ce_chunk_rows) / targets.size


def routing(params: PyTree, tokens: jax.Array, cfg: NemotronHConfig):
    """The program's own routing on `tokens`, a `dropless_moe.Routing`
    with leaves stacked over the expert layers."""
    return forward_hidden(params, tokens, cfg, with_routing=True)[1]


synthetic_batch = granite_hybrid.synthetic_batch
