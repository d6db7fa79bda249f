"""The `mellum` decoder (JetBrains' Mellum 2, `model_type: mellum`): a
code model whose every layer routes over sparse experts and whose long
context is the product.

  - Attention is sliding-window (1024) in three layers of four and full
    in the fourth (`layer_types`), with rotary positions in EVERY layer
    and of two kinds: plain in the sliding layers, YaRN in the full ones
    (per-pair frequencies and an amplitude on cos and sin,
    `yarn_inv_freq`).  Queries and keys are RMS-normed over the head;
    key-value heads are shared by groups of query heads; no output gate.
  - Every layer's feed-forward is routed experts and nothing beside them:
    softmax scores over all the experts, the top `k` a token, weights
    normalised to 1 (`norm_topk_prob`), no shared expert, no dense layer
    (`parallel/dropless_moe.py`, which computes the part of the experts
    this chip holds and drops no token).
  - Two RMS norms a layer, each on a sub-layer's INPUT:
    `x = x + f(norm(x))`.  The embedding is not scaled; the head is
    untied.

Why a module beside `afmoe.py` and not a second layer function in it:
what the two decoders share is machinery, and that is imported, not
copied: the period scan `afmoe.forward_hidden` (with `_stack_plan`,
`_unstack`, `_remat`), the attention adapter `_attn_fn`, the held slice
of embedding, head and loss (`afmoe.loss_fn`, `synthetic_batch`), and
`dropless_moe.held_experts`.  What differs is everything a model file is
read for: the layer's equations, the parameter tree, the configuration's
fields (no dense width, no shared expert, no route scale; rotary
parameters of two kinds).  One file holding both would be two models
behind one name.

A share of a deployment, as `afmoe.py` says it: `held_experts` names the
experts this chip holds (the router stays `num_experts` wide) and
`vocab_size` is the held slice of the vocabulary, ids `vocab_start ...`.
A share's backward pass holds the weight each token gives the held
experts together constant (`dropless_moe.MoEConfig.hold_held_weight`,
which says why): nothing in this layer holds a branch's size, and without
it a share that trains alone teaches its routers to leave the absent
experts within a few steps.  A model that holds every expert is the
published one, gradient and all.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel import dropless_moe
from . import afmoe
from .afmoe import FULL, SLIDING
from .transformer import _rms_norm, _rope

PyTree = Any


@dataclasses.dataclass(frozen=True)
class Yarn:
    """`rope_parameters.full_attention` of the model's `config.json`."""
    factor: float
    original_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0      # the amplitude of cos and sin


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int                    # rows of embedding and head held here
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int                   # the router's width
    num_experts_per_tok: int
    layer_types: Tuple[str, ...]       # one entry a layer that is run
    sliding_window: int
    held_experts: Optional[Tuple[int, ...]] = None   # None: all of them
    vocab_start: int = 0               # first token id of the held slice
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 500000.0
    yarn: Optional[Yarn] = None        # the full layers'; None: plain
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_impl: str = "dense"           # "dense" | "flash"
    attn_block: int = 0                # as TransformerConfig's
    attn_block_k: int = 0
    remat: bool = True                 # per layer
    remat_policy: str = "none"
    ce_chunk_rows: int = 0             # > 0: streamed head + cross-entropy
    moe_capacity_factor: float = 1.25  # dropless_moe's static buffer
    num_dense_layers = 0               # what afmoe's `_stack_plan` reads

    def __post_init__(self):
        if any(t not in (SLIDING, FULL) for t in self.layer_types):
            raise ValueError(f"layer_types={self.layer_types}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads={self.num_heads} not divisible by "
                             f"num_kv_heads={self.num_kv_heads}")
        if self.head_dim % 2:
            raise ValueError(f"rotary positions need an even head_dim "
                             f"(got {self.head_dim})")
        if self.attn_impl not in ("dense", "flash"):
            raise ValueError(f"attn_impl={self.attn_impl!r}")

    @property
    def held(self) -> Tuple[int, ...]:
        return (tuple(range(self.num_experts)) if self.held_experts is None
                else tuple(self.held_experts))

    @property
    def moe(self) -> dropless_moe.MoEConfig:
        return dropless_moe.MoEConfig(
            num_experts=self.num_experts, top_k=self.num_experts_per_tok,
            held=self.held, route_norm=self.norm_topk_prob,
            score_func="softmax", capacity_factor=self.moe_capacity_factor,
            hold_held_weight=True)


def yarn_inv_freq(head_dim: int, theta: float, yarn: Yarn) -> np.ndarray:
    """YaRN's per-pair frequencies [head_dim / 2], float32: a pair that
    turns more than `beta_fast` times over the original context keeps its
    frequency `theta ** (-2i / head_dim)`, one that turns less than
    `beta_slow` times has it divided by `factor` (its positions
    interpolated), and a linear ramp over the pairs between blends the
    two.  d(r), the pair that turns r times over the original context:
    head_dim ln(original / (2 pi r)) / (2 ln theta)."""
    half = head_dim // 2
    extrap = theta ** (-np.arange(half, dtype=np.float64) / half)

    def pair(turns):
        return (head_dim
                * math.log(yarn.original_positions / (2 * math.pi * turns))
                / (2 * math.log(theta)))
    low = max(math.floor(pair(yarn.beta_fast)), 0)
    high = min(math.ceil(pair(yarn.beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (extrap / yarn.factor * ramp
            + extrap * (1 - ramp)).astype(np.float32)


def _rotary(x, cfg: MellumConfig, kind: str):
    if kind == FULL and cfg.yarn is not None:
        return _rope(x, cfg.rope_theta,
                     yarn_inv_freq(cfg.head_dim, cfg.rope_theta, cfg.yarn),
                     cfg.yarn.attention_factor)
    return _rope(x, cfg.rope_theta)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def init_params(rng: jax.Array, cfg: MellumConfig) -> PyTree:
    """Normal / sqrt(fan_in) weights, unit norm scales.  One group,
    `moe`, its leaves stacked on a leading layer axis, as `afmoe.py`'s."""
    dt = cfg.param_dtype
    D, H, Hkv, Dh = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                     cfg.head_dim)
    n, F, held = (len(cfg.layer_types), cfg.moe_intermediate_size,
                  len(cfg.held))
    keys = iter(jax.random.split(rng, 16))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, dt)
                / math.sqrt(fan_in)).astype(dt)

    return {
        "embed": w((cfg.vocab_size, D), D),
        "head": w((cfg.vocab_size, D), D),
        "final_ln": jnp.ones((D,), dt),
        "moe": {
            "input_ln": jnp.ones((n, D), dt),
            "post_attn_ln": jnp.ones((n, D), dt),
            # [q | k | v] side by side, one product
            "qkv_w": w((n, D, (H + 2 * Hkv) * Dh), D),
            "q_norm": jnp.ones((n, Dh), dt),
            "k_norm": jnp.ones((n, Dh), dt),
            "attn_out_w": w((n, H * Dh, D), H * Dh),
            "router_w": w((n, D, cfg.num_experts), D),
            "expert_gate_w": w((n, held, D, F), D),
            "expert_up_w": w((n, held, D, F), D),
            "expert_down_w": w((n, held, F, D), F),
        },
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _qkv(x, lp, cfg: MellumConfig, kind: str):
    """What a layer's attention call is given: x [B, S, D] -> queries
    [B, H, S, Dh], keys and values [B, Hkv, S, Dh], queries and keys
    normed over the head and turned."""
    dt = cfg.dtype
    B, S, D = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    norm = functools.partial(_rms_norm, bias=None, eps=cfg.rms_norm_eps)
    a = norm(x, lp["input_ln"])
    qkv = jnp.einsum("bsd,de->bse", a, lp["qkv_w"].astype(dt))
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)

    def heads(t):
        return t.reshape(B, S, -1, Dh).transpose(0, 2, 1, 3)
    return (_rotary(norm(heads(q), lp["q_norm"]), cfg, kind),
            _rotary(norm(heads(k), lp["k_norm"]), cfg, kind), heads(v))


def _attention(x, lp, cfg: MellumConfig, kind: str):
    """The attention half of a layer: x [B, S, D] -> x + attn(norm(x))."""
    B, S, D = x.shape
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    with jax.named_scope(f"mellum.attn.{kind}"):
        with jax.named_scope(".qkv"):
            q, k, v = _qkv(x, lp, cfg, kind)
            if Hkv != H:
                k = jnp.repeat(k, H // Hkv, axis=1)
                v = jnp.repeat(v, H // Hkv, axis=1)
        # the kernels and the transpose after them stay the half's own
        ctx = afmoe._attn_fn(cfg, kind)(q, k, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, -1)
        with jax.named_scope(".out"):
            return x + jnp.einsum("bse,ed->bsd", ctx,
                                  lp["attn_out_w"].astype(cfg.dtype))


def _experts_input(x, lp, cfg: MellumConfig):
    """What a layer's router and experts are given: x [B, S, D] normed,
    [B * S, D]."""
    m = _rms_norm(x, lp["post_attn_ln"], None, eps=cfg.rms_norm_eps)
    return m.reshape(-1, x.shape[-1])


def _experts(x, lp, sel, cfg, family: str = "mellum"):
    """The other half: x -> `(x + held experts(norm(x)), routing)`;
    `family` the prefix of its scope (`models/keye.py` has this half
    too)."""
    with jax.named_scope(family + ".moe"):
        m = _experts_input(x, lp, cfg)
        experts = {n: lp["expert_" + n] for n in ("gate_w", "up_w", "down_w")}
        routed, routing = dropless_moe.held_experts(
            m, lp["router_w"], experts, cfg.moe, sel=sel)
    return x + routed.reshape(x.shape), routing


def _layer(x, lp, sel, cfg: MellumConfig, kind: str, is_moe: bool = True):
    """One layer.  x [B, S, D]; returns `(x, routing)`."""
    del is_moe                          # every layer is
    return _experts(_attention(x, lp, cfg, kind), lp, sel, cfg)


def _embed(params, tokens, cfg: MellumConfig):
    with jax.named_scope("mellum.embed"):
        return params["embed"].astype(cfg.dtype)[tokens - cfg.vocab_start]


forward_hidden = functools.partial(afmoe.forward_hidden, layer=_layer,
                                   embed=_embed, family="mellum")
loss_fn = functools.partial(afmoe.loss_fn, hidden=forward_hidden,
                            family="mellum")
routing = functools.partial(afmoe.routing, hidden=forward_hidden)
synthetic_batch = afmoe.synthetic_batch
