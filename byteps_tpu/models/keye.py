"""The language decoder of Kwai-Keye's Keye-VL-2.0 (`model_type: KeyeVL2`):
every layer routes over sparse experts, and every layer's attention runs
over the keys a LEARNED INDEXER picks for each token.

The layer, for input x_t and a_t = rms(x_t; input_ln):

  - Main heads: q_{t,h} = R_t(rms_head(a_t Wq_h)), k_{s,g} =
    R_s(rms_head(a_s Wk_g)), v_{s,g} = a_s Wv_g; 32 query heads over 4
    key-value heads (g = h // 8) of size 128, no bias.
  - R is rotary at `rope_theta` with the head's 64 pairs divided
    `mrope_section` = [16, 24, 24] among THREE position streams
    (temporal, height, width): pair i turns by the stream of its section
    (`transformer._rope`, `positions` and `sections`).  `positions`
    [3, S] or [3, B, S] goes through `forward_hidden` and `loss_fn`; None
    is a text batch, whose three streams are all 0 .. S - 1, which is
    plain rotary.
  - Indexer (`sa_config`: `indexer_num_heads` 16 of `indexer_head_dim`
    64 over `indexer_num_kv_heads` ONE key head, `topk` 2048):
    qI_{t,j} = R'_t(a_t Wqi_j), kI_s = R'_s(rms(a_s Wki; index_k_norm)),
    w_{t,j} = (a_t Ww)_j / sqrt(16 x 64); R' the same rotary on the
    indexer's 32 pairs, sections halved.  The index score is
    I_{t,s} = sum_j w_{t,j} relu(qI_{t,j} . kI_s).
  - Selection: S_t, the min(t + 1, topk) keys s <= t with the highest
    I_{t,s}; ONE set a token, shared by the 32 heads, exact (ties: the
    lowest key first).  `q_chunk_size` and `kv_chunk_size` (512) are read
    as the tile sizes of the source's own computation and change no set.
  - o_{t,h} = sum_{s in S_t} softmax_{s in S_t}(q_{t,h} . k_{s,g} /
    sqrt(128)) v_{s,g};  x <- x + concat_h(o_{t,h}) Wo.
  - x <- x + held experts(rms(x; post_attn_ln)): float32 softmax scores
    over all 128 experts, the top 8, weights over their sum
    (`norm_topk_prob`), no shared expert, no dense layer, as
    `models/mellum.py`'s.

Gradients.  The selection is a constant of the backward pass and the
indexer reads `stop_gradient(a_t)`: its three matrices and its norm are in
the tree and receive exactly zero gradient from the next-token loss, as
autodiff of the published forward gives.  They are COLUMNS of leaves that
the loss does move (`in_w`: every matrix the layer's normed input is
multiplied by, side by side; `k_norm`: both key norms' scales), not leaves
of their own: the benchmark's per-leaf comparison reads a leaf whose
gradient is zero on both sides as a norm ratio of 0 / 0.  The divergence loss that the
published description of this kind of indexer trains it with has no key
in `config.json` and is not here (ROADMAP.md).

What is shared with the other expert decoders is imported, not copied:
the period scan `afmoe.forward_hidden`, the attention adapter
`afmoe._attn_fn` (this layer's kind is `afmoe.SELECTED`), the held slice
of embedding, head and loss, `dropless_moe.held_experts`, and the expert
half of mellum's layer.  A share of a deployment is what `afmoe.py` and
`mellum.py` say it is (`held_experts`, `vocab_size`, `vocab_start`,
`hold_held_weight`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel import dropless_moe
from . import afmoe, mellum
from .afmoe import FULL, SELECTED
from .transformer import _rms_norm, _rope

PyTree = Any


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    vocab_size: int                    # rows of embedding and head held here
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int                   # the router's width
    num_experts_per_tok: int
    num_layers: int                    # layers that are run, all alike
    index_heads: int                   # sa_config.indexer_num_heads
    index_head_dim: int                # sa_config.indexer_head_dim
    index_topk: int                    # sa_config.topk
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    held_experts: Optional[Tuple[int, ...]] = None   # None: all of them
    vocab_start: int = 0               # first token id of the held slice
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_impl: str = "dense"           # "dense" | "flash"
    attn_block: int = 0                # ops/sparse_attention.py's rows
    attn_block_k: int = 0              # and keys of a tile; 0: its rule
    remat: bool = True                 # per layer
    remat_policy: str = "none"         # "selection" keeps a layer's choice
                                       # and its attention's o, lse, bits
    ce_chunk_rows: int = 0             # > 0: streamed head + cross-entropy
    moe_capacity_factor: float = 1.25  # dropless_moe's static buffer
    num_dense_layers = 0               # what afmoe's `_stack_plan` reads

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads={self.num_heads} not divisible by "
                             f"num_kv_heads={self.num_kv_heads}")
        if sum(self.mrope_section) * 2 != self.head_dim:
            raise ValueError(f"mrope_section={self.mrope_section} does not "
                             f"divide the {self.head_dim // 2} pairs")
        scale = self.head_dim // self.index_head_dim
        if (self.head_dim % self.index_head_dim
                or any(n % scale for n in self.mrope_section)):
            raise ValueError(
                f"the indexer's rotary takes the main heads' sections over "
                f"{self.head_dim} / {self.index_head_dim}")
        if self.attn_impl not in ("dense", "flash"):
            raise ValueError(f"attn_impl={self.attn_impl!r}")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """What `afmoe._stack_plan` reads, and the scopes' names: every
        layer attends to all it selects of the whole sequence."""
        return (FULL,) * self.num_layers

    @property
    def index_sections(self) -> Tuple[int, ...]:
        scale = self.head_dim // self.index_head_dim
        return tuple(n // scale for n in self.mrope_section)

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


def rotary(x, cfg: KeyeConfig, positions=None, sections=None):
    """x [B, heads, S, size] turned; `positions` None (text) or the three
    streams [3, S] / [3, B, S]; `sections` the main heads' unless given."""
    if positions is None:
        return _rope(x, cfg.rope_theta)
    return _rope(x, cfg.rope_theta, positions=positions,
                 sections=sections or cfg.mrope_section)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def init_params(rng: jax.Array, cfg: KeyeConfig) -> PyTree:
    """Normal / sqrt(fan_in) weights, unit norm scales.  One group,
    `moe`, its leaves stacked on a leading layer axis, as `afmoe.py`'s."""
    dt = cfg.param_dtype
    D, H, Hkv, Dh = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                     cfg.head_dim)
    J, Di = cfg.index_heads, cfg.index_head_dim
    n, F, held = cfg.num_layers, cfg.moe_intermediate_size, len(cfg.held)
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
            # [q | k | v | indexer's queries | its key | its weights] side
            # by side: every product of the layer's normed input
            "in_w": w((n, D, (H + 2 * Hkv) * Dh + (J + 1) * Di + J), D),
            "q_norm": jnp.ones((n, Dh), dt),
            # [the main keys' scale | the indexer key's]
            "k_norm": jnp.ones((n, Dh + Di), dt),
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
def _heads(t, size):
    B, S, _ = t.shape
    return t.reshape(B, S, -1, size).transpose(0, 2, 1, 3)


def _qkv(a, lp, cfg: KeyeConfig, positions=None):
    """The main heads of the layer's normed input a [B, S, D]: queries
    [B, H, S, Dh], keys and values [B, Hkv, S, Dh] (not repeated), queries
    and keys normed over the head and turned."""
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    norm = functools.partial(_rms_norm, bias=None, eps=cfg.rms_norm_eps)
    qkv = jnp.einsum("bsd,de->bse", a,
                     lp["in_w"][:, :(H + 2 * Hkv) * Dh].astype(cfg.dtype))
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], axis=-1)
    return (rotary(norm(_heads(q, Dh), lp["q_norm"]), cfg, positions),
            rotary(norm(_heads(k, Dh), lp["k_norm"][:Dh]), cfg, positions),
            _heads(v, Dh))


def _index(a, lp, cfg: KeyeConfig, positions=None):
    """The indexer's `(queries [B, J, S, Di], key [B, S, Di], weights
    [B, S, J] float32)` of the layer's normed input, which it reads as a
    constant.  Queries and key in the activations' precision: their
    products are the index scores' (`ops/sparse_attention.py`)."""
    dt, J, Di = cfg.dtype, cfg.index_heads, cfg.index_head_dim
    a = lax.stop_gradient(a)
    first = (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
    q_w, k_w, w_w = jnp.split(lp["in_w"][:, first:].astype(dt),
                              [J * Di, (J + 1) * Di], axis=-1)
    turn = functools.partial(rotary, cfg=cfg, positions=positions,
                             sections=cfg.index_sections)
    qi = turn(_heads(jnp.einsum("bsd,de->bse", a, q_w), Di))
    ki = _rms_norm(jnp.einsum("bsd,de->bse", a, k_w),
                   lp["k_norm"][cfg.head_dim:], None, eps=cfg.rms_norm_eps)
    ki = turn(ki[:, None])[:, 0]
    w = jnp.einsum("bsd,dj->bsj", a, w_w,
                   preferred_element_type=jnp.float32)
    return qi, ki, w / math.sqrt(J * Di)


def _attention(x, lp, cfg: KeyeConfig, kind: str, positions=None):
    """The attention half of a layer: x [B, S, D] -> `(x + attn(norm(x)),
    kept [B, S])`, `kept` the pairs the attention kept a row."""
    B, S, D = x.shape
    with jax.named_scope(f"keye.attn.{kind}"):
        with jax.named_scope(".qkv"):
            a = _rms_norm(x, lp["input_ln"], None, eps=cfg.rms_norm_eps)
            q, k, v = _qkv(a, lp, cfg, positions)
        with jax.named_scope(".index"):
            index = _index(a, lp, cfg, positions)
        # `.select` and `.sparse` are opened by the call
        ctx, kept = afmoe._attn_fn(cfg, SELECTED)(q, k, v, index)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, -1)
        with jax.named_scope(".out"):
            return x + jnp.einsum("bse,ed->bsd", ctx,
                                  lp["attn_out_w"].astype(cfg.dtype)), kept


def _layer(x, lp, sel, cfg: KeyeConfig, kind: str, is_moe: bool = True,
           positions=None):
    """One layer.  x [B, S, D]; returns `(x, (routing, kept))`."""
    del is_moe                          # every layer is
    x, kept = _attention(x, lp, cfg, kind, positions)
    x, routing = mellum._experts(x, lp, sel, cfg, family="keye")
    return x, (routing, lax.stop_gradient(kept))


def _embed(params, tokens, cfg: KeyeConfig):
    with jax.named_scope("keye.embed"):
        return params["embed"].astype(cfg.dtype)[tokens - cfg.vocab_start]


def forward_hidden(params: PyTree, tokens: jax.Array, cfg: KeyeConfig,
                   sel=None, with_routing: bool = False, positions=None):
    """`afmoe.forward_hidden` with this family's layer; `positions` the
    three streams or None (text).  With `with_routing` the second result
    is `(Routing, kept [layers, B, S])`."""
    return afmoe.forward_hidden(
        params, tokens, cfg, sel=sel, with_routing=with_routing,
        layer=functools.partial(_layer, positions=positions), embed=_embed,
        family="keye")


def loss_fn(params: PyTree, batch, cfg: KeyeConfig, sel=None,
            positions=None) -> jax.Array:
    return afmoe.loss_fn(
        params, batch, cfg, sel=sel,
        hidden=functools.partial(forward_hidden, positions=positions),
        family="keye")


def routing(params: PyTree, tokens: jax.Array, cfg: KeyeConfig,
            positions=None):
    """The program's own choice of experts, a `dropless_moe.Routing` with
    leaves stacked over the layers."""
    return forward_hidden(params, tokens, cfg, with_routing=True,
                          positions=positions)[1][0]


def kept_keys(params: PyTree, tokens: jax.Array, cfg: KeyeConfig,
              positions=None):
    """The counter: [layers, B, S], the keys every row's attention kept.
    min(t + 1, topk) in every row, or selection and attention disagree."""
    return forward_hidden(params, tokens, cfg, with_routing=True,
                          positions=positions)[1][1]


def _indexers(params: PyTree, tokens: jax.Array, cfg: KeyeConfig,
              positions=None):
    """Every layer's indexer operands `(qi, ki, w)` (`_index`), the layers
    walked as the step walks them."""
    x = _embed(params, tokens, cfg)
    for i, kind in enumerate(cfg.layer_types):
        lp = jax.tree.map(lambda leaf: leaf[i], params["moe"])
        a = _rms_norm(x, lp["input_ln"], None, eps=cfg.rms_norm_eps)
        yield _index(a, lp, cfg, positions)
        x, _ = _layer(x, lp, None, cfg, kind, positions=positions)


def chosen_keys(params: PyTree, tokens: jax.Array, cfg: KeyeConfig,
                positions=None):
    """The program's own selection, for whoever checks it: uint32
    [layers, B, S, S / 32], bit b of word c of row t set where the row
    takes key 32 c + b.  The mask is the attention kernels' own
    (`sparse_attention.keep_mask`) or, without kernels, `dense_keep`'s."""
    from ..ops import sparse_attention
    B, S = tokens.shape
    out = []
    for qi, ki, w in _indexers(params, tokens, cfg, positions):
        if cfg.attn_impl == "flash":
            kit = ki.transpose(0, 2, 1)
            keep = sparse_attention.keep_mask(
                qi, kit, sparse_attention.select(
                    qi, kit, w, cfg.index_topk, cfg.attn_block_k),
                cfg.attn_block, cfg.attn_block_k)
        else:
            keep = sparse_attention.dense_keep(qi, ki, w, cfg.index_topk)
        bits = keep.reshape(B, S, S // 32, 32).astype(jnp.uint32)
        out.append((bits << jnp.arange(32, dtype=jnp.uint32)).sum(
            -1, dtype=jnp.uint32))
    return jnp.stack(out)


def select_passes(params: PyTree, tokens: jax.Array, cfg: KeyeConfig,
                  positions=None):
    """[layers, B, S] float32: the passes over its slab of scores that
    `index_topk` ran in each row's block, the longer count where a row of
    the block had a tie at its threshold to break
    (`sparse_attention.select_pass_counts`)."""
    from ..ops import sparse_attention
    return jnp.stack([
        sparse_attention.select_passes(sparse_attention.select(
            qi, ki.transpose(0, 2, 1), w, cfg.index_topk, cfg.attn_block_k),
            cfg.index_heads)
        for qi, ki, w in _indexers(params, tokens, cfg, positions)])


synthetic_batch = afmoe.synthetic_batch
