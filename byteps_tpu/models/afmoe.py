"""The `afmoe` decoder (Arcee's Trinity family, `model_type: afmoe`): a
language model whose layers are of more than one kind.

  - Attention is sliding-window in most layers and full in every n-th
    (`layer_types`); rotary positions are applied in the sliding layers
    only.  Queries and keys are RMS-normed over the head, a sigmoid gate
    computed from the layer's input multiplies the context before the
    output projection, key-value heads are shared by groups of query
    heads, and the head size is the model's own, not `hidden / heads`.
  - The first `num_dense_layers` layers have a dense SwiGLU; the others a
    shared SwiGLU expert plus routed experts: sigmoid scores over all the
    experts (plus `expert_bias`, the load balancer's buffer, where the
    tree has the leaf, for the choice alone), the top `k` a token,
    weights normalised to `route_scale` (`parallel/dropless_moe.py`,
    which computes the part of the experts this chip holds and drops no
    token).
  - Four RMS norms a layer: the sub-layer's input and its output are both
    normed, `x = x + norm(f(norm(x)))`.
  - The embedding is scaled by sqrt(hidden) (`mup_enabled`); the head is
    untied.

Why a module beside `transformer.py` and not more knobs in it: that model
is ONE stacked block under one `lax.scan`, and its parameter tree, specs
and pipeline stacking all rest on the layers being alike.  Here the
layers differ in what they hold (dense or experts) and in what is static
for the kernel (the window), so the stack is scanned period by period
(`_stack_plan`).  What the two share is imported from there, not copied:
the flash adapter, `_rms_norm`, `_rope`, the streamed cross-entropy
`fused_nll_sum`, and the remat policies' convention.

A share of a deployment.  `AfmoeConfig.held_experts` names the experts of
every layer that this chip holds (the router stays `num_experts` wide)
and `vocab_size` is the slice of the vocabulary it holds, ids
`vocab_start ...`: embedding, head, logits and loss are over the slice.
With every expert held and the whole vocabulary it is the whole model.
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
from .transformer import (_rms_norm, _rope, dense_attention,
                          flash_attention_fn, fused_nll_sum)

PyTree = Any
SLIDING, FULL = "sliding_attention", "full_attention"
# not `layer_types` entries of this model: the kinds of `models/keye.py`
# and of `models/sdar.py`
SELECTED = "selected_attention"
BLOCK_DIFFUSION = "block_diffusion"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int                    # rows of embedding and head held here
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int             # the dense layers' SwiGLU
    moe_intermediate_size: int         # every expert's, shared or routed
    num_experts: int                   # the router's width
    num_experts_per_tok: int
    layer_types: Tuple[str, ...]       # one entry a layer that is run
    num_dense_layers: int
    sliding_window: int
    held_experts: Optional[Tuple[int, ...]] = None   # None: all of them
    vocab_start: int = 0               # first token id of the held slice
    num_shared_experts: int = 1
    route_scale: float = 1.0
    route_norm: bool = True
    score_func: str = "sigmoid"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    mup_enabled: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_impl: str = "dense"           # "dense" | "flash"
    attn_block: int = 0                # as TransformerConfig's
    attn_block_k: int = 0
    remat: bool = True                 # per layer
    remat_policy: str = "none"         # see `_remat`
    ce_chunk_rows: int = 0             # > 0: streamed head + cross-entropy
    moe_capacity_factor: float = 1.25  # dropless_moe's static buffer
    # What `post_attn_ln`'s scale starts at.  At 1 a random model routes
    # unevenly: its attention is flat, every row averages thousands of
    # keys into a vector that hardly differs from its neighbours', the
    # norm after the attention blows that up to unit size, and the routers
    # send whole stretches of a sequence to the same few experts (on the
    # chip at the published widths: a held expert at up to 2.6 times the
    # mean, the held rows 0.63-1.56 a token).  At 0.1 the attention's
    # branch starts as a tenth of the stream, as schemes that start a
    # residual branch small do, and routing is even to a few percent.
    post_attn_norm_init: float = 1.0

    def __post_init__(self):
        if any(t not in (SLIDING, FULL) for t in self.layer_types):
            raise ValueError(f"layer_types={self.layer_types}")
        if not 0 <= self.num_dense_layers <= len(self.layer_types):
            raise ValueError(f"num_dense_layers={self.num_dense_layers}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads={self.num_heads} not divisible by "
                             f"num_kv_heads={self.num_kv_heads}")
        if self.head_dim % 2:
            raise ValueError(f"rotary positions need an even head_dim "
                             f"(got {self.head_dim})")
        if self.attn_impl not in ("dense", "flash"):
            raise ValueError(f"attn_impl={self.attn_impl!r}")
        if self.num_shared_experts != 1:
            raise ValueError("one shared expert is what is written here")

    @property
    def held(self) -> Tuple[int, ...]:
        return (tuple(range(self.num_experts)) if self.held_experts is None
                else tuple(self.held_experts))

    @property
    def moe(self) -> dropless_moe.MoEConfig:
        return dropless_moe.MoEConfig(
            num_experts=self.num_experts, top_k=self.num_experts_per_tok,
            held=self.held, route_scale=self.route_scale,
            route_norm=self.route_norm, score_func=self.score_func,
            capacity_factor=self.moe_capacity_factor)


def _stack_plan(cfg):
    """`[(key, kinds of one period, periods)]`: the dense layers, then the
    expert layers.  Each group's leaves are stacked on a leading layer
    axis and scanned a period at a time, the period's layers unrolled in
    the scan's body because the window is static for the kernel: compile
    time is one period's, whatever the depth.  The period is the shortest
    that tiles the group; a group its pattern does not tile (the published
    30 expert layers start mid-period) is one period, wholly unrolled."""
    plan = []
    nd = cfg.num_dense_layers
    for key, kinds in (("dense", cfg.layer_types[:nd]),
                       ("moe", cfg.layer_types[nd:])):
        n = len(kinds)
        if n:
            p = next(p for p in range(1, n + 1)
                     if n % p == 0 and kinds == kinds[:p] * (n // p))
            plan.append((key, kinds[:p], n // p))
    return plan


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def init_params(rng: jax.Array, cfg: AfmoeConfig) -> PyTree:
    """Normal / sqrt(fan_in) weights, unit norm scales but for the one
    after the attention (`post_attn_norm_init`).  The load balancer's
    `expert_bias` is no parameter and is not made here: a layer group that
    has the leaf ([layers, experts]) adds it to the scores before the
    top-k (`dropless_moe.route`), one that lacks it runs with zero."""
    dt = cfg.param_dtype
    D, H, Hkv, Dh = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                     cfg.head_dim)
    keys = iter(jax.random.split(rng, 32))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, dt)
                / math.sqrt(fan_in)).astype(dt)

    def attention(n):
        return {
            "input_ln": jnp.ones((n, D), dt),
            "post_attn_ln": jnp.full((n, D), cfg.post_attn_norm_init, dt),
            "pre_mlp_ln": jnp.ones((n, D), dt),
            "post_mlp_ln": jnp.ones((n, D), dt),
            # [q | k | v | gate] side by side, one product
            "qkvg_w": w((n, D, (2 * H + 2 * Hkv) * Dh), D),
            "q_norm": jnp.ones((n, Dh), dt),
            "k_norm": jnp.ones((n, Dh), dt),
            "attn_out_w": w((n, H * Dh, D), H * Dh),
        }

    def swiglu(lead, width, prefix):
        return {prefix + "gate_w": w((*lead, D, width), D),
                prefix + "up_w": w((*lead, D, width), D),
                prefix + "down_w": w((*lead, width, D), width)}

    out = {"embed": w((cfg.vocab_size, D), D),
           "head": w((cfg.vocab_size, D), D),
           "final_ln": jnp.ones((D,), dt)}
    nd = cfg.num_dense_layers
    nm = len(cfg.layer_types) - nd
    if nd:
        out["dense"] = {**attention(nd),
                        **swiglu((nd,), cfg.intermediate_size, "mlp_")}
    if nm:
        F = cfg.moe_intermediate_size
        out["moe"] = {**attention(nm),
                      "router_w": w((nm, D, cfg.num_experts), D),
                      **swiglu((nm,), F, "shared_"),
                      **swiglu((nm, len(cfg.held)), F, "expert_")}
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _positional(cfg, window):
    """`(q, k, v) -> ctx`, all [B, H, S, Dh], causal, over the keys a row's
    POSITION leaves it: all before it, or a `window` of them.  A window
    that reaches past the sequence is full attention."""
    if cfg.attn_impl == "flash":
        def flash(q, k, v):
            w = window if window is not None and window < q.shape[2] else None
            return flash_attention_fn(q, k, v, True, cfg.attn_block,
                                      cfg.attn_block_k, window=w)
        return flash
    if window is None:
        return functools.partial(dense_attention, causal=True)

    def dense_windowed(q, k, v):
        s = q.shape[2]
        i = jnp.arange(s)[:, None]
        j = jnp.arange(s)[None, :]
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
        logits = logits / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
        logits = jnp.where((i >= j) & (i - j < window), logits,
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return dense_windowed


def _selected(cfg):
    """`(q, k, v, index) -> (ctx, kept)`: causal attention over the
    `cfg.index_topk` keys an indexer picks for each row
    (`ops/sparse_attention.py`, which says how).  q [B, H, S, Dh]; k and
    v [B, Hkv, S, Dh], NOT repeated over the query heads; `index` the
    indexer's `(queries [B, J, S, Di], keys [B, S, Di], weights
    [B, S, J])`, read as constants.  `kept` [B, S] counts the pairs the
    attention kept a row."""
    from ..ops import sparse_attention

    def selected(q, k, v, index):
        if cfg.attn_impl == "flash":
            return sparse_attention.selected_attention(
                q, k, v, *index, cfg.index_topk, cfg.attn_block,
                cfg.attn_block_k)
        return sparse_attention.selected_attention_dense(
            q, k, v, *index, cfg.index_topk)
    return selected


def _block_diffusion(cfg):
    """`(q, k, v) -> ctx`, all [B, H, 2 L, Dh]: attention over the two
    copies of a sequence, clean then noised, under the block-diffusion
    mask of `cfg.block_length` (`ops/flash_attention.py` `bd_tile` has
    the rule).  The mask is the kernels' alone: there is no dense form."""
    def block_diffusion(q, k, v):
        return flash_attention_fn(
            q, k, v, False, cfg.attn_block, cfg.attn_block_k,
            block_diffusion=(q.shape[2] // 2, cfg.block_length))
    return block_diffusion


# A layer's kind -> what builds its attention call from the configuration.
_ATTENTION = {
    SLIDING: lambda cfg: _positional(cfg, cfg.sliding_window),
    FULL: lambda cfg: _positional(cfg, None),
    SELECTED: _selected,
    BLOCK_DIFFUSION: _block_diffusion,
}


def _attn_fn(cfg, kind: str):
    """The attention call of a layer of `kind`, for every decoder of this
    package: one table, which a new kind joins."""
    return _ATTENTION[kind](cfg)


def _swiglu(x, lp, prefix: str, dt):
    gate = jnp.einsum("bsd,df->bsf", x, lp[prefix + "gate_w"].astype(dt))
    up = jnp.einsum("bsd,df->bsf", x, lp[prefix + "up_w"].astype(dt))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                      lp[prefix + "down_w"].astype(dt))


def _gated(ctx, g):
    """The attention's output gate."""
    return ctx * jax.nn.sigmoid(g)


def _attention(x, lp, cfg: AfmoeConfig, kind: str):
    """The attention half of a layer: x [B, S, D] -> x + norm(attn)."""
    dt = cfg.dtype
    B, S, D = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    norm = functools.partial(_rms_norm, bias=None, eps=cfg.rms_norm_eps)
    with jax.named_scope(f"afmoe.attn.{kind}"):
        with jax.named_scope(".qkv"):
            a = norm(x, lp["input_ln"])
            qkvg = jnp.einsum("bsd,de->bse", a, lp["qkvg_w"].astype(dt))
            q, k, v, g = jnp.split(
                qkvg, [H * Dh, (H + Hkv) * Dh, (H + 2 * Hkv) * Dh], axis=-1)

            def heads(t):
                return t.reshape(B, S, -1, Dh).transpose(0, 2, 1, 3)
            q = norm(heads(q), lp["q_norm"])
            k = norm(heads(k), lp["k_norm"])
            v = heads(v)
            if kind == SLIDING:
                q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
            if Hkv != H:
                k = jnp.repeat(k, H // Hkv, axis=1)
                v = jnp.repeat(v, H // Hkv, axis=1)
        # the kernels and the transpose after them stay the half's own:
        # an unnamed call is called after the innermost scope around it
        ctx = _attn_fn(cfg, kind)(q, k, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H * Dh)
        with jax.named_scope(".out"):
            o = jnp.einsum("bse,ed->bsd", _gated(ctx, g),
                           lp["attn_out_w"].astype(dt))
            return x + norm(o, lp["post_attn_ln"])


def _feed_forward(x, lp, sel, cfg: AfmoeConfig, is_moe: bool):
    """The other half: x -> `(x + norm(f), routing or None)`, `f` the
    dense SwiGLU or the shared expert plus the held routed ones."""
    dt = cfg.dtype
    B, S, D = x.shape
    norm = functools.partial(_rms_norm, bias=None, eps=cfg.rms_norm_eps)
    if not is_moe:
        with jax.named_scope("afmoe.mlp"):
            f = _swiglu(norm(x, lp["pre_mlp_ln"]), lp, "mlp_", dt)
            return x + norm(f, lp["post_mlp_ln"]), None
    with jax.named_scope("afmoe.moe"):
        m = norm(x, lp["pre_mlp_ln"])
        experts = {n: lp["expert_" + n] for n in ("gate_w", "up_w", "down_w")}
        routed, routing = dropless_moe.held_experts(
            m.reshape(B * S, D), lp["router_w"], experts, cfg.moe,
            expert_bias=lp.get("expert_bias"), sel=sel)
        with jax.named_scope(".shared"):
            shared = _swiglu(m, lp, "shared_", dt)
        f = shared + routed.reshape(B, S, D)
        return x + norm(f, lp["post_mlp_ln"]), routing


def _layer(x, lp, sel, cfg: AfmoeConfig, kind: str, is_moe: bool):
    """One layer.  x [B, S, D]; returns `(x, routing or None)`."""
    return _feed_forward(_attention(x, lp, cfg, kind), lp, sel, cfg, is_moe)


def _remat(fn, cfg):
    if not cfg.remat:
        return fn
    from ..ops import flash_attention
    from ..ops.sparse_attention import KEPT_NAMES
    policies = {
        "none": None,
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        # what a layer that selects its keys found (16 MB a layer at
        # 32,768 rows) and what its attention call made under it (o, lse
        # and the mask as bits: 407 MB), so that the backward pass neither
        # selects nor calls the forward kernel again
        "selection": jax.checkpoint_policies.save_only_these_names(
            *KEPT_NAMES),
        # what the layers of `models/nemotron_h.py` keep of an attention
        # and an expert part: the flash call's `o` and `lse` and the
        # router's choice (`models/joyai.py`, whose layers are both)
        "kernels": jax.checkpoint_policies.save_only_these_names(
            flash_attention.KEPT_NAME, dropless_moe.ROUTING_NAME),
    }
    if cfg.remat_policy not in policies:
        raise ValueError(f"remat_policy={cfg.remat_policy!r}; options: "
                         f"{sorted(policies)}")
    return jax.checkpoint(fn, policy=policies[cfg.remat_policy])


def _unstack(group: dict, n: int):
    """The `n` layers' own leaves from leaves stacked on a leading axis.
    A split and not n indexings: the transpose of a split is ONE
    concatenate, that of n indexings n padded copies to sum."""
    pieces = {k: lax.split(a, (1,) * n) for k, a in group.items()}
    return [{k: pieces[k][j][0] for k in group} for j in range(n)]


def _embed(params, tokens, cfg: AfmoeConfig):
    with jax.named_scope("afmoe.embed"):
        x = params["embed"].astype(cfg.dtype)[tokens - cfg.vocab_start]
        if cfg.mup_enabled:
            x = x * jnp.asarray(math.sqrt(cfg.hidden_size), cfg.dtype)
        return x


def forward_hidden(params: PyTree, tokens: jax.Array, cfg,
                   sel=None, with_routing: bool = False, layer=_layer,
                   embed=_embed, family: str = "afmoe"):
    """tokens [B, S] int32 (ids of the held slice) -> the final hidden
    states [B, S, D], after the last norm.

    `sel` [expert layers, B*S, k] replaces every router's own top-k (see
    `dropless_moe.route`).  With `with_routing` the result is
    `(hidden, Routing)`, the `Routing`'s leaves stacked over the expert
    layers: the program's own choice and counters, for whoever asks; the
    loss does not.

    `layer` and `embed` are what a decoder of another family puts in
    place of this one's (`models/mellum.py`): the period scan, the remat
    and the held slice are the same machinery for both, and `cfg` then
    that family's, with the fields `_stack_plan` and `_remat` read, and
    `family` the prefix of its scopes (`<family>.head` holds the last norm
    here and the head in `loss_fn`)."""
    x, routings = run_layers(params, embed(params, tokens, cfg), cfg, sel,
                             with_routing, layer)
    with jax.named_scope(family + ".head"):
        x = _rms_norm(x, params["final_ln"], None, eps=cfg.rms_norm_eps)
    return (x, routings) if with_routing else x


def run_layers(params: PyTree, x: jax.Array, cfg, sel=None,
               with_routing: bool = False, layer=_layer):
    """The layers alone, period by period: x [B, S, D] from the embedding
    -> `(x after the last layer, BEFORE the final norm; Routing or
    None)`, the arguments `forward_hidden`'s."""
    routings = None
    for key, kinds, periods in _stack_plan(cfg):
        is_moe = key == "moe"
        p = len(kinds)
        stacked = jax.tree.map(
            lambda a: a.reshape(periods, p, *a.shape[1:]), params[key])
        sels = None
        if is_moe and sel is not None:
            sels = sel.reshape(periods, p, *sel.shape[1:])

        def period(x, xs, kinds=kinds, is_moe=is_moe):
            lps, sels = xs
            lps = _unstack(lps, len(kinds))
            routed = []
            for i, kind in enumerate(kinds):
                one = _remat(functools.partial(
                    layer, cfg=cfg, kind=kind, is_moe=is_moe), cfg)
                x, r = one(x, lps[i], None if sels is None else sels[i])
                routed.append(r)
            if is_moe and with_routing:
                return x, jax.tree.map(lambda *a: jnp.stack(a), *routed)
            return x, None

        x, r = lax.scan(period, x, (stacked, sels))
        if is_moe and with_routing:
            routings = jax.tree.map(
                lambda a: a.reshape(periods * p, *a.shape[2:]), r)
    return x, routings


def head_logits(x: jax.Array, head: jax.Array) -> jax.Array:
    """Float32 logits of `x` [..., D] over the rows of `head` [V, D]: the
    held slice's columns of the whole head's logits."""
    return jnp.einsum("...d,vd->...v", x, head.astype(x.dtype),
                      preferred_element_type=jnp.float32)


def loss_fn(params: PyTree, batch, cfg, sel=None,
            hidden=forward_hidden, family: str = "afmoe") -> jax.Array:
    """Mean next-token cross-entropy over the held slice of the vocabulary.
    batch = (tokens [B, S], targets [B, S]).  `hidden` is another
    family's `forward_hidden`, `family` the prefix of its scopes."""
    tokens, targets = batch
    x = hidden(params, tokens, cfg, sel=sel)
    with jax.named_scope(family + ".head"):
        targets = targets - cfg.vocab_start
        if cfg.ce_chunk_rows:
            return fused_nll_sum(x, params["head"], targets,
                                 cfg.ce_chunk_rows) / targets.size
        logp = jax.nn.log_softmax(head_logits(x, params["head"]), axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None],
                                    axis=-1).mean()


def routing(params: PyTree, tokens: jax.Array, cfg, hidden=forward_hidden):
    """The program's own routing on `tokens`, a `dropless_moe.Routing`
    with leaves stacked over the expert layers."""
    return hidden(params, tokens, cfg, with_routing=True)[1]


def synthetic_batch(rng: jax.Array, batch_size: int, seq_len: int, cfg):
    """Token ids uniform over the held slice of the vocabulary."""
    toks = jax.random.randint(rng, (batch_size, seq_len + 1),
                              cfg.vocab_start,
                              cfg.vocab_start + cfg.vocab_size, jnp.int32)
    return toks[:, :-1], toks[:, 1:]
