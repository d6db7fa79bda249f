"""The `joyai_llm_flash` decoder (JD's JoyAI-LLM-Flash, whose `config.json`
carries DeepSeek-V3's keys): latent attention, sparse experts behind a
leading dense layer, and a multi-token-prediction module.

  - LATENT ATTENTION (MLA).  Queries and keys/values each go through a
    low-rank chain with an RMS norm in its middle, and positions turn a
    DECOUPLED part of each head.  With a = rms(x; input_ln):

        [cQ | cKV | kR] = a W_down            widths q_lora | kv_lora | rope
        [qN_h | qR_h]   = rms(cQ; q_a_ln) W_UQ,h      nope + rope a head
        [kN_h | v_h]    = rms(cKV; kv_a_ln) W_UKV,h   nope + v a head
        qR_h <- R_t(qR_h);  kR <- R_t(kR): ONE rotary key a token, the
        same for every head
        o_h = causal softmax(([qN_h | qR_h] . [kN_h | kR]) / sqrt(nope +
              rope)) v_h;   x <- x + concat_h(o) W_O

    A query and a key are `qk_nope_head_dim + qk_rope_head_dim` wide (192
    in the published model) and a value `v_head_dim` (128): the flash
    kernels take the two widths as they are (`ops/flash_attention.py`).
    Training runs this EXPANDED form; the rotary key is repeated over the
    heads before the call (reading it once a token is left to the
    kernels' next change).  No bias anywhere.
  - x <- x + f(rms(x; post_attn_ln)).  The first `num_dense_layers`
    layers: a SwiGLU of `intermediate_size`.  The others: sigmoid scores
    over ALL the experts, the top k of scores + `expert_bias` (the
    `e_score_correction_bias`: a buffer, the leaf where the tree has it,
    that moves the choice alone), weights normalised and times
    `route_scale`, SwiGLU experts, plus one shared SwiGLU expert on every
    token.  This is `afmoe.py`'s router with other numbers and
    `parallel/dropless_moe.py`'s held experts.
  - A final RMS norm and the untied head.
  - MULTI-TOKEN PREDICTION (`num_nextn_predict_layers` 1; DeepSeek-V3
    report, section 2.2).  One more module after the main stack, which
    predicts the token after the next:

        h'_i = [rms(Emb(t_{i+1}); enorm) | rms(h_i; hnorm)] W_eh

    h_i the main stack's last hidden state BEFORE its final norm, then
    one more layer of the expert kind with its own weights, its own final
    norm, and the main model's embedding and head (one leaf each).  It
    runs over the same S positions: the next token's embedding is the
    batch's `targets` looked up, the module's targets are those rolled by
    one, and the last position, which has none, is masked out of its
    loss.  The loss is `main + mtp_loss_weight x MTP`, each a mean over
    its own valid positions: two streamed cross-entropies over one held
    head slice.

Why a module beside `afmoe.py`: what the two decoders share is machinery,
and that is imported, not copied: the period scan `afmoe.run_layers` (with
`_stack_plan`, `_unstack`, `_remat`), the attention adapter `_attn_fn`,
`_swiglu`, the held slice of embedding, head and loss, and
`dropless_moe.held_experts`.  What differs is what a model file is read
for: the attention block's equations, the parameter tree, the second
prediction head.

Every layer is rematerialised whole and KEEPS by name the flash call's
`o` and `lse` and the router's choice (`afmoe._remat`'s policy
`kernels`; `models/nemotron_h.py` says what that buys): the recompute
calls no forward kernel and routes nothing a second time.

A share of a deployment, as `afmoe.py` says it: `held_experts` names the
experts this chip holds (the router stays `num_experts` wide, the shared
expert whole), `vocab_size` the held slice of the vocabulary, ids
`vocab_start ...`.  A share's backward pass holds the weight each token
gives the held experts together constant
(`dropless_moe.MoEConfig.hold_held_weight`, which says why: this layer
adds its branch to the stream un-normed, as mellum's and nemotron_h's
do, so nothing else holds the branch's size).
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
from . import afmoe
from .afmoe import FULL
from .transformer import _rms_norm, _rope, fused_nll_sum

PyTree = Any


@dataclasses.dataclass(frozen=True)
class JoyaiConfig:
    vocab_size: int                    # rows of embedding and head held here
    hidden_size: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int             # the dense layers' SwiGLU
    moe_intermediate_size: int         # every expert's, shared or routed
    num_experts: int                   # the router's width
    num_experts_per_tok: int
    num_layers: int                    # layers of the main stack that are run
    num_dense_layers: int
    num_mtp_modules: int = 1           # `num_nextn_predict_layers`: 0 or 1
    mtp_loss_weight: float = 0.3
    held_experts: Optional[Tuple[int, ...]] = None   # None: all of them
    vocab_start: int = 0               # first token id of the held slice
    route_scale: float = 1.0
    route_norm: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_impl: str = "dense"           # "dense" | "flash"
    attn_block: int = 0                # as TransformerConfig's
    attn_block_k: int = 0
    ce_chunk_rows: int = 0             # > 0: streamed head + cross-entropy
    moe_capacity_factor: float = 1.25  # dropless_moe's static buffer
    # what afmoe's `_remat` reads: every layer, keeping the kernels' names
    remat = True
    remat_policy = "kernels"

    def __post_init__(self):
        if not 0 <= self.num_dense_layers <= self.num_layers:
            raise ValueError(f"num_dense_layers={self.num_dense_layers}")
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"rotary positions need an even "
                             f"qk_rope_head_dim (got {self.qk_rope_head_dim})")
        if self.attn_impl not in ("dense", "flash"):
            raise ValueError(f"attn_impl={self.attn_impl!r}")
        if self.num_mtp_modules not in (0, 1):
            raise ValueError("one prediction module after the main stack "
                             "is what is written here")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """What afmoe's `_stack_plan` reads: every layer attends to all
        the keys before it."""
        return (FULL,) * self.num_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

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


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def init_params(rng: jax.Array, cfg: JoyaiConfig) -> PyTree:
    """Normal / sqrt(fan_in) weights, unit norm scales.  Groups `dense`
    and `moe` with leaves stacked on a leading layer axis, as `afmoe.py`'s;
    `mtp` the prediction module: an expert layer's leaves, unstacked, and
    its own `enorm`, `hnorm`, `eh_proj_w` and `final_ln` (it has no
    embedding and no head: the main model's are shared).  The load
    balancer's `expert_bias` is no parameter and is not made here."""
    dt = cfg.param_dtype
    D, H = cfg.hidden_size, cfg.num_heads
    keys = iter(jax.random.split(rng, 48))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, dt)
                / math.sqrt(fan_in)).astype(dt)

    def attention(lead):
        rq, rkv, rope = (cfg.q_lora_rank, cfg.kv_lora_rank,
                         cfg.qk_rope_head_dim)
        return {
            "input_ln": jnp.ones((*lead, D), dt),
            "post_attn_ln": jnp.ones((*lead, D), dt),
            # [cQ | cKV | kR] side by side, one product
            "down_w": w((*lead, D, rq + rkv + rope), D),
            "q_a_ln": jnp.ones((*lead, rq), dt),
            "kv_a_ln": jnp.ones((*lead, rkv), dt),
            # a head's columns side by side: [qN | qR], [kN | v]
            "q_up_w": w((*lead, rq, H * cfg.qk_head_dim), rq),
            "kv_up_w": w((*lead, rkv, H * (cfg.qk_nope_head_dim
                                           + cfg.v_head_dim)), rkv),
            "attn_out_w": w((*lead, H * cfg.v_head_dim, D),
                            H * cfg.v_head_dim),
        }

    def swiglu(lead, width, prefix):
        return {prefix + "gate_w": w((*lead, D, width), D),
                prefix + "up_w": w((*lead, D, width), D),
                prefix + "down_w": w((*lead, width, D), width)}

    def expert_layer(lead):
        F = cfg.moe_intermediate_size
        return {**attention(lead),
                "router_w": w((*lead, D, cfg.num_experts), D),
                **swiglu(lead, F, "shared_"),
                **swiglu((*lead, len(cfg.held)), F, "expert_")}

    out = {"embed": w((cfg.vocab_size, D), D),
           "head": w((cfg.vocab_size, D), D),
           "final_ln": jnp.ones((D,), dt)}
    nd = cfg.num_dense_layers
    nm = cfg.num_layers - nd
    if nd:
        out["dense"] = {**attention((nd,)),
                        **swiglu((nd,), cfg.intermediate_size, "mlp_")}
    if nm:
        out["moe"] = expert_layer((nm,))
    if cfg.num_mtp_modules:
        out["mtp"] = {"enorm": jnp.ones((D,), dt),
                      "hnorm": jnp.ones((D,), dt),
                      "eh_proj_w": w((2 * D, D), 2 * D),
                      **expert_layer(()),
                      "final_ln": jnp.ones((D,), dt)}
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _qkv(x, lp, cfg: JoyaiConfig):
    """What a layer's attention call is given: x [B, S, D] -> queries and
    keys [B, H, S, nope + rope], values [B, H, S, v]; both chains, their
    norms, the rotary parts turned and the one rotary key a token laid
    beside every head's own part."""
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
    kv = heads(jnp.einsum("bsr,re->bse", norm(ckv, lp["kv_a_ln"]),
                          lp["kv_up_w"].astype(dt)))
    qr = _rope(q[..., nope:], cfg.rope_theta)
    kr = _rope(kr[:, None], cfg.rope_theta)              # [B, 1, S, rope]
    q = jnp.concatenate([q[..., :nope], qr], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(kr, (B, H, S, rope))], axis=-1)
    return q, k, kv[..., nope:]


def _attention(x, lp, cfg: JoyaiConfig):
    """The attention half of a layer: x [B, S, D] -> x + attn(norm(x))."""
    B, S, D = x.shape
    with jax.named_scope("joyai.attn"):
        with jax.named_scope(".qkv"):
            q, k, v = _qkv(x, lp, cfg)
        # the kernels and the transpose after them stay the half's own
        ctx = afmoe._attn_fn(cfg, FULL)(q, k, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, -1)
        with jax.named_scope(".out"):
            return x + jnp.einsum("bse,ed->bsd", ctx,
                                  lp["attn_out_w"].astype(cfg.dtype))


def _experts_input(x, lp, cfg: JoyaiConfig):
    """What a layer's feed-forward, router and experts are given: x
    [B, S, D] normed."""
    return _rms_norm(x, lp["post_attn_ln"], None, eps=cfg.rms_norm_eps)


def _feed_forward(x, lp, sel, cfg: JoyaiConfig, is_moe: bool):
    """The other half: x -> `(x + f(norm(x)), routing or None)`, `f` the
    dense SwiGLU or the shared expert plus the held routed ones."""
    B, S, D = x.shape
    if not is_moe:
        with jax.named_scope("joyai.dense"):
            return x + afmoe._swiglu(_experts_input(x, lp, cfg), lp, "mlp_",
                                     cfg.dtype), None
    with jax.named_scope("joyai.moe"):
        m = _experts_input(x, lp, cfg)
        experts = {n: lp["expert_" + n] for n in ("gate_w", "up_w", "down_w")}
        routed, routing = dropless_moe.held_experts(
            m.reshape(B * S, D), lp["router_w"], experts, cfg.moe,
            expert_bias=lp.get("expert_bias"), sel=sel)
        with jax.named_scope(".shared"):
            shared = afmoe._swiglu(m, lp, "shared_", cfg.dtype)
        return x + shared + routed.reshape(B, S, D), routing


def _layer(x, lp, sel, cfg: JoyaiConfig, kind: str = FULL,
           is_moe: bool = True):
    """One layer.  x [B, S, D]; returns `(x, routing or None)`."""
    del kind                            # every layer's attention is latent
    return _feed_forward(_attention(x, lp, cfg), lp, sel, cfg, is_moe)


def _embed(params, tokens, cfg: JoyaiConfig):
    with jax.named_scope("joyai.embed"):
        return params["embed"].astype(cfg.dtype)[tokens - cfg.vocab_start]


def _mtp_input(params, h, next_tokens, cfg: JoyaiConfig):
    """What the prediction module's layer is given: `h` [B, S, D] the
    main stack's last hidden states before its final norm and the
    embedding of `next_tokens` [B, S], each normed, side by side (the
    embedding's half first) through `eh_proj_w`."""
    mp = params["mtp"]
    norm = functools.partial(_rms_norm, bias=None, eps=cfg.rms_norm_eps)
    e = params["embed"].astype(cfg.dtype)[next_tokens - cfg.vocab_start]
    both = jnp.concatenate([norm(e, mp["enorm"]), norm(h, mp["hnorm"])],
                           axis=-1)
    return jnp.einsum("bse,ed->bsd", both, mp["eh_proj_w"].astype(cfg.dtype))


def _mtp(params, h, next_tokens, sel, cfg: JoyaiConfig):
    """The prediction module: `h` and `next_tokens` as `_mtp_input`'s ->
    `(hidden states [B, S, D] after the module's own final norm,
    routing)`.  Rematerialised whole, as a layer of the main stack is; the
    scope is opened INSIDE what is rematerialised, so that the backward
    pass reads `joyai.mtp/...` and not the name twice."""
    def module(used, h, next_tokens, sel):
        with jax.named_scope("joyai.mtp"):
            x, routing = _layer(_mtp_input(used, h, next_tokens, cfg),
                                used["mtp"], sel, cfg)
            return _rms_norm(x, used["mtp"]["final_ln"], None,
                             eps=cfg.rms_norm_eps), routing
    # the leaves it reads and no other: an argument it did not read would
    # still be handed a gradient, of zeros
    used = {"embed": params["embed"], "mtp": params["mtp"]}
    return afmoe._remat(module, cfg)(used, h, next_tokens, sel)


def _record(cfg: JoyaiConfig, batch: int, seq_len: int) -> None:
    layers = cfg.num_layers + cfg.num_mtp_modules
    experts = layers - cfg.num_dense_layers
    rows = batch * seq_len
    for name, n, nbytes in (
            (flash_attention.KEPT_NAME, layers, flash_attention.kept_bytes(
                batch * cfg.num_heads, seq_len, cfg.v_head_dim, cfg.dtype)),
            (dropless_moe.ROUTING_NAME, experts, cfg.moe.kept_bytes(rows))):
        telemetry.record_static("remat_kept", labels={"name": name},
                                layers=n, bytes=n * nbytes)
    telemetry.record_static("loss_terms", labels={"loss": "main"},
                            weight=1.0, positions=rows)
    if cfg.num_mtp_modules:
        telemetry.record_static("loss_terms", labels={"loss": "mtp"},
                                weight=cfg.mtp_loss_weight,
                                positions=batch * (seq_len - 1))


def forward_hidden(params: PyTree, tokens: jax.Array, cfg: JoyaiConfig,
                   sel=None, with_routing: bool = False, next_tokens=None):
    """tokens [B, S] int32 (ids of the held slice) -> the main stack's
    final hidden states [B, S, D], after the last norm; with
    `next_tokens` [B, S] (the token after each position) and a module to
    run, `(those, the prediction module's)`.

    `sel` [expert layers, B*S, k] replaces every router's own top-k (see
    `dropless_moe.route`), the module's layer last.  With `with_routing`
    the result is `(hidden, Routing)`, the `Routing`'s leaves stacked over
    the expert layers in that order."""
    _record(cfg, *tokens.shape)
    mtp = next_tokens is not None and cfg.num_mtp_modules > 0
    n_main = cfg.num_layers - cfg.num_dense_layers
    h, routings = afmoe.run_layers(
        params, _embed(params, tokens, cfg), cfg,
        None if sel is None else sel[:n_main], with_routing, _layer)
    with jax.named_scope("joyai.head"):
        x = _rms_norm(h, params["final_ln"], None, eps=cfg.rms_norm_eps)
    if mtp:
        x2, r = _mtp(params, h, next_tokens,
                     None if sel is None else sel[n_main], cfg)
        x = (x, x2)
        if with_routing:
            one = jax.tree.map(lambda a: a[None], r)
            routings = one if routings is None else jax.tree.map(
                lambda a, b: jnp.concatenate([a, b]), routings, one)
    return (x, routings) if with_routing else x


def losses(params: PyTree, batch, cfg: JoyaiConfig, sel=None):
    """`(main, mtp)`: the mean next-token cross-entropy of the main head
    and the mean cross-entropy of the prediction module's (the token
    after the next; 0.0 where no module runs), each over the held slice
    of the vocabulary and its own valid positions.  batch = (tokens
    [B, S], targets [B, S]), targets the tokens one position on."""
    tokens, targets = batch
    x = forward_hidden(params, tokens, cfg, sel=sel, next_tokens=targets)
    held = targets - cfg.vocab_start

    def nll_mean(x, targets, weights, count, scope):
        with jax.named_scope(scope):
            if cfg.ce_chunk_rows:
                return fused_nll_sum(x, params["head"], targets,
                                     cfg.ce_chunk_rows, weights) / count
            logp = jax.nn.log_softmax(
                afmoe.head_logits(x, params["head"]), axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
            return (nll[..., 0] * (1.0 if weights is None else weights)
                    ).sum() / count
    if not cfg.num_mtp_modules:
        return (nll_mean(x, held, None, held.size, "joyai.head"),
                jnp.zeros((), jnp.float32))
    x, x2 = x
    B, S = held.shape
    # position i's second target is the target of position i + 1; the last
    # position has none
    valid = jnp.broadcast_to(jnp.arange(S) < S - 1, (B, S))
    return (nll_mean(x, held, None, held.size, "joyai.head"),
            nll_mean(x2, jnp.roll(held, -1, axis=1),
                     valid.astype(jnp.float32), B * (S - 1), "joyai.mtp"))


def loss_fn(params: PyTree, batch, cfg: JoyaiConfig, sel=None) -> jax.Array:
    """`main + mtp_loss_weight x mtp` of `losses`."""
    main, mtp = losses(params, batch, cfg, sel=sel)
    return main + cfg.mtp_loss_weight * mtp


def routing(params: PyTree, tokens: jax.Array, cfg: JoyaiConfig,
            next_tokens=None):
    """The program's own routing on `tokens`, a `dropless_moe.Routing`
    with leaves stacked over the expert layers; with `next_tokens` (a
    batch's targets) the prediction module's layer too, last."""
    return forward_hidden(params, tokens, cfg, with_routing=True,
                          next_tokens=next_tokens)[1]


synthetic_batch = afmoe.synthetic_batch
