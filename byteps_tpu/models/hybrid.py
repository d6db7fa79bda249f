"""Hybrid-parallel transformer: one train step over all five mesh axes.

The reference framework is DP-only (SURVEY §2.6); this module is the
"every axis at once" integration the TPU build adds on top: a transformer
LM (optionally Switch-MoE) whose single `shard_map` training step composes

  - dp × ep : batch sharding (expert ranks double as data ranks, the
              DeepSpeed-MoE convention),
  - sp      : sequence sharding with ring attention (ops/ring_attention),
  - tp      : Megatron column/row sharded projections (parallel/tensor_
              parallel — separate wq/wk/wv so head sharding stays clean),
  - pp      : SPMD GPipe over stacked layer slices (parallel/pipeline),
  - ep      : Switch-MoE expert dispatch (parallel/expert).

Gradient synchronization is explicit and per-parameter-group, the manual
analog of what GSPMD derives:

  group                         grads psummed over
  ------------------------------------------------
  non-stage (embed/pos/ln_f)    dp, ep, sp, pp   (loss masked to the last
                                                  pp rank so embed's head
                                                  path and input path sum
                                                  correctly — see _loss)
  stage, dense/tp               dp, ep, sp       (owned per pp rank)
  stage, expert (ffn_e_*)       dp, sp           (owned per (pp, ep) rank)

The Switch load-balancing aux loss is folded in whenever
`aux_loss_weight > 0`, including under pp: the aux scalar rides out-of-band
beside the pipeline's activation carry, accumulated per stage over its real
microbatch ticks (parallel/pipeline.gpipe_spmd with_aux=True).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.ring_attention import ring_attention_shard
from ..parallel import pipeline as pp_mod
from ..parallel import tensor_parallel as tp_mod
from ..parallel.expert import moe_core

PyTree = Any


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 1024
    num_layers: int = 4
    d_model: int = 64
    num_heads: int = 4
    d_ff: int = 128
    max_seq_len: int = 128
    num_experts: int = 0          # 0 = dense MLP in every block
    capacity_factor: float = 2.0
    #: Switch load-balancing aux-loss weight (0 = off).  Note: the aux term
    #: is an expectation over the LOCAL token shard, so its value depends
    #: (mildly) on the sharding layout; enable it for real MoE training,
    #: leave 0 when bitwise cross-layout reproducibility matters.
    aux_loss_weight: float = 0.0
    dtype: Any = jnp.float32
    causal: bool = True
    #: > 0 streams the LM-head cross-entropy in row chunks of this size so
    #: the [B*S, vocab] logits are never materialized (see
    #: transformer.fused_nll_sum); 0 = full-logits path.
    ce_chunk_rows: int = 0

    @property
    def head_dim(self):
        return self.d_model // self.num_heads


def init_params(rng: jax.Array, cfg: HybridConfig) -> PyTree:
    L, D, F, E = cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(rng, 12)

    def w(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)

    layers: Dict[str, jax.Array] = {
        "wq": w(ks[0], (L, D, D), D),
        "wk": w(ks[1], (L, D, D), D),
        "wv": w(ks[2], (L, D, D), D),
        "wo": w(ks[3], (L, D, D), D),
        "ln1_scale": jnp.ones((L, D)), "ln1_bias": jnp.zeros((L, D)),
        "ln2_scale": jnp.ones((L, D)), "ln2_bias": jnp.zeros((L, D)),
    }
    if E > 0:
        layers.update({
            "gate_w": w(ks[4], (L, D, E), D),
            "ffn_e_in": w(ks[5], (L, E, D, F), D),
            "ffn_e_out": w(ks[6], (L, E, F, D), F),
        })
    else:
        layers.update({
            "mlp_in": w(ks[7], (L, D, F), D),
            "mlp_out": w(ks[8], (L, F, D), F),
        })
    return {
        "embed": w(ks[9], (cfg.vocab_size, D), D),
        "pos": 0.02 * jax.random.normal(ks[10], (cfg.max_seq_len, D)),
        "ln_f_scale": jnp.ones((D,)),
        "ln_f_bias": jnp.zeros((D,)),
        "layers": layers,
    }


def param_specs(cfg: HybridConfig) -> PyTree:
    """Global PartitionSpecs; stacked layers carry the pp axis leading (after
    pipeline.shard_stage_params reshaping to [pp, L/pp, ...])."""
    layers = {
        "wq": P("pp", None, None, "tp"),
        "wk": P("pp", None, None, "tp"),
        "wv": P("pp", None, None, "tp"),
        "wo": P("pp", None, "tp", None),
        "ln1_scale": P("pp", None, None), "ln1_bias": P("pp", None, None),
        "ln2_scale": P("pp", None, None), "ln2_bias": P("pp", None, None),
    }
    if cfg.num_experts > 0:
        layers.update({
            "gate_w": P("pp", None, None, None),
            "ffn_e_in": P("pp", None, "ep", None, None),
            "ffn_e_out": P("pp", None, "ep", None, None),
        })
    else:
        layers.update({
            "mlp_in": P("pp", None, None, "tp"),
            "mlp_out": P("pp", None, "tp", None),
        })
    return {
        "embed": P(None, None),
        "pos": P(None, None),
        "ln_f_scale": P(None), "ln_f_bias": P(None),
        "layers": layers,
    }


def stage_params(params: PyTree, pp: int) -> PyTree:
    """[L, ...] stacked layers -> [pp, L/pp, ...] for the pp axis."""
    out = dict(params)
    out["layers"] = pp_mod.shard_stage_params(params["layers"], pp)
    return out


def _ln(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def _block(lp, x, cfg: HybridConfig, f_tp, g_tp):
    """One hybrid block on a local activation x: [mb, s_local, D].
    Returns (x, aux) — aux is the MoE load-balancing loss (0 for dense)."""
    mb, s, D = x.shape
    dh = cfg.head_dim

    h = _ln(x, lp["ln1_scale"], lp["ln1_bias"])
    h = f_tp(h)                                   # Megatron f
    q = h @ lp["wq"]                              # [mb, s, D/tp]
    k = h @ lp["wk"]
    v = h @ lp["wv"]

    def heads(t):
        return t.reshape(mb, s, -1, dh).transpose(0, 2, 1, 3)
    attn = ring_attention_shard(heads(q), heads(k), heads(v),
                                causal=cfg.causal, axis_name="sp")
    attn = attn.transpose(0, 2, 1, 3).reshape(mb, s, -1)
    y = g_tp(attn @ lp["wo"])                    # Megatron g
    x = x + y

    h2 = _ln(x, lp["ln2_scale"], lp["ln2_bias"])
    if cfg.num_experts > 0:
        y2, aux = moe_core(lp["gate_w"], lp["ffn_e_in"], lp["ffn_e_out"],
                           h2.reshape(mb * s, D), cfg.capacity_factor, "ep")
        y2 = y2.reshape(mb, s, D)
    else:
        a = jax.nn.gelu(f_tp(h2) @ lp["mlp_in"])
        y2 = g_tp(a @ lp["mlp_out"])
        aux = jnp.zeros((), jnp.float32)
    return x + y2, aux


def _stage_fn(local_layers, x, cfg: HybridConfig, f_tp, g_tp):
    """Apply this pp rank's layer slice ([L/pp, ...] stacked) to x.
    Returns (out, aux_sum over this stage's layers)."""
    def body(carry, lp):
        h, aux = carry
        h, a = _block(lp, h, cfg, f_tp, g_tp)
        return (h, aux + a), None
    (out, aux), _ = lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), local_layers)
    return out, aux


def build_hybrid_train_step(
    cfg: HybridConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    num_microbatches: int = 1,
    donate: bool = False,
    zero1: bool = False,
):
    """Returns (step, init_fn) where step(params, opt_state, (tokens,
    targets)) -> (params, opt_state, loss) is jitted over the full mesh and
    init_fn(rng) places params in their sharded layout.

    tokens/targets: [B, S] with B divisible by dp*ep*microbatches and S by
    sp.  params must come from init_fn (stacked layers pre-reshaped for pp).

    `zero1=True` additionally shards the optimizer state over 'dp'
    (ZeRO-1 on the explicit shard_map plane, the hand-built analog of
    parallel.sharded's GSPMD path): each param spec gains the dp axis on
    its first free dp-divisible dimension, the optimizer update runs on
    the local 1/dp shard of grads/params/state, and only the UPDATES are
    all-gathered back — Adam moments drop to 1/dp per device.  A
    replicated opt_state from `optimizer.init` is resharded on first
    call; at dp=1 the step is identical to zero1=False.
    """
    pp = int(mesh.shape.get("pp", 1))
    specs = param_specs(cfg)
    batch_spec = P(("dp", "ep"), "sp")

    f_tp = tp_mod.copy_to("tp")
    g_tp = tp_mod.reduce_from("tp")

    def loss_fn(params, tokens, targets):
        # [B_loc, S_loc] on this (dp,ep,sp) coordinate; replicated over tp
        # and pp.
        B, S = tokens.shape
        sp_idx = lax.axis_index("sp")
        x = params["embed"][tokens].astype(cfg.dtype)
        pos = lax.dynamic_slice_in_dim(params["pos"], sp_idx * S, S, 0)
        x = x + pos.astype(cfg.dtype)

        # Local stage slice: [pp, L/pp, ...] sharded over 'pp' arrives as
        # [1, L/pp, ...]; drop the leading singleton.
        local_layers = jax.tree.map(lambda l: l[0], params["layers"])
        run = functools.partial(_stage_fn, cfg=cfg, f_tp=f_tp, g_tp=g_tp)
        if pp > 1:
            # The aux scalar rides out-of-band beside the activation carry:
            # each pp rank accumulates its own stage's aux over its real
            # microbatch ticks (bubbles masked), so the router keeps its
            # load-balancing signal under pipeline parallelism.
            x, aux = pp_mod.gpipe_spmd(
                run, local_layers, x, num_microbatches, axis_name="pp",
                with_aux=True)
            # Per-microbatch aux terms are means over mb tokens; averaging
            # over M matches the single-pass (pp=1) per-token mean.
            aux = aux / num_microbatches
        else:
            x, aux = run(local_layers, x)

        x = _ln(x, params["ln_f_scale"], params["ln_f_bias"])
        if cfg.ce_chunk_rows:
            # Streamed LM head: per-chunk logits + logsumexp under
            # scan+checkpoint, never materializing [B*S, V] (same fused
            # path as the flagship model, transformer.fused_nll_sum).
            from .transformer import fused_nll_sum
            nll_sum = fused_nll_sum(x, params["embed"], targets,
                                    cfg.ce_chunk_rows)
        else:
            logits = jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                                params["embed"])
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
            nll_sum = nll.sum()
        # Normalize by the GLOBAL token count; mask to the last pp stage so
        # psum over pp double-counts neither the head path nor the input
        # path of the shared embedding.
        denom = (B * jax.lax.axis_size("dp") * jax.lax.axis_size("ep")
                 * S * jax.lax.axis_size("sp"))
        loss = nll_sum / denom
        # Mask the token loss to the last pp stage so psum over pp
        # double-counts neither the head path nor the input path of the
        # shared embedding.  The aux term stays UNmasked: each pp rank owns
        # the aux of its layer slice (distinct layers), so per-rank terms
        # sum to the whole-model aux under the final pp psum.
        loss = jnp.where(lax.axis_index("pp") == pp - 1, loss, 0.0)
        if cfg.num_experts > 0 and cfg.aux_loss_weight > 0.0:
            # Mean aux over layers and over the (dp, ep, sp) shards — the
            # final psum over those axes turns the per-shard term into the
            # cross-shard mean.
            shards = (jax.lax.axis_size("dp") * jax.lax.axis_size("ep")
                      * jax.lax.axis_size("sp"))
            loss = loss + cfg.aux_loss_weight * aux / (
                cfg.num_layers * shards)
        return loss

    def make_grad_sync(dp_axes):
        """Cross-shard gradient reduction.  `dp_axes` is a params-shaped
        tree of ints: the dimension each leaf's 1/dp shard lives on, or
        -1 for leaves that stay whole (zero1 off, or no free divisible
        axis).  Whole leaves get the full psum; dp-sharded leaves psum
        only the non-dp axes and REDUCE-SCATTER over dp — each rank
        receives exactly the shard its optimizer update consumes, so the
        dp wire cost is scatter + (update) gather = one ring
        all-reduce, not all-reduce + gather."""
        def sync(path, g, ax):
            keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
            if "layers" in keys:
                if any(str(k).startswith("ffn_e") for k in keys):
                    nondp = ("sp",)
                else:
                    nondp = ("ep", "sp")
            else:
                nondp = ("ep", "sp", "pp")
            if ax < 0:
                return lax.psum(g, ("dp",) + nondp)
            g = lax.psum(g, nondp)
            return lax.psum_scatter(g, "dp", scatter_dimension=ax,
                                    tiled=True)
        return lambda grads: jax.tree_util.tree_map_with_path(
            sync, grads, dp_axes)

    def make_update_leg(dp_axes):
        """Optimizer leg: grads for dp-sharded leaves already arrive as
        this rank's shard (reduce-scattered by grad_sync); params are
        sliced locally (free — they are replicated over dp) and only the
        UPDATES are all-gathered back."""
        def slice_dp(x, ax):
            if ax < 0:
                return x
            n = jax.lax.axis_size("dp")
            size = x.shape[ax] // n
            return lax.dynamic_slice_in_dim(
                x, lax.axis_index("dp") * size, size, ax)

        def gather_dp(u, ax):
            if ax < 0:
                return u
            return lax.all_gather(u, "dp", axis=ax, tiled=True)

        def update_leg(params, opt_state, grads):
            p_s = jax.tree.map(slice_dp, params, dp_axes)
            # State leaves arrive as their local shard (in_specs carry
            # the dp-upgraded layout); the update math runs on 1/dp of
            # every sharded leaf, so the moment buffers never exist
            # whole on any device.
            updates_s, opt_state = optimizer.update(grads, opt_state, p_s)
            updates = jax.tree.map(gather_dp, updates_s, dp_axes)
            return optax.apply_updates(params, updates), opt_state
        return update_leg

    def make_sm_step(grad_sync, update_leg):
        def _step(params, opt_state, batch):
            tokens, targets = batch
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, tokens, targets))(params)
            grads = grad_sync(grads)
            params, opt_state = update_leg(params, opt_state, grads)
            loss = lax.psum(loss, ("dp", "ep", "sp", "pp"))
            return params, opt_state, loss
        return _step

    # Optimizer-state specs: shape-match against params (adam mu/nu inherit
    # the param layout; scalars replicate).  With zero1 the param specs are
    # first upgraded with the dp axis, and the state follows THAT layout.
    # The shard_map+jit is built once per opt_state structure and cached
    # (rebuilding per call would retrace).
    def make_step():
        from ..parallel.sharded import (_is_spec, _shard_free_axis,
                                        opt_state_specs)
        cache = {}

        def dp_axis_of(old: P, new: P) -> int:
            for i, e in enumerate(new):
                if e == "dp" and (i >= len(old) or old[i] != "dp"):
                    return i
            return -1

        def call(params, opt_state, batch):
            key = jax.tree.structure(opt_state)
            if key not in cache:
                if zero1:
                    p_up = _shard_free_axis(specs, params, mesh, "dp",
                                            min_shard_elems=1024)
                else:
                    p_up = specs
                dp_axes = jax.tree.map(dp_axis_of, specs, p_up,
                                       is_leaf=_is_spec)
                o_specs = opt_state_specs(optimizer, params, p_up)
                sm = jax.shard_map(
                    make_sm_step(make_grad_sync(dp_axes),
                                 make_update_leg(dp_axes)), mesh=mesh,
                    in_specs=(specs, o_specs, (batch_spec, batch_spec)),
                    out_specs=(specs, o_specs, P()),
                    check_vma=False)
                donate_argnums = (0, 1) if donate else ()
                cache[key] = jax.jit(sm, donate_argnums=donate_argnums)
            return cache[key](params, opt_state, batch)
        return call

    def init_fn(rng):
        params = stage_params(init_params(rng, cfg), pp)
        from ..parallel.sharded import shard_params
        return shard_params(params, mesh, specs)

    return make_step(), init_fn
