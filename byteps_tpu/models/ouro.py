"""The `ouro` decoder (ByteDance's Ouro, `model_type: ouro`; "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741): a stack
of layers WALKED SEVERAL TIMES with the same weights, an exit gate after
every walk, and a loss that is the expected cross-entropy over the walk a
token leaves at.

  - A layer is a SANDWICH: a sub-layer's input AND its output are
    RMS-normed, four scales a layer: `x = x + rms(Attn(rms(x)))`, `x = x +
    rms(SwiGLU(rms(x)))`.  Attention is full and causal in every layer,
    rotary positions over the whole head (half-split, `rope_theta`), no
    q / k norm, no bias, as many key-value heads as the config says.  The
    queries and keys are read where they lie in the projection's result
    and turned by `ops/head_norm_rope.py` `queries_and_keys` with no scale
    (PR 65): its kernels, the norm's term absent, where a head is whole
    lane tiles over whole tiles of rows, else `transformer._rope`'s lines.
  - `total_ut_steps` walks: `h_0` the embedded tokens; walk t runs ALL
    the layers over `h_{t-1}` and norms the result with the final norm,
    INSIDE the loop: `h_t = rms(layers(h_{t-1}))`, and walk t + 1 reads
    `h_t`.  Every weight is read once a walk and its gradient is the sum
    over the walks.
  - After every walk a one-number gate `lam_t = sigmoid(h_t . w_e + b_e)`
    and the head's next-token cross-entropy `nll_t`, both a token.  A
    token leaves at walk t with probability `p_t = lam_t prod_{j<t} (1 -
    lam_j)`; the last walk takes what is left (`exit_distribution`).
  - The loss is the paper's stage-one objective: the mean over tokens of
    `sum_t p_t nll_t - beta H(p)`, the expected task loss less `beta`
    times the exit distribution's entropy.  `p` is no constant: the
    gate's gradient is each row's own `nll_t`.

Why a module of its own: no other model of this package reads a
parameter more than once a step.  What it shares is imported, not
copied: the attention adapter `afmoe._attn_fn`, `afmoe._swiglu`,
`afmoe._remat`, `transformer._rms_norm`, the rotary tables and the turn of
`ops/head_norm_rope.py`, and the streamed head
`transformer.fused_nll_sum`, which runs ONCE over the rows of all the
walks with `p` as its per-row weights.  The parameter tree is one
stacked group, `dense`, as `afmoe.run_layers` walks one (the config has
what its plan reads, and the tests walk T copies of the stack with it).

The loop is ONE `lax.scan` over walks x layers whose body reads layer `i
mod layers` (`walks`): the layer's program is compiled once, a backward
pass keeps walks x layers layer inputs (`loop_counters`) and adds a
layer's gradient into the stack a layer at a time.  It was chosen by
measurement over a scan over the walks round `afmoe.run_layers` (whose
transposed inner scan hands the outer one a whole stack of gradients a
walk: 2.7 GB more at the benchmark's cell, and 0.7% slower) and over
four calls one after another (3.9 GB more): PERF.md, Findings, PR 64.

Generation with adaptive exit (a cache a (walk, layer), leaving at a
threshold on the cumulated exit probability) and the paper's second
training stage (the gate alone, against the gain of a further walk) are
not here: ROADMAP.md.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from ..common import telemetry
from ..ops import head_norm_rope
from . import afmoe, transformer
from .afmoe import FULL
from .transformer import _rms_norm, fused_nll_sum

PyTree = Any


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    vocab_size: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int             # the SwiGLU's width
    num_layers: int                    # layers that are held, all alike
    total_ut_steps: int = 4            # walks over them
    exit_entropy_beta: float = 0.05    # beta of the loss
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_impl: str = "dense"           # "dense" | "flash"
    attn_block: int = 0                # as TransformerConfig's
    attn_block_k: int = 0
    remat: bool = True                 # per layer application
    remat_policy: str = "none"         # see `afmoe._remat`
    ce_chunk_rows: int = 0             # > 0: streamed head + cross-entropy

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads={self.num_heads} not divisible by "
                             f"num_kv_heads={self.num_kv_heads}")
        if self.head_dim % 2:
            raise ValueError(f"rotary positions need an even head_dim "
                             f"(got {self.head_dim})")
        if self.attn_impl not in ("dense", "flash"):
            raise ValueError(f"attn_impl={self.attn_impl!r}")
        if self.num_layers < 1 or self.total_ut_steps < 1:
            raise ValueError(f"num_layers={self.num_layers}, "
                             f"total_ut_steps={self.total_ut_steps}")

    # What `afmoe._stack_plan` reads: one group, `dense`, of one kind.
    @property
    def layer_types(self) -> Tuple[str, ...]:
        return (FULL,) * self.num_layers

    @property
    def num_dense_layers(self) -> int:
        return self.num_layers


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def init_params(rng: jax.Array, cfg: OuroConfig) -> PyTree:
    """Normal / sqrt(fan_in) weights, unit norm scales, the gate's bias 0
    (`exit_gate` [D + 1] is its weight, then its bias).
    One group, `dense`, its leaves stacked on a leading layer axis, as
    `afmoe.py`'s."""
    dt = cfg.param_dtype
    D, H, Hkv, Dh = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                     cfg.head_dim)
    n, F = cfg.num_layers, cfg.intermediate_size
    keys = iter(jax.random.split(rng, 8))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, dt)
                / math.sqrt(fan_in)).astype(dt)

    return {
        "embed": w((cfg.vocab_size, D), D),
        "head": w((cfg.vocab_size, D), D),
        "final_ln": jnp.ones((D,), dt),
        # the gate's Linear(D, 1), weight then bias, ONE leaf: the bias
        # alone is one number, a sum of signed terms a token whose
        # gradient no comparison by relative norm can hold
        "exit_gate": jnp.concatenate([w((D,), D), jnp.zeros((1,), dt)]),
        "dense": {
            "input_ln": jnp.ones((n, D), dt),
            "post_attn_ln": jnp.ones((n, D), dt),
            "pre_mlp_ln": jnp.ones((n, D), dt),
            "post_mlp_ln": jnp.ones((n, D), dt),
            # [q | k | v] side by side, one product
            "qkv_w": w((n, D, (H + 2 * Hkv) * Dh), D),
            "attn_out_w": w((n, H * Dh, D), H * Dh),
            "mlp_gate_w": w((n, D, F), D),
            "mlp_up_w": w((n, D, F), D),
            "mlp_down_w": w((n, F, D), F),
        },
    }


def num_params(cfg: OuroConfig) -> int:
    """Counted from the tree's shapes; nothing is allocated."""
    return transformer.num_params(jax.eval_shape(
        functools.partial(init_params, cfg=cfg), jax.random.key(0)))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _rope_tables(seq_len: int, cfg: OuroConfig):
    """`(cos, sin)` float32 [S, Dh / 2] that every layer application turns
    its queries and keys by."""
    return head_norm_rope.rope_tables(seq_len, cfg.head_dim, cfg.rope_theta)


def _attention(x, lp, cfg: OuroConfig, kind: str, tables=None):
    """The attention half of a layer: x [B, S, D] -> x + norm(attn).
    `tables`: `_rope_tables`, which `walks` makes once for the whole loop
    (made here, outside the half's scope, where a caller brings none)."""
    dt = cfg.dtype
    B, S, D = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    norm = functools.partial(_rms_norm, bias=None, eps=cfg.rms_norm_eps)
    if tables is None:
        tables = _rope_tables(S, cfg)
    with jax.named_scope(f"ouro.attn.{kind}"):
        with jax.named_scope(".qkv"):
            a = norm(x, lp["input_ln"])
            qkv = jnp.einsum("bsd,de->bse", a, lp["qkv_w"].astype(dt))
            if head_norm_rope.takes(S, Dh):
                # the kernels read `qkv` row-major.  Left to itself the
                # compiler lays the product's result S-minor, as v's
                # transpose likes it, and copies it for them: 15 ms a step
                # of the benchmark's cell, whose product is 7% faster
                # row-major besides (PERF.md, Findings, PR 65)
                qkv = with_layout_constraint(
                    qkv, Layout(major_to_minor=(0, 1, 2)))
            # q and k are read where they lie in `qkv` and turned, no head
            # normed (no scale); v alone is sliced out
            q, k = head_norm_rope.queries_and_keys(
                qkv, None, None, *tables, eps=cfg.rms_norm_eps, heads=H,
                kv_heads=Hkv)
            v = qkv[..., (H + Hkv) * Dh:]
            v = v.reshape(B, S, Hkv, Dh).transpose(0, 2, 1, 3)
            if Hkv != H:
                k = jnp.repeat(k, H // Hkv, axis=1)
                v = jnp.repeat(v, H // Hkv, axis=1)
        # the kernels and the transpose after them stay the half's own
        ctx = afmoe._attn_fn(cfg, kind)(q, k, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H * Dh)
        with jax.named_scope(".out"):
            o = jnp.einsum("bse,ed->bsd", ctx, lp["attn_out_w"].astype(dt))
        with jax.named_scope(".post_norm"):
            return x + norm(o, lp["post_attn_ln"])


def _mlp(x, lp, cfg: OuroConfig):
    """The other half: x -> x + norm(SwiGLU(norm(x)))."""
    norm = functools.partial(_rms_norm, bias=None, eps=cfg.rms_norm_eps)
    with jax.named_scope("ouro.mlp"):
        f = afmoe._swiglu(norm(x, lp["pre_mlp_ln"]), lp, "mlp_", cfg.dtype)
        with jax.named_scope(".post_norm"):
            return x + norm(f, lp["post_mlp_ln"])


def _layer(x, lp, sel, cfg: OuroConfig, kind: str, is_moe: bool = False,
           tables=None):
    """One layer application, `afmoe.run_layers`' signature and the
    rotary `tables` of `_attention`.  x [B, S, D]; returns `(x, None)`: no
    layer routes."""
    del sel, is_moe
    return _mlp(_attention(x, lp, cfg, kind, tables), lp, cfg), None


def _embed(params, tokens, cfg: OuroConfig):
    with jax.named_scope("ouro.embed"):
        return params["embed"].astype(cfg.dtype)[tokens]


def exit_gate(params: PyTree, h: jax.Array) -> jax.Array:
    """`lam` [..., S] float32 of the normed states `h` [..., S, D]: a
    Linear(D, 1) and a sigmoid, in float32 and off the MXU (a float32
    product there is one bfloat16 pass)."""
    gate = params["exit_gate"].astype(jnp.float32)
    logit = (h.astype(jnp.float32) * gate[:-1]).sum(-1)
    return jax.nn.sigmoid(logit + gate[-1])


def walks(params: PyTree, tokens: jax.Array, cfg: OuroConfig):
    """tokens [B, S] int32 -> `(h [T, B, S, D], lam [T, B, S] float32)`:
    every walk's state after the final norm, which the head and the next
    walk read, and its gate.

    ONE scan over the T x L layer applications: application i reads layer
    `i mod L` of the stacked leaves, and the last of a walk norms what it
    hands on and writes it into `h`.  The slice, the layer and that norm
    are one rematerialised body, so the backward pass keeps an
    application's input and nothing else, and adds a layer's gradient
    into the stack where it lies."""
    L, T = cfg.num_layers, cfg.total_ut_steps
    group = params["dense"]
    # once, outside the loop and every scope: 32 applications' three
    # passes read the one pair
    tables = _rope_tables(tokens.shape[1], cfg)

    def end_of_walk(u):
        with jax.named_scope("ouro.exit"):
            return _rms_norm(u, params["final_ln"], None,
                             eps=cfg.rms_norm_eps)

    def application(x, i):
        lp = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i % L, keepdims=False),
            group)
        x, _ = _layer(x, lp, None, cfg, FULL, tables=tables)
        return lax.cond(i % L == L - 1, end_of_walk, lambda u: u, x)
    application = afmoe._remat(application, cfg)

    def step(carry, i):
        x, h = carry
        x = application(x, i)
        h = lax.cond(
            i % L == L - 1,
            lambda h: lax.dynamic_update_index_in_dim(h, x, i // L, 0),
            lambda h: h, h)
        return (x, h), None

    x = _embed(params, tokens, cfg)
    (_, h), _ = lax.scan(step, (x, jnp.zeros((T, *x.shape), x.dtype)),
                         jnp.arange(T * L))
    with jax.named_scope("ouro.exit"):
        return h, exit_gate(params, h)


def exit_distribution(lam: jax.Array) -> jax.Array:
    """`p` [T, ...] from the gates `lam` [T, ...], float32: `p_t = lam_t
    prod_{j<t} (1 - lam_j)`, the last walk taking what is left (its own
    gate is computed and unused), so `p` sums to 1 over the walks."""
    lam = lam.astype(jnp.float32)
    one = jnp.ones_like(lam[:1])
    reached = jnp.concatenate([one, jnp.cumprod(1.0 - lam[:-1], axis=0)])
    return reached * jnp.concatenate([lam[:-1], one])


def exit_entropy(p: jax.Array) -> jax.Array:
    """`H(p)` [...] in nats of `p` [T, ...]; 0 log 0 = 0, and its
    gradient stays finite where a gate has saturated."""
    return -(p * jnp.log(jnp.maximum(p, jnp.finfo(p.dtype).tiny))).sum(0)


def weighted_nll_sum(params: PyTree, h: jax.Array, targets: jax.Array,
                     weights: jax.Array, cfg: OuroConfig) -> jax.Array:
    """The sum over walks and tokens of `weights` [T, B, S] times the
    head's cross-entropy of `h` [T, B, S, D] against `targets` [B, S]:
    the walks' rows are T x B "sequences" of ONE call of the streamed
    head.  Differentiable in `weights`: its gradient is each row's NLL."""
    T, B, S, D = h.shape
    rows = h.reshape(T * B, S, D)
    tiled = jnp.tile(targets, (T, 1))
    weights = weights.reshape(T * B, S)
    if cfg.ce_chunk_rows:
        return fused_nll_sum(rows, params["head"], tiled, cfg.ce_chunk_rows,
                             weights=weights)
    logp = jax.nn.log_softmax(afmoe.head_logits(rows, params["head"]),
                              axis=-1)
    nll = -jnp.take_along_axis(logp, tiled[..., None], axis=-1)[..., 0]
    return (nll * weights).sum()


def loss_fn(params: PyTree, batch, cfg: OuroConfig) -> jax.Array:
    """The mean over tokens of `sum_t p_t nll_t - beta H(p)`.  batch =
    (tokens [B, S], targets [B, S])."""
    tokens, targets = batch
    telemetry.record_static("loop", **loop_counters(cfg, *tokens.shape))
    h, lam = walks(params, tokens, cfg)
    with jax.named_scope("ouro.exit"):
        p = exit_distribution(lam)
        entropy = exit_entropy(p).mean()
    with jax.named_scope("ouro.head"):
        task = weighted_nll_sum(params, h, targets, p, cfg) / targets.size
    return task - cfg.exit_entropy_beta * entropy


# ---------------------------------------------------------------------------
# Counters
# ---------------------------------------------------------------------------
def loop_counters(cfg: OuroConfig, batch: int, seq_len: int) -> dict:
    """What the loop costs, from shapes alone: its walks, its layer
    applications, and the bytes of layer inputs that whole-layer remat
    keeps from the forward pass for the backward pass (one [B, S, D] an
    application, in the activations' dtype)."""
    applications = cfg.total_ut_steps * cfg.num_layers
    return {"steps": cfg.total_ut_steps,
            "layer_applications": applications,
            "kept_bytes": (applications * batch * seq_len * cfg.hidden_size
                           * jnp.dtype(cfg.dtype).itemsize)}


def nll_rows(params: PyTree, h: jax.Array, targets: jax.Array,
             cfg: OuroConfig) -> jax.Array:
    """Every walk's cross-entropy a token, [T, B, S], as the step's head
    computes it: `weighted_nll_sum`'s gradient with respect to its
    weights."""
    ones = jnp.ones(h.shape[:-1], jnp.float32)
    return jax.grad(weighted_nll_sum, argnums=3)(params, h, targets, ones,
                                                 cfg)


def exit_counters(p: jax.Array, nll: jax.Array) -> dict:
    """A batch's exit statistics from its `p` and `nll` [T, B, S],
    traceable: the mean `p_t` over the tokens `share` [T], the mean walk
    a token leaves at `expected_steps` (sum t p_t, walks counted from 1),
    the mean entropy `entropy` in nats and the mean `nll_t` of every walk
    `nll` [T]."""
    steps = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)
    share = p.mean(axis=(1, 2))
    return {"share": share, "expected_steps": (steps * share).sum(),
            "entropy": exit_entropy(p).mean(), "nll": nll.mean(axis=(1, 2))}


def record_exit(counters: dict) -> None:
    """`exit_counters`' numbers, on the host, as the gauges
    `bps_exit_share{step}`, `bps_loop_nll{step}`,
    `bps_exit_expected_steps` and `bps_exit_entropy`."""
    telemetry.record_static(
        "loop_exit", expected_steps=float(counters["expected_steps"]),
        entropy=float(counters["entropy"]))
    for t, (share, nll) in enumerate(zip(counters["share"],
                                         counters["nll"]), start=1):
        telemetry.record_static("loop_exit", labels={"step": str(t)},
                                share=float(share), nll=float(nll))


def synthetic_batch(rng: jax.Array, batch_size: int, seq_len: int,
                    cfg: OuroConfig):
    """Token ids uniform over the whole vocabulary; the targets are the
    next tokens."""
    toks = jax.random.randint(rng, (batch_size, seq_len + 1), 0,
                              cfg.vocab_size, jnp.int32)
    return toks[:, :-1], toks[:, 1:]
