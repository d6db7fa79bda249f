"""One chip's share of a dropless mixture-of-experts layer.

Expert parallelism gives each chip some of a layer's experts and every
chip the whole router.  This is the part one chip computes: it is told
which experts it holds, scores every token over ALL the experts, keeps
the top `k` a token, and returns what its own experts add for the tokens
routed to them,

    out[t] = sum over e in sel[t], e held here, of w[t, e] * expert_e(x[t]),

each expert a SwiGLU (three matrices) or, where the tree holds no
`gate_w`, two matrices with a squared ReLU between them,
`relu(x W_up)^2 W_down`.  What the experts on the other chips would add is
not here and nothing stands in for it: across chips the shares are summed
by the exchange (`parallel/expert.py` has the all-to-all for the hybrid
step's Switch layer, which is top-1 and drops tokens over a capacity; an
exchange for this layer is not written yet).  On one chip the layer runs
without it.

No assignment to a held expert is dropped, whatever the routing:

  - the (token, choice) pairs are sorted by the held expert they chose,
    the pairs that chose an expert held elsewhere last;
  - the first `rows` pairs (a static buffer, `capacity_factor` times the
    even share, so that a usual step fits) are gathered, go through the
    expert's products grouped by expert (`ops/grouped_matmul.py`:
    the program's own Pallas kernels, whose grid walks only the row tiles
    that hold a live row; a width they cannot tile, no multiple of 64,
    goes to `lax.ragged_dot`), are weighted, and are added back to their
    tokens.  Both moves, and both their transposes, are one Pallas kernel
    (`ops/moe_rows.py`: `out[i] = sum_j w[i, j] src[idx[i, j]]`, many row
    copies in flight), under two small `jax.custom_vjp`s, `_rows_in` and
    `_rows_out`: no XLA gather and no scatter-add of rows, forward or
    backward.  A token's sum over its choices is float32 in a fixed order;
  - pairs past the buffer go through the same code, a small buffer at a
    time (`past_rows`, an eighth of the first), in a loop that runs only
    while pairs are left (`_past_the_buffer`).  That is the exact path: no
    faster a row, and taken only when the routing is more skewed than the
    buffer allows.  Its buffers are small so that a routing a little past
    the first buffer pays a little: a step's time then follows the rows,
    and does not jump by a whole pass where they cross the buffer's edge.
    `Routing.overflow` counts the pairs it took.

The router's scores, top-k and weights are float32 (the score matmul at
`highest` precision: on a TPU a float32 matmul is otherwise one bfloat16
pass, and top-k is discontinuous in the scores).  `expert_bias` is the
load balancer's buffer, added to the scores for the choice alone.

A share's backward pass (`MoEConfig.hold_held_weight`, off unless a model
asks).  A share adds only the held experts' results, so its loss falls
whenever a token's weight moves from an absent expert to a held one:
every gradient says "send the held experts more", and a share that trains
alone sends them most of every token's choices within a few steps, which
no deployment does (there the absent experts' results come back from
their chips and compete).  With the option the weight a token gives the
held experts together, `W = sum over held of w`, is a CONSTANT of the
backward pass: the weights are used as `w * stop_gradient(W) / W`, the
same numbers, whose gradient is what the held experts' competition among
themselves gives.  Under weights that sum to 1 that is the gradient the
layer would have if each absent expert returned the weighted mean of the
held ones the token chose: the absent experts' logits get none.

What a rematerialised layer can keep (`ROUTING_NAME`).  What the router
decided and what the plan sorted carry one name for `jax.checkpoint`: the
score product's `logits` [T, E] float32 (the scores and the weights'
gradient are a few elementwise passes from them), `sel` and `weights`
[T, k], and the plan's `order`, `place`, `starts` and `ends`, integers.  A layer under
`save_only_these_names(dropless_moe.ROUTING_NAME)` holds them from its
forward pass and its recompute runs no score product, no top-k, no sort
and no count a second time (`kept_bytes`: under 10 MB a layer at 16,384
tokens and 128 experts).  Under a policy that does not list the name it is
an identity that lowers to nothing.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops import grouped_matmul as gm
from ..ops import moe_rows

# The name the router's and the plan's results carry for `jax.checkpoint`
# (the module's docstring says what a policy that lists it keeps).
ROUTING_NAME = "moe.routing"


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int                 # the router's width: all the experts
    top_k: int
    held: Tuple[int, ...]            # ids of the experts this chip holds
    route_scale: float = 1.0
    route_norm: bool = True          # weights of a token sum to route_scale
    norm_eps: float = 1e-20          # beside that sum (lfm2's is 1e-6)
    score_func: str = "sigmoid"      # "sigmoid" | "softmax"
    capacity_factor: float = 1.25    # the buffer over the even share
    row_multiple: int = 512          # the buffer is a multiple of this
    hold_held_weight: bool = False   # a share's backward pass (above)

    def __post_init__(self):
        if self.score_func not in ("sigmoid", "softmax"):
            raise ValueError(f"score_func={self.score_func!r}")
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k={self.top_k} of {self.num_experts}")
        if (len(set(self.held)) != len(self.held) or not self.held
                or not all(0 <= e < self.num_experts for e in self.held)):
            raise ValueError(f"held={self.held} must be distinct ids below "
                             f"{self.num_experts}")

    def buffer_rows(self, n_tokens: int) -> int:
        """Rows of the static buffer for `n_tokens` tokens: the even
        share of the (token, choice) pairs times `capacity_factor`, up to
        a multiple of `row_multiple`, and never more than every pair."""
        pairs = n_tokens * self.top_k
        even = pairs * len(self.held) / self.num_experts
        m = self.row_multiple
        rows = -(-int(even * self.capacity_factor + 0.5) // m) * m
        return max(min(rows, -(-pairs // m) * m), m)

    def past_rows(self, n_tokens: int) -> int:
        """Rows of one buffer of the exact path: an eighth of the first
        buffer, up to a multiple of `row_multiple`."""
        m = self.row_multiple
        return -(-self.buffer_rows(n_tokens) // (8 * m)) * m

    def sorted_rows(self, n_tokens: int) -> int:
        """Entries of the plan's sorted list of pairs: whole buffers, the
        first and, where the pairs pass it, the exact path's."""
        pairs, rows = n_tokens * self.top_k, self.buffer_rows(n_tokens)
        return max(rows, pairs + -(pairs - rows) % self.past_rows(n_tokens))

    def kept_bytes(self, n_tokens: int) -> int:
        """Bytes a layer's `ROUTING_NAME` names: the logits, `sel` and
        `weights`, the sorted list, each pair's place in it and each held
        expert's start and end, four bytes each."""
        return 4 * (n_tokens * (self.num_experts + 3 * self.top_k)
                    + self.sorted_rows(n_tokens) + 2 * len(self.held))


class Routing(NamedTuple):
    """What the router decided, and the counters of the layer."""
    sel: jax.Array        # [T, k] int32, the experts each token chose
    weights: jax.Array    # [T, k] float32
    held_rows: jax.Array  # () int32: pairs that chose an expert held here
    counts: jax.Array     # [len(held)] int32: pairs a held expert
    overflow: jax.Array   # () int32: pairs past the buffer (exact path)


def route(x, router_w, cfg: MoEConfig, expert_bias=None, sel=None):
    """Scores `x` [T, D] over all experts and returns `(sel, weights)`,
    both [T, k].  `expert_bias` [E] moves the choice and not the weights
    (the load balancer's buffer; None is zero).  `sel`, if given, is used
    in place of the top-k: the scores and weights are then this router's
    own at somebody else's choice, which is how a reference is compared
    apart from the choice."""
    with jax.default_matmul_precision("highest"):
        logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    # the product's result and not the scores: a score function's own
    # derivative reads ITS result, whatever name is laid over that, and
    # would have the product made again for it
    logits = checkpoint_name(logits, ROUTING_NAME)
    if cfg.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    if sel is None:
        biased = scores if expert_bias is None else (
            scores + lax.stop_gradient(expert_bias.astype(jnp.float32)))
        _, sel = lax.top_k(lax.stop_gradient(biased), cfg.top_k)
    sel = checkpoint_name(sel, ROUTING_NAME)
    weights = _chosen(scores, sel)
    if cfg.route_norm:
        weights = weights / (weights.sum(-1, keepdims=True) + cfg.norm_eps)
    if cfg.hold_held_weight and len(cfg.held) < cfg.num_experts:
        weights = _held_weight_held(weights, sel, cfg)
    return sel, checkpoint_name(weights * cfg.route_scale, ROUTING_NAME)


def _chosen(scores, sel):
    """`take_along_axis(scores, sel, -1)`, [T, k], bit for bit, as a
    compare and a sum over the experts: one value and E - 1 zeros a pair.
    The compiler's gather of T k single scores out of [T, E], and the
    scatter-add that is its transpose, took 2 ms and 2.3 ms a layer and
    pass at 32,768 tokens of 8 choices over 128 experts, 42 ms of
    trinity-mini's step; this is an elementwise pass over [T, k, E] that
    is never stored, both ways (PERF.md, Findings, PR 52)."""
    chosen = sel[:, :, None] == lax.broadcasted_iota(
        sel.dtype, (1, 1, scores.shape[-1]), 2)
    return jnp.where(chosen, scores[:, None, :], 0).sum(-1)


def _held_weight_held(weights, sel, cfg: MoEConfig):
    """`weights` [T, k], bit for bit, with the gradient of
    `weights * stop_gradient(W) / W`, W the token's weight on the held
    experts (1 where it chose none of them)."""
    here = jnp.isin(sel, jnp.asarray(cfg.held, sel.dtype))
    held = jnp.where(here, weights, 0.0).sum(-1, keepdims=True)
    scaled = weights * jnp.where(
        held > 0, lax.stop_gradient(held) / jnp.where(held > 0, held, 1.0),
        1.0)
    return lax.stop_gradient(weights) + (scaled - lax.stop_gradient(scaled))


def _grouped_on(group_sizes, rows: int, dtype):
    """`(lhs, weights) -> lhs` through each group's own matrix, for the
    products of ONE routing: its tables are made once and shared."""
    walk = gm.row_walk(group_sizes, rows)

    def grouped(lhs, w):
        return gm.grouped_matmul(lhs, w.astype(dtype), group_sizes,
                                 walk=walk)
    return grouped


def _swiglu_grouped(xg, experts, group_sizes, dtype):
    """The three products of every held expert on its own rows of `xg`
    [rows, D], which lie grouped by expert, `group_sizes` rows each."""
    grouped = _grouped_on(group_sizes, xg.shape[0], dtype)
    h = jax.nn.silu(grouped(xg, experts["gate_w"])) * grouped(
        xg, experts["up_w"])
    return grouped(h, experts["down_w"])


def _relu2_grouped(xg, experts, group_sizes, dtype):
    """The two products of every held expert that has no gate,
    `relu(x W_up)^2 W_down`, on rows grouped as `_swiglu_grouped`'s."""
    grouped = _grouped_on(group_sizes, xg.shape[0], dtype)
    return grouped(jnp.square(jax.nn.relu(grouped(xg, experts["up_w"]))),
                   experts["down_w"])


class _Plan(NamedTuple):
    """The sorted (token, choice) pairs; integers, nothing to
    differentiate."""
    order: jax.Array      # [rows + n * past] pair indices, held experts first
    place: jax.Array      # [T * k] where each pair lies in `order`: its inverse
    starts: jax.Array     # [len(held)] where each held expert's pairs begin
    ends: jax.Array
    held_rows: jax.Array  # ()


def _plan(sel, cfg: MoEConfig) -> _Plan:
    """Sorts the pairs by the held expert they chose, pairs of experts
    held elsewhere last, and pads the list to whole buffers
    (`MoEConfig.sorted_rows`)."""
    n_held = len(cfg.held)
    # which held expert each pair chose, [held, pairs]: compares, not a
    # lookup by T k indices (the compiler's gather of single integers
    # took 2 ms a layer and pass)
    chose = (jnp.asarray(cfg.held, jnp.int32)[:, None]
             == sel.reshape(-1)[None, :])
    slot = n_held + (chose * (jnp.arange(n_held, dtype=jnp.int32)
                              - n_held)[:, None]).sum(0, dtype=jnp.int32)
    order = jnp.argsort(slot, stable=True).astype(jnp.int32)
    counts = chose.sum(1, dtype=jnp.int32)
    ends = jnp.cumsum(counts)
    pad = cfg.sorted_rows(sel.shape[0]) - order.size
    order, place, starts, ends = (
        checkpoint_name(t, ROUTING_NAME) for t in (
            jnp.concatenate([order, jnp.zeros((pad,), jnp.int32)]),
            jnp.argsort(order).astype(jnp.int32), ends - counts, ends))
    return _Plan(order, place, starts, ends, ends[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_in(x, token, place, k):
    """`x[token]` [rows, D], zeros where `token` is -1: the tokens' rows
    into the buffer.  `place` [T * k] is the other side of the same
    list, the buffer row of each pair (-1: none), by which the transpose
    is the same kernel: a token's gradient is the float32 sum of its `k`
    rows', rounded once."""
    del place
    return moe_rows.gather_sum(x, token, k=1, use="gather")


def _rows_in_fwd(x, token, place, k):
    return _rows_in(x, token, place, k), place


def _rows_in_bwd(k, place, g):
    return moe_rows.gather_sum(g, place, k=k, use="scatter"), None, None


_rows_in.defvjp(_rows_in_fwd, _rows_in_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rows_out(y, flat_w, token, pair, place, k, dtype):
    """`out[t] = sum_j flat_w[t k + j] * y[place[t k + j]]` [T, D]
    float32, j ascending: the buffer's results weighted and added back to
    their tokens.  `token` and `pair` [rows] say whose each buffer row is
    (`token` -1: nobody's; what `y` holds there, NaN included, reaches
    neither the result, which never asks for it, nor a gradient).

    Backward: the result's gradient is fetched by token ONCE, [rows, D];
    `y`'s is that times the row's weight, the weight's its dot product
    with `y`'s row (masked BEFORE the product, whose other side may be
    NaN), handed back to the pair it came from.  The gradient is moved in
    `dtype`, the layer's: `held_experts` rounds its float32 sum to it, so
    what comes back is that dtype's values widened, and narrowing them
    again loses nothing."""
    del token, pair, dtype
    return moe_rows.gather_sum(y, place, flat_w, k=k, out_dtype=jnp.float32,
                               use="scatter")


def _rows_out_fwd(y, flat_w, token, pair, place, k, dtype):
    return (_rows_out(y, flat_w, token, pair, place, k, dtype),
            (y, flat_w, token, pair))


@jax.jit
def _rows_out_grads(y, flat_w, token, pair, gg):
    """`_rows_out`'s gradients from the result's, fetched by token (under
    a plain `jax.jit`: a dozen elementwise operations traced once a
    shape)."""
    live = token >= 0
    gg = gg.astype(jnp.float32)
    # (single weights fetched and handed back: a scope of their own, so
    # that whoever counts the layer's moves of ROWS does not count these)
    with jax.named_scope(".weights"):
        w_row = jnp.where(live, flat_w[pair], 0)
    d_y = gg * w_row[:, None]
    dots = (jnp.where(live[:, None], y, 0).astype(jnp.float32) * gg).sum(-1)
    with jax.named_scope(".weights"):
        d_w = jnp.zeros_like(flat_w).at[pair].add(jnp.where(live, dots, 0))
    return d_y.astype(y.dtype), d_w


def _rows_out_bwd(k, dtype, residuals, g):
    y, flat_w, token, pair = residuals
    gg = moe_rows.gather_sum(g.astype(dtype), token, k=1, use="gather")
    return *_rows_out_grads(y, flat_w, token, pair, gg), None, None, None


_rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


@functools.partial(jax.jit, static_argnames=("k", "rows"))
def _buffer_rows(lo, plan: _Plan, *, k: int, rows: int):
    """Whose the rows `[lo, lo + rows)` of the sorted list are: each row's
    `pair` and `token` (-1: no pair fills the row), each pair's `place`
    among these rows (-1: a pair of another buffer, or of an expert held
    elsewhere), and the rows each held expert has here.  Integers, under
    a plain `jax.jit`: traced once a shape, not once a buffer."""
    pair = lax.dynamic_slice_in_dim(plan.order, lo, rows)
    live = lo + jnp.arange(rows, dtype=jnp.int32) < plan.held_rows
    place = jnp.where(
        (plan.place >= lo)
        & (plan.place < jnp.minimum(lo + rows, plan.held_rows)),
        plan.place - lo, -1)
    group_sizes = jnp.clip(jnp.minimum(plan.ends, lo + rows)
                           - jnp.maximum(plan.starts, lo), 0)
    return pair, jnp.where(live, pair // k, -1), place, group_sizes


def _buffer(lo, x, experts, flat_w, plan: _Plan, k: int, rows: int):
    """What the pairs `[lo, lo + rows)` of the sorted list add to the
    layer's result, [T, D] float32."""
    with jax.named_scope(".gather"):
        pair, token, place, group_sizes = _buffer_rows(lo, plan, k=k,
                                                       rows=rows)
        # The grouped product's kernels (`ops/grouped_matmul.py`, as the
        # compiler's own before them) leave the rows past the last group
        # as they found them, in the forward and in the backward products
        # alike (the CPU's `lax.ragged_dot` writes zeros): whatever is
        # there, NaN included, must reach neither the result nor a
        # gradient.  So those rows are masked on the way in (a row of
        # token -1 comes in as zeros, and its gradient goes nowhere: no
        # pair's `place` names it), and on the way out BEFORE the weights
        # are multiplied in (`_rows_out`), whose gradient is otherwise
        # 0 * NaN.
        xg = _rows_in(x, token, place, k)
    with jax.named_scope(".grouped"):
        form = _swiglu_grouped if "gate_w" in experts else _relu2_grouped
        y = form(xg, experts, group_sizes, x.dtype)
    with jax.named_scope(".scatter"):
        return _rows_out(y, flat_w, token, pair, place, k, x.dtype)


def _past_buffers(rows: int, past: int, plan: _Plan):
    """How many of the exact path's buffers hold a pair."""
    return -(-jnp.maximum(plan.held_rows - rows, 0) // past)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _past_the_buffer(k, rows, past, x, experts, flat_w, plan):
    """The exact path: what the pairs past the first buffer add, `past`
    of them at a time while pairs are left.  Forward and backward are loops
    with a trip count that depends on the routing (`lax.fori_loop` to a
    traced bound), which reverse-mode differentiation cannot cross by
    itself and a `lax.scan` over every possible buffer would pay for with
    stacked residuals (1.9 GB at the published widths): so the backward
    is written here, each buffer's own `jax.vjp` summed."""
    return lax.fori_loop(
        0, _past_buffers(rows, past, plan),
        lambda i, acc: acc + _buffer(rows + i * past, x, experts, flat_w,
                                     plan, k, past),
        jnp.zeros(x.shape, jnp.float32))


def _past_fwd(k, rows, past, x, experts, flat_w, plan):
    return (_past_the_buffer(k, rows, past, x, experts, flat_w, plan),
            (x, experts, flat_w, plan))


def _past_bwd(k, rows, past, residuals, g):
    x, experts, flat_w, plan = residuals

    def body(i, acc):
        _, vjp = jax.vjp(
            lambda x, e, w: _buffer(rows + i * past, x, e, w, plan, k, past),
            x, experts, flat_w)
        return jax.tree.map(jnp.add, acc, vjp(g))

    grads = lax.fori_loop(
        0, _past_buffers(rows, past, plan), body,
        jax.tree.map(jnp.zeros_like, (x, experts, flat_w)))
    return (*grads, None)


_past_the_buffer.defvjp(_past_fwd, _past_bwd)


def held_experts(x, router_w, experts, cfg: MoEConfig, expert_bias=None,
                 sel=None):
    """`x` [T, D] -> `(out [T, D], Routing)`: the held experts' part of
    the layer.  `experts` holds `gate_w`, `up_w` [len(held), D, F] and
    `down_w` [len(held), F, D], in the order of `cfg.held`; without
    `gate_w` an expert is `relu(x up_w)^2 down_w`."""
    T = x.shape[0]
    k = cfg.top_k
    rows, past = cfg.buffer_rows(T), cfg.past_rows(T)
    gm.record_walk(rows, *experts["up_w"].shape[1:], len(cfg.held), x.dtype,
                   live=T * k * len(cfg.held) // cfg.num_experts)
    # The layer's parts are children of whatever scope it is called under
    # (`<family>.moe`): a leading "." says so, and `bps.get_step_scopes()`
    # reads `<family>.moe/route`.
    with jax.named_scope(".route"):
        sel, weights = route(x, router_w, cfg, expert_bias, sel)
        plan = _plan(sel, cfg)
        flat_w = weights.reshape(-1)
    out = _buffer(jnp.int32(0), x, experts, flat_w, plan, k, rows)
    if plan.order.size > rows:
        # around the CALL: a hand-written backward pass is traced under
        # the scopes around its call, not those its forward opened
        with jax.named_scope(".exact"):
            out = out + _past_the_buffer(k, rows, past, x, experts, flat_w,
                                         plan)
    with jax.named_scope(".route"):
        routing = Routing(sel, weights, plan.held_rows,
                          plan.ends - plan.starts,
                          jnp.maximum(plan.held_rows - rows, 0))
    return out.astype(x.dtype), routing


def counters(routing: Routing, n_tokens: int) -> dict:
    """The layer's counters from its own routing: pairs routed to held
    experts over tokens (`k * held / experts` under even routing, which
    is 1.0 where a chip holds an eighth of the experts and a token takes
    8), the fullest held expert's load over the mean, and the pairs the
    exact path took."""
    counts = routing.counts.astype(jnp.float32)
    return {"held_rows_per_token": routing.held_rows / n_tokens,
            "max_load_over_mean": counts.max() / jnp.maximum(counts.mean(),
                                                             1e-9),
            "overflow_rows": routing.overflow}
