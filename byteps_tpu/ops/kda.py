"""A chunked delta-rule scan whose state decays by a vector: one factor a
KEY CHANNEL (Kimi Delta Attention, arXiv:2510.26692), with its backward
pass.

The recurrence, per head (q_t, k_t of the key size K, v_t of the value
size V; g_t <= 0 a log-decay for each of the K channels; 0 < beta_t < 1):

    q_t <- l2norm(q_t) / sqrt(K),   k_t <- l2norm(k_t)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                  S_0 = 0, S [K, V]

The state DECAYS, a channel at its own rate, and is then CORRECTED along
k_t: what `ops/ssd.py` scans decays by one scalar a head and only gains a
rank-1 term.  With u_t = beta_t (v_t - (Diag(exp(g_t)) S_{t-1})^T k_t),
the corrected value, S_t = Diag(exp(g_t)) S_{t-1} + k_t u_t^T, so a chunk
of C positions is (G_r the sum of g over the chunk's rows up to r, S0 the
state that enters it):

    A_rj = beta_r sum_c k_rc k_jc exp(G_rc - G_jc)       j < r, else 0
    (I + A) U = diag(beta) (V - (K * exp(G)) S0)
    o_r  = (q_r * exp(G_r))^T S0
         + sum_{j<=r} (sum_c q_rc k_jc exp(G_rc - G_jc)) u_j
    S_C  = Diag(exp(G_C)) S0 + sum_j (k_j * exp(G_C - G_j)) u_j^T

Three things the SSD form never needs.  The pairwise decays are a sum
over CHANNELS of exponentials of differences, so no [C, C] decay matrix
factors out of the products; and the obvious factoring, `exp(G_r) *
exp(-G_j)`, overflows float32 (a step's log-decay reaches -10 at the
family's `A_log`, 64 of them -640).  Here every exponent is G_r - G_j with
j <= r and never positive: inside a block of `BLOCK` = 16 rows the
differences are taken pair by pair; across blocks through the later
block's FIRST row b, `exp(G_r - G_b) * exp(G_b - G_j)`, both factors at
most 1, each folded into an operand of a matrix product.  And the
correction is a triangular solve: `(I + A)^-1` is the product
(I - A)(I + A^2)(I + A^4) ... (I + A^(C/2)), A being nilpotent, taken in
float32.

Two forms of the one algorithm, both with the chunk states ([B, H, S/C,
V, K] float32, `state_bytes`) kept from the forward pass and the chunks
walked in reverse by the backward pass, which carries the state's
gradient:

  - `kda_scan`: two Pallas TPU kernels, `kda_fwd_c<C>` and `kda_bwd_c<C>`
    (the names are how a device trace tells them from the flash, SSD and
    convolution calls), grid (batch, heads / hp, chunks), the chunks
    innermost and the states of a program's `hp` heads, or their
    gradients, in VMEM scratch [hp, V, K] across them.  They
    read the MIXER'S OWN layout: q, k, v, g and the results as [B, S, H K]
    (a program's block is a slab [C, hp K] of its heads' columns, whole
    lane tiles; no transposed copy of a
    wide operand, `ops/ssd.py` says what those cost); only beta and its
    gradient, [B, S, H] float32, are laid head-major outside, a program's
    block the [S/C, C] of its `hp` heads, beside the chunk states' [1,
    hp, 1, V, K].  HEADS A PROGRAM (`heads_per_program`, a rule of shapes
    and no option): the most of 4, 2, 1 in the forward kernel, of 2, 1 in
    the backward one, that divides the heads where K and V are multiples
    of 128 lanes (a head is then a constant lane slice of the slab, at a
    tile's edge), else 1, the parent's program (the tests' small
    shapes).  A chunk of one head is ONE dependency
    chain of two kinds of work, the solve's [C, C] float32 products on
    the MXU and the diagonals' rotations, `exp`s and sums on the vector
    units, each idle while the other runs; and Mosaic's scheduler keeps
    close to the ORDER OF THE TEXT.  So a head's arithmetic is traced
    once (`jax.make_jaxpr` of `_chunk_kernel` / `_chunk_transposed`, not
    a character of which knows of this) and the program's text is that
    jaxpr's equations laid out IN STEP (`_in_step`): equation i of every
    head of the program, then equation i + 1; the heads' results stored
    side by side.  Same products, same precisions, same order within a
    head; the results are the one-head program's bit for bit.  What the
    chip said at the cell's shapes (B 1, S 32,768, H 32, K = V = 128,
    bfloat16; a call's ms in a device trace, Mosaic's seconds a kernel;
    PERF.md, Findings, PR 63): forward 36.34 ms at one head; the heads ONE
    AFTER ANOTHER in the text (`ssd._walk_heads`' way) 34.32 at two,
    33.32 at four, 32.83 at eight, 4.04 / hp off and no more: a
    program's fill and drain shared, nothing overlapped; IN STEP 23.99 at
    two (0.5 s), **19.59 at four (1.0 s)**, 19.20 at eight (3.4 s), 19.11
    at sixteen (8.7 s).  Backward 70.23 at one; one after another 62.18
    at two, 61.37 at four, by a loop of two 70.04 (a step of the grid
    costs nothing: 24 ns); in step **51.02 at two (1.3 s)**, 48.73 at
    four (3.1 s), 46.66 at eight (10.3 s).  Four forward: eight buys 2%
    of a call for three times the compile.  Two backward: a run's set-up
    traces and lowers every head's equations, 1,400 a head there and 560
    forward, on a host that traces five times slower than it does
    anything else, and with four heads both ways the cell's warm set-up
    read 231.4 s against the parent's 221.4; the two heads more would
    have bought 9 ms of a 1,530 ms step.  The l2
    norms and the 1 / sqrt(K), the cumulative sum of g (a product with a
    triangle of ones, float32) and all of the above are inside.  The
    backward kernel is `jax.vjp` of the forward kernel's chunk
    (`_chunk_kernel`) taken INSIDE its body (`_chunk_transposed`): one
    chunk's intermediates are made again in VMEM, and g's gradient leaves
    in float32.  `jax.vjp` transposes everything but the solve, which has
    a rule of its own (`_solve`, a `jax.custom_vjp`): the derivative of
    an inverse needs the inverse alone, d inv = inv dn inv, so n's
    cotangent is inv^T ct inv^T, two products of what the body has just
    made, at the solve's precision; autodiff of the doubling product
    would walk its ten products back with two transposed products each
    and hold every round's factors for it.  `solve_products` counts what
    a traced body holds (the gauge `bps_kda_bwd_solve_products`: 12).
  - `kda_scan_jnp`: the same equations in `jax.numpy`, all heads at once,
    a `lax.scan` over chunks whose step is rematerialised; the solve is
    `solve_triangular`.  What the kernels are tested against, and what
    they were timed against on the chip (PERF.md, Findings, PR 57).

Precision.  Products take their operands in q's dtype (bfloat16 in a
step, float32 in the tests and the reference checks) and sum in float32;
G, every exponent, the pairwise terms inside a block, the solve, the
state and its gradient are float32 whatever q is; `o` is rounded once.

`interpret=None` runs the kernels in the Pallas interpreter off the TPU,
as `ops/ssd.py` does.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend import core as jex_core

from ..common import telemetry
from . import ssd
from .ssd import NEG_INF, _dot, _nn, _nt, _tn

_F32 = jnp.float32
CHUNK = 64
# Rows whose pairwise decays are taken pair by pair; a later block's rows
# reach an earlier block's through the later block's first row.
BLOCK = 16
# Beside the sum of squares under an l2 norm.
L2_EPS = 1e-6
FWD_NAME, BWD_NAME = "kda_fwd_c{}", "kda_bwd_c{}"
_VMEM_MARGIN = 24 * 1024 * 1024


def state_bytes(batch: int, heads: int, seq_len: int, key_dim: int,
                value_dim: int, chunk: int = CHUNK) -> int:
    """Bytes of chunk states one call keeps for its backward pass."""
    return batch * heads * (seq_len // chunk) * key_dim * value_dim * 4


def _normed(q, k):
    """float32 q, k [..., K] -> l2-normed over the head, q over sqrt(K)."""
    def unit(t):
        return t * lax.rsqrt((t * t).sum(-1, keepdims=True) + L2_EPS)
    return unit(q) * (1.0 / math.sqrt(q.shape[-1])), unit(k)


# ---------------------------------------------------------------------------
# The jnp form: one chunk of one head (vmapped), and the scan over chunks
# ---------------------------------------------------------------------------
def _pairwise_jnp(a, k, G, dtype):
    """sum_c a_rc k_jc exp(G_rc - G_jc) for j <= r, 0 above: a, k, G
    [C, K] float32 -> [C, C] float32."""
    C, K = a.shape
    nb = C // BLOCK
    ab, kb, Gb = (t.reshape(nb, BLOCK, K) for t in (a, k, G))
    keep = jnp.arange(BLOCK)[:, None] >= jnp.arange(BLOCK)[None, :]
    # inside a block, pair by pair
    e = jnp.exp(jnp.where(keep[None, :, :, None],
                          Gb[:, :, None, :] - Gb[:, None, :, :], NEG_INF))
    inside = jnp.einsum("nrc,njc,nrjc->nrj", ab, kb, e)
    inside = jnp.einsum("nrj,nm->nrmj", inside, jnp.eye(nb, dtype=_F32))
    # across blocks, through the later block's first row
    first = Gb[:, :1]                                           # [nb, 1, K]
    later = (ab * jnp.exp(Gb - first)).astype(dtype)
    earlier = jnp.arange(C)[None, :] < (jnp.arange(nb) * BLOCK)[:, None]
    keys = (k[None] * jnp.exp(jnp.where(
        earlier[..., None], first - G[None], NEG_INF))).astype(dtype)
    across = _dot("nrc,njc->nrj", later, keys)                  # [nb, B, C]
    return inside.reshape(C, C) + across.reshape(C, C)


def _chunk_jnp(q, k, v, g, beta, state):
    """One chunk of one head.  q, k [C, K], v [C, V]; g [C, K] float32;
    beta [C] float32; state [V, K] float32 (the value's axis first: a
    channel's decay then runs along the lanes) -> `(o [C, V] float32, the
    state the chunk leaves)`."""
    dtype = q.dtype
    C = q.shape[0]
    q32, k32 = _normed(q.astype(_F32), k.astype(_F32))
    G = jnp.cumsum(g, axis=0)
    lower = jnp.arange(C)[:, None] > jnp.arange(C)[None, :]
    akk = jnp.where(lower, _pairwise_jnp(k32, k32, G, dtype), 0.0)
    aqk = _pairwise_jnp(q32, k32, G, dtype)
    e = jnp.exp(G)
    st = state.astype(dtype)
    w = beta[:, None] * (v.astype(_F32)
                         - _dot("rc,vc->rv", (k32 * e).astype(dtype), st))
    u = jax.scipy.linalg.solve_triangular(
        jnp.eye(C, dtype=_F32) + beta[:, None] * akk, w, lower=True,
        unit_diagonal=True).astype(dtype)
    o = (_dot("rc,vc->rv", (q32 * e).astype(dtype), st)
         + _dot("rj,jv->rv", aqk.astype(dtype), u))
    tail = (k32 * jnp.exp(G[-1:] - G)).astype(dtype)
    return o, jnp.exp(G[-1:]) * state + _dot("jv,jc->vc", u, tail)


def _heads(t, heads: int, chunk: int):
    """[B, S, H w] -> [S/C, B, H, C, w]: chunks in front for `lax.scan`."""
    B, S, wide = t.shape
    t = t.reshape(B, S // chunk, chunk, heads, wide // heads)
    return t.transpose(1, 0, 3, 2, 4)


def kda_scan_jnp(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                 beta: jax.Array, chunk: int = CHUNK,
                 chunk_fn=_chunk_jnp) -> jax.Array:
    """`kda_scan`'s arguments and result, in `jax.numpy`: a `lax.scan`
    over the chunks, a step all heads of one chunk (`chunk_fn` under two
    `vmap`s) and rematerialised, so that the backward pass holds a state a
    chunk and not a chunk's intermediates."""
    B, S, _ = q.shape
    H = beta.shape[-1]
    _check(q, k, v, g, beta, chunk)
    one = jax.vmap(jax.vmap(chunk_fn))

    @jax.checkpoint
    def step(state, xs):
        o, state = one(*xs, state)
        return state, o.astype(v.dtype)

    xs = (*(_heads(t, H, chunk) for t in (q, k, v, g.astype(_F32))),
          _heads(beta.astype(_F32), H, chunk)[..., 0])
    zero = jnp.zeros((B, H, v.shape[-1] // H, q.shape[-1] // H), _F32)
    _, o = lax.scan(step, zero, xs)
    return o.transpose(1, 0, 3, 2, 4).reshape(v.shape)


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------
def _hi(a, b, contract=((1,), (0,))):
    """a b in float32, every bit of the operands; `contract` the
    dimensions summed over: ((0,), (0,)) gives a^T b, ((1,), (1,)) a b^T."""
    return lax.dot_general(a, b, (contract, ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=_F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _down(x, d):
    """x [C, n] moved `d` rows down (row r holds x[r - d]; the first `d`
    rows hold the last): a rotation over the sublanes."""
    return pltpu.roll(x, d, 0)


def _down_fwd(x, d):
    return _down(x, d), None


def _down_bwd(d, _, ct):
    return (pltpu.roll(ct, ct.shape[0] - d, 0),)


_down.defvjp(_down_fwd, _down_bwd)


def _row(x, at, r):
    """Row `r` of x [C, n] as [1, n], by mask and sum (`at` the rows'
    indices, [C, n]): no slice, so nothing a transpose rule has to pad."""
    return jnp.sum(jnp.where(at == r, x, 0.0), axis=0, keepdims=True)


def _eye(n):
    return (lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _column(row):
    """[1, C] -> [C, 1]."""
    return jnp.sum(jnp.where(_eye(row.shape[1]), row, 0.0), axis=1,
                   keepdims=True)


def _as_row(col):
    """[C, 1] -> [1, C]."""
    return jnp.sum(jnp.where(_eye(col.shape[0]), col, 0.0), axis=0,
                   keepdims=True)


@jax.custom_vjp
def _solve(n):
    """(I - n)^-1 for n [C, C] float32, strictly lower and so nilpotent:
    the doubling product (I + n)(I + n^2)(I + n^4) ... (I + n^(C/2))."""
    C = n.shape[0]
    inv = jnp.where(_eye(C), 1.0, 0.0) + n
    for _ in range(max(C.bit_length() - 2, 0)):
        n = _hi(n, n)
        inv = inv + _hi(inv, n)
    return inv


def _solve_fwd(n):
    inv = _solve(n)
    return inv, inv


def _solve_bwd(inv, ct):
    """d inv = inv dn inv, the inverse's own derivative: n's cotangent is
    inv^T ct inv^T, the transposes by the contracting dimensions."""
    return (_hi(_hi(inv, ct, ((0,), (0,))), inv, ((1,), (1,))),)


_solve.defvjp(_solve_fwd, _solve_bwd)


def _chunk_kernel(q, k, v, g, beta, state):
    """`_chunk_jnp` for the kernels' bodies: beta a column [C, 1], and
    only what Mosaic lowers, forward and transposed: no slice, no
    reshape, no gather; masks, rotations over the sublanes, sums and
    products."""
    dtype = q.dtype
    C, K = q.shape
    nb = C // BLOCK
    q32, k32 = _normed(q.astype(_F32), k.astype(_F32))
    rows = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    at = lax.broadcasted_iota(jnp.int32, (C, K), 0)
    # a row's place in its block and its block (BLOCK is a power of two)
    within, block = at & (BLOCK - 1), at >> (BLOCK.bit_length() - 1)
    row_block = rows >> (BLOCK.bit_length() - 1)
    G = _hi((rows >= cols).astype(_F32), g)                     # cumsum
    # inside a block: the d-th diagonal below the main one, d < BLOCK
    akk = jnp.zeros((C, C), _F32)
    aqk = jnp.where(rows == cols,
                    jnp.sum(q32 * k32, axis=1, keepdims=True), 0.0)
    for d in range(1, BLOCK):
        ke = _down(k32, d) * jnp.exp(jnp.where(
            within >= d, G - _down(G, d), NEG_INF))
        here = rows - cols == d
        akk = jnp.where(here, jnp.sum(k32 * ke, axis=1, keepdims=True), akk)
        aqk = jnp.where(here, jnp.sum(q32 * ke, axis=1, keepdims=True), aqk)
    # across blocks: the later block's rows against its first row, the
    # earlier rows' keys against the same
    firsts = [_row(G, at, i * BLOCK) for i in range(1, nb)]
    first = firsts[-1] if firsts else None
    for i in range(nb - 2, 0, -1):
        first = jnp.where(block == i, firsts[i - 1], first)
    if firsts:
        later = jnp.exp(jnp.where(at >= BLOCK, G - first, NEG_INF))
        kl, ql = (k32 * later).astype(dtype), (q32 * later).astype(dtype)
        for i in range(1, nb):
            keys = (k32 * jnp.exp(jnp.where(
                at < i * BLOCK, firsts[i - 1] - G, NEG_INF))).astype(dtype)
            mine = row_block == i
            akk = akk + jnp.where(mine, _nt(kl, keys), 0.0)
            aqk = aqk + jnp.where(mine, _nt(ql, keys), 0.0)
    # (I + A)^-1, A = diag(beta) akk nilpotent
    inv = _solve(-beta * akk)
    e = jnp.exp(G)
    last = _row(G, at, C - 1)
    st = state.astype(dtype)
    w = beta * (v.astype(_F32) - _nt((k32 * e).astype(dtype), st))
    u = _nn(inv.astype(dtype), w.astype(dtype)).astype(dtype)
    o = _nt((q32 * e).astype(dtype), st) + _nn(aqk.astype(dtype), u)
    tail = (k32 * jnp.exp(last - G)).astype(dtype)
    return o, jnp.exp(last) * state + _tn(u, tail)


def _chunk_transposed(q, k, v, g, beta, state, do, dstate):
    """The backward kernel's body: `_chunk_kernel` made again and its
    transpose, by `jax.vjp` but for the solve (`_solve_bwd`), applied to
    the cotangents of `o` and of the state the chunk left."""
    _, pull = jax.vjp(_chunk_kernel, q, k, v, g, beta, state)
    return pull((do, dstate))


def _dots(jaxpr):
    """Every `dot_general` of a jaxpr and of the jaxprs its equations
    hold."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dots(sub)


@functools.lru_cache(maxsize=None)
def solve_products(chunk: int, key_dim: int, value_dim: int,
                   backward: bool = True) -> int:
    """The [C, C] x [C, C] products at `Precision.HIGHEST` in one chunk's
    body AS TRACED, the backward kernel's or the forward one's: the
    solve's and its transpose's where neither head size is C.  Counted
    from `jax.make_jaxpr` of what the kernel calls."""
    square = (chunk, chunk)
    keys, values = (chunk, key_dim), (chunk, value_dim)
    state = (value_dim, key_dim)
    wide = jnp.bfloat16
    args = [(keys, wide), (keys, wide), (values, wide), (keys, _F32),
            ((chunk, 1), _F32), (state, _F32)]
    if backward:
        args += [(values, _F32), (state, _F32)]
    jaxpr = jax.make_jaxpr(_chunk_transposed if backward else _chunk_kernel)(
        *(jax.ShapeDtypeStruct(*a) for a in args)).jaxpr
    highest = (lax.Precision.HIGHEST,) * 2
    return sum(eqn.params["precision"] == highest
               and all(v.aval.shape == square for v in eqn.invars)
               for eqn in _dots(jaxpr))


def heads_per_program(heads: int, key_dim: int, value_dim: int,
                      backward: bool = False) -> int:
    """Heads of one chunk a program of the forward kernel holds, or of the
    backward one: the most of 4, 2, 1 (backward 2, 1: its text is two and
    a half times the forward's, and a run's set-up traces and lowers
    every head's) that divides the heads, where a head's columns are
    whole tiles of 128 lanes (a head is then a constant slice of the slab
    at a tile's edge); else 1."""
    if key_dim % 128 or value_dim % 128:
        return 1
    return next(hp for hp in ((2, 1) if backward else (4, 2, 1))
                if heads % hp == 0)


def _called(eqn):
    """The jaxpr an equation calls and nothing else (`jnp`'s own `jit`s,
    `_solve` and `_down` where nothing differentiates them), or None."""
    if eqn.primitive.name in ("jit", "custom_vjp_call"):
        return eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
    return None


def _in_step(jaxpr, consts, heads):
    """`jaxpr` for every head's arguments, AN EQUATION AT A TIME: equation
    i of every head stands before equation i + 1 of any, calls inlined.
    Mosaic's scheduler keeps close to the order of the text, so this is
    what lets one head's products run under another's vector passes
    (module docstring).  -> every head's results."""
    envs = [dict(zip((*jaxpr.constvars, *jaxpr.invars), (*consts, *args)))
            for args in heads]

    def read(env, v):
        return env[v] if isinstance(v, jex_core.Var) else v.val
    for eqn in jaxpr.eqns:
        vals = [[read(env, v) for v in eqn.invars] for env in envs]
        inner = _called(eqn)
        if inner is not None:
            outs = _in_step(inner.jaxpr, inner.consts, vals)
        else:
            outs = [eqn.primitive.bind(*x, **eqn.params) for x in vals]
            if not eqn.primitive.multiple_results:
                outs = [[o] for o in outs]
        for env, o in zip(envs, outs):
            env.update(zip(eqn.outvars, o))
    return [[read(env, v) for v in jaxpr.outvars] for env in envs]


def _heads_in_step(chunk_fn, heads):
    """`chunk_fn` (`_chunk_kernel`, `_chunk_transposed`) of every head's
    arguments, traced ONCE and its equations laid out head by head
    (`_in_step`); with one head, the function's own text."""
    closed = jax.make_jaxpr(chunk_fn)(*(
        jax.ShapeDtypeStruct(x.shape, x.dtype) for x in heads[0]))
    return _in_step(closed.jaxpr, closed.consts, heads)


def _head_slices(ref, hp):
    """A program's slab [C, hp w] as its heads' [C, w], constant slices of
    what is loaded once."""
    slab = ref[0, 0]
    w = slab.shape[1] // hp
    return [slab[:, h * w:(h + 1) * w] for h in range(hp)]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, st_ref,
                state_scr, *, hp):
    """One chunk of a program's `hp` heads, their results stored side by
    side."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_scr[:] = jnp.zeros_like(state_scr)

    st_ref[0, :, 0] = state_scr[:]                  # the chunk's start
    heads = zip(*(_head_slices(r, hp) for r in (q_ref, k_ref, v_ref, g_ref)),
                (_column(beta_ref[0, h, pl.ds(c, 1), :]) for h in range(hp)),
                (state_scr[h] for h in range(hp)))
    os, states = zip(*_heads_in_step(_chunk_kernel, list(heads)))
    for h in range(hp):
        state_scr[h] = states[h]
    o_ref[0, 0] = ssd._columns([o.astype(o_ref.dtype) for o in os])


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, st_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate_scr, *,
                hp):
    """One chunk of a program's `hp` heads, the chunks walked last to
    first: a head's chunk made again from the state that entered it, and
    its transpose applied to `do` and the gradient of the state it left."""
    c = pl.program_id(2)
    at = pl.num_programs(2) - 1 - c

    @pl.when(c == 0)
    def _init():
        dstate_scr[:] = jnp.zeros_like(dstate_scr)

    heads = zip(*(_head_slices(r, hp) for r in (q_ref, k_ref, v_ref, g_ref)),
                (_column(beta_ref[0, h, pl.ds(at, 1), :]) for h in range(hp)),
                (st_ref[0, h, 0] for h in range(hp)),
                (do.astype(_F32) for do in _head_slices(do_ref, hp)),
                (dstate_scr[h] for h in range(hp)))
    dq, dk, dv, dg, dbeta, dstates = zip(
        *_heads_in_step(_chunk_transposed, list(heads)))
    for h in range(hp):
        dstate_scr[h] = dstates[h]
        dbeta_ref[0, h, pl.ds(at, 1), :] = _as_row(dbeta[h])
    for ref, parts in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv),
                       (dg_ref, dg)):
        ref[0, 0] = ssd._columns([t.astype(ref.dtype) for t in parts])


def _specs(B, S, H, K, V, chunk, hp, reverse):
    """The blocks of a program of `hp` heads: the wide operands a slab of
    `hp` heads' columns of the mixer's layout, whole 128-lane tiles."""
    nc = S // chunk

    def at(c):
        return nc - 1 - c if reverse else c
    keys = pl.BlockSpec((1, 1, chunk, hp * K),
                        lambda b, h, c: (b, at(c), 0, h))
    values = pl.BlockSpec((1, 1, chunk, hp * V),
                          lambda b, h, c: (b, at(c), 0, h))
    # beta and its gradient: the heads' whole [S/C, C], where they are
    # while the heads' chunks are walked
    betas = pl.BlockSpec((1, hp, nc, chunk), lambda b, h, c: (b, h, 0, 0))
    states = pl.BlockSpec((1, hp, 1, V, K),
                          lambda b, h, c: (b, h, at(c), 0, 0))
    return keys, values, betas, states


def _params(nbytes: int):
    """`short_conv._params`' way: what the blocks hold, and a margin for
    the body's own values."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=nbytes + _VMEM_MARGIN)


def _chunked(t, chunk):
    """[B, S, wide] -> [B, S/C, C, wide], the form the kernels take the
    wide operands in: no element moves (and four dimensions, not three,
    for `ssd._chunked`'s reason: a Mosaic call that returns one [B, S,
    wide] array and a float32 one reads as a flash-attention forward call
    to `benchmark/reduce/flash_cost.py classify`)."""
    B, S, wide = t.shape
    return t.reshape(B, S // chunk, chunk, wide)


def _head_major(beta, chunk):
    """[B, S, H] -> [B, H, S/C, C]: the one operand laid out anew, 4 bytes
    a head and position."""
    B, S, H = beta.shape
    return beta.transpose(0, 2, 1).reshape(B, H, S // chunk, chunk)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _fwd_call(q, k, v, g, beta, chunk, interpret):
    """The forward kernel's call -> `(o, the chunks' entering states)`.
    Under `jax.jit`, as `ssd._fwd_call` is: traced and lowered once a
    process and shape, not once a layer."""
    B, S, wide = q.shape
    H = beta.shape[-1]
    K, V = wide // H, v.shape[-1] // H
    hp = heads_per_program(H, K, V)
    keys, values, betas, states = _specs(B, S, H, K, V, chunk, hp, False)
    item = q.dtype.itemsize
    q, k, v, g = (_chunked(t, chunk) for t in (q, k, v, g))
    o, states = pl.pallas_call(
        functools.partial(_fwd_kernel, hp=hp),
        grid=(B, H // hp, S // chunk),
        in_specs=[keys, keys, values, keys, betas],
        out_specs=[values, states],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, H, S // chunk, V, K), _F32)],
        scratch_shapes=[pltpu.VMEM((hp, V, K), _F32)],
        compiler_params=_params(hp * (
            2 * chunk * (item * (2 * K + 2 * V) + 4 * K) + 4 * S * 2
            + 3 * 4 * V * K)),
        interpret=interpret, name=FWD_NAME.format(chunk),
    )(q, k, v, g, _head_major(beta, chunk))
    return o.reshape(B, S, H * V), states


@functools.partial(jax.jit, static_argnums=(7, 8))
def _bwd_call(q, k, v, g, beta, states, do, chunk, interpret):
    """The backward kernel's call -> the gradients of q, k, v, g, beta."""
    B, S, wide = q.shape
    H = beta.shape[-1]
    K, V = wide // H, v.shape[-1] // H
    hp = heads_per_program(H, K, V, backward=True)
    keys, values, betas, st = _specs(B, S, H, K, V, chunk, hp, True)
    item = q.dtype.itemsize
    q, k, v, g, do = (_chunked(t, chunk) for t in (q, k, v, g, do))
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, hp=hp),
        grid=(B, H // hp, S // chunk),
        in_specs=[keys, keys, values, keys, betas, st, values],
        out_specs=[keys, keys, values, keys, betas],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct((B, H, S // chunk, chunk), _F32)],
        scratch_shapes=[pltpu.VMEM((hp, V, K), _F32)],
        compiler_params=_params(hp * (
            2 * chunk * (item * (4 * K + 3 * V) + 8 * K) + 4 * S * 4
            + 3 * 4 * V * K)),
        interpret=interpret, name=BWD_NAME.format(chunk),
    )(q, k, v, g, _head_major(beta, chunk), states, do)
    return (dq.reshape(B, S, wide), dk.reshape(B, S, wide),
            dv.reshape(B, S, H * V), dg.reshape(B, S, wide),
            dbeta.reshape(B, H, S).transpose(0, 2, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _core(q, k, v, g, beta, chunk, interpret):
    return _fwd_call(q, k, v, g, beta, chunk, interpret)[0]


def _core_fwd(q, k, v, g, beta, chunk, interpret):
    o, states = _fwd_call(q, k, v, g, beta, chunk, interpret)
    return o, (q, k, v, g, beta, states)


def _core_bwd(chunk, interpret, residuals, do):
    return _bwd_call(*residuals, do, chunk, interpret)


_core.defvjp(_core_fwd, _core_bwd)


def _check(q, k, v, g, beta, chunk):
    B, S, wide = q.shape
    H = beta.shape[-1]
    if (k.shape != q.shape or g.shape != q.shape or v.shape[:2] != (B, S)
            or beta.shape[:2] != (B, S) or wide % H or v.shape[-1] % H):
        raise ValueError(f"kda_scan: q {q.shape}, k {k.shape}, v {v.shape}, "
                         f"g {g.shape} and beta {beta.shape} do not fit")
    if chunk < BLOCK or chunk & (chunk - 1) or S % chunk:
        raise ValueError(f"kda_scan: a chunk is a power of two from {BLOCK} "
                         f"on that divides the sequence ({chunk}, {S})")


def kda_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, chunk: int = CHUNK,
             interpret: Optional[bool] = None) -> jax.Array:
    """The recurrence of the module's docstring, chunked, by the kernels.

    q, k [batch, S, H K] and v [batch, S, H V] as the mixer has them (a
    head's columns side by side), q and k NOT yet normed; g [batch, S,
    H K] float32, a log-decay a key channel, <= 0; beta [batch, S, H]
    float32.  Returns o [batch, S, H V] in v's dtype; differentiable in
    all five, g's and beta's gradients float32.  `chunk` divides S."""
    _check(q, k, v, g, beta, chunk)
    return _core(q, k, v, g.astype(_F32), beta.astype(_F32), chunk,
                 ssd._use_interpret(interpret))


def record(layers: int, batch: int, heads: int, seq_len: int, key_dim: int,
           value_dim: int, chunk: int = CHUNK) -> None:
    """The gauges of a traced step that runs this scan's kernels."""
    telemetry.record_static(
        "kda_scan", layers=layers, chunk=chunk, kernel=1,
        state_bytes=state_bytes(batch, heads, seq_len, key_dim, value_dim,
                                chunk),
        bwd_solve_products=solve_products(chunk, key_dim, value_dim))
    for call, backward in (("fwd", False), ("bwd", True)):
        telemetry.record_static(
            "kda_scan", labels={"call": call},
            heads_per_program=heads_per_program(heads, key_dim, value_dim,
                                                backward))
