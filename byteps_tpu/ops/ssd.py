"""A chunked state-space scan (Mamba-2's state-space dual form, SSD) with
a hand-written backward pass, and the causal depthwise convolution that
goes before it in a Mamba-2 mixer.

The recurrence, per head (x_t a vector of the head's size P, B_t and C_t
of the state's size N, shared by the heads of a group; dt_t > 0, A < 0):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S_0 = 0, S [P, N]
    y_t = S_t C_t + D x_t

is computed a chunk of Q positions at a time.  With `a_t = dt_t A` and
`cs` its cumulative sum INSIDE a chunk (float32), a chunk's result is

    y_i = sum_{j <= i} exp(cs_i - cs_j) (C_i . B_j) dt_j x_j    inside
        + exp(cs_i) S_prev C_i                                  carried
    S   = exp(cs_Q) S_prev + sum_j exp(cs_Q - cs_j) dt_j x_j (x) B_j

where `S_prev` is the state the chunk before left.  The chunks are walked
in order, the state carried in float32; the only quadratic object is one
head's [Q, Q] decay matrix of one chunk, which never leaves fast memory
(the kernels) or exists for one chunk of all heads at a time (the `jnp`
form): never for a whole sequence.  Every exponent is a difference of one
float32 cumulative sum and is masked BEFORE the `exp`; with A < 0 none is
positive, so nothing overflows however fast a head decays.

Backward.  `ssd_scan` is a `custom_vjp`: the forward pass keeps, beside
its inputs, the state at the START of every chunk ([B, H, S/Q, P, N]
float32, `state_bytes`), and the backward pass walks the chunks in
reverse, recomputing a chunk's decay matrix and carrying the gradient of
the state.  Two forms of the same algorithm:

  - `impl="kernel"`: two Pallas TPU kernels, `ssd_fwd_c<Q>` and
    `ssd_bwd_c<Q>` (the names are how a device trace tells them from the
    flash-attention calls), grid (batch, blocks of heads, chunks) with
    the chunks innermost and the state, or its gradient, in VMEM scratch
    across them.  They read the MIXER'S OWN layout: x, y and their
    gradients as [B, S, H P] (what the convolution wrote and the gated
    norm reads; given to the kernels chunk by chunk, [B, S/Q, Q, H P],
    a reshape that moves nothing), a program's `HEAD_BLOCK` heads of one
    chunk a slab [Q, 8 P] of whole 128-lane tiles (512 lanes at P = 64);
    B and C, and the blocks' partial gradients of them, as [B, S, G N],
    a group's [Q, N] columns (where the chip cannot tile such blocks and
    the heads are few, the tests' small shapes, ONE program holds every
    head of every group and each array's whole width: `_plan`).  A head's
    [Q, P] is a constant slice of what a program loads of its slab, and
    the heads' results are stored
    side by side (`_walk_heads`): the forward kernel loads the slab once
    and has its 8 heads one after another in its own text, no loop, so
    one head's products overlap the next one's vector passes (a call
    1.19 -> 0.89 ms at granite's shapes, 3.68 -> 2.13 at nemotron_h's);
    the backward kernel, whose body is four times the forward's, walks
    the slab a tile of 128 lanes, two heads, a step of a loop (written
    out whole it is 9% faster and takes Mosaic 10 s a kernel to compile
    at chunks of 256, and a granite run's warm set-up 5 s of 55).
    What a run's set-up pays for that text is held down twice, both
    `jax.jit`s that change no operation (a kernel's Mosaic module is the
    same byte for byte with and without them): a head's own arithmetic,
    `_fwd_head` / `_bwd_head`, is traced once and not once a head, and a
    kernel's call, `_fwd_call` / `_bwd_call`, once a process and shape
    and not once a layer.  The chip's host traces four to six times
    slower than it runs anything else: with neither, a nemotron_h run's
    warm set-up read 163 s against the parent's 136; with the first
    alone 153-155, every one of the 37 kernel calls of its four programs
    still 0.4-0.6 s of tracing (PERF.md, Findings, PR 48).  In float32,
    which only tests and the reference checks run, the forward kernel
    walks pairs too (`_written_out`).  The cumulative sums are taken
    outside those calls, through `_cumsum`, which the benchmark's broken
    variants replace.  The `D x`
    term, and in the backward kernel `D dy` and dD, are taken inside, on
    operands already in fast memory.  Only the small float32
    rows (dt, the cumulative sum and their gradients, [B, H, S]) are
    head-major, and the chunk states.  So a step holds NO transposed copy
    of a wide operand and no pass over one outside the kernels.  It did
    until PR 48: the kernels took x as [B, H, S, P], where a head's
    [Q, 64] block is its array's whole minor dimension; on the chip's
    tiled layout that dimension is padded to 128 lanes, so the six
    transposed copies a layer (x in and y back with `D x`, forward and
    again under remat; dy in and dx back) wrote, and the kernels read
    and wrote, twice the bytes of x: 30.5 of the scan's 89.5 ms a step at
    granite-4.0-h-micro's shapes, 26.4 of 86.5 at nemotron_h's (PERF.md,
    Findings, PR 48).  The backward kernel's math is written out in
    `_bwd_kernel`.
  - `impl="jnp"`: `lax.scan` over the chunks, all heads at once, the
    backward pass a reverse scan that takes `jax.vjp` of one chunk, in a
    chunked head-major layout of its own, into which it moves its
    operands and out of which its results (`_split`, `_merge`).  What the
    kernels are tested against, and what runs where they cannot.

`interpret=None` runs the kernels in the Pallas interpreter off the TPU,
as `ops/flash_attention.py` does.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import telemetry

NEG_INF = float("-inf")
# Heads a kernel program holds: the per-head rows (dt, the cumulative sum)
# are blocks [HEAD_BLOCK, Q] of float32 arrays [B, H, S], and the chip
# takes a block's second-to-last dimension in multiples of 8; their
# columns of x are a slab [Q, HEAD_BLOCK P], whole tiles of 128 lanes from
# P = 16 on.
HEAD_BLOCK = 8
# Heads ONE program walks where it has to hold them all (`_plan`): each
# stands in the program's text.
WHOLE_HEADS = 16
# Transposed copies of a wide operand the `jnp` form makes a call, both
# passes: x and dy in (`_split`), y and dx out (`_merge`).
JNP_WIDE_COPIES = 4
_F32 = jnp.float32


def state_bytes(batch: int, heads: int, seq_len: int, head_dim: int,
                state: int, chunk: int) -> int:
    """Bytes of chunk states one call keeps for its backward pass."""
    return batch * heads * (seq_len // chunk) * head_dim * state * 4


def causal_conv1d(x: jax.Array, weight: jax.Array,
                  bias: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal convolution along the sequence.  x [B, S, C];
    weight [K, C], `weight[k]` multiplying x_{t-(K-1)+k} (so `weight[K-1]`
    meets the current position; K-1 zeros stand to the left); bias [C].
    K shifted multiply-adds, float32 accumulation, result in x's dtype."""
    K = weight.shape[0]
    S = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = None
    for k in range(K):
        term = (lax.slice_in_dim(padded, k, k + S, axis=1).astype(_F32)
                * weight[k].astype(_F32))
        out = term if out is None else out + term
    if bias is not None:
        out = out + bias.astype(_F32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# The jnp form: one chunk of all heads, and the scan over chunks
# ---------------------------------------------------------------------------
def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=_F32)


def _cumsum(a):
    """The cumulative sum inside a chunk, float32."""
    return jnp.cumsum(a, axis=-1)


def _chunk(x, dt, a, bm, cm, state):
    """One chunk.  x [B, G, R, Q, P] (R heads a group); dt, a [B, G, R, Q]
    float32; bm, cm [B, G, Q, N]; state [B, G, R, P, N] float32 ->
    `(y [B, G, R, Q, P] float32, the state the chunk leaves)`."""
    dtype = x.dtype
    Q = x.shape[3]
    cs = _cumsum(a)
    last = cs[..., -1:]
    keep = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    decay = jnp.exp(jnp.where(keep, cs[..., :, None] - cs[..., None, :],
                              NEG_INF))
    g = _dot("bgin,bgjn->bgij", cm, bm)
    mt = g[:, :, None] * decay * dt[..., None, :]
    y = _dot("bgrij,bgrjp->bgrip", mt.astype(dtype), x)
    carried = _dot("bgin,bgrpn->bgrip", cm, state.astype(dtype))
    y = y + jnp.exp(cs)[..., None] * carried
    w = (x.astype(_F32) * (jnp.exp(last - cs) * dt)[..., None]).astype(dtype)
    local = _dot("bgrjp,bgjn->bgrpn", w, bm)
    return y, jnp.exp(last)[..., None] * state + local


def _to_chunks(t, groups: int, chunk: int, per_head: bool = True):
    """[B, H or G, S, ...] -> [S/Q, B, G, (R,) Q, ...]: heads split by
    group where the array is per head, chunks in front for `lax.scan`."""
    shape = list(t.shape)
    lead = [groups, shape[1] // groups] if per_head else [shape[1]]
    t = t.reshape(shape[0], *lead, shape[2] // chunk, chunk, *shape[3:])
    return jnp.moveaxis(t, len(lead) + 1, 0)


def _from_chunks(t):
    """[S/Q, B, G, R, Q, ...] -> [B, H, S, ...]."""
    t = jnp.moveaxis(t, 0, 3)
    B, G, R, nc, Q = t.shape[:5]
    return t.reshape(B, G * R, nc * Q, *t.shape[5:])


def _chunked_inputs(x, dt, a, bm, cm, chunk):
    G = bm.shape[1]
    return (_to_chunks(x, G, chunk), _to_chunks(dt, G, chunk),
            _to_chunks(a, G, chunk), _to_chunks(bm, G, chunk, False),
            _to_chunks(cm, G, chunk, False))


def _fwd_jnp(x, dt, a, bm, cm, chunk):
    B, H, S, P = x.shape
    G, N = bm.shape[1], bm.shape[3]

    def body(state, inp):
        y, new = _chunk(*inp, state)
        return new, (y.astype(x.dtype), state)

    zero = jnp.zeros((B, G, H // G, P, N), _F32)
    _, (ys, states) = lax.scan(body, zero,
                               _chunked_inputs(x, dt, a, bm, cm, chunk))
    # states [S/Q, B, G, R, P, N] -> [B, H, S/Q, P, N]
    states = jnp.moveaxis(states, 0, 3).reshape(B, H, S // chunk, P, N)
    return _from_chunks(ys), states


def _bwd_jnp(x, dt, a, bm, cm, states, dy, chunk):
    B, H, S, P = x.shape
    G, N = bm.shape[1], bm.shape[3]
    states = jnp.moveaxis(
        states.reshape(B, G, H // G, S // chunk, P, N), 3, 0)

    def body(dstate, inp):
        *primal, state, dyc = inp
        _, pull = jax.vjp(_chunk, *primal, state)
        dx, ddt, da, dbm, dcm, dprev = pull((dyc.astype(_F32), dstate))
        return dprev, (dx, ddt, da, dbm, dcm)

    zero = jnp.zeros((B, G, H // G, P, N), _F32)
    _, (dx, ddt, da, dbm, dcm) = lax.scan(
        body, zero, (*_chunked_inputs(x, dt, a, bm, cm, chunk), states,
                     _to_chunks(dy, G, chunk)), reverse=True)

    def groups(t):                       # [S/Q, B, G, Q, N] -> [B, G, S, N]
        t = jnp.moveaxis(t, 0, 2)
        return t.reshape(B, G, S, N)
    return (_from_chunks(dx), _from_chunks(ddt), _from_chunks(da),
            groups(dbm), groups(dcm))


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------
def _nt(a, b):
    """a [m, k], b [n, k] -> [m, n]."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=_F32)


def _tn(a, b):
    """a [k, m], b [k, n] -> [m, n]."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=_F32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32)


def _head_rows(dt_ref, cs_ref, h, chunk):
    """A head's dt and cumulative sum as rows [1, Q] and columns [Q, 1],
    and the chunk's last cumulative sum [1, 1]."""
    dt_r = dt_ref[0, pl.ds(h, 1), :]
    cs_r = cs_ref[0, pl.ds(h, 1), :]
    dt_c = dt_ref[0, h, :][:, None]
    cs_c = cs_ref[0, h, :][:, None]
    lanes = lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    last = jnp.sum(jnp.where(lanes == chunk - 1, cs_r, 0.0), axis=1,
                   keepdims=True)
    return dt_r, cs_r, dt_c, cs_c, last


def _decay(cs_c, cs_r, chunk):
    """exp(cs_i - cs_j) for j <= i, 0 above the diagonal: masked before
    the exp, so no positive exponent is ever taken."""
    rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return jnp.exp(jnp.where(rows >= cols, cs_c - cs_r, NEG_INF))


def _walk_heads(heads: int, per: int, P: int, step, base: int = 0) -> None:
    """Walks `heads` heads of a program's slab, the columns from `base`
    on, `per` of them a step: `step(first head, their columns)`, a head's own
    [Q, P] a constant slice of what the step loads.  All of them in one
    step is no loop at all: the heads stand one after another in the
    program's text, and one head's products overlap the next one's vector
    passes.  Fewer is a loop of the program, whose steps' columns start
    where a count of `per P` lanes says, so `per P` has to be whole tiles
    of 128 lanes (`_tile_heads`)."""
    if per == heads:
        step(0, pl.ds(base, heads * P))
        return

    def body(j, carry):
        step(j * per, pl.ds(base + pl.multiple_of(j * per * P, per * P),
                            per * P))
        return carry
    lax.fori_loop(0, heads // per, body, 0)


def _tile_heads(heads: int, P: int) -> int:
    """The fewest heads whose columns are whole tiles of 128 lanes (2 at
    P = 64, 1 from 128 on), or all of them where none are."""
    per = min(heads, max(1, 128 // P))
    return per if heads % per == 0 and (per * P) % 128 == 0 else heads


def _columns(parts):
    """A step's heads side by side, as their slab has them."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _skip(d_ref, h):
    """A head's D along its P lanes, [1, P]."""
    return d_ref[pl.ds(h, 1), :]


@jax.jit
def _fwd_head(x, state, rows, g, bm, cm, skip):
    """One head of one chunk: x [Q, P], the state the chunk before left
    [P, N] float32 -> `(y [Q, P] float32 with D x, the state it leaves)`.
    Under `jax.jit` so that it is traced once and not once a head: a
    kernel's text is its heads one after another (module docstring)."""
    dt_r, cs_r, dt_c, cs_c, last = rows
    dtype = x.dtype
    mt = g * _decay(cs_c, cs_r, cs_r.shape[1]) * dt_r
    y = _nn(mt.astype(dtype), x)
    y = y + jnp.exp(cs_c) * _nt(cm, state.astype(dtype))
    w = (x.astype(_F32) * (jnp.exp(last - cs_c) * dt_c)).astype(dtype)
    return (y + skip * x.astype(_F32),
            jnp.exp(last) * state + _tn(w, bm))


@jax.jit
def _bwd_head(x, dy, state, dstate, rows, g, bm, cm, skip):
    """One head of one chunk of the backward pass (`_bwd_kernel` has the
    math): `(dx, dD a lane, ddt, dG's part, dcs, cols(W), dC's part, dB's
    part, the gradient of the state handed to the chunk before)`.  Under
    `jax.jit` as `_fwd_head` is."""
    dt_r, cs_r, dt_c, cs_c, last = rows
    dtype = x.dtype
    chunk = cs_r.shape[1]
    sublanes = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    decay = _decay(cs_c, cs_r, chunk)
    m = g * decay
    e_c = jnp.exp(cs_c)
    f_c = jnp.exp(last - cs_c)
    x32, dy32 = x.astype(_F32), dy.astype(_F32)
    carried = e_c * _nt(cm, state.astype(dtype))                  # [Q, P]
    from_state = f_c * _nt(bm, dstate.astype(dtype))              # [Q, P]
    du = _tn(m.astype(dtype), dy) + from_state
    dg = decay * dt_r * _nt(dy, x)                                # [Q, Q]
    w = dg * g
    handed_on = dt_c * jnp.sum(from_state * x32, axis=1, keepdims=True)
    at_end = (jnp.sum(handed_on, keepdims=True)
              + jnp.exp(last) * jnp.sum(dstate * state, keepdims=True))
    dcs = (jnp.sum(w, axis=1, keepdims=True)
           + jnp.sum(dy32 * carried, axis=1, keepdims=True) - handed_on
           + jnp.where(sublanes == chunk - 1, at_end, 0.0))
    e_dy = (e_c * dy32).astype(dtype)
    return (dt_c * du + skip * dy32,
            jnp.sum(dy32 * x32, axis=0, keepdims=True),
            jnp.sum(du * x32, axis=1, keepdims=True),
            dg, dcs, jnp.sum(w, axis=0, keepdims=True),
            _nn(e_dy, state.astype(dtype)),
            _nn((f_c * dt_c * x32).astype(dtype), dstate.astype(dtype)),
            jnp.exp(last) * dstate + _tn(e_dy, cm))


def _group_columns(ref, gi, groups):
    """Group `gi`'s columns of a block [.., Q, groups N]."""
    N = ref.shape[-1] // groups
    return pl.ds(gi * N, N)


def _fwd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, d_ref, y_ref, st_ref,
                state_scr, *, heads, per, groups, chunk):
    """A program's `groups` groups of `heads` heads each (one group, or
    all of them: `_plan`), one chunk."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_scr[:] = jnp.zeros_like(state_scr)

    dtype = x_ref.dtype
    P = x_ref.shape[3] // (groups * heads)
    for gi in range(groups):
        bm = b_ref[0, :, _group_columns(b_ref, gi, groups)]      # [Q, N]
        cm = c_ref[0, :, _group_columns(c_ref, gi, groups)]
        g = _nt(cm, bm)                                  # [Q, Q], C_i . B_j

        def step(first, cols, bm=bm, cm=cm, g=g, gi=gi):
            xs = x_ref[0, 0, :, cols]                    # [Q, per P]
            ys = []
            for i in range(per):
                h = gi * heads + first + i
                st_ref[0, h, 0] = state_scr[h]      # the chunk's start
                y, state_scr[h] = _fwd_head(
                    xs[:, i * P:(i + 1) * P], state_scr[h],
                    _head_rows(dt_ref, cs_ref, h, chunk), g, bm, cm,
                    _skip(d_ref, h))
                ys.append(y.astype(y_ref.dtype))
            y_ref[0, 0, :, cols] = _columns(ys)

        _walk_heads(heads, per, P, step, gi * heads * P)


def _bwd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, d_ref, dy_ref, st_ref,
                dx_ref, ddt_ref, dcs_ref, dcs_cols_ref, db_ref, dc_ref,
                dd_ref, dstate_scr, dg_scr, *, heads, per, groups, chunk):
    """One chunk of a block of heads, the chunks walked last to first.
    With u_j = dt_j x_j, M_ij = exp(cs_i - cs_j) (C_i . B_j) for j <= i,
    e_i = exp(cs_i), f_j = exp(cs_Q - cs_j), S the state at the chunk's
    start and dS the gradient of the state it leaves:

        du   = M^T dy + f (B dS^T)                  dx = dt du + D dy
        dD   = dy . x                               summed over the chunks
        ddt  = du . x                               (the direct part)
        W_ij = M_ij dt_j (dy_i . x_j)               float32, never rounded
        dcs  = rows(W) - cols(W)                    inside the chunk
             + dy . (e C S^T) - f u . (B dS^T)      carried and handed on
        dcs_Q += sum_j f_j u_j^T dS B_j + e_Q <dS, S>
        dG   = sum over heads of decay * dt_j * (dy x^T)
        dC   = dG B + sum over heads of (e dy) S
        dB   = dG^T C + sum over heads of (f u) dS
        dS'  = e_Q dS + (e dy)^T C                  handed to the chunk before

    `dcs` is the gradient of the cumulative sum; the caller sums it back
    into `a`'s.  The sum over the positions AFTER k of rows(W) - cols(W)
    is what is left of two nearly equal sums (all of W's lower right
    corner cancels), so both are taken from the one float32 W: taken from
    products whose operands had been rounded to bfloat16 apart (dy . y
    less du . u, the usual shortcut) they left A's and dt's gradients off
    by 3-7% at the published widths.  rows(W) leaves as `dcs`, with the
    carried terms; cols(W), which comes out as a row, apart, and the
    caller subtracts.  B's and C's gradients are summed over this block's
    heads here and over the blocks by the caller."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate_scr[:] = jnp.zeros_like(dstate_scr)
        dd_ref[:] = jnp.zeros_like(dd_ref)

    dtype = x_ref.dtype
    P = x_ref.shape[3] // (groups * heads)
    db_ref[0, 0] = jnp.zeros(db_ref.shape[2:], _F32)
    dc_ref[0, 0] = jnp.zeros(dc_ref.shape[2:], _F32)
    for gi in range(groups):
        mine = _group_columns(b_ref, gi, groups)
        bm = b_ref[0, :, mine]
        cm = c_ref[0, :, mine]
        g = _nt(cm, bm)
        dg_scr[:] = jnp.zeros_like(dg_scr)

        def step(first, cols, bm=bm, cm=cm, g=g, gi=gi, mine=mine):
            xs = x_ref[0, 0, :, cols]                    # [Q, per P]
            dys = dy_ref[0, 0, :, cols]
            dxs = []
            for i in range(per):
                h = gi * heads + first + i
                (dx, dd, ddt, dg, dcs, dcs_cols, dc, db,
                 dstate_scr[h]) = _bwd_head(
                    xs[:, i * P:(i + 1) * P], dys[:, i * P:(i + 1) * P],
                    st_ref[0, h, 0], dstate_scr[h],
                    _head_rows(dt_ref, cs_ref, h, chunk), g, bm, cm,
                    _skip(d_ref, h))
                dxs.append(dx.astype(dx_ref.dtype))
                dd_ref[0, pl.ds(h, 1), :] += dd
                ddt_ref[0, h, :] = ddt[:, 0]
                dg_scr[:] += dg
                dcs_ref[0, h, :] = dcs[:, 0]
                dcs_cols_ref[0, pl.ds(h, 1), :] = dcs_cols
                dc_ref[0, 0, :, mine] += dc
                db_ref[0, 0, :, mine] += db
            dx_ref[0, 0, :, cols] = _columns(dxs)

        _walk_heads(heads, per, P, step, gi * heads * P)
        dg = dg_scr[:].astype(dtype)
        dc_ref[0, 0, :, mine] += _nn(dg, bm)
        db_ref[0, 0, :, mine] += _tn(dg, cm)


def _chunk_cumsum(a, chunk):
    B, H, S = a.shape
    return _cumsum(a.reshape(B, H, S // chunk, chunk)).reshape(a.shape)


def _head_block(H: int, G: int) -> int:
    hb = min(HEAD_BLOCK, H // G)
    if (H // G) % hb:
        raise ValueError(f"ssd kernels: {H // G} heads a group cannot be "
                         f"walked in blocks of {hb}")
    return hb


def _plan(H: int, P: int, G: int, N: int):
    """`(heads, groups, why the chip refuses)` of one kernel program: a
    `_head_block` of ONE group's heads, their columns a slab of x, where
    the chip can tile that (it tiles a block's last dimension by 128 lanes
    and its second-to-last by 8 rows, or takes the array's whole: 8 heads
    of 64 are 512 lanes, a group's state of 128 its own block); else ALL
    the heads, every group, the whole width of every array one block,
    which the chip always takes, where they are few enough to stand in one
    program's text (`WHOLE_HEADS`: the small shapes of the tests).  What
    neither serves keeps the group's block and says why no chip takes it
    (`_served` raises that off the interpreter, which takes any block:
    `tests/test_ssd.py` holds the nemotron_h form, 64 heads in 8 groups,
    to the recurrence at a head of 8)."""
    hb = _head_block(H, G)
    unfit = [f"{what} make a block of {block}, neither a multiple of "
             f"{tile} nor the whole {whole}"
             for what, block, whole, tile in (
                 (f"{hb} heads of {P}", hb * P, H * P, 128),
                 (f"a group's state of {N}", N, G * N, 128),
                 (f"the rows of {hb} heads", hb, H, 8))
             if block % tile and block != whole]
    if not unfit:
        return hb, 1, None
    if H <= WHOLE_HEADS:
        return H // G, G, None
    return hb, 1, (f"ssd kernels: {unfit[0]}, and {H} heads are more than "
                   f"one program walks ({WHOLE_HEADS}): only the "
                   f"interpreter takes it")


def _served(dims, interpret: bool):
    """`_plan`'s `(heads, groups)` for `_dims`' shapes, or its refusal."""
    _, _, H, P, G, N = dims
    heads, groups, refused = _plan(H, P, G, N)
    if refused and not interpret:
        raise ValueError(refused)
    return heads, groups


def _lane_block(H: int, P: int, G: int, N: int) -> int:
    """Lanes of the slab of x one kernel program holds."""
    heads, groups, _ = _plan(H, P, G, N)
    return heads * groups * P


def _specs(dims, chunk, heads, groups, reverse):
    """Block specs of the arrays both kernels take, for programs of
    `groups` groups of `heads` heads each (`_plan`); with `reverse` the
    chunk axis of the grid counts from the sequence's end."""
    B, S, H, P, G, N = dims
    nc = S // chunk
    hp = heads * groups                    # heads a program
    blocks = H // G // heads               # of heads, a group

    def at(c):
        return nc - 1 - c if reverse else c
    slab = pl.BlockSpec((1, 1, chunk, hp * P),
                        lambda b, h, c: (b, at(c), 0, h))
    rows = pl.BlockSpec((1, hp, chunk), lambda b, h, c: (b, h, at(c)))
    group = pl.BlockSpec((1, chunk, groups * N),
                         lambda b, h, c: (b, at(c), h // blocks))
    states = pl.BlockSpec((1, hp, 1, P, N),
                          lambda b, h, c: (b, h, at(c), 0, 0))
    partial = pl.BlockSpec(
        (1, 1, chunk, groups * N),
        lambda b, h, c: (b, h % blocks, at(c), h // blocks))
    # D a head along its P lanes, and its gradient a lane, summed over a
    # sequence's chunks in a block that stays where it is while they are
    # walked
    skip = pl.BlockSpec((hp, P), lambda b, h, c: (h, 0))
    dskip = pl.BlockSpec((1, hp, P), lambda b, h, c: (b, h, 0))
    return slab, rows, group, states, partial, skip, dskip


_SEQUENTIAL_CHUNKS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _dims(x, dt, bm, groups):
    """(B, S, H, P, G, N) of x [B, S, H P], dt [B, H, S], bm [B, S, G N]."""
    B, S, wide = x.shape
    H = dt.shape[1]
    return B, S, H, wide // H, groups, bm.shape[2] // groups


def _chunked(t, chunk):
    """[B, S, wide] -> [B, S/Q, Q, wide], the form the kernels take x, y
    and their gradients in: no element moves.  (Four dimensions and not
    three: a Mosaic call that returns one [B, S, wide] array and a float32
    one is what the benchmark's readers take for a flash-attention forward
    call, `benchmark/reduce/flash_cost.py classify`.)"""
    B, S, wide = t.shape
    return t.reshape(B, S // chunk, chunk, wide)


def _lanes(d, P):
    """D [H] -> [H, P] float32, a head's number along its lanes."""
    return jnp.broadcast_to(d.astype(_F32)[:, None], (d.shape[0], P))


def _written_out(hb: int, P: int, dtype) -> int:
    """Heads a step of the FORWARD kernel's walk takes: all of a program's
    in bfloat16 (the module's docstring), a tile of 128 lanes in float32,
    where a product is six passes of the matrix unit and Mosaic takes
    2.3 s for the kernel written out against 0.5 (chunks of 128; a
    reference check's program, which no cache keeps, holds five)."""
    return hb if jnp.dtype(dtype).itemsize <= 2 else _tile_heads(hb, P)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _fwd_call(x, dt, cs, bm, cm, d, groups, chunk, interpret):
    """The forward kernel's call; `cs` the cumulative sums inside the
    chunks.  Under `jax.jit`, as `_bwd_call` is: a step calls each once a
    layer, and the kernel is traced and lowered once a process and shape
    (a nemotron_h run's set-up spent 0.6 s on every call's trace, 37
    calls in its four programs)."""
    B, S, H, P, G, N = dims = _dims(x, dt, bm, groups)
    hb, gb = _served(dims, interpret)
    slab, rows, group, states, _, skip, _ = _specs(dims, chunk, hb, gb,
                                                   False)
    x = _chunked(x, chunk)
    y, states = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=hb,
                          per=_written_out(hb, P, x.dtype), groups=gb,
                          chunk=chunk),
        grid=(B, H // (hb * gb), S // chunk),
        in_specs=[slab, rows, rows, group, group, skip],
        out_specs=[slab, states],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((B, H, S // chunk, P, N), _F32)],
        scratch_shapes=[pltpu.VMEM((hb * gb, P, N), _F32)],
        compiler_params=_SEQUENTIAL_CHUNKS, interpret=interpret,
        name=f"ssd_fwd_c{chunk}",
    )(x, dt, cs, bm, cm, _lanes(d, P))
    return y.reshape(B, S, H * P), states


def _fwd_kernels(x, dt, a, bm, cm, d, groups, chunk, interpret):
    return _fwd_call(x, dt, _chunk_cumsum(a, chunk), bm, cm, d, groups,
                     chunk, interpret)


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def _bwd_call(x, dt, cs, bm, cm, d, states, dy, groups, chunk, interpret):
    """The backward kernel's call -> `(dx, ddt, the cumulative sums'
    gradient, dB, dC, dD)`."""
    B, S, H, P, G, N = dims = _dims(x, dt, bm, groups)
    hb, gb = _served(dims, interpret)
    slab, rows, group, st, partial, skip, dskip = _specs(dims, chunk, hb, gb,
                                                         True)
    # B's and C's gradients, a block of heads apart: [B, blocks a group,
    # S, G N], a group's columns where B's and C's own are
    partials = jax.ShapeDtypeStruct((B, H // G // hb, S, G * N), _F32)
    x = _chunked(x, chunk)
    dx, ddt, dcs, dcs_cols, dbp, dcp, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=hb, per=_tile_heads(hb, P),
                          groups=gb, chunk=chunk),
        grid=(B, H // (hb * gb), S // chunk),
        in_specs=[slab, rows, rows, group, group, skip, slab, st],
        out_specs=[slab, rows, rows, rows, partial, partial, dskip],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   partials, partials,
                   jax.ShapeDtypeStruct((B, H, P), _F32)],
        scratch_shapes=[pltpu.VMEM((hb * gb, P, N), _F32),
                        pltpu.VMEM((chunk, chunk), _F32)],
        compiler_params=_SEQUENTIAL_CHUNKS, interpret=interpret,
        name=f"ssd_bwd_c{chunk}",
    )(x, dt, cs, bm, cm, _lanes(d, P), _chunked(dy, chunk), states)
    return (dx.reshape(B, S, H * P), ddt, dcs - dcs_cols, dbp.sum(1),
            dcp.sum(1), dd.sum((0, 2)))


def _bwd_kernels(x, dt, a, bm, cm, d, states, dy, groups, chunk, interpret):
    dx, ddt, dcs, dbm, dcm, dd = _bwd_call(
        x, dt, _chunk_cumsum(a, chunk), bm, cm, d, states, dy, groups, chunk,
        interpret)
    # cs_i = sum_{k <= i} a_k inside a chunk: a_k's gradient is the sum of
    # dcs_i over the chunk's i >= k.
    tail = jnp.flip(_chunk_cumsum(jnp.flip(dcs, -1), chunk), -1)
    return dx, ddt, tail, dbm, dcm, dd


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
def _use_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _split(t, parts: int):
    """[B, S, parts w] -> [B, parts, S, w]: the `jnp` form's own layout,
    a transposed copy."""
    B, S, wide = t.shape
    return t.reshape(B, S, parts, wide // parts).transpose(0, 2, 1, 3)


def _merge(t):
    """[B, parts, S, w] -> [B, S, parts w]."""
    B, parts, S, w = t.shape
    return t.transpose(0, 2, 1, 3).reshape(B, S, parts * w)


def _skipped(x, d):
    """D x over the width [.., H P], float32."""
    return jnp.repeat(d.astype(_F32), x.shape[-1] // d.shape[0]) * x.astype(
        _F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _core(x, dt, a, bm, cm, d, groups, chunk, impl, interpret):
    """x [B, S, H P]; dt, a [B, H, S] float32; bm, cm [B, S, G N]; d [H]:
    the wide operands as the mixer has them, and so what is kept for the
    backward pass -> y [B, S, H P], the `D x` term in it."""
    return _core_fwd(x, dt, a, bm, cm, d, groups, chunk, impl, interpret)[0]


def _core_fwd(x, dt, a, bm, cm, d, groups, chunk, impl, interpret):
    if impl == "kernel":
        y, states = _fwd_kernels(x, dt, a, bm, cm, d, groups, chunk,
                                 _use_interpret(interpret))
    else:
        y, states = _fwd_jnp(_split(x, dt.shape[1]), dt, a,
                             _split(bm, groups), _split(cm, groups), chunk)
        y = (_merge(y).astype(_F32) + _skipped(x, d)).astype(x.dtype)
    return y, (x, dt, a, bm, cm, d, states)


def _core_bwd(groups, chunk, impl, interpret, residuals, dy):
    x, dt, a, bm, cm, d, states = residuals
    if impl == "kernel":
        grads = _bwd_kernels(*residuals, dy, groups, chunk,
                             _use_interpret(interpret))
    else:
        H = dt.shape[1]
        dx, ddt, da, dbm, dcm = _bwd_jnp(
            _split(x, H), dt, a, _split(bm, groups), _split(cm, groups),
            states, _split(dy, H), chunk)
        dd = (dy.astype(_F32) * x.astype(_F32)).sum((0, 1))
        grads = (_merge(dx) + _skipped(dy, d), ddt, da, _merge(dbm),
                 _merge(dcm), dd.reshape(H, -1).sum(1))
    return tuple(g.astype(t.dtype)
                 for g, t in zip(grads, (x, dt, a, bm, cm, d)))


_core.defvjp(_core_fwd, _core_bwd)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, chunk: int = 256,
             impl: str = "kernel",
             interpret: Optional[bool] = None) -> jax.Array:
    """The state-space recurrence of the module's docstring, chunked.

    x [batch, S, H, P]; dt [batch, S, H], positive (after its softplus);
    A [H], negative; B, C [batch, S, G, N], G dividing H, a group's B and
    C serving H / G consecutive heads; D [H].  Returns y [batch, S, H, P]
    in x's dtype.  The products run in x's dtype with float32
    accumulation; dt, the decays, their cumulative sums and the carried
    state are float32 whatever x is.  `chunk` must divide S."""
    if impl not in ("kernel", "jnp"):
        raise ValueError(f"impl={impl!r}")
    S = x.shape[1]
    if chunk <= 0 or S % chunk:
        raise ValueError(f"ssd_scan: chunk {chunk} does not divide the "
                         f"sequence length {S}")
    if x.shape[2] % B.shape[2]:
        raise ValueError(f"ssd_scan: {x.shape[2]} heads in {B.shape[2]} "
                         f"groups")
    H, P = x.shape[2:]
    telemetry.record_static(
        "ssd_scan", lane_block=_lane_block(H, P, *B.shape[2:]),
        wide_copies=0 if impl == "kernel" else JNP_WIDE_COPIES)
    dt = dt.astype(_F32)
    a = dt * A.astype(_F32)
    # Over the inner width H P and the groups' G N, as the mixer has them:
    # the reshapes move nothing, and the kernels read these forms.
    wide = x.reshape(*x.shape[:2], H * P)
    groups = (*B.shape[:2], B.shape[2] * B.shape[3])
    y = _core(wide, dt.transpose(0, 2, 1), a.transpose(0, 2, 1),
              B.astype(x.dtype).reshape(groups),
              C.astype(x.dtype).reshape(groups), D.astype(_F32), B.shape[2],
              chunk, impl, interpret)
    return y.reshape(x.shape)
