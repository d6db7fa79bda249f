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
    across them.  A program holds `HEAD_BLOCK` heads of one chunk: x as
    [Q, P] a head (P = 64 is the whole minor dimension of its array, which
    is what the chip's tiling asks of a block narrower than 128 lanes),
    B and C as [Q, N].  The backward kernel's math is written out in
    `_bwd_kernel`.
  - `impl="jnp"`: `lax.scan` over the chunks, all heads at once, the
    backward pass a reverse scan that takes `jax.vjp` of one chunk.  What
    the kernels are tested against, and what runs where they cannot.

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

NEG_INF = float("-inf")
# Heads a kernel program holds: the per-head rows (dt, the cumulative sum)
# are blocks [HEAD_BLOCK, Q] of float32 arrays [B, H, S], and the chip
# takes a block's second-to-last dimension in multiples of 8.
HEAD_BLOCK = 8
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


def _fwd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, y_ref, st_ref,
                state_scr, *, heads, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_scr[:] = jnp.zeros_like(state_scr)

    dtype = x_ref.dtype
    bm = b_ref[0, 0]                                     # [Q, N]
    cm = c_ref[0, 0]
    g = _nt(cm, bm)                                      # [Q, Q], C_i . B_j

    def head(h, carry):
        x = x_ref[0, h]                                  # [Q, P]
        state = state_scr[h]                             # [P, N] float32
        dt_r, cs_r, dt_c, cs_c, last = _head_rows(dt_ref, cs_ref, h, chunk)
        mt = g * _decay(cs_c, cs_r, chunk) * dt_r
        y = _nn(mt.astype(dtype), x)
        y = y + jnp.exp(cs_c) * _nt(cm, state.astype(dtype))
        y_ref[0, h] = y.astype(y_ref.dtype)
        st_ref[0, h, 0] = state
        w = (x.astype(_F32) * (jnp.exp(last - cs_c) * dt_c)).astype(dtype)
        state_scr[h] = jnp.exp(last) * state + _tn(w, bm)
        return carry

    lax.fori_loop(0, heads, head, 0)


def _bwd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, dy_ref, st_ref,
                dx_ref, ddt_ref, dcs_ref, dcs_cols_ref, db_ref, dc_ref,
                dstate_scr, dg_scr, *, heads, chunk):
    """One chunk of a block of heads, the chunks walked last to first.
    With u_j = dt_j x_j, M_ij = exp(cs_i - cs_j) (C_i . B_j) for j <= i,
    e_i = exp(cs_i), f_j = exp(cs_Q - cs_j), S the state at the chunk's
    start and dS the gradient of the state it leaves:

        du   = M^T dy + f (B dS^T)                  dx = dt du
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

    dtype = x_ref.dtype
    bm = b_ref[0, 0]
    cm = c_ref[0, 0]
    g = _nt(cm, bm)
    dg_scr[:] = jnp.zeros_like(dg_scr)
    db_ref[0, 0] = jnp.zeros(db_ref.shape[2:], _F32)
    dc_ref[0, 0] = jnp.zeros(dc_ref.shape[2:], _F32)
    sublanes = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)

    def head(h, carry):
        x = x_ref[0, h]
        dy = dy_ref[0, h]
        state = st_ref[0, h, 0]                          # [P, N] float32
        dstate = dstate_scr[h]
        dt_r, cs_r, dt_c, cs_c, last = _head_rows(dt_ref, cs_ref, h, chunk)
        decay = _decay(cs_c, cs_r, chunk)
        m = g * decay
        e_c = jnp.exp(cs_c)
        f_c = jnp.exp(last - cs_c)
        x32, dy32 = x.astype(_F32), dy.astype(_F32)
        carried = e_c * _nt(cm, state.astype(dtype))              # [Q, P]
        from_state = f_c * _nt(bm, dstate.astype(dtype))          # [Q, P]
        du = _tn(m.astype(dtype), dy) + from_state
        dx_ref[0, h] = (dt_c * du).astype(dx_ref.dtype)
        ddt_ref[0, h, :] = jnp.sum(du * x32, axis=1, keepdims=True)[:, 0]
        dg = decay * dt_r * _nt(dy, x)                            # [Q, Q]
        dg_scr[:] += dg
        w = dg * g
        handed_on = dt_c * jnp.sum(from_state * x32, axis=1, keepdims=True)
        at_end = (jnp.sum(handed_on, keepdims=True)
                  + jnp.exp(last) * jnp.sum(dstate * state, keepdims=True))
        dcs = (jnp.sum(w, axis=1, keepdims=True)
               + jnp.sum(dy32 * carried, axis=1, keepdims=True) - handed_on
               + jnp.where(sublanes == chunk - 1, at_end, 0.0))
        dcs_ref[0, h, :] = dcs[:, 0]
        dcs_cols_ref[0, pl.ds(h, 1), :] = jnp.sum(w, axis=0, keepdims=True)
        e_dy = (e_c * dy32).astype(dtype)
        dc_ref[0, 0] += _nn(e_dy, state.astype(dtype))
        db_ref[0, 0] += _nn((f_c * dt_c * x32).astype(dtype),
                            dstate.astype(dtype))
        dstate_scr[h] = jnp.exp(last) * dstate + _tn(e_dy, cm)
        return carry

    lax.fori_loop(0, heads, head, 0)
    dg = dg_scr[:].astype(dtype)
    dc_ref[0, 0] += _nn(dg, bm)
    db_ref[0, 0] += _tn(dg, cm)


def _chunk_cumsum(a, chunk):
    B, H, S = a.shape
    return _cumsum(a.reshape(B, H, S // chunk, chunk)).reshape(a.shape)


def _specs(x, bm, chunk, hb, reverse):
    """Block specs of the arrays both kernels take; with `reverse` the
    chunk axis of the grid counts from the sequence's end."""
    B, H, S, P = x.shape
    G, N = bm.shape[1], bm.shape[3]
    nc = S // chunk
    per_group = H // G

    def at(c):
        return nc - 1 - c if reverse else c
    heads = pl.BlockSpec((1, hb, chunk, P), lambda b, h, c: (b, h, at(c), 0))
    rows = pl.BlockSpec((1, hb, chunk), lambda b, h, c: (b, h, at(c)))
    group = pl.BlockSpec(
        (1, 1, chunk, N), lambda b, h, c: (b, h * hb // per_group, at(c), 0))
    states = pl.BlockSpec((1, hb, 1, P, N),
                          lambda b, h, c: (b, h, at(c), 0, 0))
    partial = pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, at(c), 0))
    return heads, rows, group, states, partial


def _head_block(H: int, G: int) -> int:
    hb = min(HEAD_BLOCK, H // G)
    if (H // G) % hb:
        raise ValueError(f"ssd kernels: {H // G} heads a group cannot be "
                         f"walked in blocks of {hb}")
    return hb


_SEQUENTIAL_CHUNKS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd_kernels(x, dt, a, bm, cm, chunk, interpret):
    B, H, S, P = x.shape
    G, N = bm.shape[1], bm.shape[3]
    hb = _head_block(H, G)
    heads, rows, group, states, _ = _specs(x, bm, chunk, hb, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=hb, chunk=chunk),
        grid=(B, H // hb, S // chunk),
        in_specs=[heads, rows, rows, group, group],
        out_specs=[heads, states],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((B, H, S // chunk, P, N), _F32)],
        scratch_shapes=[pltpu.VMEM((hb, P, N), _F32)],
        compiler_params=_SEQUENTIAL_CHUNKS, interpret=interpret,
        name=f"ssd_fwd_c{chunk}",
    )(x, dt, _chunk_cumsum(a, chunk), bm, cm)


def _bwd_kernels(x, dt, a, bm, cm, states, dy, chunk, interpret):
    B, H, S, P = x.shape
    G, N = bm.shape[1], bm.shape[3]
    hb = _head_block(H, G)
    heads, rows, group, st, partial = _specs(x, bm, chunk, hb, True)
    dx, ddt, dcs, dcs_cols, dbp, dcp = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=hb, chunk=chunk),
        grid=(B, H // hb, S // chunk),
        in_specs=[heads, rows, rows, group, group, heads, st],
        out_specs=[heads, rows, rows, rows, partial, partial],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct((B, H // hb, S, N), _F32),
                   jax.ShapeDtypeStruct((B, H // hb, S, N), _F32)],
        scratch_shapes=[pltpu.VMEM((hb, P, N), _F32),
                        pltpu.VMEM((chunk, chunk), _F32)],
        compiler_params=_SEQUENTIAL_CHUNKS, interpret=interpret,
        name=f"ssd_bwd_c{chunk}",
    )(x, dt, _chunk_cumsum(a, chunk), bm, cm, dy, states)
    # cs_i = sum_{k <= i} a_k inside a chunk: a_k's gradient is the sum of
    # dcs_i over the chunk's i >= k.
    tail = jnp.flip(_chunk_cumsum(jnp.flip(dcs - dcs_cols, -1), chunk), -1)

    def groups(t):                     # blocks of heads -> their groups
        return t.reshape(B, G, H // hb // G, S, N).sum(2)
    return dx, ddt, tail, groups(dbp), groups(dcp)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
def _use_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _core(x, dt, a, bm, cm, chunk, impl, interpret):
    return _core_fwd(x, dt, a, bm, cm, chunk, impl, interpret)[0]


def _core_fwd(x, dt, a, bm, cm, chunk, impl, interpret):
    if impl == "kernel":
        y, states = _fwd_kernels(x, dt, a, bm, cm, chunk,
                                 _use_interpret(interpret))
    else:
        y, states = _fwd_jnp(x, dt, a, bm, cm, chunk)
    return y, (x, dt, a, bm, cm, states)


def _core_bwd(chunk, impl, interpret, residuals, dy):
    x, dt, a, bm, cm, states = residuals
    if impl == "kernel":
        grads = _bwd_kernels(*residuals, dy, chunk, _use_interpret(interpret))
    else:
        grads = _bwd_jnp(*residuals, dy, chunk)
    return tuple(g.astype(t.dtype) for g, t in zip(grads, (x, dt, a, bm, cm)))


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
    dt = dt.astype(_F32)
    a = dt * A.astype(_F32)
    heads_first = (0, 2, 1, 3)
    y = _core(x.transpose(heads_first), dt.transpose(0, 2, 1),
              a.transpose(0, 2, 1), B.astype(x.dtype).transpose(heads_first),
              C.astype(x.dtype).transpose(heads_first), chunk, impl,
              interpret)
    y = y.transpose(heads_first).astype(_F32)
    return (y + D.astype(_F32)[:, None] * x.astype(_F32)).astype(x.dtype)
