"""Short causal convolutions as one Pallas TPU kernel each way: two
operators on one walk.  The doubly gated one (`gated_short_conv`), which
this docstring describes first:

    y_t = C_t * sum_{k < K} w_k * (B * X)_{t-(K-1)+k}        [B | C | X] = bcx

`bcx` [batch, S, 3 C] is a projection's result, its thirds side by side;
`w` [K, C] the taps of a depthwise causal convolution (`w[K-1]` meets the
current position, as `ops/ssd.py causal_conv1d`'s); zeros stand before a
SEQUENCE's first position, each of the batch's sequences its own.  No
bias, no activation.  This is the whole sequence mixer of the `lfm2`
decoder's convolution layers (`models/lfm2.py`), between its two
projections.

Why a kernel.  As jnp operations the operator is a product, K shifted
multiply-adds over a padded copy and another product, which the compiler
lowers to several passes over [S, C] in float32, and as many again for
each of four gradients; in the nemotron and granite cells a 4-tap
convolution alone runs at a sixth of the HBM roofline (PERF.md, section
7).  Here the forward pass reads [S, 3 C] once and writes [S, C] once,
and the backward pass reads [S, 3 C] and [S, C] and writes [S, 3 C]:
everything between lives in VMEM.

How.  The batch's sequences are laid end to end, [T, .] with T = batch x
S (a free reshape), and a grid step takes `block_rows` rows of the whole
width: one contiguous copy in, one out.  Inside, a ROLLED loop walks the
channels a few lane tiles at a time, so a step's float32 temporaries are
[rows, 512] whatever C is.  A shifted copy of a block is a rotation of its
rows (`pltpu.roll`, the XLU's) whose first K - 1 rows are replaced by the
HALO: the last rows of the 16 before the block, which the step is handed
as a second small block of the same array (backward also the first rows
of the 16 after it, for the taps that reach forward in time).  A tap that
would reach across a sequence's start (or, backward, its end) is masked
by the row's position in its sequence, which also covers the first
block's halo (there is none: the index is clamped and every row of it
masked).  Products and sums are float32, rounded once to the result's
dtype (to the nearest: on the chip the kernel's own cast read 1.66e-3
from float32, as rounding done by hand on the bits did, PERF.md, PR 55).

Backward from `bcx` and `dy` alone: u = B * X and z = conv(u) are made
again in the kernel,

    dC = dy * z        dz = dy * C        du_t = sum_k w_k dz_{t+(K-1)-k}
    dB = du * X        dX = du * B        dw_k = sum_t dz_t u_{t-(K-1)+k}

`dw` is summed in float32 over the row blocks in an [8, C] block that
stays in VMEM for the whole call (the grid is sequential), its first K
rows the taps.  Every result of both calls is 2-D: the benchmark's readers
tell a flash-attention call by its 3-D results
(`benchmark/reduce/flash_cost.py classify`) and must not take these for
one.

Any shape goes through the same two calls: rows that do not fill the last
block are masked where they are read (a padded block holds whatever), a
width that is no multiple of 128 is one chunk of the whole width (the
interpreter's tests; the model's is 2048).  Off the TPU the kernels run in
the Pallas interpreter, as `ops/grouped_matmul.py`'s do.  The calls are
under a plain `jax.jit`, so a process traces and lowers one body a shape
(`ops/moe_rows.py` says what that saves a run's set-up), and they carry
the names the device trace shows: `short_conv_fwd`, `short_conv_bwd`.

The second operator (`mamba_conv`, PR 56) is the Mamba-2 mixers'
(`models/granite_hybrid.py` `_conv`, and through it
`models/nemotron_h.py`'s):

    y_t = silu( bias + sum_{k < K} w_k x_{t-(K-1)+k} )    x = xBC [batch, S, C]

the same blocks, halo, rotation and sum of `dw` over the sequential grid
(the bias's gradient is one more row of that block), other arithmetic an
element: bias, taps, sums and the silu in float32, ONE rounding to the
result's dtype (the jnp form, `ops/ssd.py` `causal_conv1d` and a silu,
rounds the sum and then takes the silu).  Backward from x and dy alone: the
pre-activation a is made again, da = dy * silu'(a), dx_t = sum_k w_k
da_{t+(K-1)-k}, dw_k = sum_t da_t x_{t-(K-1)+k}, dbias = sum_t da_t.  What
the gated kernel did not have to do: the rows of da AFTER a block need a
there, a convolution over the joined edge (the block's last rows before
the next halo's first), not a product of two halos.  What it does
otherwise, because on the chip these kernels are bound by the vector unit
and its registers, not by memory (PERF.md, Findings, PR 56): a shifted
tile is a rotation with NO select a row where the rows of a block divide a
sequence (the tile's first or last 8 rows are then patched from a joined
16-row piece, and a scalar says whether the halo is another sequence's);
the sigmoid is one tanh; a grid step takes 128 rows and the inner loop 128
lanes.  The result can come as several arrays, stretches of its lanes
(`parts`: x, B and C as the scan takes them), whose cotangents the
backward call reads as they are: a caller's split costs no pass.  The
calls name themselves `mamba_conv_fwd` / `mamba_conv_bwd`: no reader of
the benchmark's takes them for the gated calls (`^short_conv_`), the
scan's or a flash call (every result is 2-D).
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
from . import flash_attention

_F32 = jnp.float32
# Rows a grid step takes.  256 rows of [., 3 x 2048] bfloat16 are 3 MB a
# copy: on the chip 256 and 512 took the same time to 2% and 128 6% more
# (PERF.md, Findings, PR 55).
BLOCK_ROWS = 256
# Rows of a halo block: a tile of a bfloat16 array as it lies in VMEM.
HALO = 16
# Lanes of the inner loop's chunk, at most.
CHUNK = 512
FWD_NAME, BWD_NAME = "short_conv_fwd", "short_conv_bwd"
_VMEM_MARGIN = 8 * 1024 * 1024


def _chunk(c: int) -> int:
    """Lanes of the inner loop's chunk for a width of `c`: whole lane
    tiles that divide it, else the whole width."""
    return next((n for n in (CHUNK, 256, 128) if c % n == 0), c)


def _over_chunks(n: int, body) -> None:
    """`body(c)` for the `n` chunks of the width: a rolled loop, or the
    one call where the width is one chunk."""
    if n == 1:
        body(0)
        return

    def step(c, carry):
        body(c)
        return carry
    lax.fori_loop(0, n, step, 0)


def _lanes(c, chunk: int, base: int = 0):
    """The `c`-th chunk of `chunk` lanes from lane `base` on, as a slice
    of a ref; `c` a Python int or the rolled loop's counter."""
    start = base + c * chunk
    if not isinstance(start, int):
        start = pl.multiple_of(start, chunk)
    return pl.ds(start, chunk)


def _rows(shape):
    return lax.broadcasted_iota(jnp.int32, shape, 0)


def _shift_back(u, halo, d: int):
    """`u` [rows, lanes] moved `d` rows down: row r holds u[r - d], its
    first `d` rows the last `d` of `halo` [HALO, lanes]."""
    out = pltpu.roll(u, d, 0)
    row = _rows(u.shape)
    for r in range(d):
        out = jnp.where(row == r, halo[HALO - d + r:HALO - d + r + 1], out)
    return out


def _shift_on(u, halo, d: int):
    """`u` moved `d` rows up: row r holds u[r + d], its last `d` rows the
    first `d` of `halo`."""
    n = u.shape[0]
    out = pltpu.roll(u, n - d, 0)
    row = _rows(u.shape)
    for r in range(d):
        out = jnp.where(row == n - d + r, halo[r:r + 1], out)
    return out


def _positions(block, rows: int, lanes: int, seq_len: int):
    """Each row's position in its own sequence, [rows, lanes] int32."""
    return (block * rows + _rows((rows, lanes))) % seq_len


def _conv_taps(u, halo_u, w_ref, lanes, pos, taps: int):
    """`(z, [u moved back by K-1-k for every tap k])`: the convolution of
    `u` and the masked, shifted copies it summed."""
    moved = []
    z = None
    for k in range(taps):
        d = taps - 1 - k
        if d:
            uk = jnp.where(pos >= d, _shift_back(u, halo_u, d), 0.0)
        else:
            uk = u
        moved.append(uk)
        term = uk * w_ref[k:k + 1, lanes]
        z = term if z is None else z + term
    return z, moved


def _fwd_kernel(bcx_ref, prev_ref, w_ref, y_ref, *, width, chunk, taps,
                seq_len):
    rows = y_ref.shape[0]
    block = pl.program_id(0)

    def body(c):
        def at(ref, part):
            return ref[:, _lanes(c, chunk, part * width)].astype(_F32)
        lanes = _lanes(c, chunk)
        pos = _positions(block, rows, chunk, seq_len)
        u = at(bcx_ref, 0) * at(bcx_ref, 2)
        halo_u = at(prev_ref, 0) * at(prev_ref, 2)
        z, _ = _conv_taps(u, halo_u, w_ref, lanes, pos, taps)
        y_ref[:, lanes] = (at(bcx_ref, 1) * z).astype(y_ref.dtype)

    _over_chunks(width // chunk, body)


def _bwd_kernel(bcx_ref, prev_ref, next_ref, dy_ref, dy_next_ref, w_ref,
                dbcx_ref, dw_ref, *, width, chunk, taps, seq_len, total):
    rows = dy_ref.shape[0]
    block = pl.program_id(0)
    ragged = total % rows != 0

    @pl.when(block == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def body(c):
        def at(ref, part):
            return ref[:, _lanes(c, chunk, part * width)].astype(_F32)
        lanes = _lanes(c, chunk)
        pos = _positions(block, rows, chunk, seq_len)
        b, cg, x, dy = (at(bcx_ref, 0), at(bcx_ref, 1), at(bcx_ref, 2),
                        at(dy_ref, 0))
        if ragged:
            # a padded block holds whatever past the last row
            live = block * rows + _rows((rows, chunk)) < total
            b, cg, x, dy = (jnp.where(live, t, 0.0) for t in (b, cg, x, dy))
        u = b * x
        halo_u = at(prev_ref, 0) * at(prev_ref, 2)
        z, moved = _conv_taps(u, halo_u, w_ref, lanes, pos, taps)
        dz = dy * cg
        halo_dz = at(dy_next_ref, 0) * at(next_ref, 1)
        du = None
        for k in range(taps):
            d = taps - 1 - k
            if d:
                dzk = jnp.where(pos < seq_len - d,
                                _shift_on(dz, halo_dz, d), 0.0)
            else:
                dzk = dz
            term = dzk * w_ref[k:k + 1, lanes]
            du = term if du is None else du + term
            dw_ref[k:k + 1, lanes] += (dz * moved[k]).sum(0, keepdims=True)
        dt = dbcx_ref.dtype
        for part, grad in enumerate((du * x, dy * z, du * b)):
            dbcx_ref[:, _lanes(c, chunk, part * width)] = grad.astype(dt)

    _over_chunks(width // chunk, body)


def _blocks(total: int, block_rows: int):
    """`(rows a step, steps, halo blocks a step, halo blocks in all)`."""
    rows = max(HALO, min(block_rows or BLOCK_ROWS, total) // HALO * HALO)
    return rows, -(-total // rows), rows // HALO, -(-total // HALO)


def _params(nbytes: int):
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=nbytes + _VMEM_MARGIN)


def _taps_block(w):
    """The taps as the kernels read them: [8, C] float32, a tile."""
    return jnp.zeros((8, w.shape[1]), _F32).at[:w.shape[0]].set(
        w.astype(_F32))


@functools.partial(jax.jit, static_argnames=("seq_len", "block_rows",
                                             "interpret"))
def _fwd_call(x, w, *, seq_len, block_rows, interpret):
    total, wide = x.shape
    width, taps = wide // 3, w.shape[0]
    rows, steps, per, _ = _blocks(total, block_rows)
    chunk = _chunk(width)
    item = x.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_fwd_kernel, width=width, chunk=chunk, taps=taps,
                          seq_len=seq_len),
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((rows, wide), lambda i: (i, 0)),
            pl.BlockSpec((HALO, wide),
                         lambda i: (jnp.maximum(i * per - 1, 0), 0)),
            pl.BlockSpec((8, width), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((rows, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((total, width), x.dtype),
        compiler_params=_params(
            2 * item * (rows * (wide + width) + HALO * wide)
            + 8 * 4 * rows * chunk),
        interpret=interpret, name=FWD_NAME,
    )(x, x, _taps_block(w))


@functools.partial(jax.jit, static_argnames=("seq_len", "block_rows",
                                             "interpret"))
def _bwd_call(x, w, dy, *, seq_len, block_rows, interpret):
    total, wide = x.shape
    width, taps = wide // 3, w.shape[0]
    rows, steps, per, halos = _blocks(total, block_rows)
    chunk = _chunk(width)
    item = x.dtype.itemsize

    def before(i):
        return jnp.maximum(i * per - 1, 0), 0

    def after(i):
        return jnp.minimum((i + 1) * per, halos - 1), 0

    return pl.pallas_call(
        functools.partial(_bwd_kernel, width=width, chunk=chunk, taps=taps,
                          seq_len=seq_len, total=total),
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((rows, wide), lambda i: (i, 0)),
            pl.BlockSpec((HALO, wide), before),
            pl.BlockSpec((HALO, wide), after),
            pl.BlockSpec((rows, width), lambda i: (i, 0)),
            pl.BlockSpec((HALO, width), after),
            pl.BlockSpec((8, width), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((rows, wide), lambda i: (i, 0)),
                   pl.BlockSpec((8, width), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((total, wide), x.dtype),
                   jax.ShapeDtypeStruct((8, width), _F32)],
        compiler_params=_params(
            2 * item * (rows * (2 * wide + width) + HALO * (2 * wide + width))
            + 14 * 4 * rows * chunk),
        interpret=interpret, name=BWD_NAME,
    )(x, x, x, dy, dy, _taps_block(w))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _conv(x, w, seq_len, block_rows, interpret):
    return _fwd_call(x, w, seq_len=seq_len, block_rows=block_rows,
                     interpret=interpret)


def _conv_fwd(x, w, seq_len, block_rows, interpret):
    return _conv(x, w, seq_len, block_rows, interpret), (x, w)


def _conv_bwd(seq_len, block_rows, interpret, residuals, dy):
    x, w = residuals
    dx, dw = _bwd_call(x, w, dy, seq_len=seq_len, block_rows=block_rows,
                       interpret=interpret)
    return dx, dw[:w.shape[0]].astype(w.dtype)


_conv.defvjp(_conv_fwd, _conv_bwd)


def gated_short_conv(bcx: jax.Array, w: jax.Array, block_rows: int = 0,
                     interpret: Optional[bool] = None) -> jax.Array:
    """`bcx` [batch, S, 3 C] (`[B | C | X]`), `w` [K, C] -> [batch, S, C],
    `C * conv(B * X)` as the module's docstring has it, in `bcx`'s dtype;
    differentiable in both.  `block_rows` 0 takes `BLOCK_ROWS`."""
    batch, seq_len, wide = bcx.shape
    if wide % 3 or w.shape[1] * 3 != wide or not 1 <= w.shape[0] <= 8:
        raise ValueError(f"bcx {bcx.shape} and taps {w.shape} do not fit: "
                         f"[B | C | X] is three times the taps' width, the "
                         f"taps at most 8")
    y = _conv(bcx.reshape(batch * seq_len, wide), w, seq_len, block_rows,
              flash_attention._use_interpret(interpret))
    return y.reshape(batch, seq_len, wide // 3)


# ---------------------------------------------------------------------------
# The Mamba-2 mixers' operator: taps, a bias and a silu
# ---------------------------------------------------------------------------
MAMBA_FWD_NAME, MAMBA_BWD_NAME = "mamba_conv_fwd", "mamba_conv_bwd"
# Rows a grid step takes and lanes of the inner loop's chunk, at most.  On
# the chip (PERF.md, Findings, PR 56; [8192, 4352] and [16384, 6144]) a
# [128, 128] float32 tile is what the backward kernel's dozen live values
# fit the registers at: 128 x 128 took 0.38 / 1.04 ms backward where
# 256 x 512 took 0.56 / 1.88, forward 0.23 / 0.65 either way; 64 rows pay
# their halo (16 rows a block) and 32 more.
MAMBA_BLOCK_ROWS = 128
MAMBA_CHUNK = 128


# Rows of a float32 tile: what the kernels below patch a shifted tile's
# edge with.
EDGE = 8


def _sigmoid(a):
    """By tanh: one pass of the transcendental unit and no division."""
    return 0.5 * jnp.tanh(0.5 * a) + 0.5


def _silu_slope(a):
    """silu'(a) = s (1 + a (1 - s)), s the sigmoid."""
    s = _sigmoid(a)
    return s * (1.0 + a * (1.0 - s))


def _part_chunks(parts):
    """`[(first lane, lanes of a chunk, chunks)]` of each part of the
    width: a lane tile where tiles divide the part and its first lane,
    else the part as one chunk."""
    out, base = [], 0
    for width in parts:
        chunk = MAMBA_CHUNK if (
            width % MAMBA_CHUNK == 0 and base % MAMBA_CHUNK == 0) else width
        out.append((base, chunk, width // chunk))
        base += width
    return out


def _moved_back(x, before, d: int):
    """`x` [rows, n] float32 moved `d` < EDGE rows down, its first rows
    the last of `before` [EDGE, n]: a rotation of the tile, and one of its
    first EDGE rows joined to `before`, for the rows the first has wrong."""
    edge = pltpu.roll(jnp.concatenate([before, x[:EDGE]], 0), d, 0)[EDGE:]
    if x.shape[0] == EDGE:
        return edge
    return jnp.concatenate([edge, pltpu.roll(x, d, 0)[EDGE:]], 0)


def _moved_on(x, after, d: int):
    """`x` moved `d` rows up, its last rows the first of `after`."""
    n = x.shape[0]
    edge = pltpu.roll(jnp.concatenate([x[n - EDGE:], after], 0),
                      2 * EDGE - d, 0)[:EDGE]
    if n == EDGE:
        return edge
    return jnp.concatenate([pltpu.roll(x, n - d, 0)[:n - EDGE], edge], 0)


def _pre_activation(x, before, wb_ref, lanes, taps: int, pos=None):
    """`(a, [x moved back by K-1-k for every tap k])`: bias + the taps
    over `x` [rows, n] float32 whose rows before the first are `before`
    [EDGE, n].  `pos` (each row's position in its sequence), where a
    sequence may start inside the tile: a tap across a start is masked."""
    moved = []
    a = wb_ref[taps:taps + 1, lanes]
    for k in range(taps):
        d = taps - 1 - k
        xk = _moved_back(x, before, d) if d else x
        if d and pos is not None:
            xk = jnp.where(pos >= d, xk, 0.0)
        moved.append(xk)
        a = a + xk * wb_ref[k:k + 1, lanes]
    return a, moved


def _tile(seq_len: int, rows: int, block, n: int):
    """`(pos, first, last)` of a grid step's tile of `n` lanes.  Where
    `rows` divides the sequences no sequence starts or ends inside a tile:
    `pos` is None and the scalars `first` / `last` say whether the tile
    starts / ends one (its neighbour's rows are then zeros).  Else `pos`
    [rows, n] masks each tap by its row's position, and the scalars are
    None."""
    if seq_len % rows == 0:
        start = (block * rows) % seq_len
        return None, start == 0, start == seq_len - rows
    return _positions(block, rows, n, seq_len), None, None


def _rows_before(prev_ref, lanes, first):
    """The EDGE rows before a tile, float32: the halo's last, or zeros
    where the scalar `first` says the tile starts a sequence (None: the
    taps are masked by position instead)."""
    before = prev_ref[:, lanes].astype(_F32)[HALO - EDGE:]
    return before if first is None else jnp.where(first, 0.0, before)


def _mamba_fwd_kernel(x_ref, prev_ref, wb_ref, *y_refs, parts, taps,
                      seq_len):
    rows = x_ref.shape[0]
    block = pl.program_id(0)
    for y_ref, (base, chunk, n) in zip(y_refs, _part_chunks(parts)):
        def body(c, y_ref=y_ref, base=base, chunk=chunk):
            lanes = _lanes(c, chunk, base)
            pos, first, _ = _tile(seq_len, rows, block, chunk)
            a, _ = _pre_activation(
                x_ref[:, lanes].astype(_F32),
                _rows_before(prev_ref, lanes, first), wb_ref, lanes, taps,
                pos)
            y_ref[:, _lanes(c, chunk)] = (a * _sigmoid(a)).astype(
                y_ref.dtype)

        _over_chunks(n, body)


def _mamba_bwd_kernel(x_ref, prev_ref, next_ref, wb_ref, *refs, parts, taps,
                      seq_len, total):
    """`refs`: a block of each part's `dy`, then the halo after it of each,
    then the two results `dx` and `dwb`."""
    dy_refs, dy_next_refs = refs[:len(parts)], refs[len(parts):-2]
    dx_ref, dwb_ref = refs[-2:]
    rows = x_ref.shape[0]
    block = pl.program_id(0)
    ragged = total % rows != 0

    @pl.when(block == 0)
    def _():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)

    for dy_ref, dy_next_ref, (base, chunk, n) in zip(
            dy_refs, dy_next_refs, _part_chunks(parts)):
        def body(c, dy_ref=dy_ref, dy_next_ref=dy_next_ref, base=base,
                 chunk=chunk):
            lanes, own = _lanes(c, chunk, base), _lanes(c, chunk)
            pos, first, last = _tile(seq_len, rows, block, chunk)
            x, dy = x_ref[:, lanes].astype(_F32), dy_ref[:, own].astype(_F32)
            if ragged:
                # a padded block holds whatever past the last row
                live = block * rows + _rows((rows, chunk)) < total
                x, dy = jnp.where(live, x, 0.0), jnp.where(live, dy, 0.0)
            a, moved = _pre_activation(
                x, _rows_before(prev_ref, lanes, first), wb_ref, lanes, taps,
                pos)
            da = dy * _silu_slope(a)
            # the NEXT tile's first rows of da, for the taps that reach
            # forward: their pre-activation is a convolution over the
            # joined edge, this tile's last rows standing before the
            # next's first (past a sequence's end they are masked, or
            # zeros, whatever the halo holds)
            edge, _ = _pre_activation(
                next_ref[:, lanes].astype(_F32)[:EDGE], x[rows - EDGE:],
                wb_ref, lanes, taps, None if pos is None else (
                    ((block + 1) * rows + _rows((EDGE, chunk))) % seq_len))
            after = (dy_next_ref[:, own].astype(_F32)[:EDGE]
                     * _silu_slope(edge))
            if pos is None:
                after = jnp.where(last, 0.0, after)
            dx = None
            for k in range(taps):
                d = taps - 1 - k
                dak = _moved_on(da, after, d) if d else da
                if d and pos is not None:
                    dak = jnp.where(pos < seq_len - d, dak, 0.0)
                term = dak * wb_ref[k:k + 1, lanes]
                dx = term if dx is None else dx + term
                dwb_ref[k:k + 1, lanes] += (da * moved[k]).sum(
                    0, keepdims=True)
            dwb_ref[taps:taps + 1, lanes] += da.sum(0, keepdims=True)
            dx_ref[:, lanes] = dx.astype(dx_ref.dtype)

        _over_chunks(n, body)


def _taps_and_bias(w, bias):
    """`_taps_block` with the bias in the row after the taps (zeros where
    there is none)."""
    if bias is None:
        return _taps_block(w)
    return _taps_block(w).at[w.shape[0]].set(bias.astype(_F32))


@functools.partial(jax.jit, static_argnames=("parts", "seq_len",
                                             "block_rows", "interpret"))
def _mamba_fwd_call(x, w, bias, *, parts, seq_len, block_rows, interpret):
    total, width = x.shape
    rows, steps, per, _ = _blocks(total, block_rows)
    item = x.dtype.itemsize
    telemetry.record_static("mamba_conv", kernel=1)
    telemetry.record_static("mamba_conv", labels={"call": "fwd"}, rows=rows)
    return pl.pallas_call(
        functools.partial(_mamba_fwd_kernel, parts=parts, taps=w.shape[0],
                          seq_len=seq_len),
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((rows, width), lambda i: (i, 0)),
            pl.BlockSpec((HALO, width),
                         lambda i: (jnp.maximum(i * per - 1, 0), 0)),
            pl.BlockSpec((8, width), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((rows, p), lambda i: (i, 0)) for p in parts],
        out_shape=[jax.ShapeDtypeStruct((total, p), x.dtype) for p in parts],
        compiler_params=_params(
            2 * item * (2 * rows + HALO) * width + 8 * 4 * rows * MAMBA_CHUNK),
        interpret=interpret, name=MAMBA_FWD_NAME,
    )(x, x, _taps_and_bias(w, bias))


@functools.partial(jax.jit, static_argnames=("seq_len", "block_rows",
                                             "interpret"))
def _mamba_bwd_call(x, w, bias, dys, *, seq_len, block_rows, interpret):
    total, width = x.shape
    parts = tuple(dy.shape[1] for dy in dys)
    rows, steps, per, halos = _blocks(total, block_rows)
    item = x.dtype.itemsize
    telemetry.record_static("mamba_conv", labels={"call": "bwd"}, rows=rows)

    def before(i):
        return jnp.maximum(i * per - 1, 0), 0

    def after(i):
        return jnp.minimum((i + 1) * per, halos - 1), 0

    return pl.pallas_call(
        functools.partial(_mamba_bwd_kernel, parts=parts, taps=w.shape[0],
                          seq_len=seq_len, total=total),
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((rows, width), lambda i: (i, 0)),
            pl.BlockSpec((HALO, width), before),
            pl.BlockSpec((HALO, width), after),
            pl.BlockSpec((8, width), lambda i: (0, 0)),
            *[pl.BlockSpec((rows, p), lambda i: (i, 0)) for p in parts],
            *[pl.BlockSpec((HALO, p), after) for p in parts]],
        out_specs=[pl.BlockSpec((rows, width), lambda i: (i, 0)),
                   pl.BlockSpec((8, width), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((total, width), x.dtype),
                   jax.ShapeDtypeStruct((8, width), _F32)],
        compiler_params=_params(
            2 * item * 3 * (rows + HALO) * width
            + 16 * 4 * rows * MAMBA_CHUNK),
        interpret=interpret, name=MAMBA_BWD_NAME,
    )(x, x, x, _taps_and_bias(w, bias), *dys, *dys)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _mamba(x, w, bias, parts, seq_len, block_rows, interpret):
    return tuple(_mamba_fwd_call(x, w, bias, parts=parts, seq_len=seq_len,
                                 block_rows=block_rows, interpret=interpret))


def _mamba_fwd(x, w, bias, parts, seq_len, block_rows, interpret):
    return (_mamba(x, w, bias, parts, seq_len, block_rows, interpret),
            (x, w, bias))


def _mamba_bwd(parts, seq_len, block_rows, interpret, residuals, dys):
    x, w, bias = residuals
    taps = w.shape[0]
    dx, dwb = _mamba_bwd_call(x, w, bias, tuple(dys), seq_len=seq_len,
                              block_rows=block_rows, interpret=interpret)
    return (dx, dwb[:taps].astype(w.dtype),
            None if bias is None else dwb[taps].astype(bias.dtype))


_mamba.defvjp(_mamba_fwd, _mamba_bwd)


def mamba_conv(x: jax.Array, w: jax.Array, bias: Optional[jax.Array],
               parts=None, block_rows: int = 0,
               interpret: Optional[bool] = None):
    """`silu(bias + conv(x))`: `x` [batch, S, C], `w` [K, C] (`w[K-1]`
    meets the current position), `bias` [C] -> [batch, S, C] in `x`'s
    dtype, differentiable in all three.  `bias` None is a convolution
    without one (`models/kimi_linear.py`): the kernels' row for it holds
    zeros and its gradient's row is left unread.  With `parts`, widths
    that sum to C, the result comes as a tuple of its stretches of lanes,
    each an array of its own that the call wrote (and whose cotangent the
    backward call reads), so that a caller who splits the result pays no
    copy for it.  `block_rows` 0 takes `MAMBA_BLOCK_ROWS`."""
    batch, seq_len, width = x.shape
    split = tuple(parts) if parts else (width,)
    if (w.shape[1] != width or sum(split) != width
            or (bias is not None and bias.shape != (width,))
            or not 1 <= w.shape[0] <= 7):
        raise ValueError(f"x {x.shape}, taps {w.shape}, bias "
                         f"{None if bias is None else bias.shape} "
                         f"and parts {split} do not fit, or more than 7 taps")
    ys = _mamba(x.reshape(batch * seq_len, width), w, bias, split, seq_len,
                block_rows or MAMBA_BLOCK_ROWS,
                flash_attention._use_interpret(interpret))
    ys = tuple(y.reshape(batch, seq_len, -1) for y in ys)
    return ys if parts else ys[0]


def kept_bytes(batch: int, seq_len: int, width: int, dtype) -> int:
    """Bytes of one call's `bcx`: what a rematerialised layer that keeps
    the projection's result holds for it."""
    return batch * seq_len * 3 * width * jnp.dtype(dtype).itemsize
