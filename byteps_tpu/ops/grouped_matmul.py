"""The expert layer's grouped product as Pallas TPU kernels.

    out[r] = lhs[r] @ rhs[g]      for the rows r of group g,

`lhs` [rows, K] lies grouped: group 0's rows first, `group_sizes[g]` rows
each, and whatever lies past the last group belongs to none.  `rhs` is
[G, K, N].  That is `lax.ragged_dot`, which the TPU's compiler turns into
a kernel of its own; at an expert of width 896 = 7 x 128 that kernel ran
at a quarter of the chip's roofline (PERF.md section 6, PR 40), and this
one is the program's own.

One `jax.custom_vjp`, three kernels, named for what the trace's readers
look for (a grouped product is an instruction called `ragged-dot-none*`):

  `ragged-dot-none_fwd`       [rows, K] x [G, K, N] -> [rows, N]
  `ragged-dot-none_drows`     the same kernel on the result's gradient
                              [rows, N], contracting over N: the weights
                              are read as they lie, [K-tile, N-tile], and
                              the product takes them transposed (no
                              transposed copy in HBM)
  `ragged-dot-none_dweights`  [rows, K]^T x [rows, N] -> [G, K, N], a
                              group's row tiles summed in float32 scratch

A step of the grid is one TILE OF ROWS of one group.  The groups' offsets
are scalar-prefetched with two small tables, which group and which tile of
rows a step takes (`Walk`, made once a routing by `row_walk` and shared by
every product on it): a tile that a group's edge crosses is visited once
a group, each visit masked to its group's rows, and a tile with no live
row is no step at all: the grid's length is the number of visits, a value
of the run and not of the shapes.  So the buffer's padding costs nothing,
and an empty group costs the weights' gradient one step that keeps no row
and writes its zeros.

Rows past the last group are left as found, by all three kernels: the
result's rows there are never written, and the weights' gradient selects
them out of BOTH its operands.  `parallel/dropless_moe.py` `_buffer`
relies on exactly that.

Precision: the operands go into the MXU as they come (bfloat16 in the
cells), products are summed in float32 over all of K (over all of a
group's rows in the weights' gradient) and rounded once, to the dtype
`lax.ragged_dot` returns.

Tiles come from the shapes (`grouped_tiles`); a shape they cannot serve
is `lax.ragged_dot`'s as before (`grouped_matmul` says which ran,
`bps_grouped_*`).  A width that is a multiple of 64 and not of 128 (an
expert of 1856 = 14.5 x 128) is served too: a block may always hold a
dimension WHOLE, so such a width is never cut, and where a whole width
of K does not leave the weights' gradient room, that kernel cuts N
instead.  Off the TPU the kernels run in the Pallas interpreter.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import telemetry
from . import flash_attention

_F32 = jnp.float32
LANE = 128
# The least a width is a multiple of: half a lane tile, whole rows of
# packed bfloat16 (16) where the width is a block's second-to-last.
HALF_LANE = 64

# What a kernel's blocks may take of the chip's VMEM (128 MiB on a v5e, of
# which a kernel gets 16 unless it asks): the tile rule fits its blocks in
# this, and a call asks for what its blocks need and a margin.
VMEM_BUDGET = 48 * 1024 * 1024
_VMEM_MARGIN = 8 * 1024 * 1024


class Tiles(NamedTuple):
    """The blocks of the three kernels at one shape."""
    rows: int       # rows of a tile, all three kernels
    fwd_k: int      # forward: K a step (N whole)
    drows_n: int    # rows' gradient: N a step (K whole)
    dweights_k: int  # weights' gradient: rows of K a program owns ...
    dweights_n: int  # ... and columns of N (whole wherever K can be cut)


def _rows_vmem(tm, c, tc, to, itemsize):
    """Bytes of the rows kernel's blocks and values at `tc` of a
    contracted width `c` a step: both operands and the result
    double-buffered, a step's float32 product, and where the width goes
    in steps the float32 sum and its update beside it."""
    blocks = 2 * itemsize * (tm * tc + tc * to + tm * to)
    return blocks + 4 * tm * to * (1 if tc == c else 3)


def _dweights_vmem(tm, tk, tn, itemsize):
    """The weights' gradient's: operands and result double-buffered, the
    float32 sum and a step's product beside it."""
    return 2 * itemsize * (tm * tk + tm * tn + tk * tn) + 2 * 4 * tk * tn


def _divisors(width):
    """What a block may take of `width`, largest first: the whole of it,
    then its divisors that are multiples of 128 (none where the width
    itself is none: a block's last dimension is whole lane tiles or the
    array's own)."""
    return [width] + [d for d in range(width - width % LANE, 0, -LANE)
                      if d != width and width % d == 0]


def grouped_tiles(rows: int, k: int, n: int, groups: int,
                  dtype) -> Optional[Tiles]:
    """The tile rule: `Tiles` for `[rows, k] x [groups, k, n]`, or None
    where the kernels cannot tile the shape (a width that is no multiple
    of 64, rows that no tile of 128 divides, a result's width whose
    blocks alone pass `VMEM_BUDGET`) and `lax.ragged_dot` runs.

    The result's width is always whole, so a tile of rows is read once
    and its result written once.  The contracted width is whole too where
    the blocks fit `VMEM_BUDGET` (they do at every width a cell has: 19
    MB at 2304 x 896, over the 16 MiB a kernel gets unless it asks, so a
    call asks): a group's weights are then copied in ONCE a group, not
    once a tile of rows, since the block's index does not change between
    a group's tiles, and a step's product goes straight to the result
    with no float32 sum read and written beside it.  Else the contracted
    width goes in its largest divisor that fits.  A width of whole HALF
    lane tiles (1856 = 29 x 64) has no such divisor and stays whole in
    every kernel; the weights' gradient, whose float32 sum is [K, N],
    then takes N in its largest divisor that fits (1856 x 2688: N in
    thirds of 896, the rows of K read three times).  `_tile_rows` gives
    the rows of a tile.

    From the chip (TPU v5e, the products alone, 65,536 live rows of
    81,920 on 16 experts; docs/performance.md, "Grouped products"): at
    2304 x 896 the whole width takes 1,712 us a forward call at 256 rows
    and 1,733 at 512, 1,884 at 1,024; the width in halves 2,309 / 1,929,
    in thirds 2,391 / 2,061 (megablox at (512, 768, 896) 1,878; the
    compiler's kernel 5,972).  At trinity-mini's 2048 x 1024 on 2,048
    rows an expert 256 rows beat 512 in all three kinds, 976 against
    1,020 us forward (the compiler's 1,314)."""
    itemsize = jnp.dtype(dtype).itemsize
    if k % HALF_LANE or n % HALF_LANE or groups < 1:
        return None
    tm = _tile_rows(rows)
    if tm is None:
        return None

    def fit(width, vmem):
        return next((d for d in _divisors(width)
                     if vmem(d) <= VMEM_BUDGET), None)

    fwd_k = fit(k, lambda d: _rows_vmem(tm, k, d, n, itemsize))
    drows_n = fit(n, lambda d: _rows_vmem(tm, n, d, k, itemsize))
    dweights = None
    for tn in _divisors(n):       # N whole wherever some cut of K fits
        tk = fit(k, lambda d: _dweights_vmem(tm, d, tn, itemsize))
        if tk is not None:
            dweights = (tk, tn)
            break
    if None in (fwd_k, drows_n, dweights):
        return None
    return Tiles(tm, fwd_k, drows_n, *dweights)


def row_tiles(rows: int, groups: int, tile: int, live: int) -> dict:
    """What a kernel's grid walks at the EVEN routing, `live` rows spread
    over the groups in order: the visits it makes (a tile crossed by an
    edge once a group) over the tiles that hold a live row."""
    edges = [live * g // groups for g in range(groups + 1)]
    walked = sum(-(-hi // tile) - lo // tile
                 for lo, hi in zip(edges, edges[1:]) if hi > lo)
    return {"row_tiles_walked": walked,
            "row_tiles_needed": -(-live // tile),
            "row_tiles_buffer": rows // tile}


def record_walk(rows: int, k: int, n: int, groups: int, dtype,
                live: int) -> None:
    """Writes `row_tiles` at `live` rows into the `bps_grouped_row_tiles_*`
    gauges, for a caller that knows its even routing (the layer does, a
    product does not); nothing where the shape goes to `lax.ragged_dot`."""
    tiles = grouped_tiles(rows, k, n, groups, dtype)
    if tiles is not None:
        telemetry.record_static(
            "grouped_matmul", **row_tiles(rows, groups, tiles.rows, live))


class Walk(NamedTuple):
    """The tables of one routing's grids, which every product on that
    routing and both of its gradients share (`row_walk`).  Step `s` of a
    grid takes the rows of group `group[s]` that lie in row tile
    `tile[s]`, for `s` below `steps[0]`; `offsets[g]` is group g's first
    row and `offsets[g + 1]` the row past its last."""
    offsets: jax.Array      # [G + 1]
    group: jax.Array        # [S], forward and the rows' gradient
    tile: jax.Array
    steps: jax.Array        # [1]
    group_w: jax.Array      # [S], the weights' gradient: an empty group
    tile_w: jax.Array       #      has a step of its own there
    steps_w: jax.Array


def _steps(sizes, ends, rows: int, tile: int, visit_empty: bool):
    """One grid's `(group [S], tile [S], steps [1])`: the steps run group
    by group, each over its row tiles first to last, so a tile shared by
    two groups is visited by each in turn, one after the other.
    `visit_empty` gives a group without rows one step (at a tile it does
    not read).  S = rows / tile + G - 1 is the most the tables can hold;
    past `steps` they repeat the last step and the grid does not go
    there.  (`lax` operations and no operator: `a - b` on a traced array
    is a jitted `jnp` function of its own, and these few dozen are traced
    for every expert layer of a step, forward, recomputed and backward.)"""
    groups = sizes.shape[0]
    n_tiles, most = rows // tile, rows // tile + groups - 1
    first = lax.min(lax.div(lax.sub(ends, sizes), tile), n_tiles - 1)
    last = lax.div(lax.sub(ends, 1), tile)
    visits = lax.select(lax.gt(sizes, 0), lax.add(lax.sub(last, first), 1),
                        lax.full_like(sizes, int(visit_empty)))
    upto = lax.cumsum(visits)
    total = lax.slice(upto, (groups - 1,), (groups,))
    s = lax.min(lax.iota(jnp.int32, most),
                lax.broadcast_in_dim(lax.max(lax.sub(total, 1), 0), (most,),
                                     (0,)))
    # ended[s, g]: group g's steps end at or before step s, so the group
    # of step s is their count, and a table's entry for it, v[group[s]],
    # is v[0] plus the steps v makes at every group that has ended: no
    # gather
    ended = lax.convert_element_type(
        lax.ge(lax.broadcast_in_dim(s, (most, groups), (0,)),
               lax.broadcast_in_dim(upto, (most, groups), (1,))), jnp.int32)
    group = lax.min(lax.reduce_sum(ended, (1,)), groups - 1)
    # tile[s] = first[g] + (s - the step g starts at), g = group[s]
    at = lax.sub(first, lax.sub(upto, visits))
    jumps = lax.sub(lax.slice(at, (1,), (groups,)),
                    lax.slice(at, (0,), (groups - 1,)))
    of_group = lax.add(
        lax.reduce_sum(lax.mul(
            lax.slice(ended, (0, 0), (most, groups - 1)),
            lax.broadcast_in_dim(jumps, (most, groups - 1), (1,))), (1,)),
        lax.broadcast_in_dim(lax.slice(at, (0,), (1,)), (most,), (0,)))
    tile_of = lax.clamp(0, lax.add(of_group, s), n_tiles - 1)
    return group, tile_of, total


def _tile_rows(rows: int) -> Optional[int]:
    """256 rows a tile where they divide the buffer (its own multiple is
    512), else 128 (`grouped_tiles` says why)."""
    return next((t for t in (256, 128) if rows % t == 0), None)


@functools.partial(jax.jit, inline=True, static_argnames=("rows", "tile"))
def _row_walk(group_sizes, *, rows: int, tile: int) -> Walk:
    sizes = lax.convert_element_type(group_sizes, jnp.int32)
    ends = lax.cumsum(sizes)
    offsets = lax.concatenate([lax.full((1,), 0, jnp.int32), ends], 0)
    return Walk(offsets, *_steps(sizes, ends, rows, tile, False),
                *_steps(sizes, ends, rows, tile, True))


def row_walk(group_sizes, rows: int,
             tile: Optional[int] = None) -> Optional[Walk]:
    """The tables for `rows` rows grouped by `group_sizes`, to hand to
    every `grouped_matmul` on that routing (the expert layer's three
    products and their six gradients walk the same rows: made once, the
    tables are traced once); None where no tile divides the rows.
    `tile` is the rule's unless a test or the microbench names one."""
    tile = tile or _tile_rows(rows)
    return None if tile is None else _row_walk(group_sizes, rows=rows,
                                               tile=tile)


def _group_rows(offsets, group, tile_of, s, tm):
    """Of step `s`: its group's `(first row, past its last row)` and the
    first row of its tile."""
    g = group[s]
    return offsets[g], offsets[g + 1], tile_of[s] * tm


def _live(lo, hi, row0, tm):
    """[tm, 1]: which rows of the tile at `row0` lie in [lo, hi)."""
    row = row0 + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (row >= lo) & (row < hi)


def _rows_kernel(offsets, group, tile_of, n, lhs, rhs, out, *scratch, tm,
                 c_steps, transposed):
    del n
    s, ci = pl.program_id(0), pl.program_id(1)
    dims = (((1,), (1 if transposed else 0,)), ((), ()))
    part = lax.dot_general(lhs[...], rhs[...], dims,
                           preferred_element_type=_F32)

    def store(total):
        # Only this group's rows of the tile: the rows of other groups
        # stay as the visit before left them (the block is not written
        # back between visits of one tile), the rows of no group as they
        # were found.  A tile inside one group is masked like any other:
        # on the chip the select cost nothing against a branch round it
        # (docs/performance.md, "Grouped products").
        lo, hi, row0 = _group_rows(offsets, group, tile_of, s, tm)
        out[...] = jnp.where(_live(lo, hi, row0, tm),
                             total.astype(out.dtype), out[...])

    if c_steps == 1:
        store(part)
        return
    acc, = scratch

    @pl.when(ci == 0)
    def _():
        acc[...] = part

    @pl.when(ci > 0)
    def _():
        acc[...] += part

    @pl.when(ci == c_steps - 1)
    def _():
        store(acc[...])


@functools.partial(jax.jit, inline=True, static_argnames=(
    "tm", "tc", "transposed", "interpret"))
def _rows_call(lhs, rhs, walk: Walk, *, tm, tc, transposed, interpret):
    """`lhs` [rows, C] through `rhs` [G, C, O] -> [rows, O], C in steps of
    `tc`, on a `Walk` made for tiles of `tm`: the forward product, or
    `transposed` (`rhs` [G, O, C]) the rows' gradient.  (Jitted and
    inlined: a step's dozens of calls at a few shapes trace each shape's
    kernel once, and leave no call of their own in the program.)"""
    rows, c = lhs.shape
    o = rhs.shape[1] if transposed else rhs.shape[2]
    c_steps = c // tc
    tables = (walk.offsets, walk.group, walk.tile, walk.steps)
    if transposed:
        rhs_spec = pl.BlockSpec(
            (None, o, tc), lambda s, ci, off, grp, til, n: (grp[s], 0, ci))
    else:
        rhs_spec = pl.BlockSpec(
            (None, tc, o), lambda s, ci, off, grp, til, n: (grp[s], ci, 0))
    itemsize = lhs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_rows_kernel, tm=tm, c_steps=c_steps,
                          transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tables[3][0], c_steps),
            in_specs=[
                pl.BlockSpec((tm, tc),
                             lambda s, ci, off, grp, til, n: (til[s], ci)),
                rhs_spec],
            out_specs=pl.BlockSpec(
                (tm, o), lambda s, ci, off, grp, til, n: (til[s], 0)),
            scratch_shapes=([pltpu.VMEM((tm, o), _F32)]
                            if c_steps > 1 else [])),
        out_shape=jax.ShapeDtypeStruct((rows, o), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=(_rows_vmem(tm, c, tc, o, itemsize)
                              + _VMEM_MARGIN)),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * c * o, transcendentals=0,
            bytes_accessed=itemsize * (rows * (c + o) + rhs.size)),
        interpret=interpret,
        name="ragged-dot-none_drows" if transposed else "ragged-dot-none_fwd",
    )(*tables, lhs, rhs)


def _dweights_kernel(offsets, group, tile_of, n, lhs, g, out, acc, *, tm):
    s = pl.program_id(2)
    here = group[s]
    first = (s == 0) | (group[jnp.maximum(s - 1, 0)] != here)
    last = (s == n[0] - 1) | (group[jnp.minimum(s + 1, n[0] - 1)] != here)
    lo, hi, row0 = _group_rows(offsets, group, tile_of, s, tm)
    # Rows of another group, or of none, go out of BOTH operands, by a
    # select: what lies past the last group may be NaN on either side, and
    # 0 * NaN is NaN.  An empty group's one step keeps no row and adds
    # zeros.  (A tile inside one group is masked too: as in the rows
    # kernel, a branch round the selects bought nothing on the chip.)
    live = _live(lo, hi, row0, tm)
    part = lax.dot_general(
        jnp.where(live, lhs[...], 0), jnp.where(live, g[...], 0),
        (((0,), (0,)), ((), ())), preferred_element_type=_F32)
    # a group's first step starts the sum: what the scratch held is
    # dropped by a select, not added
    acc[...] = jnp.where(first, part, acc[...] + part)

    @pl.when(last)
    def _():
        out[...] = acc[...].astype(out.dtype)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "tm", "tk", "tn", "interpret"))
def _dweights_call(lhs, g, walk: Walk, *, tm, tk, tn=None, interpret):
    """`lhs` [rows, K], `g` [rows, N] -> [G, K, N]: each group's
    `lhs^T g` over its own rows, a program owning `tk` rows of K and
    `tn` columns of N (None: all), on a `Walk`'s tables for the weights'
    gradient."""
    rows, k = lhs.shape
    n = g.shape[1]
    tn = tn or n
    groups = walk.offsets.shape[0] - 1
    tables = (walk.offsets, walk.group_w, walk.tile_w, walk.steps_w)
    itemsize = lhs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_dweights_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // tk, n // tn, tables[3][0]),
            in_specs=[
                pl.BlockSpec(
                    (tm, tk),
                    lambda ki, ni, s, off, grp, til, n: (til[s], ki)),
                pl.BlockSpec(
                    (tm, tn),
                    lambda ki, ni, s, off, grp, til, n: (til[s], ni))],
            out_specs=pl.BlockSpec(
                (None, tk, tn),
                lambda ki, ni, s, off, grp, til, n: (grp[s], ki, ni)),
            scratch_shapes=[pltpu.VMEM((tk, tn), _F32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=(_dweights_vmem(tm, tk, tn, itemsize)
                              + _VMEM_MARGIN)),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=itemsize * (rows * (k * (n // tn)
                                               + n * (k // tk))
                                       + groups * k * n)),
        interpret=interpret, name="ragged-dot-none_dweights",
    )(*tables, lhs, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(lhs, rhs, walk: Walk, tiles: Tiles, interpret: bool):
    return _rows_call(lhs, rhs, walk, tm=tiles.rows, tc=tiles.fwd_k,
                      transposed=False, interpret=interpret)


def _grouped_fwd(lhs, rhs, walk, tiles, interpret):
    return _grouped(lhs, rhs, walk, tiles, interpret), (lhs, rhs, walk)


def _grouped_bwd(tiles, interpret, residuals, g):
    lhs, rhs, walk = residuals
    g = g.astype(lhs.dtype)
    d_lhs = _rows_call(g, rhs, walk, tm=tiles.rows, tc=tiles.drows_n,
                       transposed=True, interpret=interpret)
    d_rhs = _dweights_call(lhs, g, walk, tm=tiles.rows, tk=tiles.dweights_k,
                           tn=tiles.dweights_n, interpret=interpret)
    return d_lhs, d_rhs.astype(rhs.dtype), None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, group_sizes, interpret: Optional[bool] = None,
                   walk: Optional[Walk] = None):
    """`lax.ragged_dot(lhs, rhs, group_sizes)`, differentiable in `lhs`
    and `rhs`: `lhs` [rows, K] grouped by `group_sizes` [G] (their sum at
    most `rows`), `rhs` [G, K, N], both of one dtype.  The rows past the
    last group come back as the kernel found them, and so do their
    gradients (on the CPU's `lax.ragged_dot` they are zeros): a caller
    masks them.  `walk` is `row_walk(group_sizes, rows)` where the caller
    has made it for several products on one routing.  Which path ran,
    and on what tiles, is in the `bps_grouped_*` gauges of the last call
    traced."""
    rows, k = lhs.shape
    groups, _, n = rhs.shape
    tiles = (grouped_tiles(rows, k, n, groups, lhs.dtype)
             if lhs.dtype == rhs.dtype else None)
    if tiles is None:
        telemetry.record_static("grouped_matmul", kernel=0)
        return lax.ragged_dot(lhs, rhs, group_sizes)
    telemetry.record_static(
        "grouped_matmul", kernel=1, tile_rows=tiles.rows,
        tile_fwd_k=tiles.fwd_k, tile_drows_n=tiles.drows_n,
        tile_dweights_k=tiles.dweights_k, tile_dweights_n=tiles.dweights_n)
    if walk is None:
        walk = row_walk(group_sizes, rows)
    return _grouped_call(lhs, rhs, walk, tiles=tiles,
                         interpret=flash_attention._use_interpret(interpret))


@functools.partial(jax.jit, inline=True,
                   static_argnames=("tiles", "interpret"))
def _grouped_call(lhs, rhs, walk: Walk, *, tiles: Tiles, interpret: bool):
    """`_grouped`, jitted and inlined like the kernels' calls: a step's
    sixty products are twelve shapes, and each shape's `custom_vjp` is
    traced once."""
    return _grouped(lhs, rhs, walk, tiles, interpret)
