"""The expert layer's row moves as one Pallas TPU kernel.

    out[i] = sum over j < k of w[i, j] * src[idx[i, j]]     i < rows

`src` [n, D], `idx` [rows, k] int32, `w` [rows, k] float32 or None (ones).
The sum is float32, j ascending, rounded once to the result's dtype; an
index outside `[0, n)` adds nothing, whatever lies anywhere in `src`.
Every move of `parallel/dropless_moe.py` `_buffer` is this one operation:

  - tokens' rows into the buffer: k = 1, `idx` the row's token (and -1
    for a row no pair fills, which comes out zero);
  - results back to their tokens: k = `top_k`, `idx[t, j]` the place of
    pair (t, j) in the buffer, `w` the pair's weight; a scatter-add read
    from the side of what it adds up, so the order of a token's sum is
    fixed;
  - the first's transpose is the second without weights, the second's
    (towards the results) the first.

How it moves a row.  The chip's DMA engines copy whole tiles of an array
as it lies in HBM, eight rows by 128 lanes: Mosaic refuses a slice of one
row (`Slice shape along dimension 0 must be aligned to tiling (8)`).  So a
row is fetched with the seven that share its tiles, `GROUP` rows in one
contiguous copy of `8 D` elements, into a slot of a VMEM ring, and the one
wanted is read out of the slot by a load at a dynamic sublane: 8 times
the bytes of the row over the bus, which at the cells' widths still takes
less than issuing the copy does.  bfloat16 rows lie two to a 32-bit
sublane and no load takes one of a pair at a dynamic place: the slot is
read as 32-bit words and the row's half shifted or masked to the top of
the word, which IS its float32 value (bfloat16 rows are moved as
bfloat16, and widened where they are summed).

A grid step is a tile of `tm` result rows.  Its `tm k` indices (and
weights) are in SMEM, a block a step, and ROLLED loops walk them.  First
the pairs that name a row are written one after another, a dense list of
the tile's entries in the order they are summed (every pair is written
where the next entry goes, and only one that names a row moves that place
on: no branch, and a layer whose chip holds an eighth of the experts
walks the other seven eighths of its pairs once, here, and nowhere else).
Then a ring of `RING` slots, a DMA semaphore each: the first `RING`
copies start, and each entry in turn is waited for, read out of its slot
and added to its row of a float32 tile in VMEM, and the entry `RING` on
takes the slot over; the tile is rounded to the result's dtype at the end.
The text is a few hundred lines of MLIR whatever `tm`, `RING` and `k`
are.  Written out, four entries a turn, the kernel was 8% faster on the
chip and a body took the host four times as long to lower (0.11 s against
0.03 in the sandbox, eight bodies a program, three programs a run, on a
host four to six times slower): that is what PR 51's kernel paid 20 s of
a run's set-up for, and what is spent here (`ops/ssd.py`'s docstring has
the precedent; PERF.md, Findings, PR 52).

What a run's set-up pays.  `_gather_sum` is under a plain `jax.jit`, not
an inlined one: a model that unrolls its layers (`models/nemotron_h.py`),
the forward pass, the recompute and the exact path's loops share ONE
traced and lowered body a shape in a process, across its programs
(`ops/ssd.py` `_fwd_call` is the precedent).  The tile does not follow
`rows`, so the first buffer's calls and the exact path's differ by the
grid alone.  `texts()` counts the bodies traced.

Shapes.  Any goes through the same call, and XLA's gather and
scatter-add are nowhere behind it: a block holds the whole of `D`; a
width that is no multiple of 128 is padded to whole lane tiles round the
call and the result cut back (a copy takes whole tiles; the cells' widths
are 2048, 2304 and 2688 and pay nothing), rows likewise to whole groups
and tiles.  Off the TPU the kernel runs in the Pallas interpreter, as
`ops/grouped_matmul.py`'s do.  `src` of float32 or bfloat16; other
dtypes, which no model has, go through float32.
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

from ..common import telemetry
from . import flash_attention

_F32 = jnp.float32
# Rows of one copy: a tile of the array as it lies in HBM.
GROUP = 8
# Copies in flight: at 8 rows of 4 KB a copy, a few dozen cover the bus's
# latency (on the chip 32 and 64 and 128 took the same time to 2%).
RING = 32
# Result rows of a tile, at least: a step's ring starts empty and drains,
# so a tile is long against the ring; its float32 sum and the result's
# two blocks are what it costs in VMEM.
TILE_ROWS = 256
# What a 1-D block in SMEM is a multiple of (the layout the compiler
# gives a flat int32 array).
SMEM_BLOCK = 1024
LANE = 128
_VMEM_MARGIN = 8 * 1024 * 1024

# The (name, tile, ring, dtypes, shapes) of every body traced in this
# process.
_TEXTS = set()


def texts() -> int:
    """Distinct kernel bodies traced in this process."""
    return len(_TEXTS)


def tile_rows(k: int) -> int:
    """Result rows a grid step takes, from `k` alone: the fewest, from
    `TILE_ROWS` up, whose `k` indices a row make whole blocks of SMEM
    (k = 1: 1024 rows, 8: 256, 6: 512).  Not from `rows`: a result is
    padded to whole tiles, so the first buffer's call and the exact
    path's differ by the grid alone."""
    step = SMEM_BLOCK // math.gcd(k, SMEM_BLOCK)
    return step * -(-TILE_ROWS // step)


def _rows_kernel(idx, *refs, k, weighted, tm, ring, packed):
    if weighted:
        wts, src, out, buf, acc, sem, src_of, dst_of, w_of = refs
    else:
        src, out, buf, acc, sem, src_of, dst_of = refs
    width = acc.shape[1]

    # The tile's entries, in the order they are summed: the pairs that
    # name a row, written one after another.  Every pair is written where
    # the next entry goes and only one that names a row moves that place
    # on: no branch, about eight pairs a turn.
    per = max(1, 8 // k)

    def collect(block, n):
        for u in range(per):
            r = block * per + u
            for j in range(k):
                i = idx[r * k + j]
                src_of[n] = i
                dst_of[n] = r
                if weighted:
                    w_of[n] = wts[r * k + j]
                n = n + (i >= 0).astype(jnp.int32)
        return n

    n = lax.fori_loop(0, tm // per, collect, 0)

    def copy(q, group):
        slot = q % ring
        return pltpu.make_async_copy(
            src.at[pl.ds(pl.multiple_of(group * GROUP, GROUP), GROUP)],
            buf.at[slot], sem.at[slot])

    def issue(q):
        copy(q, src_of[q] // GROUP).start()

    def take(q):
        i, r = src_of[q], dst_of[q]
        copy(q, 0).wait()
        at = i % GROUP
        if packed:
            # two bfloat16 rows to a 32-bit sublane: the row's half, moved
            # to the top of the word, is its float32 value
            words = buf.at[q % ring].bitcast(jnp.uint32)
            word = words[pl.ds(at // 2, 1), :]
            row = lax.bitcast_convert_type(
                (word << (16 * (1 - at % 2)).astype(jnp.uint32))
                & jnp.uint32(0xFFFF0000), _F32)
        else:
            row = buf[q % ring, pl.ds(at, 1), :].astype(_F32)
        if weighted:
            row = row * w_of[q]
        if k > 1:
            row = row + acc[pl.ds(r, 1), :]
        acc[pl.ds(r, 1), :] = row

    def over(fn):
        return lambda q, c: (fn(q), c)[1]

    def zero(g, c):
        acc[pl.ds(pl.multiple_of(g * 8, 8), 8), :] = jnp.zeros((8, width),
                                                               _F32)
        return c

    # (where every row of the tile is written, k = 1 and all of them
    # named, nothing needs the zeros)
    @pl.when((n < tm) | (k > 1))
    def _():
        lax.fori_loop(0, tm // 8, zero, 0)

    # `ring` copies in flight: the first start, then each entry is read
    # out of its slot and the entry `ring` on takes the slot over
    lax.fori_loop(0, jnp.minimum(ring, n), over(issue), 0)
    steady = jnp.maximum(n - ring, 0)

    def both(q):
        take(q)
        issue(q + ring)

    lax.fori_loop(0, steady, over(both), 0)
    lax.fori_loop(steady, n, over(take), 0)

    def store(g, c):
        at = pl.ds(pl.multiple_of(g * 16, 16), 16)
        out[at, :] = acc[at, :].astype(out.dtype)
        return c

    lax.fori_loop(0, tm // 16, store, 0)


def _vmem(tm, ring, width, src_dtype, out_dtype):
    return width * (4 * tm + 2 * tm * jnp.dtype(out_dtype).itemsize
                    + ring * GROUP * jnp.dtype(src_dtype).itemsize)


def _rows_call(src, idx, w, *, k, tm, ring, out_dtype, interpret):
    """The kernel's call on whole tiles: `idx` (and `w`) flat,
    `[tiles * tm * k]`, `src` [whole groups, whole lane tiles] ->
    [tiles * tm, D]."""
    weighted = w is not None
    name = f"moe_rows_k{k}{'w' if weighted else ''}"
    _TEXTS.add((name, tm, ring, jnp.dtype(out_dtype).name,
                src.shape, src.dtype.name, idx.shape, interpret))
    rows, width = idx.shape[0] // k, src.shape[1]
    entries = tm * k            # pairs of a tile, and at most its entries
    flat = pl.BlockSpec((entries,), lambda s: (s,), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_rows_kernel, k=k, weighted=weighted, tm=tm,
                          ring=ring, packed=src.dtype.itemsize == 2),
        grid=(rows // tm,),
        in_specs=[flat] * (1 + weighted) + [
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tm, width), lambda s: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, width), out_dtype),
        scratch_shapes=[pltpu.VMEM((ring, GROUP, width), src.dtype),
                        pltpu.VMEM((tm, width), _F32),
                        pltpu.SemaphoreType.DMA((ring,)),
                        pltpu.SMEM((entries,), jnp.int32),
                        pltpu.SMEM((entries,), jnp.int32)]
        + [pltpu.SMEM((entries,), _F32)] * weighted,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(_vmem(tm, ring, width, src.dtype, out_dtype)
                              + _VMEM_MARGIN)),
        interpret=interpret, name=name,
    )(*((idx, w) if weighted else (idx,)), src)


@functools.partial(jax.jit, static_argnames=(
    "k", "out_dtype", "interpret", "use"))
def _gather_sum(src, idx, w, *, k, out_dtype, interpret, use):
    """`gather_sum` under a plain `jax.jit`, pads and all: traced and
    lowered once a process and shape, not once a call site (the module's
    docstring); the gauges are written when it is."""
    rows = idx.shape[0] // k
    n, width = src.shape
    if src.dtype not in (jnp.float32, jnp.bfloat16):
        src = src.astype(_F32)
    tm = tile_rows(k)
    telemetry.record_static("moe_rows", kernel=1, tile_rows=tm)
    telemetry.record_static("moe_rows", labels={"use": use}, rows=rows * k)
    # Whole groups of `src` and whole lane tiles of its width (a copy
    # takes the tiles a row lies in), whole tiles of the result (a step
    # walks a tile's pairs): pads that the cells' shapes never need.
    if n % GROUP or width % LANE:
        src = jnp.pad(src, ((0, -n % GROUP), (0, -width % LANE)))
    idx = jnp.where((idx >= 0) & (idx < n), idx, -1)
    pad = -rows % tm * k
    idx = jnp.pad(idx, (0, pad), constant_values=-1)
    if w is not None:
        w = jnp.pad(w.astype(_F32), (0, pad))
    out = _rows_call(src, idx, w, k=k, tm=tm, ring=RING,
                     out_dtype=out_dtype, interpret=interpret)
    telemetry.record_static("moe_rows", texts=texts())
    return out[:rows, :width]


def gather_sum(src, idx, w=None, k: Optional[int] = None, out_dtype=None,
               interpret: Optional[bool] = None, use: str = "gather"):
    """`out[i] = sum_j w[i, j] * src[idx[i, j]]` (the module's docstring):
    `src` [n, D]; `idx` int32 and `w` float32 (or None) both [rows, k],
    or both flat, `[rows * k]`, with `k` given (a flat list of pairs is
    what the layer has, and the kernel reads) -> [rows, D] of `out_dtype`
    (`src`'s unless given).  Not differentiable:
    `parallel/dropless_moe.py` writes the transposes, which are this call
    again.  `use` labels the pairs in `bps_moe_move_rows`."""
    return _gather_sum(
        src, idx.reshape(-1), None if w is None else w.reshape(-1),
        k=idx.shape[1] if k is None else k,
        out_dtype=jnp.dtype(out_dtype or src.dtype),
        interpret=flash_attention._use_interpret(interpret), use=use)
