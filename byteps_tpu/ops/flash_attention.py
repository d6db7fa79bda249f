"""Flash attention as a Pallas TPU kernel (forward + backward).

The flagship transformer's dense attention materializes the [S, S] logits
in HBM per layer (models/transformer.py dense_attention) — the classic
memory-bound hot spot.  This kernel computes attention blockwise with an
online softmax so nothing bigger than a (block_q, block_k) tile of logits
ever exists, and the backward recomputes probabilities blockwise from the
saved log-sum-exp instead of storing them.

Two execution strategies, auto-selected by VMEM footprint:

  - **resident** (K and V of a head within RESIDENT_VMEM_BUDGET: GPT-2
    at S=1024 with 256 KB a head, granite and trinity-mini at S=8192
    with 2 and 4 MiB): K and V live in VMEM
    for the whole kernel and are fetched from HBM once per (batch*head).
    A program owns the whole sequence up to PROGRAM_ROWS rows, beyond
    that one tile's worth, and takes its rows in groups of `block_q`.  A group first takes its REGION in one piece, the keys
    from the start of the `block_k` tile that holds its first row to its
    last row's own key, masked: the diagonal's part, as wide as it has to
    be and no wider.  Then it loops over the whole tiles before that,
    which no diagonal crosses and nothing masks (a sliding window's far
    edge crosses some: those are masked, in a loop of their own).  The
    dK/dV kernel walks the same square by its keys, on tiles transposed.
    Where one program owns the sequence (S <= 1024) every bound is a
    Python int and the kernel is straight-line code; with `block_k` = S a
    group is one region and a plain softmax, no running statistics.
    `k_tiles` / `q_tiles` give the bounds, `tile_schedule` counts what
    they visit (the `bps_flash_*` gauges, written when a call is traced).
  - **streaming** (K and V of a head over the budget: one sequence of
    32,768 positions at head size 128 is 16 MiB, the mellum cell): a
    grid step is one tile of K and V against one block of rows (dK/dV:
    of Q and dO against a block of keys), and the carry lives in VMEM
    scratch across a block's steps (the matmul k-loop pattern).
    `stream_walk` gives a call its grid, one of two.  Where the blocks'
    bands differ in length (a causal call: 1 to 64 tiles) a step is one
    ENTRY OF A TABLE, on a grid (bh, entries): the table (`stream_table`,
    built in Python from `k_band` / `q_band`) lists the live tiles of a
    head's square and no other, in the order the kernel visits them,
    forward and dQ row block by row block, each over its band of key
    tiles first to last, dK/dV key block by key block over its band of
    row tiles; an entry holds the block the program owns, the tile it
    walks, and whether it is the FIRST and the LAST of its block.  The
    table's columns are scalar-prefetch operands: the index maps read
    them and so does the kernel.  Where the bands are all about as long
    (a window: 3 tiles; no mask: the axis) a step is step `j` of block
    `i`'s band, on a grid (bh, blocks, longest band): the tile is
    `first + j`, computed and not looked up, and past the band's end the
    last tile again, which is not copied twice and computes nothing.
    Either way steps of one block keep the block's Q / O / dQ (dK/dV: K
    / V / dK / dV), so nothing of those is copied or written until the
    block's last step; the kernel starts its carry at a block's first
    step and writes the result at its last.  The forward kernel's running
    maximum and sum are kept in scratch REPLICATED along the lanes,
    [block_q, 128], because a [block_q, 1] column's way out of scratch
    and back cost more than the tile's arithmetic (`_spread`).  Every
    tile of a causal call is masked (`_edge`).  Per-program VMEM is
    O(block * d) regardless of S, so the kernel keeps compiling at 32k+
    contexts, at the price of re-streaming K/V once per q block.
    `stream_schedule` counts the steps, the live ones and the tiles
    copied (the `bps_flash_stream_*` gauges): a causal call 2,080 steps,
    all live, where the square has 4,096; a window of 1024 192 steps for
    189 tiles.

A fourth kind of mask beside none, causal and window: BLOCK DIFFUSION
(`block_diffusion=(L, beta)`, `models/sdar.py`), over the two copies of a
sequence, clean then noised, in blocks of beta tokens.  A clean row sees
the clean keys of its own and earlier blocks, a noised row the clean keys
of EARLIER blocks and the noised keys of its OWN, three regions of the
[2 L, 2 L] square of which the fourth is dead (`bd_tile`).  It is never
an array: `stream_table` lists the live tiles (a block's run may have a
gap; 1,088 a head at 2 L = 32,768 in tiles of 512, where the causal
table has 2,080), a masked tile's rule is computed in the kernel from
its first row, its first key and `(L, beta)` (`_bd_mask`), and a tile the
table marks WHOLE is not masked.  Such a call always takes the streaming
walk.

What a tile costs on a v5e is the vector unit's work on its float32
logits, not the MXU's: head size 64 and 128 take the same time a tile,
and bfloat16 operands, or bfloat16 probabilities, bought nothing
measurable (PERF.md section 6, PR 35).  So Q is scaled once a group (in
float32; K once a group in dK/dV), the per-row statistics are paid once a
tile and tiles of keys are wide, and P and dS go into their products in
float32.

This is the compute-path counterpart of the reference's CUDA-side
optimizations: the reference leaves model compute to torch/cudnn (no
attention kernels of its own); a TPU-native framework owns its hot ops
(pallas guide: grid/BlockSpec tiling onto the MXU, f32 accumulation,
custom-VJP pattern).

What a rematerialised layer can keep (`KEPT_NAME`).  The forward rule
names its two residuals that a kernel made, `o` and `lse` (`o` is the
call's result as well, so ONE `o` a call), with `checkpoint_name`.  A
layer under `jax.checkpoint(...,
policy=save_only_these_names(flash_attention.KEPT_NAME))` holds them from
its forward pass, its recompute has no consumer of the forward kernel left
and does not call it, and the two backward kernels read what the one call
wrote: B x H x S x (D x itemsize + 4) bytes a call.  Under any policy that
does not list the name (None, `dots`, the transformer's `proj`, afmoe's
`selection`) the name is an identity that lowers to nothing, and the
compiled step is what it was.  Resident and streaming kernels alike,
window or none.

Layout: q, k, v are [BH, S, D] (batch*heads folded into the grid's first
axis); q and k may be one width and v, o and dO another (`flash_attention`
says how).  The block sizes must divide S; block_q must be a multiple of 128
and block_k a multiple of 64 (`check_blocks` — the chip's lane rule, which
interpret mode does not enforce); D should be a multiple of 8.  A shape that doesn't satisfy the constraints is
refused up front, on every backend: the kernel never degrades to dense
attention, and neither does `models.transformer.flash_attention_fn`.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import telemetry

NEG_INF = float("-inf")
# The name a call's `o` and `lse` carry for `jax.checkpoint` (above).
KEPT_NAME = "flash.attention"


def kept_bytes(bh: int, s: int, d: int, dtype) -> int:
    """Bytes `KEPT_NAME` names a call: `o` [bh, s, d] (`d` the VALUE
    heads' width, where the keys' differs) and `lse` [bh, 1, s] float32."""
    return bh * s * (d * jnp.dtype(dtype).itemsize + 4)

# K+V (resident path) above this many bytes switch to the streaming path;
# ~16MB VMEM/core on current TPUs, leave room for q/o/do tiles + scratch.
RESIDENT_VMEM_BUDGET = 6 * 1024 * 1024


# A group of block_q rows starts a slice of the LANE dim of the per-row
# statistics (log-sum-exp and delta, shape (BH, 1, S)), and the TPU
# lowering takes a block's last dim only in multiples of 128.  Mosaic also
# refuses the resident dK/dV kernel's lane-dim slices of those rows unless
# they are provably 128-aligned, so "block_q == S" does not rescue an S
# that is an odd multiple of 64.  K/V tiles only ever sit on a sublane
# dim.  Both facts are from compiling for a described v5e
# (tests/test_tpu_aot_compile.py).
BLOCK_Q_MULTIPLE = 128
BLOCK_K_MULTIPLE = 64


def check_blocks(s: int, block_q: int, block_k: int,
                 block_diffusion=None) -> None:
    """Raise ValueError unless (block_q, block_k) tile a length-`s`
    sequence in a way the chip's compiler accepts.  One rule for both
    paths: a streaming call's blocks are its grid's tiles, and its walk
    (`stream_walk`, from `k_band` / `q_band`) takes any pair this
    allows, rows wider than keys or keys wider than rows.  Under a
    `block_diffusion` mask `(L, beta)` the sequence is the two copies,
    `s` = 2 L, a tile lies in ONE copy (L a whole number of tiles of both
    kinds) and holds whole blocks (`beta` divides both tiles): the
    kernels' rule for a tile rests on both (`_bd_mask`)."""
    if (block_q <= 0 or block_k <= 0 or s % block_q or s % block_k
            or block_q % BLOCK_Q_MULTIPLE or block_k % BLOCK_K_MULTIPLE):
        raise ValueError(
            f"flash attention cannot tile seq_len {s} with "
            f"block_q={block_q}, block_k={block_k}: both must divide the "
            f"sequence, block_q must be a multiple of {BLOCK_Q_MULTIPLE} "
            f"and block_k a multiple of {BLOCK_K_MULTIPLE}")
    if block_diffusion is None:
        return
    L, beta = block_diffusion
    if (s != 2 * L or beta <= 0 or L % block_q or L % block_k
            or block_q % beta or block_k % beta):
        raise ValueError(
            f"flash attention cannot lay a block-diffusion mask of "
            f"L={L}, beta={beta} over seq_len {s} in tiles of "
            f"block_q={block_q}, block_k={block_k}: the sequence is the two "
            f"copies (2 L), L a whole number of tiles of both kinds, and "
            f"beta divides both tiles")


def _use_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _use_streaming(k, streaming: Optional[bool], v=None,
                   block_diffusion=None) -> bool:
    """Whether a head's K [S, Dk] and V [S, Dv] (None: as wide as K)
    together pass the resident budget; a call under a `block_diffusion`
    mask streams whatever its size and whatever `streaming` says."""
    if block_diffusion is not None:
        return True
    if streaming is not None:
        return streaming
    _bh, s, dk = k.shape
    dv = dk if v is None else v.shape[2]
    return s * (dk + dv) * k.dtype.itemsize > RESIDENT_VMEM_BUDGET


def _pick(ints, traced, a, b):
    if isinstance(a, int) and isinstance(b, int):
        return ints(a, b)
    return traced(a, b)


def _least(a, b):
    return _pick(min, jnp.minimum, a, b)


def _most(a, b):
    return _pick(max, jnp.maximum, a, b)


def _clamp(x, lo, hi):
    return _least(_most(x, lo), hi)


def dkv_tile(block_k):
    """The Q tile of the dK/dV kernel: `block_k`, made a multiple of 128
    because it slices the log-sum-exp's lane dim."""
    return math.lcm(block_k, BLOCK_Q_MULTIPLE)


def k_tiles(r0, at, group, tile, num_tiles, causal, window=None):
    """What the query rows [r0, r0 + group) visit of the keys, which lie
    in tiles of `tile`:

        (start, width, masked), (a, b, c)

    First one REGION, the keys [start, start + width): under a causal
    mask the stretch from the start of the tile that holds key r0 to the
    rows' last key, r0 + group - 1, so its width is static and it ends
    where the diagonal does.  Then the tiles [a, c) before it, of which
    [a, b) are crossed by the far edge of a sliding `window` (row i sees
    the keys i - window < j <= i) and are masked, and [b, c) are seen
    whole by every row and are not.  Without a mask the region is tile 0
    and [b, c) the rest.

    `r0` is a Python int or a traced index, `at` = r0 % tile always an
    int.  The kernels' bounds and `tile_schedule`'s counts both come from
    here; `q_tiles` is the same square seen from the keys."""
    if not causal:
        return (0, tile, False), (1, 1, num_tiles)
    last = (r0 - at) // tile
    region = (r0 - at, at + group, True)
    if window is None:
        return region, (0, 0, last)
    a = _least(_most(r0 - window + 1, 0) // tile, last)
    # the first tile whose first key the LAST row still sees
    whole_from = _most(r0 + group - window + tile - 1, 0) // tile
    return region, (a, _clamp(whole_from, a, last), last)


def q_tiles(c0, at, group, tile, num_tiles, causal, window=None):
    """What visits the keys [c0, c0 + group), of the query rows in tiles
    of `tile`: `(start, height, masked), (b, c, d)`.  The region is the
    rows from c0 to the end of the tile that holds row c0 + group - 1;
    the tiles [b, d) after it are past the diagonal, [b, c) seen whole
    and [c, d) crossed by the window's edge.  `at` = c0 % tile."""
    if not causal:
        return (0, tile, False), (1, num_tiles, num_tiles)
    spans = (at + group + tile - 1) // tile     # tiles the region touches
    b = (c0 - at) // tile + spans
    region = (c0, spans * tile - at, True)
    if window is None:
        return region, (b, num_tiles, num_tiles)
    d = _clamp((c0 + group + window - 2) // tile + 1, b, num_tiles)
    # tiles whose last row still sees key c0
    return region, (b, _clamp((c0 + window) // tile, b, d), d)


def tile_schedule(s, block_q, block_k, causal, window=None):
    """What a call's forward and dQ kernels do to the [s, s] square, from
    the bounds they loop over: the tiles (regions counted as tiles) they
    compute, how many of those they mask, and the share of the computed
    (query, key) pairs that the mask leaves.  (dK/dV walks the same
    square by its keys, `q_tiles`, in groups and tiles of the same
    sizes.)"""
    computed = masked = pairs = 0
    for r0 in range(0, s, block_q):
        (_, width, edge), (a, b, c) = k_tiles(
            r0, r0 % block_k, block_q, block_k, s // block_k, causal, window)
        computed += 1 + c - a
        masked += int(edge) + b - a
        pairs += block_q * (width + (c - a) * block_k)
    w = min(window or s, s)             # keys the last row sees
    needed = w * (w + 1) // 2 + (s - w) * w if causal else s * s
    return {"tiles_computed": computed, "tiles_masked": masked,
            "pairs_needed_share": needed / pairs}


def _mask(s, q0, k0, window, q_axis=0):
    """Mask a tile of logits whose first query is `q0` and first key `k0`
    (global positions; queries run along `q_axis`): a key after the query
    goes, and under a sliding `window` one that is `window` or more
    behind it."""
    rel = (jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
           - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis))
    ahead = k0 - q0                      # query - key = rel - ahead
    keep = rel >= ahead
    if window is not None:
        keep = keep & (rel < ahead + window)
    return jnp.where(keep, s, NEG_INF)


def _bd_mask(s, q0, k0, L, beta, q_axis=0):
    """Mask a tile of logits of a block-diffusion call (`bd_tile` has the
    rule) whose first row is `q0` and first key `k0` of the two copies.
    Both are multiples of `beta` and the tile lies in one copy each way,
    so a row's block starts `row - row % beta` into the tile's rows and
    the keys the row keeps are ONE stretch of the tile's: from its block's
    first token (noised keys) or from the tile's start (clean keys), to
    its block's end (its own copy's keys) or its start (a noised row's
    clean keys).  The block's start is found on a column of `block_q`
    numbers and met by the keys' iota: two compares a pair, as a
    window's."""
    shape = [1, 1]
    shape[q_axis] = s.shape[q_axis]
    row = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    key = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    noised_q, noised_k = q0 >= L, k0 >= L
    # the row's block's first token, counted in keys from the tile's first
    start = (row - jax.lax.rem(row, beta)
             + (q0 - jnp.where(noised_q, L, 0))
             - (k0 - jnp.where(noised_k, L, 0)))
    own_copy = jnp.logical_not(jnp.logical_xor(noised_q, noised_k))
    keep = ((key >= jnp.where(noised_k, start, 0))
            & (key < start + jnp.where(own_copy, beta, 0)))
    return jnp.where(keep, s, NEG_INF)


def k_band(qi, block_q, block_k, num_tiles, causal, window=None):
    """`(first, last)` tile of keys that the rows of block `qi` see, both
    inclusive and every tile between them live: up to the tile of the
    block's last row under a causal mask, from the tile of the oldest key
    its FIRST row still sees under a `window`.  `qi` a Python int or a
    traced index: the streaming kernels' table (`stream_table`), their
    band grid's index maps and `stream_schedule` all take their bounds
    from here (`q_band` is the same band seen from the keys)."""
    if not causal:
        return 0, num_tiles - 1
    last = ((qi + 1) * block_q - 1) // block_k
    if window is None:
        return 0, last
    return _most(qi * block_q - window + 1, 0) // block_k, last


def q_band(ki, block_q, block_k, num_tiles, causal, window=None):
    """`(first, last)` tile of query rows that see the keys of block
    `ki`: from the tile of the block's first key (the diagonal) to the
    tile of the last row that still sees its LAST key under a `window`,
    else to the end."""
    if not causal:
        return 0, num_tiles - 1
    first = ki * block_k // block_q
    if window is None:
        return first, num_tiles - 1
    return first, _least(((ki + 1) * block_k + window - 2) // block_q,
                         num_tiles - 1)


def _bands(s, block_q, block_k, causal, window, by_keys):
    """`(blocks, band)` of a streaming kernel: the blocks its programs
    own, of rows (`by_keys`, dK/dV: of keys), and `band(i)`, the `(first,
    last)` tile of the other axis that block `i` walks."""
    nq, nk = s // block_q, s // block_k
    band, blocks, tiles = (q_band, nk, nq) if by_keys else (k_band, nq, nk)
    return blocks, lambda i: band(i, block_q, block_k, tiles, causal, window)


# What the table says of an entry besides its block and its tile: the
# ends of its block's run and, under a block-diffusion mask, that the rule
# leaves the tile WHOLE.
FIRST, LAST, WHOLE = 1, 2, 4


def bd_tile(q0, k0, block_q, block_k, L, beta):
    """What a block-diffusion mask leaves of the tile of rows [q0, q0 +
    block_q) and keys [k0, k0 + block_k) of the two copies `[x ; x_t]`, L
    tokens each in blocks of `beta`: None nothing, 0 some of its pairs,
    WHOLE all of them.  The rule (BD3-LMs, arXiv:2503.09573, its
    vectorised training), for the block b of a row's token and c of a
    key's: a clean row sees the clean keys with c <= b; a noised row the
    clean keys with c < b and the noised keys with c == b, those after it
    too; nobody sees a noised key of another block.  A tile lies in one
    copy and holds whole blocks (`check_blocks`), so it is judged by the
    first and last block of its rows and of its keys."""
    rows = (q0 % L // beta, (q0 % L + block_q - 1) // beta)
    keys = (k0 % L // beta, (k0 % L + block_k - 1) // beta)
    if k0 >= L:                         # noised keys: their own block's
        if q0 < L or keys[0] > rows[1] or rows[0] > keys[1]:
            return None
        return WHOLE * (rows[0] == rows[1] == keys[0] == keys[1])
    ahead = int(q0 >= L)                # a noised row's own block is not seen
    if keys[0] + ahead > rows[1]:
        return None
    return WHOLE * (keys[1] + ahead <= rows[0])


def stream_table(s, block_q, block_k, causal, window=None, by_keys=False,
                 block_diffusion=None):
    """A streaming call's grid, one head of it: `(block, tile, flags)`,
    three equally long tuples with an entry for every LIVE tile and no
    other, in the order the kernel visits them.  Forward and dQ: row
    block by row block, each over its band of key tiles (`k_band`) first
    to last; `by_keys` (dK/dV): key block by key block, each over its
    band of row tiles (`q_band`).  `block` is the block of the axis the
    program owns, `tile` the tile of the axis it walks, `flags` FIRST and
    LAST on the ends of a block's run (a block of one tile carries
    both).

    Under a `block_diffusion` mask `(L, beta)` a block's live tiles are
    those `bd_tile` keeps, in rising order, and the run may have a GAP: a
    block of noised rows walks the clean tiles before its own and then
    its own tile of the noised copy, a block of clean keys the clean row
    tiles from its own on and then the noised row tiles from its own
    on; FIRST and LAST still mark the run's ends, and WHOLE the tiles no
    rule crosses (at 2 L = 32,768 in tiles of 512: 528 + 528 + 32 = 1,088
    entries, 992 of them whole, where the causal table has 2,080)."""
    blocks, band = _bands(s, block_q, block_k, causal, window, by_keys)
    block, tile, flags = [], [], []
    for i in range(blocks):
        if block_diffusion is None:
            first, last = band(i)
            live = [(j, 0) for j in range(first, last + 1)]
        else:
            live = []
            for j in range(s // (block_q if by_keys else block_k)):
                qi, ki = (j, i) if by_keys else (i, j)
                whole = bd_tile(qi * block_q, ki * block_k, block_q, block_k,
                                *block_diffusion)
                if whole is not None:
                    live.append((j, whole))
        for j, whole in live:
            block.append(i)
            tile.append(j)
            flags.append(whole | FIRST * (j == live[0][0])
                         | LAST * (j == live[-1][0]))
    return tuple(block), tuple(tile), tuple(flags)


# A call whose bands are all about as long walks them on a grid (bh, block,
# step) and asks no table: up to this many grid steps for each live tile.
# From the chip (PR 37, docs/performance.md "The streaming path"): an entry
# read from the table costs a step 0.05-0.07 us, a dead step 0.08-0.11; at
# the mellum cell's window of 1024 (192 steps for 189 tiles) the band grid
# is 3% faster, at its causal call (4,096 for 2,080) the table 7%.
BAND_GRID_SLACK = 1.25


class Walk(NamedTuple):
    """How a streaming call's grid visits its live tiles."""
    table: tuple                # `stream_table`'s three columns
    band: Optional[Callable]    # block -> (first, last) tile, on a band grid
    grid: tuple                 # the grid's axes after bh


def stream_walk(s, block_q, block_k, causal, window=None, by_keys=False,
                block_diffusion=None):
    """The `Walk` of a streaming call (`by_keys`: of its dK/dV kernel).
    Either the grid is (bh, entry of the table), every step a live tile;
    or, where blocks x the longest band is within BAND_GRID_SLACK of the
    live tiles (a window; no mask at all), it is (bh, block, step of the
    longest band): step `j` of block `i` is tile `first + j` of its band,
    computed and not looked up, and past the band's end the last tile
    again, which is not copied twice and computes nothing.  A
    `block_diffusion` call's runs have gaps and are 1 to `L / tile + 1`
    tiles long: the table."""
    table = stream_table(s, block_q, block_k, causal, window, by_keys,
                         block_diffusion)
    if block_diffusion is not None:
        return Walk(table, None, (len(table[0]),))
    blocks, band = _bands(s, block_q, block_k, causal, window, by_keys)
    longest = max(last - first + 1
                  for first, last in map(band, range(blocks)))
    if blocks * longest > BAND_GRID_SLACK * len(table[0]):
        return Walk(table, None, (len(table[0]),))
    return Walk(table, band, (blocks, longest))


def stream_schedule(s, block_q, block_k, causal, window=None,
                    block_diffusion=None):
    """What one head of a streaming call's forward (and dQ) kernel does,
    counted from the walk it really takes: the grid steps, how many of
    them compute (the table's entries: all, on a table grid), and the
    tiles of K (and as many of V) copied in: a step whose tile is the
    one before it copies nothing, on either grid.  Of a `block_diffusion`
    call also the tiles it leaves `whole` (unmasked) and the share of the
    computed (row, key) pairs that the rule needs, L^2 + L beta a head."""
    walk = stream_walk(s, block_q, block_k, causal, window,
                       block_diffusion=block_diffusion)
    tile = walk.table[1]
    fetched = sum(a != b for a, b in zip(tile, (None,) + tile))
    out = {"steps": math.prod(walk.grid), "live": len(tile),
           "fetched": fetched}
    if block_diffusion is not None:
        L, beta = block_diffusion
        out["whole"] = sum(f & WHOLE != 0 for f in walk.table[2])
        out["pairs_needed_share"] = L * (L + beta) / (
            len(tile) * block_q * block_k)
    return out


def _dot_nt(a, b):
    """a [m, d] . b [n, d]^T -> [m, n] float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_f32(p, x):
    """p [m, n] float32 . x [n, d] -> [m, d] float32.  The probabilities
    and dS stay float32 into their products, so `x` is upcast to meet
    them."""
    return jnp.dot(p, x.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


def _scaled(x, sm_scale):
    """Q (dK/dV: K) in float32 times the softmax's scale, once for all
    the tiles it meets: cheaper than scaling every tile of logits."""
    return x.astype(jnp.float32) * sm_scale


def _to_lanes(col):
    """A [rows, 1] column as `(offset, [128] vector along lanes)` pieces,
    the layout the log-sum-exp is stored in: 128 rows at a time, the
    column broadcast along lanes, all but the diagonal zeroed, and summed
    down the sublanes.  Exact, and a few passes of the vector unit over
    [128, 128], where Mosaic's own relayout of `col[:, 0]` took a quarter
    of a forward call at S = 1024."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1))
    return [(r, jnp.sum(jnp.where(eye, col[r:r + 128], 0.0), axis=0))
            for r in range(0, col.shape[0], 128)]


def _spread(x, width):
    """A per-row statistic as wide as what it meets, [rows, width].  A
    column [rows, 1] (the resident path's, a value a loop carries) is
    left to broadcast.  One kept REPLICATED along the 128 lanes, [rows,
    128] (the streaming path's, which lives in scratch between grid
    steps), is the same vregs over again."""
    if x.shape[1] == 1:
        return x
    reps = -(-width // x.shape[1])
    wide = jnp.tile(x, (1, reps)) if reps > 1 else x
    return wide if wide.shape[1] == width else wide[:, :width]


def _masked(s, edge, window, bd, q_axis=0):
    """The tile of logits `s` under the call's mask: `edge` None leaves it
    whole, else it is the tile's `(first query, first key)`, under the
    causal diagonal and a `window`, or under the block-diffusion rule of
    `bd` = (L, beta)."""
    if edge is None:
        return s
    if bd is not None:
        return _bd_mask(s, *edge, *bd, q_axis=q_axis)
    return _mask(s, *edge, window, q_axis=q_axis)


def _online_step(q_scaled, k, v, carry, edge, window=None, bd=None):
    """One online-softmax accumulation step shared by both forward paths;
    `carry` None starts one.  `edge` is None for a tile every row sees
    whole, else the tile's `(first query, first key)`, and the tile is
    masked (`_masked`).  The carry's `m` and `l` are columns or
    replicated along lanes (`_spread`), and come back as they came."""
    s = _masked(_dot_nt(q_scaled, k.astype(jnp.float32)),  # (bq, bk)
                edge, window, bd)
    m_new = jnp.max(s, axis=-1, keepdims=True)
    if carry is None:
        p = jnp.exp(s - m_new)
        return m_new, jnp.sum(p, axis=-1, keepdims=True), _dot_f32(p, v)
    m, l, acc = carry
    m_new = jnp.maximum(m, m_new)
    if edge is None or (window is None and bd is None):
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - _spread(m_new, s.shape[1]))
    else:
        # Under a window a row can meet a live block of which it sees
        # nothing before it has seen any key: its running max is still
        # -inf, and exp(-inf - -inf) would be NaN.  (Only the streaming
        # path meets this: a resident program starts each row at its own
        # key.  Causal alone never does: every row sees key 0.  Under a
        # block-diffusion mask the first block of the noised copy does,
        # whose rows see no clean key at all.)
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        alpha = jnp.exp(m - m_safe)
        p = jnp.exp(s - _spread(m_safe, s.shape[1]))
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    return m_new, l_new, acc * _spread(alpha, acc.shape[1]) + _dot_f32(p, v)


def _dq_step(q_scaled, k, v, do, lse, delta, edge, window=None, bd=None):
    """A tile's part of dQ, short of the softmax's scale."""
    s = _masked(_dot_nt(q_scaled, k.astype(jnp.float32)), edge, window, bd)
    p = jnp.exp(s - lse)                                 # (bq, bk)
    return _dot_f32(p * (_dot_nt(do, v) - delta), k)


def _dkv_step(q, k_scaled, v, do, lse, delta, edge, window=None, bd=None):
    """A tile's parts of dK (short of the softmax's scale) and dV,
    computed on the tile TRANSPOSED: keys down, queries across.  So `lse`
    and `delta` come as the lane rows [1, bq] they are stored as, and
    P^T dO and dS^T Q are plain products, where the tile the other way
    round turns two [bq] rows into columns and transposes P and dS, every
    turn of the loop."""
    st = _masked(_dot_nt(k_scaled, q.astype(jnp.float32)),  # (bk, bq)
                 edge, window, bd, q_axis=1)
    pt = jnp.exp(st - lse)
    dst = pt * (_dot_nt(v, do) - delta)
    return _dot_f32(dst, q), _dot_f32(pt, do)


# ---------------------------------------------------------------------------
# Resident path: K/V whole in VMEM.  A program takes its rows (dK/dV:
# keys) group by group, each group with its own region and its own bounds
# on the loop over the other axis.
# ---------------------------------------------------------------------------
# A short sequence wants small groups (fewer pairs computed above the
# causal diagonal), wide tiles (the softmax's per-row bookkeeping is paid
# once a tile whatever its width) and ONE program a head: a program that
# owns the whole axis has Python ints for bounds and is straight-line
# code, which at S = 1024 was worth more than either (PERF.md section 6,
# PR 35).  A longer sequence got nothing from fatter programs (S = 8192:
# 569.7 us a head at 2048 rows a program, 573.6 at 512) but their compile
# time, so there a program owns as little as it can.
PROGRAM_ROWS = 1024


def _program_rows(s, group, tile):
    """The rows one resident program owns: the whole sequence up to
    PROGRAM_ROWS, else the least multiple of both `group` and `tile` (so
    that where a group lies in its tile is static)."""
    return s if s <= PROGRAM_ROWS else math.lcm(group, tile)


def _groups(extent, s, group, tile):
    """`(slice in the program's block, first row, first row % tile)` of
    each group of a program's `extent` rows; the first row a Python int
    where one program owns the whole axis."""
    start = 0 if extent == s else pl.program_id(1) * extent
    return [(pl.ds(t * group, group), start + t * group, t * group % tile)
            for t in range(extent // group)]


def _walk(runs, body, carry):
    """`body(index, carry, masked)` over each `(lo, hi, masked)` run of
    tiles, traced here and now (a group's closures never outlive its turn
    of the Python loop); a run that is empty in Python leaves no loop
    behind."""
    for lo, hi, masked in runs:
        if isinstance(lo, int) and isinstance(hi, int) and lo >= hi:
            continue
        carry = jax.lax.fori_loop(
            lo, hi, functools.partial(body, masked=masked), carry)
    return carry


def _fwd_kernel_res(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale,
                    causal, block_q, block_k, seq_len, window=None):
    for rows, r0, at in _groups(q_ref.shape[1], seq_len, block_q, block_k):
        q = _scaled(q_ref[0, rows, :], sm_scale)         # (bq, d)
        (start, width, edge), (a, b, c) = k_tiles(
            r0, at, block_q, block_k, seq_len // block_k, causal, window)

        def tile(k0, width, carry, masked):
            keys = pl.ds(k0, width)
            return _online_step(q, k_ref[0, keys, :], v_ref[0, keys, :],
                                carry, (r0, k0) if masked else None, window)

        def body(kb, carry, masked):
            return tile(kb * block_k, block_k, carry, masked)

        m, l, acc = _walk([(a, b, True), (b, c, False)], body,
                          tile(start, width, None, edge))
        o_ref[0, rows, :] = (acc * (1.0 / l)).astype(o_ref.dtype)
        # Layout (BH, 1, S): TPU block tiling needs the last two dims to
        # be (1, block) with both tile-divisible or dim-equal.
        for r, piece in _to_lanes(m + jnp.log(l)):
            lse_ref[0, 0, pl.ds(rows.start + r, 128)] = piece


def _dq_kernel_res(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, sm_scale, causal, block_q, block_k, seq_len,
                   window=None):
    for rows, r0, at in _groups(q_ref.shape[1], seq_len, block_q, block_k):
        q, do = _scaled(q_ref[0, rows, :], sm_scale), do_ref[0, rows, :]
        lse = lse_ref[0, 0, rows][:, None]
        delta = delta_ref[0, 0, rows][:, None]
        (start, width, edge), (a, b, c) = k_tiles(
            r0, at, block_q, block_k, seq_len // block_k, causal, window)

        def tile(k0, width, masked):
            keys = pl.ds(k0, width)
            return _dq_step(q, k_ref[0, keys, :], v_ref[0, keys, :], do,
                            lse, delta, (r0, k0) if masked else None, window)

        def body(kb, dq, masked):
            return dq + tile(kb * block_k, block_k, masked)

        dq = _walk([(a, b, True), (b, c, False)], body,
                   tile(start, width, edge))
        dq_ref[0, rows, :] = (sm_scale * dq).astype(dq_ref.dtype)


def _dkv_kernel_res(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, sm_scale, causal, block_q, block_k,
                    seq_len, window=None):
    # the keys in groups of block_q, the rows in tiles of block_k: the
    # forward's square, walked the other way
    rows_tile = dkv_tile(block_k)
    for keys, c0, at in _groups(k_ref.shape[1], seq_len, block_q, rows_tile):
        k, v = _scaled(k_ref[0, keys, :], sm_scale), v_ref[0, keys, :]
        (start, height, edge), (b, c, d) = q_tiles(
            c0, at, block_q, rows_tile, seq_len // rows_tile, causal, window)

        def tile(q0, height, masked):
            rows = pl.ds(q0, height)
            return _dkv_step(q_ref[0, rows, :], k, v, do_ref[0, rows, :],
                             lse_ref[0, :, rows], delta_ref[0, :, rows],
                             (q0, c0) if masked else None, window)

        def body(qb, carry, masked):
            dk, dv = tile(qb * rows_tile, rows_tile, masked)
            return carry[0] + dk, carry[1] + dv

        dk, dv = _walk([(b, c, False), (c, d, True)], body,
                       tile(start, height, edge))
        dk_ref[0, keys, :] = (sm_scale * dk).astype(dk_ref.dtype)
        dv_ref[0, keys, :] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Streaming path: grid (bh, entry of the table), scratch carries a block.
# ---------------------------------------------------------------------------
def _edge(causal, q0, k0):
    """Every tile of a causal streaming call is masked, the whole ones
    too.  A step that asked the table whether an edge crosses its tile
    and took one of two `pl.when` branches cost a causal forward call
    0.05 us a tile more than it saved (PERF.md section 6, PR 37: a mask
    costs nothing measurable, a branch around the step does)."""
    return (q0, k0) if causal else None


def _steps(live, flags_ref, bd, step):
    """Run `step(masked)` where the grid step computes.  A causal or
    windowed call masks every tile it computes or none (`_edge`), and a
    step past its band's end computes nothing (`live`).  A
    block-diffusion call asks the table: a tile it marks WHOLE is not
    masked, the others are (`_bd_mask`: two compares and a select a pair,
    on 96 of 2 L = 32,768's 1,088 tiles)."""
    if bd is None:
        return _when(live, step)
    whole = flags_ref[pl.program_id(1)] & WHOLE != 0
    pl.when(whole)(lambda: step(False))
    pl.when(jnp.logical_not(whole))(lambda: step(True))


def _entry(table, band):
    """`(block, tile, first, last, live)` of the grid step a streaming
    kernel is at: the block it owns, the tile it walks, whether the step
    is the first / the last of the block, and whether it computes (None:
    every step does).  From the table (its scalar-prefetch operands) on a
    table grid; on a band grid (`band` given) from the step's place in
    its block's band."""
    if band is None:
        t = pl.program_id(1)
        block, tile, flags = (ref[t] for ref in table)
        return block, tile, flags & FIRST != 0, flags & LAST != 0, None
    i, j = pl.program_id(1), pl.program_id(2)
    first, last = band(i)
    return (i, first + j, j == 0, j == pl.num_programs(2) - 1,
            first + j <= last)


def _when(live, step):
    """Run `step` where the grid step computes (`_entry`'s `live`)."""
    if live is None:
        step()
    else:
        pl.when(live)(step)


def _fwd_kernel_str(block_ref, tile_ref, flags_ref, q_ref, k_ref, v_ref,
                    o_ref, lse_ref, m_scr, l_scr, acc_scr, *, sm_scale,
                    causal, block_q, block_k, band, window=None, bd=None):
    qi, kb, first, last, live = _entry((block_ref, tile_ref, flags_ref),
                                       band)

    @pl.when(first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _step(masked=causal):
        # m and l live in scratch REPLICATED along lanes, [block_q, 128]:
        # as [block_q, 1] columns their way out of scratch and back took
        # 0.8 us of a tile's 1.9 (PERF.md section 6, PR 37)
        m, l, acc = _online_step(
            _scaled(q_ref[0], sm_scale), k_ref[0], v_ref[0],
            (m_scr[:], l_scr[:], acc_scr[:]),
            _edge(masked, qi * block_q, kb * block_k), window, bd)
        m_scr[:], l_scr[:], acc_scr[:] = m, l, acc

    _steps(live, flags_ref, bd, _step)

    @pl.when(last)
    def _finish():
        l = l_scr[:]
        o_ref[0] = (acc_scr[:] / _spread(l, acc_scr.shape[1])).astype(
            o_ref.dtype)
        for r, piece in _to_lanes(m_scr[:] + jnp.log(l)):
            lse_ref[0, 0, pl.ds(r, 128)] = piece


def _dq_kernel_str(block_ref, tile_ref, flags_ref, q_ref, k_ref, v_ref,
                   do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *, sm_scale,
                   causal, block_q, block_k, band, window=None, bd=None):
    qi, kb, first, last, live = _entry((block_ref, tile_ref, flags_ref),
                                       band)

    @pl.when(first)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _step(masked=causal):
        dq_scr[:] = dq_scr[:] + _dq_step(
            _scaled(q_ref[0], sm_scale), k_ref[0], v_ref[0], do_ref[0],
            lse_ref[0, 0, :][:, None], delta_ref[0, 0, :][:, None],
            _edge(masked, qi * block_q, kb * block_k), window, bd)

    _steps(live, flags_ref, bd, _step)

    @pl.when(last)
    def _finish():
        dq_ref[0] = (sm_scale * dq_scr[:]).astype(dq_ref.dtype)


def _dkv_kernel_str(block_ref, tile_ref, flags_ref, q_ref, k_ref, v_ref,
                    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr,
                    dv_scr, *, sm_scale, causal, block_q, block_k, band,
                    window=None, bd=None):
    ki, qb, first, last, live = _entry((block_ref, tile_ref, flags_ref),
                                       band)

    @pl.when(first)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _step(masked=causal):
        dk_i, dv_i = _dkv_step(
            q_ref[0], _scaled(k_ref[0], sm_scale), v_ref[0], do_ref[0],
            lse_ref[0], delta_ref[0],
            _edge(masked, qb * block_q, ki * block_k), window, bd)
        dk_scr[:] = dk_scr[:] + dk_i
        dv_scr[:] = dv_scr[:] + dv_i

    _steps(live, flags_ref, bd, _step)

    @pl.when(last)
    def _finish():
        dk_ref[0] = (sm_scale * dk_scr[:]).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call builders
# ---------------------------------------------------------------------------
def _q_spec(block_q, d):
    return pl.BlockSpec((1, block_q, d), lambda b, i, *_: (b, i, 0))


def _lse_spec(block_q):
    return pl.BlockSpec((1, 1, block_q), lambda b, i, *_: (b, 0, i))


def _whole_spec(s, d):
    """A head's whole [S, d] operand, resident for every program of it."""
    return pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0))


def _walk_specs(walk, rows, d, column):
    """`(wide, lanes)` BlockSpecs over a streaming call's grid: blocks of
    `rows` of a [BH, S, D] operand and of a per-row statistic [BH, 1, S],
    for `column` 0 the block the program owns, 1 the tile it walks.  On a
    table grid (bh, entry) the index is the table's, read from the
    scalar-prefetch operands; on a band grid (bh, block, step) it is the
    block, or the band's tile at the step and past its end the last one
    again.  Steps that follow each other with the same index copy
    nothing."""
    if walk.band is None:
        def at(t, *table):
            return table[column][t]
    elif column == 0:
        def at(i, j, *_):
            return i
    else:
        def at(i, j, *_):
            first, last = walk.band(i)
            return jnp.minimum(first + j, last)
    return (pl.BlockSpec((1, rows, d), lambda b, *step: (b, at(*step), 0)),
            pl.BlockSpec((1, 1, rows), lambda b, *step: (b, 0, at(*step))))


def _walk_call(kernel, walk, bh, in_specs, out_specs, scratch_shapes,
               **kwargs):
    """`pallas_call` of a streaming kernel over the grid (bh, *walk.grid),
    the table's columns its scalar-prefetch operands: on a table grid
    read by the index maps before a step's copies start, and by the
    kernel; on a band grid by nobody."""
    columns = [jnp.asarray(column, jnp.int32) for column in walk.table]
    call = pl.pallas_call(
        functools.partial(kernel, band=walk.band),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(columns), grid=(bh, *walk.grid),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        **kwargs)
    return functools.partial(call, *columns)


def _windowed(window, kind, dk, dv, bd=None):
    """The extra keywords of a call that is not the plain one, for the
    kernel and for `pallas_call`: the window or the block-diffusion mask,
    and a name that says the kind of call, the window (`flash_fwd_w2048`),
    the block-diffusion mask's block length (`flash_fwd_bd4`) and, where
    the keys' width `dk` is not the values' `dv`, both
    (`flash_fwd_d192x128`), which is how a device trace tells a sliding
    layer's calls from a full layer's and a latent-attention or a
    block-diffusion call from either.  Nothing for `window=None`, no such
    mask and one width, which leaves those calls exactly as they were."""
    kw = {} if window is None else {"window": window}
    name = f"flash_{kind}"
    if dk != dv:
        name += f"_d{dk}x{dv}"
    if window is not None:
        name += f"_w{window}"
    if bd is not None:
        kw["bd"] = bd
        name += f"_bd{bd[1]}"
    return kw, ({} if name == f"flash_{kind}" else {"name": name})


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, streaming,
         window=None, bd=None):
    bh, s, d = q.shape
    dv = v.shape[2]             # o is as wide as v; q and k share `d`
    kw, named = _windowed(window, "fwd", d, dv, bd)
    out_shape = [jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
                 jax.ShapeDtypeStruct((bh, 1, s), jnp.float32)]
    if streaming:
        walk = stream_walk(s, block_q, block_k, causal, window,
                           block_diffusion=bd)
        rows, rows_lanes = _walk_specs(walk, block_q, d, 0)
        rows_v, _ = _walk_specs(walk, block_q, dv, 0)
        keys, _ = _walk_specs(walk, block_k, d, 1)
        keys_v, _ = _walk_specs(walk, block_k, dv, 1)
        return _walk_call(
            functools.partial(_fwd_kernel_str, sm_scale=sm_scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k, **kw),
            walk, bh, in_specs=[rows, keys, keys_v],
            out_specs=[rows_v, rows_lanes],
            scratch_shapes=[
                pltpu.VMEM((block_q, 128), jnp.float32),  # running max m
                pltpu.VMEM((block_q, 128), jnp.float32),  # running sum l
                pltpu.VMEM((block_q, dv), jnp.float32),  # accumulator
            ],
            out_shape=out_shape, interpret=interpret, **named,
        )(q, k, v)
    rows = _program_rows(s, block_q, block_k)
    return pl.pallas_call(
        functools.partial(_fwd_kernel_res, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=s, **kw),
        grid=(bh, s // rows),
        in_specs=[_q_spec(rows, d), _whole_spec(s, d), _whole_spec(s, dv)],
        out_specs=[_q_spec(rows, dv), _lse_spec(rows)],
        out_shape=out_shape,
        interpret=interpret, **named,
    )(q, k, v)


def _bwd(sm_scale, causal, block_q, block_k, interpret, streaming,
         residuals, g, window=None, bd=None):
    q, k, v, o, lse = residuals
    do = g
    bh, s, d = q.shape
    dv = v.shape[2]             # v, o, do and dV; q, k, dQ and dK are `d`
    kw, dq_named = _windowed(window, "dq", d, dv, bd)
    dkv_named = _windowed(window, "dkv", d, dv, bd)[1]
    # delta_i = rowsum(dO_i * O_i): tiny elementwise pass, XLA fuses it.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]                 # (bh, 1, s)
    if streaming:
        walk = stream_walk(s, block_q, block_k, causal, window,
                           block_diffusion=bd)
        rows, rows_lanes = _walk_specs(walk, block_q, d, 0)
        rows_v, _ = _walk_specs(walk, block_q, dv, 0)
        keys, _ = _walk_specs(walk, block_k, d, 1)
        keys_v, _ = _walk_specs(walk, block_k, dv, 1)
        dq = _walk_call(
            functools.partial(_dq_kernel_str, sm_scale=sm_scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k, **kw),
            walk, bh,
            in_specs=[rows, keys, keys_v, rows_v, rows_lanes, rows_lanes],
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            interpret=interpret, **dq_named,
        )(q, k, v, do, lse, delta)
        # the same square by its keys: a program owns a block of keys
        walk = stream_walk(s, block_q, block_k, causal, window, by_keys=True,
                           block_diffusion=bd)
        keys, _ = _walk_specs(walk, block_k, d, 0)
        keys_v, _ = _walk_specs(walk, block_k, dv, 0)
        rows, rows_lanes = _walk_specs(walk, block_q, d, 1)
        rows_v, _ = _walk_specs(walk, block_q, dv, 1)
        dk, dv_ = _walk_call(
            functools.partial(_dkv_kernel_str, sm_scale=sm_scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k, **kw),
            walk, bh,
            in_specs=[rows, keys, keys_v, rows_v, rows_lanes, rows_lanes],
            out_specs=[keys, keys_v],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, dv), jnp.float32)],
            out_shape=[jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                       jax.ShapeDtypeStruct((bh, s, dv), v.dtype)],
            interpret=interpret, **dkv_named,
        )(q, k, v, do, lse, delta)
        return dq, dk, dv_

    full_lse2 = pl.BlockSpec((1, 1, s), lambda b, i: (b, 0, 0))
    rows = _program_rows(s, block_q, block_k)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel_res, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=s, **kw),
        grid=(bh, s // rows),
        in_specs=[_q_spec(rows, d), _whole_spec(s, d), _whole_spec(s, dv),
                  _q_spec(rows, dv), _lse_spec(rows), _lse_spec(rows)],
        out_specs=_q_spec(rows, d),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=interpret, **dq_named,
    )(q, k, v, do, lse, delta)
    keys = _program_rows(s, block_q, dkv_tile(block_k))
    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel_res, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=s, **kw),
        grid=(bh, s // keys),
        in_specs=[_whole_spec(s, d), _q_spec(keys, d), _q_spec(keys, dv),
                  _whole_spec(s, dv), full_lse2, full_lse2],
        out_specs=[_q_spec(keys, d), _q_spec(keys, dv)],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, dv), v.dtype)],
        interpret=interpret, **dkv_named,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv_


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None,
                    streaming: Optional[bool] = None,
                    window: Optional[int] = None,
                    block_diffusion: Optional[tuple] = None) -> jax.Array:
    """Blockwise (flash) attention.  q, k: [BH, S, Dk]; v: [BH, S, Dv]
    -> [BH, S, Dv].  One width, Dk = Dv, is the call every model but one
    makes, and compiles to what it always did.  Where the two differ
    (latent attention: a query and a key are a 128-wide part and a 64-wide
    rotary part side by side, 192, a value 128) every operand keeps its
    own width in HBM, a tile's QK product is one of depth Dk, its PV
    product one of width Dv, and the three kernels carry both widths in
    their names (`flash_fwd_d192x128`).

    `block_q` is the rows of a group (in dK/dV: the keys of one), each
    with its own bounds, and sets what is computed above a causal
    diagonal; `block_k` is the tile of the other axis that a group walks
    (models/transformer.py `flash_auto_tiles` is the rule).

    `window` (causal only) is a sliding window: row i attends to the keys
    i - window < j <= i, and the blocks no row of a tile can see are
    skipped in all three kernels, as the blocks above the diagonal are
    (resident: never looped over; streaming: not in the grid).
    `window=None` leaves the calls unnamed.

    `block_diffusion` = `(L, beta)` (neither causal nor windowed) is the
    mask block-diffusion training lays over the two copies of a sequence,
    `[x ; x_t]`, S = 2 L rows in blocks of `beta` tokens (`bd_tile` has
    the rule): L^2 + L beta pairs a head of the square's 4 L^2.  The mask
    is never an array: the live tiles are the table's (`stream_table`,
    by rows and by keys, a block's run with a gap in it), a tile's mask
    is computed in the kernel from its first row, its first key and `(L,
    beta)`, and a tile the rule leaves whole is not masked.  Such a call
    ALWAYS takes the streaming walk, within the resident budget too
    (`streaming` says nothing here): a resident program's loop bounds
    are one band's, and a second set of them for two runs a group would
    serve the tests' sizes alone.  The three kernels carry the block
    length in their names (`flash_fwd_bd4`).

    sm_scale defaults to 1/sqrt(Dk).  interpret=None auto-selects the
    Pallas interpreter off-TPU so tests run on the CPU mesh.
    streaming=None auto-selects: K/V-resident kernels while S*(Dk+Dv) fits
    the VMEM budget (fastest — K/V fetched once per batch*head), 3D-grid
    streaming kernels beyond (O(block*D) VMEM at any S).
    """
    out, _ = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                        interpret, streaming, window, block_diffusion)
    return out


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
               streaming, window=None, block_diffusion=None):
    bh, s, d = q.shape
    check_blocks(s, block_q, block_k, block_diffusion)
    if k.shape != q.shape or v.shape[:2] != q.shape[:2]:
        raise ValueError(f"flash attention: q {q.shape}, k {k.shape}, v "
                         f"{v.shape}: q and k share a shape, v their "
                         f"first two dims")
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window={window} needs causal=True and at least "
                         f"one key a row")
    if block_diffusion is not None and (causal or window is not None):
        raise ValueError(f"block_diffusion={block_diffusion} is a mask of "
                         f"its own: neither causal nor windowed")
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    streaming = _use_streaming(k, streaming, v, block_diffusion)
    if block_diffusion is not None:
        telemetry.record_static(
            "flash_block_diffusion",
            **stream_schedule(s, block_q, block_k, False,
                              block_diffusion=block_diffusion))
    elif streaming:
        telemetry.record_static(
            "flash_stream",
            labels={"window": "none" if window is None else str(window)},
            **stream_schedule(s, block_q, block_k, causal, window))
    else:
        telemetry.record_static(
            "flash_tiles",
            **tile_schedule(s, block_q, block_k, causal, window))
    out, lse = (checkpoint_name(t, KEPT_NAME) for t in _fwd(
        q, k, v, scale, causal, block_q, block_k, _use_interpret(interpret),
        streaming, window, block_diffusion))
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, streaming,
               window, block_diffusion, residuals, g):
    d = residuals[0].shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    k, v = residuals[1:3]
    return _bwd(scale, causal, block_q, block_k, _use_interpret(interpret),
                _use_streaming(k, streaming, v, block_diffusion), residuals,
                g, window, block_diffusion)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
