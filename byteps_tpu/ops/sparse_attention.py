"""Attention over the keys a learned indexer picks, as Pallas TPU kernels.

A layer of this kind has a second, cheaper set of heads that reads the
layer's input and decides what the main heads see.  For query row t and
key s <= t the INDEX SCORE is

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])         j < J heads

(one indexer key head, J indexer query heads of a small size, a learned
weight a row and head), and row t attends to S_t, the min(t + 1, topk)
keys s <= t with the highest score: ONE set a row, shared by every main
head.  Which keys those are is DATA, other ones for every row and every
step, so no schedule of live tiles can be made in Python as
`flash_attention.stream_table` makes a window's.

How S_t is realised here: as a THRESHOLD a row, flash tiles whose
forward kernel recomputes the index scores and masks by it, and backward
kernels that read the forward kernel's mask.

  - `select` (kernel `index_topk`) takes a block of rows, computes their
    scores against every key up to the diagonal tile by tile into VMEM
    scratch, as sortable integers, and finds each row's topk-th largest
    EXACTLY by bisection on the integer's bits: 32 counting passes over
    the scratch, none over HBM.  Equal scores are taken lowest key first,
    as `lax.top_k` and a stable sort take them: the row's `cut` is the
    last key that is taken AT the threshold.  The bisection carries the
    counts it makes, so it ends knowing how many keys reach the threshold
    and how many lie above it, and a row has a tie to BREAK only where
    more reach it than the row wants.  A block with such a row places
    every row's cut by a second bisection, on the position (15 more
    passes at 32,768 keys); every other block takes all its rows' keys at
    the threshold, the cut is the last of them, and one pass finds it.
    How many passes a block ran is data: lane J + 2 of its rows
    (`select_passes`; `select_pass_counts` gives the two numbers).  The
    [S, S] scores are never in memory: a block's [rows, S] slab lives in
    VMEM and leaves it as two numbers a row, `tau` and `cut`.
  - `sparse_attention` (kernels `sparse_fwd`, `sparse_dq`, `sparse_dkv`)
    is streaming flash attention over the table of causal tiles, all the
    main heads of a tile in one grid step, the query heads of a key-value
    head stacked as rows of one product, every head's online softmax
    under one mask.  WHO COMPUTES THE SCORES: `sparse_fwd` alone.  Its
    step computes the tile's index scores once, by the same function on
    the same operands as `select` did, so bit for bit the same numbers,
    and keeps the pairs `s <= t and (I > tau or (I == tau and s <=
    cut))`.  It counts the pairs it kept a row (`count`): exactly
    min(t + 1, topk) where select and attention agree.  And it writes the
    mask out, a bit a pair: `bits` [B, S, 128 * ceil(S / 4096)] int32,
    bit b of word [t, c * 128 + lane] says whether row t takes key
    c * 4096 + b * 128 + lane.  A tile of `block_k` keys is then
    `block_k / 128` bits of ONE [block_q, 128] block of words, put in and
    taken out by a shift a 128-lane piece with no move across lanes; the
    forward kernel revisits the block over the 4096 / `block_k` tiles
    that share it, as it does its accumulators.  Words past a row block's
    diagonal are never written and never read.  `sparse_dq` and
    `sparse_dkv` READ THE BITS: no index product, no threshold, no
    position (the causal mask is in the bits); `sparse_dkv`, whose tile
    is keys down and rows across, turns the unpacked tile (turning the
    words first, a fourth of it, measured the same).
  - `keep_mask` (kernel `sparse_keep`) writes the same mask out as int8,
    for the tests and for the reference check, which hands the plain
    reference the program's choice.

The backward pass holds the selection constant: `sparse_attention` is a
`custom_vjp` whose cotangents are those of q, k and v alone; the indexer's
operands get zeros.  What a layer rematerialised under the policy
"selection" (`models/afmoe.py` `_remat`) KEEPS from its forward pass, by
name: `aux` (`SELECTION_NAME`), [B, S, 128] float32 (the weights, `tau`,
`cut`), 16 MB a layer at 32,768 rows, so the layer does not select a
second time; and what the attention call made (`ATTENTION_NAME`): `o`,
`lse` and `bits`, the `custom_vjp`'s residuals, `o` the call's result as
well, so ONE `o` a layer.  The recomputed layer then has no consumer of
`sparse_fwd` left and does not call it: the kernel runs once a layer and
step, and its two backward kernels read what that one call wrote.  Kept a
sequence and layer: S x (H x D x 2 + H x 4 + S / 8) bytes for bfloat16
heads (S / 8 rounded up to 512 a 4,096 keys): 268 + 4 + 134 = 407 MB at
32,768 rows of 32 heads of 128, in the layer scan's stacks from the
forward pass to the backward pass (`bps_sparse_kept_bytes`).  A job whose
stage holds more layers than its memory has room for picks "none", which
keeps nothing and runs selection and forward kernel twice.

A mask over dense tiles does the causal triangle's work whatever topk is:
no tile of a random model's selection is empty.  The work the SELECTION
leaves is what `benchmark/reduce/sparse_cost.py` counts, so this
realisation reads low against its roofline: about 12% at most, the
selected share of the pairs.  A kernel that gathers the chosen keys feeds
a product the rows of ONE selection, the query heads of a key-value head,
8 on an array 128 deep, and pays only where a row selects under 1 / 16 of
its keys (ROADMAP.md R12).

Layouts: q [B, H, S, D]; k, v [B, Hkv, S, D] (NOT repeated over the query
heads: head h reads key-value head h // (H / Hkv)); qI [B, J, S, Di];
kI [B, S, Di]; w [B, S, J].  `block_q` and `block_k` must divide S and be
multiples of 128.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import telemetry
from . import flash_attention
from .flash_attention import (FIRST, LAST, NEG_INF, _dot_f32, _dot_nt,
                              _scaled, _spread, _to_lanes, stream_table)

# `aux`'s lanes: the J weights of a row, then its threshold, its cut and
# the passes its block of `select` ran.
AUX_LANES = 128
# What a kernel may take of a v5e's 128 MiB of VMEM.
VMEM_LIMIT = 96 * 1024 * 1024
# `select`'s slab of sortable scores, [rows, S] int32, is held to this.
SELECT_SLAB_BYTES = 16 * 1024 * 1024
INT_MIN = -2 ** 31
# `bits`: a word holds a row's mask over 32 pieces of 128 keys, one bit a
# piece, so 128 lanes of words cover this many keys of the row.
WORD_KEYS = 32 * 128
# The names a call's results carry for `jax.checkpoint`: a layer
# rematerialised under `save_only_these_names(*KEPT_NAMES)`
# (`models/afmoe.py` `_remat`, policy "selection") keeps `aux` and does not
# select a second time, and keeps the forward kernel's `o`, `lse` and
# `bits` and does not call it a second time.  Two names, for what two
# kernels made: a policy of a user's own can keep the selection's 16 MB
# without the attention's 407 (at 32,768 rows).
SELECTION_NAME = "sparse.selection"
ATTENTION_NAME = "sparse.attention"
KEPT_NAMES = (SELECTION_NAME, ATTENTION_NAME)


def _use_interpret(interpret: Optional[bool]) -> bool:
    """The flash kernels' rule, asked of their module at the call: what
    steers it there (the tests that compile for a described chip) steers
    these kernels too."""
    return flash_attention._use_interpret(interpret)


def check_blocks(s: int, block_q: int, block_k: int) -> None:
    if (block_q <= 0 or block_k <= 0 or s % block_q or s % block_k
            or block_q % 128 or block_k % 128):
        raise ValueError(
            f"sparse attention cannot tile seq_len {s} with "
            f"block_q={block_q}, block_k={block_k}: both must divide the "
            f"sequence and be multiples of 128")


def auto_blocks(s: int):
    """`(block_q, block_k)`: 128 rows (times the query heads of a
    key-value head, stacked: 1,024 rows a product at 8) against the widest
    of 512 / 256 / 128 keys that divides S; (0, 0) where none does."""
    if s % 128:
        return 0, 0
    return 128, next(b for b in (512, 256, 128) if s % b == 0)


def selected_pairs(s: int, topk: int) -> int:
    """Sum over the rows of min(t + 1, topk)."""
    full = min(topk, s)
    return full * (full + 1) // 2 + (s - full) * full


# ---------------------------------------------------------------------------
# What every kernel computes of a tile, by the same code
# ---------------------------------------------------------------------------
def _index_tile(qi, kit, aux, heads):
    """The index scores of a tile, [rows, keys] float32: qi [J, rows, Di],
    kit [Di, keys] (the indexer's keys TRANSPOSED, so the product is
    plain), aux [rows, AUX_LANES] with the row's weights in its first J
    lanes.  Products in the operands' precision, sums in float32, heads
    in order.  A sum of zeros is made +0: -0 and +0 are one score."""
    acc = None
    for j in range(heads):
        s = jnp.dot(qi[j], kit, preferred_element_type=jnp.float32)
        term = aux[:, j:j + 1] * jnp.maximum(s, 0.0)
        acc = term if acc is None else acc + term
    return jnp.where(acc == 0.0, 0.0, acc)


def _positions(shape, q0, k0):
    return (q0 + lax.broadcasted_iota(jnp.int32, shape, 0),
            k0 + lax.broadcasted_iota(jnp.int32, shape, 1))


def _keep(scores, aux, heads, q0, k0):
    """The tile's mask: causal, and above the row's threshold or at it up
    to the row's cut."""
    rows, cols = _positions(scores.shape, q0, k0)
    tau, cut = aux[:, heads:heads + 1], aux[:, heads + 1:heads + 2]
    chosen = (scores > tau) | ((scores == tau)
                               & (cols.astype(jnp.float32) <= cut))
    return (cols <= rows) & chosen


def _tile_keep(qi_ref, kit_ref, aux_ref, heads, q0, k0):
    aux = aux_ref[0]
    return _keep(_index_tile(qi_ref[0], kit_ref[0], aux, heads), aux, heads,
                 q0, k0)


def _tile_bias(qi_ref, kit_ref, aux_ref, heads, q0, k0):
    """What an attention kernel adds to a tile of logits, [rows, keys]
    float32: 0 where the row takes the key, -inf where it does not."""
    return jnp.where(_tile_keep(qi_ref, kit_ref, aux_ref, heads, q0, k0),
                     0.0, NEG_INF)


def _lane_sums(x):
    """[rows, n * 128] -> [rows, 128]: the 128-lane pieces added, which
    costs the vector unit an add a piece and no move across lanes."""
    return functools.reduce(
        jnp.add, [x[:, c:c + 128] for c in range(0, x.shape[1], 128)])


def words(s: int) -> int:
    """The width of a row of `bits`: 128 words for every WORD_KEYS keys."""
    return 128 * -(-s // WORD_KEYS)


def _first_bit(k0):
    """Where in its words the tile of keys from `k0` on begins."""
    return k0 % WORD_KEYS // 128


def _pack(keep, bit):
    """A tile's mask [rows, n * 128] bool as [rows, 128] int32: the n-th
    128-lane piece at bit `bit` + n, the other bits 0.  A shift and an OR
    a piece, and no move across lanes."""
    return functools.reduce(jnp.bitwise_or, [
        jnp.left_shift(keep[:, c:c + 128].astype(jnp.int32), bit + c // 128)
        for c in range(0, keep.shape[1], 128)])


def _unpack(word, bit, pieces):
    """`_pack` undone, as the pieces one by one: [rows, 128] bool each."""
    return [jnp.right_shift(word, bit + n) & 1 != 0 for n in range(pieces)]


def _sortable(x):
    """float32 -> int32 whose order as integers is the floats' order."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits)


def _unsortable(key):
    bits = jnp.where(key < 0, key ^ jnp.int32(0x7fffffff), key)
    return lax.bitcast_convert_type(bits, jnp.float32)


# ---------------------------------------------------------------------------
# Selection: the threshold and the cut of every row
# ---------------------------------------------------------------------------
def _select_kernel(qi_ref, kit_ref, w_ref, aux_ref, keys_scr, *, heads, topk,
                   rows, block_k, seq_len):
    q0 = pl.program_id(1) * rows
    tiles = (q0 + rows - 1) // block_k + 1          # up to the diagonal's
    qi, w = qi_ref[0], w_ref[0]

    def at(j):
        return pl.ds(pl.multiple_of(j * block_k, block_k), block_k)

    def fill(j, carry):
        scores = _index_tile(qi, kit_ref[0, :, at(j)], w, heads)
        t, s = _positions(scores.shape, q0, j * block_k)
        keys_scr[:, at(j)] = _sortable(jnp.where(s <= t, scores, NEG_INF))
        return carry
    lax.fori_loop(0, tiles, fill, 0)

    def sweep(step, start):
        """One pass over the slab: `step(acc, sortable, first key)` on one
        128-lane piece after another, `acc` [rows, 128] from `start`."""
        def body(j, acc):
            keys = keys_scr[:, at(j)]
            for c in range(0, block_k, 128):
                acc = step(acc, keys[:, c:c + 128], j * block_k + c)
            return acc
        return lax.fori_loop(0, tiles, body,
                             jnp.full((rows, 128), start, jnp.int32))

    def count(test):
        """How many of a row's keys pass `test(sortable, first key)`."""
        acc = sweep(lambda acc, k, k0: acc + test(k, k0).astype(jnp.int32), 0)
        return jnp.sum(acc, axis=1, keepdims=True)

    def wide(x):
        """A number a row over the lanes of a piece, once a pass and not
        once a compare (3 ms of a call's 29 at 32,768 rows)."""
        return jnp.broadcast_to(x, (rows, 128))

    t = q0 + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    want = jnp.minimum(t + 1, topk)

    # The want-th largest sortable, bit by bit from the sign down (the
    # first candidate is INT_MIN + 2 ** 31, which wraps to 0): `low` is
    # always a value that `want` keys reach, and ends as the largest.  The
    # counts are carried along: `reach` is count(k >= low), what the last
    # candidate taken passed on, and `above` what the last candidate
    # refused was refused on.  That candidate is `low + 1` when the loop
    # ends, so `above` ends as count(k > low) with no pass of its own.
    def bit(i, carry):
        low, reach, above = carry
        cand = low + jnp.left_shift(jnp.int32(1), 31 - i)
        spread = wide(cand)
        some = count(lambda k, _: k >= spread)
        taken = some >= want
        return (jnp.where(taken, cand, low), jnp.where(taken, some, reach),
                jnp.where(taken, above, some))
    low, reach, above = lax.fori_loop(0, 32, bit, (
        jnp.full((rows, 1), INT_MIN, jnp.int32),
        jnp.full((rows, 1), tiles * block_k, jnp.int32),
        jnp.zeros((rows, 1), jnp.int32)))
    # Of the keys AT the threshold the first `need` are taken: `cut` is
    # the largest position with fewer than `need` of them before it.
    need = want - above
    at_low = wide(low)

    def place(i, cut):
        cand = cut + jnp.left_shift(jnp.int32(1), _cut_bits(seq_len) - 1 - i)
        spread = wide(cand)

        def before(k, k0):
            s = k0 + lax.broadcasted_iota(jnp.int32, k.shape, 1)
            return (k == at_low) & (s < spread)
        return jnp.where(count(before) < need, cand, cut)

    def cut_among_ties():
        return lax.fori_loop(0, _cut_bits(seq_len), place,
                             jnp.zeros((rows, 1), jnp.int32))

    def last_at_threshold():
        """Every key at the threshold is taken: the cut is the last of
        them, one pass that keeps a lane's last piece with such a key."""
        first = sweep(lambda acc, k, k0: jnp.maximum(
            acc, jnp.where(k == at_low, k0, -1)), -1)
        lane = lax.broadcasted_iota(jnp.int32, first.shape, 1)
        return jnp.max(jnp.where(first < 0, -1, first + lane), axis=1,
                       keepdims=True)

    # A row has a tie to break only where more keys reach its threshold
    # than it wants; a block with no such row skips the bisection.
    tied = jnp.max((reach > want).astype(jnp.int32)) > 0
    cut = lax.cond(tied, cut_among_ties, last_at_threshold)
    short, long = select_pass_counts(seq_len)
    passes = jnp.where(tied, long, short).astype(jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, w.shape, 1)
    aux_ref[0] = jnp.where(
        lane == heads, _unsortable(low),
        jnp.where(lane == heads + 1, cut.astype(jnp.float32),
                  jnp.where(lane == heads + 2, passes, w)))


def _cut_bits(seq_len: int) -> int:
    return max((seq_len - 1).bit_length(), 1)


def select_pass_counts(seq_len: int):
    """`(short, long)`: the passes over its slab that a block of `select`
    runs.  One a bit of the threshold, then one that takes the last key at
    the threshold; or, where a row of the block has a tie to break, one a
    bit of the cut's position."""
    return 32 + 1, 32 + _cut_bits(seq_len)


def select_rows(seq_len: int) -> int:
    """The rows of a block of `select`: 128, fewer where their slab of
    scores would pass SELECT_SLAB_BYTES."""
    rows = 128
    while rows > 8 and rows * seq_len * 4 > SELECT_SLAB_BYTES:
        rows //= 2
    return rows


def select_passes(aux, heads: int):
    """[B, S] float32: the slab passes the row's block of `select` ran."""
    return aux[..., heads + 2]


def select(qi, kit, w, topk: int, block_k: int = 0,
           interpret: Optional[bool] = None):
    """`aux` [B, S, AUX_LANES] float32 of qi [B, J, S, Di], kit [B, Di, S]
    and w [B, S, J]: lanes 0..J-1 the weights as given, lane J the row's
    threshold `tau` (its min(t + 1, topk)-th largest index score among
    the keys s <= t), lane J + 1 its `cut` (the last key taken at the
    threshold, lowest keys first), lane J + 2 the passes over the slab
    that the row's block ran (`select_passes`)."""
    b, heads, s, _ = qi.shape
    if heads + 3 > AUX_LANES:
        raise ValueError(f"{heads} indexer heads do not fit aux's lanes")
    block_k = block_k or auto_blocks(s)[1]
    rows = select_rows(s)
    check_blocks(s, 128, block_k)
    w = jnp.pad(w.astype(jnp.float32),
                ((0, 0), (0, 0), (0, AUX_LANES - heads)))
    return pl.pallas_call(
        functools.partial(_select_kernel, heads=heads, topk=topk, rows=rows,
                          block_k=block_k, seq_len=s),
        grid=(b, s // rows),
        in_specs=[
            pl.BlockSpec((1, heads, rows, qi.shape[-1]),
                         lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, kit.shape[1], s), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, rows, AUX_LANES), lambda b, i: (b, i, 0))],
        out_specs=pl.BlockSpec((1, rows, AUX_LANES), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, AUX_LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, s), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=_use_interpret(interpret), name="index_topk",
    )(qi, kit, w)


# ---------------------------------------------------------------------------
# The mask, written out
# ---------------------------------------------------------------------------
def _keep_kernel(qi_ref, kit_ref, aux_ref, keep_ref, *, heads, block_q,
                 block_k):
    keep = _tile_keep(qi_ref, kit_ref, aux_ref, heads,
                      pl.program_id(1) * block_q, pl.program_id(2) * block_k)
    keep_ref[0] = keep.astype(jnp.int8)


def keep_mask(qi, kit, aux, block_q: int = 0, block_k: int = 0,
              interpret: Optional[bool] = None):
    """The selection as an int8 mask [B, S, S], 1 where row t takes key
    s: what the attention kernels keep, computed as they compute it."""
    b, heads, s, di = qi.shape
    auto_q, auto_k = auto_blocks(s)
    block_q, block_k = block_q or auto_q, block_k or auto_k
    check_blocks(s, block_q, block_k)
    return pl.pallas_call(
        functools.partial(_keep_kernel, heads=heads, block_q=block_q,
                          block_k=block_k),
        grid=(b, s // block_q, s // block_k),
        in_specs=[
            pl.BlockSpec((1, heads, block_q, di),
                         lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, di, block_k), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((1, block_q, AUX_LANES),
                         lambda b, i, j: (b, i, 0))],
        out_specs=pl.BlockSpec((1, block_q, block_k),
                               lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.int8),
        interpret=_use_interpret(interpret), name="sparse_keep",
    )(qi, kit, aux)


def _scores_kernel(qi_ref, kit_ref, w_ref, out_ref, *, heads):
    out_ref[0] = _index_tile(qi_ref[0], kit_ref[0], w_ref[0], heads)


def index_rows(qi, kit, w, block_k: int = 0,
               interpret: Optional[bool] = None):
    """The index scores of a FEW rows against every key, [B, R, S]
    float32, no mask: what `select` and the attention kernels compute of
    their tiles, written out for whoever compares it (qi [B, J, R, Di],
    w [B, R, J], R a multiple of 128)."""
    b, heads, r, di = qi.shape
    s = kit.shape[-1]
    block_k = block_k or auto_blocks(s)[1]
    check_blocks(s, 128, block_k)
    w = jnp.pad(w.astype(jnp.float32),
                ((0, 0), (0, 0), (0, AUX_LANES - heads)))
    return pl.pallas_call(
        functools.partial(_scores_kernel, heads=heads),
        grid=(b, r // 128, s // block_k),
        in_specs=[
            pl.BlockSpec((1, heads, 128, di), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, di, block_k), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((1, 128, AUX_LANES), lambda b, i, j: (b, i, 0))],
        out_specs=pl.BlockSpec((1, 128, block_k), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, r, s), jnp.float32),
        interpret=_use_interpret(interpret), name="index_rows",
    )(qi, kit, w)


# ---------------------------------------------------------------------------
# Attention under the mask: all the heads of a tile in one grid step
# ---------------------------------------------------------------------------
def _entry(block_ref, tile_ref, flags_ref):
    t = pl.program_id(1)
    flags = flags_ref[t]
    return block_ref[t], tile_ref[t], flags & FIRST != 0, flags & LAST != 0


def _stacked(ref, g):
    """The query heads of key-value head `g`, stacked as rows: the ref's
    block [1, Hkv, G, rows, D] -> [G * rows, D]."""
    _, _, group, rows, d = ref.shape
    return ref[0, g].reshape(group * rows, d)


def _online_step(s, v, m, l, acc):
    """One step of the online softmax over a tile of masked logits `s`
    [rows, keys]; `m` and `l` replicated along lanes (`_spread`)."""
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # a row can meet a tile in which it took no key before it has seen
    # any: its running maximum is still -inf
    m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
    alpha = jnp.exp(m - m_safe)
    p = jnp.exp(s - _spread(m_safe, s.shape[1]))
    return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
            acc * _spread(alpha, acc.shape[1]) + _dot_f32(p, v))


def _fwd_kernel(block_ref, tile_ref, flags_ref, q_ref, k_ref, v_ref, qi_ref,
                kit_ref, aux_ref, o_ref, lse_ref, count_ref, bits_ref, m_scr,
                l_scr, acc_scr, count_scr, *, sm_scale, heads, block_q,
                block_k):
    qb, kb, first, last = _entry(block_ref, tile_ref, flags_ref)
    _, hkv, group, _, d = q_ref.shape

    @pl.when(first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        count_scr[:] = jnp.zeros_like(count_scr)

    bias = _tile_bias(qi_ref, kit_ref, aux_ref, heads, qb * block_q,
                      kb * block_k)
    keep = bias > NEG_INF
    count_scr[:] = count_scr[:] + _lane_sums(keep.astype(jnp.float32))
    # the row block's words are one block of the result over the tiles
    # that share them: begun by the first, OR-ed into by the others
    bit = _first_bit(kb * block_k)
    word = _pack(keep, bit)
    bits_ref[0] = jnp.where(bit == 0, word, bits_ref[0] | word)
    bias = jnp.concatenate([bias] * group, axis=0)        # [G * bq, bk]
    for g in range(hkv):
        s = _dot_nt(_scaled(_stacked(q_ref, g), sm_scale),
                    k_ref[0, g].astype(jnp.float32)) + bias
        m_scr[g], l_scr[g], acc_scr[g] = _online_step(
            s, v_ref[0, g], m_scr[g], l_scr[g], acc_scr[g])

    @pl.when(last)
    def _finish():
        for g in range(hkv):
            l = l_scr[g]
            o = acc_scr[g] / _spread(l, d)
            o_ref[0, g] = o.reshape(group, block_q, d).astype(o_ref.dtype)
            lse = m_scr[g] + jnp.log(l)
            for i in range(group):
                rows = lse[i * block_q:(i + 1) * block_q]
                for r, piece in _to_lanes(rows):
                    lse_ref[0, g * group + i, 0, pl.ds(r, 128)] = piece
        kept = jnp.sum(count_scr[:], axis=1, keepdims=True)
        for r, piece in _to_lanes(kept):
            count_ref[0, 0, pl.ds(r, 128)] = piece


def _columns(ref, g, group):
    """A per-row statistic of key-value head `g`'s query heads, stored
    along lanes [1, H, 1, rows], as one column [G * rows, 1]."""
    return jnp.concatenate(
        [ref[0, g * group + i, 0, :][:, None] for i in range(group)], axis=0)


def _lanes(ref, g, group):
    """The same as one lane row [1, G * rows]."""
    return jnp.concatenate(
        [ref[0, g * group + i] for i in range(group)], axis=1)


def _bits_bias(bits_ref, k0, block_k):
    """The tile's bias [rows, block_k] from the words the forward kernel
    wrote, with no product, score or position."""
    keep = jnp.concatenate(
        _unpack(bits_ref[0], _first_bit(k0), block_k // 128), axis=1)
    return jnp.where(keep, 0.0, NEG_INF)


def _dq_kernel(block_ref, tile_ref, flags_ref, q_ref, k_ref, v_ref, do_ref,
               lse_ref, delta_ref, bits_ref, dq_ref, dq_scr, *, sm_scale,
               block_k):
    _, kb, first, last = _entry(block_ref, tile_ref, flags_ref)
    _, hkv, group, block_q, d = q_ref.shape

    @pl.when(first)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    bias = _bits_bias(bits_ref, kb * block_k, block_k)
    bias = jnp.concatenate([bias] * group, axis=0)
    for g in range(hkv):
        k = k_ref[0, g]
        s = _dot_nt(_scaled(_stacked(q_ref, g), sm_scale),
                    k.astype(jnp.float32)) + bias
        p = jnp.exp(s - _columns(lse_ref, g, group))
        ds = p * (_dot_nt(_stacked(do_ref, g), v_ref[0, g])
                  - _columns(delta_ref, g, group))
        dq_scr[g] = dq_scr[g] + _dot_f32(ds, k)

    @pl.when(last)
    def _finish():
        for g in range(hkv):
            dq_ref[0, g] = (sm_scale * dq_scr[g]).reshape(
                group, block_q, d).astype(dq_ref.dtype)


def _dkv_kernel(block_ref, tile_ref, flags_ref, q_ref, k_ref, v_ref, do_ref,
                lse_ref, delta_ref, bits_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                *, sm_scale, block_k):
    # the block is of keys here and the tile of rows; the tile of logits
    # is computed TRANSPOSED, keys down and rows across, as
    # `flash_attention._dkv_step` does and for its reasons.  The mask is
    # unpacked as the forward kernel packed it and then turned.
    kb, _, first, last = _entry(block_ref, tile_ref, flags_ref)
    _, hkv, group, _, d = q_ref.shape

    @pl.when(first)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    bias = _bits_bias(bits_ref, kb * block_k, block_k).T  # [bk, bq]
    bias = jnp.concatenate([bias] * group, axis=1)        # [bk, G * bq]
    for g in range(hkv):
        q, do = _stacked(q_ref, g), _stacked(do_ref, g)
        st = _dot_nt(_scaled(k_ref[0, g], sm_scale),
                     q.astype(jnp.float32)) + bias
        pt = jnp.exp(st - _lanes(lse_ref, g, group))
        dst = pt * (_dot_nt(v_ref[0, g], do) - _lanes(delta_ref, g, group))
        dk_scr[g] = dk_scr[g] + _dot_f32(dst, q)
        dv_scr[g] = dv_scr[g] + _dot_f32(pt, do)

    @pl.when(last)
    def _finish():
        dk_ref[0] = (sm_scale * dk_scr[:]).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _specs(shapes, block_q, block_k, rows_column, index=None):
    """BlockSpecs over the grid (batch, entry of the table).  `rows_column`
    is the table's column that holds the step's block of ROWS (0 where
    the program owns rows, 1 where it owns keys); the other holds its
    block of keys.  `index`: the indexer's (heads, size), for the kernel
    that reads its operands."""
    hkv, group, d = shapes
    keys_column = 1 - rows_column

    def rows(b, t, *table):
        return table[rows_column][t]

    def keys(b, t, *table):
        return table[keys_column][t]
    spec = {
        "q": pl.BlockSpec((1, hkv, group, block_q, d),
                          lambda b, t, *tb: (b, 0, 0, rows(b, t, *tb), 0)),
        "kv": pl.BlockSpec((1, hkv, block_k, d),
                           lambda b, t, *tb: (b, 0, keys(b, t, *tb), 0)),
        "stat": pl.BlockSpec((1, hkv * group, 1, block_q),
                             lambda b, t, *tb: (b, 0, 0, rows(b, t, *tb))),
        "count": pl.BlockSpec((1, 1, block_q),
                              lambda b, t, *tb: (b, 0, rows(b, t, *tb))),
        # the words of the step's rows that hold its tile of keys
        "bits": pl.BlockSpec(
            (1, block_q, 128),
            lambda b, t, *tb: (b, rows(b, t, *tb),
                               keys(b, t, *tb) * block_k // WORD_KEYS)),
    }
    if index:
        heads, di = index
        spec.update(
            qi=pl.BlockSpec((1, heads, block_q, di),
                            lambda b, t, *tb: (b, 0, rows(b, t, *tb), 0)),
            kit=pl.BlockSpec((1, di, block_k),
                             lambda b, t, *tb: (b, 0, keys(b, t, *tb))),
            aux=pl.BlockSpec((1, block_q, AUX_LANES),
                             lambda b, t, *tb: (b, rows(b, t, *tb), 0)))
    return spec


def _table_call(kernel, table, batch, in_specs, out_specs, scratch_shapes,
                out_shape, interpret, name):
    columns = [jnp.asarray(column, jnp.int32) for column in table]
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(columns), grid=(batch, len(table[0])),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=name)
    return functools.partial(call, *columns)


def _shapes(q, k):
    b, h, s, d = q.shape
    hkv = k.shape[1]
    return b, s, (hkv, h // hkv, d)


def _grouped(x, hkv):
    """[B, H, S, D] -> [B, Hkv, G, S, D], which moves nothing."""
    b, h, s, d = x.shape
    return x.reshape(b, hkv, h // hkv, s, d)


def _forward(q, k, v, qi, kit, aux, sm_scale, block_q, block_k, interpret):
    b, s, shapes = _shapes(q, k)
    hkv, group, d = shapes
    heads = qi.shape[1]
    spec = _specs(shapes, block_q, block_k, 0, (heads, qi.shape[-1]))
    rows = group * block_q
    o, lse, count, bits = _table_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, heads=heads,
                          block_q=block_q, block_k=block_k),
        stream_table(s, block_q, block_k, True), b,
        in_specs=[spec["q"], spec["kv"], spec["kv"], spec["qi"],
                  spec["kit"], spec["aux"]],
        out_specs=[spec["q"], spec["stat"], spec["count"], spec["bits"]],
        scratch_shapes=[pltpu.VMEM((hkv, rows, 128), jnp.float32),
                        pltpu.VMEM((hkv, rows, 128), jnp.float32),
                        pltpu.VMEM((hkv, rows, d), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32)],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, group, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, hkv * group, 1, s), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, s), jnp.float32),
            jax.ShapeDtypeStruct((b, s, words(s)), jnp.int32)],
        interpret=interpret, name="sparse_fwd",
    )(_grouped(q, hkv), k, v, qi, kit, aux)
    return o.reshape(q.shape), lse, count[:, 0], bits


def _backward(q, k, v, bits, o, lse, do, sm_scale, block_q, block_k,
              interpret):
    b, s, shapes = _shapes(q, k)
    hkv, group, d = shapes
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None, :]               # [B, H, 1, S]
    operands = (_grouped(q, hkv), k, v, _grouped(do, hkv), lse, delta, bits)

    def in_specs(spec):
        return [spec["q"], spec["kv"], spec["kv"], spec["q"], spec["stat"],
                spec["stat"], spec["bits"]]

    spec = _specs(shapes, block_q, block_k, 0)
    dq = _table_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, block_k=block_k),
        stream_table(s, block_q, block_k, True), b,
        in_specs=in_specs(spec), out_specs=spec["q"],
        scratch_shapes=[pltpu.VMEM((hkv, group * block_q, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, s, d), q.dtype),
        interpret=interpret, name="sparse_dq",
    )(*operands)
    spec = _specs(shapes, block_q, block_k, 1)
    dk, dv = _table_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, block_k=block_k),
        stream_table(s, block_q, block_k, True, by_keys=True), b,
        in_specs=in_specs(spec), out_specs=[spec["kv"], spec["kv"]],
        scratch_shapes=[pltpu.VMEM((hkv, block_k, d), jnp.float32),
                        pltpu.VMEM((hkv, block_k, d), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=interpret, name="sparse_dkv",
    )(*operands)
    return dq.reshape(q.shape), dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def sparse_attention(q, k, v, qi, kit, aux, sm_scale: Optional[float] = None,
                     block_q: int = 0, block_k: int = 0,
                     interpret: Optional[bool] = None):
    """`(o [B, H, S, D], count [B, S])`: every head's causal attention
    over the keys `aux` (from `select`, on the same qi and kit) leaves a
    row, and how many pairs the forward kernel kept a row, as float32.
    The selection is a constant of the backward pass."""
    return _sparse_fwd(q, k, v, qi, kit, aux, sm_scale, block_q, block_k,
                       interpret)[0]


def _blocks(s, block_q, block_k):
    auto_q, auto_k = auto_blocks(s)
    block_q, block_k = block_q or auto_q, block_k or auto_k
    check_blocks(s, block_q, block_k)
    if WORD_KEYS % block_k:
        raise ValueError(
            f"sparse attention keeps a tile's mask in one block of words: "
            f"block_k={block_k} must divide {WORD_KEYS}")
    return block_q, block_k


def _alone(results):
    """A kernel's results behind a barrier.  The compiler otherwise fuses
    what consumes them INTO the kernel's instruction (a scan's stacking of
    `count` was; its stacking of `o`, `lse` and `bits` for the backward
    pass is three more such consumers), and such a fusion is held to the
    compiler's own 16 MiB of VMEM whatever `vmem_limit_bytes` the kernel
    was given: the compile then ends with `Ran out of memory in memory
    space vmem`."""
    return lax.optimization_barrier(results)


def _sparse_fwd(q, k, v, qi, kit, aux, sm_scale, block_q, block_k,
                interpret):
    s, d = q.shape[2], q.shape[3]
    block_q, block_k = _blocks(s, block_q, block_k)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    # The barrier stands between the kernel and the names: a recomputed
    # layer needs the `o` that comes out of it, so with the names inside it
    # the barrier would be run again, want `count`, and `count` the kernel.
    o, lse, count, bits = _alone(_forward(
        q, k, v, qi, kit, aux, scale, block_q, block_k,
        _use_interpret(interpret)))
    o, lse, bits = (checkpoint_name(t, ATTENTION_NAME)
                    for t in (o, lse, bits))
    # qi, kit and aux are kept for the shapes of their zero cotangents
    return (o, count), (q, k, v, qi, kit, aux, o, lse, bits)


def _sparse_bwd(sm_scale, block_q, block_k, interpret, residuals, cotangent):
    q, k, v, qi, kit, aux, o, lse, bits = residuals
    s, d = q.shape[2], q.shape[3]
    block_q, block_k = _blocks(s, block_q, block_k)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    dq, dk, dv = _backward(q, k, v, bits, o, lse, cotangent[0], scale,
                           block_q, block_k, _use_interpret(interpret))
    return (dq, dk, dv, jnp.zeros_like(qi), jnp.zeros_like(kit),
            jnp.zeros_like(aux))


sparse_attention.defvjp(_sparse_fwd, _sparse_bwd)


def selected_attention(q, k, v, qi, ki, w, topk: int, block_q: int = 0,
                       block_k: int = 0, interpret: Optional[bool] = None):
    """Selection and attention together: `(o, count)`.  qi, ki and w are
    read as constants (`lax.stop_gradient`): the choice of keys has no
    gradient.  A call that is traced records its form
    (`bps_sparse_*`).  The kernels' results come from behind barriers
    (`_alone`), the attention's inside its forward rule."""
    _, h, s, d = q.shape
    block_q, block_k = _blocks(s, block_q, block_k)
    qi, ki, w = (lax.stop_gradient(t) for t in (qi, ki, w))
    kit = ki.transpose(0, 2, 1)
    with jax.named_scope(".select"):
        aux = checkpoint_name(
            _alone(select(qi, kit, w, topk, block_k, interpret)),
            SELECTION_NAME)
    block, tile, _ = stream_table(s, block_q, block_k, True)
    short, long = select_pass_counts(s)
    written = {(i, j * block_k // WORD_KEYS) for i, j in zip(block, tile)}
    telemetry.record_static(
        "sparse_attention", rows=s, topk=min(topk, s),
        selected_pairs=selected_pairs(s, topk),
        visible_pairs=s * (s + 1) // 2, tiles_walked=len(block),
        index_passes=1, select_passes_min=short, select_passes_max=long,
        mask_bytes=len(written) * block_q * 128 * 4,
        # o, lse and bits of a sequence: what `ATTENTION_NAME` names
        kept_bytes=s * (h * d * q.dtype.itemsize + h * 4 + words(s) * 4))
    with jax.named_scope(".sparse"):
        return sparse_attention(q, k, v, qi, kit, aux, None, block_q,
                                block_k, interpret)


# ---------------------------------------------------------------------------
# The same layer with the [S, S] scores whole: small sizes and the CPU
# ---------------------------------------------------------------------------
def index_scores(qi, ki, w):
    """I [B, S, S] float32, every pair, in plain `jax.numpy`."""
    s = jnp.einsum("bjtd,bsd->bjts", qi, ki,
                   preferred_element_type=jnp.float32)
    scores = jnp.einsum("btj,bjts->bts", w.astype(jnp.float32),
                        jnp.maximum(s, 0.0))
    return jnp.where(scores == 0.0, 0.0, scores)


def dense_keep(qi, ki, w, topk: int):
    """The selection [B, S, S] bool by `lax.top_k` on whole rows of
    scores (equal scores: the lowest key first)."""
    s = qi.shape[2]
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, index_scores(qi, ki, w), NEG_INF)
    _, chosen = lax.top_k(scores, min(topk, s))           # [B, S, k]
    keep = jnp.zeros(scores.shape, bool)
    keep = jax.vmap(jax.vmap(lambda row, at: row.at[at].set(True)))(
        keep, chosen)
    return keep & causal


def selected_attention_dense(q, k, v, qi, ki, w, topk: int):
    """`selected_attention` without a kernel."""
    group = q.shape[1] // k.shape[1]
    with jax.named_scope(".select"):
        keep = dense_keep(*(lax.stop_gradient(t) for t in (qi, ki, w)), topk)
    with jax.named_scope(".sparse"):
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
        logits = logits / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
        logits = jnp.where(keep[:, None], logits,
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return (jnp.einsum("bhqk,bhkd->bhqd", probs, v),
                keep.sum(-1).astype(jnp.float32))
