"""The keye program broken in ten ways, each of which the cell's
`correct` has to catch (ISSUE 43, Tentpole 3).  A variant is a context
manager over a family: inside it `family.loss` and what
`family.reference_loss` asks the program for (its experts, its keys, its
counter) are the broken program's; the reference stays what it is.

One is built by an option of the program; nine need its code patched,
which is done here and nowhere in the program.  Used by the tests at tiny
widths (`tests/test_keye.py`) and by `tools/reference_check.py` at the
published widths on the chip.
"""

import contextlib
import dataclasses
import math
from unittest import mock

import jax.numpy as jnp
from jax import lax

from benchmark.families import keye as family_keye
from byteps_tpu.models import afmoe, keye
from byteps_tpu.models.transformer import _rope
from byteps_tpu.ops import sparse_attention


@contextlib.contextmanager
def top2047(family):
    """One key fewer a row than `topk`."""
    kept = family.cfg
    family.cfg = dataclasses.replace(kept, index_topk=kept.index_topk - 1)
    try:
        yield family
    finally:
        family.cfg = kept


@contextlib.contextmanager
def all_visible_keys(family):
    """Attention over ALL the keys before a row: the selection is
    computed and ignored (the attention kernels mask by position alone;
    what the program reports as its selection is still the indexer's)."""
    def causal_alone(qi_ref, kit_ref, aux_ref, heads, q0, k0):
        rows, cols = sparse_attention._positions(
            (qi_ref.shape[2], kit_ref.shape[2]), q0, k0)
        return jnp.where(cols <= rows, 0.0, sparse_attention.NEG_INF)

    def dense(q, k, v, qi, ki, w, topk):
        group = q.shape[1] // k.shape[1]
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
        seen = jnp.arange(1, q.shape[2] + 1, dtype=jnp.float32)
        return (afmoe.dense_attention(q, k, v, causal=True),
                jnp.broadcast_to(seen, (q.shape[0], q.shape[2])))
    with mock.patch.object(sparse_attention, "_tile_bias", causal_alone), \
            mock.patch.object(sparse_attention, "selected_attention_dense",
                              dense):
        yield family


@contextlib.contextmanager
def selection_not_causal(family):
    """A row's threshold is found among the keys of its whole tile, those
    AFTER it too; the attention then masks them."""
    select, positions = sparse_attention.select, sparse_attention._positions

    def every_key_seen(shape, q0, k0):
        return jnp.full(shape, 2 ** 30, jnp.int32), positions(shape, q0,
                                                              k0)[1]

    def broken(*args, **kwargs):
        with mock.patch.object(sparse_attention, "_positions",
                               every_key_seen):
            return select(*args, **kwargs)
    with mock.patch.object(sparse_attention, "select", broken):
        yield family


@contextlib.contextmanager
def a_selection_a_head(family):
    """Every key-value head's group of query heads selects by itself: by
    the indexer's weights turned one head further for each group."""
    selected = afmoe._selected

    def broken(cfg):
        attend = selected(cfg)

        def by_group(q, k, v, index):
            qi, ki, w = index
            hkv, group = k.shape[1], q.shape[1] // k.shape[1]
            outs = [attend(q[:, g * group:(g + 1) * group],
                           k[:, g:g + 1], v[:, g:g + 1],
                           (qi, ki, jnp.roll(w, g, axis=-1)))
                    for g in range(hkv)]
            return jnp.concatenate([o for o, _ in outs], axis=1), outs[0][1]
        return by_group
    with mock.patch.dict(afmoe._ATTENTION, {afmoe.SELECTED: broken}):
        yield family


@contextlib.contextmanager
def relu_left_out(family):
    """I[t, s] = sum_j w[t, j] (qI[t, j] . kI[s]), negative products
    too."""
    def tile(qi, kit, aux, heads):
        acc = None
        for j in range(heads):
            term = aux[:, j:j + 1] * jnp.dot(
                qi[j], kit, preferred_element_type=jnp.float32)
            acc = term if acc is None else acc + term
        return jnp.where(acc == 0.0, 0.0, acc)

    def scores(qi, ki, w):
        s = jnp.einsum("bjtd,bsd->bjts", qi, ki,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("btj,bjts->bts", w.astype(jnp.float32), s)
    with mock.patch.object(sparse_attention, "_index_tile", tile), \
            mock.patch.object(sparse_attention, "index_scores", scores):
        yield family


@contextlib.contextmanager
def weights_left_out(family):
    """Every indexer head weighs the same: w = 1 / sqrt(16 x 64)."""
    index = keye._index

    def unweighted(a, lp, cfg, positions=None):
        qi, ki, w = index(a, lp, cfg, positions)
        return qi, ki, jnp.full_like(
            w, 1.0 / math.sqrt(cfg.index_heads * cfg.index_head_dim))
    with mock.patch.object(keye, "_index", unweighted):
        yield family


def _float8(x):
    """x rounded to float8's three mantissa bits (e4m3) at its own range,
    by its bits: what a Mosaic kernel can do to a tile."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    bits = (bits + jnp.int32(0x00080000)) & jnp.int32(-0x00100000)
    return lax.bitcast_convert_type(bits, jnp.float32).astype(x.dtype)


@contextlib.contextmanager
def index_products_in_float8(family):
    """The indexer's products on operands rounded to float8's three
    mantissa bits: the nearest precision below the bfloat16 the cell
    states."""
    tile, scores = sparse_attention._index_tile, sparse_attention.index_scores

    def rounded_tile(qi, kit, aux, heads):
        return tile(_float8(qi), _float8(kit), aux, heads)

    def rounded_scores(qi, ki, w):
        return scores(_float8(qi), _float8(ki), w)
    with mock.patch.object(sparse_attention, "_index_tile", rounded_tile), \
            mock.patch.object(sparse_attention, "index_scores",
                              rounded_scores):
        yield family


@contextlib.contextmanager
def sections_from_one_stream(family):
    """The three position streams DIFFER (a grid of image patches), and
    every section of the pairs turns by the first."""
    def one_stream(x, cfg, positions=None, sections=None):
        if positions is None:
            return _rope(x, cfg.rope_theta)
        return _rope(x, cfg.rope_theta, positions=positions[0])
    kept = family.positions
    family.positions = family_keye.grid_positions
    try:
        with mock.patch.object(keye, "rotary", one_stream):
            yield family
    finally:
        family.positions = kept


@contextlib.contextmanager
def short_rows_padded(family):
    """A row with fewer than `topk` keys before it has its list filled up
    with copies of key 0, as a gather over a fixed-width list would leave
    it: the key then counts `topk` - t times in the row's softmax."""
    bias = sparse_attention._tile_bias
    topk = family.cfg.index_topk

    def padded(qi_ref, kit_ref, aux_ref, heads, q0, k0):
        b = bias(qi_ref, kit_ref, aux_ref, heads, q0, k0)
        rows, cols = sparse_attention._positions(b.shape, q0, k0)
        copies = jnp.maximum(topk - rows, 1).astype(jnp.float32)
        return b + jnp.where(cols == 0, jnp.log(copies), 0.0)
    with mock.patch.object(sparse_attention, "_tile_bias", padded):
        yield family


@contextlib.contextmanager
def softmax_statistics_in_bfloat16(family):
    """The attention kernel's running maximum and sum rounded to bfloat16
    after every tile: the nearest precision below the float32 the kernels
    keep them in."""
    step = sparse_attention._online_step

    def rounded(s, v, m, l, acc):
        m, l, acc = step(s, v, m, l, acc)
        return (m.astype(jnp.bfloat16).astype(jnp.float32),
                l.astype(jnp.bfloat16).astype(jnp.float32), acc)
    with mock.patch.object(sparse_attention, "_online_step", rounded):
        yield family


VARIANTS = {
    "all_visible_keys": all_visible_keys,
    "top2047": top2047,
    "selection_not_causal": selection_not_causal,
    "a_selection_a_head": a_selection_a_head,
    "relu_left_out": relu_left_out,
    "weights_left_out": weights_left_out,
    "index_products_in_float8": index_products_in_float8,
    "sections_from_one_stream": sections_from_one_stream,
    "short_rows_padded": short_rows_padded,
    "softmax_statistics_in_bfloat16": softmax_statistics_in_bfloat16,
}
