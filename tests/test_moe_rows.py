"""The expert layer's row moves (`byteps_tpu/ops/moe_rows.py`) in the
Pallas interpreter: the kernel against `src[idx]` and against the
`.at[].add` form it replaces, the layer's two `custom_vjp`s round it
against `jax.grad` of the plain forms, what an index outside the source
adds (nothing, NaN or not), and what a process traces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import byteps_tpu as bps
from byteps_tpu.ops import moe_rows
from byteps_tpu.parallel import dropless_moe as dm

N = 43                       # rows of the source: no whole group of 8
TOL = {"float32": 2e-6, "bfloat16": 1e-2}


def _case(k, width, dtype, rows, seed=0, spare=3):
    """A source, indices of which some fall outside it on both sides, and
    weights."""
    rng = np.random.default_rng(seed)
    src = jnp.asarray(rng.normal(size=(N, width)), dtype)
    idx = jnp.asarray(rng.integers(-spare, N + spare, size=(rows, k)),
                      jnp.int32)
    w = jnp.asarray(rng.normal(size=(rows, k)), jnp.float32)
    return src, idx, w


def _plain(src, idx, w):
    """`sum_j w[i, j] src[idx[i, j]]` in float32, an index outside the
    source adding nothing."""
    inside = (idx >= 0) & (idx < src.shape[0])
    rows = src.astype(jnp.float32)[jnp.clip(idx, 0, src.shape[0] - 1)]
    w = jnp.where(inside, 1.0 if w is None else w, 0.0)
    return (jnp.where(inside[..., None], rows, 0) * w[..., None]).sum(1)


def _close(got, want, dtype):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    scale = np.abs(want).max() or 1.0
    np.testing.assert_allclose(got / scale, want / scale, rtol=0,
                               atol=TOL[jnp.dtype(dtype).name])


@pytest.mark.parametrize("width", [128, 384])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["plain", "weighted"])
@pytest.mark.parametrize("k", [1, 8])
def test_kernel_is_the_sum_of_the_rows_it_is_told(k, weighted, dtype, width):
    """Float32 sums of `src[idx]`, rows that are no whole tile (70) of a
    source that is no whole group (43)."""
    src, idx, w = _case(k, width, dtype, rows=70)
    got = moe_rows.gather_sum(src, idx, w if weighted else None,
                              out_dtype=jnp.float32)
    assert got.shape == (70, width) and got.dtype == jnp.float32
    _close(got, _plain(src, idx, w if weighted else None), jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("k", [1, 8])
def test_kernel_is_the_scatter_add_read_from_the_other_side(k, dtype):
    """Rows weighted and scatter-added to their tokens, which is what the
    layer did, against the kernel fed each token's places: more rows than
    a tile holds (`tile_rows(k)` of them and 44 more), and flat indices
    as the layer hands them."""
    rng = np.random.default_rng(1)
    tokens = moe_rows.tile_rows(k) + 44
    pairs = tokens * k
    held = rng.random(pairs) < 0.3             # pairs that have a row
    pair = jnp.asarray(np.flatnonzero(held), jnp.int32)
    rows = pair.size
    y = jnp.asarray(rng.normal(size=(rows, 128)), dtype)
    flat_w = jnp.asarray(rng.normal(size=(pairs,)), jnp.float32)
    want = jnp.zeros((tokens, 128), jnp.float32).at[pair // k].add(
        y.astype(jnp.float32) * flat_w[pair][:, None])
    place = jnp.full((pairs,), -1, jnp.int32).at[pair].set(
        jnp.arange(rows, dtype=jnp.int32))
    got = moe_rows.gather_sum(y, place, flat_w, k=k, out_dtype=jnp.float32,
                              use="scatter")
    _close(got, want, jnp.float32)
    assert bps.get_metrics()["bps_moe_move_kernel"] == 1
    assert bps.get_metrics()['bps_moe_move_rows{use="scatter"}'] == pairs
    assert bps.get_metrics()["bps_moe_move_tile_rows"] == moe_rows.tile_rows(
        k)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_result_is_rounded_once_to_the_dtype_asked(dtype):
    src, idx, w = _case(8, 128, dtype, rows=24)
    got = moe_rows.gather_sum(src, idx, w)
    assert got.dtype == dtype
    _close(got, _plain(src, idx, w), dtype)


@pytest.mark.parametrize("k", [1, 8])
def test_nan_outside_the_rows_asked_for_reaches_nothing(k):
    """The rows nobody names may hold anything: they lie in the groups
    the copies fetch, and are never read out."""
    src, idx, w = _case(k, 128, jnp.bfloat16, rows=40, spare=0)
    idx = idx // 3 * 3                 # every group has rows nobody names
    named = np.zeros(N, bool)
    named[np.asarray(idx).reshape(-1)] = True
    poisoned = jnp.where(jnp.asarray(named)[:, None], src, jnp.nan)
    assert int(jnp.isnan(poisoned[:, 0]).sum()) >= N // 2
    want = _plain(src, idx, w)
    _close(moe_rows.gather_sum(poisoned, idx, w, out_dtype=jnp.float32),
           want, jnp.float32)


def test_indices_past_the_source_add_zero():
    """-1, `n` and beyond: no row, no NaN, whatever the weight."""
    src = jnp.full((N, 128), jnp.nan, jnp.float32)
    idx = jnp.asarray([[-1, N, N + 7, -5]] * 9, jnp.int32)
    w = jnp.full((9, 4), jnp.inf, jnp.float32)
    got = moe_rows.gather_sum(src, idx, w)
    assert got.shape == (9, 128) and not np.asarray(got).any()


def test_tile_does_not_follow_the_rows():
    """One tile a `k`, whole blocks of SMEM: the first buffer's call and
    the exact path's differ by the grid alone."""
    assert moe_rows.tile_rows(1) == 1024
    assert moe_rows.tile_rows(8) == 256
    assert moe_rows.tile_rows(6) == 512
    for k in (1, 2, 3, 6, 8, 16):
        tm = moe_rows.tile_rows(k)
        assert tm * k % moe_rows.SMEM_BLOCK == 0 and tm % 16 == 0


def _buffer_case(dtype, seed=3):
    """`tokens` tokens of `k` choices, a third of the pairs on held
    experts, sorted into a buffer with room to spare: what `_buffer`
    hands the two moves."""
    rng = np.random.default_rng(seed)
    tokens, k, rows, width = 40, 4, 72, 128
    pairs = tokens * k
    here = np.flatnonzero(rng.random(pairs) < 0.33)
    pair = np.zeros(rows, np.int64)
    pair[:here.size] = rng.permutation(here)
    token = np.where(np.arange(rows) < here.size, pair // k, -1)
    place = np.full(pairs, -1)
    place[pair[:here.size]] = np.arange(here.size)
    x = jnp.asarray(rng.normal(size=(tokens, width)), dtype)
    y = jnp.asarray(rng.normal(size=(rows, width)), dtype)
    flat_w = jnp.asarray(rng.normal(size=(pairs,)), jnp.float32)
    ints = [jnp.asarray(a, jnp.int32) for a in (token, pair, place)]
    return x, y, flat_w, *ints, k


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_rows_in_and_its_gradient(dtype):
    """`_rows_in` against `jax.grad` of `where(dead, 0, x[token])`."""
    x, _, _, token, _, place, k = _buffer_case(dtype)
    probe = jnp.asarray(np.random.default_rng(5).normal(
        size=(token.size, x.shape[1])), jnp.float32)

    def plain(x):
        rows = jnp.where((token < 0)[:, None], 0, x[jnp.maximum(token, 0)])
        return rows, (rows.astype(jnp.float32) * probe).sum()

    def kernel(x):
        rows = dm._rows_in(x, token, place, k)
        return rows, (rows.astype(jnp.float32) * probe).sum()

    (_, want), g_want = jax.value_and_grad(
        lambda x: plain(x)[::-1], has_aux=True)(x.astype(jnp.float32))
    (_, got), g = jax.value_and_grad(
        lambda x: kernel(x)[::-1], has_aux=True)(x)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want.astype(dtype), np.float32))
    assert g.dtype == dtype
    _close(g, g_want, dtype)


@pytest.mark.parametrize("poison", [False, True], ids=["clean", "nan"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_rows_out_and_its_gradients(dtype, poison):
    """`_rows_out` against `jax.grad` of the masked, weighted scatter-add
    it replaces, in `y` and in the weights; with NaN in the rows nobody
    owns, which reaches no result and no gradient."""
    _, y, flat_w, token, pair, place, k = _buffer_case(dtype)
    tokens = flat_w.size // k
    dead = (token < 0)[:, None]
    probe = jnp.asarray(np.random.default_rng(6).normal(
        size=(tokens, y.shape[1])), dtype).astype(jnp.float32)

    def plain(y, flat_w):
        rows = jnp.where(dead, 0, y).astype(jnp.float32) * flat_w[pair][
            :, None]
        out = jnp.zeros((tokens, y.shape[1]), jnp.float32).at[
            jnp.maximum(token, 0)].add(rows)
        return (out * probe).sum(), out

    def kernel(y, flat_w):
        out = dm._rows_out(y, flat_w, token, pair, place, k, dtype)
        return (out * probe).sum(), out

    (_, want), g_want = jax.value_and_grad(plain, (0, 1), has_aux=True)(
        y.astype(jnp.float32), flat_w)
    if poison:
        y = jnp.where(dead, jnp.nan, y)
    (_, got), g = jax.value_and_grad(kernel, (0, 1), has_aux=True)(y, flat_w)
    _close(got, want, jnp.float32)
    assert g[0].dtype == dtype
    _close(jnp.where(dead, 0, g[0]), jnp.where(dead, 0, g_want[0]), dtype)
    assert not np.asarray(jnp.where(dead, g[0], 0), np.float32).any()
    _close(g[1], g_want[1], dtype)


def test_a_shape_is_traced_once_a_process():
    """Calls at one shape, eagerly and inside other programs, leave
    `bps_moe_move_texts` where the first left it."""
    src, idx, w = _case(8, 256, jnp.float32, rows=33)
    moe_rows.gather_sum(src, idx, w)
    before = bps.get_metrics()["bps_moe_move_texts"]
    assert before == moe_rows.texts() >= 1
    for _ in range(2):
        jax.jit(lambda s: moe_rows.gather_sum(s, idx, w)
                + moe_rows.gather_sum(2 * s, idx, w))(src)
    assert moe_rows.texts() == before
    moe_rows.gather_sum(src, idx)              # another body: no weights
    assert bps.get_metrics()["bps_moe_move_texts"] == before + 1
