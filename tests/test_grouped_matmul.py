"""The expert layer's grouped product (`byteps_tpu/ops/grouped_matmul.py`)
in the Pallas interpreter against a per-group `jnp.dot` in float32: all
three kernels, whatever the routing, with the rows past the last group
holding NaN; the tile rule; the gauges of the last call traced."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import byteps_tpu as bps
from byteps_tpu.ops import grouped_matmul as gm

ROWS, K, N, G = 1024, 256, 128, 4       # tiles of 256 rows: four of them

ROUTINGS = {
    "even": [256, 256, 256, 256],
    "one_group_empty": [300, 0, 500, 224],
    "group_smaller_than_a_tile": [7, 600, 3, 414],
    "edges_off_the_tile_grid": [129, 383, 257, 255],
    "live_rows_short_of_the_buffer": [200, 313, 0, 90],
    "nothing_live_in_the_last_tile": [100, 100, 100, 100],
}
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 1e-2}


def _operands(dtype, sizes, seed=0):
    """Operands with NaN planted in every row past the last group."""
    k = jax.random.split(jax.random.key(seed), 3)
    live = (jnp.arange(ROWS) < sum(sizes))[:, None]
    lhs = jnp.where(live, jax.random.normal(k[0], (ROWS, K)), jnp.nan)
    rhs = jax.random.normal(k[1], (G, K, N)) * K ** -0.5
    g = jnp.where(live, jax.random.normal(k[2], (ROWS, N)), jnp.nan)
    return lhs.astype(dtype), rhs.astype(dtype), g.astype(dtype)


def _per_group(sizes):
    """`(group, its rows)` of a routing."""
    at = 0
    for group, size in enumerate(sizes):
        yield group, slice(at, at + size)
        at += size


def _f32(x):
    return np.asarray(x, np.float32)


def _close(got, want, dtype):
    scale = np.abs(want).max() or 1.0
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got / scale, want / scale, rtol=0,
                               atol=TOL[dtype])


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("kind", ["forward", "rows_gradient",
                                  "weights_gradient"])
def test_kernel_against_per_group_dot(kind, dtype, routing):
    sizes = ROUTINGS[routing]
    live = sum(sizes)
    lhs, rhs, g = _operands(dtype, sizes)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    assert gm.grouped_tiles(ROWS, K, N, G, dtype).rows == 256

    def product(lhs, rhs):
        return gm.grouped_matmul(lhs, rhs, group_sizes)

    if kind == "forward":
        got = _f32(jax.jit(product)(lhs, rhs))
        assert got.dtype == np.float32 and product(lhs, rhs).dtype == dtype
        want = np.zeros((ROWS, N), np.float32)
        for group, rows in _per_group(sizes):
            want[rows] = _f32(lhs[rows]) @ _f32(rhs[group])
        _close(got[:live], want[:live], dtype)
        return

    def loss(lhs, rhs):
        # the cotangent `g`, NaN past the last group as the chip may
        # leave it: a caller masks the rows there, here as `_buffer` does
        out = product(lhs, rhs).astype(jnp.float32)
        return jnp.sum(jnp.where((jnp.arange(ROWS) < live)[:, None],
                                 out * g.astype(jnp.float32), 0))

    d_lhs, d_rhs = jax.jit(jax.grad(loss, (0, 1)))(lhs, rhs)
    assert d_lhs.dtype == dtype and d_rhs.dtype == dtype
    g_live = np.where(np.arange(ROWS)[:, None] < live, _f32(g), 0)
    if kind == "rows_gradient":
        want = np.zeros((ROWS, K), np.float32)
        for group, rows in _per_group(sizes):
            want[rows] = g_live[rows] @ _f32(rhs[group]).T
        _close(_f32(d_lhs)[:live], want[:live], dtype)
    else:
        want = np.zeros((G, K, N), np.float32)
        for group, rows in _per_group(sizes):
            want[group] = _f32(lhs[rows]).T @ g_live[rows]
        got = _f32(d_rhs)
        _close(got, want, dtype)
        for group, size in enumerate(sizes):
            if size == 0:       # an empty group's gradient is zeros
                assert not got[group].any()


def test_rows_past_the_last_group_are_left_as_found_not_computed():
    """The forward result there is whatever the block held, never a
    product of the NaN planted in the operand: a group's rows beside
    them, in the same tile, come out finite."""
    sizes = [100, 29, 0, 300]
    lhs, rhs, _ = _operands(jnp.float32, sizes)
    out = gm.grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32))
    assert np.isfinite(_f32(out)[:sum(sizes)]).all()


def test_same_results_as_ragged_dot_under_jit_and_grad():
    """The entry the layer calls is `lax.ragged_dot`'s drop-in on the
    rows that belong to a group."""
    sizes = ROUTINGS["edges_off_the_tile_grid"]
    group_sizes = jnp.asarray(sizes, jnp.int32)
    lhs, rhs, _ = _operands(jnp.float32, sizes)     # every row live

    def loss(product):
        return lambda a, b: jnp.sum(jnp.sin(product(a, b, group_sizes)))
    got = jax.value_and_grad(loss(gm.grouped_matmul), (0, 1))(lhs, rhs)
    want = jax.value_and_grad(loss(lax.ragged_dot), (0, 1))(lhs, rhs)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(_f32(a), _f32(b), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("k,n,whole", [
    (2304, 896, True),      # mellum's gate and up: 18 x 128, 7 x 128
    (896, 2304, True),      # its down
    (2048, 1024, True),     # trinity-mini's
    (1024, 2048, True),
])
def test_tile_rule_at_the_cells_widths(k, n, whole):
    rows = {2304: 81920, 896: 81920}.get(k, 53248)
    tiles = gm.grouped_tiles(rows, k, n, 16, jnp.bfloat16)
    assert tiles is not None and tiles.rows == 256 and rows % tiles.rows == 0
    for step, width in ((tiles.fwd_k, k), (tiles.drows_n, n),
                        (tiles.dweights_k, k)):
        assert width % step == 0 and step % 128 == 0
        assert (step == width) == whole
    assert gm._rows_vmem(tiles.rows, k, tiles.fwd_k, n, 2) <= gm.VMEM_BUDGET
    assert gm._rows_vmem(tiles.rows, n, tiles.drows_n, k,
                         2) <= gm.VMEM_BUDGET
    assert gm._dweights_vmem(tiles.rows, tiles.dweights_k, n,
                             2) <= gm.VMEM_BUDGET


@pytest.mark.parametrize("rows,k,n", [
    (512, 64, 32),          # the tiny models of the CPU tests
    (512, 2304, 480),       # no whole half lane tiles: 7.5 x 64
    (520, 256, 128),        # rows no tile of 128 divides
])
def test_tile_rule_refuses_and_ragged_dot_runs(rows, k, n):
    assert gm.grouped_tiles(rows, k, n, 4, jnp.bfloat16) is None
    lhs = jnp.ones((rows, k))
    rhs = jnp.ones((4, k, n))
    sizes = jnp.asarray([rows // 4] * 4, jnp.int32)
    out = gm.grouped_matmul(lhs, rhs, sizes)
    np.testing.assert_allclose(_f32(out), float(k))
    assert bps.get_metrics()["bps_grouped_kernel"] == 0


def test_tile_rule_splits_a_width_that_does_not_fit():
    """Contracted widths too wide for the budget go in their largest
    divisor that fits, a multiple of 128."""
    tiles = gm.grouped_tiles(4096, 8192, 2048, 8, jnp.bfloat16)
    assert tiles is not None
    assert tiles.fwd_k < 8192 and 8192 % tiles.fwd_k == 0
    assert tiles.fwd_k % 128 == 0 and tiles.dweights_k < 8192


def test_contracted_width_in_steps_sums_in_float32():
    """A width taken in steps (the scratch path) gives what the whole
    width gives."""
    sizes = ROUTINGS["edges_off_the_tile_grid"]
    group_sizes = jnp.asarray(sizes, jnp.int32)
    lhs, rhs, g = _operands(jnp.bfloat16, sizes, seed=3)
    live = sum(sizes)
    whole, halves = (gm._rows_call(
        lhs, rhs, gm.row_walk(group_sizes, ROWS, 256), tm=256, tc=tc,
        transposed=False, interpret=True) for tc in (K, K // 2))
    _close(_f32(halves)[:live], _f32(whole)[:live], jnp.bfloat16)
    thirds = gm._dweights_call(lhs, g, gm.row_walk(group_sizes, ROWS, 128),
                               tm=128, tk=128, interpret=True)
    whole = gm._dweights_call(lhs, g, gm.row_walk(group_sizes, ROWS, 512),
                              tm=512, tk=256, interpret=True)
    _close(_f32(thirds), _f32(whole), jnp.bfloat16)


def test_gauges_say_which_path_ran_and_on_what_tiles():
    sizes = jnp.asarray(ROUTINGS["even"], jnp.int32)
    lhs, rhs, _ = _operands(jnp.bfloat16, ROUTINGS["even"])
    gm.grouped_matmul(lhs, rhs, sizes)
    metrics = bps.get_metrics()
    assert metrics["bps_grouped_kernel"] == 1
    assert metrics["bps_grouped_tile_rows"] == 256
    assert metrics["bps_grouped_tile_fwd_k"] == K
    assert metrics["bps_grouped_tile_drows_n"] == N
    assert metrics["bps_grouped_tile_dweights_k"] == K


@pytest.mark.parametrize("rows,groups,live,walked,needed", [
    (81920, 16, 65536, 256, 256),   # mellum: 4,096 rows a group, 16 tiles
    (53248, 16, 32768, 128, 128),   # trinity-mini
    (1024, 4, 600, 6, 3),           # edges inside tiles: 150 rows a group
    (1024, 4, 0, 0, 0),
])
def test_walk_at_the_even_routing(rows, groups, live, walked, needed):
    assert gm.row_tiles(rows, groups, 256, live) == {
        "row_tiles_walked": walked, "row_tiles_needed": needed,
        "row_tiles_buffer": rows // 256}


# The tiles every accepted cell's products got before the rule took
# widths of whole HALF lane tiles (PR 47): (rows, k, n, groups) -> tiles.
TILES_BEFORE = {
    (53248, 2048, 1024, 16): (256, 2048, 1024, 2048, 1024),   # trinity-mini
    (53248, 1024, 2048, 16): (256, 1024, 2048, 1024, 2048),
    (81920, 2304, 896, 16): (256, 2304, 896, 2304, 896),      # mellum
    (81920, 896, 2304, 16): (256, 896, 2304, 896, 2304),
    (40960, 2048, 768, 16): (256, 2048, 768, 2048, 768),      # keye
    (40960, 768, 2048, 16): (256, 768, 2048, 768, 2048),
}


@pytest.mark.parametrize("shape", TILES_BEFORE, ids=str)
def test_the_other_cells_shapes_keep_the_tiles_they_had(shape):
    """Every width whole, the weights' gradient N whole too: the new
    field is the old behaviour wherever K can be cut."""
    assert tuple(gm.grouped_tiles(*shape, jnp.bfloat16)) == TILES_BEFORE[
        shape]


def test_tile_rule_at_a_width_of_whole_half_lane_tiles():
    """1856 = 14.5 x 128 (the nemotron_h cell's experts, 7,680 rows on 8
    of them): no divisor of it is whole lane tiles, so it is never cut;
    the weights' gradient's float32 sum [2688, 1856] passes the budget
    either way round, so the OTHER width goes in thirds: K where the
    1856 is N, N where it is K."""
    up = gm.grouped_tiles(7680, 2688, 1856, 8, jnp.bfloat16)
    assert up == gm.Tiles(256, 2688, 1856, 896, 1856)
    down = gm.grouped_tiles(7680, 1856, 2688, 8, jnp.bfloat16)
    assert down == gm.Tiles(256, 1856, 2688, 1856, 896)
    assert gm._dweights_vmem(256, 1856, 2688, 2) > gm.VMEM_BUDGET
    assert gm._dweights_vmem(256, 1856, 896, 2) <= gm.VMEM_BUDGET
    assert gm._divisors(1856) == [1856]
    assert gm._divisors(2688) == [2688, 896, 384, 128]
    assert gm.grouped_tiles(7680, 2688, 1824, 8, jnp.bfloat16) is None


@pytest.mark.parametrize("kind", ["forward", "rows_gradient",
                                  "weights_gradient"])
def test_kernels_at_width_1856_against_ragged_dot(kind):
    """The nemotron_h expert's two products at their published widths
    (fewer rows and experts), in the interpreter, on the tiles the rule
    gives them: forward and both gradients against `lax.ragged_dot`, the
    last 64 columns of the 1856 as good as the first."""
    rows, groups, sizes = 512, 2, [200, 250]
    group_sizes = jnp.asarray(sizes, jnp.int32)
    live = (jnp.arange(rows) < sum(sizes))[:, None]
    for k_, n_ in ((2688, 1856), (1856, 2688)):
        ks = jax.random.split(jax.random.key(k_), 3)
        # (bfloat16, the cell's: in float32 a whole width of 1856 beside
        # 2688 passes the budget and the rule hands the shape back)
        dtype = jnp.bfloat16
        lhs = jnp.where(live, jax.random.normal(ks[0], (rows, k_)),
                        0.0).astype(dtype)
        rhs = (jax.random.normal(ks[1], (groups, k_, n_))
               * k_ ** -0.5).astype(dtype)
        g = jnp.where(live, jax.random.normal(ks[2], (rows, n_)),
                      0.0).astype(dtype)
        tiles = gm.grouped_tiles(rows, k_, n_, groups, dtype)
        assert tiles is not None and 1856 in (tiles.fwd_k, tiles.drows_n)

        def loss(product):
            return lambda a, b: jnp.sum(jnp.where(
                live, product(a, b, group_sizes), 0.0).astype(jnp.float32)
                * g.astype(jnp.float32))
        if kind == "forward":
            got = gm.grouped_matmul(lhs, rhs, group_sizes)
            want = lax.ragged_dot(lhs, rhs, group_sizes)
            got, want = (np.where(live, _f32(t), 0) for t in (got, want))
        else:
            arg = 0 if kind == "rows_gradient" else 1
            got = _f32(jax.grad(loss(gm.grouped_matmul), arg)(lhs, rhs))
            want = _f32(jax.grad(loss(lax.ragged_dot), arg)(lhs, rhs))
            if arg == 0:
                got, want = (np.where(live, t, 0) for t in (got, want))
        _close(got, want, dtype)
        # the columns a tile of 128 would have dropped
        tail = (slice(None), slice(1792, 1856))
        if kind == "forward" and n_ == 1856:
            assert np.abs(got[tail]).max() > 0.1
        if kind == "rows_gradient" and k_ == 1856:
            assert np.abs(got[tail]).max() > 0.1
    assert bps.get_metrics()["bps_grouped_kernel"] == 1
    assert bps.get_metrics()["bps_grouped_tile_dweights_n"] == 896
