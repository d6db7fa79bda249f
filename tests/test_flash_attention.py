"""Pallas flash-attention kernel: parity with dense attention (fwd + bwd).

Runs in the Pallas interpreter on the CPU mesh, which enforces none of
the chip's tiling rules — so every block used here is one the chip's
compiler accepts too (tests/test_tpu_aot_compile.py compiles the same
kernel for a described v5e)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models.transformer import dense_attention, \
    flash_attention_fn
from byteps_tpu.ops.flash_attention import flash_attention


def _rand(rng, *shape, dtype=np.float32):
    return jnp.asarray(rng.randn(*shape).astype(dtype))


def _ref(q, k, v, causal):
    return dense_attention(q[None], k[None], v[None], causal)[0]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("bh,s,d,bq,bk", [
    (4, 256, 64, 128, 128),
    (2, 256, 64, 128, 256),    # uneven q/k blocks
    (1, 512, 128, 128, 64),
])
def test_forward_parity(causal, bh, s, d, bq, bk):
    rng = np.random.RandomState(0)
    q, k, v = (_rand(rng, bh, s, d) for _ in range(3))
    out = flash_attention(q, k, v, causal, None, bq, bk, True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_ref(q, k, v, causal)),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_gradient_parity(causal):
    rng = np.random.RandomState(1)
    q, k, v = (_rand(rng, 2, 256, 64) for _ in range(3))
    tgt = _rand(rng, 2, 256, 64)

    def loss(attn):
        def f(q, k, v):
            return jnp.sum((attn(q, k, v) - tgt) ** 2)
        return f

    flash = loss(lambda q, k, v: flash_attention(
        q, k, v, causal, None, 128, 128, True))
    ref = loss(lambda q, k, v: _ref(q, k, v, causal))
    gf = jax.grad(flash, (0, 1, 2))(q, k, v)
    gr = jax.grad(ref, (0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        scale = float(jnp.abs(b).max()) + 1e-9
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=1e-4)


def test_bf16_inputs():
    rng = np.random.RandomState(2)
    q, k, v = (_rand(rng, 2, 256, 64).astype(jnp.bfloat16)
               for _ in range(3))
    out = flash_attention(q, k, v, True, None, 128, 128, True)
    assert out.dtype == jnp.bfloat16
    want = _ref(q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=2e-2)


@pytest.mark.parametrize("s,bq,bk", [
    (200, 128, 128),    # blocks don't divide S
    (512, 64, 64),      # 64-row Q tile: the chip's lane rule refuses it
    (256, 64, 128),
    (192, 192, 192),    # whole-axis Q tile that is not 128-aligned
    (256, 128, 32),     # K tile below the 64 multiple
])
def test_rejects_blocks_the_chip_refuses(s, bq, bk):
    """Refused up front, in interpret mode too — an interpret-mode pass
    of a tile the chip's compiler rejects is the trap, not a feature."""
    q = jnp.zeros((1, s, 64))
    with pytest.raises(ValueError, match="cannot tile"):
        flash_attention(q, q, q, False, None, bq, bk, True)


@pytest.mark.parametrize("shape", [
    (2, 2, 100, 32),    # S=100: no 128 block divides it
    (2, 2, 576, 32),    # odd multiple of 64: only the refused tile fits
    (2, 2, 128, 12),    # head_dim not a multiple of 8
])
def test_model_adapter_never_silently_runs_dense(shape):
    """flash_attention_fn (the [B,H,S,D] adapter the transformer uses)
    raises on a shape the kernel can't tile: an explicit flash request
    never degrades to dense attention."""
    q = jnp.zeros(shape)
    with pytest.raises(ValueError, match="divisible by 128"):
        flash_attention_fn(q, q, q, causal=True)


def test_block_override_parity():
    """An explicit block override (attn_block) must not change values; an
    override the kernel would refuse falls back to the auto choice."""
    rng = np.random.RandomState(7)
    q = _rand(rng, 2, 2, 256, 32)
    base = flash_attention_fn(q, q, q, causal=True)
    for blk in (128, 256):                    # valid overrides
        out = flash_attention_fn(q, q, q, causal=True, block=blk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=1e-6)
    for blk in (64, 96, 384):  # not mult-of-128 / doesn't divide S -> AUTO
        out = flash_attention_fn(q, q, q, causal=True, block=blk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=1e-6)
    # Threads through the model config
    from byteps_tpu.models import transformer as tfm
    cfg_b = tfm.get_config("tiny", causal=True, attn_impl="flash",
                           attn_block=128, max_seq_len=256)
    cfg_f = tfm.get_config("tiny", causal=True, attn_impl="flash",
                           max_seq_len=256)
    params = tfm.init_params(jax.random.key(0), cfg_b)
    batch = tfm.synthetic_batch(jax.random.key(1), 2, 256, cfg_b)
    assert abs(float(tfm.loss_fn(params, batch, cfg_b))
               - float(tfm.loss_fn(params, batch, cfg_f))) < 1e-5


def test_auto_block_rule():
    """Pin the auto tile policy.  Square tiles where nothing is masked or
    S > 1024: full-sequence at S <= 512, largest of 512/256/128 dividing
    S beyond.  Causal at S <= 1024: groups of a quarter of the sequence,
    each over all its keys.  0 when S is not a multiple of 128 (the only
    tile that would fit is the 64-row one the chip's compiler refuses).
    Every nonzero answer passes the kernel's own check."""
    from byteps_tpu.models.transformer import flash_auto_block, \
        flash_auto_tiles
    from byteps_tpu.ops.flash_attention import check_blocks
    assert flash_auto_block(128) == 128
    assert flash_auto_block(512) == 512
    assert flash_auto_block(384) == 384      # mult of 128, <= 512
    assert flash_auto_block(2048) == 512
    assert flash_auto_block(4096) == 512
    assert flash_auto_block(768) == 256      # 512 doesn't divide
    assert flash_auto_block(640) == 128
    for s in (64, 448, 576, 704, 1088):      # odd multiples of 64
        assert flash_auto_block(s) == 0
        assert flash_auto_tiles(s, True) == (0, 0)
    assert flash_auto_block(100) == 0        # no valid block
    assert flash_auto_block(1000) == 0
    assert flash_auto_tiles(512) == (512, 512)
    assert flash_auto_tiles(1024) == (512, 512)
    assert flash_auto_tiles(1024, True) == (256, 1024)   # GPT-2's
    assert flash_auto_tiles(512, True) == (128, 512)
    assert flash_auto_tiles(128, True) == (128, 128)
    assert flash_auto_tiles(768, True) == (128, 768)
    assert flash_auto_tiles(2048, True) == (512, 512)
    assert flash_auto_tiles(8192, True) == (512, 512)    # the other cells'
    assert flash_auto_block(1024, True) == 256
    for s in range(128, 4097, 128):
        for causal in (False, True):
            check_blocks(s, *flash_auto_tiles(s, causal))


def test_asymmetric_block_parity():
    """block_k decoupled from block (Q tile) must not change values, in
    both tall (bq > bk) and wide (bk > bq) shapes; invalid block_k
    reverts to the Q block, and the pair threads through the config."""
    rng = np.random.RandomState(11)
    q = _rand(rng, 2, 2, 256, 32)
    base = flash_attention_fn(q, q, q, causal=True)
    for bq, bk in ((128, 64), (128, 256), (256, 64)):
        out = flash_attention_fn(q, q, q, causal=True, block=bq,
                                 block_k=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=1e-6)
    out = flash_attention_fn(q, q, q, causal=True, block=128, block_k=96)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               atol=1e-6)
    from byteps_tpu.models import transformer as tfm
    cfg_a = tfm.get_config("tiny", causal=True, attn_impl="flash",
                           attn_block=128, attn_block_k=64)
    cfg_f = tfm.get_config("tiny", causal=True, attn_impl="flash")
    params = tfm.init_params(jax.random.key(0), cfg_a)
    batch = tfm.synthetic_batch(jax.random.key(1), 2, 128, cfg_a)
    assert abs(float(tfm.loss_fn(params, batch, cfg_a))
               - float(tfm.loss_fn(params, batch, cfg_f))) < 1e-5


def test_transformer_end_to_end_parity():
    """Full model: attn_impl='flash' must track 'dense' through loss and
    gradients at bf16 tolerance."""
    from byteps_tpu.models import transformer as tfm
    cfg_f = tfm.get_config("tiny", causal=True, attn_impl="flash")
    cfg_d = tfm.get_config("tiny", causal=True, attn_impl="dense")
    params = tfm.init_params(jax.random.key(0), cfg_f)
    batch = tfm.synthetic_batch(jax.random.key(1), 4, 128, cfg_f)
    lf = float(tfm.loss_fn(params, batch, cfg_f))
    ld = float(tfm.loss_fn(params, batch, cfg_d))
    assert abs(lf - ld) < 2e-3
    gf = jax.grad(lambda p: tfm.loss_fn(p, batch, cfg_f))(params)
    gd = jax.grad(lambda p: tfm.loss_fn(p, batch, cfg_d))(params)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gd)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-3)


def test_flash_under_shard_map():
    """The production path shards batch*heads over dp; the kernel must
    trace inside shard_map with split leading dims."""
    import byteps_tpu as bps
    from jax.sharding import PartitionSpec as P

    mesh = bps.make_mesh()
    rng = np.random.RandomState(4)
    q, k, v = (_rand(rng, 16, 128, 64) for _ in range(3))

    def f(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 64, True)

    sm = jax.jit(jax.shard_map(f, mesh=mesh,
                               in_specs=(P("dp"), P("dp"), P("dp")),
                               out_specs=P("dp"), check_vma=False))
    out = sm(q, k, v)
    want = dense_attention(q[:, None], k[:, None], v[:, None], True)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_streaming_path_parity(causal):
    """The 3D-grid streaming path (used beyond the VMEM budget, where CI
    sizes never land) must match resident and dense bit-for-bit."""
    rng = np.random.RandomState(5)
    q, k, v = (_rand(rng, 2, 256, 64) for _ in range(3))
    tgt = _rand(rng, 2, 256, 64)
    stream = flash_attention(q, k, v, causal, None, 128, 64, True, True)
    resident = flash_attention(q, k, v, causal, None, 128, 64, True, False)
    np.testing.assert_allclose(np.asarray(stream), np.asarray(resident),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(stream),
                               np.asarray(_ref(q, k, v, causal)),
                               atol=2e-5, rtol=1e-4)

    def loss(stream_flag):
        def f(q, k, v):
            return jnp.sum((flash_attention(
                q, k, v, causal, None, 128, 64, True, stream_flag)
                - tgt) ** 2)
        return f

    gs = jax.grad(loss(True), (0, 1, 2))(q, k, v)
    gr = jax.grad(loss(False), (0, 1, 2))(q, k, v)
    for a, b in zip(gs, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_streaming_autoselect_threshold():
    from byteps_tpu.ops.flash_attention import _use_streaming
    small = jnp.zeros((1, 256, 64), jnp.bfloat16)     # 32KB: resident
    big = jnp.zeros((1, 32768, 64), jnp.bfloat16)     # 8MB: streaming
    assert not _use_streaming(small, None)
    assert _use_streaming(big, None)
    assert _use_streaming(small, True)                # explicit override
    assert not _use_streaming(big, False)


# ---------------------------------------------------------------------------
# Sliding window
# ---------------------------------------------------------------------------
def _windowed_ref(q, k, v, window):
    """Dense masked softmax: row i sees the keys i - window < j <= i."""
    s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(q.shape[-1])
    i = jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(q.shape[1])[None, :]
    s = jnp.where((i >= j) & (i - j < window), s, -jnp.inf)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("streaming", [False, True],
                         ids=["resident", "streaming"])
@pytest.mark.parametrize("window,bq,bk", [
    (1, 128, 128),       # a row sees itself alone
    (50, 128, 128),      # smaller than a block
    (128, 128, 128),     # a block
    (200, 128, 64),      # larger than a block, uneven tiles
    (200, 256, 128),
    (512, 128, 128),     # the sequence
    (1000, 128, 128),    # larger than the sequence: causal
], ids=lambda x: str(x))
def test_windowed_kernels_against_dense(window, bq, bk, streaming):
    """Forward and both backward kernels, the blocks a window never sees
    skipped."""
    rng = np.random.RandomState(1)
    q, k, v, g = (_rand(rng, 2, 512, 64) for _ in range(4))

    def flash(q, k, v):
        return (flash_attention(q, k, v, True, None, bq, bk, True, streaming,
                                window) * g).sum()

    def dense(q, k, v):
        return (_windowed_ref(q, k, v, window) * g).sum()

    got, got_g = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
    want, want_g = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-4)


def test_window_none_is_the_kernel_as_it_was():
    """`window=None` adds nothing to the traced program: the same jaxpr
    with the argument and without, unnamed kernels, the same bits; and the
    adapter asks for a window only where it is given one."""
    rng = np.random.RandomState(2)
    q, k, v = (_rand(rng, 2, 256, 64) for _ in range(3))

    def old(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 128, True).sum()

    def new(q, k, v):
        return flash_attention(q, k, v, True, None, 128, 128, True, None,
                               None).sum()

    a = jax.make_jaxpr(jax.grad(old, (0, 1, 2)))(q, k, v)
    b = jax.make_jaxpr(jax.grad(new, (0, 1, 2)))(q, k, v)
    assert str(a) == str(b)
    assert "flash_" not in str(a)
    windowed = jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, True, None, 128, 128, True, None, 64).sum(), (0, 1, 2)))(
            q, k, v)
    assert all(n in str(windowed) for n in
               ("flash_fwd_w64", "flash_dq_w64", "flash_dkv_w64"))
    for x, y in zip(jax.grad(old, (0, 1, 2))(q, k, v),
                    jax.grad(new, (0, 1, 2))(q, k, v)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    q4, k4, v4 = (t[None] for t in (q, k, v))
    assert str(jax.make_jaxpr(lambda *a: flash_attention_fn(*a, True))(
        q4, k4, v4)) == str(jax.make_jaxpr(
            lambda *a: flash_attention_fn(*a, True, window=None))(q4, k4, v4))
    with pytest.raises(ValueError):
        flash_attention(q, k, v, False, None, 128, 128, True, None, 64)


# ---------------------------------------------------------------------------
# The resident schedule: groups of rows, a region each, whole tiles
# ---------------------------------------------------------------------------
def _loss_and_grads(attn, q, k, v, g):
    return jax.value_and_grad(lambda q, k, v: (attn(q, k, v) * g).sum(),
                              (0, 1, 2))(q, k, v)


def _assert_close_to_dense(bh, s, d, bq, bk, window=None, seed=3):
    rng = np.random.RandomState(seed)
    q, k, v, g = (_rand(rng, bh, s, d) for _ in range(4))
    got, got_g = _loss_and_grads(
        lambda q, k, v: flash_attention(q, k, v, True, None, bq, bk, True,
                                        None, window), q, k, v, g)
    dense = (lambda q, k, v: _ref(q, k, v, True)) if window is None else (
        lambda q, k, v: _windowed_ref(q, k, v, window))
    want, want_g = _loss_and_grads(dense, q, k, v, g)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-4)


@pytest.mark.parametrize("bq,bk", [
    (256, 1024),     # the rule's, causal: a program a head, static bounds
    (128, 1024),
    (512, 1024),
    (512, 512),      # the rule's where nothing is masked; as it was
    (256, 512),      # a region narrower than its tile, then whole tiles
    (256, 256),
    (128, 64),       # a region wider than a tile
], ids=lambda x: str(x))
def test_gpt2_shape_against_dense(bq, bk):
    """S = 1024, head size 64, causal: forward and both backward kernels
    at the tiles the rule returns and the ones around them."""
    _assert_close_to_dense(2, 1024, 64, bq, bk)


@pytest.mark.parametrize("d,window", [(64, None), (128, None), (128, 2048)],
                         ids=["granite", "trinity_full", "trinity_sliding"])
def test_s8192_shapes_against_dense(d, window):
    """The other cells' calls cut in batch x heads only: 8192 positions
    in tiles of 512, sixteen programs a head with traced bounds."""
    from byteps_tpu.models.transformer import flash_auto_tiles
    _assert_close_to_dense(1, 8192, d, *flash_auto_tiles(8192, True),
                           window=window)


def _visible(s, causal, window):
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    if not causal:
        return np.ones((s, s), bool)
    return (j <= i) if window is None else (j <= i) & (i - j < window)


# every pair of tiles up to S = 1024; beyond, where a square of booleans
# gets large, the pairs that differ in kind
_SCHEDULES = [(s, bq, bk) for s in (128, 256, 384, 512, 768, 1024)
              for bq in (128, 256, 512, 1024)
              for bk in (64, 128, 256, 512, 1024, s)
              if s % bq == 0 and s % bk == 0] + [
    (s, bq, bk) for s in (2048, 4096)
    for bq, bk in ((512, 512), (256, 512), (512, 128), (128, 64),
                   (256, 1024))] + [(8192, 512, 512)]


@pytest.mark.parametrize("s", sorted({s for s, _, _ in _SCHEDULES}))
def test_bounds_visit_what_a_row_sees_and_mask_what_an_edge_crosses(s):
    """`k_tiles` (forward, dQ) and `q_tiles` (dK/dV), the functions the
    kernels take their bounds from: between them the region and the
    tiles of every group cover each (query, key) pair the mask leaves,
    once; none of them is dead; and a tile is marked for masking exactly
    when the diagonal or the window's edge crosses it."""
    from byteps_tpu.ops import flash_attention as fa
    cases = ((False, None), (True, None), (True, 1), (True, 100),
             (True, 128), (True, 300), (True, 2048), (True, 5000))
    if s > 4096:
        cases = ((True, None), (True, 2048))      # the cells' two masks
    for _, bq, bk in (c for c in _SCHEDULES if c[0] == s):
        rows_tile = fa.dkv_tile(bk)
        for causal, window in cases:
            vis = _visible(s, causal, window)
            by_rows, by_keys = [], []
            for r0 in range(0, s, bq):
                (start, width, edge), (a, b, c) = fa.k_tiles(
                    r0, r0 % bk, bq, bk, s // bk, causal, window)
                by_rows += [(r0, bq, start, width, edge)]
                by_rows += [(r0, bq, t * bk, bk, t < b) for t in range(a, c)]
                (start, height, edge), (b, c, d) = fa.q_tiles(
                    r0, r0 % rows_tile, bq, rows_tile, s // rows_tile,
                    causal, window)
                by_keys += [(start, height, r0, bq, edge)]
                by_keys += [(t * rows_tile, rows_tile, r0, bq, t >= c)
                            for t in range(b, d)]
            for tiles in (by_rows, by_keys):
                seen = np.zeros((s, s), np.int8)
                for q0, h, k0, w, masked in tiles:
                    tile = vis[q0:q0 + h, k0:k0 + w]
                    assert tile.shape == (h, w)
                    assert tile.any(), (bq, bk, window, q0, k0)
                    assert masked == (not tile.all()), (
                        bq, bk, causal, window, q0, k0)
                    seen[q0:q0 + h, k0:k0 + w] += 1
                assert seen.max() == 1 and not (vis & (seen == 0)).any(), (
                    bq, bk, causal, window)
            counts = fa.tile_schedule(s, bq, bk, causal, window)
            assert counts["tiles_computed"] == len(by_rows)
            assert counts["tiles_masked"] == sum(t[4] for t in by_rows)
            assert counts["pairs_needed_share"] == pytest.approx(
                vis.sum() / sum(h * w for _, h, _, w, _ in by_rows))


def test_the_kernels_write_their_gauges_when_a_step_is_traced():
    """GPT-2's attention call, traced and not run: a head's forward
    kernel computes four regions (256 rows over 256, 512, 768 and 1024
    keys), masks all four, and four fifths of their pairs are needed;
    the tiling it replaced computed three tiles of 512 for two thirds."""
    import byteps_tpu as bps
    from byteps_tpu.ops.flash_attention import tile_schedule
    q = jax.ShapeDtypeStruct((2, 4, 1024, 64), jnp.bfloat16)
    jax.eval_shape(jax.grad(
        lambda q, k, v: flash_attention_fn(q, k, v, True).astype(
            jnp.float32).sum(), (0, 1, 2)), q, q, q)
    metrics = bps.get_metrics()
    assert metrics["bps_flash_tiles_computed"] == 4
    assert metrics["bps_flash_tiles_masked"] == 4
    assert metrics["bps_flash_pairs_needed_share"] == pytest.approx(
        524800 / 655360)
    before = tile_schedule(1024, 512, 512, True)
    assert (before["tiles_computed"], before["tiles_masked"]) == (3, 2)
    assert before["pairs_needed_share"] == pytest.approx(0.6673, abs=1e-4)
    long = tile_schedule(8192, 512, 512, True)
    assert (long["tiles_computed"], long["tiles_masked"]) == (136, 16)


# ---------------------------------------------------------------------------
# The streaming path's table of live tiles
# ---------------------------------------------------------------------------
_TABLE_SHAPES = [
    # (s, bq, bk, causal, window)
    (32768, 512, 512, True, 1024),    # the mellum cell's two calls
    (32768, 512, 512, True, None),
    (1024, 128, 128, True, 256),
    (1024, 128, 128, True, None),
    (1024, 256, 128, True, 256),      # rows wider than keys
    (1024, 256, 128, True, None),
    (1024, 128, 256, True, 300),      # keys wider than rows, uneven window
    (1024, 128, 256, True, None),
    (1024, 128, 128, True, 1),        # a row sees itself alone
    (512, 128, 128, True, 2000),      # the window passes the sequence
    (512, 128, 64, False, None),      # no mask: the whole square
]


def _keeps(q0, k0, bq, bk, causal, window):
    """The (query, key) pairs of a tile that `_mask` keeps, from `_mask`
    itself on a tile of ones."""
    from byteps_tpu.ops.flash_attention import _mask
    ones = jnp.ones((bq, bk), jnp.float32)
    if not causal:
        return np.ones((bq, bk), bool)
    return np.asarray(_mask(ones, q0, k0, window)) == 1.0


@pytest.mark.parametrize("by_keys", [False, True], ids=["rows", "keys"])
@pytest.mark.parametrize("s,bq,bk,causal,window", _TABLE_SHAPES,
                         ids=lambda x: str(x))
def test_stream_table_lists_the_live_tiles_once_in_order(s, bq, bk, causal,
                                                         window, by_keys):
    """The table a streaming call's grid walks: every tile the band
    functions call live and no other, once, block by block and tile by
    tile; FIRST and LAST once a block, on its ends; every tile listed
    keeps at least one pair under `_mask`, and no tile left out keeps
    any."""
    from byteps_tpu.ops.flash_attention import (FIRST, LAST, k_band, q_band,
                                                stream_table)
    block, tile, flags = stream_table(s, bq, bk, causal, window,
                                      by_keys=by_keys)
    nq, nk = s // bq, s // bk
    band, blocks, tiles = (q_band, nk, nq) if by_keys else (k_band, nq, nk)
    want = []
    for i in range(blocks):
        first, last = band(i, bq, bk, tiles, causal, window)
        want += [(i, j) for j in range(first, last + 1)]
    assert list(zip(block, tile)) == want
    assert len(set(want)) == len(want)
    assert sorted(want) == want                      # block, then tile
    for i in range(blocks):
        run = [f for b, f in zip(block, flags) if b == i]
        assert [bool(f & FIRST) for f in run] == \
            [True] + [False] * (len(run) - 1)
        assert [bool(f & LAST) for f in run] == \
            [False] * (len(run) - 1) + [True]
    # the mask itself, on the tiles of a few blocks at each end and in
    # the middle (every tile of the small shapes)
    probe = set(range(blocks)) if s <= 1024 else {0, 1, 2, 3, blocks // 2,
                                                   blocks - 1}
    for b, t in zip(block, tile):
        if b not in probe:
            continue
        qi, ki = (t, b) if by_keys else (b, t)
        assert _keeps(qi * bq, ki * bk, bq, bk, causal, window).any(), \
            (qi, ki)
    if s <= 1024:
        # and no live tile is left out: a tile not listed keeps nothing
        listed = {((t, b) if by_keys else (b, t))
                  for b, t in zip(block, tile)}
        for qi in range(nq):
            for ki in range(nk):
                if (qi, ki) not in listed:
                    assert not _keeps(qi * bq, ki * bk, bq, bk, causal,
                                      window).any(), (qi, ki)


@pytest.mark.parametrize("s,bq,bk,causal,window", [
    (1024, 128, 128, True, 256),     # a band of 3 tiles a block: band grid
    (1024, 256, 128, True, 256),     # rows wider than keys
    (1024, 128, 256, True, 300),     # keys wider than rows, uneven window
    (2048, 128, 128, True, 700),     # bands of 1 to 7 tiles on a band grid
    (1024, 128, 128, True, None),    # causal alone: the table grid
    (1024, 128, 256, True, None),
    (512, 128, 128, True, 2000),     # the window passes the sequence: table
    (512, 128, 128, True, 1),        # blocks of one tile: first and last
    (512, 128, 64, False, None),     # no mask: the square, a band grid
    (512, 256, 128, False, None),
], ids=lambda x: str(x))
def test_streaming_grids_against_dense_and_resident(s, bq, bk, causal,
                                                    window):
    """The streaming kernels on their table and band grids, in the interpreter:
    result and all three gradients against dense attention and against
    the resident kernels, causal, windowed and unmasked; the forward
    kernel's running statistics replicated along lanes in scratch, tiles
    of keys of 64, 128 and 256 and a head of 64 under them."""
    rng = np.random.RandomState(3)
    q, k, v, g = (_rand(rng, 2, s, 64) for _ in range(4))

    def flash(streaming):
        def f(q, k, v):
            return (flash_attention(q, k, v, causal, None, bq, bk, True,
                                    streaming, window) * g).sum()
        return f

    def dense(q, k, v):
        if window is None:
            return (_ref(q, k, v, causal) * g).sum()
        return (_windowed_ref(q, k, v, window) * g).sum()

    got, got_g = jax.value_and_grad(flash(True), (0, 1, 2))(q, k, v)
    want, want_g = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    res, res_g = jax.value_and_grad(flash(False), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    np.testing.assert_allclose(float(got), float(res), rtol=1e-5)
    for a, b, c in zip(got_g, want_g, res_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=5e-5, rtol=1e-4)
    out = flash_attention(q, k, v, causal, None, bq, bk, True, True, window)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(flash_attention(q, k, v, causal, None, bq, bk, True,
                                   False, window)), atol=2e-6)


@pytest.mark.parametrize("window,grid", [
    # the triangle: 64 steps for 36 tiles on a band grid, so the table's
    (None, (2, 36)),
    # bands of 3 tiles in 8 blocks, 24 steps for 21 tiles: the band grid
    (256, (2, 8, 3)),
], ids=["table", "band"])
def test_streaming_grid_is_the_table_or_the_band(window, grid):
    """`stream_walk` picks a call's grid: (heads, entries of the table)
    where the bands differ in length, (heads, blocks, longest band) where
    that has few dead steps.  The table's three columns are the call's
    first operands either way."""
    from byteps_tpu.ops.flash_attention import (BAND_GRID_SLACK, _fwd,
                                                stream_walk)
    q = jnp.zeros((2, 1024, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q: _fwd(
        q, q, q, 0.125, True, 128, 128, True, True, window))(q)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    walk = stream_walk(1024, 128, 128, True, window)
    live = len(walk.table[0])
    assert call.params["grid_mapping"].grid == (2, *walk.grid) == grid
    assert (walk.band is None) == (window is None)
    # (a band grid of this call would walk 8 blocks x its longest band)
    longest = max(walk.table[0].count(i) for i in range(8))
    assert (8 * longest <= BAND_GRID_SLACK * live) == (walk.band is not None)
    assert call.params["grid_mapping"].num_index_operands == 3
    assert [v.aval.shape for v in call.invars[:3]] == [(live,)] * 3
    if walk.band is not None:
        # a band grid's steps: the band's tiles, then its last one again
        for i in range(walk.grid[0]):
            first, last = walk.band(i)
            assert [t for b, t in zip(*walk.table[:2]) if b == i] == \
                list(range(first, last + 1))


def test_band_grid_index_maps_repeat_the_last_tile_past_the_band():
    """What keeps a band grid's dead step from copying K and V: past the
    band's end the index map gives the band's last tile again."""
    from byteps_tpu.ops.flash_attention import _walk_specs, stream_walk
    walk = stream_walk(1024, 128, 128, True, 256)
    wide, lanes = _walk_specs(walk, 128, 64, 1)
    # row tile 5 sees the keys 385..767: tiles 3, 4, 5
    assert [int(wide.index_map(0, 5, j)[1]) for j in range(3)] == [3, 4, 5]
    assert [int(wide.index_map(0, 0, j)[1]) for j in range(3)] == [0, 0, 0]
    assert [int(lanes.index_map(7, 1, j)[2]) for j in range(3)] == [0, 1, 1]
    own, _ = _walk_specs(walk, 128, 64, 0)
    assert [int(own.index_map(0, 5, j)[1]) for j in range(3)] == [5, 5, 5]
    # by the keys: key tile 6 (keys 768..895) is seen by rows 768..1023
    walk = stream_walk(1024, 128, 128, True, 256, by_keys=True)
    wide, _ = _walk_specs(walk, 128, 64, 1)
    assert [int(wide.index_map(0, 6, j)[1]) for j in range(3)] == [6, 7, 7]


@pytest.mark.parametrize("s,bq,bk,window,want", [
    # the mellum cell's calls: 64 row tiles; a band of 3 tiles of keys,
    # walked on a band grid
    (32768, 512, 512, 1024, dict(steps=192, live=189, fetched=188)),
    # causal alone: the table grid, the triangle's tiles and no other
    (32768, 512, 512, None, dict(steps=2080, live=2080, fetched=2079)),
    (1024, 128, 128, 256, dict(steps=24, live=21, fetched=20)),
    (1024, 128, 128, None, dict(steps=36, live=36, fetched=35)),
], ids=lambda x: str(x) if not isinstance(x, dict) else "")
def test_stream_schedule_counts_and_gauges(s, bq, bk, window, want):
    """`stream_schedule` for known shapes, by hand: a row tile's band is
    the tiles from its first row's oldest key to its last row's own; a
    causal call's grid is the table of those and every step computes, a
    windowed call's is its blocks times its longest band; the first tile
    of a row tile is copied unless the row tile before ended on it, and a
    step past a band repeats the last tile and copies nothing.  The
    gauges carry the last traced streaming call of each window, under
    the label `window`."""
    import byteps_tpu as bps
    from byteps_tpu.ops.flash_attention import k_band, stream_schedule
    assert stream_schedule(s, bq, bk, True, window) == want
    nq, nk = s // bq, s // bk
    live = 0
    for qi in range(nq):
        first, last = k_band(qi, bq, bk, nk, True, window)
        tiles = [t for t in range(nk)
                 if t * bk <= (qi + 1) * bq - 1
                 and (window is None
                      or (t + 1) * bk - 1 > qi * bq - window)]
        assert tiles == list(range(first, last + 1))
        live += len(tiles)
    assert live == want["live"]
    if s <= 1024:
        q = jnp.zeros((1, s, 64), jnp.float32)
        jax.make_jaxpr(lambda q: flash_attention(
            q, q, q, True, None, bq, bk, True, True, window))(q)
        metrics = bps.get_metrics()
        label = '{window="%s"}' % ("none" if window is None else window)
        assert metrics["bps_flash_stream_steps" + label] == want["steps"]
        assert metrics["bps_flash_stream_live" + label] == want["live"]
        assert metrics["bps_flash_stream_fetched" + label] == want["fetched"]
