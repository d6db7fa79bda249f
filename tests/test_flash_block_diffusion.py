"""The flash kernels' fourth mask (`ops/flash_attention.py`
`block_diffusion`): the two copies of a sequence, clean then noised, in
blocks of beta.  The table of live tiles against the rule written out as
a boolean square (by rows and by keys, FIRST / LAST across a gap, WHOLE on
the tiles no rule crosses), the three kernels in interpret mode against a
dense masked softmax in float32, what `check_blocks` refuses, the gauges,
and the other kinds of call left as they were (their lowered texts are
hashed in `tests/test_joyai.py` and `tests/test_keye.py`; here the
kernels' names and a causal, a windowed and a two-width call's grids)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.ops import flash_attention as fa
from byteps_tpu.ops.flash_attention import FIRST, LAST, WHOLE

# (L, beta, block_q, block_k): rows wider than keys, keys wider than
# rows, a block as long as a tile, an L of three tiles
SHAPES = [(256, 4, 128, 128), (256, 16, 128, 64), (512, 4, 256, 128),
          (256, 128, 128, 128), (384, 8, 128, 128), (512, 64, 128, 256)]
IDS = ["L%d_b%d_%dx%d" % s for s in SHAPES]


def rule(L, beta):
    """Step 3's rule as a boolean [2 L, 2 L], clean copy first."""
    r = np.arange(2 * L)[:, None]
    c = np.arange(2 * L)[None, :]
    row_block, key_block = r % L // beta, c % L // beta
    clean = c < L
    return np.where(r < L, clean & (key_block <= row_block),
                    (clean & (key_block < row_block))
                    | (~clean & (key_block == row_block)))


def test_the_rule_keeps_what_the_paper_counts():
    """Every row sees itself; L^2 + L beta pairs in all: clean on clean
    L^2 / 2 + L beta / 2, noised on clean L^2 / 2 - L beta / 2, noised on
    noised L beta, none clean on noised."""
    L, beta = 256, 4
    keep = rule(L, beta)
    assert keep.diagonal().all()
    assert keep[:L, :L].sum() == (L * L + L * beta) // 2
    assert keep[L:, :L].sum() == (L * L - L * beta) // 2
    assert keep[L:, L:].sum() == L * beta and not keep[:L, L:].any()
    assert keep.sum() == L * (L + beta)


@pytest.mark.parametrize("by_keys", [False, True], ids=["rows", "keys"])
@pytest.mark.parametrize("L,beta,bq,bk", SHAPES, ids=IDS)
def test_the_table_lists_the_tiles_the_rule_keeps(L, beta, bq, bk, by_keys):
    keep, S = rule(L, beta), 2 * L
    block, tile, flags = fa.stream_table(S, bq, bk, False, by_keys=by_keys,
                                         block_diffusion=(L, beta))
    want = {}
    for i in range(S // bq):
        for j in range(S // bk):
            part = keep[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            if part.any():
                want[(j, i) if by_keys else (i, j)] = bool(part.all())
    got = {(b, t): bool(f & WHOLE) for b, t, f in zip(block, tile, flags)}
    assert got == want and len(block) == len(want)     # each once
    # a block's entries follow each other, tiles rising, FIRST and LAST
    # on the ends of its run and nowhere else, gap or none
    assert list(block) == sorted(block)
    gaps = 0
    for b in set(block):
        run = [(t, f) for bb, t, f in zip(block, tile, flags) if bb == b]
        tiles = [t for t, _ in run]
        assert tiles == sorted(tiles)
        assert [bool(f & FIRST) for _, f in run] == [
            n == 0 for n in range(len(run))]
        assert [bool(f & LAST) for _, f in run] == [
            n == len(run) - 1 for n in range(len(run))]
        gaps += tiles != list(range(tiles[0], tiles[-1] + 1))
    # by rows the noised blocks jump from the clean copy to their own
    # tile, by keys the clean blocks from the clean rows to the noised
    if L // max(bq, bk) > 1:
        assert gaps > 0
    walk = fa.stream_walk(S, bq, bk, False, by_keys=by_keys,
                          block_diffusion=(L, beta))
    assert walk.band is None and walk.grid == (len(block),)


def _dense(q, k, v, keep):
    s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
    return jnp.einsum("bqk,bkd->bqd", p, v)


@pytest.mark.parametrize("streaming", [None, True],
                         ids=["resident_size", "streaming"])
@pytest.mark.parametrize("L,beta,bq,bk", SHAPES[:4], ids=IDS[:4])
def test_the_three_kernels_against_a_dense_masked_softmax(L, beta, bq, bk,
                                                          streaming):
    """Forward and the gradients of q, k and v in float32.  A call within
    the resident budget takes the streaming walk too: the same numbers
    whatever `streaming` says."""
    S = 2 * L
    q, k, v, g = (jax.random.normal(key, (2, S, 32), jnp.float32)
                  for key in jax.random.split(jax.random.key(L + beta), 4))
    assert not fa._use_streaming(k, None)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, False, None, bq, bk, True,
                                  streaming, None, (L, beta))
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(flash, q, k, v)
        want, want_vjp = jax.vjp(
            lambda q, k, v: _dense(q, k, v, rule(L, beta)), q, k, v)
        for got, ref in zip((out, *vjp(g)), (want, *want_vjp(g))):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       atol=2e-5, rtol=2e-5)


def test_the_calls_carry_the_mask_in_their_names_and_no_mask_is_an_array():
    L, beta = 256, 4
    q = jax.ShapeDtypeStruct((2, 2 * L, 64), jnp.bfloat16)

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: fa.flash_attention(
            q, k, v, False, None, 128, 128, True, None, None,
            (L, beta)).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)
    jaxpr = jax.make_jaxpr(grads)(q, q, q)

    def walk(jaxpr, found):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
            else:
                # outside the kernels nothing is [2 L, 2 L]
                assert all(getattr(v.aval, "shape", ())[-2:] != (2 * L, 2 * L)
                           for v in eqn.outvars), eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, found)
        return found
    calls = walk(jaxpr.jaxpr, [])
    assert sorted(e.params["name"] for e in calls) == [
        "flash_dkv_bd4", "flash_dq_bd4", "flash_fwd_bd4"]
    # the table is the calls' scalar-prefetch operands: 8 live tiles
    for e in calls:
        assert e.params["grid_mapping"].grid == (2, 8)


@pytest.mark.parametrize("L,beta,bq,bk", [
    (256, 3, 128, 128),        # beta does not divide the tile
    (256, 96, 128, 128),
    (192, 4, 128, 128),        # L is no whole number of tiles
    (256, 4, 512, 128),        # a tile of rows in both copies
    (256, 4, 128, 512),
    (256, 0, 128, 128),
])
def test_check_blocks_refuses_up_front(L, beta, bq, bk):
    with pytest.raises(ValueError):
        fa.check_blocks(2 * L, bq, bk, (L, beta))
    x = jnp.zeros((1, 2 * L, 32), jnp.float32)
    with pytest.raises(ValueError):
        fa.flash_attention(x, x, x, False, None, bq, bk, True, None, None,
                           (L, beta))


def test_the_mask_is_a_kind_of_its_own():
    x = jnp.zeros((1, 512, 32), jnp.float32)
    fa.check_blocks(512, 128, 128, (256, 4))
    with pytest.raises(ValueError):                  # S is not 2 L
        fa.check_blocks(512, 128, 128, (128, 4))
    for causal, window in ((True, None), (True, 128)):
        with pytest.raises(ValueError):
            fa.flash_attention(x, x, x, causal, None, 128, 128, True, None,
                               window, (256, 4))


def test_the_cells_walk_and_its_gauges():
    """2 L = 32,768 in tiles of 512: 528 + 528 + 32 = 1,088 live tiles a
    head where the causal table has 2,080 and the square 4,096; 992 of
    them whole; 268,500,992 needed pairs of 285,212,672 computed."""
    import byteps_tpu as bps
    got = fa.stream_schedule(32768, 512, 512, False,
                             block_diffusion=(16384, 4))
    assert got == {"steps": 1088, "live": 1088, "fetched": 1087,
                   "whole": 992, "pairs_needed_share": 268_500_992
                   / 285_212_672}
    assert fa.stream_schedule(32768, 512, 512, True)["live"] == 2080
    by_keys = fa.stream_table(32768, 512, 512, False, by_keys=True,
                              block_diffusion=(16384, 4))
    assert len(by_keys[0]) == 1088
    # a traced call writes the walk it takes, the other kinds theirs
    x = jnp.zeros((1, 512, 32), jnp.float32)
    jax.make_jaxpr(lambda x: fa.flash_attention(
        x, x, x, False, None, 128, 128, True, None, None, (256, 4)))(x)
    metrics = bps.get_metrics()
    assert metrics["bps_flash_bd_steps"] == metrics["bps_flash_bd_live"] == 8
    assert metrics["bps_flash_bd_whole"] == 2
    assert metrics["bps_flash_bd_pairs_needed_share"] == pytest.approx(
        256 * 260 / (8 * 128 * 128))


# (BH, S, Dk, Dv, block_q, block_k, streaming, window) -> the first 16 hex
# digits of sha256 over the lowered text of a causal call's three kernels
# AS THE TREE BEFORE THE FOURTH MASK lowered it (commit 4c06370; the
# one-width digests are `tests/test_joyai.py`'s, of commit 477a616).
OTHER_KINDS = {
    (2, 512, 128, 128, 128, 128, True, None): "7529d856fccf564e",
    (2, 512, 128, 128, 128, 128, None, 256): "f22bf1768269b9a6",
    (2, 512, 128, 128, 128, 128, True, 256): "5d39529748640bf5",
    (2, 512, 192, 128, 128, 128, True, None): "4c91afda9a114372",
    (2, 512, 192, 128, 128, 128, None, None): "61f56675a9bfa01d",
}


@pytest.mark.parametrize("call", OTHER_KINDS, ids=[
    "causal_streaming", "windowed_resident", "windowed_streaming",
    "two_width_streaming", "two_width_resident"])
def test_a_call_of_another_kind_lowers_to_the_text_it_lowered_to(call):
    import hashlib
    bh, s, dk, dv, bq, bk, streaming, window = call
    q = jax.ShapeDtypeStruct((bh, s, dk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((bh, s, dv), jnp.bfloat16)

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: fa.flash_attention(
            q, k, v, True, None, bq, bk, True, streaming,
            window).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)
    text = jax.jit(grads).lower(q, q, v).as_text()
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        OTHER_KINDS[call])


def test_the_other_kinds_of_call_walk_what_they_walked():
    """A causal, a windowed and an unmasked call's tables carry no WHOLE
    flag and are the bands they were."""
    for causal, window in ((True, None), (True, 256), (False, None)):
        for by_keys in (False, True):
            block, tile, flags = fa.stream_table(1024, 128, 128, causal,
                                                 window, by_keys)
            assert not any(f & WHOLE for f in flags)
            assert set(flags) <= {0, FIRST, LAST, FIRST | LAST}
    assert len(fa.stream_table(1024, 128, 128, True)[0]) == 36
    assert fa.stream_walk(1024, 128, 128, True, 256).grid == (8, 3)
