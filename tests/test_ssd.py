"""The chunked state-space scan (`byteps_tpu/ops/ssd.py`) in float32
against the recurrence it computes, position by position, and against the
quadratic form (one masked [S, S] product a head): values and the gradient
of every input, both forms of the scan (the kernels in the Pallas
interpreter, and the `jnp` form they are tested against), chunks of 16 and
64, heads that forget at once and heads that hardly forget; the causal
convolution against a loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from byteps_tpu.ops import ssd

B, S, H, P, G, N = 2, 128, 4, 8, 2, 16
INPUTS = ("x", "dt", "A", "B", "C", "D")
# dt * A a position: near 0 the state hardly decays (exp(-0.001) a step),
# far from it a head forgets within a position or two (exp(-8)).
DECAYS = {"near_one": (1e-3, 1.0), "near_zero": (0.5, 16.0),
          "mixed": (None, None)}


def _inputs(decay: str):
    ks = jax.random.split(jax.random.key(7), 7)
    step, rate = DECAYS[decay]
    if step is None:
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) - 2.0)
        A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7))
    else:
        dt = step * jax.random.uniform(ks[1], (B, S, H), minval=0.5,
                                       maxval=1.0)
        A = -rate * jax.random.uniform(ks[2], (H,), minval=0.5, maxval=1.0)
    args = (jax.random.normal(ks[0], (B, S, H, P)), dt, A,
            jax.random.normal(ks[3], (B, S, G, N)),
            jax.random.normal(ks[4], (B, S, G, N)),
            jax.random.normal(ks[5], (H,)))
    return args, jax.random.normal(ks[6], (B, S, H, P))


def recurrence(x, dt, A, Bm, Cm, D):
    bh = jnp.repeat(Bm, H // G, axis=2)
    ch = jnp.repeat(Cm, H // G, axis=2)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (state * jnp.exp(dt_t * A)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t) + D[:, None] * x_t
        return state, y_t

    xs = tuple(t.swapaxes(0, 1) for t in (x, dt, bh, ch))
    _, ys = lax.scan(step, jnp.zeros((B, H, P, N)), xs)
    return ys.swapaxes(0, 1)


def quadratic(x, dt, A, Bm, Cm, D):
    """y_i = sum_{j <= i} exp(sum_{j < k <= i} dt_k A) (C_i . B_j) dt_j x_j
    + D x_i, the whole sequence as one chunk."""
    bh = jnp.repeat(Bm, H // G, axis=2)
    ch = jnp.repeat(Cm, H // G, axis=2)
    cs = jnp.cumsum(dt * A, axis=1)                          # [B, S, H]
    keep = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    decay = jnp.exp(jnp.where(keep[None, :, :, None],
                              cs[:, :, None] - cs[:, None, :], -jnp.inf))
    scores = jnp.einsum("bihn,bjhn->bijh", ch, bh) * decay * dt[:, None]
    return jnp.einsum("bijh,bjhp->bihp", scores, x) + D[:, None] * x


ORACLES = {"recurrence": recurrence, "quadratic": quadratic}


@pytest.fixture(scope="module")
def expected():
    """Value and gradients of each oracle for each kind of decay, made
    once."""
    out = {}
    with jax.default_matmul_precision("highest"):
        for decay in DECAYS:
            args, w = _inputs(decay)
            for name, fn in ORACLES.items():
                out[decay, name] = jax.jit(jax.value_and_grad(
                    lambda *a, fn=fn, w=w: (fn(*a) * w).sum(),
                    argnums=range(6)))(*args)
    return out


@pytest.mark.parametrize("oracle", ORACLES)
@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("impl", ["jnp", "kernel"])
def test_scan_against(expected, impl, chunk, decay, oracle):
    args, w = _inputs(decay)
    with jax.default_matmul_precision("highest"):
        value, grads = jax.jit(jax.value_and_grad(
            lambda *a: (ssd.ssd_scan(*a, chunk=chunk, impl=impl) * w).sum(),
            argnums=range(6)))(*args)
    want_value, want_grads = expected[decay, oracle]
    # Where a head forgets within a position, what reaches A's and dt's
    # gradients is the little that the off-diagonal lets through, e^-2 to
    # e^-16 of the diagonal's terms, which cancel: a small difference of
    # large float32 terms, in the scan (row sums less column sums) and in
    # the quadratic form (one cumulative sum that reaches -1,000) alike.
    tol = 1e-3 if decay == "near_zero" else 2e-5
    np.testing.assert_allclose(float(value), float(want_value), rtol=tol)
    for name, got, want in zip(INPUTS, grads, want_grads):
        err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert err < tol, (name, err)


@pytest.mark.parametrize("chunk", [16, 64])
def test_kernels_are_the_jnp_form(chunk):
    """Values, not only their weighted sum: the two forms of the scan give
    the same y, float32 to rounding, and bfloat16 inputs to bfloat16's."""
    args, _ = _inputs("mixed")
    with jax.default_matmul_precision("highest"):
        a = ssd.ssd_scan(*args, chunk=chunk, impl="kernel")
        b = ssd.ssd_scan(*args, chunk=chunk, impl="jnp")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                               rtol=2e-5)
    low = tuple(t.astype(jnp.bfloat16) if t.ndim == 4 else t for t in args)
    a = ssd.ssd_scan(*low, chunk=chunk, impl="kernel")
    b = ssd.ssd_scan(*low, chunk=chunk, impl="jnp")
    assert a.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=0.25,
                               rtol=0.05)


def test_a_fast_head_neither_overflows_nor_leaks():
    """dt A of -400 a position: every exponent is masked before the exp,
    so nothing is inf or NaN in either pass, and y_t is D x_t plus the
    position's own dt (C . B) x."""
    args, w = _inputs("mixed")
    x, dt, A, Bm, Cm, D = args
    dt, A = jnp.full_like(dt, 25.0), jnp.full_like(A, -16.0)
    for impl in ("jnp", "kernel"):
        value, grads = jax.value_and_grad(
            lambda *a: (ssd.ssd_scan(*a, chunk=16, impl=impl) * w).sum(),
            argnums=range(6))(x, dt, A, Bm, Cm, D)
        assert np.isfinite(float(value))
        assert all(bool(jnp.isfinite(g).all()) for g in grads)
    y = ssd.ssd_scan(x, dt, A, Bm, Cm, D, chunk=16, impl="jnp")
    own = jnp.einsum("bshn,bshn->bsh", jnp.repeat(Cm, H // G, 2),
                     jnp.repeat(Bm, H // G, 2))
    np.testing.assert_allclose(
        np.asarray(y), np.asarray((D[:, None] + (dt * own)[..., None]) * x),
        rtol=1e-4, atol=1e-4)


def test_arguments_are_checked():
    args, _ = _inputs("mixed")
    with pytest.raises(ValueError, match="does not divide"):
        ssd.ssd_scan(*args, chunk=48)
    with pytest.raises(ValueError, match="impl="):
        ssd.ssd_scan(*args, chunk=16, impl="cuda")
    with pytest.raises(ValueError, match="groups"):
        ssd.ssd_scan(args[0], args[1], args[2], args[3][:, :, :1].repeat(3, 2),
                     args[4][:, :, :1].repeat(3, 2), args[5], chunk=16)


def test_state_bytes():
    # the published widths: 64 heads x 32 chunks x [64, 128] float32
    assert ssd.state_bytes(1, 64, 8192, 64, 128, 256) == 64 * 32 * 64 * 128 * 4


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("taps", [1, 4])
def test_causal_conv1d_against_a_loop(taps, bias):
    ks = jax.random.split(jax.random.key(3), 3)
    x = np.asarray(jax.random.normal(ks[0], (2, 9, 5)))
    w = np.asarray(jax.random.normal(ks[1], (taps, 5)))
    b = np.asarray(jax.random.normal(ks[2], (5,))) if bias else None
    want = np.zeros_like(x)
    for t in range(x.shape[1]):
        for k in range(taps):
            src = t - (taps - 1) + k
            if src >= 0:
                want[:, t] += w[k] * x[:, src]
        if bias:
            want[:, t] += b
    got = ssd.causal_conv1d(jnp.asarray(x), jnp.asarray(w),
                            None if b is None else jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    # and it sees nothing ahead: a change at position 6 moves 6, 7, 8 only
    x2 = x.copy()
    x2[:, 6] += 1.0
    moved = np.abs(np.asarray(ssd.causal_conv1d(
        jnp.asarray(x2), jnp.asarray(w))) - np.asarray(ssd.causal_conv1d(
            jnp.asarray(x), jnp.asarray(w)))).sum((0, 2)) > 0
    assert not moved[:6].any() and moved[6]


@pytest.mark.parametrize("impl", ["jnp", "kernel"])
def test_eight_groups_and_chunks_of_128_against_the_recurrence(impl):
    """The nemotron_h cell's form of the scan (8 groups of 8 heads, a
    block of heads a group, chunks of 128; the head's and the state's
    sizes cut): values and every gradient against the recurrence, in
    which head h reads group h // 8, and against the `jnp` form; and one
    group's B and C given to all heads reads far off."""
    b, s, h, p, g, n = 1, 256, 64, 8, 8, 16
    ks = jax.random.split(jax.random.key(11), 7)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.7))
    args = (jax.random.normal(ks[0], (b, s, h, p)), dt, A,
            jax.random.normal(ks[3], (b, s, g, n)),
            jax.random.normal(ks[4], (b, s, g, n)),
            jax.random.normal(ks[5], (h,)))
    w = jax.random.normal(ks[6], (b, s, h, p))

    def plain(x, dt, A, Bm, Cm, D):
        bh, ch = (jnp.repeat(t, h // g, axis=2) for t in (Bm, Cm))

        def step(state, inp):
            x_t, dt_t, b_t, c_t = inp
            state = (state * jnp.exp(dt_t * A)[..., None, None]
                     + (dt_t[..., None] * x_t)[..., None]
                     * b_t[:, :, None, :])
            return state, (jnp.einsum("bhpn,bhn->bhp", state, c_t)
                           + D[:, None] * x_t)
        xs = tuple(t.swapaxes(0, 1) for t in (x, dt, bh, ch))
        return lax.scan(step, jnp.zeros((b, h, p, n)), xs)[1].swapaxes(0, 1)

    def value_and_grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: (fn(*a) * w).sum(), argnums=range(6)))(*args)
    with jax.default_matmul_precision("highest"):
        want_value, want = value_and_grads(plain)
        value, got = value_and_grads(
            lambda *a: ssd.ssd_scan(*a, chunk=128, impl=impl))
        y = ssd.ssd_scan(*args, chunk=128, impl=impl)
        one_group = ssd.ssd_scan(
            args[0], dt, A, jnp.broadcast_to(args[3][:, :, :1], args[3].shape),
            jnp.broadcast_to(args[4][:, :, :1], args[4].shape), args[5],
            chunk=128, impl=impl)
        y_jnp = ssd.ssd_scan(*args, chunk=128, impl="jnp")
    np.testing.assert_allclose(float(value), float(want_value), rtol=2e-5)
    for name, a, e in zip(INPUTS, got, want):
        err = float(jnp.linalg.norm(a - e) / jnp.linalg.norm(e))
        assert err < 5e-5, (name, err)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_jnp), atol=2e-4,
                               rtol=2e-5)
    assert float(jnp.linalg.norm(one_group - y) / jnp.linalg.norm(y)) > 0.5
    assert ssd._head_block(64, 8) == 8


# (heads, head size, groups, state, chunk): the shapes at which the
# kernels' blocks are whole tiles of the chip, 8 heads of a group a slab
LANE_DENSE = {"p64_one_group": (8, 64, 1, 128, 64),
              "p64_eight_groups_c128": (64, 64, 8, 128, 128),
              "p128": (8, 128, 1, 128, 64)}


@pytest.mark.parametrize("shape", LANE_DENSE)
def test_lane_dense_slabs_are_the_jnp_form(shape):
    """The layouts the kernels read at the published widths: x, y and
    their gradients as [S, H P] with a program's 8 heads a slab of 512
    lanes (1,024 at a head of 128, which a step of the walk takes alone),
    B and C a group's 128 columns of [S, G N]: values and every gradient
    against the `jnp` form, which moves its operands into a layout of its
    own; bfloat16 values to bfloat16's rounding."""
    h, p, g, n, chunk = LANE_DENSE[shape]
    b, s = 1, 2 * chunk
    assert ssd._lane_block(h, p, g, n) == 8 * p
    ks = jax.random.split(jax.random.key(13), 7)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.7))
    args = (jax.random.normal(ks[0], (b, s, h, p)), dt, A,
            jax.random.normal(ks[3], (b, s, g, n)) / n ** 0.5,
            jax.random.normal(ks[4], (b, s, g, n)) / n ** 0.5,
            jax.random.normal(ks[5], (h,)))
    w = jax.random.normal(ks[6], (b, s, h, p))

    def value_and_grads(impl):
        return jax.jit(jax.value_and_grad(
            lambda *a: (ssd.ssd_scan(*a, chunk=chunk, impl=impl) * w).sum(),
            argnums=range(6)))(*args)
    with jax.default_matmul_precision("highest"):
        value, got = value_and_grads("kernel")
        want_value, want = value_and_grads("jnp")
        y = ssd.ssd_scan(*args, chunk=chunk, impl="kernel")
        y_jnp = ssd.ssd_scan(*args, chunk=chunk, impl="jnp")
    np.testing.assert_allclose(float(value), float(want_value), rtol=2e-5)
    for name, a, e in zip(INPUTS, got, want):
        err = float(jnp.linalg.norm(a - e) / jnp.linalg.norm(e))
        assert err < 2e-5, (name, err)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_jnp), atol=2e-4,
                               rtol=2e-5)
    low = tuple(t.astype(jnp.bfloat16) if t.ndim == 4 else t for t in args)
    a = ssd.ssd_scan(*low, chunk=chunk, impl="kernel")
    e = ssd.ssd_scan(*low, chunk=chunk, impl="jnp")
    assert a.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(e, np.float32), atol=0.25,
                               rtol=0.05)


@pytest.mark.parametrize("h,p,g,n,match", [
    (32, 8, 4, 16, "8 heads of 8 make a block of 64, neither a multiple of "
                   "128 nor the whole 256, and 32 heads are more than one "
                   "program walks"),
    (32, 64, 8, 128, "the rows of 4 heads make a block of 4, neither a "
                     "multiple of 8 nor the whole 32"),
    (32, 64, 2, 64, "a group's state of 64 make a block of 64, neither a "
                    "multiple of 128 nor the whole 128"),
    (12, 64, 1, 128, "12 heads a group cannot be walked in blocks of 8")],
    ids=["lanes_of_x", "rows_of_dt", "lanes_of_a_group", "heads_a_group"])
def test_a_block_the_chip_cannot_tile_is_refused_up_front(h, p, g, n, match):
    """Off the interpreter the kernels refuse, before anything is lowered,
    a block of one group's heads that is neither whole tiles of the chip
    nor its array's whole width, where the heads are too many for one
    program to hold them all; the interpreter takes the first three."""
    args = (jnp.zeros((1, 32, h, p)), jnp.ones((1, 32, h)), -jnp.ones((h,)),
            jnp.zeros((1, 32, g, n)), jnp.zeros((1, 32, g, n)),
            jnp.zeros((h,)))
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda *a: ssd.ssd_scan(*a, chunk=16, interpret=False),
                       *args)
    if "cannot be walked" not in match:
        jax.eval_shape(lambda *a: ssd.ssd_scan(*a, chunk=16, interpret=True),
                       *args)


@pytest.mark.parametrize("h,p,g,n,plan", [
    (64, 64, 1, 128, (8, 1)),       # granite-4.0-h-micro: 8 heads a slab
    (64, 64, 8, 128, (8, 1)),       # nemotron_h: a group's 8 heads
    (8, 16, 1, 32, (8, 1)),         # the tiny granite cut: 128 lanes
    (4, 8, 2, 16, (2, 2)),          # this file's shape: all of it a program
    (16, 8, 2, 16, (8, 2))],        # the tiny nemotron_h cut, likewise
    ids=["granite", "nemotron", "tiny_granite", "this_file", "tiny_nemotron"])
def test_a_program_holds_a_groups_block_or_everything(h, p, g, n, plan):
    """`(heads, groups)` of a kernel program, from the shapes alone: one
    group's block of 8 heads where the chip tiles its slab, every head of
    every group (each array's whole width a block) where it does not and
    the heads are few; and nothing refused."""
    assert ssd._plan(h, p, g, n) == (*plan, None)
    assert ssd._lane_block(h, p, g, n) == plan[0] * plan[1] * p


@pytest.mark.parametrize("dtype,heads", [(jnp.bfloat16, 8), (jnp.float32, 2)],
                         ids=["bfloat16", "float32"])
def test_the_forward_walk_by_dtype(dtype, heads):
    """The forward kernel has a program's 8 heads of 64 in its text in
    bfloat16 and walks pairs in float32, whose text Mosaic is four times
    as long over; the backward kernel walks pairs in both."""
    assert ssd._written_out(8, 64, dtype) == heads
    assert ssd._tile_heads(8, 64) == 2


def test_a_replaced_cumsum_reaches_kernels_already_traced():
    """The kernels' calls are traced once a process and shape
    (`_fwd_call`, `_bwd_call` under `jax.jit`); the cumulative sums are
    taken outside them, so a `_cumsum` replaced AFTER a first call, as
    the benchmark's broken variant replaces it, still changes the
    result and the gradients."""
    args, w = _inputs("mixed")

    def value_and_grads():
        return jax.value_and_grad(
            lambda *a: (ssd.ssd_scan(*a, chunk=16, impl="kernel") * w).sum(),
            argnums=(0, 1))(*args)
    value, (dx, ddt) = value_and_grads()
    size = ssd._fwd_call._cache_size(), ssd._bwd_call._cache_size()
    again, _ = value_and_grads()
    assert float(again) == float(value)
    assert (ssd._fwd_call._cache_size(), ssd._bwd_call._cache_size()) == size
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ssd, "_cumsum", lambda a: 0.5 * jnp.cumsum(a, axis=-1))
        broken, (bx, bdt) = value_and_grads()
    assert (ssd._fwd_call._cache_size(), ssd._bwd_call._cache_size()) == size
    assert abs(float(broken) - float(value)) > 1e-2 * abs(float(value))
    for a, e in ((bx, dx), (bdt, ddt)):
        assert float(jnp.linalg.norm(a - e) / jnp.linalg.norm(e)) > 1e-2
