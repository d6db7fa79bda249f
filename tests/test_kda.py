"""`ops/kda.py`: the chunked delta-rule scan, its kernels in the Pallas
interpreter and its `jnp` form, held to the recurrence a position at a
time: result and the gradients of q, k, v, g and beta."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from byteps_tpu.ops import kda

FORMS = {"kernel": kda.kda_scan, "jnp": kda.kda_scan_jnp}


def recurrence(q, k, v, g, beta):
    """S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} + beta k v^T; o_t =
    S_t^T q_t, a head and a position at a time, float32."""
    B, S, W = q.shape
    H = beta.shape[-1]
    K, V = W // H, v.shape[-1] // H

    def head(q, k, v, g, beta):                         # [S, .]
        q, k = kda._normed(q, k)

        def step(state, x):
            q, k, v, g, b = x
            state = jnp.exp(g)[:, None] * state
            state = state - b * jnp.outer(k, k @ state) + b * jnp.outer(k, v)
            return state, state.T @ q
        return lax.scan(step, jnp.zeros((K, V)), (q, k, v, g, beta))[1]

    def split(t, w):
        return t.reshape(B, S, H, w).transpose(0, 2, 1, 3)
    o = jax.vmap(jax.vmap(head))(split(q, K), split(k, K), split(v, V),
                                 split(g, K), beta.transpose(0, 2, 1))
    return o.transpose(0, 2, 1, 3).reshape(B, S, H * V)


def operands(B, S, H, K, seed=0, decay=1.0):
    keys = jax.random.split(jax.random.key(seed), 6)

    def normal(i, w):
        return jax.random.normal(keys[i], (B, S, H * w), jnp.float32)
    g = -decay * jax.nn.softplus(normal(3, K))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (B, S, H)))
    return (normal(0, K), normal(1, K), normal(2, K), g, beta), normal(5, K)


def everything(fn, args, ct):
    with jax.default_matmul_precision("highest"):
        o, pull = jax.vjp(fn, *args)
        return (o, *pull(ct))


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# (heads, head size) -> heads a program of the forward and of the
# backward kernel holds: the rule engages on fours (forward) or pairs of
# heads of whole 128-lane tiles
PROGRAMS = {(2, 32): (1, 1), (2, 128): (2, 2), (4, 128): (4, 2),
            (3, 128): (1, 1)}


@pytest.mark.parametrize("heads,size", PROGRAMS)
@pytest.mark.parametrize("form", FORMS)
def test_both_forms_are_the_recurrence_with_its_five_gradients(form, heads,
                                                               size):
    """Two sequences of three chunks: the batch's second sequence starts
    from a zero state (the recurrence is taken a sequence at a time).
    The kernels in all their programs, four and two heads a program and
    the fall-back of one."""
    assert tuple(kda.heads_per_program(heads, size, size, backward)
                 for backward in (False, True)) == PROGRAMS[heads, size]
    args, ct = operands(2, 192, heads, size)
    want = everything(recurrence, args, ct)
    got = everything(FORMS[form], args, ct)
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert rel(a, b) < 2e-5, (name, rel(a, b))


@pytest.mark.parametrize("heads,size", ((1, 32), (2, 128)))
@pytest.mark.parametrize("form", FORMS)
def test_decays_of_ten_a_step_for_a_whole_chunk_stay_finite_and_right(
        form, heads, size):
    """g near -10 at every position and channel: a chunk's cumulative sum
    reaches -640, and `exp(-G)` alone is infinite from the ninth row on.
    Every exponent here is a difference that is never positive."""
    (q, k, v, g, beta), ct = operands(1, 128, heads, size, seed=1)
    g = -10.0 + 0.5 * g
    assert float(jnp.exp(-jnp.cumsum(g[0, :64], 0)).max()) == float("inf")
    want = everything(recurrence, (q, k, v, g, beta), ct)
    got = everything(FORMS[form], (q, k, v, g, beta), ct)
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert bool(jnp.isfinite(a).all())
        # g's gradient is 1e-5 of the others here (what a position adds is
        # gone a step later), left of float32 sums that cancel
        assert rel(a, b) < (1e-2 if name == "dg" else 2e-5), name


def test_a_chunk_of_32_and_a_length_that_is_no_multiple_of_64():
    """96 positions are three chunks of 32; 64 does not divide them and is
    refused, as is a chunk that is no power of two."""
    args, ct = operands(1, 96, 2, 16, seed=2)
    want = everything(recurrence, args, ct)
    for fn in FORMS.values():
        got = everything(functools.partial(fn, chunk=32), args, ct)
        assert max(rel(a, b) for a, b in zip(got, want)) < 2e-5
        with pytest.raises(ValueError, match="chunk"):
            fn(*args, chunk=64)
        with pytest.raises(ValueError, match="chunk"):
            fn(*args, chunk=48)


@pytest.mark.parametrize("heads,size", ((2, 16), (2, 128)))
@pytest.mark.parametrize("form", FORMS)
def test_no_position_reads_a_later_one(form, heads, size):
    """One position's q, k, v, g and beta changed: nothing before it
    moves, and what follows does."""
    args, _ = operands(1, 128, heads, size, seed=3)
    at = 70
    moved = tuple(t.at[:, at].add(1.0) if i < 4 else t.at[:, at].mul(0.5)
                  for i, t in enumerate(args))
    a, b = FORMS[form](*args), FORMS[form](*moved)
    assert bool((a[:, :at] == b[:, :at]).all())
    assert float(jnp.abs(a[:, at:] - b[:, at:]).max()) > 1e-3


@pytest.mark.parametrize("heads", (2, 3, 4))
def test_every_head_lands_at_its_own_place(heads):
    """Every head its own inputs, and head h's alone changed: head h's
    columns of `o` and of the four wide gradients, its column of beta's
    gradient and its chunk states move, no other head's by a bit.  With
    two and four heads a program (2 and 4 heads) as with one (3)."""
    K, at = 128, heads - 1
    args, ct = operands(1, 128, heads, K, seed=6)
    cols = slice(at * K, (at + 1) * K)
    moved = tuple(t.at[..., cols].multiply(0.5) for t in args[:4]) + (
        args[4].at[..., at].multiply(0.5),)
    mine = jnp.arange(heads) == at
    for a, b in zip(everything(kda.kda_scan, args, ct),
                    everything(kda.kda_scan, moved, ct)):
        same = (a == b).reshape(128, heads, -1).all((0, 2))
        assert bool((same == ~mine).all()), same
    (_, a), (_, b) = (kda._fwd_call(*x, 64, True) for x in (args, moved))
    assert a.shape == (1, heads, 2, K, K)
    same = (a[:, :, 1] == b[:, :, 1]).all((0, 2, 3))
    assert bool((same == ~mine).all()) and not bool(a[:, :, 0].any())


def test_the_heads_equations_stand_in_step():
    """`_heads_in_step`: every head's results are the function's own, bit
    for bit, and in the text that is traced equation i of every head
    stands before equation i + 1 of any (calls inlined): the order the
    chip's scheduler overlaps the heads by."""
    def fn(x, y):
        z = kda._solve(jnp.tril(x, -1)) @ y
        return jnp.where(z > 0, jnp.exp(-z), z), kda._hi(y, y).sum()
    keys = jax.random.split(jax.random.key(8), 6)
    heads = [(jax.random.normal(keys[i], (16, 16)),
              jax.random.normal(keys[i + 3], (16, 16))) for i in range(3)]
    for got, args in zip(kda._heads_in_step(fn, heads), heads):
        assert all(bool((a == b).all()) for a, b in zip(got, fn(*args)))

    def text(heads):
        return [e.primitive.name for e in jax.make_jaxpr(
            functools.partial(kda._heads_in_step, fn))(heads).jaxpr.eqns]
    alone = text(heads[:1])
    assert "jit" not in alone and alone.count("dot_general") == 8
    assert text(heads) == [name for name in alone for _ in heads]


def test_bfloat16_operands_round_once_and_the_state_stays_float32():
    """The step's dtypes: q, k, v bfloat16, g and beta float32; o comes
    back bfloat16 within bfloat16's rounding of the float32 recurrence,
    and g's gradient float32."""
    args, ct = operands(1, 128, 2, 32, seed=4)
    low = tuple(t.astype(jnp.bfloat16) for t in args[:3]) + args[3:]
    want = everything(recurrence,
                      tuple(t.astype(jnp.float32) for t in low), ct)
    o, pull = jax.vjp(kda.kda_scan, *low)
    grads = pull(ct.astype(jnp.bfloat16))
    assert o.dtype == jnp.bfloat16 and grads[3].dtype == jnp.float32
    assert grads[4].dtype == jnp.float32
    for a, b in zip((o, *grads), want):
        assert rel(a.astype(jnp.float32), b) < 2e-2


@pytest.mark.parametrize("C", (64, 32))
def test_the_solves_rule_is_autodiff_of_the_doubling_product(C):
    """`_solve`'s rule, inv^T ct inv^T, against autodiff of the ten (eight)
    products it stands for, outside any kernel: a strictly lower n with
    entries up to 1 in size.  The two agree where n can move, below the
    diagonal (off it the doubling product is no inverse)."""
    keys = jax.random.split(jax.random.key(5), 2)
    n = jnp.tril(jax.random.uniform(keys[0], (C, C), minval=-1.0), -1)
    ct = jax.random.normal(keys[1], (C, C))
    inv, pull = jax.vjp(kda._solve, n)
    want, doubled = jax.vjp(kda._solve.fun, n)
    assert bool((inv == want).all())
    (got,), (want,) = pull(ct), doubled(ct)
    assert got.dtype == jnp.float32
    assert rel(jnp.tril(got, -1), jnp.tril(want, -1)) < 1e-5


@pytest.mark.parametrize("backward,products", ((False, 10), (True, 12)))
def test_full_precision_products_of_a_chunks_traced_body(backward, products):
    """The solve's ten doubling products in either body, and two more for
    its gradient in the backward one (autodiff of the ten gave twenty)."""
    assert kda.solve_products(64, 128, 128, backward) == products


def test_state_bytes_and_gauges():
    from byteps_tpu.common import telemetry
    assert kda.state_bytes(1, 32, 32768, 128, 128) == 32 * 512 * 65536
    assert [kda.heads_per_program(*a) for a in (
        (32, 128, 128), (32, 128, 64), (3, 128, 128), (2, 32, 32),
        (2, 256, 128), (6, 128, 128))] == [4, 1, 1, 1, 2, 2]
    assert kda.heads_per_program(32, 128, 128, backward=True) == 2
    kda.record(4, 1, 32, 32768, 128, 128)
    text = telemetry.get_registry().render_prometheus()
    for line in ("bps_kda_scan_layers 4", "bps_kda_chunk 64",
                 "bps_kda_state_bytes 1073741824", "bps_kda_kernel 1",
                 "bps_kda_bwd_solve_products 12",
                 'bps_kda_heads_per_program{call="fwd"} 4',
                 'bps_kda_heads_per_program{call="bwd"} 2'):
        assert line in text, line
