"""`ops/head_norm_rope.py`, the q / k preparation of an attention half
(heads(t), the RMS norm over each head, the rotary turn: one Pallas kernel
each way), in the Pallas interpreter against
`_rope(_rms_norm(heads(t), scale, None, eps), ...)` of
`models/transformer.py`: value, `dt` and `dscale`, both dtypes, a head of
one lane tile and of half a one, every kind of table, a stretch of heads
read at an offset of a wider array; then the rule that sends a model's
call to the kernels, the calls' names and shapes, and the gauges.  The
TURN ALONE (no scale: a model that norms no head, `models/ouro.py`) is a
case of each, against `_rope` on the sliced, transposed heads; and the
normed calls are held to the text they lowered to before it came."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import byteps_tpu as bps
from byteps_tpu.models import mellum
from byteps_tpu.models.transformer import _rms_norm, _rope
from byteps_tpu.ops import head_norm_rope as hn

EPS = 1e-6
THETA = 10000.0
YARN = mellum.Yarn(factor=16.0, original_positions=64, beta_fast=32.0,
                   beta_slow=1.0, attention_factor=1.2772588722239782)


def _turns(kind, B, S, Dh, key):
    """`(what `_rope` is given beyond x and theta, the operator's
    tables)` for a kind of positions."""
    if kind == "none":
        return None, (None, None)
    kw = {}
    if kind == "a_sequence":
        kw["positions"] = jax.random.randint(key, (B, S), 0, 4096)
    elif kind == "one_stream":
        kw["positions"] = jnp.concatenate([jnp.arange(S // 2)] * 2)
    elif kind == "yarn":
        kw.update(inv_freq=mellum.yarn_inv_freq(Dh, THETA, YARN),
                  amplitude=YARN.attention_factor)
    return kw, hn.rope_tables(S, Dh, THETA, **kw)


def _oracle(t, scale, first, heads, turn, head_dim=None):
    """`scale` None: the turn alone of heads `head_dim` wide."""
    B, S, _ = t.shape
    Dh = head_dim if scale is None else scale.shape[0]
    y = t[..., first * Dh:(first + heads) * Dh].reshape(
        B, S, heads, Dh).transpose(0, 2, 1, 3)
    if scale is not None:
        y = _rms_norm(y, scale, None, EPS)
    return y if turn is None else _rope(y, THETA, **turn)


def _operands(B, S, W, Dh, heads, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (B, S, W), dtype),
            (1.0 + 0.3 * jax.random.normal(ks[1], (Dh,))).astype(dtype),
            jax.random.normal(ks[2], (B, heads, S, Dh), dtype), ks[3])


def _rel(got, want):
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 8e-3)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("head_dim", [128, 64])
@pytest.mark.parametrize("first,heads", [(0, 4), (4, 2), (6, 2)],
                         ids=["queries", "keys_behind_them", "the_last_two"])
@pytest.mark.parametrize("kind,normed", [
    ("none", True), ("rows", True), ("one_stream", True),
    ("a_sequence", True), ("yarn", True),
    ("rows", False), ("a_sequence", False)], ids=lambda v: {True: "normed", False: "turn_alone"}.get(
        v, v))
def test_the_kernels_are_the_norm_and_the_turn_of_each_head(
        kind, normed, first, heads, head_dim, dtype, tol):
    """[2, 64, 8 heads] in blocks of 32 rows: two row blocks a sequence,
    the stretch read at its lane offset.  float32 to rounding; bfloat16
    to the ONE rounding the kernel makes where the jnp form makes two (the
    turn alone: one, as `_rope` makes one).  `normed_and_turned_jnp` IS
    the oracle, bit for bit.  The turn alone has no scale: value and `dt`
    against `_rope` on the sliced, transposed heads."""
    B, S = 2, 64
    t, scale, g, key = _operands(B, S, 8 * head_dim, head_dim, heads, dtype)
    turn, tables = _turns(kind, B, S, head_dim, key)

    def value_and_grads(fn):
        if not normed:
            y, vjp = jax.vjp(lambda t: fn(t, None), t)
            return (y, *vjp(g))
        y, vjp = jax.vjp(fn, t, scale)
        return (y, *vjp(g))
    got = value_and_grads(lambda t, scale: hn.head_norm_rope(
        t, scale, *tables, eps=EPS, first=first, heads=heads, block_rows=32))
    want = value_and_grads(
        lambda t, scale: _oracle(t, scale, first, heads, turn, head_dim))
    old = value_and_grads(lambda t, scale: hn.normed_and_turned_jnp(
        t, scale, *tables, eps=EPS, first=first, heads=heads))
    assert got[0].shape == (B, heads, S, head_dim)
    assert [v.dtype for v in got] == [dtype] * (2 + normed)
    assert [v.shape for v in got[1:]] == [t.shape, scale.shape][:1 + normed]
    for name, a, b, c in zip(("out", "dt", "dscale"), got, want, old):
        assert _rel(a, b) < tol, (name, _rel(a, b))
        np.testing.assert_array_equal(np.asarray(b, np.float32),
                                      np.asarray(c, np.float32), name)
    # nothing of `t` outside the stretch has a gradient
    dt = np.asarray(got[1], np.float32)
    lo, hi = first * head_dim, (first + heads) * head_dim
    assert not dt[..., :lo].any() and not dt[..., hi:].any()
    assert dt[..., lo:hi].any()


@pytest.mark.parametrize("normed", [True, False],
                         ids=["normed", "turn_alone"])
def test_bfloat16_is_within_one_rounding_of_float32_on_the_same_operands(
        normed):
    """Float32 from the load to the store: an element is within half a
    bfloat16 step of the float32 result, where the jnp form, which rounds
    the normed value before it turns it, is up to a whole step off.  The
    turn alone rounds once in either form: no further off than `_rope`."""
    t, scale, _, _ = _operands(1, 32, 256, 128, 2, jnp.bfloat16)
    scale, scale32 = (scale, scale.astype(jnp.float32)) if normed else (
        None, None)
    tables = hn.rope_tables(32, 128, THETA)
    exact = np.asarray(hn.normed_and_turned_jnp(
        t.astype(jnp.float32), scale32, *tables, eps=EPS))
    got = np.asarray(hn.head_norm_rope(t, scale, *tables, eps=EPS),
                     np.float32)
    old = np.asarray(hn.normed_and_turned_jnp(t, scale, *tables, eps=EPS),
                     np.float32)
    step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(exact), 1e-30))) - 7)
    assert (np.abs(got - exact) <= 0.5 * step + 1e-6).all()
    if normed:
        assert np.abs(got - exact).mean() < np.abs(old - exact).mean()
    else:
        assert np.abs(got - exact).mean() <= np.abs(old - exact).mean()


def test_the_turn_alone_takes_queries_and_keys_as_one_stretch_or_as_two():
    """`[q | k | v]` with 4 query and 2 key heads: heads 0 .. 6 in ONE
    call are, bit for bit, the queries' call beside the keys', value and
    `dt`; nothing flows to v's columns either way."""
    t, _, g, _ = _operands(1, 128, 8 * 128, 128, 6, jnp.bfloat16)
    tables = hn.rope_tables(128, 128, THETA)

    def turned(first, heads, g):
        y, vjp = jax.vjp(lambda t: hn.head_norm_rope(
            t, None, *tables, eps=EPS, first=first, heads=heads), t)
        return y, vjp(g)[0]
    both, dt = turned(0, 6, g)
    q, dq = turned(0, 4, g[:, :4])
    k, dk = turned(4, 2, g[:, 4:])
    np.testing.assert_array_equal(*(np.asarray(v, np.float32) for v in (
        both, jnp.concatenate([q, k], axis=1))))
    np.testing.assert_array_equal(*(np.asarray(v, np.float32) for v in (
        dt, dq + dk)))
    assert not np.asarray(dt, np.float32)[..., 6 * 128:].any()
    assert _rel(both, _oracle(t, None, 0, 6, {}, 128)) < 8e-3


def test_the_rule_reads_shapes_alone_and_both_sides_agree(monkeypatch):
    """A head of whole lane tiles over whole tiles of rows goes to the
    kernels, anything else to the jnp form, and `queries_and_keys` gives
    both their stretches."""
    assert hn.takes(32768, 128) and hn.takes(128, 256)
    assert not hn.takes(32768, 64) and not hn.takes(32768, 192)
    assert not hn.takes(64, 128) and not hn.takes(8200, 128)
    seen = []
    kernel, form = hn.head_norm_rope, hn.normed_and_turned_jnp
    monkeypatch.setattr(hn, "head_norm_rope", lambda *a, **k: (
        seen.append("kernel"), kernel(*a, **k))[1])
    monkeypatch.setattr(hn, "normed_and_turned_jnp", lambda *a, **k: (
        seen.append("jnp"), form(*a, **k))[1])
    for S, Dh, which in ((128, 128, "kernel"), (64, 128, "jnp"),
                         (128, 64, "jnp")):
        t, qs, _, _ = _operands(1, S, 4 * Dh, Dh, 2, jnp.float32)
        ks = qs[::-1]
        tables = hn.rope_tables(S, Dh, THETA)
        # with the two scales, and with none (the turn alone)
        for qs, ks in ((qs, ks), (None, None)):
            del seen[:]
            q, k = hn.queries_and_keys(t, qs, ks, *tables, eps=EPS, heads=2,
                                       kv_heads=1)
            assert seen == [which] * 2
            assert _rel(q, _oracle(t, qs, 0, 2, {}, Dh)) < 1e-5
            assert _rel(k, _oracle(t, ks, 2, 1, {}, Dh)) < 1e-5
            assert q.shape == (1, 2, S, Dh) and k.shape == (1, 1, S, Dh)


def test_the_grid_takes_heads_that_start_at_a_multiple_of_their_width():
    assert hn._group(0, 32, 128) == 32 and hn._group(32, 4, 128) == 4
    assert hn._group(6, 4, 128) == 2 and hn._group(5, 3, 128) == 1
    assert hn._group(0, 64, 128) == 32      # MAX_LANES
    assert hn._group(0, 4, 8192) == 1
    assert hn._rows(32768, 256) == 256 and hn._rows(8192 + 128, 256) == 128
    assert hn._rows(48, 256) == 16 and hn._rows(20, 256) == 20


@pytest.mark.parametrize("what,t,scale,tables,first,heads", [
    ("heads_past_the_width", (1, 8, 256), (128,), None, 1, 2),
    ("an_odd_head", (1, 8, 30), (15,), None, 0, 2),
    ("tables_of_another_length", (1, 8, 256), (128,), (16, 64), 0, 2),
    ("tables_of_the_whole_head", (1, 8, 256), (128,), (8, 128), 0, 2),
    ("two_dimensions", (8, 256), (128,), None, 0, 2),
    ("neither_a_scale_nor_tables", (1, 8, 256), None, None, 0, 2),
    ("no_scale_and_heads_past_the_width", (1, 8, 256), None, (8, 64), 1, 2),
    ("no_scale_and_tables_of_another_length", (1, 8, 256), None, (16, 64),
     0, 2),
    ("no_scale_and_no_stretch_to_its_end", (1, 8, 256), None, (8, 64), 2,
     None),
])
def test_operands_that_do_not_fit_are_refused(what, t, scale, tables, first,
                                              heads):
    cs = (None, None) if tables is None else (jnp.zeros(tables),) * 2
    scale = None if scale is None else jnp.zeros(scale)
    for form in (hn.head_norm_rope, hn.normed_and_turned_jnp):
        with pytest.raises(ValueError, match="do not fit"):
            form(jnp.zeros(t), scale, *cs, eps=EPS, first=first, heads=heads)
    with pytest.raises(ValueError, match="do not fit"):
        hn.head_norm_rope(jnp.zeros((1, 8, 256)), jnp.zeros((128,)),
                          jnp.zeros((8, 64)), None, eps=EPS)


@pytest.mark.parametrize("normed", [True, False],
                         ids=["normed", "turn_alone"])
def test_two_calls_under_their_names_and_gauges(normed):
    """What the device trace and the benchmark's readers see: one call
    named `head_norm_rope_fwd` whose one result is 4-D and one
    `head_norm_rope_bwd` whose results are 2-D (`flash_cost.classify`
    takes a 3-D result for a flash kernel's), the backward call given `t`,
    the scale, the tables and the cotangent and nothing of the norm, and
    the gauges of the last traced call.  The turn alone: the same names
    and ranks, no scale either way, and a backward call given the
    cotangent and the tables ALONE (nothing of `t`: the turn is linear);
    the gauges' `call` label tells the form."""
    t, scale, g, _ = _operands(2, 64, 1024, 128, 2, jnp.bfloat16)
    tables = hn.rope_tables(64, 128, THETA)

    def both(t, scale, g):
        y, vjp = jax.vjp(lambda t, scale: hn.head_norm_rope(
            t, scale if normed else None, *tables, eps=EPS, first=4,
            heads=2), t, scale)
        return y, vjp(g)
    calls = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(both)(t, scale, g).jaxpr)
    assert [c.params["name"] for c in calls] == [hn.FWD_NAME, hn.BWD_NAME]
    fwd, bwd = calls
    assert [v.aval.shape for v in fwd.outvars] == [(2, 2, 64, 128)]
    assert [v.aval.shape for v in bwd.outvars] == [
        (128, 256), (8, 128)][:1 + normed]
    assert [v.aval.shape for v in fwd.invars] == [
        (128, 1024), *[(1, 128)] * normed, (64, 64), (64, 64)]
    assert [v.aval.shape for v in bwd.invars] == [
        *[(128, 1024), (1, 128)] * normed, (2, 2, 64, 128), (64, 64),
        (64, 64)]
    metrics = bps.get_metrics()
    fwd, bwd = ("fwd", "bwd") if normed else ("turn_fwd", "turn_bwd")
    assert metrics["bps_head_norm_rope_kernel"] == 1
    assert metrics[f'bps_head_norm_rope_rows{{call="{fwd}"}}'] == 64
    assert metrics[f'bps_head_norm_rope_bytes{{call="{fwd}"}}'] == (
        2 * 2 * 64 * 2 * 128 * 2)
    assert metrics[f'bps_head_norm_rope_bytes{{call="{bwd}"}}'] == (
        (2 + normed) * 2 * 64 * 2 * 128 * 2)


# The first 16 hex digits of sha256 over the lowered text of
# `queries_and_keys` WITH scales at [1, 4096, 5120] (32 query heads, 4 key
# heads of 128), value and gradients, AS PR 64'S TREE lowered it (commit
# aa68454, before the operator learnt the turn alone): in the interpreter,
# and for a TPU under `compile_cache.scopes_in_key()`, as
# `bps.build_train_step` lowers a step (its Mosaic bodies then carry no
# source line).  The sdar, mellum2 and trinity-mini cells' calls must stay
# what they were; a change that means to change them writes its own here.
NORMED_CALLS = {
    ("turned", "interpreter"): "2336e2390546d8ba",
    ("not_turned", "interpreter"): "09a9ddd44b7d9860",
    ("turned", "tpu"): "eae8149d20da707c",
    ("not_turned", "tpu"): "b96ce2a6a7d35bc0",
}


@pytest.mark.parametrize("turned,backend", NORMED_CALLS)
def test_a_normed_call_lowers_to_what_it_lowered_to(turned, backend,
                                                    monkeypatch):
    from byteps_tpu.ops import flash_attention
    from byteps_tpu.utils import compile_cache
    monkeypatch.setattr(flash_attention, "_use_interpret",
                        lambda interpret: backend == "interpreter")
    t = jax.ShapeDtypeStruct((1, 4096, 5120), jnp.bfloat16)
    scale = jax.ShapeDtypeStruct((128,), jnp.float32)
    table = jax.ShapeDtypeStruct((4096, 64), jnp.float32)
    gq = jax.ShapeDtypeStruct((1, 32, 4096, 128), jnp.bfloat16)
    gk = jax.ShapeDtypeStruct((1, 4, 4096, 128), jnp.bfloat16)

    def both(t, qs, ks, cos, sin, gq, gk):
        tables = (cos, sin) if turned == "turned" else (None, None)
        out, vjp = jax.vjp(lambda t, qs, ks: hn.queries_and_keys(
            t, qs, ks, *tables, eps=EPS, heads=32, kv_heads=4), t, qs, ks)
        return out, vjp((gq, gk))
    traced = jax.jit(both).trace(t, scale, scale, table, table, gq, gk)
    if backend == "tpu":
        with compile_cache.scopes_in_key():
            text = traced.lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == 4
    else:
        text = traced.lower().as_text()
    assert "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        NORMED_CALLS[turned, backend])
