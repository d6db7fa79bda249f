"""The dropless expert layer (`byteps_tpu/parallel/dropless_moe.py`): no
pair routed to a held expert is lost, whatever the routing, in the result
and in every gradient, through the static buffer and through the exact
path behind it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import byteps_tpu as bps
from byteps_tpu.parallel import dropless_moe as dm

E, K, D, F, T = 32, 4, 16, 8, 96
HELD = (3, 4, 9, 20)            # not a range, not in the router's order
CFG = dm.MoEConfig(num_experts=E, top_k=K, held=HELD, route_scale=2.5,
                   row_multiple=8)
ELSEWHERE = [e for e in range(E) if e not in HELD]


def _weights(seed=0):
    k = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(k[0], (T, D))
    router_w = jax.random.normal(k[1], (D, E)) / 4
    experts = {"gate_w": jax.random.normal(k[2], (len(HELD), D, F)) / 4,
               "up_w": jax.random.normal(k[3], (len(HELD), D, F)) / 4,
               "down_w": jax.random.normal(k[4], (len(HELD), F, D)) / 3}
    return x, router_w, experts


def _plain(x, router_w, experts, cfg, sel):
    """Every held expert on every token, times the token's weight for it."""
    _, w = dm.route(x, router_w, cfg, sel=sel)
    out = jnp.zeros_like(x)
    for slot, e in enumerate(cfg.held):
        coef = jnp.where(sel == e, w, 0.0).sum(-1)
        h = jax.nn.silu(x @ experts["gate_w"][slot]) * (
            x @ experts["up_w"][slot])
        out = out + coef[:, None] * (h @ experts["down_w"][slot])
    return out


def _forced(kind):
    """[T, K] choices: every token's pairs put where `kind` says."""
    t = np.arange(T)
    away = np.stack([np.roll(ELSEWHERE, -i)[:K] for i in t])
    sel = away.copy()
    if kind == "one_held_expert":
        sel[:, 0] = HELD[2]
    elif kind == "spread_evenly":
        sel[:, 0] = np.asarray(HELD)[t % len(HELD)]
    elif kind == "every_pair_held":
        sel = np.stack([np.roll(HELD, -i)[:K] for i in t])
    else:
        assert kind == "none_held"
    return jnp.asarray(sel, jnp.int32)


ROUTINGS = {
    # kind: (pairs on held experts, of which past the buffer)
    "none_held": 0,
    "spread_evenly": T,
    "one_held_expert": T,
    "every_pair_held": T * K,
}


@pytest.mark.parametrize("kind", ROUTINGS)
def test_no_row_is_lost_under_forced_routing(kind):
    x, router_w, experts = _weights()
    sel = _forced(kind)
    rows = CFG.buffer_rows(T)
    assert rows == 64 < T                 # the buffer is smaller than T

    def layer(x, router_w, experts):
        out, routing = dm.held_experts(x, router_w, experts, CFG, sel=sel)
        return (out * jnp.cos(out)).sum(), routing

    def plain(x, router_w, experts):
        out = _plain(x, router_w, experts, CFG, sel)
        return (out * jnp.cos(out)).sum()

    (got, routing), g = jax.jit(jax.value_and_grad(
        layer, (0, 1, 2), has_aux=True))(x, router_w, experts)
    want, g_want = jax.value_and_grad(plain, (0, 1, 2))(x, router_w, experts)
    assert int(routing.held_rows) == ROUTINGS[kind]
    assert int(routing.counts.sum()) == ROUTINGS[kind]
    assert int(routing.overflow) == max(ROUTINGS[kind] - rows, 0)
    if kind == "one_held_expert":
        assert routing.counts.tolist() == [0, 0, T, 0]
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)
    if kind == "none_held":
        out, _ = dm.held_experts(x, router_w, experts, CFG, sel=sel)
        assert not np.asarray(out).any()


@pytest.mark.parametrize("kind,passes", [
    ("none_held", 0), ("spread_evenly", 4), ("every_pair_held", 40)])
def test_exact_path_takes_small_buffers(kind, passes):
    """A routing a little past the first buffer (64 rows) pays for the
    rows past it, 8 at a time, not for a second whole buffer."""
    rows, past = CFG.buffer_rows(T), CFG.past_rows(T)
    assert (rows, past) == (64, 8)
    plan = dm._plan(_forced(kind), CFG)
    assert plan.order.size == T * K == rows + 40 * past
    assert plan.order.size == CFG.sorted_rows(T)
    assert int(dm._past_buffers(rows, past, plan)) == passes
    odd = dm._plan(_forced(kind)[:T - 1], CFG)
    assert (odd.order.size - rows) % past == 0 and odd.order.size >= 380
    assert odd.order.size == CFG.sorted_rows(T - 1)


def test_own_routing_matches_plain_and_counts():
    x, router_w, experts = _weights(1)
    out, routing = dm.held_experts(x, router_w, experts, CFG)
    assert routing.sel.shape == (T, K)
    np.testing.assert_allclose(np.asarray(routing.weights.sum(-1)),
                               CFG.route_scale, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(_plain(x, router_w, experts, CFG, routing.sel)),
        rtol=1e-5, atol=1e-6)
    held = np.isin(np.asarray(routing.sel), HELD).sum()
    assert int(routing.held_rows) == held
    c = dm.counters(routing, T)
    assert float(c["held_rows_per_token"]) == pytest.approx(held / T)
    assert float(c["max_load_over_mean"]) >= 1.0


def test_buffer_rows_and_config_errors():
    cfg = dm.MoEConfig(num_experts=128, top_k=8, held=tuple(range(16)))
    assert cfg.buffer_rows(32768) == 40960       # 1.25 x the even share
    assert cfg.buffer_rows(8192) == 10240
    assert cfg.buffer_rows(4) == 512             # never less than a tile
    # the exact path's buffers: an eighth of the first, in whole tiles
    assert cfg.past_rows(32768) == 5120
    assert cfg.past_rows(8192) == 1536
    assert cfg.past_rows(4) == 512
    whole = dataclasses.replace(cfg, held=tuple(range(128)))
    assert whole.buffer_rows(1024) == 8192       # never more than all
    with pytest.raises(ValueError):
        dm.MoEConfig(num_experts=8, top_k=2, held=(1, 1))
    with pytest.raises(ValueError):
        dm.MoEConfig(num_experts=8, top_k=2, held=(8,))
    with pytest.raises(ValueError):
        dm.MoEConfig(num_experts=8, top_k=9, held=(0,))


def _as_on_the_chip():
    """The grouped product (`ops/grouped_matmul.py` `grouped_matmul`, the
    layer's entry) as its kernels behave on the chip, and the compiler's
    own before them: the rows past the last group are neither read nor
    written, in the product and in both of its gradients.  Here they come
    back as NaN, the worst the chip's memory can hold (found on the chip:
    PERF.md, Findings, PR 29)."""
    real = jax.lax.ragged_dot

    def dead(a, group_sizes):
        return (jnp.arange(a.shape[0]) >= group_sizes.sum())[:, None]

    @jax.custom_vjp
    def ragged_dot(lhs, rhs, group_sizes):
        return jnp.where(dead(lhs, group_sizes), jnp.nan,
                         real(lhs, rhs, group_sizes))

    def fwd(lhs, rhs, group_sizes):
        return ragged_dot(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(residuals, g):
        lhs, rhs, group_sizes = residuals
        gone = dead(lhs, group_sizes)
        _, vjp = jax.vjp(lambda a, b: real(a, b, group_sizes),
                         jnp.where(gone, 0, lhs), rhs)
        d_lhs, d_rhs = vjp(jnp.where(gone, 0, g))
        return jnp.where(gone, jnp.nan, d_lhs), d_rhs, None

    ragged_dot.defvjp(fwd, bwd)
    # (the layer hands the entry its routing's tables too: `walk=`)
    return lambda lhs, rhs, group_sizes, walk=None: ragged_dot(
        lhs, rhs, group_sizes)


@pytest.mark.parametrize("kind", ["spread_evenly", "every_pair_held"])
def test_rows_past_the_last_group_reach_nothing(kind, monkeypatch):
    """The buffer is longer than the pairs it holds; what the kernel
    leaves in the rest must reach neither the result nor any gradient."""
    monkeypatch.setattr(dm.gm, "grouped_matmul", _as_on_the_chip())
    x, router_w, experts = _weights(2)
    sel = _forced(kind)

    def layer(x, router_w, experts):
        out, _ = dm.held_experts(x, router_w, experts, CFG, sel=sel)
        return (out * jnp.cos(out)).sum()

    def plain(x, router_w, experts):
        out = _plain(x, router_w, experts, CFG, sel)
        return (out * jnp.cos(out)).sum()

    got, g = jax.value_and_grad(layer, (0, 1, 2))(x, router_w, experts)
    want, g_want = jax.value_and_grad(plain, (0, 1, 2))(x, router_w, experts)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("kind", ["spread_evenly", "one_held_expert",
                                  "every_pair_held"])
def test_layer_through_the_kernels_at_lane_widths(kind):
    """At widths the kernels tile (multiples of 128) the layer runs them,
    here in the interpreter, through the buffer and the exact path behind
    it, and loses no row in the result or in any gradient."""
    D, F = 128, 256
    cfg = dataclasses.replace(CFG, row_multiple=128)
    k = jax.random.split(jax.random.key(5), 5)
    x = jax.random.normal(k[0], (T, D))
    router_w = jax.random.normal(k[1], (D, E)) / 8
    experts = {"gate_w": jax.random.normal(k[2], (len(HELD), D, F)) / 11,
               "up_w": jax.random.normal(k[3], (len(HELD), D, F)) / 11,
               "down_w": jax.random.normal(k[4], (len(HELD), F, D)) / 16}
    sel = _forced(kind)
    assert cfg.buffer_rows(T) == 128 and cfg.past_rows(T) == 128

    def layer(x, router_w, experts):
        out, _ = dm.held_experts(x, router_w, experts, cfg, sel=sel)
        return (out * jnp.cos(out)).sum()

    def plain(x, router_w, experts):
        out = _plain(x, router_w, experts, cfg, sel)
        return (out * jnp.cos(out)).sum()

    got, g = jax.jit(jax.value_and_grad(layer, (0, 1, 2)))(
        x, router_w, experts)
    assert bps.get_metrics()["bps_grouped_kernel"] == 1
    want, g_want = jax.value_and_grad(plain, (0, 1, 2))(x, router_w, experts)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5)


def _parent_buffer(lo, x, experts, flat_w, plan, k, rows):
    """`_buffer` as it stood before the row-move kernel: XLA's gather by
    token, the masks, and a scatter-add of the weighted results."""
    pair = jax.lax.dynamic_slice_in_dim(plan.order, lo, rows)
    dead = ~(lo + jnp.arange(rows, dtype=jnp.int32) < plan.held_rows)[:, None]
    token = pair // k
    group_sizes = jnp.clip(jnp.minimum(plan.ends, lo + rows)
                           - jnp.maximum(plan.starts, lo), 0)
    xg = jnp.where(dead, 0, x[token])
    y = dm._swiglu_grouped(xg, experts, group_sizes, x.dtype)
    y = jnp.where(dead, 0, y).astype(jnp.float32) * flat_w[pair][:, None]
    return jnp.zeros(x.shape, jnp.float32).at[token].add(y)


@pytest.mark.parametrize("kind", ["spread_evenly", "every_pair_held"])
def test_layer_agrees_with_the_gather_and_scatter_add_it_replaced(
        kind, monkeypatch):
    """Result and every gradient against the parent's formulation, through
    the buffer and the exact path, within float32 rounding: the same
    mathematics, a token's sum now in a fixed order."""
    x, router_w, experts = _weights(4)
    sel = _forced(kind)

    def layer(x, router_w, experts):
        out, _ = dm.held_experts(x, router_w, experts, CFG, sel=sel)
        return (out * jnp.cos(out)).sum(), out

    grad = jax.jit(jax.value_and_grad(layer, (0, 1, 2), has_aux=True))
    (_, got), g = grad(x, router_w, experts)
    assert bps.get_metrics()["bps_moe_move_kernel"] == 1
    monkeypatch.setattr(dm, "_buffer", _parent_buffer)
    (_, want), g_want = jax.jit(jax.value_and_grad(
        layer, (0, 1, 2), has_aux=True))(x, router_w, experts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_want)):
        scale = float(jnp.abs(b).max()) or 1.0
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, rtol=0, atol=4e-6)


def test_unrolled_layers_trace_what_one_layer_traces():
    """Four layers UNROLLED at one shape, each under its own
    `jax.checkpoint`, under `value_and_grad`: the row-move kernel's
    bodies in the process (`bps_moe_move_texts`) number what ONE layer
    leaves, and the counters say what a layer moves."""
    from byteps_tpu.ops import moe_rows
    D, F = 256, 128                # a width no other test of the file has
    cfg = dataclasses.replace(CFG, row_multiple=128)
    k = jax.random.split(jax.random.key(7), 5)
    x = jax.random.normal(k[0], (T, D))
    layers = [{"router_w": jax.random.normal(k[1], (D, E)) / 8,
               "gate_w": jax.random.normal(k[2], (len(HELD), D, F)) / 11,
               "up_w": jax.random.normal(k[3], (len(HELD), D, F)) / 11,
               "down_w": jax.random.normal(k[4], (len(HELD), F, D)) / 16}
              ] * 4

    def loss(layers, x):
        for layer in layers:
            x = x + jax.checkpoint(
                lambda x, p: dm.held_experts(
                    x, p.pop("router_w"), p, cfg)[0])(x, dict(layer))
        return (x * x).sum()

    def bodies(n):
        jax.jit(jax.value_and_grad(loss)).lower(layers[:n], x)
        return moe_rows.texts()

    before = moe_rows.texts()
    one = bodies(1)
    metrics = bps.get_metrics()
    assert metrics["bps_moe_move_texts"] == one
    assert metrics['bps_moe_move_rows{use="gather"}'] in (
        cfg.buffer_rows(T), cfg.past_rows(T))
    assert metrics['bps_moe_move_rows{use="scatter"}'] == T * K
    # rows into a buffer, results back weighted, the rows' gradient back
    # (here the first buffer and the exact path's are one shape)
    assert one - before == 3
    assert bodies(4) == one
    assert bps.get_metrics()["bps_moe_move_texts"] == one



def test_route_and_plan_without_the_compilers_gathers():
    """The weights are `take_along_axis(scores, sel)` bit for bit, in the
    value and in the scores' gradient, and the plan sorts what a lookup
    of each choice's slot would."""
    x, router_w, _ = _weights(6)
    scores = jax.nn.sigmoid(x @ router_w)
    sel = jax.lax.top_k(scores, K)[1]
    probe = jax.random.normal(jax.random.key(8), sel.shape)
    want, g_want = jax.value_and_grad(lambda s: (
        jnp.take_along_axis(s, sel, axis=-1) * probe).sum())(scores)
    got, g = jax.value_and_grad(lambda s: (dm._chosen(s, sel) * probe).sum())(
        scores)
    assert float(got) == float(want)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g_want))
    np.testing.assert_array_equal(
        np.asarray(dm._chosen(scores, sel)),
        np.asarray(jnp.take_along_axis(scores, sel, axis=-1)))
    slot_of = np.full((E,), len(HELD), np.int32)
    slot_of[list(HELD)] = np.arange(len(HELD))
    for kind in ("spread_evenly", "every_pair_held", "none_held"):
        sel = _forced(kind)
        plan = dm._plan(sel, CFG)
        slot = slot_of[np.asarray(sel).reshape(-1)]
        order = np.argsort(slot, kind="stable")
        np.testing.assert_array_equal(np.asarray(plan.order)[:order.size],
                                      order)
        np.testing.assert_array_equal(np.asarray(plan.place)[order],
                                      np.arange(order.size))
        counts = np.bincount(slot, minlength=len(HELD) + 1)[:len(HELD)]
        np.testing.assert_array_equal(np.asarray(plan.ends - plan.starts),
                                      counts)
        assert int(plan.held_rows) == counts.sum()
