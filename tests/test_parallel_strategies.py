"""TP / PP / EP strategy tests on the 8-device CPU mesh."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from byteps_tpu.parallel import expert, pipeline, tensor_parallel as tp


def _mesh(axes):
    sizes = {k: v for k, v in axes.items()}
    total = int(np.prod(list(sizes.values())))
    devs = np.array(jax.devices()[:total]).reshape(tuple(sizes.values()))
    return Mesh(devs, tuple(sizes.keys()))


# ---------------------------------------------------------------------------
# Tensor parallel: col+row pair == dense matmul chain.
# ---------------------------------------------------------------------------
def test_megatron_col_row_matches_dense():
    mesh = _mesh({"tp": 8})
    D, F = 16, 32
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, D), jnp.float32)
    w1 = jnp.asarray(rng.randn(D, F), jnp.float32)
    w2 = jnp.asarray(rng.randn(F, D), jnp.float32)
    b2 = jnp.asarray(rng.randn(D), jnp.float32)
    expect = jax.nn.relu(x @ w1) @ w2 + b2

    def shard_fn(x, w1l, w2l, b2):
        h = jax.nn.relu(tp.col_parallel_dense(x, w1l))
        return tp.row_parallel_dense(h, w2l, b2)

    out = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(None, "tp"), P("tp", None), P()),
        out_specs=P(), check_vma=False))(x, w1, w2, b2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_tp_split_gather_roundtrip():
    mesh = _mesh({"tp": 8})
    x = jnp.arange(64.0).reshape(4, 16)

    def f(x):
        return tp.tp_all_gather(tp.tp_split(x, axis=1), axis=1)

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(),
                                out_specs=P(), check_vma=False))(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


# ---------------------------------------------------------------------------
# Pipeline: GPipe over 'pp' == running all layers sequentially.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("num_microbatches", [2, 4])
def test_gpipe_matches_sequential(num_microbatches):
    mesh = _mesh({"pp": 4})
    L, D = 8, 16   # 8 layers, 2 per stage
    rng = np.random.RandomState(1)
    ws = jnp.asarray(rng.randn(L, D, D) / np.sqrt(D), jnp.float32)
    x = jnp.asarray(rng.randn(8, D), jnp.float32)

    def layer(w, h):
        return jnp.tanh(h @ w)

    # sequential reference
    ref = x
    for i in range(L):
        ref = layer(ws[i], ref)

    def stage_fn(stage_ws, h):
        def body(h, w):
            return layer(w, h), None
        h, _ = jax.lax.scan(body, h, stage_ws)
        return h

    staged = pipeline.shard_stage_params(ws, 4)  # [4, 2, D, D]

    def run(staged, x):
        def inner(local_ws, x):
            return pipeline.gpipe_spmd(stage_fn, local_ws[0], x,
                                       num_microbatches)
        return jax.shard_map(inner, mesh=mesh,
                             in_specs=(P("pp"), P()), out_specs=P(),
                             check_vma=False)(staged, x)

    out = jax.jit(run)(staged, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gpipe_grads_match_sequential():
    mesh = _mesh({"pp": 4})
    L, D = 4, 8
    rng = np.random.RandomState(2)
    ws = jnp.asarray(rng.randn(L, D, D) / np.sqrt(D), jnp.float32)
    x = jnp.asarray(rng.randn(4, D), jnp.float32)

    def layer(w, h):
        return jnp.tanh(h @ w)

    def seq_loss(ws, x):
        h = x
        for i in range(L):
            h = layer(ws[i], h)
        return (h ** 2).sum()

    def stage_fn(stage_ws, h):
        def body(h, w):
            return layer(w, h), None
        h, _ = jax.lax.scan(body, h, stage_ws)
        return h

    staged = pipeline.shard_stage_params(ws, 4)

    def pp_loss(staged, x):
        def inner(local_ws, x):
            y = pipeline.gpipe_spmd(stage_fn, local_ws[0], x, 2)
            return (y ** 2).sum()
        return jax.shard_map(inner, mesh=mesh, in_specs=(P("pp"), P()),
                             out_specs=P(), check_vma=False)(staged, x)

    g_ref = jax.grad(seq_loss)(ws, x)
    g_pp = jax.jit(jax.grad(pp_loss))(staged, x).reshape(g_ref.shape)
    np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_ref),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# Expert parallel: ep-sharded MoE == single-device MoE.
# ---------------------------------------------------------------------------
def test_moe_matches_single_device():
    mesh = _mesh({"ep": 8})
    E, D, F, T = 8, 16, 32, 64
    params = expert.init_moe_params(jax.random.key(0), E, D, F)
    x = jax.random.normal(jax.random.key(1), (T, D))

    # single-device reference on a 1-device ep mesh
    m1 = Mesh(np.array(jax.devices()[:1]).reshape(1), ("ep",))
    y1, aux1 = jax.jit(
        lambda p, x: expert.moe_layer(p, x, m1))(params, x)
    y8, aux8 = jax.jit(
        lambda p, x: expert.moe_layer(p, x, mesh))(params, x)
    # capacity differs (tokens per shard), so compare with generous capacity
    y1g, _ = jax.jit(lambda p, x: expert.moe_layer(p, x, m1, 16.0))(params, x)
    y8g, _ = jax.jit(lambda p, x: expert.moe_layer(p, x, mesh, 16.0))(
        params, x)
    np.testing.assert_allclose(np.asarray(y8g), np.asarray(y1g),
                               rtol=2e-4, atol=2e-5)


def test_moe_capacity_drops_tokens():
    """With capacity factor << 1 some tokens are dropped (zero output),
    never corrupted."""
    mesh = _mesh({"ep": 8})
    E, D, F, T = 8, 8, 16, 64
    params = expert.init_moe_params(jax.random.key(0), E, D, F)
    x = jax.random.normal(jax.random.key(1), (T, D))
    y, aux = jax.jit(
        lambda p, x: expert.moe_layer(p, x, mesh, 0.25))(params, x)
    assert jnp.isfinite(y).all()
    assert float(aux) > 0
    # some rows must be exactly zero (dropped)
    zeros = (np.abs(np.asarray(y)).sum(-1) == 0).sum()
    assert zeros > 0


def test_moe_grads_flow():
    mesh = _mesh({"ep": 8})
    params = expert.init_moe_params(jax.random.key(0), 8, 8, 16)
    x = jax.random.normal(jax.random.key(1), (32, 8))

    def loss(p, x):
        y, aux = expert.moe_layer(p, x, mesh, 8.0)
        return (y ** 2).sum() + 0.01 * aux

    g = jax.jit(jax.grad(loss))(params, x)
    for name, leaf in g.items():
        assert np.isfinite(np.asarray(leaf)).all(), name
    assert float(jnp.abs(g["ffn_in"]).sum()) > 0
