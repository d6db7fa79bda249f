"""Compression subsystem tests.

Strategy mirrors the reference's compression tests (reference:
tests/test_onebit.py, test_topk.py, test_randomk.py, test_dithering.py):
re-implement each compressor independently in numpy — including the exact
PRNG (xorshift32 here; the reference replays its xorshift128+ the same way,
tests/utils.py:31-52) — and assert the on-device compress→decompress equals
the simulation bit-for-bit, then check end-to-end DP training.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import byteps_tpu as bps
from byteps_tpu.ops import compressor as C


# ---------------------------------------------------------------------------
# Independent numpy replicas (no imports from the package internals).
# ---------------------------------------------------------------------------
def np_xorshift32(state: np.ndarray) -> np.ndarray:
    x = state.astype(np.uint32).copy()
    x ^= (x << np.uint32(13)) & np.uint32(0xFFFFFFFF)
    x ^= x >> np.uint32(17)
    x ^= (x << np.uint32(5)) & np.uint32(0xFFFFFFFF)
    return x


def np_seed_state(seed: int, n: int) -> np.ndarray:
    lanes = np.arange(1, n + 1, dtype=np.uint64)
    s = (lanes * np.uint64(2654435761) + np.uint64(seed | 1)) \
        & np.uint64(0xFFFFFFFF)
    s = s.astype(np.uint32)
    s[s == 0] = np.uint32(0x9E3779B9)
    return np_xorshift32(s)


def np_onebit(x: np.ndarray, scaled=True):
    n = x.size
    scale = np.abs(x).sum() / n if scaled else 1.0
    return np.where(x < 0, -scale, scale).astype(np.float32)


def np_topk(x: np.ndarray, k: int):
    idx = np.argsort(-np.abs(x), kind="stable")[:k]
    out = np.zeros_like(x)
    out[idx] = x[idx]
    return out


def np_randomk(x: np.ndarray, k: int, rng_state: np.ndarray):
    rng = np_xorshift32(rng_state)
    u = (rng >> np.uint32(8)).astype(np.float32) * (1.0 / (1 << 24))
    idx = np.minimum((u[:k] * x.size).astype(np.int32), x.size - 1)
    out = np.zeros_like(x)
    np.add.at(out, idx, x[idx])
    return out, rng


def np_dithering(x: np.ndarray, s: int, rng_state: np.ndarray,
                 partition="linear", normalize="max"):
    if normalize == "max":
        norm = np.abs(x).max()
    else:
        norm = np.sqrt((x * x).sum())
    norm = max(norm, np.finfo(np.float32).tiny)
    mag = np.abs(x) / norm
    if partition == "linear":
        levels = np.arange(s + 1, dtype=np.float32) / s
    else:
        levels = np.concatenate(
            [[0.0], 2.0 ** np.arange(-(s - 1), 1, dtype=np.float32)]
        ).astype(np.float32)
    j = np.clip(np.searchsorted(levels, mag, side="right") - 1, 0, s - 1)
    lo, hi = levels[j], levels[j + 1]
    p_up = np.where(hi > lo, (mag - lo) / np.maximum(hi - lo, 1e-30), 0.0)
    rng = np_xorshift32(rng_state[:x.size])
    u = (rng >> np.uint32(8)).astype(np.float32) * (1.0 / (1 << 24))
    level = j + (u < p_up)
    return np.sign(x) * levels[level] * norm, rng


# ---------------------------------------------------------------------------
# Bit-exactness: device compress→decompress == numpy simulation.
# ---------------------------------------------------------------------------
@pytest.fixture
def grad():
    rng = np.random.RandomState(0)
    return rng.randn(1000).astype(np.float32)


def test_onebit_matches_numpy(grad):
    comp = C.OnebitCompressor(scaled=True)
    payload, _ = jax.jit(comp.compress)(jnp.asarray(grad), ())
    out = jax.jit(lambda p: comp.decompress(p, grad.size))(payload)
    np.testing.assert_allclose(np.asarray(out), np_onebit(grad), rtol=1e-6)


def test_onebit_unscaled(grad):
    comp = C.OnebitCompressor(scaled=False)
    payload, _ = comp.compress(jnp.asarray(grad), ())
    out = comp.decompress(payload, grad.size)
    np.testing.assert_array_equal(np.asarray(out),
                                  np_onebit(grad, scaled=False))


def test_onebit_ratio(grad):
    comp = C.OnebitCompressor()
    # 32:1 + scale at the wire's 4096-element tile granularity; sub-tile
    # tensors pay the 512B tile floor (gradient buckets are partition-
    # sized, where the floor is noise — see bitpack.words_len).
    assert comp.payload_bytes(4096) == 4096 // 8 + 4
    assert comp.payload_bytes(64 * 4096) == 64 * 4096 // 8 + 4
    assert comp.payload_bytes(100) == 512 + 4  # tile floor


def test_topk_matches_numpy(grad):
    comp = C.TopkCompressor(k=50)
    payload, _ = jax.jit(comp.compress)(jnp.asarray(grad), ())
    out = comp.decompress(payload, grad.size)
    np.testing.assert_allclose(np.asarray(out), np_topk(grad, 50), rtol=1e-6)


def test_randomk_matches_numpy(grad):
    comp = C.RandomkCompressor(k=100, seed=7)
    st = comp.init_state(grad.size)
    np_rng = np_seed_state(7, 100)
    np.testing.assert_array_equal(np.asarray(st["rng"]), np_rng)
    # two successive compress calls advance the PRNG identically
    for _ in range(2):
        payload, st = jax.jit(comp.compress)(jnp.asarray(grad), st)
        out = comp.decompress(payload, grad.size)
        expect, np_rng = np_randomk(grad, 100, np_rng)
        np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)


@pytest.mark.parametrize("partition,normalize",
                         [("linear", "max"), ("linear", "l2"),
                          ("natural", "max")])
def test_dithering_matches_numpy(grad, partition, normalize):
    comp = C.DitheringCompressor(s=15, seed=3, partition=partition,
                                 normalize=normalize)
    st = comp.init_state(grad.size)
    payload, st = jax.jit(comp.compress)(jnp.asarray(grad), st)
    out = comp.decompress(payload, grad.size)
    expect, _ = np_dithering(grad, 15, np_seed_state(3, grad.size),
                             partition, normalize)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-7)


@pytest.mark.slow
def test_dithering_unbiased():
    """Stochastic rounding must be unbiased in expectation."""
    comp = C.DitheringCompressor(s=4, seed=11)
    x = jnp.full((2000,), 0.3, jnp.float32)
    st = comp.init_state(2000)
    acc = np.zeros(2000, np.float32)
    reps = 200
    for _ in range(reps):
        p, st = jax.jit(comp.compress)(x, st)
        acc += np.asarray(comp.decompress(p, 2000))
    # levels around 0.3/1.0*4=1.2 -> 0.25/0.5; mean must approach 0.3
    assert abs(acc.mean() / reps - 0.3) < 0.01


# ---------------------------------------------------------------------------
# Decorators.
# ---------------------------------------------------------------------------
def test_error_feedback_corrects(grad):
    """EF: error accumulates what compression dropped; over repeated steps on
    a constant gradient the average transmitted value approaches the truth."""
    # Steady-state EF error per element scales like sum(|g|)/k (time between
    # selections), so avg-transmitted -> grad at rate (sum|g|/k)/steps.
    comp = C.ErrorFeedback(C.TopkCompressor(k=100))
    st = comp.init_state(grad.size)
    total = np.zeros_like(grad)
    steps = 400
    cjit = jax.jit(comp.compress)
    djit = jax.jit(lambda p: comp.decompress(p, grad.size))
    for _ in range(steps):
        payload, st = cjit(jnp.asarray(grad), st)
        total += np.asarray(djit(payload))
    np.testing.assert_allclose(total / steps, grad, atol=0.06)


def test_momentum_accumulates(grad):
    comp = C.NesterovMomentum(C.OnebitCompressor(scaled=False), mu=0.5)
    st = comp.init_state(grad.size)
    _, st = comp.compress(jnp.asarray(grad), st)
    # m = 0.5*0 + g = g
    np.testing.assert_allclose(np.asarray(st["mom"]), grad, rtol=1e-6)
    _, st2 = comp.compress(jnp.asarray(grad), st)
    np.testing.assert_allclose(np.asarray(st2["mom"]), 1.5 * grad, rtol=1e-6)


# ---------------------------------------------------------------------------
# Registry.
# ---------------------------------------------------------------------------
def test_registry_layering():
    c = C.create({"compressor": "onebit", "ef": "vanilla",
                  "momentum": "nesterov"})
    assert isinstance(c, C.NesterovMomentum)
    assert isinstance(c.inner, C.ErrorFeedback)
    assert isinstance(c.inner.inner, C.OnebitCompressor)
    # server skips momentum (reference: compressor_registry.cc:49-52)
    s = C.create({"compressor": "onebit", "ef": "vanilla",
                  "momentum": "nesterov"}, server=True)
    assert isinstance(s, C.ErrorFeedback)


def test_registry_reference_style_kwargs():
    """Configs written for the reference plumb through unchanged
    (reference: byteps/mxnet/__init__.py:236-317 key names)."""
    c = C.create({"byteps_compressor_type": "randomk",
                  "byteps_compressor_k": "8", "k": 8, "seed": 1})
    assert isinstance(c, C.RandomkCompressor)
    assert c.k == 8
    with pytest.raises(ValueError):
        C.create({"compressor": "nope"})


# ---------------------------------------------------------------------------
# Distributed: compressed all-reduce over the 8-device mesh.
# ---------------------------------------------------------------------------
def _run_compressed_allreduce(tree, comp, mesh, **kw):
    from jax.sharding import PartitionSpec as P
    import functools

    state = C.init_compression_state(tree, comp)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    def f(t, st):
        return C.compressed_tree_all_reduce(t, comp, st, axis_name="dp", **kw)

    return f(tree, state)


def test_compressed_allreduce_identical_inputs(mesh8):
    """All workers hold the same gradient -> sum/size == decompressed value
    of one worker's compression (topk is deterministic)."""
    tree = {"w": jnp.asarray(np.random.RandomState(1).randn(256), jnp.float32)}
    comp = C.TopkCompressor(k=32)
    out, _ = _run_compressed_allreduce(tree, comp, mesh8, average=True)
    expect = np_topk(np.asarray(tree["w"]), 32)
    np.testing.assert_allclose(np.asarray(out["w"]), expect, rtol=1e-5)


def test_compressed_allreduce_onebit_two_way(mesh8):
    """Bidirectional onebit: the pulled value is requantized — every element
    has magnitude == mean(|sum|) and the sign of the summed signs."""
    # 4096 elements: above the expansion gate (reduce.py ships smaller
    # buckets raw, where no requantization happens).
    tree = {"w": jnp.asarray(np.random.RandomState(2).randn(4096),
                             jnp.float32)}
    comp = C.OnebitCompressor(scaled=True)
    out, _ = _run_compressed_allreduce(tree, comp, mesh8, average=False)
    w = np.asarray(out["w"])
    mags = np.unique(np.abs(w).round(5))
    assert mags.size == 1  # single scale after requantization


def test_dp_training_with_compression_converges(mesh8):
    """End-to-end: MLP trains under onebit+EF compression (the reference's
    gradient-compression example, example/mxnet/train_gluon_imagenet_byteps_gc
    in miniature)."""
    from byteps_tpu import models
    params = models.init_mlp(jax.random.key(0), (16, 32, 4))
    comp = C.create({"compressor": "onebit", "ef": "vanilla"})
    opt = bps.DistributedOptimizer(optax.sgd(0.3), inter_compressor=comp,
                                   world=8)
    step = bps.build_train_step(models.mlp_loss, opt, mesh8)
    opt_state = opt.init(params)
    x = jax.random.normal(jax.random.key(1), (32, 16))
    y = (x.sum(-1) > 0).astype(jnp.int32)
    losses = []
    for _ in range(25):
        params, opt_state, loss = step(params, opt_state, (x, y))
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_llama_trains_under_compression(mesh8):
    """The modern-LLM block composes with the compression subsystem: a
    llama-class model (GQA + RoPE + SwiGLU) trains under onebit+EF on the
    dp mesh and the loss decreases."""
    from byteps_tpu.models import transformer as tfm
    cfg = tfm.get_config("llama_tiny")
    params = tfm.init_params(jax.random.key(0), cfg)
    comp = C.create({"compressor": "onebit", "ef": "vanilla"})
    opt = bps.DistributedOptimizer(optax.adam(2e-3), inter_compressor=comp,
                                   world=8)
    step = bps.build_train_step(lambda p, b: tfm.loss_fn(p, b, cfg),
                                opt, mesh8)
    opt_state = opt.init(params)
    toks, tgts = tfm.synthetic_batch(jax.random.key(1), 16, 32, cfg)
    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, (toks, tgts))
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_compression_ratio_reporting():
    tree = {"w": jnp.zeros((4096,), jnp.float32)}
    assert C.compression_ratio(tree, C.OnebitCompressor()) > 30
    assert C.compression_ratio(tree, C.TopkCompressor(k=41)) > 40


def test_per_worker_ef_state_is_sharded(mesh8):
    """Each dp shard must keep its own error-feedback buffer: after one step
    on worker-dependent gradients, the stored error differs across the 8
    slices of the state (reference analog: per-process compressor objects,
    operations.cc:380-385)."""
    from byteps_tpu import models
    params = models.init_mlp(jax.random.key(0), (8, 8, 2))
    comp = C.ErrorFeedback(C.TopkCompressor(k=3))
    opt = bps.DistributedOptimizer(optax.sgd(0.1), inter_compressor=comp,
                                   world=8)
    step = bps.build_train_step(models.mlp_loss, opt, mesh8, donate=False)
    opt_state = opt.init(params)
    x = jax.random.normal(jax.random.key(1), (32, 8))
    y = (x.sum(-1) > 0).astype(jnp.int32)
    _, new_state, _ = step(params, opt_state, (x, y))
    leaves = jax.tree.leaves(new_state)
    # find the error buffer: tiled leading dim = 8 * bucket size
    errs = [l for l in leaves if l.ndim == 1 and l.size % 8 == 0
            and l.size > 8]
    assert errs, "no sharded EF state found"
    e = np.asarray(errs[0]).reshape(8, -1)
    # different workers saw different batch shards -> different errors
    assert not np.allclose(e[0], e[1])


def test_world_auto_derived_from_mesh(mesh8):
    """Omitting world= must still give every shard its full per-worker
    state: build_train_step tiles a world=1 state to the mesh's dp size."""
    from byteps_tpu import models
    params = models.init_mlp(jax.random.key(0), (8, 8, 2))
    comp = C.RandomkCompressor(k=16, seed=5)
    opt = bps.DistributedOptimizer(optax.sgd(0.1), inter_compressor=comp)
    step = bps.build_train_step(models.mlp_loss, opt, mesh8, donate=False)
    opt_state = opt.init(params)   # world defaults to 1
    x = jax.random.normal(jax.random.key(1), (32, 8))
    y = (x.sum(-1) > 0).astype(jnp.int32)
    _, new_state, loss = step(params, opt_state, (x, y))
    assert jnp.isfinite(loss)
    # the rng lanes must have been tiled to 8 x k
    rngs = [l for l in jax.tree.leaves(new_state)
            if l.dtype == jnp.uint32]
    assert rngs and rngs[0].size == 8 * 16


def test_set_lr_scale():
    comp = C.ErrorFeedback(C.TopkCompressor(k=4))
    st = {"opt": (comp.init_state(16),)}
    st2 = C.set_lr_scale(st, 0.5)
    assert float(st2["opt"][0]["lr_scale"]) == 0.5
    # other leaves untouched
    np.testing.assert_array_equal(np.asarray(st2["opt"][0]["error"]),
                                  np.zeros(16, np.float32))


def test_ef_lr_scale_is_one_shot():
    """The reference applies pre_lr/cur_lr ONCE then sets pre_lr = cur_lr
    (vanilla_error_feedback.cc UpdateGradient); the lr_scale entry must be
    consumed by one compress and reset to 1, never keep multiplying every
    later round's fresh error."""
    comp = C.ErrorFeedback(C.TopkCompressor(k=2))
    g = jnp.asarray(np.linspace(-1, 1, 8).astype(np.float32))
    st = comp.init_state(8)
    _, st = comp.compress(g, st)             # error now nonzero
    err = np.asarray(st["error"])
    assert float(np.abs(err).sum()) > 0
    st = C.set_lr_scale(st, 2.0)
    payload, st = comp.compress(g, st)       # applies 2*e once
    corrected = np.asarray(g) + 2.0 * err
    want_err = corrected - np.asarray(comp.decompress(payload, 8))
    np.testing.assert_allclose(np.asarray(st["error"]), want_err,
                               rtol=1e-6)
    assert float(st["lr_scale"]) == 1.0      # consumed (pre_lr = cur_lr)


def test_tiny_buckets_skip_expanding_compression(mesh8):
    """A bucket whose compressed payload would EXCEED its raw bytes (the
    sign stream's 512B tile floor) must ship raw — compression is a
    bandwidth optimization, never an expansion."""
    comp = C.OnebitCompressor()
    n = 100  # 400B raw; onebit wire floor is 516B
    assert comp.payload_bytes(n) > n * 4
    tree = {"w": jnp.linspace(-1.0, 1.0, n)}
    from byteps_tpu.ops.compressor.reduce import (
        compressed_tree_all_reduce, init_compression_state)
    import jax as _jax
    from jax.sharding import PartitionSpec as P

    def f(t):
        out, _ = compressed_tree_all_reduce(t, comp, average=False)
        return out

    sm = _jax.jit(jax.shard_map(f, mesh=mesh8, in_specs=(P(),),
                                 out_specs=P(), check_vma=False))
    out = sm(tree)
    # raw path: exact sum (no sign quantization error at all)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               8 * np.asarray(tree["w"]), rtol=1e-6)
