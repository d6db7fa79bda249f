"""Collective data-plane tests on the 8-device CPU mesh.

Correctness model: whatever scheduling/bucketing/hierarchy we apply, the
result must equal a plain sum (or mean) across the dp axis — the same
contract the reference's tests assert for push_pull (reference:
tests/test_mxnet.py:39-121).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from byteps_tpu.ops import collectives


def _shmap(f, mesh, in_specs, out_specs):
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


@pytest.fixture
def dp_mesh():
    return Mesh(np.array(jax.devices()), ("dp",))


def _make_tree(seed=0, dtype=jnp.float32):
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 4)
    return {
        "w1": jax.random.normal(ks[0], (33, 17), dtype),
        "b1": jax.random.normal(ks[1], (17,), dtype),
        "w2": jax.random.normal(ks[2], (17, 5), dtype),
        "scalar": jax.random.normal(ks[3], (), dtype),
    }


@pytest.mark.parametrize("average", [True, False])
@pytest.mark.parametrize("partition_bytes", [64, 4 * 1024 * 1024])
def test_bucketed_tree_all_reduce_matches_psum(dp_mesh, average,
                                               partition_bytes):
    # Per-device distinct trees, stacked over dp.
    trees = [_make_tree(seed=i) for i in range(8)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    def step(batch_tree):
        local = jax.tree.map(lambda x: x[0], batch_tree)  # this shard's tree
        return collectives.bucketed_tree_all_reduce(
            local, axis_name="dp", average=average,
            partition_bytes=partition_bytes)

    out = _shmap(step, dp_mesh, (P("dp"),), P())(stacked)
    expect = jax.tree.map(lambda *xs: sum(xs) / (8 if average else 1), *trees)
    for k in expect:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(expect[k]),
                                   rtol=1e-5, atol=1e-5)


def test_bucketed_reduce_handles_mixed_dtypes(dp_mesh):
    trees = []
    for i in range(8):
        k = jax.random.PRNGKey(i)
        trees.append({
            "f32": jax.random.normal(k, (11,), jnp.float32),
            "bf16": jax.random.normal(k, (7, 3), jnp.bfloat16),
        })
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    def step(t):
        local = jax.tree.map(lambda x: x[0], t)
        return collectives.bucketed_tree_all_reduce(local, average=False)

    out = _shmap(step, dp_mesh, (P("dp"),), P())(stacked)
    assert out["f32"].dtype == jnp.float32
    assert out["bf16"].dtype == jnp.bfloat16
    expect = sum(np.asarray(t["f32"]) for t in trees)
    np.testing.assert_allclose(np.asarray(out["f32"]), expect, rtol=1e-5)


def _mixed_tree():
    k = jax.random.PRNGKey(3)
    return {"f32": jax.random.normal(k, (11,), jnp.float32),
            "bf16": jax.random.normal(k, (7, 3), jnp.bfloat16)}


def _psum_transform(buf, bucket_index):
    """A transform that is the plain sum: it selects the packed path and
    makes it what `bucketed_tree_all_reduce` was for every tree before
    leaves were summed in their own shapes."""
    return collectives.all_reduce(buf, "dp")


@pytest.mark.parametrize("average", [True, False], ids=["mean", "sum"])
@pytest.mark.parametrize("make_tree,partition_bytes", [
    (_make_tree, 4 * 1024 * 1024),
    (_make_tree, 64),              # w1 is 2244 B: 36 buckets' worth
    (_mixed_tree, 4 * 1024 * 1024),
    (lambda: {"a": jnp.arange(4.0), "empty": jnp.zeros((0,))}, 64),
], ids=["plain", "leaf_over_partition", "mixed_dtypes", "zero_size_leaf"])
def test_leaves_are_summed_in_their_own_shapes(dp_mesh, make_tree,
                                               partition_bytes, average):
    """Without a transform nothing is packed: the lowered program holds an
    all_reduce a non-empty leaf and no reshape, slice or concatenate, gives
    bit for bit what the packed path gives, and the registry says so."""
    import byteps_tpu as bps
    tree = make_tree()
    nonempty = [l for l in jax.tree.leaves(tree) if l.size]

    def exchange(transform):
        def step(t):
            # distinct per device with no reshape of the harness's own
            w = jax.lax.axis_index("dp") + 1
            local = jax.tree.map(lambda x: x * w.astype(x.dtype), t)
            return collectives.bucketed_tree_all_reduce(
                local, "dp", average=average,
                partition_bytes=partition_bytes, bucket_transform=transform)
        return _shmap(step, dp_mesh, (P(),), P())

    def packed_bytes():
        return bps.get_metrics()["bps_ingraph_exchange_packed_bytes"]

    plain = exchange(None)
    ir = str(plain.lower(tree).compiler_ir(dialect="stablehlo"))
    assert packed_bytes() == 0
    assert bps.get_metrics()["bps_ingraph_exchange_leaves"] == len(nonempty)
    assert ir.count("stablehlo.all_reduce") == len(nonempty)
    for op in ("concatenate", "dynamic_slice", "reshape"):
        assert f"stablehlo.{op}" not in ir, op

    got = plain(tree)
    packed = exchange(_psum_transform)(tree)
    itemsize = jnp.result_type(*nonempty).itemsize
    assert packed_bytes() == sum(l.size for l in nonempty) * itemsize
    for k, leaf in tree.items():
        assert got[k].dtype == leaf.dtype and got[k].shape == leaf.shape
        np.testing.assert_array_equal(np.asarray(got[k], np.float32),
                                      np.asarray(packed[k], np.float32))
        expect = np.asarray(leaf, np.float32) * (4.5 if average else 36)
        np.testing.assert_allclose(np.asarray(got[k], np.float32), expect,
                                   rtol=2e-2 if leaf.dtype == jnp.bfloat16
                                   else 1e-5)


def test_bucket_plan_partitions_and_reverse_priority():
    # 3 leaves of 10 elems at 16-elem buckets (4-byte items, 64B partitions):
    # reversed order -> leaf2 first.
    plan = collectives.BucketPlan([10, 10, 10], partition_bytes=64,
                                  itemsize=4, reverse=True)
    flat = [seg for b in plan.buckets for seg in b]
    # Total coverage, each leaf exactly once.
    covered = {}
    for li, start, ln in flat:
        covered.setdefault(li, 0)
        covered[li] += ln
    assert covered == {0: 10, 1: 10, 2: 10}
    # First segment comes from the last leaf (backward-first priority).
    assert flat[0][0] == 2
    # No bucket exceeds 16 elements.
    for b in plan.buckets:
        assert sum(seg[2] for seg in b) <= 16


def test_bucket_plan_random_property():
    """Randomized invariants over many size mixes: every element of every
    leaf is covered exactly once by contiguous, in-order segments; no
    bucket exceeds the partition capacity; priority order holds."""
    import random
    rng = random.Random(0)
    for trial in range(60):
        sizes = [rng.randint(0, 50) for _ in range(rng.randint(1, 10))]
        pb = rng.choice([4, 8, 32, 128])
        plan = collectives.BucketPlan(sizes, partition_bytes=pb, itemsize=4)
        cap = max(1, pb // 4)
        segs_by_leaf = {}
        for b in plan.buckets:
            assert sum(s[2] for s in b) <= cap, (trial, sizes, pb)
            for li, start, ln in b:
                assert ln > 0
                segs_by_leaf.setdefault(li, []).append((start, ln))
        for li, size in enumerate(sizes):
            segs = segs_by_leaf.get(li, [])
            # in offset order as they come (the packed path appends them),
            # contiguous, non-overlapping, complete
            pos = 0
            for start, ln in segs:
                assert start == pos, (trial, li, segs)
                pos += ln
            assert pos == size, (trial, li, sizes)
        # Priority: first segment of the first bucket comes from the
        # highest-index nonempty leaf (backward-first).
        nonempty = [i for i, s in enumerate(sizes) if s > 0]
        if nonempty:
            assert plan.buckets[0][0][0] == nonempty[-1]


def test_large_leaf_is_split_across_buckets():
    plan = collectives.BucketPlan([100], partition_bytes=64, itemsize=4,
                                  reverse=True)
    assert plan.num_buckets() == 7  # ceil(100/16)
    segs = [seg for b in plan.buckets for seg in b]
    assert segs[0] == (0, 0, 16)
    assert sum(s[2] for s in segs) == 100


def test_hierarchical_all_reduce_matches_global_sum():
    devs = np.array(jax.devices()).reshape(2, 4)
    mesh = Mesh(devs, ("dcn_dp", "ici_dp"))
    x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)

    def step(xs):
        local = xs.reshape(-1)  # this device's (1,16) slice flattened
        return collectives.hierarchical_all_reduce(local, "ici_dp", "dcn_dp")

    out = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(("dcn_dp", "ici_dp")),), out_specs=P(),
        check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x.sum(0)),
                               rtol=1e-6)


def test_hierarchical_tree_all_reduce():
    devs = np.array(jax.devices()).reshape(2, 4)
    mesh = Mesh(devs, ("dcn_dp", "ici_dp"))
    trees = [_make_tree(seed=i) for i in range(8)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    def step(t):
        local = jax.tree.map(lambda x: x[0], t)
        return collectives.hierarchical_tree_all_reduce(
            local, average=True, partition_bytes=128)

    out = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(("dcn_dp", "ici_dp")),), out_specs=P(),
        check_vma=False))(stacked)
    expect = jax.tree.map(lambda *xs: sum(xs) / 8, *trees)
    for k in expect:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(expect[k]),
                                   rtol=1e-5, atol=1e-5)


def test_ring_permute():
    mesh = Mesh(np.array(jax.devices()), ("dp",))

    def step(x):
        return collectives.ring_permute(x, "dp", shift=1)

    x = jnp.arange(8, dtype=jnp.float32)
    out = _shmap(step, mesh, (P("dp"),), P("dp"))(x)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.roll(np.arange(8, dtype=np.float32), 1))


def test_zero_size_leaf_passes_through(dp_mesh):
    trees = [{"a": jnp.full((4,), float(i)), "empty": jnp.zeros((0,))}
             for i in range(8)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    def step(t):
        local = jax.tree.map(lambda x: x[0], t)
        return collectives.bucketed_tree_all_reduce(local, average=False)

    out = _shmap(step, dp_mesh, (P("dp"),), P())(stacked)
    np.testing.assert_allclose(np.asarray(out["a"]), np.full((4,), 28.0))
    assert out["empty"].shape == (0,)


def test_bucketed_issues_far_fewer_collectives(dp_mesh):
    """Structural claim behind bucketing: 250 leaves naive -> 250
    all-reduces; bucketed -> one per <=4MB bucket.  The program hands the
    compiler one psum a bucket, which lowers to one StableHLO all_reduce an
    operand, and it is the compiler that merges them: so the one is counted
    in the compiled program.  (250 leaves: the CPU backend's combiner takes
    at most 256 operands an all-reduce.)"""
    import re
    tree = {f"g{i}": jnp.ones((1000,), jnp.float32) for i in range(250)}

    def lower(fn):
        return _shmap(fn, dp_mesh, (P(),), P()).lower(tree)

    naive = str(lower(lambda t: collectives.tree_all_reduce(t, "dp"))
                .compiler_ir(dialect="stablehlo"))
    assert naive.count("stablehlo.all_reduce") == 250
    # 250 * 4000B = 1MB total -> a single 4MB bucket
    compiled = lower(
        lambda t: collectives.bucketed_tree_all_reduce(t, "dp")
    ).compile().as_text()
    assert len(re.findall(r"^\s*%?[\w.-]+ = .* all-reduce\(", compiled,
                          re.M)) == 1
