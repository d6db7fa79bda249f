"""XLA collective data plane.

This module is the TPU replacement for the reference's entire C++ pipeline
(reference: byteps/common/core_loops.cc — NCCL reduce-scatter, D2H copy,
ps-lite ZPush/ZPull, H2D copy, NCCL all-gather).  On TPU the whole path is a
set of XLA collectives over mesh axes; what survives of the reference design
is its *scheduling structure*:

  - a gradient tree is planned into <= BYTEPS_PARTITION_BYTES buckets
    (reference: operations.cc:140-180),
  - buckets are communicated in priority order — gradients produced first by
    the backward pass (the last layers) reduce first (reference:
    scheduled_queue.cc:82-102 orders by priority desc; plugins set
    priority = -declared_key, e.g. tensorflow/ops.cc:155-158),
  - the reduction is hierarchical when dp spans slices: reduce-scatter inside
    the ICI island, cross-island psum on the shard, all-gather back —
    the analog of NCCL-local-reduce → ps-push/pull → NCCL-broadcast
    (reference: core_loops.cc:188-267,536-616).

What the plan shapes depends on who has to see a bucket.  A compressor or
the hierarchical reduce-scatter needs a flat vector of a planned length, so
under a `bucket_transform` the leaves are sliced and concatenated into the
plan's buckets.  A plain sum needs none: every leaf is then summed in the
shape it has, and the plan gives only the order and the `byteps.bucket<N>`
scope names.  On a TPU an array is tiled in memory, so flattening a
[25088, 4096] leaf is a copy, not a view, and the compiler merges the sums it
is handed into a few all-reduces whatever their grouping (VGG-16 at dp=4 on
a v5e: 132 packed buckets became 5 all-reduces, 32 leaves become 3): packing
bought no launch and cost two copies of the tree, 9.6 ms of a 77.9 ms step.

All functions here are traced under jit/shard_map; they are pure and
shape-static.  What the chip shows of the schedule (PERF.md, section 5): the
all-reduces run synchronously at the end of the backward pass, with nothing
beside them; hiding them behind it is not done yet.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..common import telemetry
from ..common.config import get_config

PyTree = Any

# Trace-time "local mode": when set, every collective in this module is the
# identity and axis sizes are 1.  This is the analog of the reference's
# single-worker non-distributed queue list, which skips PUSH/PULL entirely
# (reference: operations.cc:429-485) — build_train_step enables it when the
# mesh has one device so the whole step lowers to a plain jit with zero
# communication or sharding machinery.
_local_mode: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "byteps_tpu_local_mode", default=False)


@contextlib.contextmanager
def local_mode():
    tok = _local_mode.set(True)
    try:
        yield
    finally:
        _local_mode.reset(tok)


def is_local() -> bool:
    return _local_mode.get()


def axis_size(axis_name: str) -> int:
    return 1 if is_local() else jax.lax.axis_size(axis_name)


# ---------------------------------------------------------------------------
# Thin wrappers (named to match the conceptual ops in SURVEY §2.6).
# ---------------------------------------------------------------------------
def all_reduce(x: jax.Array, axis_name: str = "dp") -> jax.Array:
    return x if is_local() else lax.psum(x, axis_name)


def all_gather(x: jax.Array, axis_name: str = "dp",
               axis: int = 0, tiled: bool = True) -> jax.Array:
    if is_local():
        return x if tiled else jnp.expand_dims(x, axis)
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x: jax.Array, axis_name: str = "dp",
                   axis: int = 0) -> jax.Array:
    if is_local():
        return x
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def ring_permute(x: jax.Array, axis_name: str, shift: int = 1) -> jax.Array:
    """Neighbor exchange on the ring — building block for ring attention."""
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


# ---------------------------------------------------------------------------
# Bucketing: the partitioner applied to a flattened gradient pytree.
# ---------------------------------------------------------------------------
class BucketPlan:
    """Static plan mapping pytree leaves <-> priority-ordered buckets.

    Built once per (treedef, shapes) at trace time; the plan is pure Python
    metadata, so it adds nothing to the compiled graph.  `partition_bytes`
    sizes the flat buckets a `bucket_transform` sees (and the compressor
    state built from the same plan); without a transform no bucket is ever
    materialised and it decides only which leaves share a scope name.
    """

    def __init__(self, sizes: Sequence[int], partition_bytes: int,
                 itemsize: int, reverse: bool = True):
        # Leaf order is declaration order. The backward pass produces
        # gradients roughly in reverse declaration order, so buckets go
        # from the tail end first — the reference's priority =
        # -declared_key in bucket form.  The
        # segment packing itself lives in the shared fusion planner
        # (common/fusion.py plan_segments), so the in-graph and PS-wire
        # planes agree on one bucket-composition algorithm.
        from ..common.fusion import plan_segments
        part_elems = max(1, partition_bytes // max(1, itemsize))
        # Each bucket is a list of (leaf_idx, start, length) segments.
        self.buckets: List[List[Tuple[int, int, int]]] = plan_segments(
            sizes, part_elems, reverse)
        self.sizes = list(sizes)

    def num_buckets(self) -> int:
        return len(self.buckets)


@functools.lru_cache(maxsize=256)
def _plan_cache(sizes: Tuple[int, ...], partition_bytes: int, itemsize: int,
                reverse: bool) -> BucketPlan:
    return BucketPlan(sizes, partition_bytes, itemsize, reverse)


def bucketed_tree_all_reduce(
    tree: PyTree,
    axis_name: str = "dp",
    average: bool = True,
    partition_bytes: Optional[int] = None,
    bucket_transform: Optional[Callable[[jax.Array, int], jax.Array]] = None,
) -> PyTree:
    """Priority-ordered all-reduce of a gradient pytree.

    The form handed to the compiler depends on whether anything has to see
    a bucket as a vector:

    - Without a `bucket_transform` every non-empty leaf is summed in its own
      shape (:func:`_reduce_leaves`): no reshape, slice or concatenate on
      the way in or out.  `partition_bytes` then shapes nothing but the
      scope names; a leaf larger than it goes whole.
    - With one, leaves are packed into flat buckets of <= `partition_bytes`
      (:func:`_reduce_packed`) and `bucket_transform` maps (bucket,
      bucket_index) -> reduced bucket in place of the psum: the hook of the
      compression subsystem and of the hierarchical reduce-scatter, which
      need a vector of a planned length.

    Either way the sums are issued in the plan's order, the last-declared
    leaves first.  What was built is written to the metrics registry when
    the step is traced (`telemetry.record_static`).
    """
    if is_local() and bucket_transform is None:
        # Single-device: the sum over one worker is the identity and the
        # average divides by 1 — skip the exchange entirely, as the
        # reference's non-distributed queue list skips PUSH/PULL
        # (reference: operations.cc:429-485).
        return tree
    cfg = get_config()
    pb = partition_bytes or cfg.partition_bytes
    all_leaves, treedef = jax.tree.flatten(tree)
    # Zero-size leaves have nothing to communicate; pass them through.
    nonempty_idx = [i for i, l in enumerate(all_leaves) if l.size > 0]
    leaves = [all_leaves[i] for i in nonempty_idx]
    if not leaves:
        return tree
    # One common dtype for the exchange (a packed bucket needs it, and the
    # per-leaf form sums in the same one); cast back afterwards.
    comm_dtype = jnp.result_type(*(l.dtype for l in leaves))
    itemsize = jnp.dtype(comm_dtype).itemsize
    sizes = tuple(l.size for l in leaves)
    plan = _plan_cache(sizes, pb, itemsize, True)
    denom = jnp.asarray(axis_size(axis_name), comm_dtype) if average else None
    wire = [l.astype(comm_dtype) for l in leaves]
    if bucket_transform is None:
        reduced, groups = _reduce_leaves(wire, plan, axis_name, denom)
        packed_bytes = 0
    else:
        reduced = _reduce_packed(wire, plan, bucket_transform, denom)
        groups, packed_bytes = plan.num_buckets(), sum(sizes) * itemsize
    telemetry.record_static("ingraph_exchange", leaves=len(leaves),
                            groups=groups, packed_bytes=packed_bytes)
    out_leaves = list(all_leaves)
    for i, leaf, r in zip(nonempty_idx, leaves, reduced):
        out_leaves[i] = r.astype(leaf.dtype)
    return jax.tree.unflatten(treedef, out_leaves)


def _reduce_leaves(wire: List[jax.Array], plan: BucketPlan, axis_name: str,
                   denom: Optional[jax.Array]
                   ) -> Tuple[List[jax.Array], int]:
    """Sum every leaf in the shape it has: one `lax.psum` for the leaves
    whose first segment falls in the same bucket of `plan`, in the plan's
    order.  Returns the reduced leaves and the number of sums issued."""
    reduced: List[Optional[jax.Array]] = [None] * len(wire)
    groups = 0
    for bi, bucket in enumerate(plan.buckets):
        group = [li for (li, start, _) in bucket if start == 0]
        if not group:
            continue
        groups += 1
        # Named scope per bucket: the in-graph analog of the reference's
        # per-partition trace spans (global.cc:463-579) — the XLA profiler
        # attributes the group's collective to `byteps.bucket<N>`
        # (composition documented in docs/timeline.md).
        with jax.named_scope(f"byteps.bucket{bi}"):
            outs = all_reduce(tuple(wire[li] for li in group), axis_name)
            for li, out in zip(group, outs):
                reduced[li] = out if denom is None else out / denom
    return reduced, groups


def _reduce_packed(wire: List[jax.Array], plan: BucketPlan,
                   bucket_transform: Callable[[jax.Array, int], jax.Array],
                   denom: Optional[jax.Array]) -> List[jax.Array]:
    """Pack the leaves into the plan's flat buckets, hand each to
    `bucket_transform`, and cut the results back into the leaves' shapes."""
    flat = [l.reshape(-1) for l in wire]
    out_segments: List[List[jax.Array]] = [[] for _ in wire]
    for bi, bucket in enumerate(plan.buckets):
        with jax.named_scope(f"byteps.bucket{bi}"):
            parts = [lax.dynamic_slice(flat[li], (start,), (length,))
                     for (li, start, length) in bucket]
            buf = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            buf = bucket_transform(buf, bi)
            if denom is not None:
                buf = buf / denom
        off = 0
        # A leaf's segments come in offset order (plan_segments), so
        # appending restores it.
        for (li, _, length) in bucket:
            out_segments[li].append(lax.dynamic_slice(buf, (off,), (length,)))
            off += length
    return [(jnp.concatenate(segs) if len(segs) > 1 else segs[0])
            .reshape(leaf.shape)
            for segs, leaf in zip(out_segments, wire)]


def tree_all_reduce(tree: PyTree, axis_name: str = "dp",
                    average: bool = True) -> PyTree:
    """Unplanned baseline: one psum per leaf in declaration order, each in
    its own dtype (what naive DP in JAX does).  The tests count against it.
    """
    def f(x):
        y = all_reduce(x, axis_name)
        if average:
            y = y / jnp.asarray(axis_size(axis_name), x.dtype)
        return y
    return jax.tree.map(f, tree)


# ---------------------------------------------------------------------------
# Hierarchical reduction over ('dcn_dp', 'ici_dp') — the two-level analog of
# the reference's NCCL-reduce-scatter → ps-push/pull → NCCL-all-gather.
# ---------------------------------------------------------------------------
def hierarchical_all_reduce(x: jax.Array, ici_axis: str = "ici_dp",
                            dcn_axis: str = "dcn_dp",
                            average: bool = False) -> jax.Array:
    """reduce-scatter on ICI, psum the shard over DCN, all-gather on ICI.

    Requires x's leading dim divisible by the ici axis size (callers pad flat
    buckets).  Cross-DCN traffic is 1/ici_size of the naive psum — the same
    bandwidth win the reference gets from summing locally before pushing
    (reference: docs/architecture.md:26-33).
    """
    shard = reduce_scatter(x, ici_axis, axis=0)
    shard = all_reduce(shard, dcn_axis)
    out = all_gather(shard, ici_axis, axis=0, tiled=True)
    if average:
        out = out / jnp.asarray(
            axis_size(ici_axis) * axis_size(dcn_axis), x.dtype)
    return out


def hierarchical_tree_all_reduce(tree: PyTree, ici_axis: str = "ici_dp",
                                 dcn_axis: str = "dcn_dp",
                                 average: bool = True,
                                 partition_bytes: Optional[int] = None
                                 ) -> PyTree:
    """Bucketed hierarchical all-reduce of a gradient pytree."""
    def transform(buf: jax.Array, bi: int) -> jax.Array:
        ici = axis_size(ici_axis)
        pad = (-buf.size) % ici
        if pad:
            buf = jnp.concatenate([buf, jnp.zeros((pad,), buf.dtype)])
        out = hierarchical_all_reduce(buf, ici_axis, dcn_axis, average=False)
        return out[:out.size - pad] if pad else out

    # average=False in the bucket, divide once at the end via the transform
    # caller; reuse bucketed path with explicit denominator.
    out = bucketed_tree_all_reduce(tree, axis_name=ici_axis, average=False,
                                   partition_bytes=partition_bytes,
                                   bucket_transform=transform)
    if average:
        n = axis_size(ici_axis) * axis_size(dcn_axis)
        out = jax.tree.map(lambda l: l / jnp.asarray(n, l.dtype), out)
    return out
