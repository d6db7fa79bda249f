"""Tensor parallelism: Megatron-style column/row sharded matmuls.

Absent from the reference (SURVEY §2.6) but first-class here.  Two usage
modes:

  1. GSPMD (preferred): annotate weights with the PartitionSpecs from
     `models.transformer.param_specs` and let XLA place the collectives —
     column-parallel layers need no forward comm, row-parallel layers get
     one psum, exactly the f/g operators of Megatron-LM.
  2. Explicit (shard_map): the helpers below spell the same math out for
     code running under `shard_map`, where GSPMD is bypassed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def col_parallel_dense(x: jax.Array, w_local: jax.Array,
                       b_local: jax.Array = None) -> jax.Array:
    """Column-parallel dense: inputs replicated, weight column-sharded.
    y_local = x @ W_local — no communication in forward; autodiff inserts
    the psum on dx (the Megatron "f" operator)."""
    y = x @ w_local
    if b_local is not None:
        y = y + b_local
    return y


def row_parallel_dense(x_local: jax.Array, w_local: jax.Array,
                       b: jax.Array = None,
                       axis_name: str = "tp") -> jax.Array:
    """Row-parallel dense: inputs sharded on the contracting dim, weight
    row-sharded; partial products are psummed (the Megatron "g" operator,
    with the transpose-safe custom vjp).  Bias is added once,
    post-reduction."""
    y = reduce_from(axis_name)(x_local @ w_local)
    if b is not None:
        y = y + b
    return y


def copy_to(axis_name: str):
    """The Megatron "f" operator: forward identity, backward all-reduce.

    Under shard_map autodiff is purely local, so a replicated activation
    entering column-parallel branches needs its cotangents summed across the
    tp ranks explicitly; this factory returns that identity-with-psum-vjp.
    """
    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (lax.psum(g, axis_name),)

    f.defvjp(fwd, bwd)
    return f


def reduce_from(axis_name: str):
    """The Megatron "g" operator: forward all-reduce, backward identity.

    Raw `lax.psum` must NOT be differentiated through under
    shard_map(check_vma=False): its transpose is another psum, which
    over-counts the cotangent by the axis size when the downstream loss is
    computed replicated on every rank.  This custom-vjp pins the correct
    adjoint (the replicated cotangent passes through once).
    """
    @jax.custom_vjp
    def g(x):
        return lax.psum(x, axis_name)

    def fwd(x):
        return lax.psum(x, axis_name), None

    def bwd(_, ct):
        return (ct,)

    g.defvjp(fwd, bwd)
    return g


def tp_split(x: jax.Array, axis: int, axis_name: str = "tp") -> jax.Array:
    """Slice the local chunk of a replicated array along `axis` (activation
    entering a row-parallel layer)."""
    n = jax.lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    size = x.shape[axis] // n
    return lax.dynamic_slice_in_dim(x, idx * size, size, axis)


def tp_all_gather(x_local: jax.Array, axis: int,
                  axis_name: str = "tp") -> jax.Array:
    """Re-assemble a sharded activation (exit of a column-parallel layer
    when the next op needs the full feature dim)."""
    return lax.all_gather(x_local, axis_name, axis=axis, tiled=True)
