"""Pipeline parallelism: SPMD GPipe over the 'pp' mesh axis.

Absent from the reference (SURVEY §2.6 — DP only) but first-class here.
The schedule is GPipe with M microbatches over P stages: every device runs
the same `lax.scan` of M+P-1 ticks; at each tick a stage applies its layer
slice to the microbatch it holds, then passes the activation to the next
stage with `lax.ppermute` (one hop over ICI).  Autodiff of the scan +
ppermute yields the reverse pipeline for the backward pass automatically —
no hand-built 1F1B machinery, XLA overlaps the permute with compute.

Stage weights live in the leading (stacked-layer) axis sharded over 'pp',
so the memory per device is L/P layers — the standard reason to pipeline.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

PyTree = Any


def gpipe_spmd(
    stage_fn: Callable[[PyTree, jax.Array], Any],
    stage_params: PyTree,
    x: jax.Array,
    num_microbatches: int,
    axis_name: str = "pp",
    with_aux: bool = False,
):
    """Run `x` through P pipeline stages (call under shard_map).

    stage_fn(stage_params, mb) -> mb applies THIS device's layer slice
    (or -> (mb, aux_scalar) when with_aux=True).
    `stage_params` are the local (already pp-sharded) stage weights.
    x: [B, ...] microbatched along axis 0 into `num_microbatches` chunks
    (B % num_microbatches == 0).  Returns [B, ...] final-stage outputs,
    replicated to every rank; with_aux additionally returns THIS stage's
    aux scalar summed over its real microbatch ticks (bubble ticks carry
    garbage activations and are masked out).  The aux stays per-rank —
    each pp rank owns its layers' aux term, so its gradient flows only
    into that rank's stage params and, through the ppermute chain, back to
    stage 0's embedding feed; summing across ranks happens in the caller's
    final loss psum.
    """
    P = jax.lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    M = num_microbatches
    B = x.shape[0]
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    mbs = x.reshape(M, B // M, *x.shape[1:])
    mb_shape = mbs.shape[1:]

    perm_fwd = [(i, (i + 1) % P) for i in range(P)]

    def run_stage(inp):
        res = stage_fn(stage_params, inp)
        return res if with_aux else (res, jnp.zeros((), jnp.float32))

    def tick(carry, t):
        prev_out, outs, aux_acc = carry
        # What arrives from the previous stage this tick.
        recvd = lax.ppermute(prev_out, axis_name, perm_fwd)
        # Stage 0 feeds fresh microbatches while they last.
        feed = lax.dynamic_index_in_dim(mbs, jnp.minimum(t, M - 1), axis=0,
                                        keepdims=False)
        inp = jnp.where(idx == 0, feed.astype(recvd.dtype), recvd)
        out, aux = run_stage(inp)
        # Stage `idx` works on real microbatch m = t - idx at this tick;
        # other ticks are pipeline bubbles whose aux is garbage.
        valid = jnp.logical_and(t >= idx, t - idx < M)
        aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        # The last stage finishes microbatch m = t - (P-1) at this tick.
        m = t - (P - 1)
        mc = jnp.clip(m, 0, M - 1)
        cur = lax.dynamic_index_in_dim(outs, mc, axis=0, keepdims=False)
        write = jnp.where(jnp.logical_and(m >= 0, idx == P - 1), out, cur)
        outs = lax.dynamic_update_index_in_dim(outs, write, mc, axis=0)
        return (out, outs, aux_acc), None

    # Probe stage_fn's output aval (it may change the activation dtype) to
    # type the scan carry.
    probe = jax.eval_shape(
        lambda p, a: stage_fn(p, a)[0] if with_aux else stage_fn(p, a),
        stage_params, jax.ShapeDtypeStruct(mb_shape, x.dtype))
    out0 = jnp.zeros(probe.shape, probe.dtype)
    outs0 = jnp.zeros((M,) + probe.shape, probe.dtype)
    aux0 = jnp.zeros((), jnp.float32)

    (_, outs, aux_sum), _ = lax.scan(tick, (out0, outs0, aux0),
                                     jnp.arange(M + P - 1))

    # Results live on the last stage; replicate them to all ranks (cheap
    # relative to the pipeline itself; lets the loss/psum run replicated).
    outs = lax.all_gather(outs, axis_name, axis=0, tiled=False)[P - 1]
    result = outs.reshape((B,) + probe.shape[1:])
    return (result, aux_sum) if with_aux else result


def shard_stage_params(params: PyTree, num_stages: int) -> PyTree:
    """Reshape stacked-layer params [L, ...] -> [P, L/P, ...] so the leading
    axis can be sharded over 'pp' (each stage holds L/P layers)."""
    def f(p):
        L = p.shape[0]
        if L % num_stages != 0:
            raise ValueError(f"{L} layers not divisible into "
                             f"{num_stages} stages")
        return p.reshape(num_stages, L // num_stages, *p.shape[1:])
    return jax.tree.map(f, params)
