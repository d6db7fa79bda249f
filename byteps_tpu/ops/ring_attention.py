"""Ring attention: exact attention over sequence-sharded inputs.

Long-context support is absent from the reference (SURVEY §2.6/§5 — it
scales batch, never sequence) but is first-class here.  This is blockwise
ring attention: Q stays put, K/V blocks rotate around the 'sp' ring via
`lax.ppermute` while each device accumulates its queries' attention with an
online (flash-style) softmax.  Per-step traffic is one K/V block over ICI
neighbor links; memory is O(S_local), enabling sequences far beyond one
chip's HBM.

All shapes are static and the loop is a `lax.scan`, so XLA overlaps the
ppermute of block t+1 with the matmuls of block t (double buffering falls
out of the dataflow).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = float(jnp.finfo(jnp.float32).min)


def _block_attn(q, k, v, mask):
    """One blockwise attention contribution with running-max bookkeeping.

    q: [B,H,Sq,D], k/v: [B,H,Sk,D], mask: [Sq,Sk] bool (True = attend).
    Returns (out_unnorm [B,H,Sq,D] f32, lse terms): partial numerator and
    softmax statistics (m = row max, l = row sum) for online combination.
    """
    d = q.shape[-1]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = jnp.where(mask, logits, NEG_INF)
    m = logits.max(axis=-1, keepdims=True)                     # [B,H,Sq,1]
    # All-masked rows: keep m finite so exp() is well-behaved.
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(logits - m_safe)
    p = jnp.where(mask, p, 0.0)
    l = p.sum(axis=-1, keepdims=True)                          # [B,H,Sq,1]
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)
    return o.astype(jnp.float32), m_safe, l


def ring_attention_shard(q, k, v, causal: bool, axis_name: str = "sp"):
    """Per-shard ring attention body (call under shard_map).

    q,k,v: [B, H, S_local, D] — this device's sequence block along a ring of
    `axis_size(axis_name)` devices.  Returns [B, H, S_local, D].
    """
    n = jax.lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, H, S, D = q.shape

    # Send K/V to the next rank each step; after t steps this device holds
    # the block originally owned by (my - t) mod n.
    perm = [(i, (i + 1) % n) for i in range(n)]

    q_pos = my * S + jnp.arange(S)

    def step(carry, t):
        k_t, v_t, o, m, l = carry
        origin = (my - t) % n
        if causal:
            kv_pos = origin * S + jnp.arange(S)
            mask = q_pos[:, None] >= kv_pos[None, :]
        else:
            mask = jnp.ones((S, S), bool)
        o_t, m_t, l_t = _block_attn(q, k_t, v_t, mask)
        # Online-softmax merge of (o,m,l) with the new block's stats.
        m_new = jnp.maximum(m, m_t)
        c_old = jnp.exp(m - m_new)
        c_new = jnp.exp(m_t - m_new)
        o = o * c_old + o_t * c_new
        l = l * c_old + l_t * c_new
        k_n = lax.ppermute(k_t, axis_name, perm)
        v_n = lax.ppermute(v_t, axis_name, perm)
        return (k_n, v_n, o, m_new, l), None

    o0 = jnp.zeros((B, H, S, D), jnp.float32)
    m0 = jnp.full((B, H, S, 1), NEG_INF / 2, jnp.float32)
    l0 = jnp.zeros((B, H, S, 1), jnp.float32)
    (k, v, o, m, l), _ = lax.scan(step, (k, v, o0, m0, l0), jnp.arange(n))
    out = o / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


def make_ring_attn_fn(mesh: Mesh, axis_name: str = "sp"):
    """Adaptor producing an `attn_fn(q, k, v, causal)` for
    models.transformer.forward: full-shape q/k/v come in (traced under the
    outer jit), the ring runs in a nested shard_map over the sequence axis.
    Heads stay sharded over 'tp' if the outer program shards them — the
    in_specs only constrain the sequence dim.
    """
    spec = P(None, None, axis_name, None)

    def attn_fn(q, k, v, causal):
        f = functools.partial(ring_attention_shard, causal=causal,
                              axis_name=axis_name)
        return jax.shard_map(f, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)
    return attn_fn


# ---------------------------------------------------------------------------
# Ulysses-style sequence parallelism: all-to-all re-shard seq <-> heads.
# ---------------------------------------------------------------------------
def ulysses_attention_shard(q, k, v, causal: bool, axis_name: str = "sp",
                            attn=None):
    """Per-shard Ulysses attention (call under shard_map).

    Inputs are sequence-sharded [B, H, S/n, D].  One all-to-all converts to
    head-sharded [B, H/n, S, D] (full sequence, subset of heads), dense
    attention runs locally, and a second all-to-all restores sequence
    sharding.  Communication is 2 all-to-alls instead of n ppermutes —
    better for moderate n on all-to-all-capable fabrics; requires
    num_heads % n == 0.
    """
    n = jax.lax.axis_size(axis_name)

    def seq_to_heads(x):
        # [B, H, S/n, D] -> [B, H/n, S, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    if q.shape[1] % n != 0:
        raise ValueError(
            f"ulysses needs num_heads ({q.shape[1]}) divisible by the sp "
            f"axis size ({n}); use ring attention otherwise")
    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if attn is None:
        from ..models.transformer import dense_attention
        attn = dense_attention
    out = attn(qh, kh, vh, causal)
    return heads_to_seq(out)


def make_ulysses_attn_fn(mesh: Mesh, axis_name: str = "sp", attn="dense"):
    """Ulysses counterpart of make_ring_attn_fn.

    `attn` picks the per-shard attention over the full (gathered) sequence:
    "dense", "flash" (the Pallas kernel — Ulysses hands each shard the
    WHOLE sequence for a head subset, so the S x S logits the kernel
    avoids grow with total context, making this the natural pairing for
    long-context sp), or any callable (q, k, v, causal)."""
    spec = P(None, None, axis_name, None)
    if callable(attn):
        inner = attn
    else:
        from ..models import transformer as _tfm
        if attn not in _tfm._ATTN_IMPLS:
            raise ValueError(
                f"attn must be a callable or one of "
                f"{sorted(_tfm._ATTN_IMPLS)}; got {attn!r}")
        inner = _tfm._ATTN_IMPLS[attn]

    def attn_fn(q, k, v, causal):
        f = functools.partial(ulysses_attention_shard, causal=causal,
                              axis_name=axis_name, attn=inner)
        return jax.shard_map(f, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)
    return attn_fn
