"""The hybrid step's Switch layer: top-1 MoE with all-to-all dispatch over
'ep', used by `models/hybrid.py` alone.

This is NOT a dropless layer: routing is top-1 softmax, each expert takes
at most `capacity` tokens and the rest are dropped, and dispatch and
combine are one-hot `[T, E, C]` float32 einsums, which at tens of
thousands of tokens and a hundred experts do not fit.  A model that
routes top-k without drops (`models/afmoe.py`) runs through
`parallel/dropless_moe.py`, which computes one chip's share of such a
layer and has no exchange yet; this module has the exchange.

Absent from the reference (SURVEY §2.6) but first-class here.  Top-1
(Switch) routing with capacity limiting; experts are sharded over the 'ep'
mesh axis and tokens travel to their expert's device through one
`lax.all_to_all` each way — the TPU-idiomatic expert dispatch (the
all-to-all rides ICI; dispatch/combine are one-hot einsums that the MXU
chews through).
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax

PyTree = Any


def init_moe_params(rng: jax.Array, num_experts: int, d_model: int,
                    d_ff: int, dtype=jnp.float32) -> PyTree:
    kg, k1, k2 = jax.random.split(rng, 3)
    return {
        "gate_w": jax.random.normal(kg, (d_model, num_experts), dtype)
        / jnp.sqrt(d_model),
        "ffn_in": jax.random.normal(k1, (num_experts, d_model, d_ff), dtype)
        / jnp.sqrt(d_model),
        "ffn_out": jax.random.normal(k2, (num_experts, d_ff, d_model), dtype)
        / jnp.sqrt(d_ff),
    }


def moe_param_specs(ep_axis: str = "ep") -> PyTree:
    from jax.sharding import PartitionSpec as P
    return {"gate_w": P(None, None),
            "ffn_in": P(ep_axis, None, None),
            "ffn_out": P(ep_axis, None, None)}


def _dispatch_masks(gate_logits: jax.Array, num_experts: int, capacity: int):
    """Top-1 routing -> (dispatch [T,E,C] bool-ish, combine [T,E,C] f32,
    aux_loss).  T = local token count."""
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                       # [T]
    gate = jnp.take_along_axis(probs, expert[:, None], -1)[:, 0]
    onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.float32)  # [T,E]
    # Position of each token within its expert's queue.
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot         # [T,E]
    keep = pos < capacity
    onehot = onehot * keep
    pos_idx = pos.astype(jnp.int32)
    cap_onehot = jax.nn.one_hot(pos_idx, capacity, dtype=jnp.float32)
    dispatch = onehot[..., None] * cap_onehot                 # [T,E,C]
    combine = dispatch * gate[:, None, None]
    # Switch load-balancing auxiliary loss.
    density = onehot.mean(axis=0)
    density_proxy = probs.mean(axis=0)
    aux = (density * density_proxy).sum() * num_experts
    return dispatch, combine, aux


def moe_core(gate_w: jax.Array, ffn_in: jax.Array, ffn_out: jax.Array,
             x: jax.Array, capacity_factor: float = 2.0,
             axis_name: str = "ep") -> Tuple[jax.Array, jax.Array]:
    """The Switch-MoE data path on local tokens (call under shard_map).

    x: [T_local, D]; ffn_in/ffn_out: this rank's expert slice
    [E_local, D, F] / [E_local, F, D]; gate_w [D, E_global] replicated.
    Returns (y [T_local, D], aux load-balancing loss — local, not reduced).
    Shared by the standalone moe_layer and the hybrid model's FFN so the
    dispatch/capacity logic exists exactly once.
    """
    world = jax.lax.axis_size(axis_name)
    e_local = ffn_in.shape[0]
    E = e_local * world
    T = x.shape[0]
    capacity = max(1, int(capacity_factor * T / E))

    logits = x @ gate_w                                        # [T, E]
    dispatch, combine, aux = _dispatch_masks(logits, E, capacity)

    # Tokens -> expert buffers [E, C, D]; split experts across ranks, gather
    # the share of every peer's tokens for my local experts.
    buffers = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    # [E, C, D] -> [E/world, world*C, D]
    recv = lax.all_to_all(buffers, axis_name, split_axis=0, concat_axis=1,
                          tiled=True)
    h = jnp.einsum("ecd,edf->ecf", recv, ffn_in.astype(jnp.float32))
    h = jax.nn.gelu(h)
    h = jnp.einsum("ecf,efd->ecd", h, ffn_out.astype(jnp.float32))
    # Route results back to the owners of the tokens.
    back = lax.all_to_all(h, axis_name, split_axis=1, concat_axis=0,
                          tiled=True)                          # [E, C, D]
    y = jnp.einsum("tec,ecd->td", combine, back)
    return y.astype(x.dtype), aux


def moe_layer_shard(params: PyTree, x: jax.Array, capacity_factor: float = 2.0,
                    axis_name: str = "ep") -> Tuple[jax.Array, jax.Array]:
    """Per-shard Switch-MoE layer (call under shard_map).

    x: [T_local, D] tokens on this device; params['ffn_*'] hold the LOCAL
    expert slice [E_local, ...]; gate_w is replicated.  Returns (y, aux_loss).
    """
    y, aux = moe_core(params["gate_w"], params["ffn_in"], params["ffn_out"],
                      x, capacity_factor, axis_name)
    return y, lax.pmean(aux, axis_name)


def moe_layer(params: PyTree, x: jax.Array, mesh, capacity_factor: float = 2.0,
              axis_name: str = "ep") -> Tuple[jax.Array, jax.Array]:
    """Full-shape MoE layer: shard tokens over `axis_name`, experts likewise.

    x: [T, D] (T divisible by the ep axis size).  Wraps moe_layer_shard in
    shard_map for use inside an outer jit.
    """
    from jax.sharding import PartitionSpec as P
    specs = moe_param_specs(axis_name)

    f = functools.partial(moe_layer_shard, capacity_factor=capacity_factor,
                          axis_name=axis_name)
    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(specs, P(axis_name, None)),
        out_specs=(P(axis_name, None), P()),
        check_vma=False)(params, x)
