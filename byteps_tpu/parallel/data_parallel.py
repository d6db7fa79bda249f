"""Data-parallel training: DistributedOptimizer and the jitted train step.

The reference wraps each framework's optimizer so that every gradient is
push_pull'd before the local update (reference: byteps/torch/__init__.py:
115-214, byteps/mxnet/__init__.py:74-92, byteps/tensorflow/__init__.py:
184-278).  The TPU-native equivalent wraps an optax GradientTransformation:
`update()` runs the priority-ordered all-reduce from ops.collectives over the
mesh's dp axis (hierarchical over ici/dcn when the mesh is two-level), then
applies the inner transform.  Everything is traced under jit, so the
exchange and the update are one program: with no compressor each gradient
is summed in the shape it has and the division by the axis size fuses into
the optimizer's update.  What that program does NOT do yet is the
cross-barrier effect the reference builds by hand with threads + locks
(reference: torch/cross_barrier.py): on the chip the compiler merges the
sums into a few all-reduces and runs them synchronously after the backward
pass, with nothing beside them (PERF.md, section 5).

Bucket composition routes through the shared fusion planner
(common/fusion.py, via ops.collectives.BucketPlan): the in-graph plane and
the PS wire plane (push_pull_tree / AsyncPSTrainer) plan leaves with the
same reverse-backprop-order algorithm, and `bps.get_fusion_stats()` sees
plan activity from either.  In-graph the plan's buckets are materialised
only for a compressor or the hierarchical reduce-scatter; `bps.get_metrics()`
(`bps_ingraph_exchange_*`) says which form the last traced step took.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common import devprof
from ..common.config import get_config
from ..ops import collectives
from ..ops.compression import Compression, Compressor
from ..utils import compile_cache

PyTree = Any


@jax.tree_util.register_pytree_with_keys_class
class CompressionOptState:
    """Optax state slot holding per-bucket compressor state (EF error
    buffers, momentum, PRNG lanes) — the functional stand-in for the
    reference's mutable per-partition compressor objects
    (reference: operations.cc:380-385).

    `world` (static aux data) records how many per-worker copies the state
    currently holds; build_train_step tiles/validates it against the mesh's
    dp axis size so a default-constructed state is automatically expanded.
    """

    def __init__(self, comp: Any, world: int = 1):
        self.comp = comp
        self.world = world

    def tree_flatten_with_keys(self):
        return ((jax.tree_util.GetAttrKey("comp"), self.comp),), self.world

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)

    def __repr__(self):
        return f"CompressionOptState(world={self.world})"

    def __eq__(self, other):
        return (isinstance(other, CompressionOptState)
                and other.world == self.world
                and jax.tree.structure(other.comp)
                == jax.tree.structure(self.comp))


def distributed_gradient_transform(
    axis_name: str = "dp",
    average: bool = True,
    compression: Optional[Compressor] = None,
    inter_compressor: Optional[Any] = None,
    partition_bytes: Optional[int] = None,
    hierarchical: bool = False,
    world: int = 1,
) -> optax.GradientTransformation:
    """An optax transform that all-reduces gradients across `axis_name`.

    `compression` is the framework-level cast (Compression.fp16 → bf16 wire
    format); `inter_compressor` is a byteps_tpu.ops.compressor instance
    (onebit/topk/...) applied per bucket on-device.

    `world` must be the dp axis size when a *stateful* inter_compressor is
    used on a multi-device mesh: compressor state (error-feedback buffers,
    PRNG lanes) is genuinely per-worker — like the reference's per-process
    compressor objects (operations.cc:380-385) — so init tiles each state
    buffer `world` times and build_train_step shards it over `axis_name`,
    giving every shard its own slice.
    """
    compression = compression or Compression.none

    def init_fn(params):
        if inter_compressor is not None:
            import jax.numpy as jnp
            from ..ops.compressor import init_compression_state
            # The bucket plan must match update_fn's, which bucketizes the
            # post-cast wire tree — so build state from the wire shapes,
            # not the raw params.
            wire_shapes = jax.eval_shape(
                lambda p: _tree_compress(p, compression)[0], params)
            zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                 wire_shapes)
            comp = init_compression_state(zeros, inter_compressor,
                                          partition_bytes)
            if world > 1:
                comp = _tile_state(comp, world)
            return CompressionOptState(comp, world=world)
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        del params
        wire, ctxs = _tree_compress(updates, compression)
        if inter_compressor is not None:
            from ..ops.compressor import compressed_tree_all_reduce
            reduced, new_comp = compressed_tree_all_reduce(
                wire, inter_compressor, state.comp, axis_name=axis_name,
                average=average, partition_bytes=partition_bytes)
            state = CompressionOptState(new_comp, world=state.world)
        elif hierarchical:
            reduced = collectives.hierarchical_tree_all_reduce(
                wire, average=average, partition_bytes=partition_bytes)
        else:
            reduced = collectives.bucketed_tree_all_reduce(
                wire, axis_name=axis_name, average=average,
                partition_bytes=partition_bytes)
        out = _tree_decompress(reduced, ctxs, compression)
        return out, state

    return optax.GradientTransformation(init_fn, update_fn)


def _tree_compress(tree, compression):
    leaves, treedef = jax.tree.flatten(tree)
    outs, ctxs = [], []
    for l in leaves:
        c, ctx = compression.compress(l)
        outs.append(c)
        ctxs.append(ctx)
    return jax.tree.unflatten(treedef, outs), ctxs


def _tree_decompress(tree, ctxs, compression):
    leaves, treedef = jax.tree.flatten(tree)
    outs = [compression.decompress(l, ctx) for l, ctx in zip(leaves, ctxs)]
    return jax.tree.unflatten(treedef, outs)


class DistributedGradientTransformation(NamedTuple):
    """optax-compatible (init/update duck type) transform that also records
    the `backward_passes_per_step` knob, so build_train_step can refuse the
    double-scaling combination with `accum_steps` (both would divide the
    gradient by N)."""

    init: Callable
    update: Callable
    backward_passes_per_step: int = 1


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    named_parameters: Any = None,  # accepted for API parity; unused in JAX
    compression: Optional[Compressor] = None,
    inter_compressor: Optional[Any] = None,
    axis_name: str = "dp",
    average: bool = True,
    partition_bytes: Optional[int] = None,
    hierarchical: bool = False,
    backward_passes_per_step: int = 1,
    world: int = 1,
) -> "DistributedGradientTransformation":
    """Wrap an optax optimizer so updates are preceded by distributed
    gradient push_pull — the JAX face of the reference's
    `bps.DistributedOptimizer`.

    `backward_passes_per_step > 1` scales gradients down to keep the average
    correct under gradient accumulation (reference exposes the same knob).

    The return value is an optax-compatible init/update pair, but a
    THREE-field NamedTuple (DistributedGradientTransformation) — use
    `.init`/`.update` attribute access, not 2-tuple unpacking.
    """
    del named_parameters
    chain = [distributed_gradient_transform(
        axis_name=axis_name, average=average, compression=compression,
        inter_compressor=inter_compressor, partition_bytes=partition_bytes,
        hierarchical=hierarchical, world=world)]
    if backward_passes_per_step > 1:
        chain.append(optax.scale(1.0 / backward_passes_per_step))
    chain.append(optimizer)
    chained = optax.chain(*chain)
    return DistributedGradientTransformation(
        chained.init, chained.update,
        backward_passes_per_step=backward_passes_per_step)


# ---------------------------------------------------------------------------
# Train-step builder: the canonical hot path.
# ---------------------------------------------------------------------------
def build_train_step(
    loss_fn: Callable[..., jax.Array],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    axis_name: str = "dp",
    batch_spec: Optional[P] = None,
    donate: bool = True,
    accum_steps: int = 1,
) -> Callable:
    """Returns jitted `step(params, opt_state, batch) -> (params, opt_state,
    loss)` where:

      - params/opt_state are replicated across the mesh,
      - batch is sharded over `axis_name` (default P('dp') on axis 0),
      - gradients are computed per-shard and reduced by the optimizer's
        distributed transform (which must psum over `axis_name` — use
        DistributedOptimizer).

    `accum_steps > 1` splits each shard's batch into that many microbatches
    under `lax.scan` and averages their gradients before the ONE distributed
    update — gradient accumulation with a single all-reduce per step (the
    reference's `backward_passes_per_step` semantics, reference:
    torch/__init__.py:115-174, without its per-pass push_pull traffic).
    Peak activation memory drops to one microbatch's.

    This is the structural equivalent of the reference's
    backward-hook → push_pull → optimizer.step loop (reference:
    torch/__init__.py:140-174) collapsed into one compiled program.
    """
    if (axis_name == "dp" and "dp" not in mesh.shape
            and {"dcn_dp", "ici_dp"} <= set(mesh.axis_names)):
        # Two-level mesh from make_hierarchical_mesh: the batch shards
        # over BOTH dp levels and the loss pmean spans them, so the
        # canonical `build_train_step(loss, opt, make_hierarchical_mesh(),
        # DistributedOptimizer(..., hierarchical=True))` pod recipe works
        # without the caller naming internal axes.
        axis_name = ("dcn_dp", "ici_dp")
    if batch_spec is None:
        batch_spec = P(axis_name)
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    # Best-effort guard: the knob is only visible on a directly-passed
    # DistributedOptimizer.  If you re-wrap it (optax.chain(...)), the
    # guard can't see it — don't combine the two forms yourself.
    if (accum_steps > 1
            and getattr(optimizer, "backward_passes_per_step", 1) > 1):
        raise ValueError(
            "accum_steps and DistributedOptimizer(backward_passes_per_step)"
            " are alternative forms of the same averaging — combining them"
            " would divide the update by the product.  Use accum_steps for"
            " in-step (lax.scan) accumulation, or backward_passes_per_step"
            " when the training loop itself calls update() once per pass.")
    donate_argnums = (0, 1) if donate else ()

    def _value_and_grad(params, batch):
        if accum_steps == 1:
            return jax.value_and_grad(loss_fn)(params, batch)

        def split(x):
            if x.shape[0] % accum_steps:
                raise ValueError(
                    f"per-shard batch dim {x.shape[0]} is not divisible by "
                    f"accum_steps={accum_steps}")
            return x.reshape((accum_steps, x.shape[0] // accum_steps)
                             + x.shape[1:])

        micros = jax.tree.map(split, batch)

        # Accumulate in f32 regardless of the param/grad dtype: bf16
        # partial sums would round each step and break the equals-the-
        # full-batch-gradient contract as accum_steps grows.  Cast back to
        # the native grad dtype after averaging.
        def micro(carry, mb):
            loss_sum, g_sum = carry
            l, g = jax.value_and_grad(loss_fn)(params, mb)
            return (loss_sum + l.astype(jnp.float32),
                    jax.tree.map(lambda s, x: s + x.astype(jnp.float32),
                                 g_sum, g)), None

        init = (jnp.zeros((), jnp.float32),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params))
        (loss_sum, g_sum), _ = jax.lax.scan(micro, init, micros)
        inv = 1.0 / accum_steps
        return loss_sum * inv, jax.tree.map(
            lambda g, p: (g * inv).astype(p.dtype), g_sum, params)

    def _update(params, opt_state, grads):
        # One scope for the optimizer's share of a step
        # (`bps.get_step_scopes()`, pass "optimizer"); the exchange's
        # `byteps.bucket<N>` scopes nest in it.
        with jax.named_scope("byteps.optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

    if mesh.devices.size == 1:
        # Single-device fast path: the reference's non-distributed mode
        # builds a queue list with no PUSH/PULL (operations.cc:429-485); here
        # the whole step lowers to a plain jit — collectives trace as
        # identity under local_mode, so no sharding machinery or collective
        # dispatch overhead remains.
        def _local_step(params, opt_state, batch):
            with collectives.local_mode():
                loss, grads = _value_and_grad(params, batch)
                params, opt_state = _update(params, opt_state, grads)
            return params, opt_state, loss

        jitted = _JittedStep(
            jax.jit(_local_step, donate_argnums=donate_argnums))

        def local_call(params, opt_state, batch):
            return jitted(params, _retile_comp_state(opt_state, 1), batch)

        return local_call

    def _step(params, opt_state, batch):
        loss, grads = _value_and_grad(params, batch)
        params, opt_state = _update(params, opt_state, grads)
        # Per-shard losses -> global mean for reporting.
        loss = jax.lax.pmean(loss, axis_name)
        return params, opt_state, loss

    # Compressor state inside the opt state is per-worker (see
    # distributed_gradient_transform's `world`): those leaves are sharded
    # over the dp axis; everything else is replicated.  The specs depend on
    # the opt_state pytree structure, so the shard_map is built lazily on
    # first call and cached per structure.
    cache = {}
    axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    dp_world = int(math.prod(mesh.shape.get(a, 1) for a in axes))

    def call(params, opt_state, batch):
        opt_state = _retile_comp_state(opt_state, dp_world)
        key = (jax.tree.structure(params), jax.tree.structure(opt_state))
        if key not in cache:
            state_specs = _opt_state_specs(opt_state, axis_name)
            sm = jax.shard_map(
                _step, mesh=mesh, in_specs=(P(), state_specs, batch_spec),
                out_specs=(P(), state_specs, P()), check_vma=False)
            cache[key] = _JittedStep(
                jax.jit(sm, donate_argnums=donate_argnums))
        return cache[key](params, opt_state, batch)

    return call


class _JittedStep:
    """A jitted train step as both paths of `build_train_step` call it:
    between the device plane's hooks, compiled into cache entries of its
    own (`compile_cache.scopes_in_key`), remembered for the scope map
    (`bps.get_step_scopes()`) whenever a call compiled it, and told to
    the compile log (`bps.get_compile_log()`): what such a call made is
    the step's own (`cause="train_step"`), and the first call during
    which nothing is made anywhere ends set-up."""

    def __init__(self, fn):
        self.fn = fn
        self.programs = 0       # how many the callable held when last asked
        self.calls = 0

    def __call__(self, params, opt_state, batch):
        self.calls += 1
        # Once set-up has ended this is all a step does about the log:
        # one global and two of its attributes read, no call into it.
        log = compile_cache.LOG
        since = log.made if log is not None else 0
        began = (log.call_begin()
                 if log is not None and log.steady_at is None else None)
        with compile_cache.scopes_in_key():
            # Device-plane hook (common/devprof.py): unarmed this is one
            # None check; armed it resolves cached FLOPs pre-dispatch
            # (lowering the step, hence in here) and syncs in step_end to
            # record a true device step time.
            tok = devprof.step_begin(self.fn, (params, opt_state, batch))
            out = self.fn(params, opt_state, batch)
        # A call that compiled leaves the callable one program more (the
        # first; on a mesh the second, whose state comes back placed
        # otherwise than a script hands it in; a new batch shape whenever).
        # The record takes the step's own results in place of the state it
        # was given: they are what the next step receives.
        count = getattr(self.fn, "_cache_size", None)   # a jitted callable's
        programs = count() if count is not None else 1
        if programs != self.programs:
            self.programs = programs
            devprof.remember_step(self.fn, (out[0], out[1], batch))
            if log is not None:
                # After the fact: the listener has already warned of one
                # that came after set-up, which is what a recompile is.
                log.claim(since, "train_step", self.calls)
        if began is not None:
            log.call_end(began)
        devprof.step_end(tok, out)
        return out


def _tile_state(comp: PyTree, world: int) -> PyTree:
    return jax.tree.map(
        lambda l: jnp.tile(l, (world,) + (1,) * (l.ndim - 1))
        if l.ndim >= 1 else l, comp)


def _retile_comp_state(opt_state: PyTree, dp_world: int) -> PyTree:
    """Expand (or validate) per-worker compressor state against the mesh's
    dp axis size, so a default-constructed (world=1) state just works on any
    mesh and a mismatched one fails loudly instead of silently slicing PRNG
    lanes / EF buffers."""
    def fix(node):
        if not isinstance(node, CompressionOptState):
            return node
        if node.world == dp_world:
            return node
        if node.world == 1:
            return CompressionOptState(_tile_state(node.comp, dp_world),
                                       world=dp_world)
        raise ValueError(
            f"compressor state was initialised for world={node.world} but "
            f"the mesh dp axis has {dp_world} shards; re-init the optimizer "
            f"state (opt.init) for this mesh")
    return jax.tree.map(
        fix, opt_state,
        is_leaf=lambda x: isinstance(x, CompressionOptState))


def _opt_state_specs(opt_state: PyTree, axis_name: str) -> PyTree:
    """P(axis_name) for per-worker compressor-state leaves (identified by
    sitting under a CompressionOptState), P() for everything else."""
    from jax.tree_util import tree_flatten_with_path

    paths_leaves, treedef = tree_flatten_with_path(opt_state)
    specs = []
    for path, leaf in paths_leaves:
        in_comp = any(getattr(k, "name", None) == "comp" for k in path)
        if in_comp and getattr(leaf, "ndim", 0) >= 1:
            specs.append(P(axis_name))
        else:
            specs.append(P())
    return jax.tree.unflatten(treedef, specs)
