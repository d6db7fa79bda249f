"""Asynchronous PS training: workers push weight deltas, no round barrier.

The reference's BYTEPS_ENABLE_ASYNC mode (reference: torch/__init__.py
step() under `_enable_async` at 186-214, server.cc:319-323): each worker
runs its local optimizer step, pushes the resulting weight *delta*
(w_new - w_old), and the server applies `store += delta` immediately —
no synchronization across workers.  The pull returns the server's current
global weights, which replace the worker's local params.  Convergence is
the classic async-SGD contract: workers may compute on slightly stale
weights.

TPU-native shape: the functional equivalent of the reference's in-place
`p.data.sub_(old); push_pull(p)` is an explicit trainer object that flattens
the param pytree once, tracks the last adopted global weights, and exposes
one `step(updated_params)` call.

Wire layout: with fusion enabled (BYTEPS_TPU_FUSION_BYTES > 0, the
default) the delta no longer rides one monolithic key — the fusion
planner (common/fusion.py) packs small param leaves into size-capped
buckets in reverse backprop order and leaves large params on their own
keys, each dispatched at its backprop-position priority through
PSSession.push_pull_group.  Last-layer buckets hit the wire first and the
session can overlap their round-trips instead of serializing one giant
transfer; BYTEPS_TPU_FUSION_BYTES=0 (or a session without
push_pull_group) restores the single flat vector.

Pipelining: by default the trainer double-buffers — `step()` dispatches the
new delta and waits only for the *previous* round, never its own, so each
round's network round-trip overlaps the local compute of the NEXT step
instead of serializing after it (the eager analog of the reference's
communication/compute overlap: core_loops.cc pipeline,
torch/cross_barrier.py).  Because consecutive rounds share partition keys,
the session's sequential-use guard orders round k+1's wire dispatch after
round k's pull — the overlap is round-trip-against-compute, not two
simultaneous wire transfers.  Each pushed delta is the pure local optimizer
movement, so pipelining never double-counts: the adopted view is
`global_after_previous_round + own_in_flight_movement`.
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np

PyTree = Any


class AsyncPSTrainer:
    """Weight-delta async training against an async-mode PS server tier.

    Usage (server must run with BYTEPS_ENABLE_ASYNC=1):

        trainer = AsyncPSTrainer(session, params, name="model")
        for batch in data:
            updated = local_sgd_step(trainer.params, batch)  # any local opt
            trainer.step(updated)          # push delta, adopt global view
            # trainer.params now holds the (possibly 1-round-stale) view
        final = trainer.finalize()         # drain in-flight, pure global

    `pipeline=False` restores the fully synchronous push→wait→adopt cycle
    (one round in flight, zero staleness relative to the server).
    """

    def __init__(self, session, params: PyTree, name: str = "async_param",
                 declared_key: Optional[int] = None, pipeline: bool = True,
                 fusion_bytes: Optional[int] = None, hierarchy=None):
        import jax

        if getattr(session, "server_async", True) is False:
            raise RuntimeError(
                "AsyncPSTrainer requires servers running with "
                "BYTEPS_ENABLE_ASYNC=1; against a sync server the weight-"
                "delta protocol would silently train on deltas")
        self._session = session
        self._pipeline = pipeline
        # Hierarchical reduction (BYTEPS_TPU_HIERARCHY=1, parallel/
        # hierarchy.py): slice-reduce each round's delta in-graph, only
        # the slice leader rides the wire, the pulled global weights
        # broadcast back.  None reads the env opt-in; pass an explicit
        # HierarchicalReducer to share a custom topology.
        if hierarchy is None:
            from .hierarchy import maybe_reducer
            hierarchy = maybe_reducer(session)
        self._hier = hierarchy
        self._treedef = jax.tree.structure(params)
        leaves = jax.tree.leaves(params)
        self._shapes = [np.shape(l) for l in leaves]
        self._sizes = [int(np.size(l)) for l in leaves]
        self._dtypes = [np.asarray(l).dtype for l in leaves]
        if declared_key is None:
            from ..core.native import get_core
            declared_key = get_core().declare_tensor(f"AsyncParam.{name}")
        self._key = declared_key
        self._chunks = self._plan_chunks(name, fusion_bytes)
        self._flat = self._flatten(params)
        # Outstanding round: (handle, in-flight movement) — at most one.
        self._pending = None
        # Seed the server store with the initial weights.  DT_SEED applies
        # only if the key has never been pushed — a late-joining or
        # rejoining worker adopts the live global weights from the pull
        # instead of resetting them (the analog of the reference's init
        # push populating the store before deltas flow,
        # reference: operations.cc:369-378).
        self._flat = self._dispatch(self._flat, seed=True).wait() \
            .astype(np.float32)

    def _plan_chunks(self, name: str, fusion_bytes: Optional[int]):
        """[(declared_key, flat_ranges, priority)] in priority-descending
        dispatch order, or None for the single-key layout.

        Routes the flat f32 param vector through the fusion planner:
        small leaves pack into buckets (reverse backprop order, bucket
        priority = max member position), large leaves go solo at their
        own position.  Chunk keys are derived from the deterministic
        bucket tags, so every worker — and a restarted worker after
        re-declare — maps the same params to the same wire keys.
        """
        from ..common import fusion
        from ..common.config import get_config
        from ..core.native import get_core

        fb = (get_config().fusion_bytes if fusion_bytes is None
              else int(fusion_bytes))
        if fb <= 0 or len(self._sizes) < 2 \
                or not hasattr(self._session, "push_pull_group"):
            return None
        plan = fusion.plan_buckets(
            tuple((i, n, "float32", 4) for i, n in enumerate(self._sizes)),
            fb)
        plan.record_use()
        offs = np.concatenate([[0], np.cumsum(self._sizes)]).astype(np.int64)
        core = get_core()
        # Chunk names incorporate the trainer's resolved key so trainers
        # kept distinct by an explicit declared_key (same `name`) stay
        # distinct on the wire, exactly as their single-key layouts would.
        base = f"AsyncParam.{name}.k{self._key}"
        chunks = []
        for b in plan.buckets:
            ranges = [(int(offs[li]), int(offs[li]) + n)
                      for li, n in b.members]
            chunks.append((core.declare_tensor(f"{base}.{b.tag}"),
                           ranges, b.priority))
        for li, prio in plan.solo:
            chunks.append((
                core.declare_tensor(f"{base}.leaf{li}"),
                [(int(offs[li]), int(offs[li + 1]))], prio))
        if len(chunks) < 2:
            return None
        # The scheduler's own order: push_pull_group queues each chunk as
        # it is staged, so they arrive in the order it would have picked.
        chunks.sort(key=lambda c: (-c[2], c[0]))
        return chunks

    def _dispatch(self, flat: np.ndarray, seed: bool = False):
        """Push one round's flat payload; returns an object whose
        .wait(timeout) yields the assembled global flat vector."""
        if self._hier is not None:
            # Hierarchical round: the slice's deltas sum in-graph, the
            # LEADER runs the wire leg below (same chunked layout), and
            # followers' handles resolve from the leader's broadcast.
            # Seeds skip the reduce — the initial weights are identical
            # on every member, and summing S copies would corrupt the
            # store (hierarchy.dispatch_round owns that law).
            return self._hier.dispatch_round(
                self._key, flat, seed=seed,
                leader_dispatch=lambda reduced: self._wire_dispatch(
                    reduced, seed))
        return self._wire_dispatch(flat, seed)

    def _wire_dispatch(self, flat: np.ndarray, seed: bool = False):
        if self._chunks is None:
            return self._session.push_pull_async(self._key, flat, seed=seed)
        items = [(key, _gather(flat, ranges), prio)
                 for key, ranges, prio in self._chunks]
        handles = self._session.push_pull_group(items, seed=seed)
        return _GroupRoundHandle(handles, self._chunks, len(flat))

    def _flatten(self, params: PyTree) -> np.ndarray:
        import jax

        leaves = jax.tree.leaves(params)
        return np.concatenate(
            [np.asarray(l, np.float32).ravel() for l in leaves])

    def _unflatten(self, flat: np.ndarray) -> PyTree:
        import jax

        out, off = [], 0
        for shape, size, dtype in zip(self._shapes, self._sizes,
                                      self._dtypes):
            out.append(flat[off:off + size].reshape(shape).astype(dtype))
            off += size
        return jax.tree.unflatten(self._treedef, out)

    @property
    def params(self) -> PyTree:
        """The current local view (last adopted global + own in-flight
        movement), as the original pytree."""
        return self._unflatten(self._flat)

    def step(self, updated_params: PyTree) -> PyTree:
        """Push the local movement (updated - current view) as a delta.

        Pipelined (default): dispatch the new delta, then wait for the
        PREVIOUS round's pull — which had the whole local compute step that
        produced `updated_params` to complete, so a step blocks on the
        network only for whatever round-trip time compute didn't already
        cover.  The adopted view is `global_after_prev +
        in_flight_movement`; the in-flight movement is folded in again when
        its own round is adopted next step, and the server has it already,
        so nothing is counted twice.
        """
        new_flat = self._flatten(updated_params)
        delta = new_flat - self._flat
        handle = self._dispatch(delta)
        if not self._pipeline:
            self._flat = handle.wait().astype(np.float32)
            return self.params
        prev, self._pending = self._pending, (handle, delta)
        if prev is not None:
            prev_handle, _prev_delta = prev
            g = prev_handle.wait().astype(np.float32)
            # g reflects the server *after* our previous round; our newest
            # movement (delta) is still in flight, so keep it locally.
            self._flat = g + delta
        else:
            self._flat = new_flat
        return self.params

    def finalize(self, timeout: Optional[float] = 300.0) -> PyTree:
        """Drain the in-flight round and adopt the pure global weights."""
        if self._pending is not None:
            handle, _delta = self._pending
            self._pending = None
            self._flat = handle.wait(timeout).astype(np.float32)
        return self.params

    # -- elastic input-pipeline re-sharding (docs/elasticity.md) ----------
    def data_shard(self, membership: Optional[dict] = None) -> tuple:
        """``(shard_index, shard_count)`` for this worker's input
        pipeline.  The index is this worker's position among the SORTED
        alive ids, so shards stay dense after a join or eviction even
        when worker ids have gaps; with no membership view (or a fixed
        epoch-0 job) it is the launch ``(worker_id, num_worker)``."""
        wid = int(getattr(self._session, "worker_id", 0))
        if membership is None or int(membership.get("epoch", 0)) == 0:
            from ..common.config import get_config
            return wid, max(1, int(get_config().num_worker))
        alive = sorted(int(w) for w in membership.get("alive", ()))
        if not alive:
            return 0, 1
        if wid not in alive:
            # Evicted self: the value is moot (this worker's pushes no
            # longer count) but must stay well-formed for shutdown paths.
            return 0, len(alive)
        return alive.index(wid), len(alive)

    def membership_callback(self, on_reshard):
        """A ``callback(membership)`` for :func:`bps.on_membership_change`
        that re-derives this worker's data shard on every epoch change
        and calls ``on_reshard(shard_index, shard_count, membership)``
        exactly when the shard actually moved — epoch bumps that leave
        the shard unchanged (e.g. an unrelated slice departing) stay
        quiet, so the input pipeline never reshuffles needlessly."""
        state = {"shard": self.data_shard()}

        def _cb(membership):
            shard = self.data_shard(membership)
            if shard != state["shard"]:
                state["shard"] = shard
                on_reshard(shard[0], shard[1], membership)

        return _cb

    def enable_reshard(self, on_reshard, poll_s: Optional[float] = None):
        """Wire :func:`bps.on_membership_change` into this trainer so the
        input pipeline re-shards itself on worker join/evict (ROADMAP
        autoscaling item (b)).

        ``on_reshard(shard_index, shard_count, membership)`` fires when —
        and only when — this worker's dense shard assignment changes;
        size()/rank() already follow the new epoch by the time it runs,
        so the handler can rebuild its data iterator directly.  Returns
        the registered callback (also usable standalone when the caller
        drives its own membership polling).  Requires an initialized PS
        session (``bps.init()``) — the api poller owns the CMD_MEMBERS
        traffic."""
        from ..common import api
        cb = self.membership_callback(on_reshard)
        api.on_membership_change(cb, poll_s)
        return cb


def _gather(flat: np.ndarray, ranges) -> np.ndarray:
    """Concatenate the flat-vector slices a chunk covers (a zero-copy view
    for the common single-run case)."""
    if len(ranges) == 1:
        a, b = ranges[0]
        return flat[a:b]
    return np.concatenate([flat[a:b] for a, b in ranges])


class _GroupRoundHandle:
    """Completion handle over one round's chunked dispatch: waits every
    chunk and scatters the pulled global values back into one flat f32
    vector (the single-key handle's .wait() contract)."""

    def __init__(self, handles, chunks, n: int):
        self._handles = handles
        self._chunks = chunks
        self._n = n

    def done(self) -> bool:
        return all(h.done() for h in self._handles)

    def wait(self, timeout: Optional[float] = 300.0) -> np.ndarray:
        import time
        # One deadline for the WHOLE round (the single-key contract), not
        # per chunk — num_chunks x timeout against a hung server would
        # stretch a 30s budget into minutes.
        deadline = None if timeout is None else time.monotonic() + timeout
        out = np.empty(self._n, np.float32)
        for h, (_key, ranges, _prio) in zip(self._handles, self._chunks):
            left = (None if deadline is None
                    else max(0.001, deadline - time.monotonic()))
            got = np.asarray(h.wait(left), np.float32).ravel()
            off = 0
            for a, b in ranges:
                out[a:b] = got[off:off + (b - a)]
                off += b - a
        return out
