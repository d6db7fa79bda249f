"""Hierarchical reduction: in-graph psum intra-slice, PS inter-slice.

The PS tier treats every chip as a lone worker: on an S-chip slice, S
workers each push the full gradient over the wire and pull the full sum
back, so the PS moves S× the bytes it needs to.  Real TPU pods compose
the two reduction planes instead (arXiv 2204.06514 "Scalable Training of
Language Models using JAX pjit and TPUv4"): XLA's native collectives
reduce *inside* a slice over ICI, and only one designated leader per
slice talks across slices over DCN.  This module is that composition for
the PS tier:

  1. the workers of one slice reduce their gradients in-graph — a
     ``psum`` under ``shard_map`` on the slice's device mesh;
  2. exactly ONE leader per slice runs the wire ``push_pull`` (riding
     the existing fusion planner and ``PSSession.push_pull_group``
     unchanged — the server sums the per-slice sums, which equals the
     sum over every chip);
  3. the pulled sum (or, under ``ServerOptTrainer``, the pulled
     parameters) broadcasts back to the slice's members in-graph.

Per-slice wire bytes drop by the slice size on BOTH legs: followers
never touch the data plane at all.

Topology & leadership
---------------------
Slices are contiguous worker-id ranges: worker ``w`` belongs to slice
``w // slice_size`` (the DMLC_WORKER_ID convention — chips of one host
get consecutive ids).  The leader of a slice is its LOWEST ALIVE member
under the current membership epoch (:meth:`PSSession.slice_leader`), so
leadership fails over inside the slice when the leader is evicted, and
an entirely-departed slice simply stops being expected — the server's
round completion counts *slices*, not chips (``core/server.cc``
``RoundComplete`` under ``BYTEPS_TPU_SLICE_SIZE``), expressed through
the same epoch/``round_members`` machinery elastic membership already
uses.  ``slice_size=1`` (the default) degenerates to flat mode exactly:
every worker is the sole member and leader of its own slice, every
reduce is the identity, and the wire is byte-identical to today.

Colocation contract
-------------------
Intra-slice reduction is in-graph, so a slice's members must share one
process (the JAX single-controller model: one process drives the
slice's devices; in tests, worker threads each driving one CPU device).
The process-wide :func:`get_slice_group` registry hands every member
the same :class:`SliceGroup`; a member that never shows up surfaces as
a loud ``TimeoutError`` naming the missing ids, never a silent hang.

Exactness: the slice reduce reassociates the float sum ((g0+g1)+(g2+g3)
instead of the server's arrival order), so flat-vs-hierarchical
trajectories are bit-identical exactly when the sums are (integer-valued
f32 gradients, or any value set whose sum is exact) — the same law
elastic re-finalization already documents for merge order.

Enable with ``BYTEPS_TPU_HIERARCHY=1`` + ``BYTEPS_TPU_SLICE_SIZE=S`` on
workers AND servers (the server needs the slice size for round
completion).  Off by default; an unarmed run constructs none of this
and the wire is byte-identical to flat mode (recording-stub asserted in
tests/test_hierarchy.py).
"""

from __future__ import annotations

import functools
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

__all__ = [
    "slice_of", "slice_members", "elect_leader", "intra_slice_psum",
    "SliceGroup", "get_slice_group", "reset_slice_groups",
    "HierarchicalReducer", "maybe_reducer",
]


# ---------------------------------------------------------------------------
# Topology laws (shared with server.cc RoundComplete and
# PSSession.slice_leader — one definition per side, same math)
# ---------------------------------------------------------------------------
def slice_of(worker_id: int, slice_size: int) -> int:
    """The slice a worker id belongs to: contiguous ranges of
    ``slice_size`` ids (slice 0 = ids [0, S), slice 1 = [S, 2S), ...)."""
    s = max(1, int(slice_size))
    return int(worker_id) // s


def slice_members(slice_id: int, slice_size: int,
                  world: Optional[int] = None) -> List[int]:
    """The worker ids of one slice, clipped to ``world`` when given (the
    last slice of a non-multiple world is short, never padded)."""
    s = max(1, int(slice_size))
    lo = int(slice_id) * s
    hi = lo + s
    if world is not None:
        hi = min(hi, int(world))
    return list(range(lo, hi))


def elect_leader(members: Sequence[int],
                 alive: Optional[Sequence[int]] = None) -> Optional[int]:
    """The slice leader: the LOWEST ALIVE member (None = launch set, all
    alive).  Returns None when the whole slice has departed — the server
    then stops expecting the slice at the next epoch boundary, so "a
    slice leaving reads as as many chips leaving"."""
    pool = [int(m) for m in members]
    if alive is not None:
        live = {int(a) for a in alive}
        pool = [m for m in pool if m in live]
    return min(pool) if pool else None


# ---------------------------------------------------------------------------
# In-graph intra-slice reduction
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=8)
def _psum_fn(mesh):
    """Cached jitted shard_map psum over the mesh's single axis — a
    fresh lambda per call would miss jax.jit's cache (keyed on function
    identity) and retrace every slice reduce."""
    import jax
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[-1]

    def body(x):
        return jax.lax.psum(x, axis)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(axis), out_specs=P()))


def intra_slice_psum(stacked: np.ndarray, mesh=None) -> np.ndarray:
    """Sum ``stacked`` (members, n) over axis 0 IN-GRAPH: one member row
    per device of the slice mesh, reduced by ``psum`` under ``shard_map``.

    Falls back to a deterministic host sum (ascending member order) when
    the process has fewer addressable devices than members — the values
    are identical for exactly-summable gradients either way; only the
    engine differs.
    """
    stacked = np.ascontiguousarray(stacked, dtype=np.float32)
    n = stacked.shape[0]
    if n == 1:
        return stacked[0]
    if mesh is None:
        mesh = _default_slice_mesh(n)
    if mesh is None:
        return np.add.reduce(stacked, axis=0, dtype=np.float32)
    return np.asarray(_psum_fn(mesh)(stacked))[0]


@functools.lru_cache(maxsize=8)
def _default_slice_mesh(n: int):
    """One mesh per member count, cached so every reduce of the same
    width reuses the same Mesh object (and _psum_fn's jit cache)."""
    from .mesh import make_slice_mesh
    return make_slice_mesh(n)


# ---------------------------------------------------------------------------
# SliceGroup: the rendezvous the slice's colocated members meet at
# ---------------------------------------------------------------------------
_UNSET = object()


class SliceGroup:
    """In-process rendezvous for the workers of ONE slice.

    Two channels, both keyed by a caller-supplied round key (the
    declared key, or a tuple of them for a fused group) plus a
    per-member sequence counter, so concurrent rounds on different keys
    — and handles synchronized out of call order — can never cross:

    - :meth:`reduce`: every member contributes its arrays; all members
      return the SAME slice-summed arrays (the in-graph psum ran once).
    - :meth:`broadcast`: the leader publishes a value; every member
      (including the leader) returns it.

    A member that never arrives fails the round with a ``TimeoutError``
    naming the missing ids — the colocation contract breaking loudly.
    """

    def __init__(self, slice_id: int, members: Sequence[int], mesh=None,
                 timeout_s: float = 120.0):
        self.slice_id = int(slice_id)
        self.members = sorted(int(m) for m in members)
        if not self.members:
            raise ValueError("a SliceGroup needs at least one member")
        self.mesh = mesh
        self.timeout_s = float(timeout_s)
        self._cv = threading.Condition()
        self._seq: Dict[tuple, int] = {}     # (chan, key, wid) -> next seq
        self._rounds: Dict[tuple, dict] = {}  # (chan, key, seq) -> state

    def __len__(self) -> int:
        return len(self.members)

    def _next_seq(self, chan: str, key, wid: int) -> int:
        k = (chan, key, wid)
        s = self._seq.get(k, 0)
        self._seq[k] = s + 1
        return s

    def _round(self, chan: str, key, seq: int) -> dict:
        return self._rounds.setdefault(
            (chan, key, seq),
            {"contrib": {}, "result": _UNSET, "taken": set()})

    def _finish(self, chan: str, key, seq: int, st: dict,
                wid: int) -> Any:
        st["taken"].add(wid)
        if len(st["taken"]) == len(self.members):
            del self._rounds[(chan, key, seq)]
        return st["result"]

    def _await(self, st: dict, chan: str, key) -> None:
        import time
        deadline = time.monotonic() + self.timeout_s
        while st["result"] is _UNSET:
            left = deadline - time.monotonic()
            if left <= 0 or not self._cv.wait(timeout=min(1.0, left)):
                if st["result"] is not _UNSET:
                    return
                if time.monotonic() >= deadline:
                    here = sorted(st["contrib"]) or sorted(st["taken"])
                    missing = [m for m in self.members if m not in here]
                    raise TimeoutError(
                        f"slice {self.slice_id} {chan} round on key "
                        f"{key!r} timed out after {self.timeout_s:.0f}s "
                        f"waiting on member(s) {missing} (slice members "
                        f"must share this process — see "
                        f"docs/architecture.md 'Hierarchical reduction')")

    def reduce(self, worker_id: int, key, arrays: List[np.ndarray]
               ) -> List[np.ndarray]:
        """Rendezvous all members, sum their arrays element-wise via the
        in-graph psum, return the summed list to every member."""
        flats = [np.ascontiguousarray(a, dtype=np.float32).ravel()
                 for a in arrays]
        with self._cv:
            seq = self._next_seq("reduce", key, worker_id)
            st = self._round("reduce", key, seq)
            st["contrib"][worker_id] = flats
            if len(st["contrib"]) == len(self.members):
                # Last arrival runs the reduction for everyone: ONE
                # concatenated psum per round, not one per array.
                per_member = [st["contrib"][m] for m in self.members]
                sizes = [f.size for f in per_member[0]]
                stacked = np.stack(
                    [np.concatenate(fs) if len(fs) > 1 else fs[0]
                     for fs in per_member])
                summed = intra_slice_psum(stacked, mesh=self.mesh)
                out, off = [], 0
                for a, n in zip(arrays, sizes):
                    out.append(summed[off:off + n]
                               .reshape(np.shape(a)).astype(np.float32))
                    off += n
                st["result"] = out
                st["contrib"].clear()    # drop member refs promptly
                self._cv.notify_all()
            else:
                self._await(st, "reduce", key)
            return self._finish("reduce", key, seq, st, worker_id)

    def broadcast(self, worker_id: int, key, value=_UNSET) -> Any:
        """Leader publishes ``value``; every member returns it.  Callers
        without a value block until the leader's arrives."""
        with self._cv:
            seq = self._next_seq("bcast", key, worker_id)
            st = self._round("bcast", key, seq)
            if value is not _UNSET:
                st["result"] = value
                self._cv.notify_all()
            else:
                self._await(st, "bcast", key)
            return self._finish("bcast", key, seq, st, worker_id)

    def poll(self, worker_id: int, key) -> bool:
        """True when this member's NEXT broadcast round already has its
        value (non-consuming — the follower-side handle-poll signal)."""
        with self._cv:
            seq = self._seq.get(("bcast", key, worker_id), 0)
            st = self._rounds.get(("bcast", key, seq))
            return st is not None and st["result"] is not _UNSET


# Process-wide registry: colocated worker threads constructing reducers
# for the same slice meet at the same group object.
_groups_lock = threading.Lock()
_groups: Dict[tuple, SliceGroup] = {}


def get_slice_group(slice_id: int, members: Sequence[int], mesh=None,
                    timeout_s: float = 120.0) -> SliceGroup:
    """The process-shared SliceGroup for (slice_id, members) — created on
    first request, returned to every later member."""
    key = (int(slice_id), tuple(sorted(int(m) for m in members)))
    with _groups_lock:
        g = _groups.get(key)
        if g is None:
            g = SliceGroup(slice_id, members, mesh=mesh,
                           timeout_s=timeout_s)
            _groups[key] = g
        return g


def reset_slice_groups() -> None:
    """Drop the registry (tests; a fresh job must not meet a dead
    group's counters)."""
    with _groups_lock:
        _groups.clear()


def drop_slice_group(group: SliceGroup) -> None:
    """Retire ONE group from the registry (api.shutdown): a later
    re-init in the same process must get a fresh group with fresh seq
    counters — a failed round can leave members' counters desynced —
    while groups other in-process workers still hold stay untouched."""
    with _groups_lock:
        for k, g in list(_groups.items()):
            if g is group:
                del _groups[k]


# ---------------------------------------------------------------------------
# HierarchicalReducer: one worker's view of the two-plane reduction
# ---------------------------------------------------------------------------
class _LeaderHandle:
    """Leader-side round handle: wait the wire handle, broadcast the
    pulled value to the slice, return it."""

    def __init__(self, reducer: "HierarchicalReducer", key, inner):
        self._r = reducer
        self._key = key
        self._inner = inner

    def done(self) -> bool:
        return self._inner.done()

    def wait(self, timeout: Optional[float] = 300.0) -> np.ndarray:
        try:
            out = np.asarray(self._inner.wait(timeout), np.float32)
        except Exception as e:
            # Followers are blocked on the broadcast: a leader-side wire
            # failure must propagate to the WHOLE slice, not strand it.
            self._r.group.broadcast(self._r.worker_id, self._key,
                                    value=_WireError(e))
            raise
        self._r.group.broadcast(self._r.worker_id, self._key, value=out)
        return out


class _FollowerHandle:
    """Follower-side round handle: the pulled value arrives via the
    leader's broadcast — zero wire traffic on this worker."""

    def __init__(self, reducer: "HierarchicalReducer", key):
        self._r = reducer
        self._key = key

    def done(self) -> bool:
        return self._r.group.poll(self._r.worker_id, self._key)

    def wait(self, timeout: Optional[float] = 300.0) -> np.ndarray:
        out = self._r.group.broadcast(self._r.worker_id, self._key)
        if isinstance(out, _WireError):
            raise RuntimeError(
                f"slice {self._r.slice_id} leader "
                f"{self._r.leader()} wire round failed: "
                f"{out.exc}") from out.exc
        return out


class _WireError:
    """Broadcast payload marking a leader-side wire failure."""

    def __init__(self, exc: Exception):
        self.exc = exc


class HierarchicalReducer:
    """One worker's hierarchical push_pull plane.

    ``dispatch_round`` is the trainer face (one flat vector per round);
    ``reduce_payloads``/``publish_outs``/``await_outs`` are the face
    every round of api.py rides, ``push_pull``'s one unit and a tree's
    many alike (the leader keeps the fusion-planner +
    ``push_pull_group`` dispatch verbatim).
    """

    def __init__(self, session, worker_id: int, slice_size: int,
                 world: Optional[int] = None, group: Optional[SliceGroup]
                 = None, mesh=None, timeout_s: float = 120.0):
        self.session = session
        self.worker_id = int(worker_id)
        self.slice_size = max(1, int(slice_size))
        self.world = int(world) if world else None
        self.slice_id = slice_of(self.worker_id, self.slice_size)
        members = slice_members(self.slice_id, self.slice_size, self.world)
        self.group = group or get_slice_group(
            self.slice_id, members, mesh=mesh, timeout_s=timeout_s)
        self._lock = threading.Lock()
        self.stats = {
            "leader_rounds": 0,      # wire rounds this worker ran
            "follower_rounds": 0,    # wire rounds this worker skipped
            "intra_reduces": 0,      # in-graph slice reductions joined
            "wire_bytes_saved": 0,   # push+pull payload bytes not sent
        }
        self._update_gauges()

    # -- leadership ---------------------------------------------------------
    def leader(self) -> Optional[int]:
        """The CURRENT leader of this worker's slice, elected from the
        session's last observed membership epoch (client.py owns the
        election so it rides the same view rounds are pinned to)."""
        fn = getattr(self.session, "slice_leader", None)
        if fn is not None:
            return fn(self.slice_size, world=self.world)
        return elect_leader(self.group.members)

    @property
    def is_leader(self) -> bool:
        return self.leader() == self.worker_id

    # -- trainer face: one flat vector per round ----------------------------
    def dispatch_round(self, key, flat: np.ndarray, seed: bool = False,
                       leader_dispatch: Optional[Callable] = None):
        """One hierarchical round: slice-reduce ``flat`` in-graph, the
        leader dispatches the reduced vector on the wire, everyone gets
        a handle whose ``.wait()`` is the pulled value.

        ``seed=True`` skips the reduce — a seed is the initial weights,
        identical on every member by contract, and summing S copies
        would corrupt the store.  ``leader_dispatch(reduced) -> handle``
        overrides the wire leg (AsyncPSTrainer's fused chunk layout);
        the default is a plain ``session.push_pull_async``.
        """
        flat = np.ascontiguousarray(flat, dtype=np.float32).ravel()
        if seed or len(self.group) == 1:
            reduced = flat
        else:
            reduced = self.group.reduce(self.worker_id, key, [flat])[0]
            with self._lock:
                self.stats["intra_reduces"] += 1
        if self.is_leader:
            try:
                if leader_dispatch is None:
                    inner = self.session.push_pull_async(
                        key, reduced, seed=seed)
                else:
                    inner = leader_dispatch(reduced)
            except Exception as e:
                # Followers are already past the reduce, blocked on the
                # broadcast: a stage-time failure must fail the slice's
                # round, not strand it until the rendezvous timeout.
                self.group.broadcast(self.worker_id, key,
                                     value=_WireError(e))
                raise
            with self._lock:
                self.stats["leader_rounds"] += 1
            self._update_gauges()
            return _LeaderHandle(self, key, inner)
        with self._lock:
            self.stats["follower_rounds"] += 1
            # Both legs skipped: the push payload AND the pull reply.
            self.stats["wire_bytes_saved"] += 2 * int(flat.nbytes)
        self._record_saved(2 * int(flat.nbytes))
        self._update_gauges()
        return _FollowerHandle(self, key)

    def push_pull_flat(self, key, flat: np.ndarray, seed: bool = False,
                       timeout: Optional[float] = 300.0) -> np.ndarray:
        """Synchronous :meth:`dispatch_round` (the ServerOptTrainer
        shape: the pull IS the updated parameters there)."""
        return self.dispatch_round(key, flat, seed=seed).wait(timeout)

    # -- round face (api._Round) --------------------------------------------
    def reduce_payloads(self, key, payloads: List[np.ndarray]
                        ) -> List[np.ndarray]:
        """Slice-reduce every dispatch unit's raw f32 payload in ONE
        in-graph psum, BEFORE the leader's wire compression — the codec
        then encodes the slice sum once instead of S gradients."""
        if len(self.group) == 1:
            return [np.ascontiguousarray(p, dtype=np.float32).ravel()
                    for p in payloads]
        out = self.group.reduce(self.worker_id, key, list(payloads))
        with self._lock:
            self.stats["intra_reduces"] += 1
        return out

    def publish_outs(self, key, outs: List[np.ndarray]) -> None:
        """Leader side: hand the round's decompressed, averaged unit
        outputs to the slice."""
        self.group.broadcast(self.worker_id, key, value=list(outs))
        with self._lock:
            self.stats["leader_rounds"] += 1
        self._update_gauges()

    def publish_failure(self, key, exc: Exception) -> None:
        """Leader side: fail the slice's round loudly instead of
        stranding followers on a broadcast that never comes."""
        self.group.broadcast(self.worker_id, key, value=_WireError(exc))

    def await_outs(self, key, skipped_bytes: int = 0) -> List[np.ndarray]:
        """Follower side: receive the round's unit outputs;
        ``skipped_bytes`` is the payload this worker did NOT push (the
        pull leg is counted as the same size)."""
        with self._lock:
            self.stats["follower_rounds"] += 1
            self.stats["wire_bytes_saved"] += 2 * int(skipped_bytes)
        self._record_saved(2 * int(skipped_bytes))
        self._update_gauges()
        out = self.group.broadcast(self.worker_id, key)
        if isinstance(out, _WireError):
            raise RuntimeError(
                f"slice {self.slice_id} leader {self.leader()} wire "
                f"round failed: {out.exc}") from out.exc
        return out

    def verify_topology(self) -> Optional[str]:
        """Cross-check this worker's slice size against the server tier's
        (CMD_STATS carries it).  Returns a human-readable mismatch
        description, or None when consistent / unverifiable.

        The mismatch's symptom without this check is the worst kind: a
        leaders-only round against a flat server just hangs until the
        wait timeout, naming nobody.  Called by api.init() (logged as an
        ERROR); direct-session users can call it themselves."""
        try:
            st = self.session.server_stats()
        except Exception:
            return None     # stats unreachable ≠ misconfigured
        srv = int(st.get("slice_size", 1))
        if srv == self.slice_size:
            return None
        return (f"worker slice_size={self.slice_size} but the server "
                f"tier runs slice_size={srv}"
                + (" (no BYTEPS_TPU_SLICE_SIZE on the servers, or a "
                   "pre-hierarchy server build)" if srv == 1 else "")
                + " — rounds will wait on pushes that never come; set "
                  "the SAME BYTEPS_TPU_SLICE_SIZE on workers and "
                  "servers (docs/env.md)")

    # -- observability ------------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            s = dict(self.stats)
        s.update(armed=True, worker_id=self.worker_id,
                 slice_id=self.slice_id, slice_size=self.slice_size,
                 members=list(self.group.members), leader=self.leader(),
                 is_leader=self.is_leader)
        return s

    def _record_saved(self, nbytes: int) -> None:
        from ..common import telemetry
        telemetry.record_hierarchy_saved(nbytes)

    def _update_gauges(self) -> None:
        from ..common import telemetry
        telemetry.update_hierarchy(
            slice_id=self.slice_id, slice_size=self.slice_size,
            is_leader=self.is_leader,
            members=len(self.group.members))


def maybe_reducer(session, worker_id: Optional[int] = None,
                  world: Optional[int] = None
                  ) -> Optional[HierarchicalReducer]:
    """A HierarchicalReducer when the env opts in
    (``BYTEPS_TPU_HIERARCHY=1``), else None — the trainers' and api.py's
    one-line opt-in.  Reads ``BYTEPS_TPU_SLICE_SIZE`` for the topology;
    worker id / world default to the session's id and the config
    launch count."""
    import os

    if os.environ.get("BYTEPS_TPU_HIERARCHY", "0") != "1":
        return None
    if session is None:
        return None
    from ..common.config import get_config
    cfg = get_config()
    slice_size = int(os.environ.get("BYTEPS_TPU_SLICE_SIZE")
                     or cfg.slice_size or 1)
    wid = session.worker_id if worker_id is None else int(worker_id)
    w = cfg.num_worker if world is None else int(world)
    return HierarchicalReducer(session, wid, slice_size, world=w)
