"""Hierarchical reduction tests (parallel/hierarchy.py + the
slice-aware server, docs/architecture.md "Hierarchical reduction").

The acceptance set from ISSUE 15:

- a 2-slice x 2-chip hierarchical run (4 in-process workers, CPU mesh)
  produces weight trajectories BIT-IDENTICAL to the flat 4-worker run
  while the transport counters show per-host push/pull wire bytes
  reduced ~2x (the slice size);
- with ``BYTEPS_TPU_HIERARCHY`` unset the wire is byte-identical to
  today, and single-chip slices (slice_size=1) degenerate to flat
  exactly (both recording-stub asserted);
- the server's round completion counts slices, not chips: leaders-only
  rounds publish, a whole slice leaving reads as that many chips
  leaving through the epoch machinery, and leadership fails over inside
  a slice when the leader is evicted.
"""

import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from byteps_tpu.parallel import hierarchy as H
from byteps_tpu.server.client import (
    PSSession, CMD_HELLO, CMD_INIT, CMD_PUSH, CMD_PULL,
)

from testutil import cpu_env, free_port, StubPSServer


# ---------------------------------------------------------------------------
# harness (the test_elastic.py server fixture, plus slice env plumbing)
# ---------------------------------------------------------------------------
@pytest.fixture
def ps_server():
    made = []

    def start(num_workers=4, slice_size=0, evict_s=0.0, extra_env=None):
        port = free_port()
        env = cpu_env({
            "DMLC_PS_ROOT_PORT": str(port - 1),
            "DMLC_NUM_WORKER": str(num_workers),
            "BYTEPS_SERVER_ENGINE_THREAD": "2",
            "BYTEPS_TPU_SLICE_SIZE": str(slice_size) if slice_size else "",
            "BYTEPS_TPU_EVICT_TIMEOUT_S": str(evict_s) if evict_s else "",
            "JAX_PLATFORMS": "cpu",
            **(extra_env or {}),
        })
        proc = subprocess.Popen(
            [sys.executable, "-m", "byteps_tpu.server"], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        made.append(proc)
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                socket.create_connection(("127.0.0.1", port), 0.5).close()
                return port
            except OSError:
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"server died rc={proc.returncode}")
                time.sleep(0.1)
        raise TimeoutError("PS server did not come up")

    yield start
    for p in made:
        p.kill()
        p.wait()


@pytest.fixture(autouse=True)
def _fresh_groups():
    H.reset_slice_groups()
    yield
    H.reset_slice_groups()


def _session(port, wid, slice_size=1, evict_s=0.0, **kw):
    kw.setdefault("wire_conns", 1)
    return PSSession(["127.0.0.1"], [port], worker_id=wid, num_servers=1,
                     slice_size=slice_size, evict_timeout_s=evict_s, **kw)


def _int_grads(world, rounds, dim, seed=7):
    """Integer-valued f32 gradients: every sum is exact, so flat-vs-
    hierarchical trajectories must match BIT-for-bit regardless of
    merge/reassociation order."""
    rng = np.random.default_rng(seed)
    return {(w, r): rng.integers(-8, 9, dim).astype(np.float32)
            for w in range(world) for r in range(rounds)}


# ---------------------------------------------------------------------------
# topology + election laws
# ---------------------------------------------------------------------------
def test_slice_topology_laws():
    assert [H.slice_of(w, 2) for w in range(5)] == [0, 0, 1, 1, 2]
    assert H.slice_members(1, 2, world=4) == [2, 3]
    assert H.slice_members(1, 3, world=7) == [3, 4, 5]
    assert H.slice_members(2, 3, world=7) == [6]      # short tail slice
    # slice_size=1: every worker is its own slice (the flat degenerate).
    assert H.slice_members(3, 1, world=4) == [3]


def test_leader_election_lowest_alive():
    assert H.elect_leader([2, 3]) == 2                    # launch set
    assert H.elect_leader([2, 3], alive=[0, 1, 2, 3]) == 2
    assert H.elect_leader([2, 3], alive=[0, 3]) == 3      # failover
    assert H.elect_leader([2, 3], alive=[0, 1]) is None   # slice gone


def test_session_slice_leader_follows_membership(ps_server):
    """client.py's election: launch set -> lowest slice id; after the
    leader's eviction the next membership fetch moves leadership to the
    lowest survivor (the membership-epoch law)."""
    evict_s = 0.6
    port = ps_server(num_workers=4, slice_size=2, evict_s=evict_s)
    s2 = _session(port, 2, slice_size=2, evict_s=evict_s)
    s3 = _session(port, 3, slice_size=2, evict_s=evict_s)
    try:
        assert s3.slice_leader() == 2         # launch electorate
        assert s2.slice_leader() == 2
        s2.close()                            # leader dies, no goodbye
        # (workers 0/1 never opened sessions, so their launch leases
        # lapse too — only worker 3 keeps a heartbeat.)
        deadline = time.time() + 8 * evict_s
        while time.time() < deadline:
            m = s3.membership()
            if not m["workers"].get(2, {}).get("alive", True):
                break
            time.sleep(0.05)
        m = s3.membership()
        assert m["workers"][2]["alive"] is False
        assert s3.slice_leader() == 3         # leadership moved
    finally:
        s3.close()


# ---------------------------------------------------------------------------
# SliceGroup + in-graph psum
# ---------------------------------------------------------------------------
def test_intra_slice_psum_in_graph_matches_host_sum():
    """The shard_map/psum engine (conftest's 8 CPU devices) and the host
    fallback must produce identical sums."""
    from byteps_tpu.parallel.mesh import make_slice_mesh

    rng = np.random.default_rng(0)
    stacked = rng.integers(-100, 100, (2, 513)).astype(np.float32)
    mesh = make_slice_mesh(2)
    assert mesh is not None, "conftest guarantees 8 CPU devices"
    got = H.intra_slice_psum(stacked, mesh=mesh)
    np.testing.assert_array_equal(got, stacked[0] + stacked[1])
    # Host fallback path (more members than devices): same values.
    big = rng.integers(-100, 100, (3, 64)).astype(np.float32)
    assert make_slice_mesh(1000) is None
    np.testing.assert_array_equal(
        H.intra_slice_psum(big, mesh=None) if make_slice_mesh(3) is None
        else H.intra_slice_psum(big), big.sum(axis=0, dtype=np.float32))


def test_slice_group_reduce_broadcast_threads():
    g = H.SliceGroup(0, [0, 1], timeout_s=20.0)
    out = {}

    def member(wid, scale):
        a = np.arange(8, dtype=np.float32) * scale
        b = np.full(3, scale, np.float32)
        ra, rb = g.reduce(wid, "k", [a, b])
        out[(wid, "a")], out[(wid, "b")] = ra, rb
        if wid == 0:
            g.broadcast(wid, "k", value=ra * 100)
        else:
            out["bcast"] = g.broadcast(wid, "k")

    ts = [threading.Thread(target=member, args=(w, w + 1))
          for w in (0, 1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    assert all(not t.is_alive() for t in ts)
    want_a = np.arange(8, dtype=np.float32) * 3
    np.testing.assert_array_equal(out[(0, "a")], want_a)
    np.testing.assert_array_equal(out[(1, "a")], want_a)
    np.testing.assert_array_equal(out[(0, "b")], np.full(3, 3, np.float32))
    np.testing.assert_array_equal(out["bcast"], want_a * 100)


def test_slice_group_timeout_names_missing_member():
    g = H.SliceGroup(1, [2, 3], timeout_s=0.3)
    with pytest.raises(TimeoutError, match=r"\[3\]"):
        g.reduce(2, "k", [np.ones(4, np.float32)])


def test_slice_group_registry_shares_instances():
    a = H.get_slice_group(0, [0, 1])
    b = H.get_slice_group(0, [1, 0])
    c = H.get_slice_group(1, [2, 3])
    assert a is b and a is not c
    H.reset_slice_groups()
    assert H.get_slice_group(0, [0, 1]) is not a


def test_maybe_reducer_env_gated(monkeypatch):
    class _Sess:
        worker_id = 1

    monkeypatch.delenv("BYTEPS_TPU_HIERARCHY", raising=False)
    assert H.maybe_reducer(_Sess()) is None
    monkeypatch.setenv("BYTEPS_TPU_HIERARCHY", "1")
    monkeypatch.setenv("BYTEPS_TPU_SLICE_SIZE", "2")
    r = H.maybe_reducer(_Sess(), world=4)
    assert r is not None
    assert (r.slice_id, r.slice_size, r.group.members) == (0, 2, [0, 1])
    assert r.leader() == 0 and not r.is_leader


# ---------------------------------------------------------------------------
# ACCEPTANCE: 2-slice x 2-chip vs flat 4-worker — bit-identical weights,
# ~2x fewer wire bytes
# ---------------------------------------------------------------------------
def _train_world(port, world, slice_size, grads, rounds, dim,
                 hier: bool):
    """Run `world` in-process workers for `rounds` sync rounds; returns
    (trajectories, per-worker wire payload bytes, reducers)."""
    sessions = [_session(port, w, slice_size=slice_size if hier else 1)
                for w in range(world)]
    reducers = ([H.HierarchicalReducer(s, w, slice_size, world=world)
                 for w, s in enumerate(sessions)] if hier else None)
    traj = {w: [] for w in range(world)}
    errors = []

    def worker(w):
        try:
            wt = np.zeros(dim, np.float32)
            for r in range(rounds):
                if hier:
                    got = reducers[w].push_pull_flat(1, grads[(w, r)])
                else:
                    got = sessions[w].push_pull_async(
                        1, grads[(w, r)]).wait(30)
                wt = wt - np.float32(0.1) * np.asarray(got, np.float32)
                traj[w].append(wt.copy())
        except Exception as e:          # pragma: no cover - diagnostics
            errors.append((w, e))

    ts = [threading.Thread(target=worker, args=(w,))
          for w in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(120)
    assert not errors, errors
    assert all(not t.is_alive() for t in ts)
    wire = [s.transport_stats()["lane_bytes_total"] for s in sessions]
    stats = sessions[0].server_stats()
    for s in sessions:
        s.close()
    return traj, wire, stats, reducers


def test_hier_2x2_bit_identical_and_wire_halved(ps_server):
    """THE acceptance: 2 slices x 2 chips, CPU mesh, integer gradients —
    weight trajectories bit-identical to the flat 4-worker run; total
    push/pull payload bytes ~2x lower (followers at exactly zero)."""
    world, rounds, dim = 4, 6, 256
    grads = _int_grads(world, rounds, dim)

    flat_port = ps_server(num_workers=world)            # flat server
    traj_f, wire_f, stats_f, _ = _train_world(
        flat_port, world, 1, grads, rounds, dim, hier=False)

    H.reset_slice_groups()
    hier_port = ps_server(num_workers=world, slice_size=2)
    traj_h, wire_h, stats_h, reducers = _train_world(
        hier_port, world, 2, grads, rounds, dim, hier=True)

    # Bit-identical trajectories, every worker, every round.
    for w in range(world):
        assert len(traj_h[w]) == rounds
        for r in range(rounds):
            assert np.array_equal(traj_f[w][r], traj_h[w][r]), (w, r)

    # Wire math: every flat worker paid full freight; hierarchically
    # only the two leaders did, followers exactly zero — total ~2x less.
    assert all(b > 0 for b in wire_f)
    assert wire_h[0] > 0 and wire_h[2] > 0
    assert wire_h[1] == 0 and wire_h[3] == 0
    ratio = sum(wire_h) / sum(wire_f)
    assert 0.4 <= ratio <= 0.6, (wire_f, wire_h)

    # The server counts in slices and says so; the reducers' counters
    # carry the saved bytes (what bps_hierarchy_wire_bytes_saved_total
    # exports).
    assert stats_h.get("slice_size") == 2
    assert stats_f.get("slice_size") == 1
    snap = reducers[1].snapshot()
    assert snap["is_leader"] is False
    assert snap["follower_rounds"] == rounds
    assert snap["wire_bytes_saved"] == rounds * 2 * dim * 4
    assert reducers[0].snapshot()["is_leader"] is True
    assert reducers[0].snapshot()["leader_rounds"] == rounds


def test_round_completion_counts_slices_not_chips(ps_server):
    """Leaders-only rounds publish: with slice_size=2 and 4 launch
    workers, pushes from workers 0 and 2 complete the round — the
    epoch-0 dense set maps to {slice0, slice1} coverage."""
    port = ps_server(num_workers=4, slice_size=2)
    s0 = _session(port, 0, slice_size=2)
    s2 = _session(port, 2, slice_size=2)
    try:
        a = np.arange(32, dtype=np.float32)
        t0 = time.monotonic()
        h0 = s0.push_pull_async(1, a)
        h2 = s2.push_pull_async(1, a * 10)
        np.testing.assert_array_equal(h0.wait(20), a + a * 10)
        np.testing.assert_array_equal(h2.wait(20), a + a * 10)
        assert time.monotonic() - t0 < 10   # no wait on chips 1 and 3
    finally:
        s0.close()
        s2.close()


def test_slice_leaving_reads_as_chips_leaving(ps_server):
    """A whole slice vanishing (leader AND follower evicted) must
    re-finalize the survivor's open round through the epoch machinery —
    the slice stops being expected, not just one chip."""
    evict_s = 0.6
    port = ps_server(num_workers=4, slice_size=2, evict_s=evict_s)
    sess = [_session(port, w, slice_size=2, evict_s=evict_s)
            for w in range(4)]
    try:
        a = np.arange(16, dtype=np.float32)
        # Round 0: both leaders (0 and 2) push; completes.
        h0 = sess[0].push_pull_async(1, a)
        h2 = sess[2].push_pull_async(1, a * 10)
        h0.wait(20), h2.wait(20)
        # Slice 1 (workers 2 AND 3) dies wholesale.
        sess[2].close()
        sess[3].close()
        t0 = time.monotonic()
        got = sess[0].push_pull_async(1, a).wait(30)
        dt = time.monotonic() - t0
        np.testing.assert_array_equal(got, a)   # solo-slice publish
        assert dt < 8 * evict_s, f"re-finalize took {dt:.2f}s"
        m = sess[0].membership()
        assert m["alive"] == [0, 1]
    finally:
        for s in (sess[0], sess[1]):
            s.close()


def test_leader_failover_within_slice(ps_server):
    """The leader's eviction moves the wire role to the lowest surviving
    member: worker 1's election flips to leader and its pushes complete
    rounds (slice coverage accepts any member, so a mid-round handover
    cannot wedge)."""
    evict_s = 0.6
    port = ps_server(num_workers=4, slice_size=2, evict_s=evict_s)
    s0 = _session(port, 0, slice_size=2, evict_s=0.0)  # no heartbeat
    s1 = _session(port, 1, slice_size=2, evict_s=evict_s)
    s2 = _session(port, 2, slice_size=2, evict_s=evict_s)
    s3 = _session(port, 3, slice_size=2, evict_s=evict_s)
    try:
        a = np.arange(16, dtype=np.float32)
        h0 = s0.push_pull_async(1, a)
        h2 = s2.push_pull_async(1, a)
        h0.wait(20), h2.wait(20)
        s0.close()                      # leader of slice 0 dies
        deadline = time.time() + 8 * evict_s
        while time.time() < deadline:
            if s1.membership()["alive"] == [1, 2, 3]:
                break
            time.sleep(0.05)
        assert s1.membership()["alive"] == [1, 2, 3]
        assert s1.slice_leader() == 1   # election moved to worker 1
        r1 = H.HierarchicalReducer(s1, 1, 2, world=4)
        assert r1.is_leader
        # The new leader's round completes against slice 1's leader.
        h1 = s1.push_pull_async(1, a * 2)
        h2 = s2.push_pull_async(1, a * 10)
        np.testing.assert_array_equal(h1.wait(30), a * 2 + a * 10)
        h2.wait(30)
    finally:
        for s in (s1, s2, s3):
            s.close()


# ---------------------------------------------------------------------------
# flat-mode byte identity (recording stub)
# ---------------------------------------------------------------------------
def _stub_run(use_reducer: bool):
    """One push_pull against a recording stub; returns the full frame
    list (headers + payloads)."""
    store = {}

    def handler(cmd, dt, fl, req_id, wid, key, payload):
        if cmd == CMD_HELLO:
            return 0, b"\x00\x00"
        if cmd == CMD_INIT:
            return 0, struct.pack("<Q", 0)
        if cmd == CMD_PUSH:
            store[key] = bytes(payload)
            return 0, b""
        if cmd == CMD_PULL:
            return 0, store[key]
        return 1, b""

    srv = StubPSServer(handler, record_payload=True)
    try:
        s = PSSession(["127.0.0.1"], [srv.port], worker_id=0,
                      num_servers=1, wire_conns=1, slice_size=1)
        x = np.arange(64, dtype=np.float32)
        if use_reducer:
            # Single-chip "hierarchy": a 1-member slice must degenerate
            # to flat EXACTLY — same frames, same bytes.
            r = H.HierarchicalReducer(s, 0, 1, world=1)
            assert r.is_leader and len(r.group) == 1
            got = r.push_pull_flat(3, x)
        else:
            got = s.push_pull(3, x)
        np.testing.assert_array_equal(np.asarray(got).ravel(), x)
        s.close()
        time.sleep(0.2)
        with srv.lock:
            return list(zip([f[0] for f in srv.frames],
                            [f[1] for f in srv.frames],
                            list(srv.payloads)))
    finally:
        srv.close()


def test_hierarchy_unset_wire_byte_identical():
    """The off-by-default law: with BYTEPS_TPU_HIERARCHY unset the data
    plane sends exactly the pre-hierarchy frame sequence (HELLO, INIT,
    PUSH, PULL — no new commands, no new flags, identical bytes), and a
    single-chip hierarchical run degenerates to the SAME bytes."""
    flat = _stub_run(use_reducer=False)
    H.reset_slice_groups()
    degenerate = _stub_run(use_reducer=True)
    cmds = {c for _, c, _ in flat}
    assert cmds <= {CMD_HELLO, CMD_INIT, CMD_PUSH, CMD_PULL}, cmds
    # Byte-for-byte: headers AND payloads, frame by frame.
    assert [(h, p) for h, _, p in flat] \
        == [(h, p) for h, _, p in degenerate]


# ---------------------------------------------------------------------------
# trainers under hierarchy
# ---------------------------------------------------------------------------
def test_server_opt_trainer_hierarchical_matches_flat(ps_server):
    """ServerOptTrainer under a 1-slice x 2-chip topology: gradients
    slice-reduce in-graph, the leader pushes, the pulled PARAMETERS
    broadcast back — trajectories bit-identical to the flat 2-worker
    server-opt run (integer grads, SGD)."""
    from byteps_tpu.parallel.server_opt import ServerOptTrainer

    world, rounds, dim = 2, 4, 64
    grads = _int_grads(world, rounds, dim, seed=3)
    params = {"w": np.zeros(dim, np.float32)}
    kw = {"opt": "sgd", "lr": 0.5}

    def run(hier: bool):
        H.reset_slice_groups()
        port = ps_server(num_workers=world,
                         slice_size=2 if hier else 0)
        sessions = [_session(port, w, slice_size=2 if hier else 1)
                    for w in range(world)]
        reducers = [H.HierarchicalReducer(s, w, 2, world=world)
                    for w, s in enumerate(sessions)] if hier else \
                   [None] * world
        trainers = [ServerOptTrainer(sessions[w], params, kw,
                                     name="hiertr", mode="server",
                                     hierarchy=reducers[w])
                    for w in range(world)]
        traj = {w: [] for w in range(world)}

        def worker(w):
            for r in range(rounds):
                trainers[w].step({"w": grads[(w, r)]})
                traj[w].append(
                    np.asarray(trainers[w].params["w"]).copy())

        ts = [threading.Thread(target=worker, args=(w,))
              for w in range(world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
        assert all(not t.is_alive() for t in ts)
        wire = [s.transport_stats()["lane_bytes_total"]
                for s in sessions]
        for s in sessions:
            s.close()
        return traj, wire

    traj_f, wire_f = run(False)
    traj_h, wire_h = run(True)
    for w in range(world):
        for r in range(rounds):
            assert np.array_equal(traj_f[w][r], traj_h[w][r]), (w, r)
    # The follower's data plane is silent (its session still pays the
    # CMD_OPT arming control frames, which ride the request path, not
    # the data lanes' payload counters).
    assert wire_h[1] < wire_f[1]
    assert wire_h[0] >= wire_f[0]   # leader carries the slice


def test_async_trainer_hierarchical_matches_flat(ps_server):
    """AsyncPSTrainer under one 2-chip slice: deltas slice-sum in-graph,
    the leader pushes, followers adopt the broadcast global weights —
    final params identical to the flat 2-worker async run (integer
    deltas, synchronized rounds)."""
    from byteps_tpu.parallel.async_ps import AsyncPSTrainer

    world, rounds, dim = 2, 3, 32
    deltas = _int_grads(world, rounds, dim, seed=11)
    init = {"w": np.zeros(dim, np.float32)}

    def run(hier: bool):
        H.reset_slice_groups()
        port = ps_server(num_workers=world,
                         slice_size=2 if hier else 0,
                         extra_env={"BYTEPS_ENABLE_ASYNC": "1"})
        sessions = [_session(port, w, slice_size=2 if hier else 1)
                    for w in range(world)]
        reducers = [H.HierarchicalReducer(s, w, 2, world=world)
                    for w, s in enumerate(sessions)] if hier else \
                   [None] * world
        trainers = {}
        barrier = threading.Barrier(world)
        finals = {}

        def worker(w):
            # pipeline=False: deterministic lockstep so the flat and
            # hierarchical runs see identical server states round by
            # round (the pipelined path is covered flat elsewhere).
            tr = AsyncPSTrainer(sessions[w], init, name="hierasync",
                                pipeline=False,
                                hierarchy=reducers[w])
            trainers[w] = tr
            for r in range(rounds):
                barrier.wait()
                updated = {"w": np.asarray(tr.params["w"], np.float32)
                           + deltas[(w, r)]}
                tr.step(updated)
            # An async server answers a pull with whatever it has summed
            # so far: in the flat run worker 0's last pull may leave the
            # server before worker 1's last delta arrives.  Once every
            # worker's last step has returned its delta is in the store,
            # so a step that moves nothing pulls the whole sum.
            barrier.wait()
            tr.step(tr.params)
            finals[w] = np.asarray(tr.finalize()["w"], np.float32)

        ts = [threading.Thread(target=worker, args=(w,))
              for w in range(world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
        assert all(not t.is_alive() for t in ts)
        for s in sessions:
            s.close()
        return finals

    flat = run(False)
    hier = run(True)
    want = sum(deltas[(w, r)] for w in range(world)
               for r in range(rounds))
    for w in range(world):
        np.testing.assert_array_equal(flat[w], want)
        np.testing.assert_array_equal(hier[w], want)


# ---------------------------------------------------------------------------
# api-level opt-in (world-1 degenerate, full routing through bps.*)
# ---------------------------------------------------------------------------
def test_api_hierarchy_routing_end_to_end(ps_server):
    """BYTEPS_TPU_HIERARCHY=1 through bps.init(): push_pull_tree routes
    the fused dispatch through the reducer (leader side), results are
    correct, and bps.get_hierarchy() reports the armed topology."""
    port = ps_server(num_workers=1, slice_size=1)
    code = """
import numpy as np, jax.numpy as jnp
import byteps_tpu as bps

bps.init()
h = bps.get_hierarchy()
assert h["armed"] and h["is_leader"] and h["slice_size"] == 1, h
tree = {"a": jnp.full((700,), 2.0, jnp.float32),
        "c": jnp.full((12,), 3.0, jnp.float32),
        "n": jnp.array([9], jnp.int32)}
out = bps.push_pull_tree(tree, average=False)
np.testing.assert_array_equal(np.asarray(out["a"]), np.full(700, 2.0))
np.testing.assert_array_equal(np.asarray(out["c"]), np.full(12, 3.0))
np.testing.assert_array_equal(np.asarray(out["n"]), np.array([9]))
one = bps.push_pull(jnp.arange(5, dtype=jnp.float32), name="solo",
                    average=False)
np.testing.assert_array_equal(np.asarray(one),
                              np.arange(5, dtype=np.float32))
snap = bps.get_hierarchy()
assert snap["leader_rounds"] >= 2, snap
bps.shutdown()
assert bps.get_hierarchy()["armed"] is False
print("API_HIER_OK")
"""
    env = cpu_env({
        "BYTEPS_TPU_PS_MODE": "1", "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1", "DMLC_PS_ROOT_PORT": str(port - 1),
        "BYTEPS_TPU_HIERARCHY": "1", "BYTEPS_TPU_SLICE_SIZE": "1",
        "BYTEPS_TPU_FUSION_BYTES": "16384",
    })
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "API_HIER_OK" in r.stdout


def test_fused_group_path_two_workers(ps_server):
    """The fused-tree dispatch faces (reduce_payloads / publish_outs /
    await_outs) across a 2-chip slice against the real server: the
    leader's push_pull_group carries slice sums, the follower's outs
    arrive by broadcast, and both match the arithmetic."""
    world = 2
    port = ps_server(num_workers=world, slice_size=2)
    sessions = [_session(port, w, slice_size=2) for w in range(world)]
    reducers = [H.HierarchicalReducer(s, w, 2, world=world)
                for w, s in enumerate(sessions)]
    a = {0: np.arange(64, dtype=np.float32),
         1: np.arange(64, dtype=np.float32) * 10}
    b = {0: np.full(16, 2.0, np.float32),
         1: np.full(16, 30.0, np.float32)}
    outs = {}

    def worker(w):
        r = reducers[w]
        rkey = (101, 102)
        reduced = r.reduce_payloads(rkey, [a[w], b[w]])
        if r.is_leader:
            handles = sessions[w].push_pull_group(
                [(101, reduced[0], 1), (102, reduced[1], 0)])
            vecs = [np.asarray(h.wait(30), np.float32)
                    for h in handles]
            r.publish_outs(rkey, vecs)
            outs[w] = vecs
        else:
            outs[w] = r.await_outs(
                rkey, skipped_bytes=sum(x.nbytes for x in reduced))

    ts = [threading.Thread(target=worker, args=(w,))
          for w in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert all(not t.is_alive() for t in ts)
    for w in range(world):
        np.testing.assert_array_equal(outs[w][0], a[0] + a[1])
        np.testing.assert_array_equal(outs[w][1], b[0] + b[1])
    assert sessions[1].transport_stats()["lane_bytes_total"] == 0
    for s in sessions:
        s.close()
