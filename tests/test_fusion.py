"""Fusion-bucket layer tests (common/fusion.py + the paths routed
through it: push_pull_tree, PSSession.push_pull_group, AsyncPSTrainer).

Covers the layer's contracts: deterministic dtype-homogeneous bucket
composition in reverse backprop order, priority-descending dispatch
through streamed staging, byte-identical fallback when disabled
(BYTEPS_TPU_FUSION_BYTES=0), stable keys across identical calls and
across the elastic re-declare/restart path, and the streaming buffer's
full/deadline flush law.
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.common import fusion

from test_ps_server import ps_server  # noqa: F401  (fixture reuse)


# ---------------------------------------------------------------------------
# Planner unit behavior.
# ---------------------------------------------------------------------------
def test_plan_reverse_backprop_order_and_cap():
    items = tuple((i, 1000, "float32", 4) for i in range(10))  # 4 KB each
    plan = fusion.plan_buckets(items, 8192)
    # Bucket 0 holds the LAST leaves (first out of backward) and the max
    # priority; every bucket respects the byte cap.
    assert plan.buckets[0].members == ((9, 1000), (8, 1000))
    assert plan.buckets[0].priority == 9
    prios = [b.priority for b in plan.buckets]
    assert prios == sorted(prios, reverse=True)
    assert all(b.nbytes <= 8192 for b in plan.buckets)
    assert plan.solo == ()
    assert plan.leaves_fused == 10


def test_plan_dtype_homogeneous_and_solo_split():
    items = ((0, 100, "float32", 4), (1, 50, "bfloat16", 2),
             (2, 1_000_000, "float32", 4), (3, 60, "bfloat16", 2),
             (4, 200, "float32", 4))
    plan = fusion.plan_buckets(items, 4096)
    assert {b.dtype for b in plan.buckets} == {"float32", "bfloat16"}
    for b in plan.buckets:
        assert len({b.dtype}) == 1
    # The 4 MB leaf goes solo at its own backprop position.
    assert plan.solo == ((2, 2),)
    # bf16 leaves never share a bucket with f32 ones.
    by_dtype = {b.dtype: b.members for b in plan.buckets}
    assert by_dtype["bfloat16"] == ((3, 60), (1, 50))
    assert by_dtype["float32"] == ((4, 200), (0, 100))


def test_plan_deterministic_and_cached():
    items = tuple((i, 500 + i, "float32", 4) for i in range(20))
    p1 = fusion.plan_buckets(items, 16384)
    p2 = fusion.plan_buckets(items, 16384)
    assert p1 is p2  # lru-cached: one plan per signature
    tags1 = [b.tag for b in p1.buckets]
    # A different threshold is a different plan (and different tags).
    p3 = fusion.plan_buckets(items, 8192)
    assert p3 is not p1
    assert [b.tag
            for b in fusion.plan_buckets(items, 16384).buckets] == tags1


def test_plan_disabled_sends_everything_solo():
    items = tuple((i, 10, "float32", 4) for i in range(5))
    plan = fusion.plan_buckets(items, 0)
    assert plan.buckets == () and len(plan.solo) == 5


def test_plan_segments_matches_legacy_packing():
    """The in-graph plane's packing (ops.collectives.BucketPlan now routes
    through plan_segments): reverse scan, large leaves spill across
    buckets, capacity respected."""
    segs = fusion.plan_segments([10, 25, 5], capacity_elems=16)
    flat = [(li, s, ln) for b in segs for (li, s, ln) in b]
    # Tail leaf first; leaf 1 (25 elems) spills across buckets.
    assert flat[0] == (2, 0, 5)
    assert sum(ln for li, _, ln in flat if li == 1) == 25
    for b in segs[:-1]:
        assert sum(ln for _, _, ln in b) == 16


# ---------------------------------------------------------------------------
# push_pull_tree routing (single worker: values must be identity).
# ---------------------------------------------------------------------------
def _tree():
    return {"a": jnp.arange(600, dtype=jnp.float32).reshape(20, 30),
            "b": jnp.full((40,), 2.5, jnp.bfloat16),
            "big": jnp.ones((3000,), jnp.float32),
            "steps": jnp.array([20_000_001], jnp.int32)}


def test_fused_tree_preserves_values_and_dtypes(bps_initialized):
    bps = bps_initialized
    tree = _tree()
    before = bps.get_fusion_stats()
    out = bps.push_pull_tree(tree, average=False, leaf_names=sorted(tree),
                             fusion_bytes=4096)
    for k in tree:
        assert out[k].dtype == tree[k].dtype, k
        np.testing.assert_allclose(np.asarray(out[k], jnp.float32),
                                   np.asarray(tree[k], jnp.float32))
    assert int(out["steps"][0]) == 20_000_001  # int leaf stayed exact
    after = bps.get_fusion_stats()
    assert after["plans_used"] == before["plans_used"] + 1
    assert after["buckets_built"] > before["buckets_built"]
    # "big" (12 KB >= the 4 KB threshold) rode solo.
    assert after["leaves_solo"] >= before["leaves_solo"] + 1


def test_fused_tree_handles_scalar_and_multidim_separated_leaves(
        bps_initialized):
    """Regression: separated (non-float) units ride the fused dispatch
    raveled — a 0-d step counter or a 2-D int leaf must round-trip
    exactly (the scatter slices elements, which a 0-d payload can't
    even express)."""
    bps = bps_initialized
    tree = {"w": jnp.ones((64,), jnp.float32),
            "v": jnp.ones((32,), jnp.float32),
            "step": jnp.asarray(7, jnp.int32),                   # 0-d
            "mask": jnp.arange(12, dtype=jnp.int32).reshape(3, 4)}
    out = bps.push_pull_tree(tree, average=False, fusion_bytes=4096)
    assert out["step"].shape == () and int(out["step"]) == 7
    np.testing.assert_array_equal(
        np.asarray(out["mask"]),
        np.arange(12, dtype=np.int32).reshape(3, 4))


def test_identical_calls_reuse_keys_with_nonfloat_leaf(bps_initialized):
    """Regression (fresh-key-per-call guard): two identical push_pull_tree
    calls — including a non-float leaf riding the separated exact path and
    fused buckets — must not grow the registry."""
    bps = bps_initialized
    from byteps_tpu.core.native import get_core
    tree = {"w": jnp.ones((128,), jnp.float32),
            "v": jnp.ones((64,), jnp.float32),
            "count": jnp.array([3], jnp.int32)}
    bps.push_pull_tree(tree, average=False)          # declares everything
    n1 = get_core().num_declared()
    out = bps.push_pull_tree(tree, average=False)    # must reuse every key
    assert get_core().num_declared() == n1
    np.testing.assert_array_equal(np.asarray(out["count"]), [3])
    # With no packing every leaf rides its own key, call after call.
    bps.push_pull_tree(tree, average=False, fusion_bytes=0)
    n2 = get_core().num_declared()
    bps.push_pull_tree(tree, average=False, fusion_bytes=0)
    assert get_core().num_declared() == n2


def test_leaf_names_are_tree_path_deterministic(bps_initialized):
    """Unnamed separated leaves are keyed by TREE PATH, so their names are
    reproducible from the structure alone (stable across processes and the
    re-declare path), not tied to a transient flat index."""
    bps = bps_initialized
    from byteps_tpu.core.native import get_core
    tree = {"x": jnp.ones((8,), jnp.float32),
            "flag": jnp.array([1], jnp.int32)}
    bps.push_pull_tree(tree, name="pathkeys", average=False)
    assert get_core().get_declared_key("pathkeys['flag']") >= 0


class _RecordingSession:
    """What `common/api.py` asks of a PS session, recording what
    `push_pull_group` is handed: (declared name, priority, the f32 bytes
    staged), with `_stage`'s spans and a real handle each.  One worker:
    the pull is the push."""

    def __init__(self):
        from byteps_tpu.common import stage_spans
        self.spans = stage_spans.RoundSpans()
        self._compressors = {}
        self.sent, self.fusion_keys = [], []

    def live_fusion_bytes(self):
        return None

    def note_fusion_keys(self, declared_keys):
        self.fusion_keys.extend(declared_keys)

    def set_trace_members(self, declared_key, names):
        pass

    def push_pull_group(self, items):
        from byteps_tpu.core.native import get_core
        from byteps_tpu.server.client import PSHandle
        handles = []
        for dk, tensor, priority in items:
            label = get_core().declared_name(dk)
            with self.spans.span("D2H", label, key=dk):
                arr = np.asarray(tensor)
            with self.spans.span("STAGE", label, key=dk):
                payload = np.ascontiguousarray(arr, np.float32).ravel()
                self.spans.count(units=1)
            self.sent.append((label, priority, payload.tobytes()))
            h = PSHandle(arr.shape, arr.dtype, 1, payload.copy(),
                         spans=self.spans, key=dk, label=label)
            h._part_done()
            handles.append(h)
        return handles


@pytest.fixture
def recording_session(bps_initialized, monkeypatch):
    from byteps_tpu.common import api
    sess = _RecordingSession()
    monkeypatch.setattr(api._state, "ps_session", sess)
    return sess


def test_fusion_bytes_zero_means_no_packing(bps_initialized,
                                            recording_session):
    """BYTEPS_TPU_FUSION_BYTES=0: the plan has no bucket, every leaf is
    a unit of the same round under its own key, in the scheduler's
    order, carrying its own values widened to f32 — and the tree that
    comes back is the one `fusion_bytes=4096` gives, bit for bit."""
    bps, sess = bps_initialized, recording_session
    a = jnp.arange(300, dtype=jnp.float32).reshape(20, 15)
    b = jnp.full((7,), 1.5, jnp.bfloat16)
    n = jnp.array([11, 22], jnp.int32)
    tree = {"a": a, "b": b, "n": n}
    before = bps.get_fusion_stats()
    out0 = bps.push_pull_tree(tree, name="parity", average=False,
                              fusion_bytes=0)
    # (priority desc, declared key asc): the separated int leaf rides at
    # its position like the solo leaves.
    assert [(nm, prio) for nm, prio, _ in sess.sent] == [
        ("parity['n']", 2), ("parity['b']", 1), ("parity['a']", 0)]
    for (_, _, wire), leaf in zip(sess.sent, (n, b, a)):
        assert wire == np.asarray(leaf, np.float32).tobytes()
    after = bps.get_fusion_stats()
    assert after["buckets_built"] == before["buckets_built"]
    assert after["leaves_fused"] == before["leaves_fused"]
    assert after["leaves_solo"] == before["leaves_solo"] + 2
    # only the plan's units may be withdrawn by a FUSION_BYTES switch
    from byteps_tpu.core.native import get_core
    assert sorted(get_core().declared_name(k)
                  for k in sess.fusion_keys) == ["parity['a']",
                                                 "parity['b']"]
    del sess.sent[:]
    out1 = bps.push_pull_tree(tree, name="parity", average=False,
                              fusion_bytes=4096)
    assert any(".fb" in nm for nm, _, _ in sess.sent)
    for k in tree:
        assert out0[k].dtype == out1[k].dtype == tree[k].dtype
        assert out0[k].shape == tree[k].shape
        assert np.asarray(out0[k]).tobytes() == np.asarray(
            out1[k]).tobytes() == np.asarray(tree[k]).tobytes()


def _stage_rows(core, tmp_path, tag):
    import json

    from byteps_tpu.common import stage_spans
    path = tmp_path / f"{tag}.json"
    core.trace_dump(str(path), 0)
    rows = [r for r in json.load(open(path))["traceEvents"]
            if r.get("tid") in stage_spans.STAGES]
    return sorted(rows, key=lambda r: r["ts"])


@pytest.mark.parametrize("hierarchy", [False, True],
                         ids=["flat", "hierarchy"])
def test_push_pull_is_a_tree_of_one_unit(bps_initialized,
                                         recording_session, monkeypatch,
                                         tmp_path, hierarchy):
    """`bps.push_pull(x)` and `bps.push_pull_tree([x])` are the same
    round: one key, the same bytes, the same kinds of span in the same
    order (the tree's under a ROUND, with its planner's PACKs before the
    round's own), the same telemetry; under BYTEPS_TPU_HIERARCHY both
    take the same hand-over (reduce_payloads, then the leader's
    publish_outs)."""
    from byteps_tpu.common import api, telemetry
    from byteps_tpu.core.native import get_core
    from byteps_tpu.parallel import hierarchy as H
    bps, sess, core = bps_initialized, recording_session, get_core()
    calls = []
    if hierarchy:
        H.reset_slice_groups()
        red = H.HierarchicalReducer(sess, 0, 1, world=1)

        def recording(face, real):
            def call(key, *args, **kwargs):
                calls.append((face, key))
                return real(key, *args, **kwargs)
            return call

        for face in ("reduce_payloads", "publish_outs", "await_outs",
                     "publish_failure", "dispatch_round"):
            monkeypatch.setattr(red, face,
                                recording(face, getattr(red, face)))
        monkeypatch.setattr(api._state, "hierarchy", red)
    x = jnp.arange(24, dtype=jnp.bfloat16).reshape(4, 6) / 2

    def sent_bytes():
        return telemetry.get_registry().snapshot().get(
            "bps_pushpull_bytes_total", 0)

    got = {}
    core.trace_enable(True)
    try:
        for tag, call in (
                ("one", lambda: bps.push_pull(x, name="one.x",
                                              average=False)),
                ("tree", lambda: bps.push_pull_tree(
                    [x], name="one", leaf_names=["one.x"], average=False,
                    fusion_bytes=0)[0])):
            b0 = sent_bytes()
            out = call()
            assert out.dtype == x.dtype and out.shape == x.shape
            assert np.asarray(out).tobytes() == np.asarray(x).tobytes()
            rows = _stage_rows(core, tmp_path, tag)
            kinds = [r["tid"] for r in rows if r["tid"] != "ROUND"]
            got[tag] = (list(sess.sent), [k for i, k in enumerate(kinds)
                                          if i == 0 or kinds[i - 1] != k],
                        sent_bytes() - b0, list(calls),
                        [r["tid"] for r in rows].count("ROUND"))
            del sess.sent[:], calls[:]
    finally:
        core.trace_enable(False)
    assert got["one"][:4] == got["tree"][:4]
    sent, kinds, nbytes, faces, _ = got["one"]
    assert sent == [("one.x", 0, np.asarray(x, np.float32).tobytes())]
    assert kinds == ["PACK", "D2H", "STAGE", "WAIT", "H2D", "SCATTER",
                     "FREE"]
    assert nbytes == x.size * 2
    assert (got["one"][4], got["tree"][4]) == (0, 1)
    key = (core.get_declared_key("one.x"),)
    assert faces == ([("reduce_payloads", key), ("publish_outs", key)]
                     if hierarchy else [])


# ---------------------------------------------------------------------------
# Streamed staging + priority-descending dispatch (live PS server).
# ---------------------------------------------------------------------------
def test_push_pull_group_correct_and_priority_descending(ps_server):
    from byteps_tpu.server.client import PSSession

    port = ps_server(num_workers=1)
    s = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1)
    tensors = {10 + i: np.full(256, float(i + 1), np.float32)
               for i in range(6)}
    items = [(k, v, k - 10) for k, v in tensors.items()]  # priority = i
    s.record_push_order = True
    s.pause_dispatch()
    handles = s.push_pull_group(items)
    s.resume_dispatch()
    for (k, v, _), h in zip(items, handles):
        np.testing.assert_array_equal(h.wait(), v)
    # One partition per tensor, dispatched strictly (priority desc, key
    # asc): key 15 (prio 5) first, key 10 (prio 0) last.
    assert s.push_order == [(15 - i) << 16 for i in range(6)]
    s.close()


def test_push_pull_group_duplicate_key_does_not_deadlock(ps_server):
    """A repeated declared key inside one group (two rounds of the same
    tensor) must proceed, not deadlock the sequential-use guard: the
    earlier round is already queued when the later one is staged."""
    from byteps_tpu.server.client import PSSession

    port = ps_server(num_workers=1)
    s = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1)
    a = np.ones(64, np.float32)
    b = np.full(64, 2.0, np.float32)
    h1, h2 = s.push_pull_group([(5, a, 1), (5, b, 0)])
    np.testing.assert_array_equal(h1.wait(timeout=60), a)   # round 0
    np.testing.assert_array_equal(h2.wait(timeout=60), b)   # round 1
    s.close()


def _session(ps_server):
    from byteps_tpu.server.client import PSSession

    port = ps_server(num_workers=1)
    return PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1)


def test_push_pull_group_first_item_on_the_wire_while_second_stages(
        ps_server):
    """Streamed staging: an item is in the scheduler the moment it is
    staged, so its push leaves while the next item's copy off the device
    is still awaited (here: a `_stage` that waits for that push)."""
    s = _session(ps_server)
    s.record_push_order = True
    real_stage, pushed_before_second = s._stage, []

    def slow_stage(declared_key, *args):
        if declared_key == 21:
            deadline = time.time() + 20
            while not s.push_order and time.time() < deadline:
                time.sleep(0.005)
            pushed_before_second.append(list(s.push_order))
        return real_stage(declared_key, *args)

    s._stage = slow_stage
    a, b = np.ones(256, np.float32), np.full(256, 2.0, np.float32)
    h1, h2 = s.push_pull_group([(20, a, 1), (21, b, 0)])
    assert pushed_before_second == [[20 << 16]]
    np.testing.assert_array_equal(h1.wait(timeout=60), a)
    np.testing.assert_array_equal(h2.wait(timeout=60), b)
    assert s.push_order == [20 << 16, 21 << 16]
    s.close()


def test_push_pull_group_strict_order_with_equal_priorities(ps_server):
    """Under pause_dispatch the scheduler sees the whole set, however
    the items arrive, and picks (priority desc, key asc): key breaks
    the ties among equal priorities."""
    s = _session(ps_server)
    items = [(42, 1), (40, 1), (41, 2), (43, 0), (44, 1), (45, 2)]
    tensors = {k: np.full(128, float(k), np.float32) for k, _ in items}
    s.record_push_order = True
    s.pause_dispatch()
    handles = s.push_pull_group([(k, tensors[k], p) for k, p in items])
    s.resume_dispatch()
    for (k, _), h in zip(items, handles):
        np.testing.assert_array_equal(h.wait(timeout=60), tensors[k])
    assert s.push_order == [k << 16 for k in (41, 45, 40, 42, 44, 43)]
    s.close()


class _NoCopy:
    """A tensor whose copy off the device fails."""

    def __array__(self, *args, **kwargs):
        raise RuntimeError("no copy off the device")


@pytest.mark.parametrize("fails_in", ["d2h", "parts"])
def test_push_pull_group_item_that_fails_to_stage(ps_server, fails_in):
    """Item k fails mid-group: the items before it are already on the
    wire and complete, the exception surfaces, nothing of item k stays
    pinned, and the next group over the same keys goes through."""
    s = _session(ps_server)
    a, b, c = (np.full(300, v, np.float32) for v in (1.0, 2.0, 3.0))
    real_stage, real_parts, staged = s._stage, s._stage_parts, []

    def recording_stage(*args):
        out = real_stage(*args)
        staged.append(out[0])
        return out

    def failing_parts(plan, *args, **kwargs):
        real_parts(plan, *args, **kwargs)
        if plan[0][0] >> 16 == 31:      # after its parts are pinned
            raise RuntimeError("staging failed")

    s._stage = recording_stage
    if fails_in == "parts":
        s._stage_parts = failing_parts
    bad = _NoCopy() if fails_in == "d2h" else b
    with pytest.raises(RuntimeError, match="no copy|staging failed"):
        s.push_pull_group([(30, a, 2), (31, bad, 1), (32, c, 0)])
    assert len(staged) == 1             # item 0 only; item 2 never began
    np.testing.assert_array_equal(staged[0].wait(timeout=60), a)
    with s._inflight_lock:
        assert not s._inflight
    s._stage_parts = real_parts
    handles = s.push_pull_group([(30, 2 * a, 2), (31, b, 1), (32, c, 0)])
    for h, want in zip(handles, (2 * a, b, c)):
        np.testing.assert_array_equal(h.wait(timeout=60), want)
    s.close()


def test_fused_tree_round_counts_units_early(ps_server):
    """`units_early` on the ROUND of a traced fused push_pull_tree is
    `units` - 1: every unit but the last was in the scheduler before the
    round's last copy off the device began.  With tracing off no ROUND
    is open, and the count adds to nothing."""
    import subprocess
    import sys

    from testutil import cpu_env

    port = ps_server(num_workers=1)
    code = """
import json, os, tempfile, numpy as np, jax.numpy as jnp
import byteps_tpu as bps
from byteps_tpu.core.native import get_core
bps.init()
core = get_core()
sess = bps.get_ps_session()
tree = {f"g{i:02d}": jnp.full((2000,), float(i), jnp.float32)
        for i in range(12)}
real_count, open_rounds = sess.spans.count, []
def count(**add):
    open_rounds.append(getattr(sess.spans._open, "counts", None))
    real_count(**add)
sess.spans.count = count
bps.push_pull_tree(tree, average=False)          # tracing off
assert open_rounds and all(c is None for c in open_rounds), open_rounds
assert sess.spans._rounds == 0
core.trace_enable(True)
out = bps.push_pull_tree(tree, average=False)
path = os.path.join(tempfile.mkdtemp(), "trace.json")
core.trace_dump(path, 0)
rows = json.load(open(path))["traceEvents"]
(rnd,) = [r for r in rows if r["tid"] == "ROUND"]
d2h = [r for r in rows if r["tid"] == "D2H"]
assert rnd["args"]["units"] == len(d2h) >= 3, rnd
assert rnd["args"]["units_early"] == rnd["args"]["units"] - 1, rnd
for k in tree:
    np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(tree[k]))
print("EARLY_OK")
"""
    env = cpu_env({
        "BYTEPS_TPU_PS_MODE": "1", "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1", "DMLC_PS_ROOT_PORT": str(port - 1),
        "BYTEPS_TPU_FUSION_BYTES": "16384",
    })
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "EARLY_OK" in r.stdout


def test_fused_tree_trace_spans_priority_descending(ps_server):
    """The acceptance contract for the overlap story: trace spans of one
    fused push_pull_tree show buckets leaving in priority-descending
    order (fb0 — the tail of the tree — first), each span carrying the
    bucket's priority in args."""
    import subprocess
    import sys

    from testutil import cpu_env

    port = ps_server(num_workers=1)
    code = """
import json, os, tempfile, numpy as np, jax.numpy as jnp
import byteps_tpu as bps
from byteps_tpu.core.native import get_core
bps.init()
core = get_core()
core.trace_enable(True)
sess = bps.get_ps_session()
sess.pause_dispatch()
tree = {f"g{i:02d}": jnp.full((2000,), float(i), jnp.float32)
        for i in range(12)}
import threading
t = threading.Thread(target=bps.push_pull_tree, args=(tree,),
                     kwargs={"average": False})
t.start()
import time
time.sleep(1.0)        # let every bucket stage + enqueue
sess.resume_dispatch()
t.join(timeout=60)
path = os.path.join(tempfile.mkdtemp(), "trace.json")
core.trace_dump(path, 0)
rows = json.load(open(path))["traceEvents"]
push = [r for r in rows if r["tid"] == "PUSH" and ".fb" in r["name"]]
assert len(push) >= 2, rows
push.sort(key=lambda r: r["ts"])
prios = [r["args"]["priority"] for r in push]
assert prios == sorted(prios, reverse=True), prios
assert all("priority" in r["args"] and r["args"]["bytes"] > 0
           for r in push)
print("TRACE_OK")
"""
    env = cpu_env({
        "BYTEPS_TPU_PS_MODE": "1", "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1", "DMLC_PS_ROOT_PORT": str(port - 1),
        "BYTEPS_TPU_FUSION_BYTES": "16384",
    })
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "TRACE_OK" in r.stdout


def test_restart_redeclare_keeps_bucket_composition(ps_server):
    """The re-declare/restart path (api.resume): bucket composition and
    key assignment must be identical after a simulated server restart,
    including with a wire compressor registered (the compressed leaf
    stays solo on both sides of the restart, so the fused set — and
    therefore every bucket name — is unchanged)."""
    import subprocess
    import sys

    from testutil import cpu_env

    port = ps_server(num_workers=1)
    code = """
import numpy as np, jax.numpy as jnp
import byteps_tpu as bps
from byteps_tpu.core.native import get_core

def names():
    core = get_core()
    return [core.declared_name(i) for i in range(core.num_declared())]

bps.init()
bps.register_compressor("t.comp", {"compressor": "onebit"})
tree = {"a": jnp.full((700,), 2.0, jnp.float32),
        "b": jnp.ones((300,), jnp.bfloat16),
        "c": jnp.full((12,), 3.0, jnp.float32),
        "n": jnp.array([9], jnp.int32),
        "t.comp": jnp.asarray(np.linspace(-1, 1, 4096, dtype=np.float32))}
leaf_names = sorted(tree)
out1 = bps.push_pull_tree(tree, average=False, leaf_names=leaf_names)
keys1 = names()
st1 = bps.get_fusion_stats()
assert st1["buckets_built"] > 0
bps.suspend()
bps.resume(num_workers=1, num_servers=1)
# Compressor registrations live on the torn-down session; re-register
# (the restart contract, like the reference's re-declare).
bps.register_compressor("t.comp", {"compressor": "onebit"})
assert names() == keys1, "resume() changed key assignment"
out2 = bps.push_pull_tree(tree, average=False, leaf_names=leaf_names)
assert names() == keys1, "post-restart call declared new keys"
st2 = bps.get_fusion_stats()
assert st2["buckets_built"] == 2 * st1["buckets_built"]
assert st2["leaves_fused"] == 2 * st1["leaves_fused"]
for k in ("a", "b", "c", "n"):
    np.testing.assert_array_equal(np.asarray(out2[k], np.float32),
                                  np.asarray(out1[k], np.float32))
bps.shutdown()
print("RESTART_OK")
"""
    env = cpu_env({
        "BYTEPS_TPU_PS_MODE": "1", "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1", "DMLC_PS_ROOT_PORT": str(port - 1),
        "BYTEPS_MIN_COMPRESS_BYTES": "0",
    })
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "RESTART_OK" in r.stdout


# ---------------------------------------------------------------------------
# AsyncPSTrainer chunked dispatch.
# ---------------------------------------------------------------------------
class _Resolved:
    def __init__(self, value):
        self._value = value

    def done(self):
        return True

    def wait(self, timeout=None):
        return self._value


class _FakeGroupSession:
    """In-memory async store with the grouped-staging face."""

    server_async = True

    def __init__(self):
        self.store = {}
        self.group_calls = 0
        self.pushed_priorities = []

    def _apply(self, key, arr, seed):
        arr = np.asarray(arr, np.float32).ravel()
        if seed:
            self.store.setdefault(key, arr.copy())
        else:
            self.store[key] = self.store.get(key, 0) + arr
        return _Resolved(self.store[key].copy())

    def push_pull_async(self, key, tensor, seed=False, **kw):
        return self._apply(key, tensor, seed)

    def push_pull_group(self, items, seed=False, **kw):
        self.group_calls += 1
        self.pushed_priorities.append([p for _, _, p in items])
        return [self._apply(k, t, seed) for k, t, p in items]


def test_async_trainer_chunks_through_planner():
    from byteps_tpu.parallel.async_ps import AsyncPSTrainer

    params = {"w1": np.zeros((300,), np.float32),
              "w2": np.zeros((70000,), np.float32),
              "b": np.zeros((10,), np.float32)}
    sess = _FakeGroupSession()
    t = AsyncPSTrainer(sess, params, name="fused", fusion_bytes=65536)
    assert t._chunks is not None and len(t._chunks) >= 2
    # Every group dispatch is priority-descending (reverse backprop).
    for prios in sess.pushed_priorities:
        assert prios == sorted(prios, reverse=True)
    for _ in range(3):
        t.step({k: v + 1.0 for k, v in t.params.items()})
    final = t.finalize()
    for k, v in params.items():
        np.testing.assert_allclose(final[k], np.full(v.shape, 3.0))

    # Chunked and single-key layouts train to identical weights.
    t0 = AsyncPSTrainer(_FakeGroupSession(), params, name="solo",
                        fusion_bytes=0)
    assert t0._chunks is None
    for _ in range(3):
        t0.step({k: v + 1.0 for k, v in t0.params.items()})
    for k in params:
        np.testing.assert_allclose(t0.finalize()[k], final[k])


def test_async_trainer_fused_against_live_server(ps_server):
    from byteps_tpu.parallel.async_ps import AsyncPSTrainer
    from byteps_tpu.server.client import PSSession

    port = ps_server(num_workers=1, async_mode=True)
    s = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1)
    params = {"w": np.zeros((5000,), np.float32),
              "b": np.zeros((16,), np.float32)}
    t = AsyncPSTrainer(s, params, name="live", fusion_bytes=8192)
    assert t._chunks is not None
    for _ in range(2):
        t.step({k: v + 2.0 for k, v in t.params.items()})
    final = t.finalize()
    np.testing.assert_allclose(final["w"], np.full(5000, 4.0))
    np.testing.assert_allclose(final["b"], np.full(16, 4.0))
    s.close()


# ---------------------------------------------------------------------------
# Streaming FusionBuffer (deadline flush for straggler leaves).
# ---------------------------------------------------------------------------
def _collecting_buffer(**kw):
    got = []

    def dispatch(packed, members, priority):
        got.append((np.asarray(packed).copy(), list(members), priority))

    return fusion.FusionBuffer(dispatch, **kw), got


def test_buffer_full_flush_and_solo():
    buf, got = _collecting_buffer(fusion_bytes=1024, flush_ms=0)
    small = np.ones(100, np.float32)               # 400 B
    buf.add("g0", small, priority=0)
    buf.add("g1", 2 * small, priority=1)
    assert got == []                               # 800 B still open
    buf.add("g2", 3 * small, priority=2)           # would exceed 1 KiB
    assert len(got) == 1                           # g0+g1 flushed full
    packed, members, prio = got[0]
    assert [m[0] for m in members] == ["g0", "g1"] and prio == 1
    np.testing.assert_array_equal(packed,
                                  np.concatenate([small, 2 * small]))
    big = np.ones(1000, np.float32)                # 4000 B >= threshold
    buf.add("big", big, priority=7)
    assert len(got) == 2 and got[1][1][0][0] == "big"  # solo, immediate
    buf.close()                                    # drains g2
    assert len(got) == 3 and got[2][1][0][0] == "g2"


def test_buffer_deadline_flushes_stragglers():
    before = fusion.get_stats()["deadline_flushes"]
    buf, got = _collecting_buffer(fusion_bytes=1 << 20, flush_ms=50)
    buf.add("straggler", np.ones(10, np.float32), priority=3)
    deadline = time.time() + 5
    while not got and time.time() < deadline:
        time.sleep(0.01)
    assert got and got[0][1][0][0] == "straggler"
    assert fusion.get_stats()["deadline_flushes"] == before + 1
    buf.close()


def test_buffer_meta_carries_original_shapes():
    """The dispatch contract's scatter metadata reports each member's
    ORIGINAL shape (what a callback needs to reshape pulled values), for
    fused and solo members alike."""
    buf, got = _collecting_buffer(fusion_bytes=1 << 20, flush_ms=0)
    buf.add("m", np.ones((20, 30), np.float32), priority=0)
    buf.add("v", np.ones((8,), np.float32), priority=1)
    buf.close()
    (_, members, _) = got[0]
    assert members == [("m", (20, 30), 600), ("v", (8,), 8)]


def test_buffer_dispatch_not_under_lock():
    """A dispatch callback that blocks (a wire round-trip, the
    sequential-use guard) must not stall concurrent add() calls — the
    FLUSH_MS straggler guarantee depends on it."""
    release = threading.Event()
    entered = threading.Event()

    def slow_dispatch(packed, members, priority):
        entered.set()
        assert release.wait(10), "dispatch never released"

    buf = fusion.FusionBuffer(slow_dispatch, fusion_bytes=1024, flush_ms=0)
    small = np.ones(100, np.float32)               # 400 B
    buf.add("a", small)
    buf.add("b", small)
    t = threading.Thread(target=buf.add, args=("c", small))  # trips flush
    t.start()
    assert entered.wait(5)
    # While the flush dispatch blocks, another thread's add proceeds.
    done = threading.Event()
    t2 = threading.Thread(
        target=lambda: (buf.add("d", np.ones(10, np.float32)), done.set()))
    t2.start()
    assert done.wait(5), "add() blocked behind a slow dispatch"
    release.set()
    t.join(timeout=10)
    t2.join(timeout=10)
    buf.close()


def test_buffer_keeps_dtypes_separate():
    buf, got = _collecting_buffer(fusion_bytes=1 << 20, flush_ms=0)
    buf.add("f", np.ones(8, np.float32), priority=0)
    buf.add("h", np.ones(8, np.float16), priority=1)
    buf.close()
    assert len(got) == 2
    assert {g[0].dtype.name for g in got} == {"float32", "float16"}


def test_stats_surface_shape(bps_initialized):
    st = bps_initialized.get_fusion_stats()
    assert set(st) == set(fusion.ZERO_STATS)
    assert all(isinstance(v, int) for v in st.values())
