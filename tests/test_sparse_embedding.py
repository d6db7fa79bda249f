"""Row-sparse embedding plane (ISSUE 17): sparse/dense bit-identity
when every row is touched, f32-exact row-wise Adagrad/Adam vs the
worker-local optax baseline at ~1% density, wire economy (sparse bytes
<= 5% of the dense baseline at 1% density; dense traffic byte-identical
with the sparse plane present-but-unused), zero-wire-frame warm-cache
lookups, and pull-only sessions (no round stall, monotone
param_version, ring-drain survival mid-read).
"""

import os
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from byteps_tpu.server.client import (CMD_HELLO, CMD_INIT, CMD_PULL,
                                      CMD_PUSH, DT_SPARSE,
                                      DT_SPARSE_READ,
                                      HELLO_FLAG_OBSERVER, _REQ,
                                      PSSession)
from byteps_tpu.core.native import get_core
from byteps_tpu.server import wire
from byteps_tpu.parallel.embedding import EmbeddingTable

from testutil import StubPSServer, cpu_env


def _wait_up(port, procs, deadline_s=60):
    deadline = time.time() + deadline_s
    while True:
        try:
            socket.create_connection(("127.0.0.1", port), 0.5).close()
            return
        except OSError:
            for p in procs:
                if p.poll() is not None:
                    raise RuntimeError(f"server died rc={p.returncode}")
            if time.time() > deadline:
                raise TimeoutError("PS server did not come up")
            time.sleep(0.1)


@pytest.fixture
def ps_server():
    made = []

    def start(num_workers=1, extra_env=None):
        last = None
        for _ in range(3):
            with socket.socket() as sk:
                sk.bind(("127.0.0.1", 0))
                port = sk.getsockname()[1]
            env = cpu_env({
                "DMLC_PS_ROOT_PORT": str(port - 1),
                "DMLC_NUM_WORKER": str(num_workers),
                "BYTEPS_SERVER_ENGINE_THREAD": "2",
                "JAX_PLATFORMS": "cpu",
                **(extra_env or {}),
            })
            proc = subprocess.Popen(
                [sys.executable, "-m", "byteps_tpu.server"], env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
            made.append(proc)
            try:
                _wait_up(port, [proc])
                return port
            except (RuntimeError, TimeoutError) as e:
                last = e
        raise last

    yield start
    for p in made:
        p.kill()
        p.wait()


@pytest.fixture
def server_group():
    """n PS servers sharing one root port (ring optional)."""
    made = []

    def start(n, num_workers=1, ring=False):
        last = None
        for _ in range(4):
            try:
                return _start_group(n, num_workers, ring)
            except (RuntimeError, TimeoutError) as e:
                last = e
        raise last

    def _start_group(n, num_workers, ring):
        with socket.socket() as sk:
            sk.bind(("127.0.0.1", 0))
            base = sk.getsockname()[1]
        ports = [base + i for i in range(n)]
        procs = []
        for i in range(n):
            env = cpu_env({
                "DMLC_PS_ROOT_PORT": str(base - 1),
                "DMLC_NUM_WORKER": str(num_workers),
                "DMLC_NUM_SERVER": str(n),
                "DMLC_SERVER_ID": str(i),
                "BYTEPS_SERVER_ENGINE_THREAD": "2",
                "JAX_PLATFORMS": "cpu",
                **({"BYTEPS_TPU_RING": "1"} if ring else {}),
            })
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "byteps_tpu.server"], env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
        made.extend(procs)
        for p in ports:
            _wait_up(p, procs)
        return ports

    yield start
    for p in made:
        p.kill()
        p.wait()


def _session(ports, wid=0, **kw):
    kw.setdefault("wire_conns", 1)
    kw.setdefault("compress_threads", 0)
    return PSSession(["127.0.0.1"] * len(ports), list(ports),
                     worker_id=wid, num_servers=len(ports), **kw)


# ---------------------------------------------------------------------------
# fast: sparse == dense bit-identity when every row is touched
# ---------------------------------------------------------------------------
def test_sparse_matches_dense_when_all_rows_touched(ps_server):
    """Sparsity is a wire optimization, not a numerics change: with
    EVERY row pushed every round, the sparse plane's published sums are
    bit-identical to dense push_pull of the same values — including
    2-worker merge accumulation (<= 2 workers so f32 commutativity
    covers arrival order)."""
    rows, width, rounds, nw = 64, 8, 3, 2
    port = ps_server(num_workers=nw)

    def grad(wid, rnd):
        rng = np.random.RandomState(1000 + 31 * wid + rnd)
        return (rng.randn(rows, width) * 3).astype(np.float32)

    results = {}

    def worker(wid):
        s = _session([port], wid=wid)
        try:
            s.declare_embedding(12, rows, width)
            dense, sparse = [], []
            idx = np.arange(rows, dtype=np.uint32)
            for rnd in range(rounds):
                g = grad(wid, rnd)
                d = s.push_pull(11, g.ravel().copy())
                sp = s.push_pull_sparse(12, idx, g)
                dense.append(np.asarray(d, np.float32)
                             .reshape(rows, width))
                sparse.append(sp)
            results[wid] = (dense, sparse)
        finally:
            s.close()

    ts = [threading.Thread(target=worker, args=(w,)) for w in range(nw)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
        assert not t.is_alive()
    assert set(results) == {0, 1}
    for wid, (dense, sparse) in results.items():
        for rnd in range(rounds):
            want = grad(0, rnd) + grad(1, rnd)
            np.testing.assert_array_equal(
                dense[rnd], want, err_msg=f"dense w{wid} r{rnd}")
            np.testing.assert_array_equal(
                sparse[rnd], dense[rnd],
                err_msg=f"sparse!=dense w{wid} r{rnd}")


# ---------------------------------------------------------------------------
# fast: row-wise server optimizer == worker-local optax, 1% density
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("optname,kwargs", [
    ("adagrad", {"opt": "adagrad", "lr": 0.5}),
    ("adam", {"opt": "adam", "lr": 0.01}),
], ids=["adagrad", "adam"])
def test_rowwise_opt_matches_optax_at_1pct_density(ps_server, optname,
                                                   kwargs):
    """Armed row-wise Adagrad/Adam steps EXACTLY the pushed rows and
    matches a per-row worker-local optax trajectory at ~1% touched
    density — Adam f32-bit-exactly, Adagrad to the ulps its scale allows
    (below) — and untouched rows stay bit-equal to the seed (their slots
    never materialize)."""
    import jax
    import optax

    port = ps_server()
    s = _session([port])
    try:
        rows, width = 400, 16
        rng = np.random.RandomState(42)
        table0 = rng.randn(rows, width).astype(np.float32)
        s.declare_embedding(5, rows, width)
        doc = s.arm_embedding(5, kwargs, table=table0)
        assert doc["accepted"], doc

        tx = (optax.adagrad(0.5) if optname == "adagrad"
              else optax.adam(0.01))
        params = table0.copy()
        states = {}

        def local_step(r, g):
            import jax.numpy as jnp
            p = jnp.asarray(params[r])
            st = states.get(r) or tx.init(p)
            with jax.disable_jit():
                u, st = tx.update(jnp.asarray(g), st, p)
                p = optax.apply_updates(p, u)
            states[r] = st
            params[r] = np.asarray(p, np.float32)
            return np.asarray(u, np.float32)

        for rnd in range(3):
            touched = np.unique(rng.choice(
                rows, size=4, replace=False).astype(np.uint32))
            g = rng.randn(touched.size, width).astype(np.float32)
            out = s.push_pull_sparse(5, touched, g)
            upd = np.stack([local_step(int(r), g[j])
                            for j, r in enumerate(touched)])
            if optname == "adagrad":
                # core/server.cc scales by 1.0f / std::sqrt(s + eps) where
                # optax's scale_by_rss calls lax.rsqrt(s + eps): the scales
                # differ by one ulp, the updates by at most two, and the
                # row by that at the update's magnitude (many of its own
                # ulps where row and update cancel).
                want = params[touched]
                ulp = np.spacing(np.maximum(np.abs(upd), np.abs(want)))
                assert (np.abs(out - want) <= 2 * ulp).all(), \
                    f"adagrad round {rnd}"
                # The next step of a row starts from what was served.
                params[touched] = out
            else:
                np.testing.assert_array_equal(
                    out, params[touched], err_msg=f"{optname} round {rnd}")
        # The whole table — touched rows stepped, the rest bit-equal to
        # the seed.
        served = s.pull_rows(5, np.arange(rows, dtype=np.uint32))
        np.testing.assert_array_equal(served, params)
    finally:
        s.close()


# ---------------------------------------------------------------------------
# fast: wire economy + byte-identity against the recording stub
# ---------------------------------------------------------------------------
def _sparse_stub():
    """Recording stub that answers both planes: dense echo + a sparse
    table of zeros at param_version 1.  Returns (stub, resp_log) where
    resp_log accumulates (cmd, response_bytes)."""
    store = {}
    resp_log = []

    def handler(cmd, dt, fl, req_id, wid, key, payload):
        if cmd == CMD_HELLO:
            out = (0, b"\x00\x00")
        elif cmd == CMD_INIT:
            out = (0, struct.pack("<Q", 0))
        elif cmd == CMD_PUSH:
            if dt == DT_SPARSE:
                idx, rows = wire.decode_sparse_block(payload)
                tbl = store.setdefault(("sparse", key), {})
                if rows is not None:
                    for j, r in enumerate(idx):
                        tbl[int(r)] = rows[j]
                out = (0, b"")
            else:
                store[key] = bytes(payload)
                out = (0, b"")
        elif cmd == CMD_PULL:
            if dt in (DT_SPARSE, DT_SPARSE_READ):
                idx, _ = wire.decode_sparse_block(payload)
                tbl = store.get(("sparse", key), {})
                nrows, width = wire.SPARSE_HDR.unpack_from(payload)[:2]
                rows = np.zeros((len(idx), width), np.float32)
                for j, r in enumerate(idx):
                    if int(r) in tbl:
                        rows[j] = tbl[int(r)]
                out = (0, struct.pack("<Q", 1) + rows.tobytes())
            else:
                out = (0, store[key])
        else:
            out = (1, b"")
        resp_log.append((cmd, len(out[1])))
        return out

    return StubPSServer(handler, record_payload=True), resp_log


def test_sparse_wire_bytes_within_5pct_of_dense_at_1pct_density():
    """The headline wire economy: one sparse round at 1% density moves
    <= 5% of the dense round's push+pull bytes for the same table
    (requests AND responses counted; measured ~1%)."""
    rows, width = 10000, 32
    density_rows = rows // 100

    def run(sparse):
        srv, resp_log = _sparse_stub()
        try:
            s = _session([srv.port], partition_bytes=1 << 22)
            rng = np.random.RandomState(5)
            if sparse:
                s.declare_embedding(9, rows, width)
                idx = np.unique(rng.choice(
                    rows, size=density_rows,
                    replace=False).astype(np.uint32))
                g = rng.randn(idx.size, width).astype(np.float32)
                s.push_pull_sparse(9, idx, g)
            else:
                s.push_pull(9, rng.randn(rows * width)
                            .astype(np.float32))
            s.close()
            with srv.lock:
                frames = list(zip(srv.frames, srv.payloads))
            req = sum(len(h) + len(p) for (h, c, f), p in frames
                      if c in (CMD_PUSH, CMD_PULL))
            resp = sum(n for c, n in resp_log
                       if c in (CMD_PUSH, CMD_PULL))
            return req + resp
        finally:
            srv.close()

    dense_bytes = run(sparse=False)
    sparse_bytes = run(sparse=True)
    assert dense_bytes >= rows * width * 4 * 2       # push + pull legs
    assert sparse_bytes <= 0.05 * dense_bytes, (
        sparse_bytes, dense_bytes)


def test_dense_wire_byte_identical_with_sparse_plane_unused():
    """A dense-only job is wire byte-identical whether or not the
    sparse knobs are set: no sparse dtype ever appears, no observer
    HELLO flag, and the frame stream (headers AND payloads) matches
    byte for byte — the present-but-unused plane costs nothing."""
    def run(extra_env):
        old = {k: os.environ.get(k) for k in extra_env}
        os.environ.update(extra_env)
        try:
            srv, _ = _sparse_stub()
            try:
                s = _session([srv.port])
                rng = np.random.RandomState(3)
                for _ in range(3):
                    s.push_pull(3, rng.randn(256).astype(np.float32))
                s.close()
                with srv.lock:
                    return list(zip(srv.frames, srv.payloads))
            finally:
                srv.close()
        finally:
            for k, v in old.items():
                os.environ.pop(k, None)
                if v is not None:
                    os.environ[k] = v

    base = run({})
    knobbed = run({"BYTEPS_TPU_SPARSE_CACHE_ROWS": "1024",
                   "BYTEPS_TPU_SPARSE_CACHE_TTL_MS": "500"})
    assert [h for (h, c, f), _ in base] \
        == [h for (h, c, f), _ in knobbed]
    assert [p for _, p in base] == [p for _, p in knobbed]
    for (h, c, f), _ in base:
        cmd, dt, fl = _REQ.unpack(h)[:3]
        assert dt not in (DT_SPARSE, DT_SPARSE_READ)
        if cmd == CMD_HELLO:
            assert not (fl & HELLO_FLAG_OBSERVER)


def test_warm_cache_lookup_is_zero_wire_frames():
    """The zero-frame law: a repeat lookup whose rows are ALL cached at
    a fresh param_version sends NOTHING — asserted against the
    recording stub's frame count, not timing."""
    os.environ["BYTEPS_TPU_SPARSE_CACHE_TTL_MS"] = "60000"
    try:
        srv, _ = _sparse_stub()
        try:
            s = _session([srv.port])
            s.declare_embedding(4, 500, 8)
            idx = np.array([7, 3, 499, 3], np.uint32)
            first = s.pull_rows(4, idx)
            with srv.lock:
                n_before = len(srv.frames)
            again = s.pull_rows(4, idx)          # warm: all rows cached
            with srv.lock:
                n_after = len(srv.frames)
            np.testing.assert_array_equal(first, again)
            assert n_after == n_before, "warm lookup touched the wire"
            st = s.embed_cache_stats()
            assert st["hits"] >= 3 and st["rows_cached"] >= 3
            # One cold row joins the batch: exactly one wire unit more.
            s.pull_rows(4, np.array([7, 100], np.uint32))
            with srv.lock:
                assert len(srv.frames) == n_after + 1
            s.close()
        finally:
            srv.close()
    finally:
        os.environ.pop("BYTEPS_TPU_SPARSE_CACHE_TTL_MS", None)


# ---------------------------------------------------------------------------
# fast: pull-only sessions — readers cannot stall training
# ---------------------------------------------------------------------------
def test_pull_only_reader_never_stalls_rounds(ps_server):
    """A pull-only session is an observer: rounds complete with it
    attached (it is not an admitted pusher), its reads see the
    published state, and its push-side surface raises."""
    port = ps_server(num_workers=1)
    s = _session([port])
    r = _session([port], wid=99, pull_only=True)
    try:
        s.declare_embedding(7, 1000, 8)
        r.declare_embedding(7, 1000, 8)          # idempotent attach
        out = s.push_pull_sparse(
            7, np.array([3], np.uint32), np.ones((1, 8), np.float32))
        assert np.allclose(out[0], 1.0)
        got = r.pull_rows(7, np.array([3, 5], np.uint32))
        assert np.allclose(got[0], 1.0) and np.allclose(got[1], 0.0)
        # The 1-pusher round still completes with the reader attached —
        # a push_pull_sparse would hang forever if the reader counted.
        out2 = s.push_pull_sparse(
            7, np.array([9], np.uint32),
            np.full((1, 8), 0.5, np.float32))
        assert np.allclose(out2[0], 0.5)
        with pytest.raises(RuntimeError):
            r.push_pull_sparse(7, np.array([1], np.uint32),
                               np.ones((1, 8), np.float32))
    finally:
        r.close()
        s.close()


def test_pull_only_sees_monotone_param_version(ps_server):
    """param_version names published table state: a reader polling
    across training rounds observes a non-decreasing version that
    strictly advances past each publish."""
    port = ps_server()
    s = _session([port])
    r = _session([port], wid=50, pull_only=True)
    try:
        s.declare_embedding(8, 100, 4)
        r.declare_embedding(8, 100, 4)
        seen = []
        for rnd in range(4):
            s.push_pull_sparse(8, np.array([rnd], np.uint32),
                               np.ones((1, 4), np.float32))
            r.pull_rows(8, np.array([rnd], np.uint32))
            seen.append(r.embed_version(8))
        assert all(v is not None for v in seen)
        assert seen == sorted(seen), seen
        assert seen[-1] > seen[0], seen          # publishes advanced it
    finally:
        r.close()
        s.close()


def test_pull_only_survives_ring_drain_mid_read(server_group,
                                                monkeypatch):
    """Ring drain with a reader mid-stream: embedding state migrates
    with the key (the CMD_MIGRATE embed trailer), the reader's next
    lookups land on the new owner via the MOVED redirect, values stay
    correct, and its param_version never goes backwards.  Cache TTL 0:
    every read goes to the wire — this test is about the server path,
    and loopback reads outrun the default 50ms bounded-staleness window
    (the cache laws have their own tests above)."""
    monkeypatch.setenv("BYTEPS_TPU_SPARSE_CACHE_TTL_MS", "0")
    ports = server_group(2, ring=True)
    s = PSSession(["127.0.0.1"] * 2, list(ports), worker_id=0,
                  num_servers=2, ring=True, wire_conns=1,
                  compress_threads=0)
    r = PSSession(["127.0.0.1"] * 2, list(ports), worker_id=77,
                  num_servers=2, ring=True, wire_conns=1,
                  compress_threads=0, pull_only=True)
    try:
        rows, width = 300, 8
        rng = np.random.RandomState(2)
        table0 = rng.randn(rows, width).astype(np.float32)
        s.declare_embedding(21, rows, width)
        r.declare_embedding(21, rows, width)
        doc = s.arm_embedding(21, {"opt": "adagrad", "lr": 0.1},
                              table=table0)
        assert doc["accepted"], doc
        idx = np.arange(0, rows, 7, dtype=np.uint32)
        for _ in range(2):
            g = rng.randn(idx.size, width).astype(np.float32)
            want = s.push_pull_sparse(21, idx, g)
        got = r.pull_rows(21, idx)
        np.testing.assert_array_equal(got, want)
        v_pre = r.embed_version(21)

        # Drain the embed key's owner (fall back to the other slot if
        # the ring placed it on server 0, which holds the barrier).
        pkey = s._embed_pkey(21)
        target = s._embed_srv(pkey) or 1
        s.drain_server(target)

        got2 = r.pull_rows(21, idx)              # reader rides MOVED
        np.testing.assert_array_equal(got2, want)
        assert r.embed_version(21) >= v_pre
        # Training continues on the new owner; the reader follows.
        g = rng.randn(idx.size, width).astype(np.float32)
        want2 = s.push_pull_sparse(21, idx, g)
        got3 = r.pull_rows(21, idx)
        np.testing.assert_array_equal(got3, want2)
        assert r.embed_version(21) >= v_pre
    finally:
        r.close()
        s.close()


# ---------------------------------------------------------------------------
# fast: EmbeddingTable — sharded worker surface
# ---------------------------------------------------------------------------
def test_embedding_table_shards_across_servers(server_group):
    """2-shard table on 2 servers: seed lookup bit-exact, push_pull
    steps exactly the touched rows (untouched bit-equal to seed), and
    CMD_STATS reports the declared bytes split across the tier."""
    ports = server_group(2)
    s = _session(ports)
    try:
        rows, width = 1001, 16
        rng = np.random.RandomState(0)
        init = rng.randn(rows, width).astype(np.float32)
        # The two shards take the next two declared keys, and the server
        # a key hashes to depends on how many tensors this process
        # declared before: step past a count that puts both on one.
        core = get_core()
        while len({s._embed_srv(s._embed_pkey(core.num_declared() + i))
                   for i in (0, 1)}) < 2:
            core.declare_tensor(f"pad.{core.num_declared()}")
        t = EmbeddingTable(s, rows, width, name="t",
                           opt_kwargs={"opt": "adagrad", "lr": 0.1},
                           init=init)
        ids = np.array([0, 1, 2, 1000, 999, 500], np.int64)
        np.testing.assert_array_equal(t.lookup(ids), init[ids])
        out = t.push_pull(ids, np.ones((ids.size, width), np.float32))
        assert not np.array_equal(out, init[ids])
        np.testing.assert_array_equal(t.lookup(ids), out)
        other = np.array([3, 4, 5], np.int64)
        np.testing.assert_array_equal(t.lookup(other), init[other])
        st = s.server_stats()
        assert st["embed_table_bytes"] == rows * width * 4
        assert st["embed_rows_served"] > 0
        per_srv = [int(d.get("embed_table_bytes", 0))
                   for d in st["servers"].values()]
        assert sum(per_srv) == rows * width * 4
        assert all(b > 0 for b in per_srv)       # actually sharded
        assert all(v is not None and v >= 1 for v in t.versions())
    finally:
        s.close()


# ---------------------------------------------------------------------------
# fast: host-side units — batching plan + telemetry export
# ---------------------------------------------------------------------------
def test_plan_row_batches_covers_and_caps():
    from byteps_tpu.common.fusion import plan_row_batches

    assert plan_row_batches(0, 64, 1 << 16) == []
    batches = plan_row_batches(1000, 64, 1 << 12)
    assert batches[0][0] == 0 and batches[-1][1] == 1000
    for (a, b), (c, d) in zip(batches, batches[1:]):
        assert b == c                            # contiguous, no gaps
    for a, b in batches:
        assert (b - a) * 64 * 4 <= (1 << 12)
    # A row wider than the cap still ships (alone).
    assert plan_row_batches(3, 4096, 100) == [(0, 1), (1, 2), (2, 3)]


def test_update_embed_exports_gauges_and_stays_quiet_when_dense():
    from byteps_tpu.common import telemetry as tm

    reg = tm.MetricsRegistry()
    tm.update_embed({"embed_rows_served": 0, "embed_table_bytes": 0,
                     "servers": {"0": {"embed_table_bytes": 0}}},
                    registry=reg)
    assert not any(k.startswith("bps_embed")
                   for k in reg.snapshot())          # dense job: quiet
    tm.update_embed(
        {"embed_rows_served": 123, "embed_table_bytes": 4096,
         "servers": {"0": {"embed_table_bytes": 4096}}},
        registry=reg)
    snap = reg.snapshot()
    assert snap["bps_embed_rows_served_total"] == 123
    assert snap['bps_embed_table_bytes{server="0"}'] == 4096
