"""A PS round that keeps its host memory (common/host_memory.py, the
`minflt` count of a `ROUND`, docs/performance.md "Host memory a PS
worker keeps").

The policy is process-wide and cannot be taken back, so every case runs
in a subprocess of its own: nothing leaks into the xdist worker.  `api.init`
sets it for a PS worker on an accelerator only; the jobs here, on the
CPU, call `host_memory.keep_freed_memory()` themselves (what such a
worker's `init` does) or tell `init` that the backend is a TPU.
"""

import subprocess
import sys

import pytest

from test_ps_server import ps_server  # noqa: F401  (fixture reuse)
from testutil import cpu_env

# What every job starts with.  `mapped_by(n)`: bytes of an n-byte block
# that malloc served by mmap: n and a header under glibc's defaults, 0
# once mmap is off.
_PRELUDE = """
import ctypes, json, os, sys, tempfile
import numpy as np
from byteps_tpu.common import host_memory, stage_spans
from byteps_tpu.core.native import get_core

class _Mallinfo2(ctypes.Structure):
    _fields_ = [(n, ctypes.c_size_t) for n in (
        "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
        "fsmblks", "uordblks", "fordblks", "keepcost")]
_libc = ctypes.CDLL(None)
_libc.mallinfo2.restype = _Mallinfo2

def mapped_by(nbytes):
    before = _libc.mallinfo2().hblkhd
    block = np.empty(nbytes, np.uint8)
    return _libc.mallinfo2().hblkhd - before

def round_faults(core):
    path = os.path.join(tempfile.mkdtemp(), "trace.json")
    core.trace_dump(path, 0)
    rows = json.load(open(path))["traceEvents"]
    return [r["args"] for r in rows if r["tid"] == "ROUND"]
"""

_PS_ENV = {"BYTEPS_TPU_PS_MODE": "1", "DMLC_NUM_WORKER": "1",
           "DMLC_NUM_SERVER": "1", "BYTEPS_LOG_LEVEL": "ERROR"}
_BLOCK = 1 << 26


def _run(code, env=None, port=None):
    env = dict(env or {})
    if port is not None:
        env.update(_PS_ENV, DMLC_PS_ROOT_PORT=str(port - 1),
                   PS_PORT=str(port))
    r = subprocess.run([sys.executable, "-c", _PRELUDE + code],
                       env=cpu_env(env), capture_output=True, text=True,
                       timeout=240)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


# ---------------------------------------------------------------------------
# (a) the engagement counter: a steady round takes no new pages.
# ---------------------------------------------------------------------------
_SESSION_ROUNDS = """
from byteps_tpu.server.client import PSSession
if os.environ["KEEP"] == "1":
    assert host_memory.keep_freed_memory()
sess = PSSession(["127.0.0.1"], [int(os.environ["PS_PORT"])], worker_id=0,
                 num_servers=1)
core = get_core()
core.trace_enable(True)
# Over 32 MiB each: glibc's own threshold adapts up to that, and under
# it the defaults keep some of what they free.
leaves = [np.full(9_000_000, float(i), np.float32) for i in range(2)]
for _ in range(4):
    with sess.spans.round("t30"):
        handles = sess.push_pull_group(
            [(300 + i, leaf, 2 - i) for i, leaf in enumerate(leaves)])
        pulled = [h.wait(timeout=60) for h in handles]
        for got, leaf in zip(pulled, leaves):
            np.testing.assert_array_equal(got, leaf)
        del handles, pulled, got
args = round_faults(core)
sess.close()
assert [a["bytes_in"] for a in args] == [2 * 36_000_000] * 4, args
print("MINFLT", json.dumps([a["minflt"] for a in args]))
"""


def _printed(out, tag):
    """The JSON value a job printed after `tag`."""
    import json
    (line,) = [l for l in out.splitlines() if l.startswith(tag + " ")]
    return json.loads(line[len(tag) + 1:])


@pytest.mark.parametrize("keep", ["1", "0"], ids=["kept", "defaults"])
def test_steady_round_takes_no_new_pages(ps_server, keep):  # noqa: F811
    """Four traced rounds of a 72 MB tree of numpy leaves against a real
    server child: with the memory kept, `ROUND.args.minflt` of rounds 3
    and 4 is under a tenth of round 1's; with the allocator's defaults
    every round pays again.  (Numpy leaves through the session, not
    `push_pull_tree`: on the CPU backend XLA's own arrays come from the
    same heap and fault by a rule of their own.)"""
    faults = _printed(_run(_SESSION_ROUNDS, {"KEEP": keep},
                           port=ps_server(num_workers=1)), "MINFLT")
    assert len(faults) == 4
    if faults[0] == 0:
        pytest.skip("this kernel does not account minor page faults")
    if keep == "1":
        assert max(faults[2:]) < faults[0] / 10, faults
    else:
        assert min(faults[2:]) > faults[0] / 4, faults


def test_round_counts_faults_only_when_traced(ps_server):  # noqa: F811
    """`push_pull_tree`: with tracing off nothing is counted (no
    `getrusage` call); a traced ROUND carries `minflt`, a whole number."""
    out = _run("""
import jax.numpy as jnp
import byteps_tpu as bps
bps.init()
calls = []
real = stage_spans._minor_faults
def counting():
    calls.append(1)
    return real()
stage_spans._minor_faults = counting
tree = {f"g{i}": jnp.full((300_000,), float(i), jnp.float32)
        for i in range(3)}
bps.push_pull_tree(tree, average=False)
assert not calls, calls
core = get_core()
core.trace_enable(True)
bps.push_pull_tree(tree, average=False)
assert len(calls) == 2, calls
(args,) = round_faults(core)
assert isinstance(args["minflt"], int) and args["minflt"] >= 0, args
print("COUNTED_OK")
""", port=ps_server(num_workers=1))
    assert "COUNTED_OK" in out


# ---------------------------------------------------------------------------
# (b) kept memory is never a pulled array's: what round k pulled is what
# it was after round k+1 has run.
# ---------------------------------------------------------------------------
_ALIAS_HEAD = """
import jax, jax.numpy as jnp
import byteps_tpu as bps
assert host_memory.keep_freed_memory()
assert mapped_by(1 << 26) == 0

def tree_of(k):
    return {f"g{i}": jnp.full((n,), float(100 * k + i), jnp.float32)
            for i, n in enumerate((400_000, 250_000, 400_000, 300, 35))}

def frozen(tree):
    return {k: np.array(v, copy=True) for k, v in tree.items()}
"""

_ALIAS_JOBS = {
    "fused": """
bps.init()
pulled, want = [], []
for k in range(4):
    out = bps.push_pull_tree(tree_of(k), average=False)
    jax.block_until_ready(out)
    for old, was in zip(pulled, want):
        for name in was:
            np.testing.assert_array_equal(np.asarray(old[name]), was[name])
    pulled.append(out)
    want.append(frozen(tree_of(k)))
print("ALIAS_OK")
""",
    "unfused": """
bps.init()
pulled, want = [], []
for k in range(4):
    tree = tree_of(k)
    handles = {name: bps.push_pull_async(leaf, name="t30." + name,
                                         average=False)
               for name, leaf in tree.items()}
    out = {name: bps.synchronize(h) for name, h in handles.items()}
    for old, was in zip(pulled, want):
        for name in was:
            np.testing.assert_array_equal(np.asarray(old[name]), was[name])
    pulled.append(out)
    want.append(frozen(tree))
print("ALIAS_OK")
""",
    "async_trainer": """
from byteps_tpu.parallel.async_ps import AsyncPSTrainer
from byteps_tpu.server.client import PSSession
sess = PSSession(["127.0.0.1"], [int(os.environ["PS_PORT"])], worker_id=0,
                 num_servers=1)
trainer = AsyncPSTrainer(sess, frozen(tree_of(0)), name="t30",
                         pipeline=False)
seen, want = [], []
for k in range(1, 5):
    trainer.step({name: v + 1.0 for name, v in trainer.params.items()})
    for old, was in zip(seen, want):
        for name in was:
            np.testing.assert_array_equal(np.asarray(old[name]), was[name])
    seen.append(trainer.params)
    want.append(frozen(trainer.params))
    first = frozen(tree_of(0))
    for name in first:
        np.testing.assert_array_equal(want[-1][name], first[name] + k)
trainer.finalize()
sess.close()
print("ALIAS_OK")
""",
}


@pytest.mark.parametrize("path", sorted(_ALIAS_JOBS))
def test_pulled_tree_survives_the_next_round(ps_server, path):  # noqa: F811
    port = ps_server(num_workers=1, async_mode=path == "async_trainer")
    assert "ALIAS_OK" in _run(_ALIAS_HEAD + _ALIAS_JOBS[path], port=port)


# ---------------------------------------------------------------------------
# (c) who gets the policy: a PS worker on an accelerator, nobody else.
# ---------------------------------------------------------------------------
_INIT_THEN_ASK = """
import jax, jax.numpy as jnp
import byteps_tpu as bps
if os.environ.get("BACKEND"):
    jax.default_backend = lambda: os.environ["BACKEND"]
bps.init()
out = bps.push_pull_tree({"w": jnp.ones((1000,), jnp.float32)},
                         average=False)
np.testing.assert_array_equal(np.asarray(out["w"]), np.ones(1000))
print("MAPPED", mapped_by(1 << 26))
bps.shutdown()
"""


@pytest.mark.parametrize("who", ["no_session", "cpu_worker",
                                 "accelerator_worker"])
def test_only_an_accelerators_ps_worker_keeps_memory(ps_server,  # noqa: F811
                                                     who):
    """A process that never makes a PS session (the in-graph path) and a
    PS worker on the CPU backend have the allocator's defaults: a 64 MB
    block is mmapped.  A PS worker whose backend is an accelerator has
    mmap off."""
    if who == "no_session":
        mapped = _printed(_run(_INIT_THEN_ASK), "MAPPED")
    else:
        env = {"BACKEND": "tpu"} if who == "accelerator_worker" else {}
        mapped = _printed(_run(_INIT_THEN_ASK, env,
                               port=ps_server(num_workers=1)), "MAPPED")
    if who == "accelerator_worker":
        assert mapped == 0
    else:
        assert mapped >= _BLOCK


@pytest.mark.parametrize("env", [
    {"MALLOC_MMAP_MAX_": "65536"},
    {"GLIBC_TUNABLES": "glibc.malloc.mmap_max=65536"}],
    ids=["variable", "tunable"])
def test_a_parameter_the_user_set_is_left_alone(env):
    """glibc's own variables override the policy: with mmap_max given by
    the environment a 64 MB block is still mmapped after the call."""
    out = _run("""
host_memory.keep_freed_memory()
print("MAPPED", mapped_by(1 << 26))
""", env)
    assert _printed(out, "MAPPED") >= _BLOCK


# ---------------------------------------------------------------------------
# (d) no glibc, no mallopt: the session still comes up.
# ---------------------------------------------------------------------------
_OTHER_LIBC = {
    "not_glibc": """
class Libc:
    def mallopt(self, *a):
        raise AssertionError("another libc's mallopt was called")
""",
    "no_mallopt": """
class Libc:
    gnu_get_libc_version = None
""",
    "no_libc": """
def Libc():
    raise OSError("no libc to load")
""",
}


@pytest.mark.parametrize("libc", sorted(_OTHER_LIBC))
def test_session_comes_up_without_mallopt(ps_server, libc):  # noqa: F811
    """Where the process's C library is not glibc, has no `mallopt`, or
    cannot be loaded, the policy is a no-op and `init` goes on."""
    out = _run(_OTHER_LIBC[libc] + """
real_cdll = ctypes.CDLL
ctypes.CDLL = lambda name=None, *a, **k: (
    Libc() if name is None else real_cdll(name, *a, **k))
assert host_memory.keep_freed_memory() is False
os.environ["BACKEND"] = "tpu"
""" + _INIT_THEN_ASK, port=ps_server(num_workers=1))
    assert _printed(out, "MAPPED") >= _BLOCK
