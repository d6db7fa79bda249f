"""Shared test helpers (importable from any test module)."""

import os
import socket


def free_port() -> int:
    """An ephemeral TCP port that was free at bind time."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def cpu_env(extra=None):
    """Subprocess environment hermetically pinned to the CPU backend
    (see byteps_tpu.utils.hermetic for why JAX_PLATFORMS alone fails)."""
    from byteps_tpu.utils.hermetic import cpu_subprocess_env
    return cpu_subprocess_env(extra)


def tiny_gpt2_config() -> dict:
    """The gpt2-medium cell's configuration at the benchmark's tiny
    widths."""
    import json

    from benchmark.harness import manifest
    from benchmark.tests import tiny
    with open(os.path.join(manifest.BENCH, "configs",
                           "gpt2-medium.json")) as f:
        return tiny.tiny_config(json.load(f))


def eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs among its equations'
    parameters, each as often as it is written (a scan's body once)."""
    import jax
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns(sub)


def is_flash_forward(eqn) -> bool:
    """A flash attention forward kernel's call: the Pallas call, unnamed
    or `flash_fwd_w<window>`, that writes `o` [bh, s, d] and `lse`
    [bh, 1, s] (dq writes one array, dK/dV two of one shape)."""
    if eqn.primitive.name != "pallas_call":
        return False
    name = eqn.params.get("name")
    out = [v.aval.shape for v in eqn.outvars]
    return ((name is None or name.startswith("flash_fwd"))
            and len(out) == 2 and len(out[0]) == 3
            and out[1] == (out[0][0], 1, out[0][1]))


def is_product(eqn, *shapes) -> bool:
    """A `dot_general` of operands of exactly these shapes."""
    return (eqn.primitive.name == "dot_general"
            and tuple(v.aval.shape for v in eqn.invars) == shapes)


def named_bytes(jaxpr) -> dict:
    """`{name: bytes}` of what `checkpoint_name` names in a jaxpr."""
    out = {}
    for eqn in eqns(jaxpr):
        if eqn.primitive.name == "name":
            v = eqn.outvars[0].aval
            out[eqn.params["name"]] = (out.get(eqn.params["name"], 0)
                                       + v.size * v.dtype.itemsize)
    return out


class StubPSServer:
    """Minimal in-thread PS-protocol stub for wire tests.

    Parses request frames (client.py ``_REQ``) off every accepted
    connection and answers each with ``handler(cmd, dtype, flags, req_id,
    worker_id, key, payload) -> (status, resp_bytes)`` wrapped in a
    ``_RESP`` header.  One implementation for every hand-rolled stub the
    wire tests need (old-server compatibility shims, frame recorders) —
    a future header change lands here once.

    With ``record=True`` every raw request header is kept in
    ``self.frames`` as ``(raw_header_bytes, cmd, flags)`` under
    ``self.lock``; ``record_payload=True`` additionally keeps the raw
    payload bytes in ``self.payloads`` (index-aligned with
    ``self.frames``) — the wire byte-identity tests' surface.
    """

    def __init__(self, handler, record: bool = False,
                 record_payload: bool = False):
        import socket as _socket
        import threading as _threading
        self.handler = handler
        self.record = record or record_payload
        self.record_payload = record_payload
        self.frames = []
        self.payloads = []
        self.lock = _threading.Lock()
        self._srv = _socket.socket()
        self._srv.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(8)
        self.port = self._srv.getsockname()[1]
        self._stop = _threading.Event()
        self._accept = _threading.Thread(target=self._accept_loop,
                                         daemon=True)
        self._accept.start()

    def _accept_loop(self):
        import socket as _socket
        import threading as _threading
        self._srv.settimeout(0.2)
        conns = []
        while not self._stop.is_set():
            try:
                c, _ = self._srv.accept()
            except _socket.timeout:
                continue
            conns.append(c)
            _threading.Thread(target=self._serve, args=(c,),
                              daemon=True).start()
        for c in conns:
            c.close()
        self._srv.close()

    @staticmethod
    def _recv_exact(c, n):
        buf = b""
        while len(buf) < n:
            got = c.recv(n - len(buf))
            if not got:
                raise OSError("closed")
            buf += got
        return buf

    def _serve(self, c):
        from byteps_tpu.server.client import _REQ, _RESP
        try:
            while True:
                hdr = self._recv_exact(c, _REQ.size)
                cmd, dt, fl, req_id, wid, key, ln = _REQ.unpack(hdr)
                payload = self._recv_exact(c, ln) if ln else b""
                if self.record:
                    with self.lock:
                        self.frames.append((hdr, cmd, fl))
                        if self.record_payload:
                            self.payloads.append(bytes(payload))
                status, resp = self.handler(cmd, dt, fl, req_id, wid, key,
                                            payload)
                c.sendall(_RESP.pack(status, req_id, key, len(resp))
                          + resp)
        except OSError:
            pass

    def close(self):
        self._stop.set()
        self._accept.join(timeout=5)


def mixer_trains_as_with_the_jnp_convolution(family, monkeypatch, tol):
    """Loss and every gradient leaf of `family.loss`, the Mamba-2 mixers'
    convolution on `ops/short_conv.py`'s kernels, against the same program
    with `granite_hybrid._conv` as it was until PR 56 (`ssd.causal_conv1d`
    and a silu), within `tol` of each leaf's norm."""
    import jax
    import jax.numpy as jnp

    import byteps_tpu as bps
    from benchmark.harness import seeded
    from byteps_tpu.models import granite_hybrid
    from byteps_tpu.ops import ssd
    params = seeded.params(family, 0)
    batch = seeded.batch(family, 0, family.reference_check["samples"])
    loss, grads = jax.jit(jax.value_and_grad(family.loss))(params, batch)
    assert bps.get_metrics()["bps_mamba_conv_kernel"] == 1
    monkeypatch.setattr(
        granite_hybrid, "_conv", lambda xbc, lp: jax.nn.silu(
            ssd.causal_conv1d(xbc, lp["conv_w"], lp["conv_b"])))
    want, want_grads = jax.jit(jax.value_and_grad(family.loss))(
        params, batch)
    assert abs(float(loss) - float(want)) <= tol * abs(float(want))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        diff = float(jnp.linalg.norm((got - ref).astype(jnp.float32)))
        assert diff <= tol * float(jnp.linalg.norm(ref)) + 1e-12, (
            jax.tree_util.keystr(path), diff)
