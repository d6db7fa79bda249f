"""The PS-plane job in the joint layout: one `python -m byteps_tpu.server`
child on the same host and this process as its one worker.

    loss, grads = jit(value_and_grad(loss))(params, batch)
    grads       = bps.push_pull_tree(grads)      # leaves the device,
                                                 # crosses the server tier
                                                 # and comes back
    params, opt = jit(optimizer update)(params, opt, grads)
    bps.mark_step()

Default options, no compressor.  With one worker the server's sum is a
copy, so the pulled tree must equal the pushed one bit for bit, which
`checked_step` verifies.  The server child gets this process's whole
environment, as the launcher's joint role hands it over, and must not
touch the accelerator this process holds.  (The boot and the probe are
chip_smoke.py's `start_server` and `_touches_accelerator`.)
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import optax

import byteps_tpu as bps
from benchmark.harness import manifest, seeded
from byteps_tpu.core import build, native

_WORKER_ENV = {"BYTEPS_TPU_PS_MODE": "1", "DMLC_ROLE": "worker",
               "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
               "DMLC_WORKER_ID": "0", "DMLC_PS_ROOT_URI": "127.0.0.1"}
_SERVER_LOG = os.path.join(manifest.ROOT, ".bench_trace", "ps_server.log")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def touches_accelerator(pid: int) -> list:
    """Evidence that process `pid` created an accelerator backend: the
    TPU runtime mapped into it, or a device node open."""
    found = []
    with open(f"/proc/{pid}/maps") as f:
        if "libtpu" in f.read():
            found.append("libtpu mapped")
    for fd in os.listdir(f"/proc/{pid}/fd"):
        try:
            target = os.readlink(f"/proc/{pid}/fd/{fd}")
        except OSError:
            continue    # closed between listdir and readlink
        if target.startswith(("/dev/accel", "/dev/vfio")):
            found.append(f"open {target}")
    return found


def start_server(port: int, env: dict) -> subprocess.Popen:
    """The server through its normal entry; returns once it listens."""
    os.makedirs(os.path.dirname(_SERVER_LOG), exist_ok=True)
    env = {**env, "DMLC_ROLE": "server", "DMLC_NUM_WORKER": "1",
           # serve() binds scheduler_port + 1 + server_id
           "DMLC_PS_ROOT_PORT": str(port - 1)}
    with open(_SERVER_LOG, "w") as log:
        proc = subprocess.Popen([sys.executable, "-m", "byteps_tpu.server"],
                                env=env, cwd=manifest.ROOT,
                                stdout=subprocess.DEVNULL, stderr=log)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and proc.poll() is None:
        try:
            socket.create_connection(("127.0.0.1", port), 0.5).close()
            return proc
        except OSError:
            time.sleep(0.05)
    proc.kill()
    proc.wait()
    with open(_SERVER_LOG) as log:
        raise RuntimeError(f"the PS server did not come up "
                           f"(rc={proc.returncode}):\n{log.read()[-2000:]}")


class Job:
    # A name of its own: host-clocked work must not loosen the bound on
    # the in-graph figure.
    metric_prefix = "ps_"
    n_shards = 1
    # One worker's round is the identity, so the losses differ from a
    # plain step's only by how XLA rounds the optimizer as a program of
    # its own.
    plain_kind = "rounding"

    def __init__(self, family, job: dict, traffic: dict, devices, seed: int,
                 trace=None):
        if len(devices) != 1 or (traffic["servers"], traffic["workers"]) \
                != (1, 1):
            raise ValueError("ps_joint is one worker on one chip and one "
                             "server; other layouts are other jobs")
        self.family, self.seed, self.trace = family, seed, trace
        self.samples_per_step = int(job["per_chip_batch"])
        self.env = {k: str(v) for k, v in traffic["env"].items()}
        self.round_s: list = []     # host seconds of every round so far

    def __enter__(self):
        build.build()       # once per checkout; the child then finds it
        if not native.is_native():
            raise RuntimeError("core.native fell back to the Python core")
        port = _free_port()
        self.server = start_server(port, {**os.environ, **self.env})
        env = {**_WORKER_ENV, **self.env, "DMLC_PS_ROOT_PORT": str(port - 1)}
        if self.trace is not None:
            # The program's own spans, in the traced run only: worker and
            # server stages of every partition, merged into comm.json when
            # the last traced step is marked.  The program counts steps
            # over the life of the process.
            done = bps.current_step()
            env.update(
                BYTEPS_TRACE_ON="1", BYTEPS_TRACE_DIR=self.trace.dir,
                BYTEPS_TRACE_START_STEP=str(done + self.trace.first_step),
                BYTEPS_TRACE_END_STEP=str(done + self.trace.last_step))
        self._saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            bps.init()
            family, opt = self.family, self.family.optimizer()
            self._grad = jax.jit(jax.value_and_grad(family.loss))

            def apply(params, opt_state, grads):
                updates, opt_state = opt.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), opt_state

            self._apply = jax.jit(apply, donate_argnums=(0, 1))
            self._same = jax.jit(lambda a, b: jnp.all(jnp.asarray(
                [jnp.array_equal(x, y) for x, y in
                 zip(jax.tree.leaves(a), jax.tree.leaves(b))])))
            self.params = seeded.params(family, self.seed)
            self.opt_state = jax.jit(opt.init)(self.params)
            self.batch = seeded.batch(family, self.seed,
                                      self.samples_per_step)
        except BaseException:
            self.__exit__()
            raise
        return self

    def __exit__(self, *exc):
        try:
            bps.shutdown()
        finally:
            self.server.kill()
            self.server.wait()
            for k, v in self._saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def _round(self, check: bool):
        with jax.profiler.TraceAnnotation("bench.grad"):
            loss, grads = self._grad(self.params, self.batch)
            # push_pull_tree's first act is to wait for the gradients;
            # waiting here keeps that out of the round's timer.
            jax.block_until_ready(grads)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.push_pull"):
            pulled = bps.push_pull_tree(grads, name="bench.grads")
            jax.block_until_ready(pulled)
        self.round_s.append(time.perf_counter() - t0)
        same = bool(self._same(grads, pulled)) if check else None
        del grads
        with jax.profiler.TraceAnnotation("bench.apply"):
            self.params, self.opt_state = self._apply(
                self.params, self.opt_state, pulled)
        bps.mark_step()
        return loss, same

    def step(self):
        return self._round(check=False)[0]

    def checked_step(self):
        """A step in which the pulled tree is compared with the pushed
        one, and the server child is looked at from outside."""
        loss, same = self._round(check=True)
        touched = touches_accelerator(self.server.pid)
        checks = {"pulled_equals_pushed": same,
                  "server_alive": self.server.poll() is None,
                  "server_off_the_accelerator": not touched}
        if jax.devices()[0].platform != "cpu":
            # The same probe on this process, which holds the chip, must
            # be positive, or its silence about the child proves nothing.
            checks["probe_sees_this_process"] = bool(
                touches_accelerator(os.getpid()))
        return loss, checks

    def extras(self) -> dict:
        return {"ps_round_s": list(self.round_s)}
