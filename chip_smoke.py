"""chip_smoke.py — the quickest proof that byteps_tpu still starts on the chip.

    python chip_smoke.py             # one chip: phases 1-3 (what the driver runs)
    python chip_smoke.py --chips 4   # four chips: phase 1, then the dp=4 phase only

Drives both gradient paths once through the entry points a user calls, at
the full width of the flagship (bert_large; weights random from --seed):

  1. device   — jax.devices() must be TPUs of a kind the peak table knows;
  2. in-graph — bps.init / make_mesh / DistributedOptimizer /
                build_train_step: a few donated steps, the Pallas kernel
                in the lowered step, first loss against dense attention +
                the full-logits head;
  3. PS       — a `python -m byteps_tpu.server` child (native core, forced
                rebuild from the tracked .cc files) and this process as its
                one worker: the full gradient tree through
                bps.push_pull_tree, bit for bit, while this process holds
                the chip and the child must not touch it;
  4. dp=4     — (--chips 4 only) the bucketed shard_map step over all four
                chips against the same global batch on chip 0 alone.

One process holds the chip from start to end.  Any failed check exits
non-zero and prints no result line.  The last line of stdout is the result:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Earlier lines are smoke output (compile seconds, tokens/s, memory): they
say the path ran, they are not measurements.

The phases are plain functions of a config and sizes; tests/test_chip_smoke.py
rehearses them at tiny size on the CPU mesh.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import signal
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# What "agrees at bf16 tolerance" means for a loss (a mean of f32 NLLs
# over bf16 activations): relative to the loss's own size.
BF16_LOSS_RTOL = 1e-2


class SmokeError(RuntimeError):
    """A check failed: the smoke run exits non-zero."""


@dataclasses.dataclass(frozen=True)
class Sizes:
    per_chip_batch: int     # sequences per chip per step
    seq: int
    ref_slice: int          # sequences compared with the dense reference
    steps: int              # steps after the warm-up (compile) step
    ps_rounds: int = 2


FULL = Sizes(per_chip_batch=64, seq=512, ref_slice=8, steps=3)
FULL_DP4 = dataclasses.replace(FULL, per_chip_batch=16)


def flagship_config(**overrides):
    """bert_large as the flagship: 24 layers, d_model 1024, 16 heads,
    d_ff 4096, vocab 32768, seq 512, bf16 activations, flash block 512,
    CE chunk 2048, per-layer remat."""
    from byteps_tpu.models import transformer as tfm
    return tfm.get_config(
        "bert_large", causal=True, vocab_size=32768, max_seq_len=512,
        ce_chunk_rows=2048, attn_impl="flash", attn_block=512, **overrides)


def say(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, "smoke_output_not_a_measurement":
                      facts}), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def _on_tpu(device) -> bool:
    return device.platform == "tpu"


# ---------------------------------------------------------------------------
# Phase 1: device
# ---------------------------------------------------------------------------
def phase_device(devices, n_chips: int) -> dict:
    """`devices` (what jax.devices() returned) must be exactly `n_chips`
    TPUs whose device_kind is a key of the one peak table: an unknown
    kind is an error, not a 0.0 peak."""
    from byteps_tpu.common.devprof import PEAK_BF16
    check(len(devices) > 0, "jax.devices() is empty")
    d0 = devices[0]
    check(d0.platform == "tpu",
          f"JAX found no accelerator: platform is {d0.platform!r}, not 'tpu'")
    check(d0.device_kind in PEAK_BF16,
          f"device_kind {d0.device_kind!r} is not a key of "
          f"devprof.PEAK_BF16 {sorted(PEAK_BF16)}")
    check(len(devices) == n_chips,
          f"expected {n_chips} chip(s), jax.devices() has {len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# Shared by phases 2 and 4
# ---------------------------------------------------------------------------
def _memory_peaks(devices) -> list:
    """peak_bytes_in_use per device; None where the backend reports none
    (the CPU)."""
    out = []
    for d in devices:
        st = d.memory_stats()
        out.append(None if st is None else int(st["peak_bytes_in_use"]))
    return out


def _lowered_text(step, args) -> str:
    """StableHLO of the step for these arguments, traced from shapes (the
    arguments themselves are about to be donated)."""
    import jax
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
    return jax.jit(step).lower(*shapes).as_text()


def _train(cfg, mesh, batch_size: int, sizes: Sizes, seed: int,
           expect_all_reduce: bool) -> dict:
    """A few steps of bert-class LM training on `mesh` through the normal
    entry points, from weights and a batch made from `seed`.  Returns the
    loss trajectory, the final params and what was observed on the way."""
    import jax
    import optax

    import byteps_tpu as bps
    from byteps_tpu.models import transformer as tfm

    def loss_fn(p, b):
        return tfm.loss_fn(p, b, cfg)

    opt = bps.DistributedOptimizer(optax.adamw(1e-4))
    step = bps.build_train_step(loss_fn, opt, mesh, donate=True)
    params = tfm.init_params(jax.random.key(seed), cfg)
    batch = tfm.synthetic_batch(jax.random.key(seed + 1), batch_size,
                                sizes.seq, cfg)
    opt_state = opt.init(params)

    hlo = _lowered_text(step, (params, opt_state, batch))
    device = mesh.devices.flat[0]
    if cfg.attn_impl == "flash" and _on_tpu(device):
        # Neither flash_attention's interpret switch nor a dense path
        # can pass unnoticed: the Mosaic kernel is in the program or
        # the run fails.
        check("tpu_custom_call" in hlo,
              "the lowered train step holds no tpu_custom_call: the "
              "flash kernel is not in the program")
    if expect_all_reduce:
        check("all_reduce" in hlo,
              "the lowered dp step holds no all-reduce: gradients are "
              "not being reduced across the mesh")

    losses, step_s = [], []
    for _ in range(1 + sizes.steps):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))      # waits for the device every step
        step_s.append(round(time.perf_counter() - t0, 3))

    check(all(math.isfinite(x) for x in losses),
          f"non-finite loss in {losses}")
    ln_v = math.log(cfg.vocab_size)
    check(abs(losses[0] - ln_v) < 1.0,
          f"first loss {losses[0]:.4f} is not near ln(vocab) = {ln_v:.4f}")
    check(losses[-1] < losses[0],
          f"loss did not fall over {len(losses)} steps: {losses}")
    return {
        "losses": losses, "params": params,
        "kernel_in_hlo": "tpu_custom_call" in hlo,
        "all_reduce_in_hlo": "all_reduce" in hlo,
        # Per step, compile included where one happened: a step far
        # slower than the last is a compile, not the device.
        "step_s": step_s,
        "compile_s": round(step_s[0] - step_s[-1], 2),
        "tokens_per_s_last_step": round(
            batch_size * sizes.seq / step_s[-1], 1),
    }


# ---------------------------------------------------------------------------
# Phase 2: the in-graph path on one chip
# ---------------------------------------------------------------------------
def phase_ingraph(cfg, sizes: Sizes, seed: int = 0) -> dict:
    """bps.init -> make_mesh -> DistributedOptimizer -> build_train_step
    (donated) on every device JAX offers, plus the first loss on a slice
    against dense attention with the full-logits head on the same params.

    A batch that does not fit is halved until one does; the batch used
    and the memory peak are part of the output."""
    import jax

    import byteps_tpu as bps
    from byteps_tpu.models import transformer as tfm

    bps.init()
    try:
        mesh = bps.make_mesh()
        n_dev = mesh.devices.size

        params = tfm.init_params(jax.random.key(seed), cfg)
        toks, tgts = tfm.synthetic_batch(
            jax.random.key(seed + 1), sizes.ref_slice, sizes.seq, cfg)
        ref_cfg = dataclasses.replace(cfg, attn_impl="dense",
                                      ce_chunk_rows=0)
        got = float(jax.jit(lambda p, b: tfm.loss_fn(p, b, cfg))(
            params, (toks, tgts)))
        ref = float(jax.jit(lambda p, b: tfm.loss_fn(p, b, ref_cfg))(
            params, (toks, tgts)))
        del params
        check(abs(got - ref) <= BF16_LOSS_RTOL * abs(ref),
              f"first loss {got:.5f} disagrees with dense attention + "
              f"full-logits head {ref:.5f} beyond bf16 tolerance")

        per_chip = sizes.per_chip_batch
        while True:
            try:
                run = _train(cfg, mesh, per_chip * n_dev, sizes, seed,
                             expect_all_reduce=False)
                break
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e) or per_chip <= 1:
                    raise
                say("ingraph", batch_did_not_fit=per_chip,
                    peak_bytes_in_use=_memory_peaks(jax.devices()))
                per_chip //= 2
        run.pop("params")
        return {"slice_loss": got, "slice_loss_dense_full_logits": ref,
                "per_chip_batch": per_chip, "devices": n_dev,
                "peak_bytes_in_use": _memory_peaks(jax.devices()),
                "memory_stats": jax.devices()[0].memory_stats(), **run}
    finally:
        bps.shutdown()


# ---------------------------------------------------------------------------
# Phase 3: the PS path, this process holding the chip
# ---------------------------------------------------------------------------
def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _touches_accelerator(pid: int) -> list:
    """Evidence that process `pid` created an accelerator backend: the
    TPU runtime mapped into it, or a device node open.  Empty = none."""
    found = []
    with open(f"/proc/{pid}/maps") as f:
        if "libtpu" in f.read():
            found.append("libtpu mapped")
    fd_dir = f"/proc/{pid}/fd"
    for fd in os.listdir(fd_dir):
        try:
            target = os.readlink(os.path.join(fd_dir, fd))
        except OSError:
            continue    # closed between listdir and readlink
        if target.startswith(("/dev/accel", "/dev/vfio")):
            found.append(f"open {target}")
    return found


def start_server(port: int, log_path: str) -> subprocess.Popen:
    """`python -m byteps_tpu.server` for one worker on this host, through
    the normal entry — with this process's WHOLE environment, as the
    launcher's joint role hands it over.  Waits for the listening socket
    (the boot pattern of tests/test_ps_server.py)."""
    env = dict(os.environ)
    env.update({"DMLC_ROLE": "server", "DMLC_NUM_WORKER": "1",
                # serve() binds scheduler_port + 1 + server_id
                "DMLC_PS_ROOT_PORT": str(port - 1)})
    with open(log_path, "w") as log:
        proc = subprocess.Popen([sys.executable, "-m", "byteps_tpu.server"],
                                env=env, cwd=REPO,
                                stdout=subprocess.DEVNULL, stderr=log)
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), 0.5).close()
            return proc
        except OSError:
            if proc.poll() is not None:
                break
            time.sleep(0.1)
    proc.kill()
    proc.wait()
    with open(log_path) as log:
        raise SmokeError(f"PS server did not come up (rc={proc.returncode}):"
                         f"\n{log.read()[-2000:]}")


_PS_ENV = {"BYTEPS_TPU_PS_MODE": "1", "DMLC_ROLE": "worker",
           "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
           "DMLC_WORKER_ID": "0", "DMLC_PS_ROOT_URI": "127.0.0.1"}


def phase_ps(cfg, sizes: Sizes, seed: int = 0) -> dict:
    """The native core, a server child, and this process as its one
    worker in PS mode: the model's whole f32 gradient tree through
    bps.push_pull_tree, device arrays in and out.  With one worker the
    pulled tree equals the pushed tree bit for bit."""
    import jax
    import jax.numpy as jnp

    import byteps_tpu as bps
    from byteps_tpu.core import native
    from byteps_tpu.models import transformer as tfm

    check(native.is_native(), "core.native fell back to the Python core")

    params = tfm.init_params(jax.random.key(seed), cfg)
    batch = tfm.synthetic_batch(jax.random.key(seed + 1), sizes.ref_slice,
                                sizes.seq, cfg)
    grads = jax.jit(jax.grad(lambda p, b: tfm.loss_fn(p, b, cfg)))(
        params, batch)
    del params
    n_bytes = sum(g.size * g.dtype.itemsize for g in jax.tree.leaves(grads))
    same = jax.jit(lambda a, b: jnp.all(jnp.asarray(
        [jnp.array_equal(x, y) for x, y in
         zip(jax.tree.leaves(a), jax.tree.leaves(b))])))

    port = _free_port()
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    server = start_server(
        port, os.path.join(REPO, "chiprun_out", "chip_smoke_server.log"))
    saved = {k: os.environ.get(k)
             for k in (*_PS_ENV, "DMLC_PS_ROOT_PORT")}
    try:
        os.environ.update(_PS_ENV, DMLC_PS_ROOT_PORT=str(port - 1))
        bps.init()
        try:
            round_s = []
            for r in range(sizes.ps_rounds):
                # A different tree every round: a stale buffer from the
                # round before cannot pass for this round's answer.
                pushed = jax.tree.map(lambda g: g * (0.5 ** r), grads)
                t0 = time.perf_counter()
                pulled = bps.push_pull_tree(pushed, name="chip_smoke.grads")
                ok = bool(same(pushed, pulled))
                round_s.append(round(time.perf_counter() - t0, 2))
                check(all(isinstance(x, jax.Array)
                          for x in jax.tree.leaves(pulled)),
                      "push_pull_tree did not return device arrays")
                check(ok, f"round {r}: the pulled tree differs from the "
                          f"pushed tree (one worker: must be bit-identical)")
        finally:
            bps.shutdown()
        check(server.poll() is None,
              f"the server child died (rc={server.returncode})")
        # The child got this process's whole environment.  It must not
        # have created a backend on the chip this process holds.
        touched = _touches_accelerator(server.pid)
        check(not touched,
              f"the server child touched the accelerator: {touched}")
        if _on_tpu(jax.devices()[0]):
            # The same probe on this process, which does hold the chip,
            # must be positive — or the check above proves nothing.
            check(_touches_accelerator(os.getpid()),
                  "the accelerator probe finds nothing in the process "
                  "that holds the chip: it cannot vouch for the child")
    finally:
        server.kill()
        server.wait()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return {"native_core": True, "tree_bytes": n_bytes, "leaves": len(jax.tree.leaves(grads)),
            "rounds": sizes.ps_rounds, "round_s": round_s,
            "server_alive_after_rounds": True,
            "server_touched_accelerator": False}


# ---------------------------------------------------------------------------
# Phase 4 (--chips 4): the shard_map data-parallel step across chips
# ---------------------------------------------------------------------------
def phase_dp(cfg, sizes: Sizes, n_chips: int, seed: int = 0) -> dict:
    """The bucketed shard_map step on bps.make_mesh() over `n_chips`
    devices, against the same global batch on a one-device mesh of the
    first: the loss trajectories must agree.  Also shows the work is
    really spread — code that has only seen virtual devices may leave
    everything on the first."""
    import jax

    import byteps_tpu as bps

    devices = jax.devices()[:n_chips]
    check(len(devices) == n_chips,
          f"need {n_chips} devices, jax.devices() has {len(jax.devices())}")
    global_batch = sizes.per_chip_batch * n_chips
    bps.init()
    try:
        mesh = bps.make_mesh(devices=devices)
        check(mesh.devices.size == n_chips and mesh.shape["dp"] == n_chips,
              f"make_mesh gave {dict(mesh.shape)}, not dp={n_chips}")
        dp = _train(cfg, mesh, global_batch, sizes, seed,
                    expect_all_reduce=True)
        params = jax.tree.leaves(dp.pop("params"))
        spans = [len(p.sharding.device_set) for p in params]
        check(all(n == n_chips for n in spans),
              f"a param's sharding spans {min(spans)} device(s), "
              f"not {n_chips}")
        param_bytes = sum(p.size * p.dtype.itemsize for p in params)
        del params
        peaks = _memory_peaks(devices)
        if _on_tpu(devices[0]):
            check(all(p >= param_bytes for p in peaks),
                  f"a chip's peak memory {peaks} is below one copy of "
                  f"the params ({param_bytes} B): the work is not spread")

        one = _train(cfg, bps.make_mesh(devices=devices[:1]), global_batch,
                     sizes, seed, expect_all_reduce=False)
        one.pop("params")
    finally:
        bps.shutdown()
    worst = max(abs(a - b) / abs(b)
                for a, b in zip(dp["losses"], one["losses"]))
    check(worst <= BF16_LOSS_RTOL,
          f"dp={n_chips} losses {dp['losses']} disagree with one-device "
          f"losses {one['losses']} beyond bf16 tolerance")
    return {"global_batch": global_batch, "dp": dp, "one_device": one,
            "worst_rel_loss_diff": worst,
            "param_sharding_spans": n_chips,
            "peak_bytes_in_use": peaks}


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run phase 1 and the dp=4 phase, nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # A kill at the time limit must still reach the finally blocks that
    # stop the server child.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    import jax

    from byteps_tpu.utils import compile_cache
    cache = compile_cache.enable()

    try:
        device = phase_device(jax.devices(), args.chips)
        say("device", **device)
        cfg = flagship_config()
        if args.chips == 1:
            # The checkout may be a copy whose mtimes prove nothing:
            # rebuild the native core from the tracked .cc files before
            # the worker or the server child can load a stale one.
            from byteps_tpu.core import build
            t0 = time.perf_counter()
            build.build(force=True)
            say("core_build", seconds=round(time.perf_counter() - t0, 1))
            say("ingraph", **phase_ingraph(cfg, FULL, args.seed))
            say("ps", **phase_ps(cfg, FULL, args.seed))
        else:
            say("dp", **phase_dp(cfg, FULL_DP4, args.chips, args.seed))
        # Programs by the cache's answer and seconds by stage: whether
        # this call's set-up was warm.
        say("compile_cache", dir=cache, **compile_cache.summary())
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
