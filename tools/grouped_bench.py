"""The expert layer's grouped products alone on the chip, us a call: the
compiler's kernel (`lax.ragged_dot` and its two gradients), the Pallas
kernels that ship with JAX (megablox `gmm` / `tgmm`) and the program's own
(`byteps_tpu/ops/grouped_matmul.py`), each kind at each shape, over a few
tilings.  The table in docs/performance.md, "Grouped products", is this
tool's output.

    python3 tools/grouped_bench.py --out chiprun_out/grouped_bench.jsonl

Three kinds: `fwd` [rows, K] x [G, K, N]; `drows`, the rows' gradient,
[rows, N] x [G, K, N]^T; `dweights`, the weights' gradient, [rows, K]^T x
[rows, N] -> [G, K, N].  The routing is drawn from `--seed`: `live` rows
of the buffer spread over the groups unevenly (a multinomial), so that a
group's edge falls inside a tile as in a step.  Every result is compared
with the compiler's on the live rows.  A variant the chip's compiler
refuses (VMEM) is a line with its error.  `--cpu` runs a tiny shape in
the interpreter, to rehearse the tool's own paths.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (rows of the buffer, live rows, K, N, groups): the mellum cell's
# gate / up and down products, trinity-mini's.
SHAPES = {
    "mellum.up": (81920, 65536, 2304, 896, 16),
    "mellum.down": (81920, 65536, 896, 2304, 16),
    "trinity.up": (40960, 32768, 2048, 1024, 16),
    "trinity.down": (40960, 32768, 1024, 2048, 16),
}
TINY = {"tiny.up": (1024, 768, 256, 128, 4)}


def _routing(seed, live, groups):
    import numpy as np
    rng = np.random.default_rng(seed)
    return rng.multinomial(live, rng.dirichlet([8.0] * groups)).astype(
        np.int32)


def _time(fn, args, repeats):
    import jax
    out = jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        last = fn(*args)
    jax.block_until_ready(last)
    return out, (time.perf_counter() - t0) / repeats * 1e6


def variants(kind, rows, k, n, interpret, tiny):
    """`(implementation, tiles, callable(lhs, rhs, g, sizes))` of one
    kind at one shape."""
    import jax
    from jax import lax
    # (the package's `gmm` is the function; `tgmm` lives in the module)
    mb = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")

    from byteps_tpu.ops import grouped_matmul as gm

    def vjp(which):
        def call(lhs, rhs, g, sizes):
            _, pull = jax.vjp(lambda a, b: lax.ragged_dot(a, b, sizes),
                              lhs, rhs)
            return pull(g)[which]
        return call

    def parts(width):
        return sorted({width, *(d for d in (width // 2, width // 3,
                                            width // 4)
                                if d % 128 == 0 and width % d == 0)},
                      reverse=True)

    c, o = (k, n) if kind == "fwd" else (n, k)      # rows kernel: C -> O
    row_tiles = (128, 256) if tiny else (128, 256, 512, 1024)
    if kind in ("fwd", "drows"):
        transposed = kind == "drows"

        def kernel(tm, tc):
            return lambda a, b, g, s: gm._rows_call(
                g if transposed else a, b, gm.row_walk(s, rows, tm), tm=tm,
                tc=tc, transposed=transposed, interpret=interpret)
        yield "compiler", None, (vjp(0) if transposed else
                                 lambda a, b, g, s: lax.ragged_dot(a, b, s))
        for t in ([(128, 128, 128)] if tiny else
                  [(512, 512, 512), (512, parts(c)[-1], o),
                   (1024, parts(c)[-1], o)]):
            yield "megablox", t, (lambda a, b, g, s, t=t: mb.gmm(
                g if transposed else a, b, s, a.dtype, t,
                transpose_rhs=transposed, interpret=interpret))
        for tm in row_tiles:
            for tc in parts(c):
                yield "kernel", (tm, tc, o), kernel(tm, tc)
        return

    def kernel(tm, tk):
        return lambda a, b, g, s: gm._dweights_call(
            a, g, gm.row_walk(s, rows, tm), tm=tm, tk=tk,
            interpret=interpret)
    yield "compiler", None, vjp(1)
    for t in ([(128, 128, 128)] if tiny else
              [(512, 512, 512), (512, parts(k)[-1], n)]):
        yield "megablox", t, (lambda a, b, g, s, t=t: mb.tgmm(
            a.T, g, s, a.dtype, t, interpret=interpret))
    for tm in row_tiles:
        for tk in parts(k)[:3]:
            yield "kernel", (tm, tk, n), kernel(tm, tk)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/grouped_bench.jsonl")
    ap.add_argument("--shapes", nargs="*", default=None)
    ap.add_argument("--kinds", nargs="*",
                    default=["fwd", "drows", "dweights"])
    ap.add_argument("--seed", type=int, default=2147483401)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--cpu", action="store_true",
                    help="a tiny shape in the interpreter: times mean "
                         "nothing")
    args = ap.parse_args(argv)
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.cpu:
        print(f"no TPU here ({device.platform}); --cpu rehearses the tool",
              file=sys.stderr)
        return 1
    shapes = TINY if args.cpu else SHAPES
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as out:
        for shape in args.shapes or shapes:
            rows, live, k, n, groups = shapes[shape]
            keys = jax.random.split(jax.random.PRNGKey(args.seed % 2**31), 3)
            lhs = jax.random.normal(keys[0], (rows, k), jnp.bfloat16)
            rhs = jax.random.normal(keys[1], (groups, k, n),
                                    jnp.bfloat16) * k ** -0.5
            g = jax.random.normal(keys[2], (rows, n), jnp.bfloat16)
            sizes = jnp.asarray(_routing(args.seed, live, groups))
            operands = (lhs, rhs, g, sizes)
            for kind in args.kinds:
                want = None
                for impl, tiles, fn in variants(kind, rows, k, n, args.cpu,
                                                args.cpu):
                    line = {"shape": shape, "kind": kind, "impl": impl,
                            "tiles": tiles, "device": device.device_kind,
                            "rows": rows, "live": live, "k": k, "n": n}
                    try:
                        got, us = _time(jax.jit(fn), operands, args.repeats)
                    except Exception as e:  # noqa: BLE001 — the compiler's
                        line["error"] = f"{type(e).__name__}: {e}"[:300]
                    else:
                        got = np.asarray(got, np.float32)
                        if kind != "dweights":
                            got = got[:live]
                        if want is None:
                            want = got
                        flops = 2.0 * live * k * n
                        line.update(
                            us=us, tflops=flops / us / 1e6,
                            rel_err=float(np.abs(got - want).max()
                                          / np.abs(want).max()))
                    print(json.dumps(line), flush=True)
                    out.write(json.dumps(line) + "\n")
                    out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
