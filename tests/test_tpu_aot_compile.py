"""The main path's kernels and one train step, compiled for a described
TPU v5e — no chip attached, none needed.

The TPU's compiler is part of the installation and compiles for a chip
that is described, not present.  It refuses what the Pallas interpreter
lets through (a block that breaks the lane rule, a kernel over its VMEM
budget, a program over the device's memory), so these few compiles guard
every later change at no chip time.  Nothing runs here: a compile that
passes is not a chip run.

Code that asks `jax.default_backend()` still sees the CPU under test, so
the two switches that would pick the interpreter are steered here, in
the test (not through an option of the program).
"""

import math
import os
import re
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else it logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from byteps_tpu.ops import flash_attention as fa
from byteps_tpu.ops.compressor import bitpack


@pytest.fixture(scope="module")
def v5e():
    """The four described devices of a v5e 2x2 host."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e!r:.200}")
    return list(topo.devices)


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip: the next one would
    warn and compile again.  Keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _flash_fwd_bwd(bh, s, d, block, streaming, device, window=None,
                   block_k=None):
    x = jax.ShapeDtypeStruct((bh, s, d), jnp.bfloat16,
                             sharding=SingleDeviceSharding(device))

    def grads(q, k, v):
        def loss(q, k, v):
            out = fa.flash_attention(q, k, v, True, None, block,
                                     block_k or block,
                                     False, streaming, window)
            return jnp.sum(out.astype(jnp.float32))
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    return _compile(grads, x, x, x)


@pytest.mark.parametrize("bh,s,d,block,streaming", [
    (64 * 16, 512, 64, 512, None),     # bert_large width, per-chip batch 64
    (16, 2048, 64, 512, None),
    (4, 8192, 64, 512, True),          # the streaming path
    (16, 512, 128, 512, None),         # the MXU-ideal head dim
], ids=["bert_large_s512", "s2048", "streaming_s8192", "d128"])
def test_flash_fwd_bwd_compiles(v5e, bh, s, d, block, streaming):
    hlo = _flash_fwd_bwd(bh, s, d, block, streaming, v5e[0]).as_text()
    assert "tpu_custom_call" in hlo


def test_flash_compiles_at_gpt2_shape_with_the_results_the_benchmark_reads(
        v5e):
    """The `gpt2-medium` cells' call: batch 32 x 16 heads, 1024 positions,
    head size 64, at the tiles the rule gives a causal call (groups of
    256 rows over all their keys, a program a head).  The benchmark tells
    the three kernels by their results (`benchmark/reduce/flash_cost.py`
    `classify`), so those keep their forms: forward `(bf16[N,S,D],
    f32[N,1,S])`, dQ one `bf16[N,S,D]`, dK/dV two."""
    from benchmark.reduce import flash_cost
    from byteps_tpu.models.transformer import flash_auto_tiles
    block, block_k = flash_auto_tiles(1024, True)
    assert (block, block_k) == (256, 1024)
    text = _flash_fwd_bwd(512, 1024, 64, block, None, v5e[0],
                          block_k=block_k).as_text()
    calls = [line for line in text.splitlines()
             if flash_cost.is_kernel(line) and " custom-call(" in line]
    kinds = sorted(flash_cost.classify(line) for line in calls)
    assert kinds == [("dkv", 512, 1024, 64), ("dq", 512, 1024, 64),
                     ("forward", 512, 1024, 64)], calls
    assert not any("flash_" in line for line in calls)     # unnamed


@pytest.mark.parametrize("streaming", [None, True],
                         ids=["resident", "streaming"])
def test_windowed_flash_compiles_at_trinity_shapes(v5e, streaming):
    """Head size 128, 8192 positions, window 2048: the sliding layers of
    the afmoe cell (resident there; the streaming kernels too)."""
    compiled = _flash_fwd_bwd(8, 8192, 128, 512, streaming, v5e[0],
                              window=2048)
    text = compiled.as_text()
    assert all(f"flash_{kind}_w2048" in text
               for kind in ("fwd", "dq", "dkv"))


@pytest.mark.parametrize("window", [1024, None], ids=["w1024", "full"])
def test_streaming_flash_compiles_at_mellum_shapes(v5e, window):
    """32 heads of 128 over ONE sequence of 32,768: K and V of a head are
    16 MiB, past the resident budget, so the rule picks the streaming
    kernels by itself.  The causal call's grid is (heads, entries of the
    table of live tiles), the table's three columns scalar-prefetch
    operands that the index maps and the kernels read; the windowed
    call's is (heads, blocks, the band's 3 steps), its index maps a floor
    division and a minimum of traced indices: both are what the chip's
    compiler has to take.  The table's columns are operands: the three
    results keep the forms the benchmark tells the kernels by
    (`flash_cost.classify`), forward `(bf16[N,S,D], f32[N,1,S])`, dQ one
    `bf16[N,S,D]`, dK/dV two."""
    from benchmark.reduce import afmoe_cost, flash_cost
    from byteps_tpu.models.transformer import flash_auto_tiles
    assert flash_auto_tiles(32768, True) == (512, 512)
    q = jnp.zeros((32, 32768, 128), jnp.bfloat16)
    assert fa._use_streaming(q, None)
    text = _flash_fwd_bwd(32, 32768, 128, 512, None, v5e[0],
                          window=window).as_text()
    lines = [line for line in text.splitlines()
             if flash_cost.is_kernel(line) and " custom-call(" in line]
    kinds = [(kind, 32, 32768, 128) for kind in ("dkv", "dq", "forward")]
    assert sorted(flash_cost.classify(line) for line in lines) == kinds, \
        lines
    assert sorted(afmoe_cost.attention_call(line)[:4]
                  for line in lines) == kinds
    # the table is in the call: three s32 columns, one entry a live tile
    walk = fa.stream_walk(32768, 512, 512, True, window)
    assert walk.grid == ((64, 3) if window else (2080,))
    entries = len(walk.table[0])
    assert entries == (189 if window else 2080)
    assert all(line.count(f"s32[{entries}]") >= 3 for line in lines), lines
    # a bare `jax.grad` puts `jvp_` before a call's name, which a train
    # step's remat does not: the window is read from the text here
    assert all((f"flash_{kind}_w1024" in text) == (window is not None)
               for kind in ("fwd", "dq", "dkv"))


def test_block_diffusion_flash_compiles_at_sdar_shapes(v5e):
    """32 heads of 128 over the two copies of ONE sequence of 16,384
    tokens, 2 L = 32,768 rows, under the block-diffusion mask of blocks of
    4: the grid is (heads, entries of the table), 1,088 live tiles a head
    where the causal call's has 2,080, the table's three columns
    scalar-prefetch operands; a tile's mask an integer remainder on a
    column of 512 numbers and two compares, which the chip's compiler has
    to take; a whole tile behind one `pl.when`, a masked one behind the
    other.  The kernels carry the mask in their names and keep the forms
    the benchmark tells them by (`benchmark/reduce/bd_cost.py`)."""
    from benchmark.reduce import bd_cost
    L, beta = 16384, 4
    x = jax.ShapeDtypeStruct((32, 2 * L, 128), jnp.bfloat16,
                             sharding=SingleDeviceSharding(v5e[0]))

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, False, None, 512, 512, False, None, None,
            (L, beta)).astype(jnp.float32)), (0, 1, 2))(q, k, v)
    text = _compile(grads, x, x, x).as_text()
    lines = [line.strip().removeprefix("ROOT ").replace("%transpose_", "%")
             .replace("%jvp_", "%") for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert sorted(bd_cost.call(line) for line in lines) == [
        (kind, 32, L, 128, beta) for kind in ("dkv", "dq", "forward")], lines
    assert all(line.count("s32[1088]") >= 3 for line in lines), lines


def _row_moves(text: str, width: int):
    """Of a compiled step: `(left, kernels)`.  `left` are the compiler's
    own moves of rows under an expert layer, `gather(` and `scatter(`
    instructions (fused or not) under a `.moe` scope with an operand or a
    result `[rows, width]`; `kernels` the program's (`ops/moe_rows.py`),
    each `(name, path)` as `moe.move_kernel_share` reads them."""
    from benchmark.reduce import afmoe_cost
    left, kernels = [], []
    for line in map(str.strip, text.splitlines()):
        if 'custom_call_target="tpu_custom_call"' in line:
            name = afmoe_cost.xplane.op_name(line.removeprefix("ROOT "))
            if name.startswith("moe_rows_"):
                path = re.search(r'op_name="([^"]*)"', line)
                kernels.append((name, path.group(1) if path else ""))
        elif (re.search(r" (gather|scatter)\(", line) and ".moe" in line
              and re.search(rf"(?:bf16|f32)\[\d+,{width}\]", line)):
            left.append(line[:240])
    return left, kernels


def _assert_rows_move_by_kernel(text: str, width: int, k: int):
    """No gather or scatter of rows is left under the expert layers, and
    every kernel that moves them carries a name and a path the counter
    reads: `moe_rows_k1` (rows into a buffer, the result's gradient by
    token), `moe_rows_k<k>w` (results back, weighted) and `moe_rows_k<k>`
    (the rows' gradient back), under `.../<family>.moe/.gather` or
    `.scatter` (the exact path's a scope deeper), ending `pallas_call`."""
    left, kernels = _row_moves(text, width)
    assert not left, left
    assert {n.split(".")[0] for n, _ in kernels} == {
        "moe_rows_k1", f"moe_rows_k{k}w", f"moe_rows_k{k}"}, kernels
    from byteps_tpu.common import devprof
    for name, path in kernels:
        scope = devprof.classify_op_name(path)[0]
        assert re.search(r"\.moe(/exact)?/(gather|scatter)$", scope), (
            name, path)
        assert path.split(";")[0].endswith("/pallas_call"), (name, path)


@pytest.mark.parametrize(
    "experts,hidden,width,tokens,capacity,what",
    [(128, 2048, 1024, 8192, 1.25, "forward"),
     (128, 2048, 1024, 8192, 1.25, "gradient"),
     (64, 2304, 896, 32768, 4.0, "gradient")],
    ids=["trinity_widths-forward", "trinity_widths-gradient",
         "mellum_widths-gradient"])
def test_dropless_expert_layer_compiles(v5e, monkeypatch, experts, hidden,
                                        width, tokens, capacity, what):
    """16 held experts, 8 a token, at trinity-mini's widths (of 128
    experts, hidden 2048, expert width 1024, 8192 tokens) and at mellum's
    (of 64, 2304 / 896 = 18 and 7 lane tiles, one sequence of 32,768; a
    buffer that holds every pair, so the exact path, which trinity's case
    compiles, is not compiled twice): forward alone (which once broke the
    compiler's scatter emitter inside a loop) and the gradient under
    remat inside a scan, as the models have it.  The grouped products are
    the program's own kernels under the names the trace's readers know a
    grouped product by, none of the compiler's is left, and each kernel's
    instruction is what `benchmark/reduce/afmoe_cost.py` takes it for.
    The rows move by `ops/moe_rows.py`'s kernel: no `gather(` and no
    `scatter(` over a `[rows, hidden]` operand is left under the layer's
    scope (`_assert_rows_move_by_kernel`)."""
    from benchmark.reduce import afmoe_cost
    from byteps_tpu.parallel import dropless_moe as dm
    monkeypatch.setattr(fa, "_use_interpret", lambda interpret: False)
    cfg = dm.MoEConfig(num_experts=experts, top_k=8, held=tuple(range(16)),
                       capacity_factor=capacity)
    one = SingleDeviceSharding(v5e[0])

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    x = shape(tokens, hidden, dtype=jnp.bfloat16)
    weights = {"gate_w": shape(16, hidden, width),
               "up_w": shape(16, hidden, width),
               "down_w": shape(16, width, hidden)}

    def layers(x, router_w, weights):
        # inside a scan, each layer rematerialised, under the model's scope
        @jax.checkpoint
        def layer(x):
            with jax.named_scope("family.moe"):
                out, routing = dm.held_experts(x, router_w, weights, cfg)
            return x + out, routing.counts
        return jax.lax.scan(lambda x, _: layer(x), x, None, length=2)

    def loss(x, router_w, weights):
        return layers(x, router_w, weights)[0].astype(jnp.float32).sum()

    args = (x, shape(hidden, experts), weights)
    if what == "forward":
        text = _compile(layers, *args).as_text()
        assert "ragged-dot-none_fwd" in text
        assert "ragged-dot-metadata" not in text
        left, kernels = _row_moves(text, hidden)
        assert not left, left
        assert {n.split(".")[0] for n, _ in kernels} == {
            "moe_rows_k1", "moe_rows_k8w"}, kernels
        return
    text = _compile(jax.grad(loss, (0, 1, 2)), *args).as_text()
    assert "ragged-dot-metadata" not in text        # the compiler's own
    # the rows move by the program's kernel, forward and backward
    _assert_rows_move_by_kernel(text, hidden, 8)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "moe_rows_" not in line.split(" = ")[0]]
    names = [afmoe_cost.xplane.op_name(line.strip()) for line in calls]
    # 3 products: forward, again under remat, and two gradients each
    for kind, least in (("fwd", 6), ("drows", 3), ("dweights", 3)):
        assert sum(n.startswith(f"ragged-dot-none_{kind}")
                   for n in names) >= least, names
    assert all(n.startswith("ragged-dot-none_") for n in names), names
    for line in map(str.strip, calls):
        assert afmoe_cost.is_grouped(line)
        assert afmoe_cost.attention_call(line) is None
        assert afmoe_cost.grouped_call(line) in (
            (16, hidden, width), (16, width, hidden)), line
        assert "/family.moe/" in line and ".grouped" in line


# (positions, groups, chunk) of the two cells that run the scan, both with
# 64 heads of 64 and a state of 128
SCAN_CELLS = {"granite": (8192, 1, 256), "nemotron": (16384, 8, 128)}


def _wide_moves(text: str, elements: int):
    """The `transpose` and `copy` instructions of a compiled module, and
    the fusions that hold one, with an operand or a result of `elements`
    elements: a re-laid-out copy of an array of x's size."""
    held = {m.group(1) for m in re.finditer(
        r"^(?:ROOT )?%?(\S+) \([^\n]*\{\n(?:[^}]*\n)*?[^}\n]* "
        r"(?:transpose|copy)\(", text, re.M)}
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?\S+ = .*? (transpose|copy|fusion)\(",
                     line)
        if not m or "tpu_custom_call" in line:
            continue
        if m.group(1) == "fusion":
            calls = re.search(r"calls=%?([\w.-]+)", line)
            if not calls or calls.group(1) not in held:
                continue
        sizes = [math.prod(map(int, dims.split(",")))
                 for dims in re.findall(r"(?:bf16|f32)\[([\d,]+)\]", line)]
        if elements in sizes:
            found.append(line.strip()[:240])
    return found


def _float32_wide_under(text: str, scope: str, rows: int, width: int):
    """The instructions of a compiled module, outside its fused
    computations (what a fusion computes in registers is no array), whose
    path holds `scope` and whose result has a float32 `[.., rows, width]`
    in it: a float32 copy of an array of the mixer's width written to
    memory there."""
    found, fused = [], False
    wide = re.compile(rf"f32\[(?:\d+,)*{rows},{width}\]")
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?(\S+) \(.*\{$", line)
        if head:
            fused = "fused_computation" in head.group(1)
        if fused or " = " not in line or scope not in line:
            continue
        result = line.split(" = ", 1)[1]
        result = (result[:result.index(")") + 1] if result.startswith("(")
                  else result.split(" ", 1)[0])
        if wide.search(result):
            found.append(line.strip()[:240])
    return found


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("cell", SCAN_CELLS)
def test_ssd_scan_compiles_at_the_cells_shapes(v5e, cell, dtype):
    """The chunked state-space scan of the granite-4.0-h-micro cell (one
    sequence of 8192 positions, one group, chunks of 256) and of the
    nemotron_h cell (16384 positions, 8 groups, chunks of 128), 64 heads
    of 64 and state 128 in both: forward and backward kernels, under the
    names a device trace tells them by, given x and the groups' B and C as
    the mixer has them ([S, 4096] and [S, G x 128]: `_mamba`'s reshapes)
    and y read back the same way.  The kernels read those forms, so the
    compiled module holds NO transposed copy of an array of x's size,
    value or gradient: the test that fails if a later change brings one
    back (until PR 48 there were six a layer, a sixth of the scan's
    scope)."""
    from byteps_tpu.ops import ssd
    S, G, chunk = SCAN_CELLS[cell]
    one = SingleDeviceSharding(v5e[0])

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def grads(x, dt, A, B, C, D, w):
        def loss(x, dt, A, B, C, D):
            y = ssd.ssd_scan(
                x.reshape(1, S, 64, 64), dt, A, B.reshape(1, S, G, 128),
                C.reshape(1, S, G, 128), D, chunk=chunk, impl="kernel",
                interpret=False)
            return (y.reshape(1, S, 4096) * w).astype(jnp.float32).sum()
        return jax.grad(loss, tuple(range(6)))(x, dt, A, B, C, D)

    wide = shape(1, S, 4096, dtype=dtype)
    group = shape(1, S, G * 128, dtype=dtype)
    text = _compile(grads, wide, shape(1, S, 64), shape(64), group, group,
                    shape(64), wide).as_text()
    assert f"ssd_fwd_c{chunk}" in text and f"ssd_bwd_c{chunk}" in text
    assert text.count("tpu_custom_call") == 2
    assert not _wide_moves(text, S * 4096)
    # and the benchmark's readers tell the two calls for what they are: a
    # forward kernel that returned y as [1, S, 4096] beside its float32
    # states read as a flash-attention forward call (`attn.roofline` of
    # the nemotron_h cell 131% on the chip, PR 48)
    from benchmark.reduce import afmoe_cost, ssd_cost
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert sorted(ssd_cost.scan_call(c) for c in calls) == [
        ("backward", chunk), ("forward", chunk)]
    assert not any(afmoe_cost.attention_call(c) for c in calls)


def test_wide_moves_finds_a_transposed_copy(v5e):
    """The reader of the test above, on a module that has what it looks
    for: x handed to the scan head-major, as it was until PR 48."""
    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((1, 8192, 4096), jnp.bfloat16, sharding=one)
    text = _compile(lambda x: x.reshape(1, 8192, 64, 64).transpose(
        0, 2, 1, 3).reshape(1, 64, 8192 * 64) * 2, x).as_text()
    assert _wide_moves(text, 8192 * 4096)


def test_flash_64_row_block_is_refused_up_front(v5e):
    """F's decision: the kernel refuses a 64-row Q tile itself, with a
    message that names the rule — because the chip's compiler refuses it
    (shown by going around the check), and only the interpreter ever
    accepted it."""
    with pytest.raises(ValueError, match="multiple of 128"):
        _flash_fwd_bwd(16, 512, 64, 64, None, v5e[0])
    # Around the check: where programs have traced bounds (S = 2048 is
    # two a head) the dK/dV kernel slices the log-sum-exp's lane dim at a
    # group's first key, and Mosaic wants that provably 128-aligned.
    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((16, 2048, 64), jnp.bfloat16, sharding=one)
    lse = jax.ShapeDtypeStruct((16, 1, 2048), jnp.float32, sharding=one)
    with pytest.raises(Exception, match="128"):
        _compile(lambda q, k, v, o, lse, g: fa._bwd(
            0.125, True, 64, 64, False, False, (q, k, v, o, lse), g),
            x, x, x, x, lse, x)
    x = jax.ShapeDtypeStruct((16, 512, 64), jnp.bfloat16, sharding=one)
    # The K tile sits on a sublane dim: 64 rows are fine there.
    _flash_fwd_bwd(16, 512, 64, 128, None, v5e[0])
    _compile(lambda q, k, v: fa.flash_attention(q, k, v, True, None, 128,
                                                64, False), x, x, x)


@pytest.mark.parametrize("n", [1 << 20, 33 * bitpack.GRAN],
                         ids=["1M", "33_tiles"])
@pytest.mark.parametrize("op", ["pack", "unpack"])
def test_bitpack_compiles(v5e, op, n):
    one = SingleDeviceSharding(v5e[0])
    if op == "pack":
        c = _compile(lambda x: bitpack.pack_signs(x, impl="pallas"),
                     jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one))
    else:
        c = _compile(
            lambda w: bitpack.unpack_signs(w, n, impl="pallas"),
            jax.ShapeDtypeStruct((bitpack.words_len(n),), jnp.uint32,
                                 sharding=one))
    assert "tpu_custom_call" in c.as_text()


def _vgg16_leaf_shapes():
    """The 32 leaves of VGG-16 (arXiv:1409.1556 column D): 553 MB of f32,
    411 MB of it the one leaf [25088, 4096]."""
    shapes, cin = {}, 3
    for i, width in enumerate((64, 64, 128, 128, 256, 256, 256,
                               512, 512, 512, 512, 512, 512)):
        shapes[f"conv{i:02d}"] = {"kernel": (3, 3, cin, width),
                                  "bias": (width,)}
        cin = width
    fan_in = 7 * 7 * 512
    for i, width in enumerate((4096, 4096, 1000)):
        shapes[f"fc{i}"] = {"kernel": (fan_in, width), "bias": (width,)}
        fan_in = width
    return shapes


def test_dp4_exchange_leaves_the_gradients_where_they_lie(v5e):
    """The in-graph exchange plus an SGD update over VGG-16's leaves on
    the four described chips, no model: every all-reduce operand keeps its
    leaf's shape and tiling (a flat copy of a tiled [25088, 4096] array is
    different bytes in memory, 411 MB of them), and nothing the size of a
    gradient is concatenated.  Seconds to compile; its own limit is 60."""
    import numpy as np
    import optax
    from jax.sharding import Mesh

    import byteps_tpu as bps

    mesh = Mesh(np.array(v5e), ("dp",))
    opt = bps.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))

    def step(params, opt_state, weight):
        grads = jax.tree.map(lambda p: p * weight[0], params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def on(spec, tree):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    params = jax.tree.map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32),
        _vgg16_leaf_shapes(), is_leaf=lambda x: isinstance(x, tuple))
    leaves = jax.tree.leaves(params)
    assert len(leaves) == 32
    sharded = jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P(), P("dp")),
        out_specs=(P(), P()), check_vma=False)
    began = time.monotonic()
    text = jax.jit(sharded, donate_argnums=(0, 1)).lower(
        on(P(), params), on(P(), jax.eval_shape(opt.init, params)),
        on(P("dp"), jax.ShapeDtypeStruct((4,), jnp.float32))
    ).compile().as_text()
    assert time.monotonic() - began < 60

    metrics = bps.get_metrics()
    assert metrics["bps_ingraph_exchange_leaves"] == 32
    assert metrics["bps_ingraph_exchange_packed_bytes"] == 0

    def elements(dims):
        return math.prod(int(d) for d in dims.split(",") if d)

    vector_leaves = {l.shape[0] for l in leaves if l.ndim == 1}
    smallest_kernel = min(l.size for l in leaves if l.ndim > 1)
    summed = 0
    for line in text.splitlines():
        made = re.match(r"\s*%?[\w.-]+ = (.*?) "
                        r"(all-reduce(?:-start)?|concatenate)\(", line)
        if not made:
            continue
        results = re.findall(r"f32\[([\d,]*)\]", made.group(1))
        if made.group(2) == "concatenate":
            assert all(elements(r) < smallest_kernel for r in results), line
            continue
        for dims in results:
            summed += elements(dims)
            assert "," in dims or elements(dims) in vector_leaves, \
                f"a flat f32[{dims}] is summed: {line[:200]}"
    assert summed == sum(l.size for l in leaves)


def test_bert_large_train_step_compiles(v5e, monkeypatch):
    """One train step through the normal entry points at the flagship's
    full width (chip_smoke.flagship_config), depth cut to 2 layers, per-chip
    batch 64, on a one-device mesh of the described chip: the kernel is in
    the program and the program fits the chip."""
    import optax

    import byteps_tpu as bps
    import chip_smoke
    from byteps_tpu.models import transformer as tfm

    monkeypatch.setattr(fa, "_use_interpret", lambda interpret: False)
    cfg = chip_smoke.flagship_config(num_layers=2)
    mesh = bps.make_mesh(devices=v5e[:1])
    opt = bps.DistributedOptimizer(optax.adamw(1e-4))
    step = bps.build_train_step(lambda p, b: tfm.loss_fn(p, b, cfg), opt,
                                mesh, donate=True)
    params = jax.eval_shape(
        lambda: tfm.init_params(jax.random.key(0), cfg))
    opt_state = jax.eval_shape(opt.init, params)
    batch = jax.eval_shape(lambda: tfm.synthetic_batch(
        jax.random.key(1), chip_smoke.FULL.per_chip_batch,
        chip_smoke.FULL.seq, cfg))

    def on(spec):
        sharding = NamedSharding(mesh, spec)
        return lambda t: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sharding), t)

    compiled = jax.jit(step).lower(on(P())(params), on(P())(opt_state),
                                   on(P("dp"))(batch)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("what", ["select", "attention"])
def test_sparse_attention_compiles_at_keye_shapes(v5e, monkeypatch, what):
    """32 query heads over 4 key-value heads of 128, an indexer of 16
    heads of 64 over one key head, ONE sequence of 32,768 of which a row
    selects 2,048: the keye cell's calls.  `index_topk` holds 128 rows'
    sortable scores against every key in VMEM (16 MiB of scratch, which
    the chip's compiler has to take with the limit the kernel asks for);
    the attention kernels walk the table of causal tiles, 128 rows by 512
    keys, all heads a step, under the names the trace's readers know them
    by (`benchmark/reduce/sparse_cost.py`)."""
    from benchmark.reduce import sparse_cost
    from byteps_tpu.ops import sparse_attention as sa
    monkeypatch.setattr(fa, "_use_interpret", lambda interpret: False)
    one = SingleDeviceSharding(v5e[0])

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    S = 32768
    assert sa.auto_blocks(S) == (128, 512)
    q, kv = shape(1, 32, S, 128), shape(1, 4, S, 128)
    qi, kit = shape(1, 16, S, 64), shape(1, 64, S)
    w = shape(1, S, 16, dtype=jnp.float32)
    aux = shape(1, S, sa.AUX_LANES, dtype=jnp.float32)
    if what == "select":
        text = _compile(lambda qi, kit, w: sa.select(qi, kit, w, 2048),
                        qi, kit, w).as_text()
        names = {"select"}
    else:
        def grads(q, k, v, qi, kit, aux):
            def loss(q, k, v):
                out, _ = sa.sparse_attention(q, k, v, qi, kit, aux)
                return jnp.sum(out.astype(jnp.float32))
            return jax.grad(loss, (0, 1, 2))(q, k, v)
        text = _compile(grads, q, kv, kv, qi, kit, aux).as_text()
        names = {"forward", "dq", "dkv"}
        # the table is in the calls: one entry a causal tile
        assert text.count("s32[8320]") >= 9
    # a bare `jax.grad` puts `jvp_` and `transpose_` before a call's
    # name, which a train step's remat does not
    calls = [line.strip().removeprefix("ROOT ").replace("%transpose_", "%")
             .replace("%jvp_", "%") for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert {sparse_cost.kernel(line) for line in calls} == names, calls


# 69 s beside five other workers (PR 59); the keye cell's set-up compiles
# this step on the chip in every check of every PR (`first_setup_s`).
@pytest.mark.slow
def test_keye_train_step_compiles_at_the_cells_shapes(v5e, monkeypatch):
    """The keye cell's step (`benchmark/configs/keye-vl-2.0-30b-a3b.json`:
    four layers, one sequence of 32,768, a plain `value_and_grad` and
    adamw) for one described chip: every attention kernel inside the
    limit it asks of VMEM, the backward kernels fed the forward kernel's
    words ([1, 32768, 1024] int32, 134 MB a layer) and not the indexer's
    operands, and every kernel ONCE a layer: the scan bodies hold `dkv`,
    `dq`, `forward`, `select`, because the layer's remat policy keeps by
    name what selection and forward kernel made (a second `forward` was
    there until PR 45); and the program's peak inside the chip's memory
    with the four layers' 1.63 GB of `o`, `lse` and `bits` in its
    stacks."""
    import json

    import optax

    from benchmark.families import keye as family_keye
    from benchmark.harness import manifest
    from benchmark.reduce import sparse_cost
    from byteps_tpu.ops import sparse_attention as sa
    monkeypatch.setattr(fa, "_use_interpret", lambda interpret: False)
    with open(os.path.join(manifest.BENCH, "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        config = json.load(f)
    family = family_keye.Family(config, config["job"])
    opt = family.optimizer()
    one = SingleDeviceSharding(v5e[0])

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(family.loss)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)
    params = jax.eval_shape(family.init, jax.random.key(0))
    opt_state = jax.eval_shape(opt.init, params)
    batch = jax.eval_shape(lambda k: family.make_batch(k, 1),
                           jax.random.key(0))
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(opt_state), on_chip(batch)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    kinds = [sparse_cost.kernel(line.strip().removeprefix("ROOT "))
             for line in calls]
    # a scan over the layers: its body holds a layer's calls once
    assert sorted(k for k in kinds if k) == ["dkv", "dq", "forward",
                                             "select"]
    _assert_rows_move_by_kernel(text, config["hidden_size"],
                                config["num_experts_per_tok"])
    words = f"s32[1,32768,{sa.words(32768)}]"
    for line, kind in zip(calls, kinds):
        operands = line.split(" custom-call(", 1)[1]
        if kind in ("dq", "dkv"):
            assert words in operands, line
            assert "bf16[1,16,32768,64]" not in operands, line
        if kind == "forward":
            assert words in line.split(" custom-call(", 1)[0], line
    # The compiler's peak plus its code is what tells the chip's
    # `memory_peak_bytes`: PR 44's step 12,667,947,520 + 84e6 here and
    # 13,047,652,864 measured; this step 14,164,443,136 + 80e6 here and
    # 14,254,139,392 measured (my chip runs, PR 45).  `argument_size_in_bytes +
    # temp_size_in_bytes`, which this line held under 16.9e9 until PR 45,
    # reads 16.63e9 and 19.02e9 for the two: 3.6e9 over what the chip
    # measured for the first, and no bound on what fits.
    mem = compiled.memory_analysis()
    assert (mem.peak_memory_in_bytes
            + mem.generated_code_size_in_bytes) < 15.5e9


def _nemotronh_family(layers):
    import json

    from benchmark.families import nemotronh as family_nemotronh
    from benchmark.harness import manifest
    with open(os.path.join(manifest.BENCH, "configs",
                           "nemotron-labs-twotower-30b-a3b-base.json")) as f:
        config = json.load(f)
    config["held"].update(layers=layers, num_hidden_layers=len(layers))
    return family_nemotronh.Family(config, config["job"])


def _nemotronh_step(layers, device):
    """The cell's step cut to `layers`, a plain `value_and_grad` and
    adamw, jitted, and its arguments' shapes on `device`."""
    import optax
    family = _nemotronh_family(layers)
    opt = family.optimizer()
    one = SingleDeviceSharding(device)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(family.loss)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)
    params = jax.eval_shape(family.init, jax.random.key(0))
    opt_state = jax.eval_shape(opt.init, params)
    batch = jax.eval_shape(lambda k: family.make_batch(k, 1),
                           jax.random.key(0))
    return jax.jit(step, donate_argnums=(0, 1)), (
        on_chip(params), on_chip(opt_state), on_chip(batch))


@pytest.mark.parametrize("layers", [
    [4, 5],
    # 40-63 s beside five other workers (PR 59); the nemotron cell's
    # set-up compiles this step on the chip in every check of every PR
    pytest.param([6], marks=pytest.mark.slow),
], ids=["mixer_and_attention", "expert_layer"])
def test_nemotronh_train_step_compiles_at_the_cells_shapes(v5e, monkeypatch,
                                                           layers):
    """The nemotron_h cell's step
    (`benchmark/configs/nemotron-labs-twotower-30b-a3b-base.json`: one
    sequence of 16,384, a plain `value_and_grad` and adamw, embedding and
    head included) for one described chip, in two parts of the cell's
    nine layers, because the twelve grouped kernels at width 1856 alone
    take Mosaic 25 s (the nine layers compile in 40 s alone and in 80
    beside five other workers, over a test's budget): layers 4 and 5, `M*`
    (the scan's kernels `ssd_fwd_c128` / `ssd_bwd_c128` with 128 chunks of
    state, the flash kernels on 32 heads), and layer 6, `E` (BOTH products
    of an expert at width 1856 = 14.5 x 128 are the program's own kernels
    under the names the trace's readers know, none of the compiler's
    `ragged-dot` is left).  Arguments and temporaries as the compiler
    counts them are printed.  The whole cell, compiled the same way
    (PR 47): arguments 8,003,700,736, temporaries 6,225,738,752, peak
    13,995,448,832 + 257,163,264 of code; the chip measured
    `memory_peak_bytes` 14,370,686,464."""
    from benchmark.reduce import afmoe_cost, ssd_cost
    from byteps_tpu.ops import ssd
    monkeypatch.setattr(fa, "_use_interpret", lambda interpret: False)
    monkeypatch.setattr(ssd, "_use_interpret", lambda interpret: False)
    step, args = _nemotronh_step(layers, v5e[0])
    t0 = time.perf_counter()
    compiled = step.lower(*args).compile()
    seconds = time.perf_counter() - t0
    text = compiled.as_text()
    calls = [line.strip().removeprefix("ROOT ")
             for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    scans = sorted(c for c in map(ssd_cost.scan_call, calls) if c)
    grouped = [afmoe_cost.grouped_call(c) for c in calls
               if afmoe_cost.is_grouped(c)]
    flash = sorted(f[:4] for f in map(afmoe_cost.attention_call, calls) if f)
    if layers == [6]:
        # two products forward, again under remat, and their two gradients
        # each, in the first buffer and in the exact path's loop
        assert len(grouped) == 2 * (2 + 2 + 2 * 2)
        assert set(grouped) == {(8, 2688, 1856), (8, 1856, 2688)}
        assert "ragged-dot-metadata" not in text
        assert not scans and not flash
        _assert_rows_move_by_kernel(text, 2688, 6)
    else:
        # the scan's forward kernel, again under remat, and its backward;
        # the flash forward kernel ONCE: the layer keeps its `o` and `lse`
        assert scans == [("backward", 128)] + [("forward", 128)] * 2
        assert "f32[1,64,128,64,128]" in text       # 128 chunks of state
        assert flash == [("dkv", 32, 16384, 128), ("dq", 32, 16384, 128),
                         ("forward", 32, 16384, 128)]
        assert not grouped
        # the gated norm by the scan's groups: the forward kernel, again
        # under remat, and the backward one, and no float32 copy of the
        # inner width written round them
        assert sorted(c.split(" = ")[0].split(".")[0] for c in calls
                      if c.startswith("%gated_norm_")) == [
            "%gated_norm_bwd", "%gated_norm_fwd", "%gated_norm_fwd"]
        assert not _float32_wide_under(text, "gate_norm", 16384, 4096)
    mem = compiled.memory_analysis()
    said = (f"arguments {mem.argument_size_in_bytes:,} temporaries "
            f"{mem.temp_size_in_bytes:,} peak {mem.peak_memory_in_bytes:,} "
            f"code {mem.generated_code_size_in_bytes:,} compiled in "
            f"{seconds:.0f} s")
    print(said)
    # (the nine layers' arguments are 8.0e9 of the chip's 16.9e9)
    assert mem.temp_size_in_bytes < 7.0e9, said


def test_nemotronh_four_unrolled_expert_layers_share_the_move_kernels(
        v5e, monkeypatch):
    """The nemotron_h step cut to its four `E` layers (1, 3, 6, 8), which
    the model UNROLLS, each under its own `jax.checkpoint`: traced and
    lowered, not compiled.  The row-move kernel's `tpu_custom_call`
    bodies in the lowered module number what ONE layer's module holds,
    not four times that, and their distinct texts (name, operands and
    result) are what the process traced (`bps_moe_move_texts`): a kernel's
    call under a plain `jax.jit` is one body a shape, shared by the
    layers, and a run's set-up pays its tracing and lowering once
    (`ops/moe_rows.py`; PR 51's kernel, unshared, cost the cell 20 s).
    Prints the seconds of tracing and lowering."""
    import byteps_tpu as bps
    from byteps_tpu.ops import moe_rows, ssd
    monkeypatch.setattr(fa, "_use_interpret", lambda interpret: False)
    monkeypatch.setattr(ssd, "_use_interpret", lambda interpret: False)

    def lowered(layers):
        step, args = _nemotronh_step(layers, v5e[0])
        t0 = time.perf_counter()
        traced = step.trace(*args)
        t1 = time.perf_counter()
        text = traced.lower().as_text()
        return text, t1 - t0, time.perf_counter() - t1

    def bodies(text):
        """`(kernel's name, its operands' and result's types)` of every
        move-kernel body in a lowered module."""
        return [(m.group(1), line[line.rfind(" : "):])
                for line in text.splitlines()
                if "stablehlo.custom_call @tpu_custom_call" in line
                for m in [re.search(r'kernel_name = "(moe_rows_[^"]+)"',
                                    line)] if m]

    one_layer = bodies(lowered([6])[0])
    traced = moe_rows.texts()
    text, trace_s, lower_s = lowered([1, 3, 6, 8])
    four_layers = bodies(text)
    print(f"four E layers: traced in {trace_s:.2f} s, lowered in "
          f"{lower_s:.2f} s, {len(four_layers)} move-kernel bodies of "
          f"{len(set(four_layers))} texts, "
          f"{text.count('stablehlo.custom_call @tpu_custom_call')} kernel "
          f"bodies in all, {len(text) // 1000}k characters")
    # three more layers traced no body the one had not
    assert moe_rows.texts() == traced >= len(set(one_layer))
    assert bps.get_metrics()["bps_moe_move_texts"] == traced
    # rows in, results back, the rows' gradient back; the first buffer and
    # the exact path's
    assert len(set(four_layers)) == 6, set(four_layers)
    assert sorted(four_layers) == sorted(one_layer)
    assert {n for n, _ in four_layers} == {"moe_rows_k1", "moe_rows_k6w",
                                           "moe_rows_k6"}


def test_nemotronh_four_unrolled_mixers_share_the_convolutions_kernels(
        v5e, monkeypatch):
    """The nemotron_h step cut to its four `M` layers (0, 2, 4, 7), which
    the model UNROLLS, each under its own `jax.checkpoint`: traced and
    lowered, not compiled.  The convolution's calls sit under a plain
    `jax.jit` (`ops/short_conv.py` `_mamba_fwd_call` / `_mamba_bwd_call`),
    so the lowered module holds the forward kernel's body once for the
    forward pass and once for the recompute and the backward kernel's
    once, for four layers as for one, and a run's set-up pays their
    tracing and lowering once (ROADMAP.md S6); so do the gated norm's
    (`ops/gated_norm.py` `_fwd_call` / `_bwd_call`, PR 60).  Prints the
    seconds beside the parent's (PR 59's tree, the norm's jnp form, this
    sandbox, second call: traced in 0.42 s, lowered in 0.41 s; this tree
    read 0.34 and 0.37.  PR 55's tree, the convolution's jnp form too:
    0.74 and 0.42)."""
    from byteps_tpu.ops import ssd
    monkeypatch.setattr(fa, "_use_interpret", lambda interpret: False)
    monkeypatch.setattr(ssd, "_use_interpret", lambda interpret: False)

    def lowered(layers):
        step, args = _nemotronh_step(layers, v5e[0])
        t0 = time.perf_counter()
        traced = step.trace(*args)
        t1 = time.perf_counter()
        text = traced.lower().as_text()
        return text, t1 - t0, time.perf_counter() - t1

    def bodies(text):
        return sorted(re.findall(
            r'kernel_name = "((?:mamba_conv|gated_norm)_[a-z]+)"', text))
    one_layer = bodies(lowered([4])[0])
    text, trace_s, lower_s = lowered([0, 2, 4, 7])
    print(f"four M layers: traced in {trace_s:.2f} s, lowered in "
          f"{lower_s:.2f} s (the parent's 0.42 and 0.41), "
          f"{bodies(text)} kernel bodies, {len(text) // 1000}k characters")
    assert bodies(text) == one_layer == [
        "gated_norm_bwd", "gated_norm_fwd", "gated_norm_fwd",
        "mamba_conv_bwd", "mamba_conv_fwd", "mamba_conv_fwd"]


@pytest.mark.parametrize("cell,seq_len,inner,state", [
    ("granite", 8192, 4096, 128), ("nemotron", 16384, 4096, 1024)])
def test_mamba_conv_compiles_at_the_cells_shapes(v5e, cell, seq_len, inner,
                                                 state):
    """`ops/short_conv.py` `mamba_conv` at [1, 8192, 4352] and
    [1, 16384, 6144] bfloat16, x, B and C as three results, forward and
    backward: two Mosaic calls under their names, every result 2-D, and no
    reader of the benchmark's takes either for a kernel of its own (the
    gated convolution's, the scan's, a flash call); the reshape round them
    free and no temporary beside them."""
    from benchmark.reduce import afmoe_cost, conv_cost, flash_cost, ssd_cost
    from byteps_tpu.ops import short_conv
    one = SingleDeviceSharding(v5e[0])
    width = inner + 2 * state
    parts = (inner, state, state)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def both(x, w, b, gs):
        ys, vjp = jax.vjp(lambda *a: short_conv.mamba_conv(
            *a, parts=parts, interpret=False), x, w, b)
        return ys, vjp(gs)
    compiled = _compile(
        both, shape(1, seq_len, width), shape(4, width, dtype=jnp.float32),
        shape(width, dtype=jnp.float32),
        tuple(shape(1, seq_len, p) for p in parts))
    calls = [line.strip().removeprefix("ROOT ")
             for line in compiled.as_text().splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert sorted(c.split(" = ")[0].lstrip("%").split(".")[0]
                  for c in calls) == ["mamba_conv_bwd", "mamba_conv_fwd"]
    for call in calls:
        assert conv_cost.call(call) is None
        assert ssd_cost.scan_call(call) is None
        assert flash_cost.classify(call) is None
        assert not afmoe_cost.attention_call(call)
        results = call.split(" custom-call(")[0]
        assert not re.search(r"\[\d+,\d+,\d+", results), results
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("cell,seq_len,groups,gate", [
    ("kimi", 32768, 32, "sigmoid_after"),
    ("nemotron", 16384, 8, "silu_before")])
def test_gated_norm_compiles_at_the_cells_shapes(v5e, cell, seq_len, groups,
                                                 gate):
    """`ops/gated_norm.py` `gated_norm` at [1, 32768, 4096] in 32 heads of
    128 (kimi: the norm, then the sigmoid) and [1, 16384, 4096] in 8
    groups of 512 (nemotron: the silu, then the norm), bfloat16, forward
    and backward: two Mosaic calls under their names, every result 2-D,
    and no reader of the benchmark's takes either for a kernel of its own
    (a flash call, the scans', the convolutions'); the reshape round them
    free and, but for the partial sums of the scale's gradient, no
    temporary beside them."""
    from benchmark.reduce import (afmoe_cost, conv_cost, flash_cost,
                                  kda_cost, mla_cost, ssd_cost)
    from byteps_tpu.ops import gated_norm
    one = SingleDeviceSharding(v5e[0])
    width = 4096

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def both(x, z, scale, g):
        y, vjp = jax.vjp(lambda *a: gated_norm.gated_norm(
            *a, groups=groups, gate=gate, eps=1e-5, interpret=False),
            x, z, scale)
        return y, vjp(g)
    wide = shape(1, seq_len, width)
    compiled = _compile(both, wide, wide, shape(width, dtype=jnp.float32),
                        wide)
    text = compiled.as_text()
    calls = [line.strip().removeprefix("ROOT ")
             for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert sorted(c.split(" = ")[0].lstrip("%").split(".")[0]
                  for c in calls) == ["gated_norm_bwd", "gated_norm_fwd"]
    for call in calls:
        assert flash_cost.classify(call) is None
        assert ssd_cost.scan_call(call) is None
        assert kda_cost.call(call) is None
        assert conv_cost.call(call) is None
        assert mla_cost.call(call) is None
        assert not afmoe_cost.attention_call(call)
        results = call.split(" custom-call(")[0]
        assert not re.search(r"\[\d+,\d+,\d+", results), results
    assert not _float32_wide_under(text, "gated_norm", seq_len, width)
    # [8, W] float32 a block of 256 rows
    partials = seq_len // 256 * 8 * width * 4
    assert compiled.memory_analysis().temp_size_in_bytes <= partials + (
        1 << 20)


@pytest.mark.parametrize("cell,batch,seq_len,width,first,heads,tables", [
    ("sdar_q", 1, 32768, 5120, 0, 32, "rows"),
    ("sdar_k", 1, 32768, 5120, 32, 4, "rows"),
    ("mellum_q", 4, 8192, 5120, 0, 32, "rows"),
    ("trinity_k_sliding", 4, 8192, 9216, 32, 4, "rows"),
    ("trinity_q_full", 4, 8192, 9216, 0, 32, None),
    ("positions_a_sequence", 4, 8192, 5120, 32, 4, "sequences"),
    ("ouro_q_turn_alone", 1, 8192, 6144, 0, 16, "rows"),
    ("ouro_k_turn_alone", 1, 8192, 6144, 16, 16, "rows")])
def test_head_norm_rope_compiles_at_the_cells_shapes(
        v5e, cell, batch, seq_len, width, first, heads, tables):
    """`ops/head_norm_rope.py` `head_norm_rope` on the projection's whole
    result, bfloat16, as the sdar, mellum2 and trinity-mini cells call it
    (32 query heads of 128 from lane 0, 4 key heads behind them; with
    tables a row, a row and sequence, or none), forward and backward: two
    Mosaic calls under their names, the forward result 4-D and the
    backward ones 2-D, and no reader of the benchmark's takes either for a
    kernel of its own; no float32 copy of the heads beside them.  The ouro
    cell's calls (PR 65: 16 query heads and 16 key heads behind them, NO
    scale: the turn alone) the same, their backward call given the
    cotangent and the tables and no operand as wide as the projection's
    result."""
    from benchmark.reduce import (afmoe_cost, conv_cost, flash_cost,
                                  kda_cost, mla_cost, ssd_cost)
    from byteps_tpu.ops import head_norm_rope
    one = SingleDeviceSharding(v5e[0])

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    table = {None: (), "rows": (shape(seq_len, 64, dtype=jnp.float32),) * 2,
             "sequences": (shape(batch, seq_len, 64, dtype=jnp.float32),) * 2
             }[tables]

    normed = not cell.endswith("turn_alone")

    def both(t, scale, g, *cs):
        y, vjp = jax.vjp(lambda t, scale: head_norm_rope.head_norm_rope(
            t, scale if normed else None, *cs, eps=1e-6, first=first,
            heads=heads, interpret=False), t, scale)
        return y, vjp(g)
    compiled = _compile(both, shape(batch, seq_len, width), shape(128),
                        shape(batch, heads, seq_len, 128), *table)
    text = compiled.as_text()
    calls = [line.strip().removeprefix("ROOT ")
             for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert sorted(c.split(" = ")[0].lstrip("%").split(".")[0]
                  for c in calls) == ["head_norm_rope_bwd",
                                      "head_norm_rope_fwd"]
    for call in calls:
        assert flash_cost.classify(call) is None
        assert ssd_cost.scan_call(call) is None
        assert kda_cost.call(call) is None
        assert conv_cost.call(call) is None
        assert mla_cost.call(call) is None
        assert not afmoe_cost.attention_call(call)
        results = call.split(" custom-call(")[0]
        assert not re.search(r"\[\d+,\d+,\d+\]", results), results
        if not normed and call.startswith("%head_norm_rope_bwd"):
            assert f"{width}]" not in call.split("custom_call_target")[0]
    rows = batch * seq_len
    assert not re.search(rf"f32\[({rows}|{batch},{seq_len}),"
                         rf"({heads * 128}|{width})\]", text)
    # `dt` before it is padded to the projection's width, and the tables
    # a head wide
    mem = compiled.memory_analysis()
    print(cell, f"temporaries {mem.temp_size_in_bytes:,}")
    assert mem.temp_size_in_bytes <= (
        rows * heads * 128 * 2 + 4 * rows * 128 * 4 + (1 << 20))


# 58 and 90 s beside five other workers (PR 59); the joyai cell's set-up
# compiles this step on the chip in every check of every PR.
@pytest.mark.slow
@pytest.mark.parametrize("layers,modules", [([0], 1), ([1, 2, 3, 4], 0)],
                         ids=["dense_layer_and_module", "expert_layers"])
def test_joyai_train_step_compiles_at_the_cells_shapes(v5e, monkeypatch,
                                                       layers, modules):
    """The joyai cell's step (`benchmark/configs/joyai-llm-flash.json`: one
    sequence of 16,384, a plain `value_and_grad` and adamw, embedding,
    head and the cross-entropies included) for one described chip, in two
    parts of the cell, because the three layer bodies together (the dense
    layer's, the scan's, the module's) take a minute to compile alone and
    two beside five other workers, over a test's budget: layer 0 with the
    prediction module, and the four expert layers under their one scan.
    Every attention call is the STREAMING kernels with queries and keys
    192 wide and values 128, under names that say so; a rematerialised
    layer keeps its call's `o` and `lse`, so the forward kernel is
    compiled ONCE a layer body; the experts' three products at width 768
    are the program's own kernels.  The whole cell, compiled the same way
    (PR 50): arguments 8,165,415,424, temporaries 11,366,176,768, peak
    15,665,868,800 + 109,260,288 of code (the compiler fills the chip it
    is given); with two sequences a step it needs 15.84 GiB of 15.75 and
    does not compile; the chip measured `memory_peak_bytes`
    15,599,556,096."""
    import json

    import optax

    from benchmark.families import joyai as family_joyai
    from benchmark.harness import manifest
    from benchmark.reduce import afmoe_cost
    monkeypatch.setattr(fa, "_use_interpret", lambda interpret: False)
    with open(os.path.join(manifest.BENCH, "configs",
                           "joyai-llm-flash.json")) as f:
        config = json.load(f)
    config["held"].update(layers=layers, num_hidden_layers=len(layers),
                          num_nextn_predict_layers=modules)
    family = family_joyai.Family(config, config["job"])
    opt = family.optimizer()
    one = SingleDeviceSharding(v5e[0])

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(family.loss)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)
    params = jax.eval_shape(family.init, jax.random.key(0))
    opt_state = jax.eval_shape(opt.init, params)
    batch = jax.eval_shape(
        lambda k: family.make_batch(k, config["job"]["per_chip_batch"]),
        jax.random.key(0))
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(opt_state), on_chip(batch)).compile()
    seconds = time.perf_counter() - t0
    text = compiled.as_text()
    calls = [line.strip().removeprefix("ROOT ")
             for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    named = sorted(re.findall(r"flash_[a-z]+_d192x128", " ".join(
        c.split(" = ")[0] for c in calls)))
    bodies = 1 + modules            # the dense layer's and the module's;
    assert named == sorted(         # the scan's one
        ["flash_fwd_d192x128", "flash_dq_d192x128",
         "flash_dkv_d192x128"] * bodies), named
    grouped = [afmoe_cost.grouped_call(c) for c in calls
               if afmoe_cost.is_grouped(c)]
    assert set(grouped) == {(16, 2048, 768), (16, 768, 2048)}
    assert "ragged-dot-metadata" not in text
    _assert_rows_move_by_kernel(text, 2048, 8)
    mem = compiled.memory_analysis()
    said = (f"arguments {mem.argument_size_in_bytes:,} temporaries "
            f"{mem.temp_size_in_bytes:,} peak {mem.peak_memory_in_bytes:,} "
            f"code {mem.generated_code_size_in_bytes:,} compiled in "
            f"{seconds:.0f} s")
    print(said)
    assert mem.peak_memory_in_bytes < 16.9e9, said


def test_gated_short_conv_compiles_at_the_cells_shape(v5e):
    """`ops/short_conv.py` at [4, 8192, 3 x 2048] bfloat16, forward and
    backward: two Mosaic calls under their names, every result 2-D (what
    keeps the benchmark's attention readers off them), the reshape round
    them free and no temporary beside them."""
    from benchmark.reduce import afmoe_cost, conv_cost
    from byteps_tpu.ops import short_conv
    one = SingleDeviceSharding(v5e[0])
    bcx = jax.ShapeDtypeStruct((4, 8192, 6144), jnp.bfloat16, sharding=one)
    taps = jax.ShapeDtypeStruct((3, 2048), jnp.float32, sharding=one)
    g = jax.ShapeDtypeStruct((4, 8192, 2048), jnp.bfloat16, sharding=one)

    def both(bcx, taps, g):
        y, vjp = jax.vjp(lambda a, b: short_conv.gated_short_conv(
            a, b, interpret=False), bcx, taps)
        return y, vjp(g)
    compiled = _compile(both, bcx, taps, g)
    calls = [line.strip().removeprefix("ROOT ")
             for line in compiled.as_text().splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert sorted(map(conv_cost.call, calls)) == [
        ("bwd", 32768, 2048), ("fwd", 32768, 2048)]
    assert not any(afmoe_cost.attention_call(c) for c in calls)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# 72 and 62 s beside five other workers (PR 59); the lfm2 cell's set-up
# compiles this step on the chip in every check of every PR.
@pytest.mark.slow
@pytest.mark.parametrize("layers", [[1, 2], [3, 4, 5]],
                         ids=["dense_conv_and_attention", "three_convs_run"])
def test_lfm2_train_step_compiles_at_the_cells_shapes(v5e, monkeypatch,
                                                      layers):
    """The lfm2 cell's step (`benchmark/configs/lfm2-24b-a2b.json`: four
    sequences of 8,192, a plain `value_and_grad` and adamw, embedding,
    tied head and cross-entropy included) for one described chip, in two
    parts of the cell, because its five runs' bodies take 50 s to compile
    alone and more beside five other workers: the dense conv layer with
    the first attention layer, and the run of three conv expert layers
    under its one scan.  A conv layer is the two `short_conv` calls,
    the forward one made once again under remat; an attention layer the
    RESIDENT flash kernels at head size 64, its `o` and `lse` kept; the
    experts' products at width 1536 the program's own kernels.  The whole
    cell, compiled the same way (PR 55): arguments 7,774,222,848,
    temporaries 10,156,730,368, peak 15,573,386,752 + 76,397,056 of code,
    of 16.91e9."""
    import json

    import optax

    from benchmark.families import lfm2 as family_lfm2
    from benchmark.harness import manifest
    from benchmark.reduce import afmoe_cost, conv_cost
    monkeypatch.setattr(fa, "_use_interpret", lambda interpret: False)
    with open(os.path.join(manifest.BENCH, "configs",
                           "lfm2-24b-a2b.json")) as f:
        config = json.load(f)
    dense = sum(i < 2 for i in layers)
    config["held"].update(layers=layers, num_hidden_layers=len(layers),
                          num_dense_layers=dense)
    family = family_lfm2.Family(config, config["job"])
    opt = family.optimizer()
    one = SingleDeviceSharding(v5e[0])

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(family.loss)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)
    params = jax.eval_shape(family.init, jax.random.key(0))
    opt_state = jax.eval_shape(opt.init, params)
    batch = jax.eval_shape(
        lambda k: family.make_batch(k, config["job"]["per_chip_batch"]),
        jax.random.key(0))
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(opt_state), on_chip(batch)).compile()
    seconds = time.perf_counter() - t0
    text = compiled.as_text()
    calls = [line.strip().removeprefix("ROOT ")
             for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    conv = sorted(c[0] for c in map(conv_cost.call, calls) if c)
    # one body a run that has the mixer: forward, its recompute, backward
    assert conv == ["bwd", "fwd", "fwd"], conv
    flash = sorted(c for c in map(afmoe_cost.attention_call, calls) if c)
    assert flash == [(kind, 128, 8192, 64, None) for kind in (
        "dkv", "dq", "forward")] * (2 in layers), flash
    grouped = {afmoe_cost.grouped_call(c) for c in calls
               if afmoe_cost.is_grouped(c)}
    assert grouped == {(8, 2048, 1536), (8, 1536, 2048)}
    assert "ragged-dot-metadata" not in text
    _assert_rows_move_by_kernel(text, 2048, 4)
    mem = compiled.memory_analysis()
    said = (f"arguments {mem.argument_size_in_bytes:,} temporaries "
            f"{mem.temp_size_in_bytes:,} peak {mem.peak_memory_in_bytes:,} "
            f"code {mem.generated_code_size_in_bytes:,} compiled in "
            f"{seconds:.0f} s")
    print(said)
    assert mem.peak_memory_in_bytes < 16.9e9, said


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_kda_scan_compiles_at_the_cells_shape(v5e, dtype):
    """The delta-rule scan of the kimi-linear cell (one sequence of 32,768
    positions, 32 heads of 128, chunks of 64): forward and backward
    kernels under the names a device trace tells them by, given q, k, v
    and g as the mixer has them ([1, S, 4096]) and o read back the same
    way.  The compiled module holds NO transposed copy of an array of
    that size, value or gradient (beta's [S, 32] is the one operand laid
    out anew), and none of the accepted readers takes the two calls for
    its own."""
    from benchmark.reduce import afmoe_cost, conv_cost, kda_cost, ssd_cost
    from byteps_tpu.ops import kda
    S, W, H = 32768, 4096, 32
    one = SingleDeviceSharding(v5e[0])

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def grads(q, k, v, g, beta, w):
        def loss(q, k, v, g, beta):
            o = kda.kda_scan(q, k, v, g, beta, interpret=False)
            return (o * w).astype(jnp.float32).sum()
        return jax.grad(loss, tuple(range(5)))(q, k, v, g, beta)

    wide = shape(1, S, W, dtype=dtype)
    compiled = _compile(grads, wide, wide, wide, shape(1, S, W),
                        shape(1, S, H), wide)
    text = compiled.as_text()
    assert "kda_fwd_c64" in text and "kda_bwd_c64" in text
    assert text.count("tpu_custom_call") == 2
    assert not _wide_moves(text, S * W)
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert sorted(kda_cost.call(c) for c in calls) == [("bwd", 64),
                                                       ("fwd", 64)]
    assert not any(afmoe_cost.attention_call(c) or ssd_cost.scan_call(c)
                   or conv_cost.call(c) for c in calls)
    # the chunk states and little else: 32 x 512 x 64 KB
    states = kda.state_bytes(1, H, S, 128, 128)
    assert states == 1 << 30
    assert compiled.memory_analysis().temp_size_in_bytes < states + (64 << 20)


def test_kimilinear_train_step_compiles_at_the_cells_shapes(v5e, monkeypatch):
    """The kimi-linear cell's step
    (`benchmark/configs/kimi-linear-48b-a3b-instruct.json`: ONE sequence
    of 32,768, a plain `value_and_grad` and adamw, embedding, untied head
    and cross-entropy included) for one described chip, cut to the model's
    layer 1 (KDA, the dense SwiGLU): the new mixer whole, in 20 s alone
    (the cell's three runs take 55 s to compile alone and 100 beside five
    other workers; its latent-attention layer is the joyai cell's calls at
    twice the length, its experts the other cells' kernels at width
    1024).  The KDA layer is the scan's two calls and the convolution's
    two, the forward ones made once again under remat (nothing of the
    layer is kept), and NO transposed or re-tiled copy of an array of q's
    size lies round them (a reshape of the scan's result to heads for its
    norm made three a layer: `kimi_linear._gate_norm`).  The whole cell,
    compiled the same way (PR 57): arguments 7,229,584,896, temporaries
    9,255,834,624, peak 15,749,446,656 + 97,150,464 of code, of 16.91e9;
    at 16,384 positions peak 14,127,934,976."""
    import json

    import optax

    from benchmark.families import kimilinear
    from benchmark.harness import manifest
    from benchmark.reduce import kda_cost
    from byteps_tpu.ops import ssd
    monkeypatch.setattr(fa, "_use_interpret", lambda interpret: False)
    monkeypatch.setattr(ssd, "_use_interpret", lambda interpret: False)
    with open(os.path.join(manifest.BENCH, "configs",
                           "kimi-linear-48b-a3b-instruct.json")) as f:
        config = json.load(f)
    config["held"].update(layers=[1], num_hidden_layers=1,
                          layer_kinds=["kda"])
    family = kimilinear.Family(config, config["job"])
    opt = family.optimizer()
    one = SingleDeviceSharding(v5e[0])

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(family.loss)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)
    params = jax.eval_shape(family.init, jax.random.key(0))
    opt_state = jax.eval_shape(opt.init, params)
    batch = jax.eval_shape(
        lambda k: family.make_batch(k, config["job"]["per_chip_batch"]),
        jax.random.key(0))
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(opt_state), on_chip(batch)).compile()
    seconds = time.perf_counter() - t0
    text = compiled.as_text()
    calls = [line.strip().removeprefix("ROOT ")
             for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    # forward, its recompute, backward
    scan = sorted(c[0] for c in map(kda_cost.call, calls) if c)
    assert scan == ["bwd", "fwd", "fwd"], scan
    # the backward call's chunk takes the solve's gradient from the
    # inverse: the ten doubling products made again and two more
    import byteps_tpu as bps
    assert bps.get_metrics()["bps_kda_bwd_solve_products"] == 12
    # four heads of a chunk a program forward, two backward (PR 63)
    heads = 'bps_kda_heads_per_program{call="%s"}'
    assert [bps.get_metrics()[heads % c] for c in ("fwd", "bwd")] == [4, 2]
    assert len([c for c in calls if c.startswith("%mamba_conv_")]) == 3
    # the head norm and the output gate: the forward kernel, again under
    # remat, the backward one, and no float32 copy of the heads' width
    # written round them (the jnp form wrote several a pass)
    assert sorted(c.split(" = ")[0].split(".")[0] for c in calls
                  if c.startswith("%gated_norm_")) == [
        "%gated_norm_bwd", "%gated_norm_fwd", "%gated_norm_fwd"]
    assert not _float32_wide_under(text, "gate_norm", 32768, 4096)
    assert len(calls) == 9
    assert not _wide_moves(text, 32768 * 4096)
    mem = compiled.memory_analysis()
    said = (f"arguments {mem.argument_size_in_bytes:,} temporaries "
            f"{mem.temp_size_in_bytes:,} peak {mem.peak_memory_in_bytes:,} "
            f"code {mem.generated_code_size_in_bytes:,} compiled in "
            f"{seconds:.0f} s")
    print(said)
    assert mem.peak_memory_in_bytes < 16.9e9, said


# 40 s alone; the sdar cell's set-up compiles this step on the chip in
# every check of every PR (`first_setup_s`).
@pytest.mark.slow
def test_sdar_train_step_compiles_at_the_cells_shapes(v5e, monkeypatch):
    """The sdar cell's step (`benchmark/configs/sdar-30b-a3b-chat.json`:
    six layers, ONE sequence of 16,384 tokens as 32,768 rows, a plain
    `value_and_grad` and adamw over 645,623,296 parameters) for one
    described chip, at SIX layers: a scan over the layers whose body holds
    the masked forward kernel twice (whole-layer remat: `remat_policy`
    none), dQ and dK/dV once, the q / k kernel of
    `ops/head_norm_rope.py` (PR 62: forward twice, backward once, for the
    queries and for the keys) and the expert layer's kernels; peak
    15,690,275,840 + 38,709,760 of code, of 16.91e9 (arguments
    7,747,781,120, temporaries 12,586,199,040; PR 61's tree, the jnp
    form of the q / k norm and turn: 15,699,713,024 + 42,608,640, which
    the peak is held under: the float32 copies of q are gone and the
    rotary tables stay half a head wide in HBM).  With the flash call's `o`
    and `lse` kept by name (`remat_policy` kernels, 1.6 GB over six
    layers) the compiler refuses the step, 16.82G of 15.75G: five layers
    would fit that way (peak 15,652,873,728), and six without it were
    chosen (the configuration says why)."""
    import dataclasses
    import json

    import optax

    from benchmark.families import sdarmoe as family_sdarmoe
    from benchmark.harness import manifest
    from benchmark.reduce import bd_cost
    monkeypatch.setattr(fa, "_use_interpret", lambda interpret: False)
    with open(os.path.join(manifest.BENCH, "configs",
                           "sdar-30b-a3b-chat.json")) as f:
        config = json.load(f)
    family = family_sdarmoe.Family(config, config["job"])
    assert family.cfg.num_layers == 6
    assert family.cfg.remat_policy == "none"
    opt = family.optimizer()
    one = SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)
    params = jax.eval_shape(family.init, jax.random.key(0))
    opt_state = jax.eval_shape(opt.init, params)
    batch = jax.eval_shape(lambda k: family.make_batch(k, 1),
                           jax.random.key(0))

    def compiled(loss):
        def step(params, opt_state, batch):
            value, grads = jax.value_and_grad(loss)(params, batch)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, value
        return jax.jit(step, donate_argnums=(0, 1)).lower(
            on_chip(params), on_chip(opt_state), on_chip(batch)).compile()
    step = compiled(family.loss)
    text = step.as_text()
    calls = [line.strip().removeprefix("ROOT ")
             for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    masked = sorted(c[0] for c in map(bd_cost.call, calls) if c)
    assert masked == ["dkv", "dq", "forward", "forward"], masked
    _assert_rows_move_by_kernel(text, config["hidden_size"],
                                config["num_experts_per_tok"])
    mem = step.memory_analysis()
    said = (f"arguments {mem.argument_size_in_bytes:,} temporaries "
            f"{mem.temp_size_in_bytes:,} peak {mem.peak_memory_in_bytes:,} "
            f"code {mem.generated_code_size_in_bytes:,}")
    print(said)
    assert (mem.peak_memory_in_bytes
            + mem.generated_code_size_in_bytes) < 16.0e9, said
    assert mem.peak_memory_in_bytes <= 15_699_713_024, said
    heads = sorted(c.split(" = ")[0].lstrip("%").split(".")[0] for c in calls
                   if c.startswith("%head_norm_rope_"))
    assert heads == ["head_norm_rope_bwd"] * 2 + ["head_norm_rope_fwd"] * 4
    # and what was not chosen does not fit
    kept = dataclasses.replace(family.cfg, remat_policy="kernels")
    from byteps_tpu.models import sdar
    with pytest.raises(Exception, match="hbm"):
        compiled(lambda p, b: sdar.loss_fn(p, b, kept))


# 13 s and 16 s alone: the form that ships is tier-1's, the other's
# reading is kept as a slow case.
@pytest.mark.parametrize("form", [
    "flat", pytest.param("scan_of_walks", marks=pytest.mark.slow)])
def test_ouro_train_step_compiles_at_the_cells_shapes(v5e, monkeypatch, form):
    """The ouro cell's step (`benchmark/configs/ouro-2.6b.json`: eight
    layers walked four times, ONE sequence of 8,192 tokens, a plain
    `value_and_grad` and adamw over 612,438,017 parameters) for one
    described chip, in the loop's two forms (ISSUE 64: memory first).

    `flat`, the form that ships (`ouro.walks`: one scan over the 32 layer
    applications that reads layer `i mod 8`): arguments 7,349,323,776,
    temporaries 6,888,796,160, peak 12,106,292,224 + 26,582,016 of code,
    of 16.91e9.  `scan_of_walks` (a scan over the walks round
    `afmoe.run_layers`, `benchmark/tests/ouro_variants.py`
    `scan_of_walks`): temporaries
    12,611,866,112, peak 14,850,430,464: the inner scan's transpose hands
    the outer one a whole 1.64 GB stack of gradients a walk.  (Four calls
    of `run_layers` one after another: 16 kernel calls, temporaries
    14,491,262,976, peak 15,977,119,744; not kept as a case.)  On the chip
    `flat` ran 1,397.5 ms a step and `scan_of_walks` 1,406-1,408 (PERF.md,
    Findings, PR 64).  Either way the layer's program is there ONCE: the
    flash forward kernel twice (whole-layer remat), dQ and dK/dV once.

    Since PR 65 the queries and keys are read where they lie in the
    projection's result and turned by `ops/head_norm_rope.py`'s kernels
    with no scale: in the described chip's program the forward call four
    times (q and k, the pass and its recompute) and the backward call
    twice, none of them a flash call to the benchmark's cost, and the
    compiled peak no higher than the parent's 12,106,292,224 (`flat`:
    11,942,721,024 now; `scan_of_walks` 14,686,871,040).
    The projection's result is pinned row-major, as the kernels read it:
    no layout copy of it stands between the product and the calls (the
    compiler laid it S-minor and copied it twice a layer application, 15
    ms a step on the chip: PERF.md, Findings, PR 65)."""
    import json

    import optax

    from benchmark.families import ouro as family_ouro
    from benchmark.harness import manifest
    from benchmark.reduce import afmoe_cost
    from benchmark.tests import ouro_variants
    from byteps_tpu.models import ouro
    monkeypatch.setattr(fa, "_use_interpret", lambda interpret: False)
    if form == "scan_of_walks":
        monkeypatch.setattr(ouro, "walks", ouro_variants.scan_of_walks)
    with open(os.path.join(manifest.BENCH, "configs",
                           "ouro-2.6b.json")) as f:
        config = json.load(f)
    family = family_ouro.Family(config, config["job"])
    assert (family.cfg.num_layers, family.cfg.total_ut_steps) == (8, 4)
    assert family.cfg.remat_policy == "none"
    opt = family.optimizer()
    one = SingleDeviceSharding(v5e[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)
    params = jax.eval_shape(family.init, jax.random.key(0))
    opt_state = jax.eval_shape(opt.init, params)
    batch = jax.eval_shape(lambda k: family.make_batch(k, 1),
                           jax.random.key(0))

    def step(params, opt_state, batch):
        value, grads = jax.value_and_grad(family.loss)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, value
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(opt_state), on_chip(batch)).compile()
    text = compiled.as_text()
    assert not re.search(r"%copy[\w.]* = bf16\[(1,)?8192,6144\]", text)
    calls = [line.strip().removeprefix("ROOT ")
             for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    turns = [c for c in calls if c.startswith("%head_norm_rope_")]
    assert sorted(c.split(" = ")[0].lstrip("%").split(".")[0]
                  for c in turns) == (["head_norm_rope_bwd"] * 2
                                      + ["head_norm_rope_fwd"] * 4)
    assert not any(afmoe_cost.attention_call(c) for c in turns)
    calls = [c for c in calls if c not in turns]
    kinds = sorted(afmoe_cost.attention_call(c)[0] for c in calls)
    assert kinds == ["dkv", "dq", "forward", "forward"], calls
    # 16 ungrouped heads of 128 over 8,192 positions, no window
    assert {afmoe_cost.attention_call(c)[1:] for c in calls} == {
        (16, 8192, 128, None)}
    mem = compiled.memory_analysis()
    said = (f"{form}: arguments {mem.argument_size_in_bytes:,} temporaries "
            f"{mem.temp_size_in_bytes:,} peak {mem.peak_memory_in_bytes:,} "
            f"code {mem.generated_code_size_in_bytes:,}")
    print(said)
    assert (mem.peak_memory_in_bytes
            + mem.generated_code_size_in_bytes) < 15.75 * 2 ** 30, said
    if form == "flat":
        assert mem.peak_memory_in_bytes <= 12_106_292_224, said
    else:
        assert mem.peak_memory_in_bytes >= 14_500_000_000, said
