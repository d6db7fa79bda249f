"""Benchmark: flagship (BERT-large-class) DP training throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The reference's headline number is ~90% scaling efficiency for BERT-large
DP training (reference: README.md:38-46, BASELINE.md).  Scaling efficiency
is throughput-with-the-framework / ideal-throughput; on a single chip the
ideal is the raw jitted train step with no distribution framework, so
`efficiency = framework_step_throughput / raw_step_throughput` measured on
the same hardware — the framework's communication/scheduling overhead is
exactly what scaling efficiency penalises at scale.  vs_baseline =
efficiency / 0.90 (the reference's 256-GPU result; >1.0 beats it).

detail carries tokens/sec/chip and MFU (6·N·tokens/s over the chip's peak
bf16 FLOPs — the scaling-book utilization metric).

Modes:
  (default)          flagship efficiency bench (framework path donates its
                     buffers, the deployment configuration)
  BENCH_MACHINERY=1  communication-machinery bench on the device mesh:
                     naive tree_all_reduce vs bucketed vs hierarchical
                     (reference analog: example/pytorch/benchmark_byteps.py
                     measuring the framework's own data path)
  BENCH_WIRE=1       raw-speed acceptance: PS goodput as pct_of_floor of
                     the same-host raw socket echo floor (wire_bench.py
                     --echo-floor; BENCH_WIRE_UDS=1 for the AF_UNIX path)
  BENCH_PS=1         PS wire goodput through the real C++ server over
                     loopback TCP (reference analog: the ps-lite transport
                     benchmark in .travis.yml:29-34)
  BENCH_FAULT=1      fault-tolerance bench: mid-round connection reset via
                     tools/chaos_proxy.py; emits fault_reconnect_recovery_ms
  BENCH_ELASTIC=1    elastic-membership bench: permanent worker kill +
                     replacement join; emits evict_detect_ms and
                     join_catchup_ms (BENCH_ELASTIC_EVICT_S tunes the lease)
  BENCH_FUSION=1     fusion-layer wire bench: many small tensors, per-leaf
                     vs fused-bucket dispatch through the real PS server
                     (emits fusion_small_tensor_caller_block)
  BENCH_TRACE=1      tracing-overhead bench: sync-round time with the
                     distributed tracer hot (worker+server spans, traced
                     wire flags) vs off (emits trace_overhead_ms)
  BENCH_AUDIT=1      auditor-overhead bench: sync-round time with the
                     consistency auditor hot (publish digests, pull
                     trailers, re-digest, health sampling) vs off —
                     audit_overhead_ms, expected within noise
  BENCH_DOCTOR=1     signal-plane/doctor-overhead bench: sync-round time
                     with the windowed key-signal plane + doctor rules
                     hot vs off, plus the per-window roll cost
  BENCH_FLEET=1      fleet-plane bench: sync-round time with CMD_WINDOW
                     publishing + CMD_FLEET fetching hot per window vs
                     off; emits fleet_plane_overhead_ms and the goodput
                     ledger's fleet_goodput_pct over the live merged view
  BENCH_AUTOTUNE=1   adaptive-compression bench: the same mixed-key
                     workload UNTUNED-with-tuner (starts raw, the tuner
                     renegotiates codecs live off the signal plane) vs
                     HAND-TUNED (codecs registered up front); emits
                     autotune_step_time_gap_pct (target: within a few %)
                     plus switch counts and the per-key final codec
                     assignments
  BENCH_KNOB=1       knob-plane bench: cold-start job whose predictive
                     tuner must discover FUSION_BYTES + codecs live
                     (cost-model jumps + actuated CMD_KNOB sets at
                     round boundaries) vs the hand-tuned expert config;
                     emits knob_step_time_gap_pct (target: <= 0) with
                     the cost-model seed and final knob assignments
  BENCH_SERVEROPT=1  server-resident-optimizer bench: the same Adam
                     workload with the update stage on the PS tier
                     (push grads, pull params) vs worker-local optax;
                     emits serveropt_step_time_gap_pct plus the
                     structural detail (worker optimizer-state bytes ->
                     0 in server mode, param_version == rounds)
  BENCH_HIER=1       hierarchical-reduction bench: the same 4-worker
                     sync workload flat vs 2-slice x 2-chip (in-graph
                     psum intra-slice, leaders-only on the wire;
                     BENCH_HIER_SLICE overrides the slice size); emits
                     hier_wire_bytes_saved_pct plus the per-worker wire
                     bytes and step-time deltas
  BENCH_TELEMETRY=1  telemetry-overhead bench: sync-round time with the
                     metrics endpoint scraped at 20Hz vs export plane off
                     (emits telemetry_overhead_ms; expected within noise)
  BENCH_CNN=<name>   image-model throughput (resnet50 / vgg16 ...), fp32 —
                     the reference's other headline rows (reference:
                     docs/performance.md:5-26); BENCH_CNN_BATCH per chip
  BENCH_SMALL=1      shrink the model for quick local runs
  BENCH_FORCE_CPU=1  the one explicit CPU mode: 8 virtual CPU devices

Sweep knobs (tools/mfu_sweep.py): BENCH_MODEL picks any named config
(e.g. llama_300m), BENCH_SEQ overrides its sequence length, BENCH_BATCH /
BENCH_ATTN / BENCH_ATTN_BLOCK / BENCH_ATTN_BLOCK_K (decoupled K/V tile) /
BENCH_REMAT / BENCH_REMAT_POLICY / BENCH_CE_CHUNK / BENCH_UNROLL
(layer-scan unroll) override the rest of the geometry.  BENCH_COST=1
adds XLA's compile-time accounting (flops, HBM bytes, arithmetic
intensity) for the raw single-chip step to the JSON detail — off by
default, only sweeps ask for it.

The device modes (flagship, BENCH_MACHINERY, BENCH_CNN) run once, in this
process, on the accelerator jax.devices() offers, and exit non-zero when
there is none: a chip belongs to one process, so there is no probe child,
no retry and no CPU stand-in.  BENCH_FORCE_CPU=1 is the one explicit CPU
mode (tests and dev loops); pair it with BENCH_SMALL=1 for a CPU-feasible
model.  The persistent compilation cache goes where
byteps_tpu.utils.compile_cache says.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

def _param_count(params) -> int:
    import jax
    return sum(int(l.size) for l in jax.tree.leaves(params))


def _peak_flops(device) -> float:
    """Peak dense bf16 FLOPs/s for a device.  The spec-sheet table and
    the env override live in byteps_tpu.common.devprof — ONE table
    shared with the live MFU gauges, so bench MFU and `bps_mfu` can never
    disagree on a platform's peak, and a TPU kind missing from it is an
    error.  Lazy import: bench.py's module load must stay
    side-effect-free for the host-only subprocess benches."""
    from byteps_tpu.common.devprof import peak_flops
    return peak_flops(device)


def _device_stamp() -> dict:
    """Platform-honesty stamp for every BENCH record.  The detector lives
    in byteps_tpu.common.devprof: the live doctor's device sentinel probes
    the SAME function every signal window, so the bench-time stamp and the
    runtime verdict cannot drift."""
    from byteps_tpu.common.devprof import device_stamp
    return device_stamp()


def _require_chip() -> None:
    """The device modes measure the chip: exit non-zero when JAX found no
    accelerator, before any work.  BENCH_FORCE_CPU=1 is the one explicit
    CPU mode."""
    import jax
    if os.environ.get("BENCH_FORCE_CPU", "0") == "1":
        return
    platform = jax.devices()[0].platform
    if platform == "cpu":
        raise SystemExit(
            "bench.py: jax.devices() found no accelerator (platform "
            "'cpu'); a device mode does not fall back to the CPU — set "
            "BENCH_FORCE_CPU=1 for an explicit CPU run")


def _time_steps(fn, params, opt_state, batch, n, per_step):
    """Shared timing harness: warmup+compile step, then n timed steps.

    `fn(params, opt_state, batch) -> (params, opt_state, loss)`; returns
    units/sec where one step advances `per_step` units (tokens, images).
    The `float(loss)` every step waits for the device: dispatch is
    asynchronous, and a loop that never reads a result times the enqueue.
    """
    params, opt_state, loss = fn(params, opt_state, batch)
    float(loss)  # warmup + compile
    t0 = time.perf_counter()
    for _ in range(n):
        params, opt_state, loss = fn(params, opt_state, batch)
        float(loss)
    return n * per_step / (time.perf_counter() - t0)


def _attn_block_for(seq: int) -> int:
    """BENCH_ATTN_BLOCK, normalized to the kernel's auto choice when unset
    or when the kernel would reject it (must divide seq and be a multiple
    of 128) — so the JSON label always states the block that actually ran.
    The auto rule and the tile multiple are imported, not duplicated, so
    record and kernel can't drift."""
    from byteps_tpu.models.transformer import flash_auto_block
    from byteps_tpu.ops.flash_attention import BLOCK_Q_MULTIPLE
    ab = int(os.environ.get("BENCH_ATTN_BLOCK", "0"))
    if ab and seq % ab == 0 and ab % BLOCK_Q_MULTIPLE == 0:
        return ab
    return flash_auto_block(seq)


def _cfg_with_env_overrides(cfg, seq: int, default_attn: str = ""):
    """Apply the sweep env knobs (BENCH_ATTN / BENCH_ATTN_BLOCK /
    BENCH_REMAT / BENCH_REMAT_POLICY) to a model config — one parser for
    every bench branch so the knobs can't silently diverge.  Defaults
    come from the config itself unless `default_attn` pins a different
    attention choice (the flagship default)."""
    attn = os.environ.get("BENCH_ATTN", default_attn or cfg.attn_impl)
    if attn == "flash" and _attn_block_for(seq) == 0:
        # Fail before building the model, not at trace time.
        raise SystemExit(f"BENCH_ATTN=flash needs seq divisible by 128 "
                         f"(got BENCH_SEQ/seq={seq})")
    bk = 0
    if attn == "flash":
        # Same normalize-to-auto contract as BENCH_ATTN_BLOCK: an invalid
        # K tile reverts to the Q tile (exactly what the adapter would
        # run), and the knob is ignored entirely off the flash path.
        bk = int(os.environ.get("BENCH_ATTN_BLOCK_K", "0"))
        if bk and (seq % bk or bk % 64):
            bk = 0
    return dataclasses.replace(
        cfg, attn_impl=attn,
        # BENCH_REMAT=0 disables per-layer remat entirely (viable only
        # when the config avoids the S^2 logits, i.e. with flash, and at
        # batches where saved activations fit HBM).
        remat=(os.environ["BENCH_REMAT"] != "0"
               if "BENCH_REMAT" in os.environ else cfg.remat),
        remat_policy=os.environ.get("BENCH_REMAT_POLICY", cfg.remat_policy),
        # Gate on flash so the record never carries a block the dense
        # path silently ignored.
        attn_block=_attn_block_for(seq) if attn == "flash" else 0,
        attn_block_k=bk if attn == "flash" else 0,
        # BENCH_UNROLL=k groups k layers per scan iteration (must divide
        # num_layers — the config validates, so a bad sweep value fails
        # loudly rather than silently benching unroll=1).
        scan_unroll=int(os.environ.get("BENCH_UNROLL", "0")) or
        cfg.scan_unroll)


def bench_flagship():
    import jax
    import optax

    import byteps_tpu as bps
    from byteps_tpu.models import transformer as tfm

    alt_model = os.environ.get("BENCH_MODEL", "")
    small = os.environ.get("BENCH_SMALL", "0") == "1"
    ce_chunk = int(os.environ.get("BENCH_CE_CHUNK", "2048"))
    if small:
        cfg = tfm.get_config("tiny", causal=True)
        batch, seq, steps = 8 * max(1, jax.device_count()), 128, 5
    elif alt_model:
        # Bench any named config (e.g. BENCH_MODEL=llama_1b for the
        # modern-LLM block) at its native sequence length.  The streamed
        # LM head applies here too (llama_1b's full logits at seq 2048
        # would be 2.1 GB of f32 HBM traffic).  BENCH_ATTN / _ATTN_BLOCK /
        # _REMAT_POLICY / _BATCH override the config's defaults so sweeps
        # (e.g. the long-seq block question in tools/mfu_sweep.py) can
        # run on these geometries too.
        cfg = tfm.get_config(alt_model, causal=True, ce_chunk_rows=ce_chunk)
        seq = int(os.environ.get("BENCH_SEQ", "0")) \
            or min(cfg.max_seq_len, 2048)
        if seq > cfg.max_seq_len:
            cfg = dataclasses.replace(cfg, max_seq_len=seq)
        cfg = _cfg_with_env_overrides(cfg, seq)
        batch = int(os.environ.get("BENCH_BATCH",
                                   "8")) * jax.device_count()
        steps = 10
    else:
        # Full BERT-large geometry (reference benchmark: README.md:38-46),
        # causal-LM objective, bf16 activations, per-layer remat, streamed
        # LM-head cross-entropy, flash attention with a full-sequence 512
        # block, per-chip batch 64 — the configuration chip_smoke.py
        # brings up on the chip.  Each knob stays env-overridable for
        # sweeps:
        # BENCH_CE_CHUNK=0 / BENCH_ATTN=dense / BENCH_REMAT_POLICY=proj /
        # BENCH_BATCH=48.
        cfg = tfm.get_config(
            "bert_large", causal=True, vocab_size=32768, max_seq_len=512,
            ce_chunk_rows=ce_chunk)
        cfg = _cfg_with_env_overrides(cfg, 512, default_attn="flash")
        batch = int(os.environ.get("BENCH_BATCH", "64")) * jax.device_count()
        seq, steps = 512, 10

    mesh = bps.make_mesh()  # all devices on dp
    params = tfm.init_params(jax.random.key(0), cfg)
    n_params = _param_count(params)
    toks, tgts = tfm.synthetic_batch(jax.random.key(1), batch, seq, cfg)

    def loss_fn(p, b):
        return tfm.loss_fn(p, b, cfg)

    # Framework path: DistributedOptimizer (bucketed priority all-reduce),
    # donated buffers — the deployment configuration.  Donation consumes
    # the input arrays, so the framework path runs on its own copies and
    # the raw path keeps the originals.
    import jax.numpy as jnp
    opt = bps.DistributedOptimizer(optax.adamw(1e-4))
    step = bps.build_train_step(loss_fn, opt, mesh, donate=True)
    fw_tps = _time_steps(step, jax.tree.map(jnp.copy, params),
                         opt.init(params), (toks, tgts), steps, batch * seq)

    # Ideal path: same model/optimizer, no distribution framework, one shard
    # of the global batch on one device -> ideal per-chip throughput.
    raw_opt = optax.adamw(1e-4)
    n_dev = jax.device_count()
    rb = max(1, batch // n_dev)

    def raw_step(p, s, b):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        u, s = raw_opt.update(g, s, p)
        return optax.apply_updates(p, u), s, loss

    rstep = jax.jit(raw_step, donate_argnums=(0, 1))
    raw_state = raw_opt.init(params)
    # Abstract arg shapes captured before timing donates the buffers —
    # BENCH_COST re-lowers from these (cache-warm) for cost_analysis.
    abs_args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (params, raw_state, (toks[:rb], tgts[:rb])))
    raw_tps = _time_steps(rstep, params, raw_state,
                          (toks[:rb], tgts[:rb]), steps, rb * seq)

    cost = {}
    if os.environ.get("BENCH_COST", "0") == "1":
        # XLA's compile-time accounting for the single-chip step: total
        # flops and HBM bytes accessed -> arithmetic intensity and which
        # roofline (compute vs bandwidth) the config sits under.  Off by
        # default: only sweeps ask for it.
        try:
            ca = rstep.lower(*abs_args).compile().cost_analysis() or {}
            flops = float(ca.get("flops", 0.0))
            hbm = float(ca.get("bytes accessed", 0.0))
            cost = {"xla_flops_per_raw_step": flops,
                    "xla_hbm_bytes_per_raw_step": hbm}
            if hbm > 0:
                cost["arithmetic_intensity"] = round(flops / hbm, 2)
        except Exception as e:   # never let accounting kill the bench
            cost = {"cost_analysis_error": repr(e)[:200]}

    efficiency = fw_tps / (raw_tps * n_dev)
    tps_per_chip = fw_tps / n_dev
    peak = _peak_flops(jax.devices()[0])
    # No peak (the explicit CPU mode): MFU is not measured, never 0.0.
    mfu = round(6.0 * n_params * tps_per_chip / peak, 4) if peak else None
    model_name = ("tiny" if small else (alt_model or "bert_large"))
    print(json.dumps({
        "metric": f"{model_name}_dp_scaling_efficiency",
        "value": round(efficiency, 4),
        "unit": "fraction_of_ideal",
        "vs_baseline": round(efficiency / 0.90, 4),
        "detail": {
            "framework_tokens_per_sec": round(fw_tps),
            "tokens_per_sec_per_chip": round(tps_per_chip),
            "ideal_tokens_per_sec_per_chip": round(raw_tps),
            "mfu": mfu,
            "params": n_params,
            "peak_bf16_flops": peak,
            "donate": True,
            "devices": n_dev,
            "batch": batch, "seq": seq,
            "model": model_name,
            "ce_chunk_rows": cfg.ce_chunk_rows,
            "attn_impl": cfg.attn_impl,
            "attn_block": cfg.attn_block,
            "attn_block_k": cfg.attn_block_k or cfg.attn_block,
            "remat": cfg.remat,
            "remat_policy": cfg.remat_policy,
            "scan_unroll": cfg.scan_unroll,
            **cost,
            **_device_stamp(),
        },
    }))


def bench_cnn():
    """Image-model DP training throughput: full framework path vs the
    raw-jit roofline, images/sec.

    Mirrors the reference's other headline rows — ResNet-50 / VGG-16
    throughput at BS=64/GPU, fp32 (reference: docs/performance.md:5-26,
    BASELINE.md) — with the flagship bench's methodology: identical
    model/optimizer on both sides of the ratio, hard device sync every
    step, efficiency = framework / ideal and vs_baseline against the
    reference's 0.90 scaling-efficiency bar.  fp32 like the reference
    rows (the MXU runs f32 matmuls in multi-pass emulation, so absolute
    images/sec is conservative; the RATIO is what the metric carries).
    """
    import jax
    import jax.numpy as jnp
    import optax

    import byteps_tpu as bps
    from byteps_tpu import models

    name = os.environ.get("BENCH_CNN", "resnet50")
    small = os.environ.get("BENCH_SMALL", "0") == "1"
    if small:
        # CPU-feasible stand-in keeping the same code path: shallow
        # member of the same family, CIFAR-sized images.
        name = "vgg16" if "vgg" in name else "resnet18"
        batch_per, hw, steps = 8, 32, 3
    else:
        batch_per = int(os.environ.get("BENCH_CNN_BATCH", "64"))
        hw, steps = 224, 10
    n_dev = jax.device_count()
    batch = batch_per * n_dev

    # dtype=f32 explicitly: the model zoo defaults to bf16 compute, but
    # the reference rows being mirrored are fp32.
    model = models.create_cnn(name, num_classes=1000, dtype=jnp.float32)
    x0 = jnp.ones((2, hw, hw, 3), jnp.float32)
    variables = model.init(jax.random.key(0), x0, train=False)
    n_params = _param_count(variables)
    loss_fn = models.cnn_loss_fn(model)
    images = jax.random.normal(jax.random.key(1), (batch, hw, hw, 3),
                               jnp.float32)
    labels = jax.random.randint(jax.random.key(2), (batch,), 0, 1000)

    mesh = bps.make_mesh()
    opt = bps.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    step = bps.build_train_step(loss_fn, opt, mesh, donate=True)
    fw_ips = _time_steps(step, jax.tree.map(jnp.copy, variables),
                         opt.init(variables), (images, labels), steps, batch)

    raw_opt = optax.sgd(0.1, momentum=0.9)

    def raw_step(v, s, b):
        loss, g = jax.value_and_grad(loss_fn)(v, b)
        u, s = raw_opt.update(g, s, v)
        return optax.apply_updates(v, u), s, loss

    rb = max(1, batch // n_dev)
    rstep = jax.jit(raw_step, donate_argnums=(0, 1))
    raw_ips = _time_steps(rstep, variables, raw_opt.init(variables),
                          (images[:rb], labels[:rb]), steps, rb)

    efficiency = fw_ips / (raw_ips * n_dev)
    print(json.dumps({
        "metric": f"{name}_dp_scaling_efficiency",
        "value": round(efficiency, 4),
        "unit": "fraction_of_ideal",
        "vs_baseline": round(efficiency / 0.90, 4),
        "detail": {
            "framework_images_per_sec": round(fw_ips, 1),
            "images_per_sec_per_chip": round(fw_ips / n_dev, 1),
            "ideal_images_per_sec_per_chip": round(raw_ips, 1),
            "params": n_params,
            "devices": n_dev,
            "batch": batch, "image_size": hw,
            "model": name, "dtype": "float32",
            **_device_stamp(),
        },
    }))


def bench_machinery():
    """Measure the framework's own collective machinery: naive one-psum-per
    -leaf vs bucketed vs hierarchical tree all-reduce on the device mesh.

    Two regimes, both reported:
      - small_leaves (headline): thousands of small gradients — the DNN
        gradient-list regime bucketing was built for; per-collective
        overhead dominates, fewer+larger transfers win (reference analog:
        the packing rationale of cross_device_ops.py:251-296).
      - mixed: realistic large+small mix.  On a virtual CPU mesh the
        pack/unpack copies are the dominant cost and bucketing roughly
        ties; on real ICI the per-collective latency it removes is far
        larger, which is why 4MB bucketing is the deployment default.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import byteps_tpu as bps
    from byteps_tpu.ops import collectives

    n_dev = jax.device_count()
    mesh = bps.make_mesh()
    ici = max(1, n_dev // 2)
    hmesh = bps.make_hierarchical_mesh(ici)
    rng = jax.random.key(0)

    def make_tree(sizes):
        leaves = [jax.random.normal(jax.random.fold_in(rng, i), (s,),
                                    dtype=jnp.float32)
                  for i, s in enumerate(sizes)]
        return {f"g{i}": l for i, l in enumerate(leaves)}

    def timed(mesh_, fn, tree, reps=5):
        sm = jax.jit(jax.shard_map(
            fn, mesh=mesh_, in_specs=(P(),), out_specs=P(),
            check_vma=False))
        jax.block_until_ready(sm(tree))  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(sm(tree))
            best = min(best, time.perf_counter() - t0)
        return best

    def run_regime(sizes):
        tree = make_tree(sizes)
        t_naive = timed(mesh, lambda t: collectives.tree_all_reduce(t, "dp"),
                        tree)
        t_bucket = timed(
            mesh, lambda t: collectives.bucketed_tree_all_reduce(t, "dp"),
            tree)
        t_hier = timed(
            hmesh,
            lambda t: collectives.hierarchical_tree_all_reduce(t), tree)
        return {
            "naive_ms": round(t_naive * 1e3, 3),
            "bucketed_ms": round(t_bucket * 1e3, 3),
            "hierarchical_ms": round(t_hier * 1e3, 3),
            "bucketed_speedup": round(t_naive / t_bucket, 4),
            "leaves": len(sizes),
            "mbytes": round(sum(sizes) * 4 / 1e6, 1),
        }

    small = run_regime([1_000] * 2000)
    mixed = run_regime([1_000] * 150 + [50_000] * 30 + [1_000_000] * 4)
    print(json.dumps({
        "metric": "machinery_bucketed_speedup_vs_naive",
        "value": small["bucketed_speedup"],
        # >1.0: bucketing pays
        "unit": "x",
        "vs_baseline": small["bucketed_speedup"],
        "detail": {
            "small_leaves": small,
            "mixed": mixed,
            "devices": n_dev,
            "ici_size": ici,
            **_device_stamp(),
        },
    }))


def bench_fusion():
    """Fusion-layer wire benchmark: the many-small-tensors regime through
    the real PS server (tools/wire_bench.py fusion_ab), emitted as the
    `fusion_small_tensor_caller_block` metric so BENCH_r* tracks the
    trajectory.

    value = the fused caller-block wall time for one round of the
    many-small-tensors scenario (512 leaves of 4-64 KiB; 128 with
    BENCH_SMALL=1); vs_baseline = the per-leaf (unfused) caller-block
    time over it — how many times faster the caller gets back to its
    step compute with the fusion layer on.  Host-only, like BENCH_PS.
    """
    import subprocess
    import sys

    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "wire_bench.py")
    argv = [sys.executable, tool, "--fusion-only", "--json"]
    if os.environ.get("BENCH_SMALL", "0") == "1":
        argv.append("--quick")
    r = subprocess.run(argv, capture_output=True, text=True, timeout=1200)
    if r.returncode != 0:
        _error_record(f"fusion bench failed rc={r.returncode}: "
                      f"{r.stderr[-400:]}")
        raise SystemExit(3)
    fus = json.loads(r.stdout)["fusion"]
    print(json.dumps({
        "metric": "fusion_small_tensor_caller_block",
        "value": round(fus["fused"]["caller_block_best_s"] * 1e3, 3),
        "unit": "ms",
        "vs_baseline": fus["caller_block_speedup"],
        "detail": {
            "num_leaves": fus["num_leaves"],
            "leaf_kb": fus["leaf_kb"],
            "total_mb": fus["total_mb"],
            "fusion_bytes": fus["fusion_bytes"],
            "wire_message_reduction": fus["wire_message_reduction"],
            "sync_round_speedup": fus["sync_round_speedup"],
            "priority_descending": fus["priority_descending"],
            "unfused_caller_block_ms": round(
                fus["unfused"]["caller_block_best_s"] * 1e3, 3),
            "unfused_msgs_per_round":
                fus["unfused"]["wire_messages_per_round"],
            "fused_msgs_per_round":
                fus["fused"]["wire_messages_per_round"],
            "buckets": fus["fused"]["buckets"],
            "note": "vs_baseline = unfused/fused caller-block time; "
                    "wire messages are PUSH dispatches per round "
                    "(PULLs mirror 1:1)",
            **_device_stamp(),
        },
    }))


def _boot_ps_server(engine_threads: int, num_workers: int = 1,
                    extra_env: dict = None):
    """Start the native PS server on a freshly-probed free port, retrying
    on a new port if another process snatches it (bind/close-then-launch
    is inherently TOCTOU on a busy host).  Returns (proc, port); shared by
    the PS-tier benches (BENCH_PS / BENCH_FAULT / BENCH_ELASTIC)."""
    import socket
    import subprocess
    import sys
    import tempfile

    from byteps_tpu.utils.hermetic import cpu_subprocess_env

    for _ in range(4):
        # The server binds root_port + 1 + server_id; only the data
        # port is ever bound here (no scheduler process), so probe THAT
        # one free and derive the root port from it.
        with socket.socket() as sk:
            sk.bind(("127.0.0.1", 0))
            port = sk.getsockname()[1]      # the server's data port
        env = cpu_subprocess_env({
            "DMLC_PS_ROOT_PORT": str(port - 1),
            "DMLC_NUM_WORKER": str(num_workers),
            "BYTEPS_SERVER_ENGINE_THREAD": str(engine_threads),
            **(extra_env or {}),
        })
        errf = tempfile.TemporaryFile(mode="w+")
        proc = subprocess.Popen(
            [sys.executable, "-m", "byteps_tpu.server"],
            env=env, stdout=subprocess.DEVNULL, stderr=errf)
        deadline = time.time() + 30
        while True:
            try:
                socket.create_connection(
                    ("127.0.0.1", port), 0.5).close()
                return proc, port
            except OSError:
                if proc.poll() is not None:
                    # Only an actual bind conflict is worth a retry on
                    # a fresh port; any other startup death (import
                    # error, missing native lib) must surface.
                    errf.seek(0)
                    stderr = errf.read()[-500:]
                    errf.close()
                    if "in use" not in stderr.lower():
                        raise RuntimeError(
                            f"PS server died at startup "
                            f"(rc={proc.returncode}): {stderr}")
                    break           # lost the port race — retry fresh
                if time.time() > deadline:
                    proc.kill()
                    proc.wait()
                    raise RuntimeError("PS server did not come up")
                time.sleep(0.1)
    raise RuntimeError("PS server lost the port race 4 times")


def bench_wire():
    """Raw-speed transport benchmark (BENCH_WIRE=1): the ≥85%-of-wire-
    floor acceptance number, measured by tools/wire_bench.py
    --echo-floor and recorded in the BENCH json rather than
    hand-calculated.

    value = `wire_pct_of_floor`: PS raw push_pull goodput (4 MiB
    partitions, interleaved best-of batches) as a percentage of the
    same host's raw socket echo floor on the same transport;
    vs_baseline = pct / 85 (the ROADMAP target).  BENCH_WIRE_UDS=1
    measures the AF_UNIX colocated fast path instead of loopback TCP.
    Host-only, like BENCH_PS.
    """
    import subprocess
    import sys

    from byteps_tpu.utils.hermetic import cpu_subprocess_env

    args = [sys.executable,
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tools", "wire_bench.py"),
            "--echo-floor", "--json"]
    if os.environ.get("BENCH_WIRE_UDS", "0") == "1":
        args.append("--uds")
    if os.environ.get("BENCH_SMALL", "0") == "1":
        args.append("--quick")
    r = subprocess.run(args, env=cpu_subprocess_env({}),
                       capture_output=True, text=True, timeout=900)
    if r.returncode != 0:
        _error_record(f"wire bench failed rc={r.returncode}: "
                      f"{r.stderr[-400:]}")
        raise SystemExit(3)
    ef = json.loads(r.stdout)["echo_floor"]
    print(json.dumps({
        "metric": "wire_pct_of_floor",
        "value": ef["pct_of_floor"],
        "unit": "pct_of_echo_floor",
        "vs_baseline": round(ef["pct_of_floor"]
                             / ef["target_pct_of_floor"], 3),
        "detail": {**ef, **_device_stamp()},
    }))


def bench_fault():
    """Fault-tolerance benchmark: wall-clock cost of a mid-round
    connection reset through the chaos proxy (tools/chaos_proxy.py).

    value = `fault_reconnect_recovery_ms`: the extra time a push_pull
    round takes when its connection is RST mid-payload and the transport
    must park, re-dial, re-handshake, and replay — versus a healthy round
    (vs_baseline = faulted / healthy round time).  Measures the real
    client + real C++ server + real backoff path, loopback TCP.
    Host-only, like BENCH_PS.
    """
    import socket
    import subprocess
    import sys

    import numpy as np

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from chaos_proxy import ChaosProxy

    from byteps_tpu.server.client import PSSession
    from byteps_tpu.utils.hermetic import cpu_subprocess_env


    backoff_ms = float(os.environ.get("BENCH_FAULT_BACKOFF_MS", "20"))
    reps = int(os.environ.get("BENCH_FAULT_REPS", "5"))
    proc, port = _boot_ps_server(engine_threads=2)
    proxy = ChaosProxy("127.0.0.1", port).start()
    try:
        sess = PSSession(["127.0.0.1"], [proxy.port], worker_id=0,
                         num_servers=1, wire_conns=1,
                         reconnect_attempts=8,
                         reconnect_backoff_ms=backoff_ms)
        x = np.random.default_rng(0).standard_normal(
            1 << 20, dtype=np.float32)            # 4 MB, one partition
        sess.push_pull(1, x)                      # init + warm
        healthy = []
        for _ in range(reps):
            t0 = time.perf_counter()
            sess.push_pull(1, x)
            healthy.append(time.perf_counter() - t0)
        faulted = []
        for _ in range(reps):
            proxy.reset_after(1 << 20)            # RST 1 MB into the push
            t0 = time.perf_counter()
            sess.push_pull(1, x)                  # parks, re-dials, replays
            faulted.append(time.perf_counter() - t0)
        stats = sess.transport_stats()
        sess.close()
        healthy_best = min(healthy)
        faulted_med = sorted(faulted)[len(faulted) // 2]
        recovery_ms = (faulted_med - healthy_best) * 1e3
        print(json.dumps({
            "metric": "fault_reconnect_recovery_ms",
            "value": round(recovery_ms, 1),
            "unit": "ms",
            "vs_baseline": round(faulted_med / healthy_best, 2),
            "detail": {
                "healthy_round_best_ms": round(healthy_best * 1e3, 1),
                "faulted_round_median_ms": round(faulted_med * 1e3, 1),
                "reps": reps,
                "reconnect_backoff_ms": backoff_ms,
                "reconnects": stats["reconnects"],
                "replayed_pushes": stats["replayed_pushes"],
                "replayed_pulls": stats["replayed_pulls"],
                "parked_total": stats["parked_total"],
                "fault": "RST 1 MiB into a 4 MiB push, one-shot, "
                         "via tools/chaos_proxy.py",
                "note": "value = median faulted round minus best healthy "
                        "round: park + backoff + re-dial + HELLO/INIT "
                        "re-handshake + replay",
                **_device_stamp(),
            },
        }))
    finally:
        proxy.stop()
        proc.kill()
        proc.wait()


def _boot_ring_servers(n: int, engine_threads: int = 2,
                       extra_env: dict = None):
    """Start `n` ring-armed PS servers on consecutive ports (the
    root+1+id convention both the servers' peer book and the workers
    derive).  Returns (procs, ports); retries the whole group on a port
    collision."""
    import socket
    import subprocess
    import sys

    from byteps_tpu.utils.hermetic import cpu_subprocess_env

    for _ in range(4):
        with socket.socket() as sk:
            sk.bind(("127.0.0.1", 0))
            base = sk.getsockname()[1]
        ports = [base + i for i in range(n)]
        procs = []
        ok = True
        for i in range(n):
            env = cpu_subprocess_env({
                "DMLC_PS_ROOT_PORT": str(base - 1),
                "DMLC_NUM_WORKER": "1",
                "DMLC_NUM_SERVER": str(n),
                "DMLC_SERVER_ID": str(i),
                "BYTEPS_TPU_RING": "1",
                "BYTEPS_SERVER_ENGINE_THREAD": str(engine_threads),
                **(extra_env or {}),
            })
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "byteps_tpu.server"], env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
        deadline = time.time() + 30
        up = set()
        while time.time() < deadline and len(up) < n:
            for i, p in enumerate(ports):
                if i in up:
                    continue
                try:
                    socket.create_connection(("127.0.0.1", p), 0.5).close()
                    up.add(i)
                except OSError:
                    if procs[i].poll() is not None:
                        ok = False
                        break
            if not ok:
                break
            time.sleep(0.1)
        if ok and len(up) == n:
            return procs, ports
        for p in procs:
            p.kill()
            p.wait()
    raise RuntimeError(f"could not boot {n} ring servers")


def bench_elastic():
    """Elastic-membership benchmark (BENCH_ELASTIC=1): wall-clock cost of
    the transitions an autoscaled/preempted fleet pays — both halves.

    Worker half (PR 7):
    `evict_detect_ms`: 2 workers mid-training with lease eviction armed
    (BYTEPS_TPU_EVICT_TIMEOUT_S = BENCH_ELASTIC_EVICT_S, default 0.5);
    worker 1 dies without notice, and the value is how long worker 0's
    next round blocks until the server evicts the corpse and re-finalizes
    the open round (minus a healthy round) — the unavailability window a
    permanent worker loss costs the survivors.

    `join_catchup_ms`: a replacement worker then HELLOs in while the
    survivor keeps stepping; the value is session construction -> its
    first completed push_pull (epoch admission + INIT round rebase +
    first post-join round).

    Server half (elastic PS ring):
    `migration_ms`: 2 ring-armed servers; server 1 is gracefully drained
    (bps-level drain_server: state handoff + redirect) and the value is
    the drain call plus the first post-drain round, minus a healthy
    round — the availability cost of scaling the PS tier down by one.

    `server_failover_ms`: 2 ring-armed servers with the worker-side
    server-lease scanner armed; server 1 is SIGKILLed mid-job and the
    value is how long the next round blocks until the scanner declares
    it dead, the survivors claim its key ranges, and the open round
    re-pushes — minus a healthy round.  Host-only, like BENCH_FAULT.
    """
    import threading

    import numpy as np

    from byteps_tpu.server.client import PSSession

    evict_s = float(os.environ.get("BENCH_ELASTIC_EVICT_S", "0.5"))
    proc, port = _boot_ps_server(
        engine_threads=2, num_workers=2,
        extra_env={"BYTEPS_TPU_EVICT_TIMEOUT_S": str(evict_s)})

    def mk(wid):
        return PSSession(["127.0.0.1"], [port], worker_id=wid,
                         num_servers=1, wire_conns=1,
                         evict_timeout_s=evict_s)

    try:
        s0, s1 = mk(0), mk(1)
        x = np.random.default_rng(0).standard_normal(
            1 << 18, dtype=np.float32)          # 1 MB, one partition
        for _ in range(3):                       # init + warm
            h0 = s0.push_pull_async(1, x)
            h1 = s1.push_pull_async(1, x)
            h0.wait(30); h1.wait(30)
        t0 = time.perf_counter()
        h0 = s0.push_pull_async(1, x)
        h1 = s1.push_pull_async(1, x)
        h0.wait(30); h1.wait(30)
        healthy_ms = (time.perf_counter() - t0) * 1e3

        # Permanent kill: worker 1 vanishes (no leave, no FIN courtesy).
        s1.close()
        t0 = time.perf_counter()
        s0.push_pull_async(1, x).wait(60)
        evict_detect_ms = (time.perf_counter() - t0) * 1e3 - healthy_ms

        # Replacement joins while the survivor keeps stepping.
        stop = threading.Event()

        def survivor():
            while not stop.is_set():
                try:
                    s0.push_pull_async(1, x).wait(60)
                except Exception:
                    return

        th = threading.Thread(target=survivor, daemon=True)
        th.start()
        t0 = time.perf_counter()
        s1b = mk(1)
        s1b.push_pull_async(1, x).wait(60)
        join_catchup_ms = (time.perf_counter() - t0) * 1e3
        stop.set()
        th.join(timeout=60)
        epoch = s0.membership()["epoch"]
        s0.close()
        s1b.close()
        detail = {
            "healthy_round_ms": round(healthy_ms, 1),
            "evict_timeout_s": evict_s,
            "final_epoch": epoch,
            "note": "evict_detect_ms = survivor's blocked round minus a "
                    "healthy round (lease expiry + re-finalize); "
                    "join_catchup_ms = session construction -> first "
                    "completed post-join push_pull",
            **_device_stamp(),
        }
        print(json.dumps({
            "metric": "evict_detect_ms",
            "value": round(evict_detect_ms, 1),
            "unit": "ms",
            "vs_baseline": round(evict_detect_ms / (evict_s * 1e3), 2),
            "detail": detail,
        }))
        print(json.dumps({
            "metric": "join_catchup_ms",
            "value": round(join_catchup_ms, 1),
            "unit": "ms",
            "vs_baseline": round(join_catchup_ms / max(healthy_ms, 1e-3),
                                 2),
            "detail": detail,
        }))
    finally:
        proc.kill()
        proc.wait()

    # ---- server half: graceful drain (migration) ------------------------
    import numpy as np
    from byteps_tpu.server.client import PSSession

    def ring_session(ports, srv_evict=0.0, audit=False):
        return PSSession(["127.0.0.1"] * len(ports), ports, worker_id=0,
                         num_servers=len(ports), wire_conns=1, ring=True,
                         server_evict_timeout_s=srv_evict, audit=audit,
                         partition_bytes=1 << 18)

    # Several 256 KiB keys so both servers own a share of the ring.
    keys = list(range(1, 9))
    x = np.random.default_rng(0).standard_normal(1 << 16,
                                                 dtype=np.float32)

    def round_all(sess, timeout=60):
        hs = [sess.push_pull_async(k, x) for k in keys]
        for h in hs:
            h.wait(timeout)

    procs, ports = _boot_ring_servers(2)
    plain_round_ms = None
    try:
        sess = ring_session(ports)
        for _ in range(3):                   # init + warm
            round_all(sess)
        t0 = time.perf_counter()
        round_all(sess)
        healthy_ms = (time.perf_counter() - t0) * 1e3
        plain_round_ms = healthy_ms          # replication-off baseline

        t0 = time.perf_counter()
        drain_doc = sess.drain_server(1)
        round_all(sess)                      # first fully re-homed round
        migration_ms = (time.perf_counter() - t0) * 1e3 - healthy_ms
        stats = sess.transport_stats()
        sess.close()
        print(json.dumps({
            "metric": "migration_ms",
            "value": round(migration_ms, 1),
            "unit": "ms",
            "vs_baseline": round(migration_ms / max(healthy_ms, 1e-3), 2),
            "detail": {
                "healthy_round_ms": round(healthy_ms, 1),
                "keys": len(keys),
                "ring_epoch": drain_doc.get("epoch"),
                "ring_redirects": stats.get("ring_redirects", 0),
                "note": "drain_server(1) (state handoff via CMD_MIGRATE "
                        "+ kMoved redirects) plus the first post-drain "
                        "round, minus a healthy round",
                **_device_stamp(),
            },
        }))
    finally:
        for p in procs:
            p.kill()
            p.wait()

    # ---- server half: failover (permanent server death) -----------------
    # Chain replication + the auditor are ARMED here (BYTEPS_TPU_REPL /
    # BYTEPS_TPU_AUDIT): the record proves the zero-loss law — the
    # SIGKILLed server's ranges resume from its ring successor's
    # replica, the audit cross-check counts the lost rounds (must be 0),
    # and the healthy-round delta vs the replication-off drain half
    # above prices what the protection costs on the publish path.
    procs, ports = _boot_ring_servers(
        2, extra_env={"BYTEPS_TPU_REPL": "1", "BYTEPS_TPU_AUDIT": "1"})
    os.environ["BYTEPS_TPU_REPL"] = "1"      # client-side reconcile law
    try:
        sess = ring_session(ports, srv_evict=evict_s, audit=True)
        for _ in range(3):
            round_all(sess)
        t0 = time.perf_counter()
        round_all(sess)
        healthy_ms = (time.perf_counter() - t0) * 1e3

        procs[1].kill()                      # the PS process is GONE
        procs[1].wait()
        t0 = time.perf_counter()
        round_all(sess, timeout=120)         # blocks until failover lands
        server_failover_ms = (time.perf_counter() - t0) * 1e3 - healthy_ms
        round_all(sess)                      # a clean post-failover round
        audit = sess.audit_check()
        lost_rounds = len(audit.get("lost_rounds") or ())
        stats = sess.transport_stats()
        srv = sess.server_stats()
        ring_epoch = sess.get_ring().get("epoch")
        sess.close()
        print(json.dumps({
            "metric": "server_failover_ms",
            "value": round(server_failover_ms, 1),
            "unit": "ms",
            "vs_baseline": round(server_failover_ms / (evict_s * 1e3), 2),
            "detail": {
                "healthy_round_ms": round(healthy_ms, 1),
                "server_evict_timeout_s": evict_s,
                "ring_epoch": ring_epoch,
                "server_failovers": stats.get("server_failovers", 0),
                "replayed_pushes": stats.get("replayed_pushes", 0),
                "repl_promotions": srv.get("repl_promotions", 0),
                "note": "SIGKILL of 1-of-2 ring servers with chain "
                        "replication armed; value = blocked round "
                        "(down-detect + ring epoch + replica adoption + "
                        "open-round re-push) minus a healthy round",
                **_device_stamp(),
            },
        }))
        print(json.dumps({
            "metric": "failover_lost_rounds",
            "value": lost_rounds,
            "unit": "rounds",
            "vs_baseline": 0.0,
            "detail": {
                "audit_mismatches": len(audit.get("mismatches") or ()),
                "audit_compared": audit.get("compared", 0),
                "repl_promotions": srv.get("repl_promotions", 0),
                "note": "audit cross-check after a SIGKILL failover "
                        "with BYTEPS_TPU_REPL=1 — the zero-loss law "
                        "says this is 0, always",
                **_device_stamp(),
            },
        }))
        if plain_round_ms:
            overhead_pct = (healthy_ms - plain_round_ms) \
                / max(plain_round_ms, 1e-3) * 100.0
            print(json.dumps({
                "metric": "repl_overhead_pct",
                "value": round(overhead_pct, 1),
                "unit": "pct",
                "vs_baseline": round(healthy_ms
                                     / max(plain_round_ms, 1e-3), 2),
                "detail": {
                    "repl_on_round_ms": round(healthy_ms, 1),
                    "repl_off_round_ms": round(plain_round_ms, 1),
                    "repl_bytes_total": srv.get("repl_bytes_total", 0),
                    "note": "healthy sync-round time with chain "
                            "replication armed vs off (same keys, same "
                            "tier) — the ack gate holds pulls for the "
                            "successor ack, so this prices the publish-"
                            "path cost of the zero-loss law",
                    **_device_stamp(),
                },
            }))
    finally:
        os.environ.pop("BYTEPS_TPU_REPL", None)
        for p in procs:
            p.kill()
            p.wait()


def bench_telemetry():
    """Telemetry-overhead benchmark: sync-round time with the metrics
    plane HOT (endpoint up + a scraper polling it + CMD_STATS refresh)
    vs OFF (BYTEPS_TPU_METRICS_PORT=0: no exporter, nothing scraping).

    The registry's per-partition feeds (push RTT / queue wait observes)
    are always on — they are lock-free and O(ns)-class, asserted by
    tests/test_telemetry.py — so the measurable cost of the telemetry
    subsystem is the export plane, and `telemetry_overhead_ms` is
    expected to sit within round-to-round noise.  Host-only, like
    BENCH_PS.  detail also reports the measured per-inc registry cost.
    """
    import threading
    import urllib.request

    import numpy as np

    from byteps_tpu.common import telemetry as tm
    from byteps_tpu.server.client import PSSession

    reps = int(os.environ.get("BENCH_TELEMETRY_REPS", "30"))
    proc, port = _boot_ps_server(engine_threads=2)
    try:
        sess = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1)
        x = np.random.default_rng(0).standard_normal(
            1 << 20, dtype=np.float32)            # 4 MB, one partition
        sess.push_pull(1, x)                      # init + warm

        def rounds(n):
            times = []
            for _ in range(n):
                t0 = time.perf_counter()
                sess.push_pull(1, x)
                times.append(time.perf_counter() - t0)
            return times

        rounds(5)                                 # settle
        off = rounds(reps)                        # export plane off

        # _free_port is bind-then-close (TOCTOU): another process can take
        # the port before the exporter rebinds it — retry on a fresh one,
        # the same mitigation as _boot_ps_server.
        for attempt in range(4):
            try:
                exporter = tm.TelemetryExporter(
                    tm.get_registry(), port=_free_port(),
                    refresh=lambda: sess.server_stats()).start()
                break
            except OSError:
                if attempt == 3:
                    raise
        stop = threading.Event()

        def scrape():
            url = f"http://127.0.0.1:{exporter.port}/metrics"
            while not stop.is_set():
                try:
                    urllib.request.urlopen(url, timeout=2).read()
                except OSError:
                    pass
                stop.wait(0.05)                   # 20 scrapes/s: hostile

        scraper = threading.Thread(target=scrape, daemon=True)
        scraper.start()
        rounds(5)                                 # settle under scrape
        hot = rounds(reps)                        # export plane hot
        stop.set()
        scraper.join(timeout=5)
        exporter.stop()
        sess.close()

        # Per-inc registry cost, measured inline (the fast test asserts
        # the bound; this records the number alongside the round delta).
        c = tm.get_registry().counter("bench_telemetry_probe")
        n_inc = 200_000
        t0 = time.perf_counter()
        for _ in range(n_inc):
            c.inc()
        inc_ns = (time.perf_counter() - t0) / n_inc * 1e9

        off_med = sorted(off)[len(off) // 2]
        hot_med = sorted(hot)[len(hot) // 2]
        delta_ms = (hot_med - off_med) * 1e3
        print(json.dumps({
            "metric": "telemetry_overhead_ms",
            "value": round(delta_ms, 3),
            "unit": "ms",
            "vs_baseline": round(hot_med / off_med, 3),
            "detail": {
                "round_off_median_ms": round(off_med * 1e3, 2),
                "round_hot_median_ms": round(hot_med * 1e3, 2),
                "reps": reps,
                "scrape_hz": 20,
                "registry_inc_ns": round(inc_ns, 1),
                "note": "value = median 4MB sync round with the metrics "
                        "endpoint scraped at 20Hz (+CMD_STATS refresh "
                        "per scrape) minus median with the export plane "
                        "off; expected within round-to-round noise",
                **_device_stamp(),
            },
        }))
    finally:
        proc.kill()
        proc.wait()


def bench_audit():
    """Auditor-overhead benchmark (BENCH_AUDIT=1): sync-round time with
    the value-domain consistency auditor HOT (server publish digests +
    pull trailers + worker re-digest + health sampling every round) vs
    OFF (BYTEPS_TPU_AUDIT unset: the wire is byte-identical to
    pre-audit, asserted by tests/test_audit.py).

    `audit_overhead_ms` is the median per-round delta for a 4 MB
    partition; expected within round-to-round noise — the armed cost is
    one CRC pass over the published buffer per publish (server), one
    per pull (worker, off the receiver thread), and the trailer's loss
    of the zero-copy pull sink (one 4 MB body copy).  Host-only, like
    BENCH_PS; mirrors BENCH_TELEMETRY.
    """
    import numpy as np

    from byteps_tpu.server.client import PSSession

    reps = int(os.environ.get("BENCH_AUDIT_REPS", "30"))
    x = np.random.default_rng(0).standard_normal(
        1 << 20, dtype=np.float32)                # 4 MB, one partition

    def measure(audit: bool, health: int) -> tuple:
        extra = {"BYTEPS_TPU_AUDIT": "1"} if audit else {}
        proc, port = _boot_ps_server(engine_threads=2, extra_env=extra)
        try:
            sess = PSSession(["127.0.0.1"], [port], worker_id=0,
                             num_servers=1, audit=audit,
                             health_sample_rounds=health)
            sess.push_pull(1, x)                  # init + warm
            for _ in range(5):                    # settle
                sess.push_pull(1, x)
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                sess.push_pull(1, x)
                times.append(time.perf_counter() - t0)
            checked = sess.audit_stats()["checked"] if audit else 0
            sess.close()
            return sorted(times)[len(times) // 2], checked
        finally:
            proc.kill()
            proc.wait()

    off_med, _ = measure(audit=False, health=0)
    hot_med, checked = measure(audit=True, health=0)
    health_med, _ = measure(audit=True, health=1)
    delta_ms = (hot_med - off_med) * 1e3
    print(json.dumps({
        "metric": "audit_overhead_ms",
        "value": round(delta_ms, 3),
        "unit": "ms",
        "vs_baseline": round(hot_med / off_med, 3),
        "detail": {
            "round_off_median_ms": round(off_med * 1e3, 2),
            "round_hot_median_ms": round(hot_med * 1e3, 2),
            "round_hot_health1_median_ms": round(health_med * 1e3, 2),
            "reps": reps,
            "audited_pulls": int(checked),
            "note": "value = median 4MB sync round with publish digests "
                    "+ pull trailers + worker re-digest (verify runs "
                    "off the critical path) minus median with the "
                    "auditor off; expected within round-to-round noise. "
                    "round_hot_health1 additionally samples gradient "
                    "health EVERY round (BYTEPS_TPU_HEALTH_SAMPLE_"
                    "ROUNDS=1, the max-hostile cadence)",
            **_device_stamp(),
        },
    }))


def bench_doctor():
    """Signal-plane overhead benchmark (BENCH_DOCTOR=1): sync-round time
    with the windowed key-signal plane + doctor rules HOT (window
    rolling every 0.5 s, per-part feeds live, CMD_STATS refresh per
    window, all 9 rules evaluated) vs OFF (BYTEPS_TPU_SIGNAL_WINDOW_S=0
    semantics: the module plane is None and every feed is a global
    read + None check).

    `signal_plane_overhead_ms` is the median per-round delta for a 4 MB
    partition, expected within round-to-round noise — the armed
    hot-path cost is one small dict update under a short lock per
    partition round trip; the per-window cost (one registry snapshot +
    rule pass, measured separately as `window_roll_ms`) runs on its own
    thread once per window.  Host-only, like BENCH_PS; mirrors
    BENCH_TELEMETRY.
    """
    import numpy as np

    from byteps_tpu.common import doctor as doctor_mod
    from byteps_tpu.common import signals
    from byteps_tpu.server.client import PSSession

    reps = int(os.environ.get("BENCH_DOCTOR_REPS", "30"))
    proc, port = _boot_ps_server(engine_threads=2)
    try:
        sess = PSSession(["127.0.0.1"], [port], worker_id=0,
                         num_servers=1)
        x = np.random.default_rng(0).standard_normal(
            1 << 20, dtype=np.float32)            # 4 MB, one partition
        sess.push_pull(1, x)                      # init + warm

        def rounds(n):
            times = []
            for _ in range(n):
                t0 = time.perf_counter()
                sess.push_pull(1, x)
                times.append(time.perf_counter() - t0)
            return times

        rounds(5)                                 # settle
        off = rounds(reps)                        # plane off (None)

        eng = doctor_mod.DoctorEngine()
        plane = signals.arm(
            window_s=0.5, history=32,
            refresh=lambda: sess.server_stats(),
            providers={"transport": sess.transport_stats},
            on_window=eng.observe)
        rounds(5)                                 # settle under windows
        hot = rounds(reps)                        # plane + doctor hot

        # Per-window roll cost over LOADED windows: the background
        # thread drains the accumulators every 0.5s, so stop it and
        # feed one round before each timed roll — timing back-to-back
        # rolls would fold empty windows and underreport exactly the
        # per-key work this number exists to quantify.
        signals.disarm()
        plane = signals.arm(
            window_s=60.0, history=32, start_thread=False,
            refresh=lambda: sess.server_stats(),
            providers={"transport": sess.transport_stats},
            on_window=eng.observe)
        rounds(1)
        keys_seen = len(plane.roll()["keys"])
        n_rolls = 10
        roll_total = 0.0
        for _ in range(n_rolls):
            rounds(1)                         # re-load the window
            t0 = time.perf_counter()
            plane.roll()
            roll_total += time.perf_counter() - t0
        roll_ms = roll_total / n_rolls * 1e3
        signals.disarm()
        sess.close()

        off_med = sorted(off)[len(off) // 2]
        hot_med = sorted(hot)[len(hot) // 2]
        delta_ms = (hot_med - off_med) * 1e3
        print(json.dumps({
            "metric": "signal_plane_overhead_ms",
            "value": round(delta_ms, 3),
            "unit": "ms",
            "vs_baseline": round(hot_med / off_med, 3),
            "detail": {
                "round_off_median_ms": round(off_med * 1e3, 2),
                "round_hot_median_ms": round(hot_med * 1e3, 2),
                "window_roll_ms": round(roll_ms, 3),
                "window_s": 0.5,
                "reps": reps,
                "keys_tracked": keys_seen,
                "note": "value = median 4MB sync round with the signal "
                        "plane rolling 0.5s windows + doctor rules + "
                        "CMD_STATS refresh per window minus median "
                        "with the plane off; expected within "
                        "round-to-round noise.  window_roll_ms is the "
                        "off-thread per-window cost (registry snapshot "
                        "+ classification + 9-rule pass)",
                **_device_stamp(),
            },
        }))
    finally:
        proc.kill()
        proc.wait()


def bench_fleet():
    """Fleet-plane benchmark (BENCH_FLEET=1): the two headline numbers
    the observability plane is accountable for.

    `fleet_plane_overhead_ms` — median 4 MB sync-round time with the
    fleet plane HOT (0.5 s signal windows each publishing one
    CMD_WINDOW frame and fetching the merged CMD_FLEET view — the full
    armed per-window wire cost) minus median with the plane idle (fleet
    wire armed, nothing published).  The publish/fetch pair rides the
    window-roll thread, so the delta is expected within round-to-round
    noise — the armed-cost-off-critical-path law this bench exists to
    keep honest.  Lower is better.

    `fleet_goodput_pct` — the goodput ledger's compute share over the
    live merged view's last aligned window: wall-time partitioned
    EXACTLY into compute/wire/straggler-wait/stall/recovery/disruption
    (the partition is asserted inside the ledger).  Higher is better.
    Host-only, like BENCH_PS; mirrors BENCH_DOCTOR's shape.
    """
    import numpy as np

    from byteps_tpu.common import doctor as doctor_mod
    from byteps_tpu.common import goodput as goodput_mod
    from byteps_tpu.common import signals
    from byteps_tpu.server.client import PSSession

    reps = int(os.environ.get("BENCH_FLEET_REPS", "30"))
    proc, port = _boot_ps_server(engine_threads=2,
                                 extra_env={"BYTEPS_TPU_FLEET": "1"})
    try:
        sess = PSSession(["127.0.0.1"], [port], worker_id=0,
                         num_servers=1, fleet=True)
        if not sess._fleet_wire:
            raise RuntimeError("fleet bootstrap probe downgraded against "
                               "a fleet-armed server — wire bug")
        x = np.random.default_rng(0).standard_normal(
            1 << 20, dtype=np.float32)            # 4 MB, one partition
        sess.push_pull(1, x)                      # init + warm

        def rounds(n):
            times = []
            for _ in range(n):
                t0 = time.perf_counter()
                sess.push_pull(1, x)
                times.append(time.perf_counter() - t0)
            return times

        rounds(5)                                 # settle
        off = rounds(reps)                        # armed wire, idle plane

        published = {"n": 0}

        def _on_window(summary):
            doc = doctor_mod.fleet_publish_doc(
                summary, 0, clock=sess.fleet_clock_offset())
            if sess.publish_window(int(doc.get("window") or 0), doc):
                published["n"] += 1
            sess.fetch_fleet()

        signals.arm(window_s=0.5, history=32,
                    refresh=lambda: sess.server_stats(),
                    providers={"transport": sess.transport_stats},
                    on_window=_on_window)
        rounds(5)                                 # settle under windows
        hot = rounds(reps)                        # publish+fetch per window
        time.sleep(0.7)                           # let the last window roll
        view = sess.fetch_fleet()
        fw = doctor_mod.fleet_windows_from_view(view)
        signals.disarm()
        sess.close()
        if not fw:
            raise RuntimeError("no fleet window published over the run")
        ledger = goodput_mod.fleet_ledger(fw[-1])

        off_med = sorted(off)[len(off) // 2]
        hot_med = sorted(hot)[len(hot) // 2]
        delta_ms = (hot_med - off_med) * 1e3
        print(json.dumps({
            "metric": "fleet_goodput_pct",
            "value": round(ledger["goodput_pct"], 2),
            "unit": "pct",
            "detail": {
                "window": ledger["window"],
                "total_s": round(ledger["total_s"], 3),
                "seconds": {c: round(v, 4)
                            for c, v in ledger["seconds"].items()},
                "windows_published": published["n"],
                "note": "compute share of fleet wall-time from the "
                        "goodput ledger over the live merged CMD_FLEET "
                        "view's last aligned window; the six categories "
                        "sum exactly to the total (asserted)",
                **_device_stamp(),
            },
        }))
        print(json.dumps({
            "metric": "fleet_plane_overhead_ms",
            "value": round(delta_ms, 3),
            "unit": "ms",
            "vs_baseline": round(hot_med / off_med, 3),
            "detail": {
                "round_off_median_ms": round(off_med * 1e3, 2),
                "round_hot_median_ms": round(hot_med * 1e3, 2),
                "window_s": 0.5,
                "reps": reps,
                "windows_published": published["n"],
                "note": "value = median 4MB sync round with one "
                        "CMD_WINDOW publish + CMD_FLEET fetch per 0.5s "
                        "window minus median with the plane idle; the "
                        "pair rides the window-roll thread, so expected "
                        "within round-to-round noise",
                **_device_stamp(),
            },
        }))
    finally:
        proc.kill()
        proc.wait()


def bench_autotune():
    """Adaptive-compression benchmark (BENCH_AUTOTUNE=1): how close the
    self-tuning control loop gets an UNTUNED job to the HAND-TUNED
    config's step time — the ISSUE-13 headline.

    Workload: two 2 MB gradient keys + one 16 KiB bias key, synchronous
    push_pull rounds against the real native server over loopback.
    HAND-TUNED registers the expert config up front (onebit+EF on the
    big keys, the bias raw — what the class->action table in
    docs/gradient-compression.md prescribes for this shape).  UNTUNED
    starts everything raw with the tuner armed (0.4 s signal windows,
    hold=1): the tuner must discover the same assignment live through
    CMD_CODEC renegotiations, and the measured steady-state step time
    is compared.  `autotune_step_time_gap_pct` = (untuned_with_tuner -
    hand_tuned) / hand_tuned * 100; lower is better, 0 = converged.
    Per-key final codec assignments and tuner_switches_total ride the
    detail.  Host-only (no device backend), honest about the 2-core
    container: on a CPU-bound loopback the compressed and raw configs
    can land within noise, in which case the gap is honest noise around
    0 — the number being measured is the TUNER's convergence, not the
    codec's win.
    """
    import numpy as np

    from byteps_tpu.common import signals
    from byteps_tpu.common.tuner import Tuner
    from byteps_tpu.server.client import PSSession

    reps = int(os.environ.get("BENCH_AUTOTUNE_REPS", "40"))
    warm_s = float(os.environ.get("BENCH_AUTOTUNE_WARM_S", "4.0"))
    proc, port = _boot_ps_server(engine_threads=2)
    rng = np.random.default_rng(0)
    big_a = rng.standard_normal(1 << 19, dtype=np.float32)   # 2 MB
    big_b = rng.standard_normal(1 << 19, dtype=np.float32)   # 2 MB
    bias = rng.standard_normal(1 << 12, dtype=np.float32)    # 16 KiB

    def step(sess):
        hs = [sess.push_pull_async(1, big_a),
              sess.push_pull_async(2, big_b),
              sess.push_pull_async(3, bias)]
        for h in hs:
            h.wait()

    def timed_steps(sess, n):
        times = []
        for _ in range(n):
            t0 = time.perf_counter()
            step(sess)
            times.append(time.perf_counter() - t0)
        return sorted(times)[len(times) // 2]

    try:
        # --- hand-tuned: the expert assignment, fixed up front --------
        sess = PSSession(["127.0.0.1"], [port], worker_id=0,
                         num_servers=1)
        sess.register_compressor(1, {"compressor": "onebit",
                                     "ef": "vanilla"})
        sess.register_compressor(2, {"compressor": "onebit",
                                     "ef": "vanilla"})
        for _ in range(8):
            step(sess)                              # settle
        hand_med = timed_steps(sess, reps)
        sess.close()

        # --- untuned + tuner: starts raw, converges live --------------
        sess = PSSession(["127.0.0.1"], [port], worker_id=0,
                         num_servers=1)
        tuner = Tuner(sess, propose=True, hold=1, blacklist=4,
                      margin_rounds=2)
        plane = signals.arm(window_s=0.4, history=32,
                            on_window=tuner.observe)
        deadline = time.time() + warm_s
        warm_steps = 0
        while time.time() < deadline:
            step(sess)                              # tuner converges here
            warm_steps += 1
        tuned_med = timed_steps(sess, reps)
        signals.disarm()
        final = {k: v["name"] for k, v in sess.codec_table().items()}
        tstate = tuner.state()
        stale = sess.transport_stats()["codec_stale_retries"]
        sess.close()

        gap_pct = (tuned_med - hand_med) / hand_med * 100.0
        print(json.dumps({
            "metric": "autotune_step_time_gap_pct",
            "value": round(gap_pct, 2),
            "unit": "pct_gap",
            "vs_baseline": round(tuned_med / hand_med, 3),
            "detail": {
                "hand_tuned_step_ms": round(hand_med * 1e3, 3),
                "untuned_with_tuner_step_ms": round(tuned_med * 1e3, 3),
                "tuner_switches_total": tstate["switches_total"],
                "tuner_reverts_total": tstate["reverts_total"],
                "codec_stale_retries": stale,
                "final_codecs": final,
                "warm_steps": warm_steps,
                "reps": reps,
                "note": "value = (untuned-with-tuner - hand-tuned) / "
                        "hand-tuned step time in %, medians over "
                        f"{reps} steps after {warm_s:.0f}s of live "
                        "convergence; 0 = the tuner found the expert "
                        "config.  Loopback on a small host can put "
                        "both configs within noise — the number "
                        "measures tuner convergence, not codec wins",
                **_device_stamp(),
            },
        }))
    finally:
        proc.kill()
        proc.wait()


_KNOB_WORKER_CODE = """
import json, os, time
import numpy as np
import jax.numpy as jnp
import byteps_tpu as bps

reps = int(os.environ["KB_REPS"])
warm_s = float(os.environ["KB_WARM_S"])
expert = os.environ.get("KB_EXPERT", "0") == "1"
bps.init()
rng = np.random.default_rng(0)
tree = {}
# Two FC-sized gradients + a sheaf of layernorm-sized leaves: the
# mixed shape both the fusion planner and the codec dial care about.
tree["fc1.w"] = jnp.asarray(rng.standard_normal(1 << 19).astype(np.float32))
tree["fc2.w"] = jnp.asarray(rng.standard_normal(1 << 19).astype(np.float32))
for i in range(48):
    tree[f"ln{i:02d}.g"] = jnp.asarray(
        rng.standard_normal(1 << 10).astype(np.float32))
names = sorted(tree)
if expert:
    bps.register_compressor("fc1.w", {"compressor": "onebit",
                                      "ef": "vanilla"})
    bps.register_compressor("fc2.w", {"compressor": "onebit",
                                      "ef": "vanilla"})

def step():
    out = bps.push_pull_tree(tree, name="knobwl", average=False,
                             leaf_names=names)
    jnp.asarray(out["fc1.w"]).block_until_ready()

deadline = time.time() + warm_s
warm_steps = 0
while time.time() < deadline or warm_steps < 8:
    step()
    warm_steps += 1
times = []
for _ in range(reps):
    t0 = time.perf_counter()
    step()
    times.append(time.perf_counter() - t0)
med = sorted(times)[len(times) // 2]
tstate = {}
try:
    tstate = bps.get_tuner() or {}
except Exception:
    pass
print("KB_RESULT " + json.dumps({
    "step_ms": med * 1e3,
    "warm_steps": warm_steps,
    "knob_table": tstate.get("knob_table"),
    "predict_jumps_total": tstate.get("predict_jumps_total", 0),
    "switches_total": tstate.get("switches_total", 0),
    "cost_model": tstate.get("cost_model"),
    "final_codecs": {k: v.get("codec")
                     for k, v in (tstate.get("keys") or {}).items()},
}))
bps.shutdown()
"""


def bench_knob():
    """Knob-plane benchmark (BENCH_KNOB=1): a cold-start job whose
    predictive tuner must DISCOVER the global knobs live vs the same
    workload hand-tuned by an expert up front — the CMD_KNOB headline.

    Both arms launch the same mixed-key workload (two 2 MB FC gradients
    + 48 layernorm-sized 4 KiB leaves through push_pull_tree) with a
    deliberately naive launch config (64 KiB fusion buckets, raw
    codecs).  EXPERT overrides up front: 256 KiB fusion (one bucket
    holds the whole layernorm sheaf) and onebit+EF on the FC keys.
    COLD keeps the naive launch but arms the tuner with a persisted
    codec cost model (seeded here by an in-tree
    ``wire_bench --codec-sweep --quick --json`` run): it must
    predict-jump the FC codecs from the model and actuate
    FUSION_BYTES doublings through epoch-versioned CMD_KNOB sets at
    round boundaries, mid-job, no restart.
    ``knob_step_time_gap_pct`` = (cold - expert) / expert * 100; <= 0
    means the cold-start tuner matched or beat the expert.  The
    cost-model seed and COLD's final knob assignments ride the detail.
    Host-only loopback on a small container: both arms can land within
    noise (same honesty clause as BENCH_AUTOTUNE) — the number
    measures knob-plane convergence, not the knobs' absolute win.
    """
    import subprocess
    import sys
    import tempfile

    from byteps_tpu.utils.hermetic import cpu_subprocess_env

    reps = int(os.environ.get("BENCH_KNOB_REPS", "30"))
    warm_s = float(os.environ.get("BENCH_KNOB_WARM_S", "8.0"))

    # Seed the cost model at a bench-private path — never the operator's
    # real ~/.cache table.
    tmpdir = tempfile.mkdtemp(prefix="bench_knob_")
    model_path = os.path.join(tmpdir, "codec_cost_model.json")
    sweep = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools", "wire_bench.py"),
         "--codec-sweep", "--quick", "--json"],
        env=cpu_subprocess_env(
            {"BYTEPS_TPU_KNOB_COST_MODEL": model_path}),
        capture_output=True, text=True, timeout=600)
    if sweep.returncode != 0 or not os.path.exists(model_path):
        raise RuntimeError(f"cost-model seed sweep failed: "
                           f"{sweep.stderr[-500:]}")
    with open(model_path) as f:
        model_rows = len(json.load(f).get("codec_sweep") or [])

    def run_arm(extra_env: dict) -> dict:
        proc, port = _boot_ps_server(engine_threads=2)
        try:
            env = cpu_subprocess_env({
                "BYTEPS_TPU_PS_MODE": "1",
                "DMLC_NUM_WORKER": "1",
                "DMLC_NUM_SERVER": "1",
                "DMLC_PS_ROOT_PORT": str(port - 1),
                # The naive launch config both arms start from.
                "BYTEPS_TPU_FUSION_BYTES": str(64 << 10),
                "KB_REPS": str(reps),
                "KB_WARM_S": str(warm_s),
                **extra_env,
            })
            r = subprocess.run([sys.executable, "-c", _KNOB_WORKER_CODE],
                               env=env, capture_output=True, text=True,
                               timeout=900)
            if r.returncode != 0:
                raise RuntimeError(f"knob bench arm failed: "
                                   f"{r.stderr[-1500:]}")
            for line in r.stdout.splitlines():
                if line.startswith("KB_RESULT "):
                    return json.loads(line[len("KB_RESULT "):])
            raise RuntimeError(f"knob bench arm emitted no result: "
                               f"{r.stdout[-500:]}")
        finally:
            proc.kill()
            proc.wait()

    expert = run_arm({"KB_EXPERT": "1",
                      "BYTEPS_TPU_FUSION_BYTES": str(256 << 10)})
    cold = run_arm({"BYTEPS_TPU_TUNER": "1",
                    "BYTEPS_TPU_SIGNAL_WINDOW_S": "0.4",
                    "BYTEPS_TPU_TUNER_HOLD": "1",
                    "BYTEPS_TPU_KNOB_ACTUATE": "1",
                    "BYTEPS_TPU_KNOB_COST_MODEL": model_path})

    gap_pct = ((cold["step_ms"] - expert["step_ms"])
               / expert["step_ms"] * 100.0)
    print(json.dumps({
        "metric": "knob_step_time_gap_pct",
        "value": round(gap_pct, 2),
        "unit": "pct_gap",
        "vs_baseline": round(cold["step_ms"] / expert["step_ms"], 3),
        "detail": {
            "expert_step_ms": round(expert["step_ms"], 3),
            "cold_with_tuner_step_ms": round(cold["step_ms"], 3),
            "cost_model_path": model_path,
            "cost_model_rows": model_rows,
            "predict_jumps_total": cold.get("predict_jumps_total", 0),
            "tuner_switches_total": cold.get("switches_total", 0),
            "final_knob_table": cold.get("knob_table"),
            "final_codecs": cold.get("final_codecs"),
            "launch_fusion_bytes": 64 << 10,
            "expert_fusion_bytes": 256 << 10,
            "warm_steps": cold.get("warm_steps"),
            "reps": reps,
            "note": "value = (cold-start-with-predictive-tuner - "
                    "hand-tuned expert) / expert step time in %, "
                    f"medians over {reps} steps after {warm_s:.0f}s of "
                    "live convergence; <= 0 = the knob plane found the "
                    "expert config mid-job.  Loopback on a small host "
                    "can put both arms within noise — the number "
                    "measures knob-plane convergence, not the knobs' "
                    "absolute win",
            **_device_stamp(),
        },
    }))


def bench_hier():
    """Hierarchical-reduction benchmark (BENCH_HIER=1): the ISSUE-15
    headline — the same 4-worker synchronous workload run FLAT (every
    chip pushes/pulls the full gradient) and HIERARCHICAL (2 slices x 2
    chips: in-graph psum intra-slice, one leader per slice on the wire,
    broadcast back), against the real native server over loopback.

    Headline ``hier_wire_bytes_saved_pct`` = (1 - hier_bytes /
    flat_bytes) * 100 — structurally ~(1 - 1/S) for slice size S, read
    from the transport lane counters (payload bytes actually sent), with
    the step-time delta in the detail.  Host-only honesty: on a small
    loopback container the in-graph psum and the wire round trip share
    cores, so step time can land anywhere within noise — the number
    being measured is the wire traffic removed, which is what DCN-bound
    pods buy with this mode.
    """
    import threading

    import numpy as np

    from byteps_tpu.parallel.hierarchy import (HierarchicalReducer,
                                               reset_slice_groups)
    from byteps_tpu.server.client import PSSession

    reps = int(os.environ.get("BENCH_HIER_REPS", "30"))
    slice_size = max(1, int(os.environ.get("BENCH_HIER_SLICE", "2")))
    world = 4
    n = 1 << 18                       # 1 MiB f32 per worker per round
    rng = np.random.default_rng(0)
    grads = [rng.standard_normal(n).astype(np.float32)
             for _ in range(world)]

    def run(hier: bool) -> dict:
        reset_slice_groups()
        extra = ({"BYTEPS_TPU_SLICE_SIZE": str(slice_size)}
                 if hier else None)
        proc, port = _boot_ps_server(engine_threads=2, num_workers=world,
                                     extra_env=extra)
        try:
            sessions = [PSSession(["127.0.0.1"], [port], worker_id=w,
                                  num_servers=1, wire_conns=1,
                                  slice_size=slice_size if hier else 1)
                        for w in range(world)]
            reducers = ([HierarchicalReducer(s, w, slice_size,
                                             world=world)
                         for w, s in enumerate(sessions)]
                        if hier else None)
            times = []

            def worker(w, barrier):
                for r in range(reps + 3):
                    barrier.wait()
                    t0 = time.perf_counter()
                    if hier:
                        reducers[w].push_pull_flat(1, grads[w])
                    else:
                        sessions[w].push_pull_async(
                            1, grads[w]).wait(60)
                    if w == 0 and r >= 3:          # settle 3 rounds
                        times.append(time.perf_counter() - t0)

            barrier = threading.Barrier(world)
            ts = [threading.Thread(target=worker, args=(w, barrier))
                  for w in range(world)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=300)
            if any(t.is_alive() for t in ts):
                raise RuntimeError("bench worker hung")
            per_worker = [s.transport_stats()["lane_bytes_total"]
                          for s in sessions]
            for s in sessions:
                s.close()
            return {"step_ms": sorted(times)[len(times) // 2] * 1e3,
                    "bytes_per_worker": per_worker,
                    "bytes_total": int(sum(per_worker))}
        finally:
            proc.kill()
            proc.wait()

    flat = run(False)
    hier = run(True)
    saved_pct = (1.0 - hier["bytes_total"] / flat["bytes_total"]) * 100.0
    print(json.dumps({
        "metric": "hier_wire_bytes_saved_pct",
        "value": round(saved_pct, 2),
        "unit": "pct",
        "detail": {
            "slice_size": slice_size,
            "workers": world,
            "flat_bytes_total": flat["bytes_total"],
            "hier_bytes_total": hier["bytes_total"],
            "flat_bytes_per_worker": flat["bytes_per_worker"],
            "hier_bytes_per_worker": hier["bytes_per_worker"],
            "flat_step_ms": round(flat["step_ms"], 3),
            "hier_step_ms": round(hier["step_ms"], 3),
            "step_time_delta_pct": round(
                (hier["step_ms"] - flat["step_ms"])
                / flat["step_ms"] * 100.0, 2),
            "reps": reps,
            "note": "value = wire payload bytes removed by leaders-only "
                    "push_pull, ~(1 - 1/slice_size) by construction; "
                    "step-time delta on a loopback container shares "
                    "cores between the psum and the wire and is "
                    "reported as detail, not headline",
            **_device_stamp(),
        },
    }))


def bench_serveropt():
    """Server-resident-optimizer benchmark (BENCH_SERVEROPT=1): step
    time and per-worker optimizer-state bytes, server-side update stage
    vs the worker-local optax baseline, on the same workload — the
    ISSUE-14 headline.

    Workload: one ~4.2 MB flat Adam-trained parameter vector (two 2 MB
    "layers" + a 16 KiB bias, flattened — the BENCH_AUTOTUNE key mix),
    synchronous rounds against the real native server over loopback.
    LOCAL pulls the gradient sum and runs optax here (N workers would
    each hold the full m/v slots and run the identical step N times);
    SERVER pushes the same gradients and pulls post-update parameters
    (CMD_OPT — the slots live in the server's KeyState, once).
    `serveropt_step_time_gap_pct` = (server - local) / local * 100;
    lower is better, and the structural win is in the detail:
    `worker_opt_state_bytes` collapses to 0 in server mode while
    `server_opt_slot_bytes` picks the state up exactly once, and
    `param_version` == rounds proves exactly-one update.  Host-only
    honesty: on a 2-core loopback container the wire round trip
    dominates and the eliminated local optax pass can land within
    noise — the number being measured is the redundancy moved, not a
    loopback speedup.
    """
    import numpy as np

    from byteps_tpu.parallel.server_opt import ServerOptTrainer
    from byteps_tpu.server.client import PSSession

    reps = int(os.environ.get("BENCH_SERVEROPT_REPS", "30"))
    rng = np.random.default_rng(0)
    params = {"layer_a": rng.standard_normal(1 << 19, dtype=np.float32),
              "layer_b": rng.standard_normal(1 << 19, dtype=np.float32),
              "bias": rng.standard_normal(1 << 12, dtype=np.float32)}
    grads = {k: rng.standard_normal(v.shape, dtype=np.float32)
             for k, v in params.items()}
    kw = {"opt": "adam", "lr": 1e-3}

    results = {}
    for mode in ("local", "server"):
        proc, port = _boot_ps_server(engine_threads=2)
        try:
            sess = PSSession(["127.0.0.1"], [port], worker_id=0,
                             num_servers=1)
            tr = ServerOptTrainer(sess, params, kw,
                                  name=f"bench_{mode}", mode=mode)
            for _ in range(6):
                tr.step(grads)                      # settle
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                tr.step(grads)
                times.append(time.perf_counter() - t0)
            med = sorted(times)[len(times) // 2]
            st = sess.server_stats()
            results[mode] = {
                "step_ms": med * 1e3,
                "worker_opt_state_bytes": tr.opt_state_bytes(),
                "server_opt_slot_bytes": int(st.get("opt_slot_bytes",
                                                    0)),
                "opt_updates": int(st.get("opt_updates", 0)),
                "rounds": tr.rounds,
                "param_version": max(
                    [int(d.get("param_version", 0))
                     for d in tr.server_docs().values()] or [0]),
            }
            sess.close()
        finally:
            proc.kill()
            proc.wait()

    loc, srv = results["local"], results["server"]
    gap_pct = (srv["step_ms"] - loc["step_ms"]) / loc["step_ms"] * 100.0
    print(json.dumps({
        "metric": "serveropt_step_time_gap_pct",
        "value": round(gap_pct, 2),
        "unit": "pct_gap",
        "vs_baseline": round(srv["step_ms"] / loc["step_ms"], 3),
        "detail": {
            "local_step_ms": round(loc["step_ms"], 3),
            "server_step_ms": round(srv["step_ms"], 3),
            "local_worker_opt_state_bytes":
                loc["worker_opt_state_bytes"],
            "server_worker_opt_state_bytes":
                srv["worker_opt_state_bytes"],
            "server_opt_slot_bytes": srv["server_opt_slot_bytes"],
            "server_param_version": srv["param_version"],
            "server_rounds": srv["rounds"],
            "reps": reps,
            "note": "value = (server-resident - worker-local) / "
                    "worker-local Adam step time in %; the structural "
                    "claim is worker_opt_state_bytes -> 0 in server "
                    "mode (slots live once, server-side) and "
                    "param_version == rounds (exactly-one update). "
                    "Loopback on a small host can put both within "
                    "noise — the redundancy moved is the headline",
            **_device_stamp(),
        },
    }))


def bench_sparse():
    """Row-sparse embedding benchmark (BENCH_SPARSE=1): the PS tier as a
    recommendation-scale lookup tier — the ISSUE-17 headline.

    Workload: a server-resident rows x width f32 embedding table armed
    with row-wise Adagrad, driven by a zipfian id stream (the recsys
    shape: a small hot set absorbs most lookups).  Phase 1 trains
    sparse rounds (push (indices, rows), server steps exactly the
    touched rows, pull the post-update rows).  Phase 2 is the serving
    path: batched ungated row reads through the param_version-keyed
    hot-row LRU cache, where a warm zipf head costs ZERO wire frames.

    Headline `sparse_lookup_rows_per_s` = rows served per second over
    the read phase (higher is better); the structural numbers ride in
    the detail: `cache_hit_rate` (zipf head absorbed client-side),
    `p99_pull_ms` (tail of a batched read), and the wire-economy ratio
    `touched_frac` — the fraction of the table a training round
    actually shipped (dense push_pull would ship 1.0 every round).
    """
    import numpy as np

    from byteps_tpu.parallel.embedding import EmbeddingTable
    from byteps_tpu.server.client import PSSession

    rows = int(os.environ.get("BENCH_SPARSE_ROWS", "200000"))
    width = int(os.environ.get("BENCH_SPARSE_WIDTH", "64"))
    batch = int(os.environ.get("BENCH_SPARSE_BATCH", "4096"))
    rounds = int(os.environ.get("BENCH_SPARSE_ROUNDS", "15"))
    reads = int(os.environ.get("BENCH_SPARSE_READS", "60"))
    rng = np.random.default_rng(0)

    def zipf_ids(n):
        # rank-based zipfian over [0, rows): rejection-free fold of the
        # unbounded zipf draw onto the table (head stays the head).
        return (rng.zipf(1.2, n).astype(np.int64) - 1) % rows

    proc, port = _boot_ps_server(engine_threads=2)
    try:
        sess = PSSession(["127.0.0.1"], [port], worker_id=0,
                         num_servers=1)
        table = EmbeddingTable(
            sess, rows=rows, width=width, name="bench_emb",
            opt_kwargs={"opt": "adagrad", "lr": 0.05},
            init=lambda srows, w, s: np.zeros((srows, w), np.float32))

        touched = set()
        t0 = time.perf_counter()
        for _ in range(rounds):
            ids = zipf_ids(batch)
            touched.update(np.unique(ids).tolist())
            g = rng.standard_normal((batch, width)).astype(np.float32)
            table.push_pull(ids, g)
        train_s = time.perf_counter() - t0

        read_batches = [zipf_ids(batch) for _ in range(reads)]
        table.lookup(read_batches[0])               # settle / warm
        times = []
        t0 = time.perf_counter()
        for ids in read_batches:
            t1 = time.perf_counter()
            table.lookup(ids)
            times.append(time.perf_counter() - t1)
        read_s = time.perf_counter() - t0

        cs = sess.embed_cache_stats()
        st = sess.server_stats()
        sess.close()
    finally:
        proc.kill()
        proc.wait()

    total_read_rows = batch * len(read_batches)
    rows_per_s = total_read_rows / read_s
    hits, misses = cs.get("hits", 0), cs.get("misses", 0)
    hit_rate = hits / max(1, hits + misses)
    times.sort()
    p99_ms = times[min(len(times) - 1, int(0.99 * len(times)))] * 1e3
    print(json.dumps({
        "metric": "sparse_lookup_rows_per_s",
        "value": round(rows_per_s, 1),
        "unit": "rows_per_s",
        "detail": {
            "rows": rows, "width": width, "batch": batch,
            "train_rounds": rounds, "read_batches": reads,
            "cache_hit_rate": round(hit_rate, 4),
            "cache_hits": int(hits), "cache_misses": int(misses),
            "rows_cached": int(cs.get("rows_cached", 0)),
            "p99_pull_ms": round(p99_ms, 3),
            "p50_pull_ms": round(times[len(times) // 2] * 1e3, 3),
            "train_round_ms": round(train_s / max(1, rounds) * 1e3, 3),
            "touched_frac": round(len(touched) / rows, 4),
            "server_rows_served": int(st.get("embed_rows_served", 0)),
            "server_table_bytes": int(st.get("embed_table_bytes", 0)),
            "note": "value = rows served per second over the zipfian "
                    "read phase; the structural claims are "
                    "cache_hit_rate (the zipf head served with zero "
                    "wire frames) and touched_frac (a training round "
                    "ships that fraction of the table — dense "
                    "push_pull ships 1.0)",
            **_device_stamp(),
        },
    }))


def bench_trace():
    """Tracing-overhead benchmark: sync-round time with the distributed
    tracer HOT (worker span recording + traced wire flags + server-side
    span ring + clock sync) vs OFF (BYTEPS_TRACE_ON unset: untraced
    frames are byte-identical to the pre-trace wire, asserted by
    tests/test_trace.py).

    `trace_overhead_ms` is the median per-round delta; expected within
    round-to-round noise — the tracer's hot-path cost is a few clock
    reads and a mutex-guarded ring append per partition per stage.
    Host-only, like BENCH_PS; mirrors BENCH_TELEMETRY.
    """
    import tempfile

    import numpy as np

    from byteps_tpu.core.native import get_core
    from byteps_tpu.server.client import PSSession

    reps = int(os.environ.get("BENCH_TRACE_REPS", "30"))
    proc, port = _boot_ps_server(engine_threads=2)
    core = get_core()
    try:
        sess = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1)
        x = np.random.default_rng(0).standard_normal(
            1 << 20, dtype=np.float32)            # 4 MB, one partition

        def rounds(n):
            times = []
            for _ in range(n):
                t0 = time.perf_counter()
                sess.push_pull(1, x)
                times.append(time.perf_counter() - t0)
            return times

        sess.push_pull(1, x)                      # init + warm
        rounds(5)                                 # settle
        off = rounds(reps)                        # tracer off

        core.trace_enable(True)
        sess.sync_clocks()                        # the trace-enable leg
        rounds(5)                                 # settle traced
        hot = rounds(reps)                        # tracer hot
        worker_spans = core.trace_count()
        server_spans = sess.fetch_server_trace()
        core.trace_enable(False)
        # Drain the worker buffer so a later bench in the same process
        # never inherits this one's spans.
        core.trace_dump(os.path.join(tempfile.gettempdir(),
                                     "bps_bench_trace.json"), 0)
        sess.close()

        off_med = sorted(off)[len(off) // 2]
        hot_med = sorted(hot)[len(hot) // 2]
        delta_ms = (hot_med - off_med) * 1e3
        print(json.dumps({
            "metric": "trace_overhead_ms",
            "value": round(delta_ms, 3),
            "unit": "ms",
            "vs_baseline": round(hot_med / off_med, 3),
            "detail": {
                "round_off_median_ms": round(off_med * 1e3, 2),
                "round_hot_median_ms": round(hot_med * 1e3, 2),
                "reps": reps,
                "worker_spans": int(worker_spans),
                "server_spans": len(server_spans),
                "server_stages": sorted(
                    {s["stage"] for s in server_spans}),
                "note": "value = median 4MB sync round with worker+server "
                        "span recording on (traced wire flags, server "
                        "ring appends) minus median with tracing off; "
                        "expected within round-to-round noise",
                **_device_stamp(),
            },
        }))
    finally:
        proc.kill()
        proc.wait()


def _free_port() -> int:
    import socket
    with socket.socket() as sk:
        sk.bind(("127.0.0.1", 0))
        return sk.getsockname()[1]


def bench_ps():
    """PS-tier wire benchmark: push_pull goodput through the real native
    KV server over loopback TCP.

    The reference's only automated perf check is the ps-lite transport
    benchmark its CI runs (reference: .travis.yml:29-34); this is the
    analog for the TCP/req_id wire + C++ engine path (core/server.cc),
    measuring aggregate push+pull goodput for a 64MB tensor split into
    4MB partitions.  vs_baseline is self-calibrating: the fraction of this
    host's raw Python loopback echo floor (same socket API, no protocol,
    no summing, no store) that the full PS semantics sustain — the honest
    "how much does the KV layer cost over the transport" number.
    """
    import socket
    import subprocess
    import sys
    import threading

    import numpy as np

    from byteps_tpu.server.client import PSSession

    def echo_floor(nbytes: int, reps: int) -> float:
        """Raw synchronous send+recv echo over loopback — the transport
        ceiling for a Python client on this host."""
        srv = socket.socket()
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        eport = srv.getsockname()[1]

        def serve():
            c, _ = srv.accept()
            buf = bytearray(nbytes)
            view = memoryview(buf)
            for _ in range(reps + 1):
                got = 0
                while got < nbytes:
                    r = c.recv_into(view[got:], nbytes - got)
                    if r == 0:
                        return
                    got += r
                c.sendall(buf)

        th = threading.Thread(target=serve, daemon=True)
        th.start()
        c = socket.create_connection(("127.0.0.1", eport))
        data = bytes(nbytes)
        out = bytearray(nbytes)
        oview = memoryview(out)

        def rt():
            c.sendall(data)
            got = 0
            while got < nbytes:
                got += c.recv_into(oview[got:], nbytes - got)

        rt()  # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            rt()
        dt = time.perf_counter() - t0
        c.close()
        srv.close()
        return 2 * nbytes * reps / dt / 1e9

    from byteps_tpu.utils.hermetic import cpu_subprocess_env


    # BENCH_PS_COMPRESSOR: measure EFFECTIVE goodput with a compressed
    # wire — logical gradient bytes synced per second while the TCP link
    # carries the compressed stream (the reference's slow-network pitch:
    # compression buys wire bytes, docs/performance.md:5-26).  Accepts a
    # shorthand name or full "k=v,k=v" kwargs.
    comp_env = os.environ.get("BENCH_PS_COMPRESSOR", "")
    comp_presets = {
        "onebit": {"compressor": "onebit"},
        "dithering": {"compressor": "dithering", "k": "15", "seed": "5",
                      "partition": "linear", "normalize": "max"},
        "dithering_elias": {"compressor": "dithering", "k": "15",
                            "seed": "5", "partition": "linear",
                            "normalize": "max", "coding": "elias"},
    }
    comp_kw = None
    if comp_env:
        comp_kw = comp_presets.get(comp_env) or dict(
            kv.split("=", 1) for kv in comp_env.split(","))

    # Engines beyond the core count only add context switches to the
    # serve path (measured -10% goodput at 4 engines on a 1-core host).
    proc, port = _boot_ps_server(
        engine_threads=min(4, os.cpu_count() or 4))
    try:
        sess = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1,
                         wire_conns=int(os.environ.get(
                             "BYTEPS_TPU_WIRE_CONNS", "2")),
                         compress_threads=int(os.environ.get(
                             "BYTEPS_TPU_COMPRESS_THREADS", "2")),
                         **({"min_compress_bytes": 0} if comp_kw else {}))
        x = np.random.default_rng(0).standard_normal(
            16 << 20, dtype=np.float32)            # 64 MB
        wire_detail = {}
        if comp_kw:
            from byteps_tpu.server import wire as _wire
            sess.register_compressor(1, comp_kw)
            # Size one 4MB PARTITION (what the session actually ships, with
            # its own per-partition norm) — encoding the whole 64MB in one
            # call would also spike the elias emitter's per-bit temporaries.
            part = x[:1 << 20]
            blob = _wire.WireCompressor(dict(comp_kw)).encode(0, part)
            wire_detail = {
                "compressor": ",".join(f"{k}={v}"
                                       for k, v in sorted(comp_kw.items())),
                "wire_bytes_per_partition": len(blob),
                "wire_reduction": round(part.nbytes / len(blob), 2),
            }
            if comp_kw.get("coding") == "elias":
                # The bench tensor is dense standard-normal — the regime
                # where elias roughly ties the dense packing.  Also report
                # the heavy-tailed (sparse-quantizing) regime elias is FOR
                # (real gradients: most levels quantize to 0).
                sp = (part * (np.random.default_rng(1)
                              .random(part.size) < 0.1)).astype(np.float32)
                sblob = _wire.WireCompressor(dict(comp_kw)).encode(0, sp)
                wire_detail["wire_reduction_sparse_gradient"] = round(
                    sp.nbytes / len(sblob), 2)
        sess.push_pull(1, x)                       # init push + warm path
        reps = int(os.environ.get("BENCH_PS_REPS", "10"))
        t0 = time.perf_counter()
        for _ in range(reps):
            sess.push_pull(1, x)
        dt = time.perf_counter() - t0
        sess.close()
        goodput = 2 * x.nbytes * reps / dt / 1e9   # logical push+pull bytes
        floor = echo_floor(x.nbytes, reps)
        print(json.dumps({
            "metric": ("ps_wire_goodput_compressed" if comp_kw
                       else "ps_wire_goodput"),
            "value": round(goodput, 3),
            "unit": "GB/s",
            "vs_baseline": round(goodput / floor, 3),
            "detail": {
                "tensor_mbytes": round(x.nbytes / 1e6, 1),
                "reps": reps,
                "partitions": -(-x.nbytes // (4 << 20)),
                "transport": "loopback TCP, req_id-multiplexed",
                "raw_loopback_echo_floor_gbps": round(floor, 3),
                **wire_detail,
                "note": "vs_baseline = fraction of this host's raw Python "
                        "loopback echo floor sustained by full PS "
                        "semantics (partitioned, summed, round-tracked)"
                        + ("; goodput counts LOGICAL f32 bytes — the wire "
                           "carries the compressed stream" if comp_kw
                           else ""),
                **_device_stamp(),
            },
        }))
    finally:
        proc.kill()
        proc.wait()


def _error_record(err: str) -> None:
    print(json.dumps({
        "metric": "bench_backend_init",
        "value": 0.0,
        "unit": "error",
        "vs_baseline": 0.0,
        "detail": {"error": err, **_device_stamp()},
    }), flush=True)


def _device_mode(bench) -> None:
    """Run one device bench once, in this process.  No chip, a compile
    the chip refuses, an OOM: each is this process's non-zero exit, not a
    different run under the same metric's name."""
    from byteps_tpu.utils import compile_cache
    compile_cache.enable()
    _require_chip()
    bench()


def main():
    if os.environ.get("BENCH_FORCE_CPU", "0") == "1":
        from byteps_tpu.utils.hermetic import force_host_device_count
        if ("xla_force_host_platform_device_count"
                not in os.environ.get("XLA_FLAGS", "")):
            force_host_device_count(os.environ, 8)  # keep a user-set count
        import jax
        jax.config.update("jax_platforms", "cpu")
    if os.environ.get("BENCH_MACHINERY", "0") == "1":
        _device_mode(bench_machinery)
    elif os.environ.get("BENCH_PS", "0") == "1":
        bench_ps()           # host-only: no device backend involved
    elif os.environ.get("BENCH_WIRE", "0") == "1":
        bench_wire()         # host-only: no device backend involved
    elif os.environ.get("BENCH_FUSION", "0") == "1":
        bench_fusion()       # host-only: no device backend involved
    elif os.environ.get("BENCH_FAULT", "0") == "1":
        bench_fault()        # host-only: no device backend involved
    elif os.environ.get("BENCH_ELASTIC", "0") == "1":
        bench_elastic()      # host-only: no device backend involved
    elif os.environ.get("BENCH_TELEMETRY", "0") == "1":
        bench_telemetry()    # host-only: no device backend involved
    elif os.environ.get("BENCH_TRACE", "0") == "1":
        bench_trace()        # host-only: no device backend involved
    elif os.environ.get("BENCH_AUDIT", "0") == "1":
        bench_audit()        # host-only: no device backend involved
    elif os.environ.get("BENCH_DOCTOR", "0") == "1":
        bench_doctor()       # host-only: no device backend involved
    elif os.environ.get("BENCH_FLEET", "0") == "1":
        bench_fleet()        # host-only: no device backend involved
    elif os.environ.get("BENCH_SERVEROPT", "0") == "1":
        bench_serveropt()    # host-only: no device backend involved
    elif os.environ.get("BENCH_HIER", "0") == "1":
        bench_hier()         # host-only: no device backend involved
    elif os.environ.get("BENCH_AUTOTUNE", "0") == "1":
        bench_autotune()     # host-only: no device backend involved
    elif os.environ.get("BENCH_KNOB", "0") == "1":
        bench_knob()         # host-only: no device backend involved
    elif os.environ.get("BENCH_SPARSE", "0") == "1":
        bench_sparse()       # host-only: no device backend involved
    elif os.environ.get("BENCH_CNN", ""):
        from byteps_tpu.models.cnn import CNN_NAMES
        if os.environ["BENCH_CNN"] not in CNN_NAMES:
            _error_record(f"unknown BENCH_CNN={os.environ['BENCH_CNN']!r}; "
                          f"options: {sorted(CNN_NAMES)}")
            raise SystemExit(3)
        _device_mode(bench_cnn)
    else:
        _device_mode(bench_flagship)


if __name__ == "__main__":
    main()
