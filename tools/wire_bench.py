#!/usr/bin/env python
"""PS-wire codec microbenchmark.

Three sections, all CPU-only (no JAX, no accelerator):

  1. codec throughput — raw encode/decode MB/s and compression ratio per
     wire codec (`server/wire.py`, riding the C codec when built);
  2. pipeline A/B — a multi-partition compressed push_pull through the
     real native PS server over loopback, codec pipeline ON
     (BYTEPS_TPU_COMPRESS_THREADS=N) vs the inline fallback
     (COMPRESS_THREADS=0, encode on the caller thread / decode on the
     receiver thread).  Headline: the CALLER-BLOCK wall time — how long
     the compressed push_pull holds the caller thread before it can
     overlap its own step compute (inline pays every partition's encode
     there; the pipeline hands it to pool threads and returns in ~ms).
     Full sync round-trips are reported alongside (see pipeline_ab's
     docstring for the colocated-server caveat on small hosts);
  3. fusion A/B — the many-small-tensors regime (hundreds of layernorm
     scales / biases): per-leaf push_pull (one declare/push/ack chain per
     leaf) vs the fusion-bucket layer (common/fusion.py packing small
     leaves into ~BYTEPS_TPU_FUSION_BYTES buckets dispatched through
     PSSession.push_pull_group in priority-descending order).  Reports
     wire messages, caller-block time, and sync-round time per mode.

Usage:
    python tools/wire_bench.py [--quick] [--json] [--threads N]
                               [--mb MB] [--part-kb KB] [--rounds R]
                               [--fusion-only] [--fusion-leaves N]

--json prints a machine-readable result document on stdout (progress
lines go to stderr); tests/test_wire_bench.py runs `--quick --json` as
the `-m slow` smoke invocation.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import statistics
import subprocess
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from byteps_tpu.server import wire, wire_floor          # noqa: E402
from byteps_tpu.server.client import PSSession          # noqa: E402
from byteps_tpu.utils.hermetic import cpu_subprocess_env  # noqa: E402

# Codec set for the throughput section: the production wire formats.
_CODECS = [
    ("onebit", {"compressor": "onebit"}),
    ("onebit+ef", {"compressor": "onebit", "ef": "vanilla"}),
    ("dithering-dense", {"compressor": "dithering", "k": "15"}),
    ("dithering-elias", {"compressor": "dithering", "k": "15",
                         "coding": "elias"}),
    ("topk", {"compressor": "topk", "k": "4096"}),
    ("qblock8", {"compressor": "qblock", "bits": "8", "block": "256"}),
    ("qblock4+ef", {"compressor": "qblock", "bits": "4", "block": "256",
                    "ef": "vanilla"}),
]

# The adaptive-compression dial (common/tuner.py DIAL) for --codec-sweep:
# the sweep is the tuner's cost-model ground truth — per-codec
# encode/decode throughput and compression ratio across the real
# partition-size range, so the dial's "step harder under wire pressure"
# direction can be sanity-checked against measured numbers.
_SWEEP_CODECS = [
    ("onebit+ef", {"compressor": "onebit", "ef": "vanilla"}),
    ("elias+ef", {"compressor": "dithering", "k": "15",
                  "coding": "elias", "ef": "vanilla"}),
    ("qblock8+ef", {"compressor": "qblock", "bits": "8", "block": "256",
                    "ef": "vanilla"}),
    ("qblock4+ef", {"compressor": "qblock", "bits": "4", "block": "256",
                    "ef": "vanilla"}),
]


def codec_sweep(sizes_bytes, reps: int) -> list:
    """Per-(codec, size) encode/decode throughput + ratio table — the
    tuner's cost-model seed (``--codec-sweep``).  Sizes are partition
    payload bytes (f32 elements = bytes/4), spanning the fusion floor
    (64 KiB) to the 16 MiB receive-pool ceiling."""
    out = []
    for nbytes in sizes_bytes:
        n = nbytes // 4
        x = _gradient(n)
        raw_row = {"codec": "raw", "size_bytes": nbytes,
                   "encode_MBps": None, "decode_MBps": None, "ratio": 1.0}
        out.append(raw_row)
        for name, kw in _SWEEP_CODECS:
            wc = wire.WireCompressor(dict(kw))
            blob = wc.encode(1, x)                 # warm (+ EF state)
            t0 = time.perf_counter()
            for _ in range(reps):
                blob = wc.encode(1, x)
            enc = (time.perf_counter() - t0) / reps
            wire.decode(blob, n)                   # warm
            t0 = time.perf_counter()
            for _ in range(reps):
                wire.decode(blob, n)
            dec = (time.perf_counter() - t0) / reps
            row = {
                "codec": name,
                "size_bytes": nbytes,
                "encode_MBps": round(x.nbytes / enc / 1e6, 1),
                "decode_MBps": round(x.nbytes / dec / 1e6, 1),
                "ratio": round(x.nbytes / len(blob), 2),
                "wire_bytes": len(blob),
                "native": wire._c_wire() is not None,
            }
            out.append(row)
            _log(f"  {nbytes >> 10:6d} KiB  {name:12s} "
                 f"enc {row['encode_MBps']:8.1f} MB/s   "
                 f"dec {row['decode_MBps']:8.1f} MB/s   "
                 f"{row['ratio']:6.1f}x")
    return out


def sparse_sweep(table_rows: int, widths, densities, reps: int) -> list:
    """Per-(width, density) row-sparse block codec table — encode/decode
    rows/s and the index-codec ratio (``--sparse-sweep``).

    The row-sparse plane ships ``(indices, rows)`` blocks
    (wire.encode_sparse_block: 16-byte header + index stream + f32
    rows); the index stream picks elias-delta over gaps when strictly
    smaller than raw u32 LE.  This sweep answers the sizing questions
    docs/sparse-embedding.md points at: how many rows/s one core can
    frame at each embedding width, and how much the gap codec saves at
    recsys densities (sorted-unique zipfian-ish indices, where dense
    regions give small gaps)."""
    out = []
    rng = np.random.RandomState(7)
    for width in widths:
        for density in densities:
            nrows = max(1, int(table_rows * density))
            # Sorted-unique draw — the shape push_pull_sparse ships
            # after client-side coalescing (np.unique output).
            idx = np.unique(rng.choice(table_rows, size=nrows,
                                       replace=False).astype(np.uint32))
            rows = rng.randn(idx.size, width).astype(np.float32)
            blob = wire.encode_sparse_block(idx, rows, width)   # warm
            t0 = time.perf_counter()
            for _ in range(reps):
                blob = wire.encode_sparse_block(idx, rows, width)
            enc = (time.perf_counter() - t0) / reps
            wire.decode_sparse_block(blob)                      # warm
            t0 = time.perf_counter()
            for _ in range(reps):
                wire.decode_sparse_block(blob)
            dec = (time.perf_counter() - t0) / reps
            codec, stream = wire.encode_sparse_indices(idx)
            raw_idx = idx.size * 4
            row = {
                "width": width,
                "density": density,
                "nrows": int(idx.size),
                "encode_rows_per_s": round(idx.size / enc, 1),
                "decode_rows_per_s": round(idx.size / dec, 1),
                "wire_bytes": len(blob),
                "idx_codec": ("elias"
                              if codec == wire.SPARSE_CODEC_ELIAS
                              else "raw"),
                "idx_codec_ratio": round(
                    raw_idx / max(1, len(stream) or raw_idx), 3),
                "dense_ratio": round(table_rows * width * 4
                                     / len(blob), 1),
            }
            out.append(row)
            _log(f"  w={width:5d} d={density * 100:5.1f}% "
                 f"({idx.size:6d} rows)  "
                 f"enc {row['encode_rows_per_s'] / 1e6:7.2f} Mrow/s  "
                 f"dec {row['decode_rows_per_s'] / 1e6:7.2f} Mrow/s  "
                 f"idx={row['idx_codec']:5s} "
                 f"{row['idx_codec_ratio']:5.2f}x  "
                 f"vs-dense {row['dense_ratio']:7.1f}x")
    return out


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _gradient(n: int, seed: int = 1) -> np.ndarray:
    """Heavy-tailed sparse-ish gradient (the regime real training ships:
    most dithering levels quantize to 0, so elias has gaps to code)."""
    rng = np.random.RandomState(seed)
    return (rng.randn(n) * (rng.rand(n) < 0.2)).astype(np.float32)


def codec_throughput(n: int, reps: int) -> list:
    out = []
    x = _gradient(n)
    for name, kw in _CODECS:
        wc = wire.WireCompressor(dict(kw))
        blob = wc.encode(1, x)                     # warm (+ EF state)
        t0 = time.perf_counter()
        for _ in range(reps):
            blob = wc.encode(1, x)
        enc = (time.perf_counter() - t0) / reps
        wire.decode(blob, n)                       # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            wire.decode(blob, n)
        dec = (time.perf_counter() - t0) / reps
        row = {
            "codec": name,
            "encode_MBps": round(x.nbytes / enc / 1e6, 1),
            "decode_MBps": round(x.nbytes / dec / 1e6, 1),
            "ratio": round(x.nbytes / len(blob), 2),
            "native": wire._c_wire() is not None,
        }
        out.append(row)
        _log(f"  {name:17s} enc {row['encode_MBps']:8.1f} MB/s   "
             f"dec {row['decode_MBps']:8.1f} MB/s   {row['ratio']:5.1f}x")
    return out


def boot_server(extra_env=None):
    """Native PS server subprocess on a freshly-probed port (retried:
    another process can take the port between the probe and the bind)."""
    import tempfile
    for _ in range(4):
        with socket.socket() as sk:
            sk.bind(("127.0.0.1", 0))
            port = sk.getsockname()[1]
        env = cpu_subprocess_env({
            "DMLC_PS_ROOT_PORT": str(port - 1),
            "DMLC_NUM_WORKER": "1",
            "BYTEPS_SERVER_ENGINE_THREAD": str(min(4, os.cpu_count() or 4)),
            **(extra_env or {}),
        })
        errf = tempfile.TemporaryFile(mode="w+")
        proc = subprocess.Popen(
            [sys.executable, "-m", "byteps_tpu.server"],
            env=env, stdout=subprocess.DEVNULL, stderr=errf)
        deadline = time.time() + 30
        while True:
            try:
                socket.create_connection(("127.0.0.1", port), 0.5).close()
                return proc, port
            except OSError:
                if proc.poll() is not None:
                    errf.seek(0)
                    stderr = errf.read()[-500:]
                    errf.close()
                    if "in use" not in stderr.lower():
                        raise RuntimeError(
                            f"PS server died at startup "
                            f"(rc={proc.returncode}): {stderr}")
                    break               # lost the port race — retry fresh
                if time.time() > deadline:
                    proc.kill()
                    proc.wait()
                    raise RuntimeError("PS server did not come up")
                time.sleep(0.1)
    raise RuntimeError("PS server lost the port race 4 times")


def echo_floor_section(nbytes: int, part_bytes: int, reps: int,
                       uds: bool = False, wire_conns: int = 0) -> dict:
    """The ≥85%-of-wire-floor acceptance number, emitted by the bench
    instead of hand-calculated: the floor and full-PS raw push_pull
    goodput on the SAME host, transport and lanes, as a percentage.

    The floor is the package's own probe (server/wire_floor.py, the one a
    traced worker runs at shutdown): another PROCESS, as many lanes as
    the session holds, frames of its partition size streamed both ways
    at once (`duplex`).  The PS goodput counts logical push+pull bytes
    (2 * tensor bytes per round) against wall time — the same accounting
    as duplex's out+in — so pct_of_floor is exactly "how much of the
    achievable wire rate the full KV semantics (partitioned, summed,
    round-tracked) sustain"."""
    uds_path = f"/tmp/bps_wire_bench_{os.getpid()}" if uds else ""
    batches = 4
    batch_reps = max(2, reps // batches)
    _log(f"  wire floor ({nbytes / 1e6:.0f} MB, {batches} interleaved "
         f"batches x {batch_reps} reps, {'uds' if uds else 'tcp'}) ...")
    proc, port = boot_server(
        {"BYTEPS_TPU_SERVER_UDS": uds_path} if uds else None)
    try:
        kw = {"wire_conns": wire_conns} if wire_conns else {}
        sess = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1,
                         partition_bytes=part_bytes,
                         uds_path=uds_path, **kw)
        transports = sorted({c.transport
                             for pool in sess._data_conns for c in pool})
        x = np.random.default_rng(0).standard_normal(
            nbytes // 4).astype(np.float32)
        sess.push_pull(1, x)               # init + warm
        # INTERLEAVED best-of batches: on shared/small hosts the floor
        # itself swings ~2x with CPU-frequency and neighbor noise, so a
        # single floor-then-PS sequence reports whatever the host was
        # doing that second.  Alternating short batches and taking each
        # side's best compares like with like.
        probes, goods = [], []
        for _ in range(batches):
            got = wire_floor.probe_session(
                sess, bytes_out=nbytes * batch_reps,
                bytes_in=nbytes * batch_reps)
            if got is None:
                raise RuntimeError("the wire floor probe failed")
            probes.append(got)
            t0 = time.perf_counter()
            for _ in range(batch_reps):
                sess.push_pull(1, x)
            goods.append(2 * x.nbytes * batch_reps
                         / (time.perf_counter() - t0) / 1e9)
        floors = [p["duplex"]["GB_per_s"] for p in probes]
        floor, goodput = max(floors), max(goods)
        stats = sess.server_stats()
        tstats = sess.transport_stats()
        sess.close()
    finally:
        proc.kill()
        proc.wait()
    row = {
        "transport": "+".join(transports),
        "tensor_mb": round(nbytes / 1e6, 1),
        "partitions": (nbytes + part_bytes - 1) // part_bytes,
        "reps": batches * batch_reps,
        "lanes": probes[0]["lanes"],
        "floor_gbps": round(floor, 3),
        "floor_batches_gbps": [round(f, 3) for f in floors],
        "floor_out_gbps": round(max(p["out"]["GB_per_s"]
                                    for p in probes), 3),
        "floor_in_gbps": round(max(p["in"]["GB_per_s"]
                                   for p in probes), 3),
        "goodput_gbps": round(goodput, 3),
        "goodput_batches_gbps": [round(g, 3) for g in goods],
        "pct_of_floor": round(100.0 * goodput / floor, 1),
        "target_pct_of_floor": 85.0,
        "scatter_frames": stats.get("scatter_frames", 0),
        "pool_hits": tstats["pool_hits"],
    }
    _log(f"  {row['transport']:8s} floor {row['floor_gbps']:6.2f} GB/s   "
         f"PS {row['goodput_gbps']:6.2f} GB/s   "
         f"pct_of_floor {row['pct_of_floor']:5.1f}%")
    return row


def _timed_rounds(sess, key, data, rounds: int):
    """(caller_block, sync_round) second-pairs per round.

    caller_block = the push_pull_async() call's own duration: how long
    the CALLER thread is captive to codec work before it can go do the
    training step's compute.  sync_round = issue + wait, the full
    round-trip."""
    sess.push_pull(key, data)          # warm: INITs + first merge
    out = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        h = sess.push_pull_async(key, data)
        t1 = time.perf_counter()
        h.wait()
        out.append((t1 - t0, time.perf_counter() - t0))
    return out


def pipeline_ab(nbytes: int, part_bytes: int, rounds: int,
                threads: int, kw: dict) -> dict:
    """Compressed multi-partition push_pull, codec pipeline vs inline.

    Headline (`inline_s`/`pipelined_s`): best-of caller-block wall time —
    the wall time a compressed push_pull holds the CALLER thread, which
    is what the pipeline exists to remove (inline mode encodes every
    partition before push_pull_async returns; a training loop pays that
    serially against its step compute every iteration).  Best-of because
    shared hosts put noisy-neighbor stalls in the tail of both modes.

    `sync_round` (reported alongside): the full issue+wait round trip.
    NOTE an honest caveat: with the PS server COLOCATED on a small host
    (this bench's only option), total CPU is the binding resource, so
    overlapping encode with the server's merge buys little and the
    thread interleaving costs a few percent — parity-ish sync rounds
    here.  The overlap pays on deployment shapes: server on separate
    hardware, or workers with idle cores for the pool.
    """
    data = _gradient(nbytes // 4, seed=2)
    proc, port = boot_server()
    try:
        res = {}
        # Pipelined first, then inline: if anything, the later run enjoys
        # the warmer page cache, biasing AGAINST the pipeline claim.
        for label, ct, key in (("pipelined", threads, 7), ("inline", 0, 8)):
            s = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1,
                          partition_bytes=part_bytes, min_compress_bytes=0,
                          compress_threads=ct)
            s.register_compressor(key, dict(kw))
            times = _timed_rounds(s, key, data, rounds)
            blocks = [b for b, _ in times]
            syncs = [r for _, r in times]
            res[label] = {
                "caller_block_best_s": round(min(blocks), 5),
                "caller_block_median_s": round(
                    statistics.median(blocks), 5),
                "sync_round_best_s": round(min(syncs), 4),
                "sync_round_median_s": round(statistics.median(syncs), 4),
                "compress_threads": ct,
                **{k: v for k, v in s.codec_stats().items()
                   if k in ("encoded_parts", "decoded_parts",
                            "encode_busy_us", "decode_busy_us")},
            }
            s.close()
            r = res[label]
            _log(f"  {label:10s} (threads={ct}) caller-block best "
                 f"{r['caller_block_best_s'] * 1e3:7.2f} ms   sync round "
                 f"best {r['sync_round_best_s'] * 1e3:7.2f} ms  median "
                 f"{r['sync_round_median_s'] * 1e3:7.2f} ms")
        blk_i = res["inline"]["caller_block_best_s"]
        blk_p = res["pipelined"]["caller_block_best_s"]
        return {
            "tensor_mb": nbytes / 1e6,
            "partitions": (nbytes + part_bytes - 1) // part_bytes,
            "compressor": dict(kw),
            "rounds": rounds,
            "stat": "caller_block_best",
            "inline_s": blk_i,
            "pipelined_s": blk_p,
            "speedup": round(blk_i / blk_p, 2) if blk_p else 0.0,
            **res,
        }
    finally:
        proc.kill()
        proc.wait()


def fusion_ab(num_leaves: int, min_kb: int, max_kb: int, rounds: int,
              fusion_bytes: int) -> dict:
    """Many-small-tensors A/B: per-leaf push_pull vs fused buckets.

    The regime the fusion layer exists for: `num_leaves` gradients of
    min_kb-max_kb each (a transformer's layernorm scales and biases).
    Unfused, every leaf pays its own declare/push/ack chain — per-message
    overhead dominates at these sizes.  Fused, the planner packs them
    into ~fusion_bytes buckets, each riding ONE partition key through
    push_pull_group at the max member priority.

    Reported per mode: wire messages per round (PUSH dispatches; PULLs
    mirror them 1:1), caller-block wall time (issue-all duration — what
    the training loop pays before it can overlap its own compute; the
    fused figure honestly includes the bucket packing), and the full
    sync round.  `priority_descending` asserts the fused dispatch order
    the trace spans show: bucket 0 (last-layer grads) first.
    """
    from byteps_tpu.common import fusion

    rng = np.random.RandomState(3)
    sizes = [int(n) for n in rng.randint(
        min_kb * 1024 // 4, max_kb * 1024 // 4 + 1, num_leaves)]
    leaves = [rng.randn(n).astype(np.float32) for n in sizes]
    total_mb = sum(sizes) * 4 / 1e6
    proc, port = boot_server()
    try:
        res = {}
        s = PSSession(["127.0.0.1"], [port], worker_id=0, num_servers=1)

        # ---- unfused: one key chain per leaf, per-leaf priorities.
        base = 1000
        for i, l in enumerate(leaves):      # warm: INITs + first merge
            s.push_pull(base + i, l, priority=i)
        s.push_order = []
        s.record_push_order = True
        blocks, syncs = [], []
        for _ in range(rounds):
            t0 = time.perf_counter()
            hs = [s.push_pull_async(base + i, leaves[i], priority=i)
                  for i in range(num_leaves)]
            t1 = time.perf_counter()
            for h in hs:
                h.wait()
            blocks.append(t1 - t0)
            syncs.append(time.perf_counter() - t0)
        s.record_push_order = False
        res["unfused"] = {
            "wire_messages_per_round": len(s.push_order) // rounds,
            "caller_block_best_s": round(min(blocks), 5),
            "caller_block_median_s": round(statistics.median(blocks), 5),
            "sync_round_best_s": round(min(syncs), 4),
            "sync_round_median_s": round(statistics.median(syncs), 4),
        }

        # ---- fused: planner buckets through grouped staging.
        plan = fusion.plan_buckets(
            tuple((i, sizes[i], "float32", 4) for i in range(num_leaves)),
            fusion_bytes)
        bkey = {b.index: 2000 + b.index for b in plan.buckets}
        prio_of_key = {bkey[b.index]: b.priority for b in plan.buckets}
        solo_items = [(3000 + li, li) for li, _ in plan.solo]
        prio_of_key.update({k: p for k, p in solo_items})

        def build_items():
            items = [(bkey[b.index],
                      np.concatenate([leaves[li] for li, _ in b.members])
                      if len(b.members) > 1 else leaves[b.members[0][0]],
                      b.priority) for b in plan.buckets]
            items += [(k, leaves[li], p)
                      for (k, p), (li, _) in zip(solo_items, plan.solo)]
            items.sort(key=lambda it: -it[2])
            return items

        for h in s.push_pull_group(build_items()):    # warm
            h.wait()
        s.push_order = []
        s.record_push_order = True
        blocks, syncs = [], []
        for _ in range(rounds):
            t0 = time.perf_counter()
            hs = s.push_pull_group(build_items())
            t1 = time.perf_counter()
            for h in hs:
                h.wait()
            blocks.append(t1 - t0)
            syncs.append(time.perf_counter() - t0)
        s.record_push_order = False
        first_round = s.push_order[:len(s.push_order) // rounds]
        prios = [prio_of_key.get(pk >> 16, -1) for pk in first_round]
        res["fused"] = {
            "wire_messages_per_round": len(s.push_order) // rounds,
            "caller_block_best_s": round(min(blocks), 5),
            "caller_block_median_s": round(statistics.median(blocks), 5),
            "sync_round_best_s": round(min(syncs), 4),
            "sync_round_median_s": round(statistics.median(syncs), 4),
            "buckets": len(plan.buckets),
            "solo_leaves": len(plan.solo),
        }
        s.close()
        uf, fu = res["unfused"], res["fused"]
        for label, r in res.items():
            _log(f"  {label:8s} msgs/round {r['wire_messages_per_round']:4d}"
                 f"   caller-block best "
                 f"{r['caller_block_best_s'] * 1e3:8.2f} ms   sync best "
                 f"{r['sync_round_best_s'] * 1e3:8.2f} ms")
        return {
            "num_leaves": num_leaves,
            "leaf_kb": [min_kb, max_kb],
            "total_mb": round(total_mb, 2),
            "fusion_bytes": fusion_bytes,
            "rounds": rounds,
            "wire_message_reduction": round(
                uf["wire_messages_per_round"]
                / max(1, fu["wire_messages_per_round"]), 2),
            "caller_block_speedup": round(
                uf["caller_block_best_s"]
                / max(1e-9, fu["caller_block_best_s"]), 2),
            "sync_round_speedup": round(
                uf["sync_round_best_s"]
                / max(1e-9, fu["sync_round_best_s"]), 2),
            "priority_descending": all(
                a >= b for a, b in zip(prios, prios[1:])),
            **res,
        }
    finally:
        proc.kill()
        proc.wait()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small sizes / few reps (CI smoke)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable results on stdout")
    ap.add_argument("--threads", type=int, default=2,
                    help="codec pipeline width for the A/B (default 2)")
    ap.add_argument("--mb", type=float, default=None,
                    help="tensor size for the A/B in MB")
    ap.add_argument("--part-kb", type=int, default=None,
                    help="partition size in KB")
    ap.add_argument("--rounds", type=int, default=None,
                    help="timed push_pull rounds per mode")
    ap.add_argument("--fusion-only", action="store_true",
                    help="run only the many-small-tensors fusion A/B")
    ap.add_argument("--echo-floor", action="store_true",
                    help="run only the raw-speed section: raw socket echo "
                         "floor vs full-PS raw push_pull goodput on the "
                         "same transport, reported as pct_of_floor "
                         "(target >= 85)")
    ap.add_argument("--uds", action="store_true",
                    help="with --echo-floor: measure the AF_UNIX fast "
                         "path (floor AND PS session both ride UDS)")
    ap.add_argument("--wire-conns", type=int, default=0,
                    help="with --echo-floor: lane count override "
                         "(default: session default)")
    ap.add_argument("--no-fusion", action="store_true",
                    help="skip the fusion A/B (codec/pipeline sections "
                         "only, the pre-fusion bench surface)")
    ap.add_argument("--fusion-leaves", type=int, default=None,
                    help="leaf count for the fusion A/B (default 512, "
                         "128 with --quick)")
    ap.add_argument("--codec-sweep", action="store_true",
                    help="run only the per-codec encode/decode "
                         "throughput + ratio sweep across partition "
                         "sizes (64 KiB - 16 MiB) — the adaptive-"
                         "compression tuner's cost-model ground truth")
    ap.add_argument("--sparse-sweep", action="store_true",
                    help="run only the row-sparse block codec sweep: "
                         "encode/decode rows/s and index-codec ratio "
                         "across embedding widths 32-1024 and touched "
                         "densities 0.1%%-10%% "
                         "(docs/sparse-embedding.md)")
    args = ap.parse_args(argv)

    quick = args.quick
    n_codec = (1 << 18) if quick else (1 << 21)
    reps = 3 if quick else 10
    mb = args.mb if args.mb is not None else (8.0 if quick else 32.0)
    part_kb = args.part_kb or (512 if quick else 1024)
    rounds = args.rounds or (9 if quick else 15)

    if args.sparse_sweep:
        table_rows = 1 << 17 if quick else 1 << 20
        widths = [32, 256] if quick else [32, 64, 128, 256, 512, 1024]
        densities = ([0.001, 0.1] if quick
                     else [0.001, 0.003, 0.01, 0.03, 0.1])
        sweep_reps = 2 if quick else 5
        _log(f"wire_bench: sparse sweep ({table_rows} table rows, "
             f"{len(widths)} widths x {len(densities)} densities, "
             f"{sweep_reps} reps)")
        sweep = sparse_sweep(table_rows, widths, densities, sweep_reps)
        doc = {"sparse_sweep": sweep,
               "config": {"quick": quick, "table_rows": table_rows,
                          "cpus": os.cpu_count()}}
        if args.json:
            print(json.dumps(doc, indent=1))
        return 0

    if args.codec_sweep:
        sizes = ([64 << 10, 1 << 20] if quick
                 else [64 << 10, 256 << 10, 1 << 20, 4 << 20, 16 << 20])
        sweep_reps = 2 if quick else 5
        _log(f"wire_bench: codec sweep ({len(sizes)} sizes x "
             f"{len(_SWEEP_CODECS)} codecs, {sweep_reps} reps)")
        sweep = codec_sweep(sizes, sweep_reps)
        doc = {"codec_sweep": sweep,
               "config": {"quick": quick, "cpus": os.cpu_count(),
                          "native": wire._c_wire() is not None}}
        if args.json:
            # Persist the table machine-readable at the STABLE path the
            # predictive tuner seeds from (BYTEPS_TPU_KNOB_COST_MODEL,
            # default ~/.cache/byteps_tpu/codec_cost_model.json) — the
            # producer half of the cost-model contract.  Atomic rename
            # so a tuner loading mid-write never sees a torn file.
            from byteps_tpu.common.tuner import cost_model_path
            path = cost_model_path()
            try:
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(doc, f, indent=1)
                os.replace(tmp, path)
                doc["cost_model_path"] = path
                _log(f"wire_bench: cost model written to {path}")
            except OSError as e:
                _log(f"wire_bench: cost model NOT persisted: {e}")
            print(json.dumps(doc, indent=1))
        return 0

    if args.echo_floor:
        # The acceptance workload: 4 MiB partitions, raw f32, same-host
        # wire floor on the same transport and lanes.  16 MB tensor under --quick
        # keeps the CI smoke short; 64 MB otherwise.
        ef_bytes = (16 << 20) if quick else (64 << 20)
        ef_reps = args.rounds or (5 if quick else 15)
        _log(f"wire_bench: wire floor vs PS goodput "
             f"({ef_bytes >> 20} MB, 4 MiB partitions, {ef_reps} reps)")
        ef = echo_floor_section(ef_bytes, 4 << 20, ef_reps, uds=args.uds,
                                wire_conns=args.wire_conns)
        doc = {"echo_floor": ef,
               "config": {"quick": quick, "cpus": os.cpu_count()}}
        if args.json:
            print(json.dumps(doc, indent=1))
        return 0

    # Many-small-tensors fusion A/B (the transformer layernorm/bias tail):
    # 512 leaves of 4-64 KiB, fused at the 1 MiB default threshold.
    fus = None
    if not args.no_fusion:
        fus_leaves = args.fusion_leaves or (128 if quick else 512)
        fus_rounds = args.rounds or (5 if quick else 9)
        _log(f"wire_bench: fusion A/B ({fus_leaves} leaves of 4-64 KiB, "
             f"{fus_rounds} rounds)")
        fus = fusion_ab(fus_leaves, 4, 64, fus_rounds, 1 << 20)
        _log(f"  wire-message reduction "
             f"{fus['wire_message_reduction']:.1f}x   caller-block speedup "
             f"{fus['caller_block_speedup']:.1f}x   sync speedup "
             f"{fus['sync_round_speedup']:.1f}x   "
             f"priority_descending={fus['priority_descending']}")
    if args.fusion_only:
        doc = {"fusion": fus,
               "config": {"quick": quick, "cpus": os.cpu_count()}}
        if args.json:
            print(json.dumps(doc, indent=1))
        return 0

    _log(f"wire_bench: codec throughput ({n_codec} f32, {reps} reps)")
    codec = codec_throughput(n_codec, reps)

    # Encode-heavy codec for the headline A/B: elias dithering is the
    # reference's entropy coder and the costliest encoder in the set, the
    # regime the pipeline exists for.  No EF: the EF state lock would
    # serialize the pool's encoders (documented in docs/performance.md).
    ab_kw = {"compressor": "dithering", "k": "15", "coding": "elias"}
    _log(f"wire_bench: pipeline A/B ({mb:.0f} MB tensor, {part_kb} KB "
         f"partitions, {rounds} rounds, threads={args.threads})")
    pipeline = pipeline_ab(int(mb * 1e6), part_kb * 1024, rounds,
                           max(1, args.threads), ab_kw)
    _log(f"  caller-block speedup (inline/pipelined): "
         f"{pipeline['speedup']:.1f}x")

    # Bidirectional codec A/B: onebit's pull leg comes back re-compressed,
    # so this is the config that drives the DECODE half of the pipeline
    # (decoded_parts > 0 in the pipelined row proves the receiver thread
    # stayed codec-free); cheap codec, so the caller-block gap is smaller
    # — the elias A/B above stays the headline.
    bidi_kw = {"compressor": "onebit"}
    _log(f"wire_bench: bidirectional (decode-leg) A/B "
         f"({mb:.0f} MB tensor, onebit)")
    bidi = pipeline_ab(int(mb * 1e6), part_kb * 1024, rounds,
                       max(1, args.threads), bidi_kw)
    _log(f"  caller-block speedup (inline/pipelined): {bidi['speedup']:.1f}x"
         f"  decoded_parts={bidi['pipelined']['decoded_parts']}")

    doc = {"codec": codec, "pipeline": pipeline,
           "pipeline_bidirectional": bidi,
           **({"fusion": fus} if fus is not None else {}),
           "config": {"quick": quick, "threads": args.threads,
                      "cpus": os.cpu_count()}}
    if args.json:
        print(json.dumps(doc, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
