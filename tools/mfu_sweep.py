"""On-chip MFU sweep driver for the flagship bench.

Runs a list of bench configurations serially, each in its own disposable
subprocess, and records every JSON line to a results file.  A chip belongs
to one process at a time: this parent never touches JAX, so each child gets
the chip and releases it when it exits.  The children share one persistent
compilation cache (byteps_tpu.utils.compile_cache).

Usage:  python tools/mfu_sweep.py [results.jsonl]

Config list lives in SWEEP below — edit freely; each entry is a dict of
extra env vars layered on the flagship bench defaults.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Conventions:
# - an anchor of the current default opens a pass whenever the default
#   moved, so every sweep file self-calibrates against the same hour;
# - every entry pins BENCH_BATCH explicitly so a future default change
#   can't silently move an entry into a different memory regime;
# - entries that escalate memory carry `group`: once one entry of a
#   group fails (OOM), later entries of the SAME group are skipped.
#
# Order: (a) flagship anchor (self-calibration), (b) the CNN baseline
# rows, (c) the levers not measured yet (proj remat at b64/96, the
# no-remat ladder, asymmetric K tile at S=512, CE chunk ladder, unroll),
# (d) the long-context sweeps (llama batch escalation, llama_1b S=2048,
# S=8192 end-to-end).
SWEEP = [
    {"name": "flagship_anchor",
     "env": {"BENCH_BATCH": "64", "BENCH_COST": "1"}},
    # CNN rows: BS=64/chip like the reference's headline table
    # (reference docs/performance.md:5-12).  fp32, 224x224.
    {"name": "cnn_resnet50", "timeout": 1200,
     "env": {"BENCH_CNN": "resnet50", "BENCH_CNN_BATCH": "64"}},
    {"name": "cnn_vgg16", "timeout": 1200, "group": "cnn_vgg",
     "env": {"BENCH_CNN": "vgg16", "BENCH_CNN_BATCH": "64"}},
    # proj selective remat at the tuned batch: skips ~2/3 of the
    # recomputed matmul FLOPs vs full remat.  (The b96/no-remat
    # escalations live at the END of the list with the other OOM
    # candidates.)
    {"name": "flagship_proj_b64", "group": "proj",
     "env": {"BENCH_BATCH": "64", "BENCH_REMAT_POLICY": "proj"}},
    # No remat at all: zero recompute, activations live in HBM.  b16 is
    # the safe rung (flash keeps the S^2 logits out of HBM); the b24/32
    # escalation is at the tail with the other OOM risks.
    {"name": "flagship_noremat_b16", "group": "noremat",
     "env": {"BENCH_BATCH": "16", "BENCH_REMAT": "0"}},
    # Asymmetric tiles at the flagship geometry: narrow K tile trims
    # masked diagonal waste in the causal kernel.
    {"name": "flagship_q512_k256",
     "env": {"BENCH_BATCH": "64", "BENCH_ATTN_BLOCK_K": "256"}},
    # CE chunk ladder: 2048 is the tuned default; the sweep has never
    # measured either neighbor at batch 64.
    {"name": "flagship_ce4096",
     "env": {"BENCH_BATCH": "64", "BENCH_CE_CHUNK": "4096"}},
    {"name": "flagship_ce8192",
     "env": {"BENCH_BATCH": "64", "BENCH_CE_CHUNK": "8192"}},
    {"name": "flagship_unroll2",
     "env": {"BENCH_BATCH": "64", "BENCH_UNROLL": "2"}},
    # Long context: the batch escalation under blk512, then llama_1b at
    # S=2048.
    {"name": "l300m_b16_blk512", "group": "lbatch",
     "env": {"BENCH_MODEL": "llama_300m", "BENCH_ATTN": "flash",
             "BENCH_BATCH": "16", "BENCH_ATTN_BLOCK": "512"}},
    {"name": "l300m_b24_blk512", "group": "lbatch",
     "env": {"BENCH_MODEL": "llama_300m", "BENCH_ATTN": "flash",
             "BENCH_BATCH": "24", "BENCH_ATTN_BLOCK": "512"}},
    {"name": "l1b_s2048_blk512", "group": "l1b", "timeout": 1200,
     "env": {"BENCH_MODEL": "llama_1b", "BENCH_ATTN": "flash",
             "BENCH_BATCH": "4", "BENCH_ATTN_BLOCK": "512"}},
    {"name": "l1b_s2048_blk256", "group": "l1b", "timeout": 1200,
     "env": {"BENCH_MODEL": "llama_1b", "BENCH_ATTN": "flash",
             "BENCH_BATCH": "4", "BENCH_ATTN_BLOCK": "256"}},
    # Long-S selective remat: the O(S^2)-free proj policy at S=2048.
    {"name": "l300m_s2048_proj", "group": "lproj",
     "env": {"BENCH_MODEL": "llama_300m", "BENCH_ATTN": "flash",
             "BENCH_BATCH": "8", "BENCH_ATTN_BLOCK": "512",
             "BENCH_REMAT_POLICY": "proj"}},
    {"name": "l300m_s2048_noremat", "group": "lproj",
     "env": {"BENCH_MODEL": "llama_300m", "BENCH_ATTN": "flash",
             "BENCH_BATCH": "8", "BENCH_ATTN_BLOCK": "512",
             "BENCH_REMAT": "0"}},
    # S=8192 end-to-end: the streaming flash path inside a full train
    # step.  Grouped: the 8k compile is the memory-heavy one; an OOM
    # skips the second leg.
    {"name": "l300m_s8192_blk512", "group": "s8k", "timeout": 1200,
     "env": {"BENCH_MODEL": "llama_300m", "BENCH_SEQ": "8192",
             "BENCH_ATTN": "flash", "BENCH_BATCH": "1",
             "BENCH_ATTN_BLOCK": "512"}},
    {"name": "l300m_s8192_blk128", "group": "s8k", "timeout": 1200,
     "env": {"BENCH_MODEL": "llama_300m", "BENCH_SEQ": "8192",
             "BENCH_ATTN": "flash", "BENCH_BATCH": "1",
             "BENCH_ATTN_BLOCK": "128"}},
    # ---- memory-escalation tail: every entry below is an OOM
    # candidate.
    {"name": "flagship_noremat_b24", "group": "noremat",
     "env": {"BENCH_BATCH": "24", "BENCH_REMAT": "0"}},
    {"name": "flagship_noremat_b32", "group": "noremat",
     "env": {"BENCH_BATCH": "32", "BENCH_REMAT": "0"}},
    {"name": "flagship_proj_b96", "group": "proj",
     "env": {"BENCH_BATCH": "96", "BENCH_REMAT_POLICY": "proj"}},
]

sys.path.insert(0, REPO)
from byteps_tpu.utils.compile_cache import cache_dir  # noqa: E402


def run_one(entry: dict, timeout: float) -> dict:
    env = dict(os.environ)
    env.update(entry["env"])
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir()   # one cache per sweep
    t0 = time.time()
    try:
        r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                           env=env, timeout=timeout, capture_output=True,
                           text=True)
        rc, out, err = r.returncode, r.stdout, r.stderr
    except subprocess.TimeoutExpired as e:
        rc = 124
        out = (e.stdout or b"").decode(errors="replace") \
            if isinstance(e.stdout, bytes) else (e.stdout or "")
        err = (e.stderr or b"").decode(errors="replace") \
            if isinstance(e.stderr, bytes) else (e.stderr or "")
    rec = {"name": entry["name"], "env": entry["env"], "rc": rc,
           "ts": time.strftime("%Y-%m-%d %H:%M"),
           "wall_s": round(time.time() - t0, 1)}
    lines = [ln for ln in out.strip().splitlines() if ln.startswith("{")]
    try:
        if rc == 0 and lines:
            rec["result"] = json.loads(lines[-1])
        else:
            rec["stderr_tail"] = err[-1500:]
    except json.JSONDecodeError:
        # A half-flushed line from a dying child must not abort the sweep.
        rec["bad_stdout_tail"] = out[-500:]
        rec["stderr_tail"] = err[-1000:]
    return rec


def main() -> None:
    out_path = sys.argv[1] if len(sys.argv) > 1 else \
        os.path.join(REPO, "sweep_results.jsonl")
    timeout = float(os.environ.get("SWEEP_RUN_TIMEOUT", "700"))
    failed_groups = set()
    with open(out_path, "a") as f:
        for entry in SWEEP:
            if entry.get("group") in failed_groups:
                print(f"[sweep] skipping {entry['name']} (group "
                      f"{entry['group']!r} already failed)", file=sys.stderr)
                f.write(json.dumps({"name": entry["name"],
                                    "skipped": "group failed"}) + "\n")
                f.flush()
                continue
            print(f"[sweep] running {entry['name']} ...", file=sys.stderr)
            rec = run_one(entry, float(entry.get("timeout", timeout)))
            f.write(json.dumps(rec) + "\n")
            f.flush()
            if rec["rc"] != 0 and entry.get("group"):
                failed_groups.add(entry["group"])
            res = rec.get("result", {}).get("detail", {})
            print(f"[sweep] {entry['name']}: rc={rec['rc']} "
                  f"tok/s={res.get('tokens_per_sec_per_chip')} "
                  f"mfu={res.get('mfu')}", file=sys.stderr)
    print(f"[sweep] results in {out_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
