"""Smoke coverage for tools/wire_bench.py (the codec-pipeline microbench).

The full bench is a perf tool; this runs the `--quick` invocation end to
end (real native server subprocess, real codecs) and asserts the
pipeline's headline claim — a pipelined multi-partition compressed
push_pull holds the caller thread far below the
BYTEPS_TPU_COMPRESS_THREADS=0 inline mode's wall time — plus the
structural health of the JSON document.  Marked slow: it is a timing
test over subprocesses, not a unit test.
"""

import json
import os
import subprocess
import sys

import pytest

from testutil import cpu_env

_TOOL = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools", "wire_bench.py")


def _run_quick() -> dict:
    r = subprocess.run([sys.executable, _TOOL, "--quick", "--json"],
                       env=cpu_env(), capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout)


@pytest.mark.slow
def test_wire_bench_quick_smoke():
    doc = _run_quick()

    codecs = {row["codec"]: row for row in doc["codec"]}
    assert {"onebit", "dithering-dense", "dithering-elias"} <= set(codecs)
    for row in codecs.values():
        assert row["encode_MBps"] > 0 and row["decode_MBps"] > 0
        assert row["ratio"] > 1.0
    assert codecs["onebit"]["ratio"] == pytest.approx(32.0, rel=0.01)

    pl = doc["pipeline"]
    # The pool really did the encoding (inline mode really didn't).
    assert pl["pipelined"]["encoded_parts"] > 0
    assert pl["inline"]["encoded_parts"] == 0
    assert pl["partitions"] >= 4
    # The bidirectional A/B drove the DECODE half of the pipeline: pull
    # payloads decoded on pool threads, never on the receiver thread.
    bidi = doc["pipeline_bidirectional"]
    assert bidi["pipelined"]["decoded_parts"] > 0
    assert bidi["inline"]["decoded_parts"] == 0
    # The headline: the compressed push_pull's caller-block wall time
    # sits well below the inline fallback's — inline pays every
    # partition's encode on the caller thread before push_pull_async
    # returns (measured 8-30x on the 2-core dev host; asserting 2x
    # leaves a vast noise margin).
    assert pl["stat"] == "caller_block_best"
    assert pl["pipelined_s"] * 2 < pl["inline_s"], pl
    assert bidi["pipelined_s"] < bidi["inline_s"], bidi
    # Sync round-trips are reported for both modes and are sane.
    for mode in ("pipelined", "inline"):
        assert pl[mode]["sync_round_best_s"] > 0


@pytest.mark.slow
def test_wire_bench_codec_sweep_smoke(tmp_path):
    """--codec-sweep structural smoke (ISSUE 13 satellite): every dial
    codec reports throughput + ratio at every swept size, and the
    ratios land where the dial's documentation claims (onebit ~32x,
    qblock8 ~4x, qblock4 ~8x).  With --json the table is also
    PERSISTED machine-readable at the cost-model path (ISSUE 16: the
    predictive tuner's seed) — pinned to a tmp path here so the test
    never writes the operator's real ~/.cache table."""
    model = tmp_path / "cost_model.json"
    r = subprocess.run(
        [sys.executable, _TOOL, "--codec-sweep", "--quick", "--json"],
        env=cpu_env({"BYTEPS_TPU_KNOB_COST_MODEL": str(model)}),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    doc = json.loads(r.stdout)
    assert doc["cost_model_path"] == str(model)
    assert model.exists()
    persisted = json.loads(model.read_text())
    assert persisted["codec_sweep"] == doc["codec_sweep"]
    rows = doc["codec_sweep"]
    sizes = {row["size_bytes"] for row in rows}
    assert len(sizes) >= 2
    by = {(row["codec"], row["size_bytes"]): row for row in rows}
    for size in sizes:
        assert ("raw", size) in by
        for codec in ("onebit+ef", "elias+ef", "qblock8+ef",
                      "qblock4+ef"):
            row = by[(codec, size)]
            assert row["encode_MBps"] > 0 and row["decode_MBps"] > 0
            assert row["ratio"] > 1.0
        assert by[("onebit+ef", size)]["ratio"] == pytest.approx(
            32.0, rel=0.05)
        assert by[("qblock8+ef", size)]["ratio"] == pytest.approx(
            4.0, rel=0.05)
        assert by[("qblock4+ef", size)]["ratio"] == pytest.approx(
            8.0, rel=0.1)


@pytest.mark.slow
def test_wire_bench_sparse_sweep_smoke():
    """--sparse-sweep structural smoke (ISSUE 17 satellite): every
    (width, density) cell reports encode/decode rows/s and the
    index-codec choice, the dense-economy ratio tracks 1/density (a
    0.1%-touched round ships ~1000x fewer bytes than dense push_pull
    modulo index overhead), and elias gap coding never reports a ratio
    below raw (the encoder falls back to raw u32 when gaps don't
    pay)."""
    r = subprocess.run([sys.executable, _TOOL, "--sparse-sweep",
                        "--quick", "--json"],
                       env=cpu_env(), capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    doc = json.loads(r.stdout)
    rows = doc["sparse_sweep"]
    widths = {row["width"] for row in rows}
    densities = {row["density"] for row in rows}
    assert len(rows) == len(widths) * len(densities)
    assert min(densities) <= 0.001 and max(densities) >= 0.1
    table_rows = doc["config"]["table_rows"]
    for row in rows:
        assert row["encode_rows_per_s"] > 0
        assert row["decode_rows_per_s"] > 0
        assert row["idx_codec"] in ("raw", "elias")
        assert row["idx_codec_ratio"] >= 1.0, row
        assert row["nrows"] == max(1, int(table_rows * row["density"]))
        # Wire economy vs a dense round: the f32 rows dominate the
        # block, so the ratio lands within ~25% of 1/density (header
        # + index stream is the only overhead).
        assert row["dense_ratio"] > (1.0 / row["density"]) * 0.75, row


@pytest.mark.slow
@pytest.mark.parametrize("uds", [False, True], ids=["tcp", "uds"])
def test_wire_bench_echo_floor_smoke(uds):
    """--echo-floor structural smoke on both transports: the bench emits
    the pct_of_floor acceptance number itself (the package's floor probe,
    server/wire_floor.py, and PS goodput measured in interleaved batches
    on the SAME transport and lanes), the server's scatter path actually
    engaged, and the UDS run really rode AF_UNIX.
    No threshold on pct here — shared CI hosts swing the floor ~2x; the
    number is the host's to state (docs/performance.md "Transport")."""
    r = subprocess.run([sys.executable, _TOOL, "--quick", "--json",
                        "--echo-floor"] + (["--uds"] if uds else []),
                       env=cpu_env(), capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    ef = json.loads(r.stdout)["echo_floor"]
    assert ef["transport"] == ("uds" if uds else "tcp")
    assert ef["floor_gbps"] > 0 and ef["goodput_gbps"] > 0
    assert ef["lanes"] == 4                # the session's pool, dialled too
    assert ef["floor_out_gbps"] > 0 and ef["floor_in_gbps"] > 0
    assert ef["floor_gbps"] == max(ef["floor_batches_gbps"])
    assert ef["pct_of_floor"] == pytest.approx(
        100.0 * ef["goodput_gbps"] / ef["floor_gbps"], abs=0.1)
    assert ef["target_pct_of_floor"] == 85.0
    assert ef["partitions"] == 4          # 16 MB quick tensor, 4 MiB parts
    assert ef["scatter_frames"] > 0       # raw-f32 pushes scatter-received
    assert len(ef["floor_batches_gbps"]) == len(ef["goodput_batches_gbps"])


@pytest.mark.slow
def test_wire_bench_fusion_smoke():
    """Many-small-tensors scenario (--fusion-only): fusion must cut wire
    messages >= 4x (the headline structural claim — each bucket replaces
    its members' per-leaf chains), measurably reduce caller-block time,
    and dispatch buckets in priority-descending order (the overlap the
    single-vector fallback cannot have)."""
    r = subprocess.run([sys.executable, _TOOL, "--quick", "--json",
                        "--fusion-only"],
                       env=cpu_env(), capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    fus = json.loads(r.stdout)["fusion"]
    uf, fu = fus["unfused"], fus["fused"]
    # One chain per leaf unfused; >= 4x fewer messages fused (measured
    # ~25x at the 1 MiB threshold on 4-64 KiB leaves).
    assert uf["wire_messages_per_round"] == fus["num_leaves"]
    assert fus["wire_message_reduction"] >= 4.0, fus
    assert fu["wire_messages_per_round"] >= fu["buckets"]
    # The caller gets back to its compute measurably sooner: a handful of
    # staged dispatches instead of one per leaf.  Best-of comparison,
    # plain < (the absolute gap varies wildly with GIL/scheduler
    # contention on shared 2-core hosts — measured 1.7x on a bad run,
    # ~50x on a quiet one), plus the sync round, which is robustly
    # message-bound.
    assert fu["caller_block_best_s"] < uf["caller_block_best_s"], fus
    assert fus["sync_round_speedup"] >= 2.0, fus
    # Buckets left the worker in priority-descending (reverse backprop)
    # order — the trace-visible overlap contract.
    assert fus["priority_descending"] is True
