"""The reductions from traces and spans to numbers: interval arithmetic
on hand-made intervals, the xplane reader on a trace recorded on a TPU
v5e (data/tiny.xplane.pb: the five traced steps of a two-layer gpt2
through the in-graph job, as the harness captures them), the comm.json chain on a merged trace recorded from a CPU
PS round (data/comm/0/comm.json: five steps of the tiny PS cell)."""

import json
import os

import pytest

from benchmark.harness import tracecap
from benchmark.reduce import comm_chain, flash_cost, intervals, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_gaps_overlap():
    busy = intervals.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert busy == [(0, 3), (5, 7)]
    assert intervals.total(busy) == 5
    assert intervals.gaps(busy, 0, 10) == [(3, 5), (7, 10)]
    assert intervals.gaps(busy, 1, 6) == [(3, 5)]
    assert intervals.overlap([(0, 4), (6, 8)], [(2, 7)]) == 3
    assert intervals.total(busy) + intervals.total(
        intervals.gaps(busy, 0, 10)) == 10


@pytest.mark.parametrize("collectives,compute,exposed", [
    ([(0, 10)], [(0, 10)], 0),                  # wholly hidden
    ([(0, 10)], [], 10),                        # nothing to hide behind
    ([(0, 10)], [(2, 4), (6, 7)], 7),           # hidden in two pieces
    ([(0, 4), (2, 8)], [(3, 5)], 6),            # overlapping collectives
    ([(0, 4), (10, 14)], [(2, 12)], 4),         # the middle is compute only
], ids=["hidden", "bare", "pieces", "overlapping", "two"])
def test_exposed_collective_time(collectives, compute, exposed):
    assert intervals.exposed(collectives, compute) == exposed


def test_self_times_take_children_out():
    events = [("while", 0, 10), ("a", 1, 3), ("b", 3, 6), ("c", 12, 13),
              ("a", 13, 14), ("call", 4, 5)]
    own = intervals.self_times(events)
    assert own == {"while": 5, "a": 3, "b": 2, "call": 1, "c": 1}
    assert sum(own.values()) == intervals.total(
        intervals.union((s, e) for _, s, e in events))
    assert [n for n, _, _ in xplane.leaves(events)] == [
        "a", "call", "c", "a"]


def test_attribute_gaps_to_innermost_span():
    got = intervals.attribute([(0, 10), (20, 22)],
                              [("outer", 1, 9), ("inner", 3, 5)], "none")
    assert got == {"none": 4, "outer": 6, "inner": 2}


def test_instruction_text():
    text = ("%all-reduce-start.3 = (f32[1024]{0:T(1024)}, f32[1024]{0:T(1024)"
            "S(1)}) all-reduce-start(f32[1024]{0:T(1024)} %fusion.2), "
            "channel_id=3, replica_groups={{0,1,2,3}}")
    assert xplane.op_name(text) == "all-reduce-start.3"
    assert xplane.opcode(text) == "all-reduce-start"
    assert xplane.is_collective(text)
    fusion = ("%fusion.371 = bf16[4,256,128]{2,1,0:T(8,128)(2,1)} "
              "fusion(bf16[4,256,128]{2,1,0:T(8,128)(2,1)} %p), kind=kLoop")
    assert xplane.opcode(fusion) == "fusion"
    assert not xplane.is_collective(fusion)


@pytest.fixture(scope="module")
def ctx():
    trace = xplane.read(os.path.join(DATA, "tiny.xplane.pb"),
                        host_prefix=tracecap.PREFIX)
    return tracecap.Context(
        trace=trace, n_steps=5, first_step=3, n_chips=1, samples_per_step=2,
        family=None, peaks={"bf16_flops_per_s": 197e12,
                            "hbm_bytes_per_s": 819e9},
        extras={}, dir=DATA)


def test_recorded_trace_reduces(ctx):
    assert len(ctx.trace.ops) == 1            # one chip, one device plane
    lo, hi = ctx.window
    assert [n for n, _, _ in ctx.trace.host].count("bench.dispatch") == 5
    busy = ctx.busy(0)
    idle = intervals.gaps(busy, lo, hi)
    assert (lo, hi) == (busy[0][0], busy[-1][1])
    assert 0 < intervals.total(busy) < hi - lo
    assert intervals.total(busy) + intervals.total(idle) == pytest.approx(
        hi - lo)
    # Every instruction's own time adds up to the busy union: the line
    # nests and nothing is counted twice.
    own = intervals.self_times(
        (xplane.op_name(n), s, e) for n, s, e in ctx.ops(0))
    assert sum(own.values()) == pytest.approx(intervals.total(busy),
                                              rel=1e-6)
    assert 0 < ctx.busy_s() < ctx.window_s
    assert not ctx.collectives(0)             # one chip: nothing exchanged
    bd = ctx.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and bd["idle_gaps"]
    assert all(isinstance(n, str) and s >= 0
               for n, s in bd["device_ops"] + bd["idle_gaps"])
    assert sum(s for _, s in bd["idle_gaps"]) <= ctx.window_s


def test_recorded_trace_names_the_flash_kernels(ctx):
    kinds = [flash_cost.classify(n) for n, _, _ in ctx.ops(0)
             if flash_cost.is_kernel(n)]
    # two layers, five steps; per layer and step: forward, the forward
    # again under remat, dq, dkv
    assert len(kinds) == 2 * 5 * 4
    assert {k[0] for k in kinds} == {"forward", "dq", "dkv"}
    assert {k[1:] for k in kinds} == {(2 * 4, 128, 16)}


def test_flash_cost():
    flops, nbytes = flash_cost.cost("forward", 512, 1024, 64, causal=True)
    assert flops == 2 * 2 * 512 * 1024 * 1024 * 64 / 2
    assert nbytes == 4 * 512 * 1024 * 64 * 2 + 512 * 1024 * 4
    seconds, bound = flash_cost.least_seconds(
        flops, nbytes, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and seconds == pytest.approx(flops / 197e12)
    assert flash_cost.least_seconds(1.0, 819e9, {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}) == (
            1.0, "memory")


def test_comm_chain_on_recorded_round():
    rows = comm_chain.rows(os.path.join(DATA, "comm"))
    assert len(rows) == 5 and not comm_chain.rows(DATA)
    with open(os.path.join(DATA, "comm", "0", "comm.json")) as f:
        events = json.load(f)["traceEvents"]
    for r in rows:
        chain = sum(r[c] for c in comm_chain.WIRE + comm_chain.SERVER)
        assert 0 < chain <= r["dur_us"]
        assert r["partitions"] >= 1
    # The same arithmetic as the program's own analyzer, which the copy
    # must not drift from while both exist.
    from byteps_tpu.common import trace_analysis
    theirs = trace_analysis.analyze(events)["mean_breakdown_us"]
    for c in comm_chain.WIRE + comm_chain.SERVER:
        assert int(comm_chain.mean_us(rows, (c,))) == theirs[c]


def test_comm_chain_scales_an_overfull_chain():
    step = {"ph": "X", "pid": 0, "tid": "STEP", "ts": 0, "dur": 100}
    spans = [{"ph": "X", "pid": 0, "tid": t, "ts": 0, "dur": 80,
              "args": {"key": 7}} for t in ("PUSH", "PULL")]
    (row,) = comm_chain.step_chains([step, *spans])
    assert row["push_wire"] + row["pull_wire"] == pytest.approx(100)
