"""The afmoe family, its cost functions and its readers: the cell at tiny
widths (the import of `tiny_afmoe` is what lets `test_jobs.py` cut the
cell: run this directory as a whole), the costs on hand-counted shapes,
the readers on a trace recorded on a TPU v5e (data/tiny_afmoe.xplane.pb:
the five traced steps of the cell at tiny widths through the in-graph
job)."""

import os

import pytest

from benchmark.harness import tracecap
from benchmark.reduce import afmoe_cost, flash_cost, xplane
from benchmark.tests import tiny_afmoe  # noqa: F401  (joins tiny's table)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_window_pairs_and_attention_cost():
    assert afmoe_cost.window_pairs(8192, None) == 8192 * 8193 // 2
    band = afmoe_cost.window_pairs(8192, 2048)
    assert band == 2048 * 2049 // 2 + 6144 * 2048
    assert 0.43 < band / afmoe_cost.window_pairs(8192, None) < 0.44
    # forward: two matmuls of 2 FLOPs a multiply-add over the pairs
    flops, nbytes = afmoe_cost.attention_cost("forward", 128, 8192, 128, 2048)
    assert flops == 2 * 2.0 * 128 * band * 128
    assert nbytes == flash_cost.cost("forward", 128, 8192, 128, True)[1]
    full, _ = afmoe_cost.attention_cost("dkv", 128, 8192, 128, None)
    assert full == 4 * 2.0 * 128 * (8192 * 8193 // 2) * 128
    # never more than the causal count that flash_cost has
    assert full == pytest.approx(
        flash_cost.cost("dkv", 128, 8192, 128, True)[0], rel=2e-4)


FWD = ('%flash_fwd_w2048.3 = (bf16[128,8192,128]{2,1,0:T(8,128)(2,1)}, '
       'f32[128,1,8192]{2,1,0:T(1,128)}) custom-call(%a, %b, %c), '
       'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
FULL_DQ = ('%afmoe.attn.full_attention.13 = bf16[128,8192,128]{2,1,0} '
           'custom-call(%a, %b), custom_call_target="tpu_custom_call"')
GROUPED = ('%ragged-dot-none.57 = bf16[40960,2048]{1,0:T(8,128)(2,1)} '
           'custom-call(%m, %x, %w), custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={s32[1]{0}, s32[17]{0}, s32[95]{0}, '
           's32[95]{0}, s32[1]{0}, bf16[40960,1024]{1,0}, '
           'bf16[16,1024,2048]{2,1,0}}')
DRHS = ('%ragged-dot-none.9 = bf16[16,2048,1024]{2,1,0} custom-call(%m), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{s32[1]{0}, bf16[40960,2048]{1,0}, bf16[40960,1024]{1,0}}')
METADATA = ('%ragged-dot-metadata.1 = (s32[17]{0}, s32[95]{0}, s32[95]{0}, '
            's32[1]{0}) custom-call(%gs), '
            'custom_call_target="tpu_custom_call"')


def test_calls_are_told_apart_by_name_and_shape():
    assert afmoe_cost.attention_call(FWD) == ("forward", 128, 8192, 128, 2048)
    assert afmoe_cost.attention_call(FULL_DQ) == ("dq", 128, 8192, 128, None)
    for grouped in (GROUPED, DRHS, METADATA):
        assert afmoe_cost.attention_call(grouped) is None
        assert afmoe_cost.is_grouped(grouped)
    assert afmoe_cost.grouped_call(GROUPED) == (16, 1024, 2048)
    assert afmoe_cost.grouped_call(DRHS) == (16, 2048, 1024)
    assert afmoe_cost.grouped_call(METADATA) is None
    assert not afmoe_cost.is_grouped(FWD)


def test_grouped_cost_counts_the_rows_needed():
    flops, nbytes = afmoe_cost.grouped_cost(32768, 16, 1024, 2048)
    assert flops == 2.0 * 32768 * 1024 * 2048
    assert nbytes == (32768 * 3072 + 16 * 1024 * 2048) * 2
    seconds, bound = flash_cost.least_seconds(flops, nbytes, PEAKS)
    assert bound == "compute"


@pytest.mark.parametrize("text,belongs", [
    (GROUPED, True), (METADATA, True),
    ("%fusion.1 = bf16[40960,2048]{1,0} fusion(%a), kind=kLoop", True),
    ("%fusion.8 = bf16[5120,2048]{1,0} fusion(%a), kind=kLoop", True),
    ("%fusion.2 = s32[262144]{0} fusion(%a), kind=kLoop", True),
    ("%sort.3 = (s32[262144]{0}, s32[262144]{0}) sort(%a, %b)", True),
    ("%fusion.4 = f32[32768,128]{1,0} fusion(%a), kind=kOutput", True),
    ("%fusion.5 = bf16[4,8192,1024]{2,1,0} fusion(%a), kind=kOutput", True),
    ("%fusion.6 = bf16[4,8192,2048]{2,1,0} fusion(%a), kind=kOutput", False),
    ("%fusion.7 = bf16[4,8192,6144]{2,1,0} fusion(%a), kind=kOutput", False),
    (FWD, False),
], ids=lambda x: x if isinstance(x, bool) else x.split(" = ")[0])
def test_expert_layer_instructions(text, belongs):
    assert afmoe_cost.is_expert_layer(
        text, tokens=32768, top_k=8, experts=128, buffers=(40960, 5120),
        expert_width=1024) is belongs


@pytest.fixture(scope="module")
def ctx():
    """The recorded trace (`tools/afmoe_record_trace.py`: two expert
    layers, one sliding and one full, 2 x 256 tokens a step, five steps)
    as a reader sees it."""
    from benchmark.families import afmoe as family_afmoe
    config = tiny_afmoe.config(layers=[4, 7])
    family = family_afmoe.Family(config, config["job"])
    family.routing_counters = [
        {"held_rows_per_token": [1.0, 1.25], "max_load_over_mean": [1.1, 1.5],
         "overflow_rows": [0.0, 3.0]},
        {"held_rows_per_token": [0.75, 1.0], "max_load_over_mean": [1.3, 1.2],
         "overflow_rows": [0.0, 0.0]}]
    family.selection = [{"swapped_share": 0.07}, {"swapped_share": 0.09}]
    trace = xplane.read(os.path.join(DATA, "tiny_afmoe.xplane.pb"),
                        host_prefix=tracecap.PREFIX)
    return tracecap.Context(
        trace=trace, n_steps=5, first_step=3, n_chips=1, samples_per_step=2,
        family=family, peaks=PEAKS, extras={}, dir=DATA)


def test_recorded_trace_names_every_new_part(ctx):
    calls = [afmoe_cost.attention_call(n) for n, _, _ in ctx.ops(0)]
    calls = [c for c in calls if c]
    # per layer and step: forward, forward again under remat, dq, dkv
    assert len(calls) == 2 * 5 * 4
    assert {c[0] for c in calls} == {"forward", "dq", "dkv"}
    assert {c[1:4] for c in calls} == {(2 * 4, 256, 16)}
    # the sliding layer's calls carry its window in their name
    assert sorted(c[4] or 0 for c in calls) == [0] * 20 + [128] * 20
    grouped = [afmoe_cost.grouped_call(n) for n, _, _ in ctx.ops(0)
               if afmoe_cost.is_grouped(n)]
    # per layer and step: three products forward, again under remat, and
    # two gradients each; a metadata kernel before each pass's first
    products = [g for g in grouped if g]
    assert len(products) == 2 * 5 * 3 * 4
    assert {g[0] for g in products} == {16}
    assert {g[1] * g[2] for g in products} == {64 * 32}
    assert 0 < len(grouped) - len(products) <= len(products)


def test_readers_on_the_recorded_trace(ctx):
    from benchmark.harness import readers
    got = {name: readers.reader(name)(ctx) for name in (
        "attn.ms_per_step", "attn.roofline", "moe.ms_per_step",
        "moe.grouped_roofline", "step.device_ms",
        "route.held_rows_per_token", "route.max_load_over_mean",
        "route.overflow_rows", "route.swapped_share")}
    assert 0 < got["attn.ms_per_step"] < got["step.device_ms"]
    assert 0 < got["moe.ms_per_step"] < got["step.device_ms"]
    # tiny calls are all launch overhead: far below their rooflines, and
    # never above
    assert 0 < got["attn.roofline"] < 100
    assert 0 < got["moe.grouped_roofline"] < 100
    assert got["route.held_rows_per_token"] == pytest.approx(1.0)
    assert got["route.max_load_over_mean"] == pytest.approx(1.4)
    assert got["route.overflow_rows"] == pytest.approx(1.5)
    assert got["route.swapped_share"] == pytest.approx(8.0)
    # the attention reader is the flash kernels' time and nothing else's
    flash = sum(e - s for n, s, e in ctx.ops(0)
                if flash_cost.is_kernel(n) and not afmoe_cost.is_grouped(n))
    assert got["attn.ms_per_step"] == pytest.approx(flash / 5 / 1e6)


def test_readers_say_nothing_where_there_is_nothing(ctx):
    """On a trace of another model, or a family without the counters (the
    parent's program, another cell), every new reader returns None."""
    import dataclasses

    from benchmark.harness import readers
    gpt2 = xplane.read(os.path.join(DATA, "tiny.xplane.pb"),
                       host_prefix=tracecap.PREFIX)
    other = dataclasses.replace(ctx, trace=gpt2)
    assert readers.reader("moe.ms_per_step")(other) is None
    assert readers.reader("moe.grouped_roofline")(other) is None
    bare = dataclasses.replace(ctx, family=object())
    for name in ("route.held_rows_per_token", "route.max_load_over_mean",
                 "route.overflow_rows", "route.swapped_share"):
        assert readers.reader(name)(bare) is None
