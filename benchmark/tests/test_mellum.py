"""The mellum family, what the accepted cost functions and readers make of
its calls, and its new readers: the cell at tiny widths (the import of
`tiny_mellum` is what lets `test_jobs.py` cut the cell: run this directory
as a whole), the costs on hand-counted shapes, the readers on a trace
recorded on a TPU v5e (data/tiny_mellum.xplane.pb: `tools/
reference_check.py --record`, five traced steps of a sliding and a full
layer on the STREAMING kernels)."""

import dataclasses
import os

import pytest

from benchmark.harness import readers, tracecap
from benchmark.reduce import afmoe_cost, flash_cost, xplane
from benchmark.tests import tiny_mellum

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "mellum2-12b-a2.5b-instruct.ingraph-1chip"

FWD = ('%flash_fwd_w1024.3 = (bf16[32,32768,128]{2,1,0:T(8,128)(2,1)}, '
       'f32[32,1,32768]{2,1,0:T(1,128)}) custom-call(%a, %b, %c), '
       'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
FULL_DKV = ('%mellum.attn.full_attention.13 = (bf16[32,32768,128]{2,1,0}, '
            'bf16[32,32768,128]{2,1,0}) custom-call(%a, %b), '
            'custom_call_target="tpu_custom_call"')
GROUPED = ('%ragged-dot-none.57 = bf16[81920,896]{1,0:T(8,128)(2,1)} '
           'custom-call(%m, %x, %w), custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={s32[1]{0}, s32[17]{0}, s32[95]{0}, '
           's32[95]{0}, s32[1]{0}, bf16[81920,2304]{1,0}, '
           'bf16[16,2304,896]{2,1,0}}')


def test_the_cells_calls_are_costed_at_the_band_and_the_triangle():
    """A streaming call is a flash call like another to the accepted
    readers: told by its results, a sliding layer's by its name, and
    costed at the pairs its mask leaves."""
    assert afmoe_cost.attention_call(FWD) == ("forward", 32, 32768, 128,
                                              1024)
    assert afmoe_cost.attention_call(FULL_DKV) == ("dkv", 32, 32768, 128,
                                                   None)
    band = afmoe_cost.window_pairs(32768, 1024)
    assert band == 1024 * 1025 // 2 + (32768 - 1024) * 1024
    flops, _ = afmoe_cost.attention_cost("forward", 32, 32768, 128, 1024)
    assert flops == 2 * 2.0 * 32 * band * 128
    full, _ = afmoe_cost.attention_cost("dkv", 32, 32768, 128, None)
    assert full == 4 * 2.0 * 32 * (32768 * 32769 // 2) * 128
    # a sliding call needs a sixteenth of what the full one does
    assert 0.06 < band / afmoe_cost.window_pairs(32768, None) < 0.0625
    assert afmoe_cost.grouped_call(GROUPED) == (16, 2304, 896)
    assert afmoe_cost.attention_call(GROUPED) is None


def test_the_family_has_what_the_accepted_readers_ask():
    from benchmark.families import mellum as family_mellum
    from benchmark.harness import manifest
    cell = manifest.load_cell(CELL)
    family = family_mellum.Family(cell.config, cell.job)
    cfg = family.cfg
    assert (family.seq_len, cfg.num_experts, cfg.num_experts_per_tok,
            len(cfg.held), cfg.moe_intermediate_size) == (32768, 64, 8, 16,
                                                          896)
    assert cfg.moe.score_func == "softmax" and cfg.moe.route_scale == 1.0
    assert family.routing_counters == [] and family.selection == []
    # what `moe.ms_per_step` marks the expert layer's instructions by
    assert cfg.moe.buffer_rows(32768) % 512 == 0
    assert cfg.moe.buffer_rows(32768) >= 65536
    listed = {m["name"] for m in cell.per_layer}
    assert {"attn.ms_per_step", "attn.roofline", "attn.sliding_ms_per_step",
            "attn.full_ms_per_step", "attn.stream_live_share",
            "moe.ms_per_step", "moe.grouped_roofline", "step.mfu_busy",
            "route.overflow_rows"} <= listed
    assert "flash_roofline" not in listed and "ssd.roofline" not in listed


@pytest.fixture(scope="module")
def ctx():
    from benchmark.families import mellum as family_mellum
    config = tiny_mellum.config(layers=[2, 3])
    config["published"].update(head_dim=128, sliding_window=256)
    config["job"].update(per_chip_batch=1, seq_len=1024)
    family = family_mellum.Family(config, config["job"])
    family.routing_counters = [
        {"held_rows_per_token": [2.0, 2.5], "max_load_over_mean": [1.1, 1.5],
         "overflow_rows": [0.0, 0.0]}]
    family.selection = [{"swapped_share": 0.05}]
    trace = xplane.read(os.path.join(DATA, "tiny_mellum.xplane.pb"),
                        host_prefix=tracecap.PREFIX)
    return tracecap.Context(
        trace=trace, n_steps=5, first_step=3, n_chips=1, samples_per_step=1,
        family=family, peaks=PEAKS, extras={}, dir=DATA)


def test_recorded_trace_names_every_kind_of_call(ctx):
    calls = [afmoe_cost.attention_call(n) for n, _, _ in ctx.ops(0)]
    calls = [c for c in calls if c]
    # per layer and step: forward, forward again under remat, dq, dkv
    assert len(calls) == 2 * 5 * 4
    assert {c[0] for c in calls} == {"forward", "dq", "dkv"}
    assert {c[1:4] for c in calls} == {(4, 1024, 128)}
    assert sorted(c[4] or 0 for c in calls) == [0] * 20 + [256] * 20
    names = {xplane.op_name(n) for n, _, _ in ctx.ops(0)
             if afmoe_cost.attention_call(n)}
    assert any(n.startswith("flash_fwd_w256") for n in names)
    # a full layer's calls are unnamed: called after the scope around them
    assert any("full_attention" in n or "remat" in n or "checkpoint" in n
               or "closed_call" in n for n in names), names
    grouped = [afmoe_cost.grouped_call(n) for n, _, _ in ctx.ops(0)
               if afmoe_cost.is_grouped(n)]
    products = [g for g in grouped if g]
    assert len(products) == 2 * 5 * 3 * 4
    assert {g[0] for g in products} == {16}


def test_readers_on_the_recorded_trace(ctx):
    got = {name: readers.reader(name)(ctx) for name in (
        "attn.ms_per_step", "attn.roofline", "attn.sliding_ms_per_step",
        "attn.full_ms_per_step", "moe.ms_per_step", "moe.grouped_roofline",
        "step.device_ms", "step.mfu_busy", "route.held_rows_per_token",
        "route.max_load_over_mean", "route.overflow_rows",
        "route.swapped_share")}
    assert 0 < got["attn.ms_per_step"] < got["step.device_ms"]
    assert 0 < got["moe.ms_per_step"] < got["step.device_ms"]
    # the two kinds apart add up to the whole
    assert got["attn.sliding_ms_per_step"] > 0
    assert got["attn.full_ms_per_step"] > 0
    assert got["attn.sliding_ms_per_step"] + got["attn.full_ms_per_step"] \
        == pytest.approx(got["attn.ms_per_step"])
    # tiny calls are all launch overhead: far below their rooflines
    assert 0 < got["attn.roofline"] < 100
    assert 0 < got["moe.grouped_roofline"] < 100
    assert 0 < got["step.mfu_busy"] < 100
    assert got["route.held_rows_per_token"] == pytest.approx(2.25)
    assert got["route.max_load_over_mean"] == pytest.approx(1.5)
    assert got["route.overflow_rows"] == 0
    assert got["route.swapped_share"] == pytest.approx(5.0)
    flash = sum(e - s for n, s, e in ctx.ops(0)
                if flash_cost.is_kernel(n) and not afmoe_cost.is_grouped(n))
    assert got["attn.ms_per_step"] == pytest.approx(flash / 5 / 1e6)


def test_new_readers_say_nothing_where_there_is_nothing(ctx):
    """On a trace of another model the two new trace readers return None
    or leave the other kind out; the counter's reader returns None for a
    program that traced no streaming call."""
    gpt2 = xplane.read(os.path.join(DATA, "tiny.xplane.pb"),
                       host_prefix=tracecap.PREFIX)
    other = dataclasses.replace(ctx, trace=gpt2)
    assert readers.reader("attn.sliding_ms_per_step")(other) is None
    trinity = xplane.read(os.path.join(DATA, "tiny_afmoe.xplane.pb"),
                          host_prefix=tracecap.PREFIX)
    other = dataclasses.replace(ctx, trace=trinity)
    assert readers.reader("attn.sliding_ms_per_step")(other) > 0
    assert readers.reader("attn.full_ms_per_step")(other) > 0


def test_stream_live_share_sums_the_steps_layers_whatever_their_order():
    """One set of gauges a window; the reader takes each layer's set by
    the family's `layer_types`, so the number is the step's and not the
    last traced call's: three sliding layers and one full."""
    import types

    import jax
    import jax.numpy as jnp

    import byteps_tpu as bps
    from byteps_tpu.common import telemetry
    from byteps_tpu.ops.flash_attention import flash_attention
    read = readers.reader("attn.stream_live_share")
    registry = telemetry.get_registry()
    for window in ("256", "none"):
        for name in ("bps_flash_stream_steps", "bps_flash_stream_live"):
            registry.gauge(name, labels={"window": window}).set(0)
    kinds = ("sliding_attention",) * 3 + ("full_attention",)
    family = types.SimpleNamespace(seq_len=1024, cfg=types.SimpleNamespace(
        layer_types=kinds, sliding_window=256))
    ctx = types.SimpleNamespace(family=family)
    assert read(ctx) is None
    assert read(types.SimpleNamespace(family=object())) is None
    q = jnp.zeros((1, 1024, 64), jnp.float32)

    def trace(window):
        jax.make_jaxpr(lambda q: flash_attention(
            q, q, q, True, None, 128, 128, True, True, window))(q)
    trace(256)
    assert read(ctx) is None            # the full layer's call not yet seen
    trace(None)
    want = 100.0 * (3 * 21 + 36) / (3 * 24 + 64)
    assert read(ctx) == pytest.approx(want)
    trace(256)                          # the other order: the same number
    assert read(ctx) == pytest.approx(want)
    assert bps.get_metrics()['bps_flash_stream_steps{window="256"}'] == 24
    # a window that reaches past the sequence is a full call
    family.cfg.sliding_window = 4096
    assert read(ctx) == pytest.approx(100.0 * 36 / 64)
