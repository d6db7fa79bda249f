"""The sdarmoe family, the cost of its masked attention calls and its new
readers: the cell at tiny widths (the import of `tiny_sdarmoe` is what
lets `test_jobs.py` cut the cell: run this directory as a whole), the
cost on hand-counted shapes, and the readers where there is nothing to
read."""

import dataclasses
import os
import types

import pytest

from benchmark.harness import manifest, readers, tracecap
from benchmark.reduce import bd_cost, flash_cost, xplane
from benchmark.tests import tiny_sdarmoe  # noqa: F401  (joins tiny._TINY)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "sdar-30b-a3b-chat.ingraph-1chip"
NEW = ("bd.attn_ms_per_step", "bd.attn_roofline", "bd.pairs_needed_share",
       "bd.masked_share", "sdar.noise_ms")

FWD = ('%flash_fwd_bd4.3 = (bf16[32,32768,128]{2,1,0:T(8,128)(2,1)}, '
       'f32[32,1,32768]{2,1,0:T(1,128)}) custom-call(%t, %u, %w, %a, %b, '
       '%c), custom_call_target="tpu_custom_call"')
DKV = ('%flash_dkv_bd4 = (bf16[32,32768,128]{2,1,0}, '
       'bf16[32,32768,128]{2,1,0}) custom-call(%a, %b), '
       'custom_call_target="tpu_custom_call"')
CAUSAL = FWD.replace("flash_fwd_bd4", "flash_fwd_w1024")


def test_a_masked_call_is_costed_at_the_pairs_the_mask_needs():
    """L^2 + L beta pairs a head from `(L, beta, heads, head size)` alone:
    half of what the causal call over the same 2 L rows is costed at, and
    the same whatever tiles a kernel walks."""
    assert bd_cost.call(FWD) == ("forward", 32, 16384, 128, 4)
    assert bd_cost.call(DKV) == ("dkv", 32, 16384, 128, 4)
    assert bd_cost.call(CAUSAL) is None
    pairs = bd_cost.needed_pairs(16384, 4)
    assert pairs == 16384 * 16384 + 16384 * 4 == 268_500_992
    flops, nbytes = bd_cost.cost("forward", 32, 16384, 128, 4)
    assert flops == 2 * 2.0 * 32 * pairs * 128
    assert nbytes == 4 * 32 * 32768 * 128 * 2 + 32 * 32768 * 4
    assert bd_cost.cost("dkv", 32, 16384, 128, 4)[0] == 2 * flops
    causal, same_bytes = flash_cost.cost("forward", 32, 32768, 128, True)
    assert 0.5 < flops / causal < 0.5002 and same_bytes == nbytes
    # the walk at tiles of 512 computes 1,088 tiles for them: 94.1%
    from byteps_tpu.ops import flash_attention as fa
    walk = fa.stream_schedule(32768, 512, 512, False,
                              block_diffusion=(16384, 4))
    assert walk["live"] == 1088 and walk["whole"] == 992
    assert walk["pairs_needed_share"] == pairs / (1088 * 512 * 512)


def test_the_family_has_what_the_accepted_readers_ask():
    from benchmark.families import sdarmoe as family_sdarmoe
    cell = manifest.load_cell(CELL)
    family = family_sdarmoe.Family(cell.config, cell.job)
    cfg = family.cfg
    assert (family.seq_len, cfg.num_experts, cfg.num_experts_per_tok,
            len(cfg.held), cfg.moe_intermediate_size, cfg.num_layers,
            cfg.block_length) == (16384, 128, 8, 16, 768, 6, 4)
    assert family.units_per_sample == 16384          # TOKENS, not rows
    assert cfg.moe.score_func == "softmax" and cfg.moe.hold_held_weight
    assert cfg.moe.buffer_rows(32768) % 512 == 0
    assert family.routing_counters == [] and family.selection == []
    # 6 a matmul parameter a row over 2 L rows, the head over L, and
    # attention at the needed pairs: 28.1 + 3.8 + 79.2 TFLOP
    layer = 2048 * 40 * 128 + 4096 * 2048 + 2048 * 128 + 3 * 2048 * 768
    want = (6.0 * 6 * layer * 32768 + 6.0 * 18992 * 2048 * 16384
            + 12.0 * 6 * 268_500_992 * 4096)
    assert family.model_flops_per_sample() == want
    assert 110e12 < want < 112e12
    listed = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {"step.mfu_busy", "moe.grouped_roofline",
                       "route.overflow_rows", "attn.around_kernel_ms",
                       "step.scoped_share"} <= listed
    assert not any(n.startswith(("sparse.", "attn.roofline", "flash"))
                   for n in listed)
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s",
                                                    "setup_s"]


def test_new_readers_say_nothing_where_there_is_nothing():
    """On a trace of another model (what the parent's program gives a
    traced run of any cell) the trace readers return None, and the
    counters' readers None for a program that set no such gauge."""
    from byteps_tpu.common import telemetry
    registry = telemetry.get_registry()
    for name in ("bps_flash_bd_pairs_needed_share", "bps_bd_masked_share"):
        registry.gauge(name).set(0)
    trace = xplane.read(os.path.join(DATA, "tiny_mellum.xplane.pb"),
                        host_prefix=tracecap.PREFIX)
    ctx = tracecap.Context(
        trace=trace, n_steps=5, first_step=3, n_chips=1, samples_per_step=1,
        family=types.SimpleNamespace(), peaks=PEAKS, extras={}, dir=DATA)
    assert [readers.reader(name)(ctx) for name in NEW[:4]] == [None] * 4
    empty = dataclasses.replace(ctx, trace=xplane.Trace([[]], [[]], []))
    assert readers.reader("sdar.noise_ms")(empty) is None


def test_the_trace_readers_on_a_made_up_window():
    """Two masked calls of 1 ms each in a window of one step."""
    ops = [(FWD, 0, 1_000_000), (CAUSAL, 1_000_000, 2_000_000),
           (DKV, 2_000_000, 3_000_000)]
    ctx = types.SimpleNamespace(ops=lambda chip=0: ops, n_steps=1,
                                peaks=PEAKS)
    assert readers.reader("bd.attn_ms_per_step")(ctx) == pytest.approx(2.0)
    least = sum(flash_cost.least_seconds(
        *bd_cost.cost(kind, 32, 16384, 128, 4), PEAKS)[0]
        for kind in ("forward", "dkv"))
    assert readers.reader("bd.attn_roofline")(ctx) == pytest.approx(
        100.0 * least / 2e-3)


@pytest.mark.parametrize("variant", ["softmax_statistics_in_bfloat16",
                                     "expert_products_in_float8"])  # CONTROLS
def test_the_two_precision_controls_fail(variant):
    """The nearest precision below the cell's in the attention kernels'
    statistics and in the experts' products: each leaves `correct` at
    tiny widths in float32, told by its own part."""
    import sys
    sys.path.insert(0, os.path.join(manifest.ROOT, "tests"))
    from family_cases import Cases

    from benchmark.families import sdarmoe as family_sdarmoe
    from benchmark.tests import sdarmoe_variants
    told = {"attn_row_diff": ("attn_row_tol",
                              {"softmax_statistics_in_bfloat16"}),
            "experts_rel_diff": ("experts_rel_tol",
                                 {"expert_products_in_float8"})}
    Cases(tiny_sdarmoe, family_sdarmoe.Family).broken_variant_fails(
        sdarmoe_variants.CONTROLS, variant, [0], told)
