"""The nemotronh family and its readers: the cell at tiny widths (the
import of `tiny_nemotronh` is what lets `test_jobs.py` cut the cell: run
this directory as a whole), the accepted readers and the new ones on a
trace recorded on a TPU v5e (data/tiny_nemotronh.xplane.pb: five traced
steps of three layers, M, *, E, at the widths `tiny_nemotronh.ON_THE_CHIP`
names, through the in-graph job; `tools/reference_check.py --record`), the
share of grouped products on the program's own kernels from a map, and the
variants in the cell's own dtype."""

import dataclasses
import os
from unittest import mock

import pytest

from benchmark.harness import correct, readers, seeded, tracecap
from benchmark.reduce import afmoe_cost, flash_cost, ssd_cost, xplane
from benchmark.tests import nemotronh_variants as variants
from benchmark.tests import tiny_nemotronh

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TAIL = ('custom-call(%a, %b), custom_call_target="tpu_custom_call", '
        'operand_layout_constraints={}')


@pytest.fixture(scope="module")
def ctx():
    """The recorded trace as a reader sees it."""
    from benchmark.families import nemotronh
    config = tiny_nemotronh.config(layers=[4, 5, 6])
    config["published"].update(tiny_nemotronh.ON_THE_CHIP)
    config["job"].update(per_chip_batch=1, seq_len=1024)
    family = nemotronh.Family(config, config["job"])
    family.routing_counters.append(
        {"held_rows_per_token": [0.375], "max_load_over_mean": [1.2],
         "overflow_rows": [0.0]})
    family.selection.append({"swapped_share": 0.01})
    trace = xplane.read(os.path.join(DATA, "tiny_nemotronh.xplane.pb"),
                        host_prefix=tracecap.PREFIX)
    return tracecap.Context(
        trace=trace, n_steps=5, first_step=3, n_chips=1, samples_per_step=1,
        family=family, peaks=PEAKS, extras={}, dir=DATA)


def test_recorded_trace_names_every_kernel(ctx):
    """A step of M, *, E: the scan forward, again under remat, backward,
    at chunks of 128; the flash kernels likewise (forward twice, dq,
    dkv); and the expert's TWO products at a width of half a lane tile on
    the program's own kernels: forward, again, rows' and weights'
    gradients, eight a step, none of the compiler's."""
    names = [n for n, _, _ in ctx.ops(0)]
    scans = [c for c in map(ssd_cost.scan_call, names) if c]
    assert sorted(scans) == ([("backward", 128)] * 5
                             + [("forward", 128)] * 10)
    flash = [c[0] for c in map(afmoe_cost.attention_call, names) if c]
    assert sorted(flash) == ["dkv"] * 5 + ["dq"] * 5 + ["forward"] * 10
    grouped = [n for n in names if afmoe_cost.is_grouped(n)]
    assert len(grouped) == 8 * 5
    assert all(flash_cost.is_kernel(n) for n in grouped)
    assert {afmoe_cost.grouped_call(n) for n in grouped} == {
        (8, 128, 64), (8, 64, 128)}
    kinds = {xplane.op_name(n).split(".")[0].rsplit("_", 1)[1]
             for n in grouped}
    assert kinds == {"fwd", "drows", "dweights"}
    # the accepted hybrid readers would count the grouped products for
    # attention here, which is why the cell is not on their lists
    assert sum(1 for n in names if ssd_cost.attention_call(n)) > len(flash)


ACCEPTED = ("ssd.ms_per_step", "ssd.roofline", "attn.ms_per_step",
            "attn.roofline", "moe.grouped_roofline", "step.device_ms",
            "step.mfu_busy", "entry.host_gap_ms",
            "route.held_rows_per_token", "route.max_load_over_mean",
            "route.overflow_rows", "route.swapped_share")


def test_accepted_readers_read_the_cell_right(ctx):
    """The readers whose lists the cell joins, unedited, on its trace."""
    got = {name: readers.reader(name)(ctx) for name in ACCEPTED}
    assert all(v is not None for v in got.values()), got
    ops = ctx.ops(0)
    scan = sum(e - s for n, s, e in ops if ssd_cost.scan_call(n))
    assert got["ssd.ms_per_step"] == pytest.approx(scan / 5 / 1e6)
    flash = sum(e - s for n, s, e in ops if afmoe_cost.attention_call(n))
    assert got["attn.ms_per_step"] == pytest.approx(flash / 5 / 1e6)
    # tiny calls are all launch overhead: far below their roofline, and
    # never above
    for name in ("ssd.roofline", "attn.roofline", "moe.grouped_roofline",
                 "step.mfu_busy"):
        assert 0 < got[name] < 100, (name, got[name])
    assert (got["ssd.ms_per_step"] + got["attn.ms_per_step"]
            < got["step.device_ms"])
    assert got["route.held_rows_per_token"] == 0.375
    assert got["route.swapped_share"] == 1.0
    # the scan's cost takes the family's 2 groups and the call's chunk
    shape = ctx.family.scan_shape()
    assert (shape["groups"], shape["chunk"], shape["heads"]) == (2, 128, 16)


def test_new_readers_say_nothing_without_the_programs_map(ctx):
    """The scope readers and the kernel share read the program's own map
    of the step that ran; a process that built no step (this one, or the
    parent's program on another cell) has none and each reads nothing."""
    import byteps_tpu as bps
    with mock.patch.object(bps, "get_step_scopes", lambda: None):
        for name in ("nh.mamba_ms", "nh.moe_ms", "nh.attn_ms",
                     "nh.shared_ms", "moe.grouped_kernel_share"):
            assert readers.reader(name)(dataclasses.replace(ctx)) is None


def test_new_scope_readers_on_a_map_laid_over_the_trace(ctx):
    """A map that places the recorded instructions by what they are: the
    scan under `nemotronh.mamba.scan`, the flash calls under
    `nemotronh.attn`, the grouped products under
    `nemotronh.moe/grouped`; the readers then give those kernels' time a
    step."""
    import byteps_tpu as bps
    scopes = {}
    for n, _, _ in ctx.ops(0):
        name = xplane.op_name(n)
        if ssd_cost.scan_call(n):
            scope = "nemotronh.mamba.scan"
        elif afmoe_cost.is_grouped(n):
            scope = "nemotronh.moe/grouped"
        elif afmoe_cost.attention_call(n):
            scope = "nemotronh.attn"
        else:
            continue
        scopes[name] = {"scope": scope, "pass": "forward",
                        "op_name": f"jit(step)/{scope}/pallas_call"}
    scopes["fusion.shared"] = {"scope": "nemotronh.moe/shared",
                               "pass": "forward", "op_name": "x/dot_general"}
    fresh = dataclasses.replace(ctx)      # a join of its own
    with mock.patch.object(bps, "get_step_scopes", lambda: scopes):
        got = {name: readers.reader(name)(fresh) for name in (
            "nh.mamba_ms", "nh.moe_ms", "nh.attn_ms", "nh.shared_ms",
            "moe.grouped_kernel_share", "ssd.ms_per_step",
            "attn.ms_per_step")}
    assert got["nh.mamba_ms"] == pytest.approx(got["ssd.ms_per_step"],
                                               rel=0.02)
    assert got["nh.attn_ms"] == pytest.approx(got["attn.ms_per_step"],
                                              rel=0.02)
    assert got["nh.moe_ms"] > 0 and got["nh.shared_ms"] == 0
    assert got["moe.grouped_kernel_share"] == 100.0
    os.remove(os.path.join(DATA, "scopes.json"))


def test_kernel_share_counts_the_compilers_products():
    """Of four grouped products in a map, one the compiler's own."""
    import byteps_tpu as bps
    own = {"scope": "nemotronh.moe/grouped", "pass": "forward",
           "op_name": "jit(step)/nemotronh.moe/.grouped/pallas_call"}
    theirs = {"scope": "nemotronh.moe/grouped", "pass": "forward",
              "op_name": "jit(step)/nemotronh.moe/.grouped/ragged_dot"}
    scopes = {"ragged-dot-none_fwd.1": own, "ragged-dot-none_drows.2": own,
              "ragged-dot-none_dweights.3": own, "ragged-dot-none.4": theirs,
              "fusion.5": own}
    read = readers.reader("moe.grouped_kernel_share")
    with mock.patch.object(bps, "get_step_scopes", lambda: scopes):
        assert read(None) == 75.0
    with mock.patch.object(bps, "get_step_scopes",
                           lambda: {"fusion.5": own}):
        assert read(None) is None


@pytest.fixture(scope="module")
def tiny_family():
    return tiny_nemotronh.family(layers=[4, 5, 6])


@pytest.mark.parametrize("variant", variants.VARIANTS)
def test_variant_fails_in_bfloat16_too(tiny_family, variant):
    """At tiny widths and the cell's own dtype every variant leaves the
    family's tolerances; the three that only round where the
    configuration states a precision read inside the three limits and are
    told by the family's own numbers, which fail the loss."""
    family = tiny_family
    args = (seeded.params(family, 3), seeded.batch(family, 3, 2))
    with variants.VARIANTS[variant](family):
        got = correct.gradient_agreement(family.loss, family.reference_loss,
                                         *args)
    assert not correct.agreement_ok(got, family.reference_check), got
    if variant in variants.ONLY_ROUNDING:
        assert got["loss_rel_diff"] > 0.05      # the 1 a part's check adds


def test_the_program_passes_in_bfloat16(tiny_family):
    family = tiny_family
    got = correct.gradient_agreement(
        family.loss, family.reference_loss, seeded.params(family, 3),
        seeded.batch(family, 3, 2))
    assert correct.agreement_ok(got, family.reference_check), got
