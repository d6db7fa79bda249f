"""The lfm2 family and its readers: the cell at tiny widths (the import of
`tiny_lfm2` is what lets `test_jobs.py` cut the cell: run this directory
as a whole), the thirteen broken variants in float32 where the program IS
the reference up to rounding, the program in the cell's own dtype, the
cost of a gated convolution's call, what the accepted attention readers
make of the new kernels' instructions (nothing: every result of theirs is
2-D), and the new readers on a window laid out by hand from the
instructions the cell's step compiles to for a described v5e
(`tests/test_tpu_aot_compile.py` compiles them; no trace of this cell is
recorded in the repository)."""

import dataclasses
import json
import os
from unittest import mock

import jax.numpy as jnp
import pytest

from benchmark.harness import correct, manifest, readers, seeded, tracecap
from benchmark.reduce import (afmoe_cost, conv_cost, flash_cost, ssd_cost,
                              xplane)
from benchmark.tests import lfm2_variants as variants
from benchmark.tests import tiny_lfm2

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "lfm2-24b-a2b.ingraph-1chip"
NEW = ("conv.ms_per_step", "conv.roofline", "lfm2.conv_mixer_ms",
       "lfm2.dense_ms")
# The two calls as the cell's step compiles them for a described v5e.
FWD = ('%short_conv_fwd.1 = bf16[32768,2048]{1,0:T(8,128)(2,1)} '
       'custom-call(%bitcast, %bitcast, %pad.4), '
       'custom_call_target="tpu_custom_call", operand_layout_constraints='
       '{bf16[32768,6144]{1,0}, bf16[32768,6144]{1,0}, f32[8,2048]{1,0}}')
BWD = ('%short_conv_bwd.1 = (bf16[32768,6144]{1,0:T(8,128)(2,1)}, '
       'f32[8,2048]{1,0:T(8,128)S(1)}) custom-call(%bitcast, %bitcast, '
       '%bitcast, %bitcast.5, %bitcast.6, %pad.4), '
       'custom_call_target="tpu_custom_call", operand_layout_constraints='
       '{bf16[32768,6144]{1,0}, bf16[32768,2048]{1,0}, f32[8,2048]{1,0}}')
FLASH = ('%closed_call.7 = (bf16[128,8192,64]{2,1,0}, f32[128,1,8192]{2,1,0}'
         ') custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", '
         'operand_layout_constraints={}')


# -- the variants ------------------------------------------------------------
@pytest.fixture(scope="module")
def float32_family():
    # a dense conv layer, an attention expert layer and a conv expert
    # layer are all they need
    return tiny_lfm2.family(jnp.float32, tiny_lfm2.FLOAT32, layers=[1, 2, 3])


@pytest.mark.parametrize(
    "variant", [None, "bias_as_it_should_be", *variants.VARIANTS])
def test_broken_variant_fails(float32_family, variant):
    """Each way of breaking the program leaves at least one of the
    comparisons that decide `correct`; the program as it is passes all,
    with a router's bias that is not zero too."""
    family = float32_family
    if variant is None:
        got = tiny_lfm2.agreement(family)
        assert correct.agreement_ok(got, family.reference_check), got
        return
    if variant == "bias_as_it_should_be":
        with variants.bias_as_it_should_be(family):
            got = tiny_lfm2.agreement(family)
        assert correct.agreement_ok(got, family.reference_check), got
        assert "loss" not in vars(family)       # the methods are back
        return
    with variants.VARIANTS[variant](family):
        got = tiny_lfm2.agreement(family)
    assert not correct.agreement_ok(got, family.reference_check), got
    parts = family.selection[-1]
    told = {
        "conv_rel_diff": (family.conv_rel_tol, {
            "taps_reversed", "tap_across_a_sequences_start",
            "gate_b_left_out", "gate_c_left_out",
            "silu_after_the_convolution", "conv_summed_in_bfloat16"}),
        "router_rel_diff": (family.router_rel_tol, {
            "weights_not_normed", "softmax_router",
            "router_scores_in_bfloat16"}),
    }
    for name, (limit, which) in told.items():
        assert (parts[name] > limit) == (variant in which), (
            name, parts[name])


def test_the_program_passes_in_bfloat16():
    family = tiny_lfm2.family(layers=[1, 2, 3])
    got = correct.gradient_agreement(
        family.loss, family.reference_loss, seeded.params(family, 3),
        seeded.batch(family, 3, 2))
    assert correct.agreement_ok(got, family.reference_check), got


# -- the cost of a call ------------------------------------------------------
def test_a_gated_convolutions_call_is_found_by_its_name_and_costed_by_bytes():
    assert conv_cost.call(FWD) == ("fwd", 32768, 2048)
    assert conv_cost.call(BWD) == ("bwd", 32768, 2048)
    assert conv_cost.call(FLASH) is None
    assert conv_cost.call(FWD.replace("short_conv_fwd", "fusion")) is None
    flops, nbytes = conv_cost.cost("fwd", 32768, 2048)
    assert nbytes == 2 * 32768 * (3 * 2048 + 2048)
    assert flops == 7 * 32768 * 2048
    assert conv_cost.cost("bwd", 32768, 2048)[1] == 2 * 32768 * 7 * 2048
    # the bytes bind: 0.66 ms forward and 1.15 backward at 819 GB/s
    for kind, ms in (("fwd", 0.655), ("bwd", 1.147)):
        least, bound = flash_cost.least_seconds(
            *conv_cost.cost(kind, 32768, 2048), PEAKS)
        assert bound == "memory" and least * 1e3 == pytest.approx(ms, 1e-2)


def test_the_accepted_attention_readers_do_not_take_the_kernels_for_flash():
    """`flash_cost.classify` tells a flash call by its 3-D results; the
    convolution's calls return 2-D arrays alone (`ops/short_conv.py` says
    so), so `attn.*` and `hybrid_attn.*` leave them out and this cell can
    join `attn.ms_per_step` and `attn.roofline`."""
    for text in (FWD, BWD):
        assert flash_cost.is_kernel(text)
        assert flash_cost.classify(text) is None
        assert afmoe_cost.attention_call(text) is None
        assert ssd_cost.attention_call(text) is None
    assert afmoe_cost.attention_call(FLASH) == ("forward", 128, 8192, 64,
                                                None)


def test_the_step_counts_the_flops_of_both_mixers_and_both_feed_forwards():
    from benchmark.families import lfm2
    n = dict(hidden_size=8, num_attention_heads=4, num_key_value_heads=2,
             head_dim=2, intermediate_size=32, moe_intermediate_size=16,
             num_experts=16, num_experts_per_tok=4)
    got = lfm2.matmul_params_per_token(
        n, ("conv", "full_attention", "conv"), dense_layers=1,
        held_experts=8, held_vocab=100)
    conv, attn = 4 * 8 * 8, 8 * (4 + 4) * 2 + 4 * 2 * 8
    # 4 choices a token, half the experts held: two experts' worth
    moe = 8 * 16 + 2 * 3 * 8 * 16
    assert got == 2 * conv + attn + 3 * 8 * 32 + 2 * moe + 100 * 8
    # the cell's: 223.1M parameters a token meets, 38% of them the conv
    # mixers' (ISSUE 55)
    with open(os.path.join(manifest.BENCH, "configs",
                           tiny_lfm2.NAME + ".json")) as f:
        config = json.load(f)
    family = lfm2.Family(config, config["job"])
    per_token = lfm2.matmul_params_per_token(
        family.numbers | {"num_experts": 64}, family.layer_types, 1, 8, 8192)
    assert per_token == pytest.approx(223.1e6, 1e-3)
    assert 5 * 4 * 2048 * 2048 / per_token == pytest.approx(0.376, 1e-2)
    triangle = 2 * 12.0 * (8192 * 8193 // 2) * 2048
    assert family.model_flops_per_sample() == (
        6.0 * per_token * 8192 + triangle)


# -- the readers -------------------------------------------------------------
@pytest.fixture()
def ctx():
    """Two steps laid out by hand: a flash call, the convolution's forward
    call twice (the pass itself and the recompute) and its backward call
    once a step, at the times the roofline would give them twice over."""
    family = tiny_lfm2.family()
    ops, t = [], 0
    for _ in range(2):
        for text, ns in ((FLASH, 3_000_000), (FWD, 1_310_000),
                         (FWD, 1_310_000), (BWD, 2_294_000)):
            ops.append((text, t, t + ns))
            t += ns + 1000
    trace = xplane.Trace(ops=[ops], async_ops=[[]], host=[])
    return tracecap.Context(
        trace=trace, n_steps=2, first_step=3, n_chips=1, samples_per_step=4,
        family=family, peaks=PEAKS, extras={}, dir="/nonexistent")


def test_the_convolutions_readers_read_their_kernels_alone(ctx):
    got = {name: readers.reader(name)(ctx) for name in (
        "conv.ms_per_step", "conv.roofline", "attn.ms_per_step")}
    assert got["conv.ms_per_step"] == pytest.approx(4.914)
    assert got["conv.roofline"] == pytest.approx(50.0, 1e-2)
    assert got["attn.ms_per_step"] == pytest.approx(3.0)


def test_new_readers_say_nothing_where_there_is_nothing_to_read(ctx):
    """The parent's program on this cell, or this program on another: no
    kernel of that name in the trace, no map of the step from a process
    that built none; each reader returns None and does not raise."""
    import byteps_tpu as bps
    other = xplane.Trace(ops=[[(FLASH, 0, 1000)]], async_ops=[[]], host=[])
    bare = dataclasses.replace(ctx, trace=other)
    for name in NEW[:2]:
        assert readers.reader(name)(bare) is None
    with mock.patch.object(bps, "get_step_scopes", lambda: None):
        for name in NEW[2:]:
            assert readers.reader(name)(dataclasses.replace(ctx)) is None
    with mock.patch.object(bps, "get_step_scopes", None, create=True):
        for name in NEW[2:]:
            assert readers.reader(name)(dataclasses.replace(ctx)) is None


def test_the_scope_readers_on_a_map_laid_over_the_window(ctx, tmp_path):
    """A map that places the convolution's calls under
    `lfm2.conv.gate_conv` and the flash call under `lfm2.attn`: the mixer's
    reader takes the first, the dense layer's nothing."""
    import byteps_tpu as bps
    scopes = {
        "short_conv_fwd.1": {"scope": "lfm2.conv.gate_conv",
                             "pass": "forward", "op_name": "x/pallas_call"},
        "short_conv_bwd.1": {"scope": "lfm2.conv.gate_conv",
                             "pass": "backward", "op_name": "x/pallas_call"},
        "closed_call.7": {"scope": "lfm2.attn", "pass": "forward",
                          "op_name": "x/pallas_call"}}
    fresh = dataclasses.replace(ctx, dir=str(tmp_path))
    with mock.patch.object(bps, "get_step_scopes", lambda: scopes):
        got = {name: readers.reader(name)(fresh) for name in (
            "lfm2.conv_mixer_ms", "lfm2.dense_ms", "attn.around_kernel_ms")}
    assert got["lfm2.conv_mixer_ms"] == pytest.approx(4.914)
    assert got["lfm2.dense_ms"] is None
    assert got["attn.around_kernel_ms"] == 0


def test_the_manifest_lists_the_cell_where_its_readers_read():
    cell = manifest.load_cell(CELL)
    listed = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= listed
    assert {"attn.ms_per_step", "attn.roofline", "step.mfu_busy",
            "moe.grouped_roofline", "route.held_rows_per_token",
            "setup.step_s"} <= listed
    assert not {"hybrid_attn.roofline", "mla.attn_roofline",
                "ssd.roofline"} & listed
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s", "setup_s"]
    assert cell.job["per_chip_batch"] == 4 and cell.job["seq_len"] == 8192
    for m in cell.per_layer:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
