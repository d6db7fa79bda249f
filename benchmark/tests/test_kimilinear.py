"""The kimilinear family and its readers: the cell at tiny widths (the
import of `tiny_kimilinear` is what lets `test_jobs.py` cut the cell: run
this directory as a whole), the broken variants in float32 where
the program IS the reference up to rounding, the program in the cell's own
dtype, the cost of a call of the delta-rule scan, what the accepted
readers make of the new kernels' instructions (nothing), and the new
readers on a window laid out by hand from the instructions the scan
compiles to at the cell's shape for a described v5e
(`tests/test_tpu_aot_compile.py` compiles them; no trace of this cell is
recorded in the repository)."""

import dataclasses
import json
import math
import os
from unittest import mock

import jax.numpy as jnp
import pytest

from benchmark.harness import correct, manifest, readers, seeded, tracecap
from benchmark.reduce import (afmoe_cost, conv_cost, flash_cost, kda_cost,
                              mla_cost, ssd_cost, xplane)
from benchmark.tests import kimilinear_variants as variants
from benchmark.tests import tiny_kimilinear

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "kimi-linear-48b-a3b-instruct.ingraph-1chip"
NEW = ("kda.ms_per_step", "kda.roofline", "kimi.kda_mixer_ms",
       "kimi.kda_around_scan_ms", "kimi.dense_ms", "kimi.shared_ms")
# The two calls as they compile at the cell's shape for a described v5e.
WIDE = "bf16[1,512,64,4096]{3,2,1,0:T(8,128)(2,1)}"
FWD = (f'%kda_fwd_c64.1 = ({WIDE}, f32[1,32,512,128,128]'
       '{4,3,2,1,0:T(8,128)}) custom-call(%bitcast, %bitcast.1, %bitcast.2, '
       '%bitcast.3, %reshape.14), custom_call_target="tpu_custom_call", '
       'operand_layout_constraints={bf16[1,512,64,4096]{3,2,1,0}}')
BWD = (f'%kda_bwd_c64.1 = ({WIDE}, {WIDE}, {WIDE}, f32[1,512,64,4096]'
       '{3,2,1,0:T(8,128)}, f32[1,32,512,64]{3,2,1,0:T(8,128)S(1)}) '
       'custom-call(%bitcast, %bitcast.1, %bitcast.2, %bitcast.3, '
       '%reshape.14, %pallas_call.1, %bitcast.5), '
       'custom_call_target="tpu_custom_call", '
       'operand_layout_constraints={bf16[1,512,64,4096]{3,2,1,0}}')
FLASH = ('%flash_fwd_d192x128.1 = (bf16[32,32768,128]{2,1,0}, '
         'f32[32,1,32768]{2,1,0}) custom-call(%q, %k, %v), '
         'custom_call_target="tpu_custom_call", '
         'operand_layout_constraints={bf16[32,32768,192]{2,1,0}}')


# -- the variants ------------------------------------------------------------
@pytest.fixture(scope="module")
def float32_family():
    # a dense KDA layer and a latent-attention expert layer are all they
    # need
    return tiny_kimilinear.family(jnp.float32, tiny_kimilinear.FLOAT32,
                                  layers=[1, 8])


@pytest.mark.parametrize(
    "variant", [None, "bias_as_it_should_be", "recurrence_as_it_is",
                *(v for v in variants.VARIANTS
                  if v not in variants.NEEDS_THE_CHIP)])
def test_broken_variant_fails(float32_family, variant):
    """Each way of breaking the program leaves at least one of the
    comparisons that decide `correct`; the program as it is passes all,
    with a router's bias that is not zero and with the recurrence in the
    scan's place too."""
    family = float32_family
    if variant is None:
        got = tiny_kimilinear.agreement(family)
        assert correct.agreement_ok(got, family.reference_check), got
        return
    if variant in ("bias_as_it_should_be", "recurrence_as_it_is"):
        with getattr(variants, variant)(family):
            got = tiny_kimilinear.agreement(family)
        assert correct.agreement_ok(got, family.reference_check), got
        assert "loss" not in vars(family)       # the methods are back
        return
    with variants.VARIANTS[variant](family):
        got = tiny_kimilinear.agreement(family)
    assert not correct.agreement_ok(got, family.reference_check), got
    parts = family.selection[-1]
    if not math.isfinite(got["loss"]):
        return              # a decay with no sign: nothing is a number
    told = {
        "kda_rel_diff": (family.kda_rel_tol, {
            "decay_after_the_correction", "qk_not_normed", "q_unscaled",
            "state_in_bfloat16", "pairwise_decays_factored"}),
        "conv_rel_diff": (family.conv_rel_tol, {
            "silu_left_out", "tap_across_a_sequences_start",
            "conv_summed_in_bfloat16"}),
        "router_rel_diff": (family.router_rel_tol, {
            "weights_not_normed", "route_scale_left_out",
            "router_scores_in_bfloat16"}),
    }
    for name, (limit, which) in told.items():
        assert (not parts[name] <= limit) == (variant in which), (
            name, parts[name])


def test_the_program_passes_in_bfloat16():
    family = tiny_kimilinear.family(layers=[1, 8])
    got = correct.gradient_agreement(
        family.loss, family.reference_loss, seeded.params(family, 3),
        seeded.batch(family, 3, 2))
    assert correct.agreement_ok(got, family.reference_check), got


# -- the cost of a call ------------------------------------------------------
def test_a_scans_call_is_found_by_its_name_and_costed_by_the_chunked_form():
    assert kda_cost.call(FWD) == ("fwd", 64)
    assert kda_cost.call(BWD) == ("bwd", 64)
    assert kda_cost.call(FLASH) is None
    assert kda_cost.call(FWD.replace("kda_fwd_c64", "fusion")) is None
    shape = dict(tokens=32768, heads=32, key_dim=128, value_dim=128)
    flops, nbytes = kda_cost.cost("fwd", **shape, chunk=64)
    chunk = 2 * (3 * 64 * 128 * 128 + 4 * 64 * 64 * 128)
    assert flops == 512 * 32 * chunk
    wide = 32768 * 4096
    assert nbytes == 4 * wide * 2 + wide * 4 + 32768 * 32 * 4 + (
        512 * 32 * 128 * 128 * 4)
    back, back_bytes = kda_cost.cost("bwd", **shape, chunk=64)
    assert back == 3 * flops and back_bytes == (
        7 * wide * 2 + 2 * wide * 4 + 2 * 32768 * 32 * 4
        + 512 * 32 * 128 * 128 * 4)
    # the bytes bind: 3.3 ms forward and 5.0 backward at 819 GB/s
    for kind, ms in (("fwd", 3.28), ("bwd", 4.92)):
        least, bound = flash_cost.least_seconds(
            *kda_cost.cost(kind, **shape, chunk=64), PEAKS)
        assert bound == "memory" and least * 1e3 == pytest.approx(ms, 1e-2)


def test_the_accepted_readers_do_not_take_the_scans_calls_for_theirs():
    """`flash_cost.classify` tells a flash call by its 3-D results,
    `ssd_cost.scan_call` and `conv_cost.call` theirs by their names: the
    scan's calls return 4-D and 5-D arrays under names of their own."""
    for text in (FWD, BWD):
        assert flash_cost.is_kernel(text)
        assert flash_cost.classify(text) is None
        assert afmoe_cost.attention_call(text) is None
        assert ssd_cost.attention_call(text) is None
        assert ssd_cost.scan_call(text) is None
        assert conv_cost.call(text) is None
        assert mla_cost.call(text) is None
    assert mla_cost.call(FLASH) == ("fwd", 32, 32768, 192, 128)


def test_the_step_counts_the_flops_of_both_mixers_and_the_scan():
    from benchmark.families import kimilinear
    with open(os.path.join(manifest.BENCH, "configs",
                           tiny_kimilinear.NAME + ".json")) as f:
        config = json.load(f)
    family = kimilinear.Family(config, config["job"])
    per_token = kimilinear.matmul_params_per_token(
        family.numbers | {"num_experts": 256}, family.layer_types, 1, 8,
        20480)
    # ISSUE 57: 336M a token, 47% of them the KDA mixers', the dense MLP
    # 19%, the head 14%, the held experts a quarter row a token
    kda_mixer = 39_514_272 - 49_152 - 32 - 4_096 - 128
    assert per_token == pytest.approx(336e6, 5e-3)
    assert 4 * kda_mixer / per_token == pytest.approx(0.47, 2e-2)
    assert 3 * 2304 * 9216 / per_token == pytest.approx(0.19, 2e-2)
    S = config["job"]["seq_len"]
    triangle = 6.0 * (S * (S + 1) // 2) * 32 * (192 + 128)
    scan = 3 * kda_cost.cost("fwd", **family.kda_shape(), chunk=64)[0]
    assert family.model_flops_per_sample() == (
        6.0 * per_token * S + triangle + 4 * scan)
    assert family.kda_shape() == dict(tokens=S, heads=32, key_dim=128,
                                      value_dim=128)


# -- the readers -------------------------------------------------------------
@pytest.fixture()
def ctx():
    """Two steps laid out by hand: a flash call, the scan's forward call
    twice (the pass itself and the recompute) and its backward call once a
    step, at ten times what the roofline would give them."""
    family = tiny_kimilinear.family()
    family.kda_shape = lambda: dict(tokens=32768, heads=32, key_dim=128,
                                    value_dim=128)
    ops, t = [], 0
    for _ in range(2):
        for text, ns in ((FLASH, 3_000_000), (FWD, 32_800_000),
                         (FWD, 32_800_000), (BWD, 49_200_000)):
            ops.append((text, t, t + ns))
            t += ns + 1000
    trace = xplane.Trace(ops=[ops], async_ops=[[]], host=[])
    return tracecap.Context(
        trace=trace, n_steps=2, first_step=3, n_chips=1, samples_per_step=1,
        family=family, peaks=PEAKS, extras={}, dir="/nonexistent")


def test_the_scans_readers_read_their_kernels_alone(ctx):
    got = {name: readers.reader(name)(ctx) for name in (
        "kda.ms_per_step", "kda.roofline", "mla.attn_ms_per_step")}
    assert got["kda.ms_per_step"] == pytest.approx(114.8)
    assert got["kda.roofline"] == pytest.approx(10.0, 1e-2)
    assert got["mla.attn_ms_per_step"] == pytest.approx(3.0)


def test_new_readers_say_nothing_where_there_is_nothing_to_read(ctx):
    """The parent's program on this cell, or this program on another: no
    kernel of that name in the trace, no `kda_shape` on the family, no map
    of the step from a process that built none; each reader returns None
    and does not raise."""
    import byteps_tpu as bps
    other = xplane.Trace(ops=[[(FLASH, 0, 1000)]], async_ops=[[]], host=[])
    bare = dataclasses.replace(ctx, trace=other)
    for name in NEW[:2]:
        assert readers.reader(name)(bare) is None
    del ctx.family.kda_shape
    with mock.patch.object(type(ctx.family), "kda_shape", None):
        assert readers.reader("kda.roofline")(ctx) is None
    with mock.patch.object(bps, "get_step_scopes", lambda: None):
        for name in NEW[2:]:
            assert readers.reader(name)(dataclasses.replace(ctx)) is None
    with mock.patch.object(bps, "get_step_scopes", None, create=True):
        for name in NEW[2:]:
            assert readers.reader(name)(dataclasses.replace(ctx)) is None


def test_the_scope_readers_on_a_map_laid_over_the_window(ctx, tmp_path):
    """A map that places the scan's calls under `kimi.kda.scan` and the
    flash call under `kimi.attn`: the mixer's reader takes the first, what
    lies round the scan and the dense layer's nothing."""
    import byteps_tpu as bps
    scopes = {
        "kda_fwd_c64.1": {"scope": "kimi.kda.scan", "pass": "forward",
                          "op_name": "x/pallas_call"},
        "kda_bwd_c64.1": {"scope": "kimi.kda.scan", "pass": "backward",
                          "op_name": "x/pallas_call"},
        "flash_fwd_d192x128.1": {"scope": "kimi.attn", "pass": "forward",
                                 "op_name": "x/pallas_call"}}
    fresh = dataclasses.replace(ctx, dir=str(tmp_path))
    with mock.patch.object(bps, "get_step_scopes", lambda: scopes):
        got = {name: readers.reader(name)(fresh) for name in (
            "kimi.kda_mixer_ms", "kimi.kda_around_scan_ms", "kimi.dense_ms",
            "kimi.shared_ms", "attn.around_kernel_ms")}
    assert got["kimi.kda_mixer_ms"] == pytest.approx(114.8)
    assert got["kimi.kda_around_scan_ms"] is None
    assert got["kimi.dense_ms"] is None and got["kimi.shared_ms"] is None
    assert got["attn.around_kernel_ms"] == 0


def test_the_manifest_lists_the_cell_where_its_readers_read():
    cell = manifest.load_cell(CELL)
    listed = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= listed
    assert {"mla.attn_ms_per_step", "mla.attn_roofline", "step.mfu_busy",
            "moe.grouped_roofline", "route.held_rows_per_token",
            "moe.move_kernel_share", "attn.around_kernel_ms",
            "setup.step_s"} <= listed
    assert not {"hybrid_attn.roofline", "attn.roofline", "ssd.roofline",
                "mla.chain_ms", "mtp.ms_per_step"} & listed
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s", "setup_s"]
    assert cell.job["per_chip_batch"] == 1 and cell.job["seq_len"] == 32768
    assert cell.chips == 1
    for m in cell.per_layer:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
