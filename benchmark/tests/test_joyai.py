"""The joyai family and its readers: the cell at tiny widths (the import
of `tiny_joyai` is what lets `test_jobs.py` cut the cell: run this
directory as a whole), the ten broken variants in float32 where the
program IS the reference up to rounding, the program and the variants in
the cell's own dtype, the cost of a two-width flash call, and the accepted
readers and the new ones on a trace recorded on a TPU v5e
(data/tiny_joyai.xplane.pb: five traced steps of the dense layer, an
expert layer and the prediction module at the widths
`tiny_joyai.ON_THE_CHIP` names, through the in-graph job;
`tools/reference_check.py --record`)."""

import dataclasses
import os
from unittest import mock

import jax.numpy as jnp
import pytest

from benchmark.harness import correct, readers, seeded, tracecap
from benchmark.reduce import afmoe_cost, flash_cost, mla_cost, xplane
from benchmark.tests import joyai_variants as variants
from benchmark.tests import tiny_joyai

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TAIL = ('custom-call(%a, %b), custom_call_target="tpu_custom_call", '
        'operand_layout_constraints={}')
NEW = ("mla.attn_ms_per_step", "mla.attn_roofline", "mla.chain_ms",
       "mtp.ms_per_step", "joyai.dense_ms", "joyai.shared_ms")


# -- the variants ------------------------------------------------------------
@pytest.fixture(scope="module")
def float32_family():
    # the dense layer, an expert layer and the module's are all they need
    return tiny_joyai.family(jnp.float32, tiny_joyai.FLOAT32, layers=[0, 1])


@pytest.mark.parametrize(
    "variant", [None, "bias_as_it_should_be", *variants.VARIANTS])
def test_broken_variant_fails(float32_family, variant):
    """Each way of breaking the program leaves at least one of the
    comparisons that decide `correct`; the program as it is passes all,
    with a router's bias that is not zero too."""
    family = float32_family
    if variant is None:
        got = tiny_joyai.agreement(family)
        assert correct.agreement_ok(got, family.reference_check), got
        return
    if variant == "bias_as_it_should_be":
        with variants.bias_as_it_should_be(family):
            got = tiny_joyai.agreement(family)
        assert correct.agreement_ok(got, family.reference_check), got
        assert "loss" not in vars(family)       # the methods are back
        return
    with variants.VARIANTS[variant](family):
        got = tiny_joyai.agreement(family)
    if variant in variants.NEEDS_FLASH:
        # dense attention at these widths: the kernels are not run
        assert correct.agreement_ok(got, family.reference_check), got
        return
    assert not correct.agreement_ok(got, family.reference_check), got
    parts = family.selection[-1]
    told = {
        "router_rel_diff": (family.router_rel_tol, {
            "route_scale_left_out", "router_scores_in_bfloat16"}),
        # the router's weights scale what the experts add
        "experts_rel_diff": (family.experts_rel_tol, {
            "route_scale_left_out", "router_scores_in_bfloat16"}),
        # on seeded weights, whose norm scales are 1, the ONLY limit that
        # tells it at the cell's tolerances
        "mtp_state_diff": (family.mtp_state_tol, {
            "mtp_fed_the_normed_hidden_state"}),
    }
    for name, (limit, which) in told.items():
        assert (parts[name] > limit) == (variant in which), (
            name, parts[name])


def test_softmax_statistics_in_bfloat16_are_told_by_the_rows():
    """The one variant that reaches the kernels alone, with flash
    attention at 128 positions in float32: the call is further from float32
    than `attn_rel_tol`, which adds 1 to the reference's loss."""
    config = tiny_joyai.config(layers=[1], modules=0)
    config["program_options"]["pinned"]["attn_impl"] = "flash"
    config["job"]["seq_len"] = 128
    config["reference_check"].update(tiny_joyai.FLOAT32)
    from benchmark.families import joyai
    family = joyai.Family(config, config["job"])
    family.cfg = dataclasses.replace(family.cfg, dtype=jnp.float32)
    got = tiny_joyai.agreement(family)
    assert correct.agreement_ok(got, family.reference_check), got
    with variants.softmax_stats_in_bfloat16(family):
        got = tiny_joyai.agreement(family)
    assert family.selection[-1]["attn_rel_diff"] > family.attn_rel_tol
    assert not correct.agreement_ok(got, family.reference_check), got


@pytest.fixture(scope="module")
def tiny_family():
    return tiny_joyai.family(layers=[0, 1])


def test_the_program_passes_in_bfloat16(tiny_family):
    family = tiny_family
    got = correct.gradient_agreement(
        family.loss, family.reference_loss, seeded.params(family, 3),
        seeded.batch(family, 3, 2))
    assert correct.agreement_ok(got, family.reference_check), got


# -- the cost of a call ------------------------------------------------------
def _kernel(name, results):
    return f"%{name} = {results} {TAIL}"


def test_a_two_width_call_is_found_by_its_name_and_costed_by_both_widths():
    fwd = _kernel("flash_fwd_d192x128.3",
                  "(bf16[32,16384,128]{2,1,0}, f32[32,1,16384]{2,1,0})")
    dq = _kernel("flash_dq_d192x128.4", "bf16[32,16384,192]{2,1,0}")
    dkv = _kernel("flash_dkv_d192x128.5",
                  "(bf16[32,16384,192]{2,1,0}, bf16[32,16384,128]{2,1,0})")
    assert mla_cost.call(fwd) == ("fwd", 32, 16384, 192, 128)
    assert mla_cost.call(dq) == ("dq", 32, 16384, 192, 128)
    assert mla_cost.call(dkv) == ("dkv", 32, 16384, 192, 128)
    # a one-width call, named or not, is not these; and the accepted
    # reader cannot tell dK from dV where their widths differ
    assert mla_cost.call(_kernel("flash_fwd_w2048.1",
                                 "(bf16[8,8192,128], f32[8,1,8192])")) is None
    assert mla_cost.call(_kernel("closed_call.7", "bf16[8,8192,128]")) is None
    assert afmoe_cost.attention_call(dkv) is None
    pairs = 16384 * 16385 / 2
    flops, nbytes = mla_cost.cost("fwd", 32, 16384, 192, 128)
    assert flops == 2 * 32 * pairs * (192 + 128)
    assert nbytes == 32 * 16384 * (2 * (2 * 192 + 2 * 128) + 4)
    assert mla_cost.cost("dq", 32, 16384, 192, 128)[0] == (
        2 * 32 * pairs * (2 * 192 + 128))
    flops, nbytes = mla_cost.cost("dkv", 32, 16384, 192, 128)
    assert flops == 2 * 32 * pairs * (2 * 192 + 2 * 128)
    assert nbytes == 32 * 16384 * (2 * (3 * 192 + 3 * 128) + 8)
    # at one width the count is `flash_cost`'s over the causal triangle
    one = flash_cost.cost("dkv", 4, 1024, 64, causal=False)
    two = mla_cost.cost("dkv", 4, 1024, 64, 64)
    assert two[0] == pytest.approx(one[0] * (1025 / 2048)) and two[1] == one[1]


def test_the_step_counts_the_flops_of_both_heads_and_both_widths():
    from benchmark.families import joyai
    n = dict(hidden_size=8, num_attention_heads=2, q_lora_rank=6,
             kv_lora_rank=4, qk_nope_head_dim=4, qk_rope_head_dim=2,
             v_head_dim=4, moe_intermediate_size=16, intermediate_size=32,
             n_routed_experts=16, num_experts_per_tok=4, n_shared_experts=1)
    attn = 8 * (6 + 4 + 2) + 6 * 2 * 6 + 4 * 2 * 8 + 2 * 4 * 8
    expert = 3 * 8 * 16
    got = joyai.matmul_params_per_token(n, layers=3, dense_layers=1,
                                        modules=1, held_experts=8,
                                        held_vocab=100)
    # 4 choices a token, half the experts held: two experts' worth
    moe = attn + 8 * 16 + expert * (1 + 2)
    assert got == (attn + 3 * 8 * 32) + 3 * moe + 2 * 8 * 8 + 2 * 100 * 8


# -- the recorded trace --------------------------------------------------------
@pytest.fixture(scope="module")
def ctx():
    """The recorded trace as a reader sees it."""
    from benchmark.families import joyai
    config = tiny_joyai.config(layers=[0, 1])
    config["published"].update(tiny_joyai.ON_THE_CHIP)
    config["job"].update(per_chip_batch=1, seq_len=1024)
    family = joyai.Family(config, config["job"])
    family.routing_counters.append(
        {"held_rows_per_token": [0.5, 0.5], "max_load_over_mean": [1.2, 1.1],
         "overflow_rows": [0.0, 0.0]})
    family.selection.append({"swapped_share": 0.01})
    trace = xplane.read(os.path.join(DATA, "tiny_joyai.xplane.pb"),
                        host_prefix=tracecap.PREFIX)
    return tracecap.Context(
        trace=trace, n_steps=5, first_step=3, n_chips=1, samples_per_step=1,
        family=family, peaks=PEAKS, extras={}, dir=DATA)


def test_recorded_trace_names_every_kernel(ctx):
    """A step of the dense layer, an expert layer and the module: three
    latent-attention calls, each the forward kernel ONCE (the layer keeps
    its `o` and `lse`), dQ and dK/dV, all under names that say 192 and
    128; the experts' three products on the program's own kernels."""
    names = [n for n, _, _ in ctx.ops(0)]
    calls = [c for c in map(mla_cost.call, names) if c]
    assert sorted(c[0] for c in calls) == (
        ["dkv"] * 15 + ["dq"] * 15 + ["fwd"] * 15)
    assert {c[1:] for c in calls} == {(2, 1024, 192, 128)}
    grouped = [n for n in names if afmoe_cost.is_grouped(n)]
    assert grouped and all(flash_cost.is_kernel(n) for n in grouped)
    assert {afmoe_cost.grouped_call(n) for n in grouped} == {
        (16, 128, 128)}


def test_readers_read_the_cell_right(ctx):
    """The device-trace readers the cell lists, accepted and new, on its
    trace."""
    got = {name: readers.reader(name)(ctx) for name in (
        "mla.attn_ms_per_step", "mla.attn_roofline", "moe.grouped_roofline",
        "step.device_ms", "step.mfu_busy", "entry.host_gap_ms",
        "route.held_rows_per_token", "route.swapped_share")}
    assert all(v is not None for v in got.values()), got
    flash = sum(e - s for n, s, e in ctx.ops(0) if mla_cost.call(n))
    assert got["mla.attn_ms_per_step"] == pytest.approx(flash / 5 / 1e6)
    for name in ("mla.attn_roofline", "moe.grouped_roofline",
                 "step.mfu_busy"):
        assert 0 < got[name] < 100, (name, got[name])
    assert got["mla.attn_ms_per_step"] < got["step.device_ms"]
    # the accepted attention readers see only what they can classify
    assert readers.reader("attn.ms_per_step")(ctx) != got[
        "mla.attn_ms_per_step"]


def test_new_readers_say_nothing_without_the_programs_map(ctx):
    """The scope readers read the program's own map of the step that ran;
    a process that built no step (this one, or the parent's program on
    another cell) has none and each reads nothing; and the kernel readers
    read nothing in a trace without their kernels."""
    import byteps_tpu as bps
    with mock.patch.object(bps, "get_step_scopes", lambda: None):
        for name in NEW[2:]:
            assert readers.reader(name)(dataclasses.replace(ctx)) is None
    other = xplane.read(os.path.join(DATA, "tiny_nemotronh.xplane.pb"),
                        host_prefix=tracecap.PREFIX)
    for name in NEW[:2]:
        assert readers.reader(name)(
            dataclasses.replace(ctx, trace=other)) is None


def test_new_scope_readers_on_a_map_laid_over_the_trace(ctx):
    """A map that places the recorded flash calls under `joyai.attn` but
    for every third, which it places under the module's scope, and two
    made-up products under the other scopes: each reader gives its own."""
    import byteps_tpu as bps
    scopes, flash = {}, 0
    for n, _, _ in ctx.ops(0):
        if mla_cost.call(n):
            flash += 1
            scope = ("joyai.mtp/joyai.attn" if flash % 3 == 0
                     else "joyai.attn")
            scopes[xplane.op_name(n)] = {
                "scope": scope, "pass": "forward",
                "op_name": f"jit(step)/{scope}/pallas_call"}
    fresh = dataclasses.replace(ctx)      # a join of its own
    with mock.patch.object(bps, "get_step_scopes", lambda: scopes):
        got = {name: readers.reader(name)(fresh) for name in (
            *NEW, "attn.around_kernel_ms")}
    assert got["mtp.ms_per_step"] > 0
    assert got["mla.chain_ms"] == got["attn.around_kernel_ms"] == 0
    assert got["joyai.dense_ms"] is None and got["joyai.shared_ms"] is None
    os.remove(os.path.join(DATA, "scopes.json"))
