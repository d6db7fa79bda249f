"""The granitemoehybrid model (`byteps_tpu/models/granite_hybrid.py`) at
tiny widths against its plain float32 reference
(`benchmark/reference/granitehybrid.py`), through the benchmark's own
family and comparison: loss and every gradient leaf over several lists of
layers, and the tests that tie one chip's share (a pipeline stage, a slice
of the tied vocabulary) to the whole model.  (The lists of layers against
the reference are `test_granite_hybrid_reference.py`'s, the fourteen broken
variants `test_granite_hybrid_variants.py`'s.)"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import byteps_tpu as bps
from benchmark.families import granitehybrid as family_granite
from benchmark.harness import correct, seeded
from benchmark.reduce import ssd_cost
from benchmark.reference import granitehybrid as reference
from benchmark.tests import granitehybrid_variants as variants
from benchmark.tests import tiny_granitehybrid
from byteps_tpu.models import granite_hybrid as gh
from byteps_tpu.ops import ssd
from family_cases import Cases
from testutil import mixer_trains_as_with_the_jnp_convolution

M, A = gh.MAMBA, gh.ATTENTION

CASES = Cases(tiny_granitehybrid, family_granite.Family)
_family, _agreement = CASES.family, CASES.agreement


def test_the_jnp_form_of_the_scan_trains_the_same_model():
    family = _family(jnp.float32, tiny_granitehybrid.FLOAT32,
                     layers=[4, 5, 6])
    with variants.jnp_scan(family):
        got = _agreement(family)
    assert correct.agreement_ok(got, family.reference_check), got


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_the_convolutions_kernel_trains_the_model_the_jnp_form_did(
        monkeypatch, dtype, tol):
    """One mamba layer with the mixer's convolution on
    `ops/short_conv.py`'s kernels against the same program with
    `ssd.causal_conv1d` and a silu in its place: float32 to rounding,
    bfloat16 to what one rounding less of xBC explains."""
    mixer_trains_as_with_the_jnp_convolution(
        _family(dtype, layers=[4]), monkeypatch, tol)


def _scan_alone(family, seed=0):
    tokens = seeded.batch(family, seed, 1)[0]
    return float(jax.jit(lambda p, t: family.scan_disagreement(p, t))(
        seeded.params(family, seed), tokens))


@pytest.mark.parametrize("variant", [None, *variants.ONLY_ROUNDING])
def test_the_scan_alone_tells_what_bfloat16_hides(variant):
    """In the cell's own dtype a carried state (or a cumulative sum) in
    bfloat16 reads inside the three limits, like the program's own
    bfloat16 products.  The family's fourth number, the program's scan
    alone on float32 operands against the recurrence, tells it, and
    reaches `correct` through the loss."""
    # the program as it is between two layers of the other kind; what
    # breaks the scan on the one mamba layer it breaks
    family = _family(jnp.bfloat16, layers=[4, 5, 6] if variant is None
                     else [4])
    limits = family.reference_check
    if variant is None:
        got = _agreement(family)
        assert correct.agreement_ok(got, limits), got
        assert _scan_alone(family) < limits["scan_rel_tol"] / 5
        return
    with variants.VARIANTS[variant](family):
        got = _agreement(family)
        alone = _scan_alone(family)
    assert not correct.agreement_ok(got, limits), got
    assert got["worst_grad_rel_diff"] < limits["grad_rel_tol"]
    assert abs(got["worst_grad_norm_ratio"] - 1) < limits["grad_norm_tol"]
    assert alone > 5 * limits["scan_rel_tol"]
    # the 1 the family adds to the reference's loss
    assert got["reference_loss"] - got["loss"] == pytest.approx(1, abs=0.01)


def test_a_layer_list_without_a_scan_has_no_fourth_number():
    family = _family(jnp.float32, tiny_granitehybrid.FLOAT32, layers=[5])
    got = _agreement(family)
    assert correct.agreement_ok(got, family.reference_check), got
    # and a sequence of one chunk carries nothing from chunk to chunk
    one_chunk = _family(jnp.float32, layers=[0])
    one_chunk.cfg = dataclasses.replace(one_chunk.cfg, mamba_chunk_size=256)
    assert _scan_alone(one_chunk) == 0.0


def test_the_scan_writes_its_gauges_when_a_step_is_traced():
    family = _family(layers=[4, 5, 6])
    jax.eval_shape(family.loss, seeded.params(family, 0),
                   seeded.batch(family, 0, 2))
    metrics = bps.get_metrics()
    assert metrics["bps_ssd_scan_layers"] == 2
    assert metrics["bps_ssd_chunk"] == 64
    # 2 sequences x 8 heads x 4 chunks x [16, 32] float32
    assert metrics["bps_ssd_state_bytes"] == 2 * 8 * 4 * 16 * 32 * 4


@pytest.mark.parametrize("impl,copies", [("kernel", 0), ("jnp", 4)])
def test_the_scan_says_which_layout_ran(monkeypatch, impl, copies):
    """A traced step's two gauges of the scan's layout: the lanes of the
    slab of x a kernel program holds (this cut's 8 heads of 16), and the
    transposed copies of a wide operand a call makes outside the kernels:
    none on the model's path, which hands the kernels the mixer's own
    [S, H P]; x, y, dy and dx in the `jnp` form, which keeps a layout of
    its own."""
    family = _family(layers=[4, 5, 6])
    if impl != "kernel":
        monkeypatch.setattr(ssd, "ssd_scan",
                            functools.partial(ssd.ssd_scan, impl=impl))
    jax.eval_shape(jax.grad(family.loss), seeded.params(family, 0),
                   seeded.batch(family, 0, 2))
    metrics = bps.get_metrics()
    assert metrics["bps_ssd_lane_block"] == 8 * 16
    assert metrics["bps_ssd_wide_copies"] == copies
    assert metrics["bps_ssd_chunk"] == 64


def test_a_slice_that_starts_elsewhere():
    """The second chip's slice of the vocabulary: ids from `vocab_start`,
    the same loss as the first chip's on the same rows, in the program
    and in the reference."""
    family = _family(jnp.float32, layers=[0])
    params = seeded.params(family, 2)
    tokens, targets = seeded.batch(family, 2, 1)
    first = family.loss(params, (tokens, targets))
    start = family.cfg.vocab_size
    moved = dataclasses.replace(family.cfg, vocab_start=start)
    batch = (tokens + start, targets + start)
    assert float(first) == float(gh.loss_fn(params, batch, moved))
    np.testing.assert_allclose(
        float(reference.loss(params, batch,
                             {**family.spec, "vocab_start": start})),
        float(first), rtol=1e-5)
    made = gh.synthetic_batch(jax.random.key(0), 2, 8, moved)
    assert int(made[0].min()) >= start and int(made[0].max()) < 2 * start


@pytest.fixture(scope="module")
def whole_model():
    """The uncut model at tiny widths, float32: 40 layers, a vocabulary
    of 8 x 48 ids, its parameters and a batch."""
    family = _family(jnp.float32, layers=range(40), vocab=(0, 8 * 48))
    assert gh._stack_plan(family.cfg) == (4, [(M, 5), (A, 1), (M, 4)])
    assert reference.runs_of(family.layer_types) == (
        4, [(M, 5), (A, 1), (M, 4)])
    params = seeded.params(family, 5)
    tokens = gh.synthetic_batch(jax.random.key(5), 2, 128, family.cfg)[0]
    return family, params, tokens


def test_the_eight_slices_of_the_vocabulary_are_the_models_logits(
        whole_model):
    """Guide, section 4: the logits of the eight slices of the tied
    embedding, each computed by a chip that holds that slice alone, laid
    side by side are the uncut reference's."""
    family, params, tokens = whole_model
    logits = jax.jit(lambda p, t: reference.logits(p, t, family.spec))
    uncut = logits(params, tokens)
    # every id of the batch must lie in a chip's slice for that chip to
    # embed it, so the hidden states come from the chip that holds all
    hidden = gh.forward_hidden(params, tokens, family.cfg)
    rows = family.cfg.vocab_size // 8
    side_by_side = jnp.concatenate(
        [gh.head_logits(hidden, params["embed"][c * rows:(c + 1) * rows],
                        family.cfg) for c in range(8)], axis=-1)
    np.testing.assert_allclose(np.asarray(side_by_side), np.asarray(uncut),
                               atol=2e-4, rtol=2e-4)
    # and a chip that holds one slice embeds and scores its own ids alone
    chip = dataclasses.replace(family.cfg, vocab_size=rows,
                               vocab_start=3 * rows)
    own = {**params, "embed": params["embed"][3 * rows:4 * rows]}
    ids = tokens % rows + 3 * rows
    np.testing.assert_allclose(
        np.asarray(gh.head_logits(gh.forward_hidden(own, ids, chip),
                                  own["embed"], chip)),
        np.asarray(logits(params, ids)[..., 3 * rows:4 * rows]),
        atol=2e-4, rtol=2e-4)


def test_the_four_stages_one_after_another_are_the_model(whole_model):
    """The four pipeline stages, a period of the layer pattern each, each
    run by the program on what the stage before handed over, end at the
    uncut 40-layer reference's hidden states."""
    family, params, tokens = whole_model
    stage_cfg = dataclasses.replace(family.cfg,
                                    layer_types=family.layer_types[:10])

    def stage(x, i):
        leaves = jax.tree.map(lambda a: a[i:i + 1], params["layers"])
        _, runs = gh._stack_plan(stage_cfg)
        for (kind, _), lps in zip(runs, leaves):
            for j in range(lps["input_ln"].shape[1]):
                x = gh._layer(x, jax.tree.map(lambda a: a[0, j], lps),
                              stage_cfg, kind)
        return x

    @jax.jit
    def stages():
        x = gh._embed(params, tokens, family.cfg)
        for i in range(4):
            x = stage(x, i)
        return gh._norm(x, params["final_ln"], family.cfg)

    x = stages()
    np.testing.assert_allclose(
        np.asarray(x), np.asarray(jax.jit(
            lambda p, t: reference.hidden(p, t, family.spec))(params, tokens)),
        atol=2e-4, rtol=2e-4)
    # the program's own scan over the four periods is the same walk
    np.testing.assert_allclose(
        np.asarray(gh.forward_hidden(params, tokens, family.cfg)),
        np.asarray(x), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("types,plan", [
    ((M, M, A, M), (1, [(M, 2), (A, 1), (M, 1)])),
    ((M, A) * 3, (3, [(M, 1), (A, 1)])),
    ((A, A, M), (1, [(A, 2), (M, 1)])),
    ((M,) * 4, (4, [(M, 1)])),
], ids=["one_period", "three_periods", "attention_first", "all_alike"])
def test_stack_plan(types, plan):
    cfg = dataclasses.replace(_family().cfg, layer_types=types)
    assert gh._stack_plan(cfg) == plan
    assert reference.runs_of(types) == plan
    params = jax.eval_shape(lambda: gh.init_params(jax.random.key(0), cfg))
    assert [p["input_ln"].shape[:2] for p in params["layers"]] == [
        (plan[0], n) for _, n in plan[1]]


def test_parameters_of_the_published_stage():
    """The cell's 772,160,448 parameters, from shapes alone."""
    import json
    import os

    from benchmark.harness import manifest
    with open(os.path.join(manifest.BENCH, "configs",
                           "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    family = family_granite.Family(config, config["job"])
    shapes = jax.eval_shape(family.init, jax.random.key(0))
    sizes = [int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)]
    assert sum(sizes) == 772_160_448
    mamba = 2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048
    assert mamba == 25_847_232
    assert sum(sizes) == (9 * (mamba + 3 * 2048 * 8192 + 2 * 2048)
                          + (2 * 2048 * 2048 + 2 * 2048 * 512
                             + 3 * 2048 * 8192 + 2 * 2048)
                          + 12544 * 2048 + 2048)
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]


def test_model_flops_against_a_count_by_hand():
    n = dict(hidden_size=8, num_attention_heads=2, num_key_value_heads=1,
             shared_intermediate_size=16, mamba_n_heads=4, mamba_d_head=4,
             mamba_d_state=8, mamba_n_groups=1)
    mlp = 3 * 8 * 16
    mamba = 8 * (16 + (16 + 16) + 4) + 16 * 8
    attn = 8 * (2 + 2) * 4 + 2 * 4 * 8
    assert family_granite.matmul_params_per_token(n, (M, A, M), 10) == (
        3 * mlp + 2 * mamba + attn + 10 * 8)
    # the scan, a layer and a sequence: 32 tokens in chunks of 8
    shape = dict(tokens=32, heads=4, head_dim=4, state=8, groups=1, chunk=8)
    per_head = 2 * 8 * 8 * 4 + 2 * 8 * 8 * 4 + 2 * 8 * 4 * 8
    forward = 4 * (4 * per_head + 2 * 8 * 8 * 8)
    assert ssd_cost.cost("forward", **shape)[0] == forward
    assert ssd_cost.model_flops(**shape) == 3 * forward
    family = _family(layers=[4, 5, 6])
    S, num = family.seq_len, family.numbers
    params = family_granite.matmul_params_per_token(
        num, family.layer_types, num["vocab_size"])
    assert family.model_flops_per_sample() == (
        6.0 * params * S + 2 * ssd_cost.model_flops(**family.scan_shape())
        + 12.0 * (S * (S + 1) // 2) * num["hidden_size"])


def test_the_new_code_stays_out_of_the_other_cells_imports():
    """`import byteps_tpu`, the transformer, the afmoe model and the other
    families and jobs import nothing of the granite model or the scan (a
    PR was once refused on another cell's set-up time)."""
    import subprocess
    import sys
    from testutil import cpu_env
    code = ("import sys, byteps_tpu, byteps_tpu.models, byteps_tpu.ops, "
            "byteps_tpu.models.transformer, byteps_tpu.models.afmoe, "
            "benchmark.families.gpt2, benchmark.families.vgg, "
            "benchmark.families.afmoe, benchmark.jobs.ingraph, "
            "benchmark.jobs.ps_joint; "
            "bad = [m for m in sys.modules if 'granite' in m or 'ssd' in m]"
            "; assert not bad, bad")
    r = subprocess.run([sys.executable, "-c", code], env=cpu_env(),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
