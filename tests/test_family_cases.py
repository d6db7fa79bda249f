"""`family_cases.Cases`, the one body of the families' reference and
broken-variant cases, on a family made up here (one weight vector), and
the families' tables of variants against the variants there are."""

import contextlib
import types

import jax
import jax.numpy as jnp
import pytest

import test_afmoe_variants
import test_granite_hybrid_variants
import test_keye_variants
import test_mellum_variants
import test_nemotron_h_variants
import test_ouro_variants
import test_sdar_variants
from benchmark.tests import (afmoe_variants, granitehybrid_variants,
                             keye_variants, mellum_variants,
                             nemotronh_variants, ouro_variants,
                             sdarmoe_variants)
from family_cases import Cases

LIMITS = dict(samples=2, loss_rel_tol=1e-3, grad_rel_tol=1e-3,
              grad_norm_tol=1e-3)


class MadeUp:
    """A family of one weight vector: `loss` is the reference's unless
    `broken` says otherwise, and both say when they are traced, which the
    comparison does once a call."""

    part_tol = 0.5

    def __init__(self, dtype, tolerances, cut):
        self.dtype, self.cut, self.broken = dtype, cut, None
        self.compared, self.selection = 0, []
        self.reference_check = {**LIMITS, **(tolerances or {})}

    def init(self, key):
        return {"w": jax.random.normal(key, (4,))}

    def make_batch(self, key, n_samples):
        return jax.random.normal(key, (n_samples, 4))

    def reference_loss(self, params, batch):
        self.selection.append(
            {"part_diff": 1.0 if self.broken == "the_part" else 0.0})
        return jnp.square(batch @ params["w"]).mean()

    def loss(self, params, batch):
        self.compared += 1
        return (2.0 if self.broken else 1.0) * jnp.square(
            batch @ params["w"]).mean()


def _made_up():
    """A `tiny_<family>` module's surface, and the families it made."""
    made = []

    def family(dtype=None, tolerances=None, **cut):
        made.append(MadeUp(dtype, tolerances, cut))
        return made[-1]

    return types.SimpleNamespace(family=family,
                                 FLOAT32=dict(loss_rel_tol=1e-6)), made


@contextlib.contextmanager
def _broken(family, how):
    family.broken = how
    try:
        yield
    finally:
        family.broken = None


VARIANTS = {how: (lambda family, how=how: _broken(family, how))
            for how in ("the_part", "something_else")}
TOLD = {"part_diff": ("part_tol", {"the_part"})}


def test_a_case_at_the_cells_depth_is_the_cells_in_its_own_dtype():
    """Every id is a case of its own (none is another's under a second
    name); `layers` None is the cell's depth, which only a float32 twin
    leaves, for the layers its family names."""
    tiny, made = _made_up()
    cases = Cases(tiny)
    first, _ = cases.against_reference(jnp.float32, layers=[4, 5])
    again, _ = cases.against_reference(jnp.float32, layers=[4, 5])
    assert again is not first and first.compared == again.compared == 1
    assert first.reference_check["loss_rel_tol"] == 1e-6    # FLOAT32's
    cells, _ = cases.against_reference(jnp.bfloat16, [5, 7], layers=None,
                                       experts=None)
    assert cells.cut == {"layers": None, "experts": None}
    assert cells.reference_check["loss_rel_tol"] == 1e-3    # the cell's
    twin, _ = cases.against_reference(jnp.float32, [5, 7], layers=None)
    whole, _ = cases.against_reference(jnp.float32, layers=None)
    named, _ = cases.against_reference(jnp.float32, [5, 7], layers=[4])
    assert [f.cut["layers"] for f in (twin, whole, named)] == [
        [5, 7], None, [4]]
    assert len(made) == 6


def test_a_variant_runs_on_its_own_layers_family_and_puts_it_back():
    tiny, made = _made_up()
    cases = Cases(tiny)
    family, _ = cases.broken_variant_fails(VARIANTS, None, [4, 5, 6], TOLD)
    assert family.cut == {"layers": [4, 5, 6]}
    one, _ = cases.broken_variant_fails(VARIANTS, "the_part", [6], TOLD)
    two, _ = cases.broken_variant_fails(VARIANTS, "something_else", [6],
                                        TOLD)
    assert one is two is not family and one.cut == {"layers": [6]}
    assert one.dtype == jnp.float32 and one.broken is None
    assert len(made) == 2 and one.compared == 2


def test_a_variant_that_every_comparison_passes_fails_its_case():
    tiny, _ = _made_up()
    harmless = {"harmless": lambda family: contextlib.nullcontext()}
    with pytest.raises(AssertionError):
        Cases(tiny).broken_variant_fails(harmless, "harmless", [6])


@pytest.mark.parametrize("told", [
    {"part_diff": ("part_tol", set())},             # breaks it, not listed
    {"part_diff": ("part_tol", {"the_part", "something_else"})},
], ids=["not_listed", "listed_and_whole"])
def test_the_told_table_is_held_both_ways(told):
    tiny, _ = _made_up()
    cases = Cases(tiny)
    with pytest.raises(AssertionError, match="part_diff"):
        for variant in VARIANTS:
            cases.broken_variant_fails(VARIANTS, variant, [6], told)


@pytest.mark.parametrize("module,variants", [
    (test_afmoe_variants, afmoe_variants),
    (test_granite_hybrid_variants, granitehybrid_variants),
    (test_keye_variants, keye_variants),
    (test_mellum_variants, mellum_variants),
    (test_nemotron_h_variants, nemotronh_variants),
    (test_sdar_variants, sdarmoe_variants),
    (test_ouro_variants, ouro_variants),
], ids=["afmoe", "granitehybrid", "keye", "mellum", "nemotronh", "sdarmoe",
        "ouro"])
def test_a_familys_table_names_every_variant_and_its_layers(module,
                                                            variants):
    """One row a variant: the layers it runs on, among those the program
    as it is keeps (a layer of each kind), and every name a `told` table
    lists is a variant."""
    assert set(module.HELD) - {None} == set(variants.VARIANTS)
    whole = max(module.HELD.values(), key=len)
    for name in variants.VARIANTS:
        assert 0 < len(module.HELD[name]) <= len(whole), name
        assert set(module.HELD[name]) <= set(whole), name
    assert min(len(module.HELD[name]) for name in variants.VARIANTS) == 1
    for part, (limit, broken) in getattr(module, "TOLD", {}).items():
        assert broken <= set(variants.VARIANTS), part
        assert limit.endswith("_tol")
