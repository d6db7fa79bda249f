"""The two cases every model family's tier-1 tests run through the
benchmark's own family and comparison (`benchmark/harness/correct.py`),
written once: the program against its plain reference on a cut of the
model, and the program broken in one way, which has to leave at least one
of the comparisons that decide `correct`.  A family's test file keeps the
parametrisation (the ids), the table of its variants and whatever it
asserts of its own records; it imports this module, which holds no test.

What a case costs on the CPU is its LAYERS: the Pallas kernels lowered
for the interpreter and the unrolled layers compiled, for the program's
gradient and again for the reference's (PR 59: nemotron_h's expert layer
alone 11 s, a layer of each kind 26 s, the cell's nine 52 s on an idle
machine).  So a case holds the layers it is about and no other: a broken
variant one layer of the kind it patches, named in the family's table.
A family's tier-1 tests cost at most 200 test-seconds in all (ROADMAP
D10), and `tools/check_test_budget.py` holds a file to 350.
"""

import dataclasses

import jax
import jax.numpy as jnp

from benchmark.harness import correct, seeded


def _key(value):
    return value if value is None or isinstance(value, int) else tuple(value)


class Cases:
    """A family's cases at tiny widths.  `tiny` is its
    `benchmark.tests.tiny_<family>` module; `family_class` is the family's
    `Family` for a module that has `config` and `FLOAT32` only (mellum,
    afmoe, granitehybrid: `benchmark/` is another kind of PR's to edit)."""

    def __init__(self, tiny, family_class=None):
        self.tiny = tiny
        self._family_class = family_class
        self._float32, self._operands = {}, {}

    def family(self, dtype=None, tolerances=None, **cut):
        """The family on `cut` of the model, its activations in `dtype`
        (None: the cell's bfloat16), its limits `tolerances` where given."""
        if hasattr(self.tiny, "family"):
            return self.tiny.family(dtype, tolerances, **cut)
        config = self.tiny.config(**cut)
        if tolerances:
            config["reference_check"].update(tolerances)
        family = self._family_class(config, config["job"])
        if dtype is not None:
            family.cfg = dataclasses.replace(family.cfg, dtype=dtype)
        return family

    def agreement(self, family, seed=0, operands=None):
        """What `benchmark/harness/correct.py` compares, on `seed` (the
        body of the modules' own `agreement`), or on `operands`, the
        weights and the batch a caller made from it before."""
        params, batch = operands or self.operands(family, seed)
        got = correct.gradient_agreement(family.loss, family.reference_loss,
                                         params, batch)
        jax.effects_barrier()
        return got

    @staticmethod
    def operands(family, seed=0):
        return (seeded.params(family, seed),
                seeded.batch(family, seed,
                             family.reference_check["samples"]))

    def float32(self, layers):
        """The family in float32 under the module's `FLOAT32` limits,
        where the program IS the reference up to rounding, holding
        `layers`: one object a list of layers for all of a module's cases
        (a variant puts back what it patches)."""
        if _key(layers) not in self._float32:
            self._float32[_key(layers)] = self.family(
                jnp.float32, self.tiny.FLOAT32, layers=layers)
        return self._float32[_key(layers)]

    def against_reference(self, dtype, float32_layers=None, **cut):
        """In float32 the program IS the reference up to rounding; in
        bfloat16 it is within the family's tolerances at these widths.
        Returns the family and what was compared, for what the family's
        file asserts of its own records.  A case at the cell's depth
        (`layers` None) is the cell's in bfloat16, its dtype; its float32
        twin holds `float32_layers` where a family names them: other
        layers than any other case's, each kind of the plan once."""
        if (float32_layers and dtype == jnp.float32
                and cut.get("layers") is None):
            cut["layers"] = float32_layers
        family = self.family(
            dtype, self.tiny.FLOAT32 if dtype == jnp.float32 else None,
            **cut)
        got = self.agreement(family)
        assert correct.agreement_ok(got, family.reference_check), got
        return family, got

    def broken_variant_fails(self, variants, variant, layers, told=None):
        """Each way of breaking the program leaves at least one of the
        comparisons that decide `correct`; the program as it is (`variant`
        None) passes all.  `variants` is the family's `VARIANTS`, `layers`
        what this variant runs on, and `told` the family's table
        `{part: (the family's limit for it, the variants that break it)}`:
        the parts alone, on the step's own operands, equal on both sides,
        each tell the variants that break THEM, whatever the choice does.
        A part none of whose layers is held reads 0, as of a variant that
        does not break it."""
        family = self.float32(layers)
        # the same seed gives the same arrays: made once a shared family
        if _key(layers) not in self._operands:
            self._operands[_key(layers)] = self.operands(family)
        operands = self._operands[_key(layers)]
        if variant is None:
            got = self.agreement(family, operands=operands)
            assert correct.agreement_ok(got, family.reference_check), got
            return family, got
        with variants[variant](family):
            got = self.agreement(family, operands=operands)
        assert not correct.agreement_ok(got, family.reference_check), got
        parts = family.selection[-1] if told else {}
        for name, (limit, broken) in (told or {}).items():
            assert (parts[name] > getattr(family, limit)) == (
                variant in broken), (name, parts[name])
        return family, got
