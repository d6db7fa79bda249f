"""The keye configuration cut to widths a CPU can run, for
`benchmark/tests/tiny.py` (whose table of cuts it joins when it is
imported, as `tiny_mellum.py` does) and for the tests of the family in
`tests/` and here.

Only widths are cut, the indexer's with them, and `topk` to a quarter of
the tiny sequence, so that most rows select.  What the chip holds stays:
four layers, 16 of 128 experts, 8 a token, 18,992 rows of the vocabulary.
"""

import json
import os

from benchmark.harness import manifest
from benchmark.tests import tiny

CUT = {
    "published": dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, moe_intermediate_size=32,
        rope_scaling={"mrope_section": [2, 2, 4], "rope_type": "default",
                      "type": "default"},
        rope_theta=10000,
        sa_config={"indexer_head_dim": 8, "indexer_num_heads": 3,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                   "q_chunk_size": 512, "topk": 64}),
    "job": dict(per_chip_batch=2, seq_len=256),
    "pinned": dict(ce_chunk_rows=128),
    # 64 numbers average less than 2048, and 8 less than 64: at these
    # widths bfloat16 moves a router's logits and an index score far more
    "tolerances": dict(grad_rel_tol=0.4, grad_norm_tol=0.15,
                       selection_eps=0.15, index_selection_eps=0.05,
                       index_rel_tol=0.02, attn_row_tol=0.004,
                       experts_rel_tol=0.01),
}
tiny._TINY.setdefault("keye", CUT)

# The program in float32 is the reference up to rounding: what the broken
# variants are held to.
FLOAT32 = dict(grad_rel_tol=1e-4, grad_norm_tol=1e-4, loss_rel_tol=1e-5,
               selection_eps=1e-3, index_selection_eps=1e-4,
               index_rel_tol=1e-5, experts_rel_tol=1e-4, attn_row_tol=1e-4)


def config(layers=None, experts=None) -> dict:
    """The cell's configuration at tiny widths; `layers` picks other
    layers of the model than the cell's four, `experts` another share."""
    with open(os.path.join(manifest.BENCH, "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        out = tiny.tiny_config(json.load(f))
    if layers is not None:
        out["held"] = {**out["held"], "layers": list(layers),
                       "num_hidden_layers": len(layers)}
    if experts is not None:
        out["held"] = {**out["held"], "experts": list(experts),
                       "num_experts": len(experts)}
    return out


def family(dtype=None, tolerances=None, **cut):
    """The family at tiny widths, its activations in `dtype` (None: the
    cell's bfloat16), its limits `tolerances` where given."""
    import dataclasses

    from benchmark.families import keye as family_keye
    cfg = config(**cut)
    if tolerances:
        cfg["reference_check"].update(tolerances)
    out = family_keye.Family(cfg, cfg["job"])
    if dtype is not None:
        out.cfg = dataclasses.replace(out.cfg, dtype=dtype)
    return out


def agreement(family, seed=0):
    """What `benchmark/harness/correct.py` compares, on `seed`."""
    import jax

    from benchmark.harness import correct, seeded
    got = correct.gradient_agreement(
        family.loss, family.reference_loss, seeded.params(family, seed),
        seeded.batch(family, seed, family.reference_check["samples"]))
    jax.effects_barrier()
    return got
