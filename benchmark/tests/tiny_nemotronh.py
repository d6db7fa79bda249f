"""The nemotronh configuration cut to widths a CPU can run, for
`benchmark/tests/tiny.py` (whose table of cuts it joins when it is
imported, as `tiny_mellum.py` does) and for the tests of the family in
`tests/` and here.

Only widths are cut, each keeping what makes the published one awkward:
the expert's width stays an odd number of half lane tiles in spirit (24
is no multiple of 16), the scan keeps several groups (2 of 4 heads), the
mixer's inner width (8 heads of 8) is not `expand` times the hidden size.
What the chip holds stays: the model's layers 0-8 (`MEMEM*EME`), 8 of 128
experts, 6 a token, 16,384 rows of the vocabulary.
"""

import json
import os

from benchmark.harness import manifest
from benchmark.tests import tiny

CUT = {
    "published": dict(hidden_size=64, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16,
                      mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16,
                      n_groups=2, chunk_size=64, moe_intermediate_size=24,
                      moe_shared_expert_intermediate_size=48),
    "job": dict(per_chip_batch=2, seq_len=256),
    "pinned": dict(ce_chunk_rows=128),
    # 64 numbers average less than 2688: at these widths bfloat16 moves a
    # router's score by up to 0.01, some tokens swap an expert, and a
    # mixer's 8 numbers a layer (D, dt_bias) move by up to a fifth.
    "tolerances": dict(grad_rel_tol=0.4, grad_norm_tol=0.15,
                       selection_eps=0.02, attn_row_tol=0.002,
                       experts_rel_tol=0.012, scan_rel_tol=1e-4),
}
tiny._TINY.setdefault("nemotronh", CUT)

# The program in float32 is the reference up to rounding: what the broken
# variants are held to.
FLOAT32 = dict(grad_rel_tol=2e-4, grad_norm_tol=1e-4, loss_rel_tol=1e-5,
               selection_eps=1e-4, experts_rel_tol=1e-4, attn_row_tol=1e-4,
               scan_rel_tol=1e-5, router_rel_tol=1e-5)
NAME = "nemotron-labs-twotower-30b-a3b-base"
# What the chip's tiles ask of a tiny model that is compiled for it (the
# recorded trace, `tests/test_step_scopes.py`): a hidden size of a lane
# tile, experts of HALF a lane tile (64: the grouped kernels take a width
# that is no multiple of 128), 8 mixer heads a group (a block of the
# scan's rows), chunks of 128.
ON_THE_CHIP = dict(hidden_size=128, moe_intermediate_size=64,
                   moe_shared_expert_intermediate_size=128,
                   mamba_num_heads=16, chunk_size=128)


def config(layers=None, experts=None) -> dict:
    """The cell's configuration at tiny widths; `layers` picks other
    layers of the model than the cell's nine, `experts` another share."""
    with open(os.path.join(manifest.BENCH, "configs", NAME + ".json")) as f:
        out = tiny.tiny_config(json.load(f))
    out["reference_check"]["reference_blocks"] = dict(
        q_block=128, mlp_block=256, ce_block=128, scan_segment=32,
        mamba_block=64)
    if layers is not None:
        out["held"] = {**out["held"], "layers": list(layers),
                       "num_hidden_layers": len(layers)}
    if experts is not None:
        out["held"] = {**out["held"], "experts": list(experts),
                       "n_routed_experts": len(experts)}
    return out


def family(dtype=None, tolerances=None, **cut):
    """The family at tiny widths, its activations in `dtype` (None: the
    cell's bfloat16), its limits `tolerances` where given."""
    import dataclasses

    from benchmark.families import nemotronh
    cfg = config(**cut)
    if tolerances:
        cfg["reference_check"].update(tolerances)
    out = nemotronh.Family(cfg, cfg["job"])
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
