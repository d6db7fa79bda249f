"""The joyai configuration cut to widths a CPU can run, for
`benchmark/tests/tiny.py` (whose table of cuts it joins when it is
imported, as `tiny_mellum.py` does) and for the tests of the family in
`tests/` and here.

Only widths are cut, each keeping what makes the published one awkward: a
query and a key stay one and a half times a value (24 and 16), the rotary
part half the other, the two ranks unlike each other and unlike every
other width (so a test can tell the norms apart by what they norm).  What
the chip holds stays: the dense layer, four expert layers and the
prediction module, 16 of 256 experts, 8 a token, 16,160 rows of the
vocabulary.  Dense attention, so that a sequence is any length; the flash
kernels at 192/128 have their own tests (`tests/test_joyai.py`).
"""

import json
import os

from benchmark.harness import manifest
from benchmark.tests import tiny

CUT = {
    "published": dict(hidden_size=64, num_attention_heads=2,
                      num_key_value_heads=2, q_lora_rank=48, kv_lora_rank=32,
                      qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
                      head_dim=8, v_head_dim=16, intermediate_size=128,
                      moe_intermediate_size=32),
    "job": dict(per_chip_batch=2, seq_len=64),
    "pinned": dict(ce_chunk_rows=64, attn_impl="dense"),
    # 64 numbers average less than 2048: at these widths bfloat16 moves a
    # router's score by up to 0.01, some tokens swap an expert, and the
    # router's own gradient is off by up to a third.
    "tolerances": dict(grad_rel_tol=0.45, grad_norm_tol=0.15,
                       selection_eps=0.03, attn_rel_tol=0.01,
                       experts_rel_tol=0.012, router_rel_tol=1e-4),
}
tiny._TINY.setdefault("joyai", CUT)

# The program in float32 is the reference up to rounding: what the broken
# variants are held to.
FLOAT32 = dict(grad_rel_tol=2e-4, grad_norm_tol=1e-4, loss_rel_tol=1e-5,
               selection_eps=1e-4, experts_rel_tol=1e-4, attn_rel_tol=1e-4,
               router_rel_tol=1e-5)
NAME = "joyai-llm-flash"
# What the chip's tiles ask of a tiny model that is compiled for it (the
# recorded trace, `tests/test_step_scopes.py`): the published head (128 +
# 64 and 128: the widths the kernels' names carry), a hidden size of a
# lane tile, experts of a lane tile.
ON_THE_CHIP = dict(hidden_size=128, q_lora_rank=128, kv_lora_rank=128,
                   qk_nope_head_dim=128, qk_rope_head_dim=64,
                   qk_head_dim=192, head_dim=64, v_head_dim=128,
                   intermediate_size=256, moe_intermediate_size=128)


def config(layers=None, experts=None, modules=None) -> dict:
    """The cell's configuration at tiny widths; `layers` picks other
    layers of the model than the cell's five, `experts` another share,
    `modules` how many prediction modules run (None: the published 1)."""
    with open(os.path.join(manifest.BENCH, "configs", NAME + ".json")) as f:
        out = tiny.tiny_config(json.load(f))
    out["reference_check"]["reference_blocks"] = dict(
        q_block=32, mlp_block=64, ce_block=64)
    if layers is not None:
        out["held"] = {**out["held"], "layers": list(layers),
                       "num_hidden_layers": len(layers)}
    if experts is not None:
        out["held"] = {**out["held"], "experts": list(experts),
                       "n_routed_experts": len(experts)}
    if modules is not None:
        out["held"] = {**out["held"], "num_nextn_predict_layers": modules}
    return out


def family(dtype=None, tolerances=None, **cut):
    """The family at tiny widths, its activations in `dtype` (None: the
    cell's bfloat16), its limits `tolerances` where given."""
    import dataclasses

    from benchmark.families import joyai
    cfg = config(**cut)
    if tolerances:
        cfg["reference_check"].update(tolerances)
    out = joyai.Family(cfg, cfg["job"])
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
