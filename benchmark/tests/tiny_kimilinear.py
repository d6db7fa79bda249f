"""The kimilinear configuration cut to widths a CPU can run, for
`benchmark/tests/tiny.py` (whose table of cuts it joins when it is
imported, as `tiny_mellum.py` does) and for the tests of the family in
`tests/` and here.

Only widths are cut: a hidden size of 64, two KDA heads of 16 channels,
two latent-attention heads whose queries and keys are 16 + 8 wide and
values 16 over a latent of 16, a dense width that is no multiple of the
experts'.  What the chip holds stays: the model's layer 1 (KDA, dense)
and layers 5-8 (KDA, KDA, KDA, latent attention; experts) in three runs,
8 of 256 experts, 8 a token, 20,480 rows of the vocabulary.  The model
has one path, so the tests run the kernels in the interpreter: a sequence
is 128 positions, the flash kernels' tile and two of the scan's chunks.
"""

import json
import os

from benchmark.harness import manifest
from benchmark.tests import tiny

NAME = "kimi-linear-48b-a3b-instruct"


def _published():
    with open(os.path.join(manifest.BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)["published"]


CUT = {
    "published": dict(
        hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        linear_attn_config={**_published()["linear_attn_config"],
                            "num_heads": 2, "head_dim": 16}),
    "job": dict(per_chip_batch=2, seq_len=128),
    "pinned": dict(ce_chunk_rows=64),
    # 64 numbers average less than 2304: at these widths bfloat16 moves a
    # router's score by up to 0.01, some tokens swap an expert, and the
    # router's own gradient is off by up to a third.
    "tolerances": dict(grad_rel_tol=0.45, grad_norm_tol=0.15,
                       selection_eps=0.03, attn_rel_tol=0.01,
                       experts_rel_tol=0.012, router_rel_tol=1e-4,
                       conv_rel_tol=0.004, kda_rel_tol=1e-4),
}
tiny._TINY.setdefault("kimilinear", CUT)

# The program in float32 is the reference up to rounding: what the broken
# variants are held to.
FLOAT32 = dict(grad_rel_tol=5e-4, grad_norm_tol=2e-4, loss_rel_tol=1e-5,
               selection_eps=1e-4, experts_rel_tol=1e-4, attn_rel_tol=1e-4,
               router_rel_tol=1e-5, conv_rel_tol=1e-5, kda_rel_tol=1e-4)


# What the chip's tiles ask of a tiny model that is compiled for it: the
# published heads (KDA's 128 channels; 128 + 64 and 128 over a latent of a
# lane tile), a hidden size of two lane tiles, experts of a lane tile.
ON_THE_CHIP = dict(
    hidden_size=256, kv_lora_rank=128, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, intermediate_size=384,
    moe_intermediate_size=128,
    linear_attn_config={**CUT["published"]["linear_attn_config"],
                        "head_dim": 128})


def config(layers=None, experts=None, vocab=None) -> dict:
    """The cell's configuration at tiny widths; `layers` picks other
    layers of the model (numbered from 1, as `linear_attn_config` does)
    than the cell's five, `experts` another share, `vocab` another count
    of held rows."""
    with open(os.path.join(manifest.BENCH, "configs", NAME + ".json")) as f:
        out = tiny.tiny_config(json.load(f))
    out["reference_check"]["reference_blocks"] = dict(
        q_block=64, mlp_block=64, ce_block=64, head_block=1, scan_block=32)
    if layers is not None:
        lin = out["published"]["linear_attn_config"]
        out["held"] = {
            **out["held"], "layers": list(layers),
            "num_hidden_layers": len(layers),
            "layer_kinds": ["kda" if i in lin["kda_layers"] else "mla"
                            for i in layers]}
    if experts is not None:
        out["held"] = {**out["held"], "experts": list(experts),
                       "num_experts": len(experts)}
    if vocab is not None:
        out["held"] = {**out["held"], "vocab_size": vocab}
    return out


def family(dtype=None, tolerances=None, **cut):
    """The family at tiny widths, its activations in `dtype` (None: the
    cell's bfloat16), its limits `tolerances` where given."""
    import dataclasses

    from benchmark.families import kimilinear
    cfg = config(**cut)
    if tolerances:
        cfg["reference_check"].update(tolerances)
    out = kimilinear.Family(cfg, cfg["job"])
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
