"""The sdar configuration cut to widths a CPU can run, for
`benchmark/tests/tiny.py` (whose table of cuts it joins when it is
imported, as `tiny_mellum.py` does) and for the tests of the family in
`tests/` and here.

Only widths are cut, and the sequence: 128 tokens are 256 rows in the
layers, two tiles of 128 of which each lies in one copy.  What the chip
holds stays: 16 of 128 experts, 8 a row, 18,992 rows of the vocabulary,
blocks of 4; the cell's six layers are all alike, so the cases name the
depth they hold.
"""

import json
import os

from benchmark.harness import manifest
from benchmark.tests import tiny

CUT = {
    "published": dict(hidden_size=64, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16,
                      moe_intermediate_size=32),
    "job": dict(per_chip_batch=2, seq_len=128),
    "pinned": dict(ce_chunk_rows=128),
    # 64 numbers average less than 2048: at these widths bfloat16 moves a
    # router's logits by up to 0.05, a tenth of the rows swap an expert,
    # and the router's own gradient is off by up to 30%.
    "tolerances": dict(grad_rel_tol=0.4, grad_norm_tol=0.15,
                       selection_eps=0.15, attn_row_tol=0.0037,
                       experts_rel_tol=0.01),
}
tiny._TINY.setdefault("sdarmoe", CUT)

# The program in float32 is the reference up to rounding: what the broken
# variants are held to.
FLOAT32 = dict(grad_rel_tol=1e-4, grad_norm_tol=1e-4, loss_rel_tol=1e-5,
               selection_eps=1e-3, experts_rel_tol=1e-4, attn_row_tol=1e-4)


def config(layers=None, experts=None) -> dict:
    """The cell's configuration at tiny widths; `layers` picks other
    layers of the model than the cell's six, `experts` another share."""
    with open(os.path.join(manifest.BENCH, "configs",
                           "sdar-30b-a3b-chat.json")) as f:
        out = tiny.tiny_config(json.load(f))
    if layers is not None:
        out["held"] = {**out["held"], "layers": list(layers),
                       "num_hidden_layers": len(layers)}
    if experts is not None:
        out["held"] = {**out["held"], "experts": list(experts),
                       "num_experts": len(experts)}
    return out
