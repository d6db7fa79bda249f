"""The mellum configuration cut to widths a CPU can run, for
`benchmark/tests/tiny.py` (whose table of cuts it joins when it is
imported, as `tiny_afmoe.py` does) and for the tests of the family in
`tests/` and here.

Only widths are cut, and YaRN's original context with them (so that the
ramp still falls inside the head's pairs).  What the chip holds stays:
four layers, 16 of 64 experts, 8 a token, 24,576 rows of the vocabulary.
"""

import copy
import json
import os

from benchmark.harness import manifest
from benchmark.tests import tiny

CUT = {
    "published": dict(hidden_size=64, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16,
                      moe_intermediate_size=32, sliding_window=128),
    "job": dict(per_chip_batch=2, seq_len=256),
    "pinned": dict(ce_chunk_rows=128),
    # 64 numbers average less than 2304: at these widths bfloat16 moves a
    # router's logits by up to 0.05, a tenth of the tokens swap an
    # expert, and the router's own gradient is off by up to 30%.
    "tolerances": dict(grad_rel_tol=0.4, grad_norm_tol=0.15,
                       selection_eps=0.15, attn_row_tol=0.0037,
                       experts_rel_tol=0.01),
}
tiny._TINY.setdefault("mellum", CUT)

# The program in float32 is the reference up to rounding: what the broken
# variants are held to.
FLOAT32 = dict(grad_rel_tol=1e-4, grad_norm_tol=1e-4, loss_rel_tol=1e-5,
               selection_eps=1e-3, experts_rel_tol=1e-4, attn_row_tol=1e-4)


def config(layers=None, experts=None) -> dict:
    """The cell's configuration at tiny widths; `layers` picks other
    layers of the model than the cell's four, `experts` another share."""
    with open(os.path.join(manifest.BENCH, "configs",
                           "mellum2-12b-a2.5b-instruct.json")) as f:
        out = tiny.tiny_config(json.load(f))
    rope = copy.deepcopy(out["published"]["rope_parameters"])
    # 8 pairs in a head of 16: d(32) = -0.99, d(1) = 2.02, so the ramp
    # runs over pairs 0-3 and pairs 3-7 are interpolated
    rope["full_attention"]["original_max_position_embeddings"] = 64
    rope["full_attention"]["rope_theta"] = 10000
    rope["sliding_attention"]["rope_theta"] = 10000
    out["published"]["rope_parameters"] = rope
    if layers is not None:
        out["held"] = {**out["held"], "layers": list(layers),
                       "num_hidden_layers": len(layers)}
    if experts is not None:
        out["held"] = {**out["held"], "experts": list(experts),
                       "num_experts": len(experts)}
    return out
