"""The afmoe configuration cut to widths a CPU can run, for
`benchmark/tests/tiny.py` (whose table of cuts it joins when it is
imported: that file names the families it knows, and is not this PR's to
edit) and for the tests of the family in `tests/` and here.

Only widths are cut.  What the chip holds stays: five layers, 16 of 128
experts, 8 a token, 25,024 rows of the vocabulary.
"""

import json
import os

from benchmark.harness import manifest
from benchmark.tests import tiny

CUT = {
    "published": dict(hidden_size=64, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16,
                      intermediate_size=128, moe_intermediate_size=32,
                      sliding_window=128),
    "job": dict(per_chip_batch=2, seq_len=256),
    # (unit norm scales, as the tolerances below were measured)
    "pinned": dict(ce_chunk_rows=128, post_attn_norm_init=1.0),
    # 64 numbers average less than 2048: at these widths bfloat16 moves a
    # router's scores by up to 0.02 (0.002 at the published widths), a
    # tenth of the tokens swap an expert, and the router's own gradient
    # is off by up to 25%.
    "tolerances": dict(grad_rel_tol=0.4, grad_norm_tol=0.15,
                       selection_eps=0.05),
}
tiny._TINY.setdefault("afmoe", CUT)

# The program in float32 is the reference up to rounding: what the broken
# variants are held to.
FLOAT32 = dict(grad_rel_tol=1e-4, grad_norm_tol=1e-4, loss_rel_tol=1e-5,
               selection_eps=1e-4)


def config(layers=None, published_dense_layers=None) -> dict:
    """The cell's configuration at tiny widths; `layers` picks other
    layers of the model than the cell's five."""
    with open(os.path.join(manifest.BENCH, "configs",
                           "trinity-mini.json")) as f:
        out = tiny.tiny_config(json.load(f))
    if published_dense_layers is not None:
        out["published"]["num_dense_layers"] = published_dense_layers
    if layers is not None:
        dense = sum(i < out["published"]["num_dense_layers"] for i in layers)
        out["held"] = {**out["held"], "layers": list(layers),
                       "num_hidden_layers": len(layers),
                       "num_dense_layers": dense}
    return out
