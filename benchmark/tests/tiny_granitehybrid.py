"""The granitehybrid configuration cut to widths a CPU can run, for
`benchmark/tests/tiny.py` (whose table of cuts it joins when it is
imported, as `tiny_afmoe.py` does: that file names the families it knows,
and is not this PR's to edit) and for the tests of the family in `tests/`
and here.

Only widths are cut.  What the chip holds stays: the model's layers 0-9
(five mamba, attention, four mamba) and 12,544 rows of the vocabulary.
"""

import json
import os

from benchmark.harness import manifest
from benchmark.tests import tiny

CUT = {
    "published": dict(hidden_size=64, num_attention_heads=4,
                      num_key_value_heads=2, intermediate_size=128,
                      shared_intermediate_size=128, mamba_n_heads=8,
                      mamba_d_head=16, mamba_d_state=32,
                      mamba_chunk_size=64),
    "job": dict(per_chip_batch=2, seq_len=256),
    "pinned": dict(ce_chunk_rows=128),
    # 64 numbers average less than 2048: at these widths bfloat16 moves
    # the worst leaf (a mamba layer's D or dt_bias, 8 numbers a layer) by
    # up to a tenth.
    "tolerances": dict(grad_rel_tol=0.2, grad_norm_tol=0.1),
}
tiny._TINY.setdefault("granitehybrid", CUT)

# The program in float32 is the reference up to rounding: what the broken
# variants are held to.
FLOAT32 = dict(grad_rel_tol=2e-4, grad_norm_tol=1e-4, loss_rel_tol=1e-5)


def config(layers=None, vocab=None) -> dict:
    """The cell's configuration at tiny widths; `layers` picks other
    layers of the model than the cell's ten, `vocab` another slice
    `(start, rows)` of the vocabulary."""
    with open(os.path.join(manifest.BENCH, "configs",
                           "granite-4.0-h-micro.json")) as f:
        out = tiny.tiny_config(json.load(f))
    out["reference_check"]["reference_blocks"] = dict(
        q_block=128, mlp_block=256, ce_block=128, scan_segment=32)
    if layers is not None:
        out["held"] = {**out["held"], "layers": list(layers),
                       "num_hidden_layers": len(layers)}
    if vocab is not None:
        out["held"] = {**out["held"], "vocab_start": vocab[0],
                       "vocab_size": vocab[1]}
    return out
