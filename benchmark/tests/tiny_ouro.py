"""The ouro configuration cut to widths a CPU can run, for
`benchmark/tests/tiny.py` (whose table of cuts it joins when it is
imported, as `tiny_mellum.py` does) and for the tests of the family in
`tests/` and here.

Widths are cut, the vocabulary (512 rows) and the sequence; the four
walks stay.  The cell's eight layers are all alike, so the cases name the
depth they hold, and the walks where a case runs fewer.
"""

import json
import os

from benchmark.harness import manifest
from benchmark.tests import tiny

CUT = {
    "published": dict(hidden_size=64, num_attention_heads=4,
                      num_key_value_heads=4, head_dim=16,
                      intermediate_size=160, vocab_size=512),
    "job": dict(per_chip_batch=1, seq_len=128),
    "pinned": dict(ce_chunk_rows=128),
    # 64 numbers average less than 2048, and 128 rows less than 8,192: at
    # these widths bfloat16 moves the worst leaf by 2.6-11% over seeds 0-2
    # (the gate's bias, ONE number, the sum of signed terms a token; else
    # a norm's scale or attn_out_w at 2.7%) and a row's NLL by 1.5e-3 to
    # 1.8e-3 (root mean square; 1.3e-2 with the logits in bfloat16).
    "tolerances": dict(grad_rel_tol=0.2, grad_norm_tol=0.2,
                       nll_rms_tol=0.005),
}
tiny._TINY.setdefault("ouro", CUT)

# The program in float32 is the reference up to rounding: what the broken
# variants are held to.
FLOAT32 = dict(grad_rel_tol=1e-4, grad_norm_tol=1e-4, loss_rel_tol=1e-5,
               exit_abs_tol=1e-5, nll_rms_tol=1e-4)


def config(layers=None, walks=None) -> dict:
    """The cell's configuration at tiny widths; `layers` picks other
    layers of the model than the cell's eight, `walks` another number of
    walks than the model's four."""
    with open(os.path.join(manifest.BENCH, "configs",
                           "ouro-2.6b.json")) as f:
        out = tiny.tiny_config(json.load(f))
    if layers is not None:
        out["held"] = {**out["held"], "layers": list(layers),
                       "num_hidden_layers": len(layers)}
    if walks is not None:
        out["published"]["total_ut_steps"] = walks
    return out
