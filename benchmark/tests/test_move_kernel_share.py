"""`moe.move_kernel_share` on hand-made maps of a step
(`bps.get_step_scopes()`): the program's row-move kernel against the
compiler's gather and scatter-add, under the expert layer's `gather` and
`scatter` scopes and nowhere else."""

from unittest import mock

import pytest

import byteps_tpu as bps
from benchmark.harness import manifest, readers

CELLS = ("trinity-mini.ingraph-1chip",
         "mellum2-12b-a2.5b-instruct.ingraph-1chip",
         "keye-vl-2.0-30b-a3b.ingraph-1chip",
         "nemotron-labs-twotower-30b-a3b-base.ingraph-1chip",
         "joyai-llm-flash.ingraph-1chip")


def _entry(scope, primitive, which="forward"):
    return {"scope": scope, "pass": which,
            "op_name": f"jit(step)/{scope}/{primitive}"}


def _kernel(scope, which="forward"):
    # a body shared by several call sites: the call site lends its path
    return {"scope": scope, "pass": which,
            "op_name": f"jit(step)/{scope}/jit(_gather_sum)/pallas_call"}


PARENT = {
    "fusion.1": _entry("afmoe.moe/gather", "select_n"),     # masked rows
    "gather.2": _entry("afmoe.moe/gather", "gather"),
    "fusion.3": _entry("afmoe.moe/scatter", "scatter-add"),
    "fusion.4": _entry("afmoe.moe/scatter", "scatter-add", "backward"),
    "fusion.5": _entry("afmoe.moe/route", "gather"),        # not a move
    "ragged-dot-none_fwd.6": _entry("afmoe.moe/grouped", "pallas_call"),
}
CHANGE = {
    "moe_rows_k1.1": _kernel("afmoe.moe/gather"),
    "moe_rows_k1.2": _kernel("afmoe.moe/gather", "recompute"),
    "moe_rows_k8w.3": _kernel("afmoe.moe/scatter"),
    "moe_rows_k1.4": _kernel("afmoe.moe/scatter", "backward"),
    "moe_rows_k8.5": _kernel("afmoe.moe/gather", "backward"),
    "moe_rows_k1.6": _kernel("afmoe.moe/exact/gather"),
    "fusion.7": _entry("afmoe.moe/gather", "select_n"),     # the indices
    # single weights, a scope deeper: not rows
    "fusion.8": _entry("afmoe.moe/scatter/weights", "gather", "backward"),
    "fusion.9": _entry("afmoe.moe/scatter/weights", "scatter-add",
                       "backward"),
    "ragged-dot-none_fwd.10": _entry("afmoe.moe/grouped", "pallas_call"),
}


def _read(scopes):
    read = readers.reader("moe.move_kernel_share")
    with mock.patch.object(bps, "get_step_scopes", lambda: scopes,
                           create=True):
        return read(None)


def test_all_or_none_of_the_moves_are_the_kernel():
    assert _read(PARENT) == 0.0
    assert _read(CHANGE) == 100.0


def test_a_gather_left_beside_the_kernels_lowers_the_share():
    left = dict(CHANGE, **{"gather.11": _entry("afmoe.moe/exact/scatter",
                                               "gather", "backward")})
    assert _read(left) == pytest.approx(100.0 * 6 / 7)


def test_scopes_with_no_move_read_zero_and_no_scopes_nothing():
    assert _read({"fusion.7": CHANGE["fusion.7"]}) == 0.0
    assert _read({"fusion.1": _entry("transformer.mlp", "dot_general"),
                  "gather.2": _entry("transformer.embed", "gather")}) is None
    assert _read({}) is None
    assert _read(None) is None


def test_an_older_program_without_the_map_reads_nothing(monkeypatch):
    monkeypatch.delattr(bps, "get_step_scopes", raising=False)
    monkeypatch.setattr(bps, "_HOME", {
        k: v for k, v in bps._HOME.items() if k != "get_step_scopes"})
    assert readers.reader("moe.move_kernel_share")(None) is None


@pytest.mark.parametrize("cell", CELLS)
def test_the_manifest_lists_it_in_the_expert_cells(cell):
    metric, = (m for m in manifest.load_cell(cell).per_layer
               if m["name"] == "moe.move_kernel_share")
    assert metric["source"] == "program_counter"
    assert metric["layer"] == "expert layer"
    assert metric["moves"] == "tokens_per_s"
    assert metric["unit"] == "%" and metric["better"] == "higher"


def test_cells_without_an_expert_layer_do_not_list_it():
    for cell in ("gpt2-medium.ingraph-1chip", "vgg16.ingraph-1chip",
                 "granite-4.0-h-micro.ingraph-1chip"):
        assert "moe.move_kernel_share" not in {
            m["name"] for m in manifest.load_cell(cell).per_layer}
