"""The keye family, what the accepted readers make of it, and its new
readers: the cell at tiny widths (the import of `tiny_keye` is what lets
`test_jobs.py` cut the cell: run this directory as a whole), the costs on
hand-counted shapes, the readers on a trace recorded on a TPU v5e
(data/tiny_keye.xplane.pb: `tools/reference_check.py --record`, five
traced steps of two layers)."""

import os

import pytest

from benchmark.harness import manifest, readers, tracecap
from benchmark.reduce import afmoe_cost, sparse_cost, xplane
from benchmark.tests import tiny_keye  # noqa: F401  (joins tiny's cuts)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "keye-vl-2.0-30b-a3b.ingraph-1chip"
SPARSE = ("sparse.index_ms_per_step", "sparse.select_ms_per_step",
          "sparse.attn_ms_per_step", "sparse.index_roofline",
          "sparse.attn_roofline", "sparse.selected_share",
          "sparse.swapped_share")

FWD = ('%sparse_fwd.3 = (bf16[1,4,8,32768,128]{4,3,2,1,0:T(8,128)(2,1)}, '
       'f32[1,32,1,32768]{3,2,1,0:T(1,128)}, f32[1,1,32768]{2,1,0:T(1,128)}) '
       'custom-call(%a, %b, %c), custom_call_target="tpu_custom_call"')
SELECT = ('%index_topk.7 = f32[1,32768,128]{2,1,0:T(8,128)} '
          'custom-call(%a, %b, %c), custom_call_target="tpu_custom_call"')


def test_the_kernels_are_told_by_their_names_and_costed_at_the_selection():
    assert sparse_cost.kernel(FWD) == "forward"
    assert sparse_cost.kernel(SELECT) == "select"
    assert sparse_cost.kernel(FWD.replace("sparse_fwd", "sparse_dkv")) == "dkv"
    assert sparse_cost.kernel(FWD.replace("sparse_fwd", "fusion")) is None
    # the accepted attention readers do not take an attention kernel of
    # this kind for a flash call; the selection's one wide float32 result
    # they WOULD take for a dq call, which is one reason the cell is in
    # none of their lists
    assert afmoe_cost.attention_call(FWD) is None
    assert afmoe_cost.attention_call(SELECT) == ("dq", 1, 32768, 128, None)
    causal = sparse_cost.causal_pairs(32768)
    chosen = sparse_cost.selected_pairs(32768, 2048)
    assert causal == 32768 * 32769 // 2
    assert chosen == 2048 * 2049 // 2 + (32768 - 2048) * 2048
    assert 0.1209 < chosen / causal < 0.1211
    # a sequence no longer than topk selects every causal pair
    assert sparse_cost.selected_pairs(1024, 2048) == 1024 * 1025 // 2
    flops, nbytes = sparse_cost.index_cost(32768, 16, 64)
    assert flops == 2.0 * causal * 16 * 64
    assert nbytes == 17 * 32768 * 64 * 2 + 16 * 32768 * 4 + 32768 * 8
    for kind, matmuls in (("forward", 2), ("dq", 3), ("dkv", 4)):
        flops, _ = sparse_cost.attention_cost(kind, 32768, 2048, 32, 4, 128)
        assert flops == matmuls * 2.0 * chosen * 32 * 128
    _, nbytes = sparse_cost.attention_cost("forward", 32768, 2048, 32, 4, 128)
    assert nbytes == (2 * 32 + 2 * 4) * 32768 * 128 * 2 + 32 * 32768 * 4


def test_the_family_has_what_the_accepted_readers_ask():
    from benchmark.families import keye as family_keye
    cell = manifest.load_cell(CELL)
    family = family_keye.Family(cell.config, cell.job)
    cfg = family.cfg
    assert (family.seq_len, cfg.num_experts, cfg.num_experts_per_tok,
            len(cfg.held), cfg.moe_intermediate_size) == (32768, 128, 8, 16,
                                                          768)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (16, 64,
                                                                     2048)
    assert cfg.moe.score_func == "softmax" and cfg.moe.hold_held_weight
    assert family.routing_counters == [] and family.selection == []
    assert cfg.layer_types == ("full_attention",) * 4
    listed = {m["name"] for m in cell.per_layer}
    assert set(SPARSE) <= listed
    assert {"step.mfu_busy", "step.scoped_share", "moe.grouped_roofline",
            "moe.scope_ms", "route.overflow_rows",
            "attn.around_kernel_ms"} <= listed
    # read from shapes that this family's own arrays share ([tokens, 128]
    # is `aux` here, and the experts' scores), or from flash calls it has not
    assert not {"moe.ms_per_step", "attn.ms_per_step", "attn.roofline",
                "attn.full_ms_per_step", "flash_roofline"} & listed
    # attention counted at the selected pairs, the indexer at the causal
    flops = family.model_flops_per_sample()
    attention = 4 * 12.0 * sparse_cost.selected_pairs(32768, 2048) * 4096
    indexer = 4 * 2.0 * sparse_cost.causal_pairs(32768) * 1024
    assert attention + indexer < flops < 4 * (attention + indexer)


def test_new_readers_say_nothing_where_there_is_nothing():
    """On a trace of another model, and for a family without the counters
    (what the parent's program gives), every new reader returns None."""
    from benchmark.families import mellum as family_mellum
    from benchmark.tests import tiny_mellum
    config = tiny_mellum.config(layers=[2, 3])
    family = family_mellum.Family(config, config["job"])
    family.selection = [{"swapped_share": 0.05}]
    trace = xplane.read(os.path.join(DATA, "tiny_mellum.xplane.pb"),
                        host_prefix=tracecap.PREFIX)
    ctx = tracecap.Context(
        trace=trace, n_steps=5, first_step=3, n_chips=1, samples_per_step=1,
        family=family, peaks=PEAKS, extras={}, dir=DATA)
    for name in SPARSE:
        assert readers.reader(name)(ctx) is None, name


@pytest.fixture(scope="module")
def ctx():
    """The trace `tools/reference_check.py --record` made on a TPU v5e:
    heads of 128 and indexer heads of 64 (the published sizes), two
    layers, one sequence of 1,024 positions of which a row selects 256,
    tiles of 128 x 128, five traced steps."""
    from benchmark.families import keye as family_keye
    config = tiny_keye.config(layers=[0, 1])
    config["published"].update(
        head_dim=128,
        rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                      "type": "default"},
        sa_config={"indexer_head_dim": 64, "indexer_num_heads": 4,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                   "q_chunk_size": 512, "topk": 256})
    config["job"].update(per_chip_batch=1, seq_len=1024)
    family = family_keye.Family(config, config["job"])
    family.routing_counters = [
        {"held_rows_per_token": [1.0, 1.5], "max_load_over_mean": [1.1, 1.3],
         "overflow_rows": [0.0, 0.0]}]
    family.selection = [{"swapped_share": 0.04, "key_swapped_share": 0.9,
                         "selected_share": 0.43}]
    trace = xplane.read(os.path.join(DATA, "tiny_keye.xplane.pb"),
                        host_prefix=tracecap.PREFIX)
    return tracecap.Context(
        trace=trace, n_steps=5, first_step=3, n_chips=1, samples_per_step=1,
        family=family, peaks=PEAKS, extras={}, dir=DATA)


def test_recorded_trace_names_every_kernel(ctx):
    spans = sparse_cost.kernel_spans(ctx.ops(0))
    # a layer and step: the selection ONCE (kept for the backward pass),
    # forward, forward again under remat, dq, dkv
    assert {k: len(v) for k, v in spans.items()} == {
        "select": 2 * 5, "forward": 2 * 5 * 2, "dq": 2 * 5, "dkv": 2 * 5}
    # none of them is a flash call to the accepted readers but the
    # selection's one wide result, which is why the cell is not in their
    # lists
    taken = [n for n, _, _ in ctx.ops(0) if afmoe_cost.attention_call(n)]
    assert {sparse_cost.kernel(n) for n in taken} <= {"select"}


def test_readers_on_the_recorded_trace(ctx):
    got = {name: readers.reader(name)(ctx) for name in (
        *SPARSE[1:], "step.device_ms", "step.mfu_busy",
        "moe.grouped_roofline", "route.held_rows_per_token",
        "route.overflow_rows", "route.swapped_share")}
    assert 0 < got["sparse.select_ms_per_step"] < got["step.device_ms"]
    assert 0 < got["sparse.attn_ms_per_step"] < got["step.device_ms"]
    spans = sparse_cost.kernel_spans(ctx.ops(0))
    assert got["sparse.select_ms_per_step"] == pytest.approx(
        sum(spans["select"]) / 5 / 1e6)
    assert got["sparse.attn_ms_per_step"] == pytest.approx(
        sum(sum(spans[k]) for k in ("forward", "dq", "dkv")) / 5 / 1e6)
    # tiny calls are all launch overhead: far below their rooflines
    assert 0 < got["sparse.index_roofline"] < 100
    assert 0 < got["sparse.attn_roofline"] < 100
    assert 0 < got["step.mfu_busy"] < 100
    assert got["sparse.selected_share"] == pytest.approx(43.0)
    assert got["sparse.swapped_share"] == pytest.approx(90.0)
    # the accepted readers that the cell lists read this family unedited
    assert got["route.held_rows_per_token"] == pytest.approx(1.25)
    assert got["route.overflow_rows"] == 0
    assert got["route.swapped_share"] == pytest.approx(4.0)
    assert got["moe.grouped_roofline"] is None or (
        0 < got["moe.grouped_roofline"] < 100)
    # the scopes' readers find no map beside a recorded trace
    assert readers.reader("sparse.index_ms_per_step")(ctx) is None
