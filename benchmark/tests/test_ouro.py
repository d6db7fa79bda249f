"""The ouro family and its new readers: the cell as the accepted readers
find it (the import of `tiny_ouro` is what lets `test_jobs.py` cut the
cell: run this directory as a whole), the readers on a hand-made map of a
step laid over a hand-made window, and where there is nothing to read."""

import dataclasses
import os
import types
from unittest import mock

import pytest

import byteps_tpu as bps
from benchmark.harness import manifest, readers, tracecap
from benchmark.reduce import xplane
from benchmark.tests import tiny_ouro  # noqa: F401  (joins tiny._TINY)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "ouro-2.6b.ingraph-1chip"
SPANS = ("loop.exit_ms", "loop.post_norm_ms")
GAUGES = {"loop.kept_MB": "bps_loop_kept_bytes",
          "exit.expected_steps": "bps_exit_expected_steps",
          "exit.entropy_nats": "bps_exit_entropy"}


def test_the_family_has_what_the_accepted_readers_ask():
    from benchmark.families import ouro as family_ouro
    cell = manifest.load_cell(CELL)
    family = family_ouro.Family(cell.config, cell.job)
    cfg = family.cfg
    assert (family.seq_len, cfg.num_layers, cfg.total_ut_steps,
            cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size) == (
        8192, 8, 4, 16, 16, 128, 5632, 49152)
    assert (cfg.attn_impl, cfg.ce_chunk_rows, cfg.remat,
            cfg.remat_policy) == ("flash", 2048, True, "none")
    assert cfg.exit_entropy_beta == 0.05 and cfg.rope_theta == 1e6
    assert family.units_per_sample == 8192 and family.unit == "tokens"
    assert family.causal_attention is True and family.selection == []
    # 12.28 + 3.22 GFLOP a token: four walks of eight layers and of the
    # head, the causal pairs of 32 layer applications
    want = 8192 * (6.0 * 4 * (8 * 51_380_224 + 49152 * 2048 + 2048)
                   + 12.0 * 32 * 8193 / 2 * 2048)
    assert family.model_flops_per_sample() == want
    assert 126e12 < want < 128e12
    # the file holds every published number; the depth alone is cut
    config = cell.config
    changed = {k for k, v in config["published"].items() if config[k] != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers"}
    assert config["held"]["layers"] == list(range(8))
    listed = {m["name"] for m in cell.per_layer}
    assert {*SPANS, *GAUGES, "step.mfu_busy", "step.head_ms",
            "step.scoped_share", "attn.roofline", "attn.ms_per_step",
            "attn.around_kernel_ms"} <= listed
    assert not any(n.startswith(("moe.", "route.", "flash", "bd."))
                   for n in listed)
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s",
                                                    "setup_s"]


def _entry(scope, which, primitive="mul"):
    return {"scope": scope, "pass": which,
            "op_name": f"jit(step)/{scope}/{primitive}"}


def test_the_span_readers_on_a_made_up_window(tmp_path):
    """One step: a product under `qkv`, the two post norms forward, again
    under remat and backward, the exit scope's passes, the head."""
    attn = "ouro.attn.full_attention"
    scopes = {
        "fusion.1": _entry(f"{attn}/qkv", "forward", "dot_general"),
        "fusion.2": _entry(f"{attn}/post_norm", "forward"),
        "fusion.3": _entry(f"{attn}/post_norm", "recompute"),
        "fusion.4": _entry(f"{attn}/post_norm", "backward"),
        "fusion.5": _entry("ouro.mlp/post_norm", "forward"),
        "fusion.6": _entry("ouro.mlp/post_norm", "backward"),
        "fusion.7": _entry("ouro.mlp", "forward", "dot_general"),
        "fusion.8": _entry("ouro.exit", "forward"),
        "fusion.9": _entry("ouro.exit", "backward"),
        "fusion.10": _entry("ouro.head", "forward", "dot_general"),
    }
    ops, t = [], 0
    for i in range(1, 11):
        ns = i * 1_000_000
        ops.append((f"%fusion.{i} = bf16[1,8192,2048]{{2,1,0}} fusion(%p)",
                    t, t + ns))
        t += ns + 1000
    ctx = tracecap.Context(
        trace=xplane.Trace(ops=[ops], async_ops=[[]], host=[]), n_steps=1,
        first_step=3, n_chips=1, samples_per_step=1, family=None, peaks=PEAKS,
        extras={}, dir=str(tmp_path))
    with mock.patch.object(bps, "get_step_scopes", lambda: scopes,
                           create=True):
        got = {name: readers.reader(name)(ctx)
               for name in (*SPANS, "step.head_ms", "attn.around_kernel_ms")}
    assert got["loop.post_norm_ms"] == pytest.approx(2 + 3 + 4 + 5 + 6)
    assert got["loop.exit_ms"] == pytest.approx(8 + 9)
    # and the accepted readers read the family's scopes as the others'
    assert got["step.head_ms"] == pytest.approx(10)
    assert got["attn.around_kernel_ms"] == pytest.approx(1)


def test_the_gauge_readers():
    from byteps_tpu.common import telemetry
    from byteps_tpu.models import ouro
    telemetry.record_static("loop", steps=4, layer_applications=32,
                            kept_bytes=32 * 8192 * 2048 * 2)
    ouro.record_exit({"share": [0.5, 0.25, 0.125, 0.125],
                      "nll": [10.8, 10.8, 10.8, 10.8],
                      "expected_steps": 1.875, "entropy": 1.2130})
    ctx = types.SimpleNamespace()
    assert readers.reader("loop.kept_MB")(ctx) == pytest.approx(1073.741824)
    assert readers.reader("exit.expected_steps")(ctx) == 1.875
    assert readers.reader("exit.entropy_nats")(ctx) == pytest.approx(1.2130)


def test_new_readers_say_nothing_where_there_is_nothing():
    """On a trace of another model (what the parent's program gives a
    traced run of any cell) the span readers return None, and the gauges'
    readers None for a program that set no such gauge."""
    from byteps_tpu.common import telemetry
    registry = telemetry.get_registry()
    for name in GAUGES.values():
        registry.gauge(name).set(0)
    trace = xplane.read(os.path.join(DATA, "tiny_mellum.xplane.pb"),
                        host_prefix=tracecap.PREFIX)
    ctx = tracecap.Context(
        trace=trace, n_steps=5, first_step=3, n_chips=1, samples_per_step=1,
        family=types.SimpleNamespace(), peaks=PEAKS, extras={}, dir=DATA)
    mellum = {"fusion.1": _entry("mellum.moe/grouped", "forward")}
    with mock.patch.object(bps, "get_step_scopes", lambda: mellum,
                           create=True):
        assert [readers.reader(name)(ctx) for name in SPANS] == [None, None]
    assert [readers.reader(name)(ctx) for name in GAUGES] == [None] * 3
    empty = dataclasses.replace(ctx, trace=xplane.Trace([[]], [[]], []))
    with mock.patch.object(bps, "get_step_scopes", lambda: None,
                           create=True):
        assert [readers.reader(name)(empty) for name in SPANS] == [None, None]
