"""The join of a traced step with the program's map of it
(`benchmark/reduce/scopes.py`) on a hand-made trace and map, and the
fifteen readers over it."""

import json
import types

import pytest

import byteps_tpu as bps
from benchmark.harness import manifest, readers, tracecap
from benchmark.reduce import intervals, scopes, xplane

NEW = ("step.fwd_ms", "step.bwd_ms", "step.remat_ms", "step.opt_ms",
       "step.head_ms", "step.scoped_share", "img.step.fwd_ms",
       "img.step.bwd_ms", "img.step.opt_ms", "img.step.scoped_share",
       "moe.scope_ms", "moe.exact_ms", "moe.move_ms",
       "attn.around_kernel_ms", "mamba.scope_ms")


def _entry(scope, which):
    return {"scope": scope, "pass": which, "op_name": "jit(step)/" + scope}


# instruction -> (start, end) in ns, two steps of 100 ns; `while.1` holds
# the layer's instructions and keeps 2 ns of its own
OPS = {"while.1": (0, 60), "fusion.1": (0, 10), "fusion.2": (10, 25),
       "afmoe.attn.7": (25, 40), "fusion.3": (40, 45), "fusion.4": (45, 50),
       "fusion.5": (50, 58), "fusion.6": (60, 70), "fusion.7": (70, 80),
       "fusion.8": (80, 90), "fusion.9": (90, 96), "copy.1": (96, 100)}
MAP = {"while.1": _entry("", "forward"),
       "fusion.1": _entry("afmoe.attn.full_attention/qkv", "forward"),
       "fusion.2": _entry("afmoe.moe/route", "forward"),
       "afmoe.attn.7": _entry("afmoe.attn.full_attention", "backward"),
       "fusion.3": _entry("afmoe.moe/gather", "backward"),
       "fusion.4": _entry("afmoe.moe/exact/gather", "backward"),
       # a kernel of the compiler's, which the map lent its scope
       "fusion.5": dict(_entry("afmoe.moe", "recompute"), lent=True),
       "fusion.6": _entry("afmoe.head", "forward"),
       "fusion.7": _entry("byteps.optimizer", "optimizer"),
       "fusion.8": _entry("granite.mamba.scan/granite.mamba.scan",
                          "backward"),
       "fusion.9": _entry("afmoe.attn.sliding_attention/out", "recompute"),
       "copy.1": _entry("", "other")}


def _events(ops):
    return [(f"%{name} = f32[8]{{0}} fusion(%p), kind=kLoop", s, e)
            for name, (s, e) in ops.items()]


def _ctx(ops=OPS, tmp_path="."):
    return tracecap.Context(
        trace=xplane.Trace(ops=[_events(ops)], async_ops=[[]], host=[]),
        n_steps=2, n_chips=1, samples_per_step=1, family=None, peaks={},
        extras={}, first_step=3, dir=str(tmp_path))


@pytest.fixture
def program(monkeypatch):
    """`bps.get_step_scopes` gives what the test puts in `program.map`."""
    holder = types.SimpleNamespace(map=dict(MAP))
    monkeypatch.setattr(bps, "get_step_scopes", lambda: holder.map,
                        raising=False)
    monkeypatch.setattr(scopes, "_kept", None)
    return holder


def _read(name, ctx):
    return readers.reader(name)(ctx)


def test_the_passes_partition_the_busy_time(program, tmp_path):
    ctx = _ctx(tmp_path=tmp_path)
    j = scopes.join(ctx)
    assert j.busy_ns == intervals.total(ctx.busy(0)) == 100
    assert sum(j.by_pass.values()) == j.busy_ns
    assert j.by_pass == {"forward": 37, "backward": 35, "recompute": 14,
                         "optimizer": 10, "other": 4}
    assert _read("step.fwd_ms", ctx) == 37 / 2 / 1e6
    assert _read("step.bwd_ms", ctx) == 35 / 2 / 1e6
    assert _read("step.remat_ms", ctx) == 14 / 2 / 1e6
    assert _read("step.opt_ms", ctx) == 10 / 2 / 1e6
    assert _read("img.step.opt_ms", ctx) == 10 / 2 / 1e6
    assert _read("step.scoped_share", ctx) == pytest.approx(94.0)
    assert scopes.join(ctx) is j                  # one join a context
    written = json.load(open(tmp_path / "scopes.json"))
    assert [r[0] for r in written["unplaced_ms_per_step"]] == [
        "copy.1", "while.1"]
    # what the map lent counts in the share and in its scope, and the file
    # says how much of either that is
    assert j.lent == {"afmoe.moe": 8}
    assert written["scoped_share"] == pytest.approx(94.0)
    assert written["lent_share"] == pytest.approx(8.0)
    assert written["lent_ms_per_step_by_scope"] == {"afmoe.moe": 8 / 2 / 1e6}
    assert written["ms_per_step_by_scope"]["afmoe.moe"] == 8 / 2 / 1e6


def test_a_childs_time_is_its_parents_and_no_siblings(program, tmp_path):
    ctx = _ctx(tmp_path=tmp_path)
    j = scopes.join(ctx)
    assert j.under("afmoe.moe") == 15 + 5 + 5 + 8
    assert j.under("afmoe.moe/gather") == 5       # not the exact path's
    assert j.under("afmoe.moe/exact") == 5
    assert j.under("afmoe.mo") == 0               # whole components only
    assert _read("moe.scope_ms", ctx) == 33 / 2 / 1e6
    assert _read("moe.exact_ms", ctx) == 5 / 2 / 1e6
    assert _read("moe.move_ms", ctx) == 5 / 2 / 1e6
    assert _read("step.head_ms", ctx) == 10 / 2 / 1e6
    # the kernel is the half's own time, not a child's
    assert _read("attn.around_kernel_ms", ctx) == (10 + 6) / 2 / 1e6
    assert _read("mamba.scope_ms", ctx) == 10 / 2 / 1e6


def test_an_instruction_the_map_lacks_lowers_the_share_alone(program,
                                                             tmp_path):
    whole = {n: _read(n, _ctx(tmp_path=tmp_path)) for n in NEW}
    del program.map["fusion.6"]                   # the head's, 10 ns
    scopes._kept = None
    short = {n: _read(n, _ctx(tmp_path=tmp_path)) for n in NEW}
    assert short["step.scoped_share"] == pytest.approx(84.0)
    moved = {n for n in NEW if short[n] != whole[n]}
    # its time leaves its scope and its pass for "other", nothing else
    assert moved == {"step.scoped_share", "img.step.scoped_share",
                     "step.head_ms", "step.fwd_ms", "img.step.fwd_ms"}
    assert short["step.head_ms"] is None


@pytest.mark.parametrize("gives", ["no function", "none", "raises nothing"])
def test_without_a_map_all_fifteen_read_nothing(monkeypatch, gives,
                                                tmp_path):
    monkeypatch.setattr(scopes, "_kept", None)
    if gives == "no function":                    # an older program
        monkeypatch.delattr(bps, "get_step_scopes", raising=False)
        monkeypatch.setattr(bps, "_HOME", {
            k: v for k, v in bps._HOME.items() if k != "get_step_scopes"})
    elif gives == "none":                         # no step was built
        monkeypatch.setattr(bps, "get_step_scopes", lambda: None,
                            raising=False)
    else:                                         # a trace with no chip
        monkeypatch.setattr(bps, "get_step_scopes", lambda: dict(MAP),
                            raising=False)
    ctx = _ctx(ops={} if gives == "raises nothing" else OPS,
               tmp_path=tmp_path)
    assert [n for n in NEW if _read(n, ctx) is not None] == []


def test_the_manifest_lists_the_fifteen_where_the_issue_puts_them():
    listed = {}
    for cell in ("gpt2-medium.ingraph-1chip", "gpt2-medium.ps-joint-1chip",
                 "vgg16.ingraph-dp4", "vgg16.ingraph-1chip",
                 "trinity-mini.ingraph-1chip",
                 "granite-4.0-h-micro.ingraph-1chip",
                 "mellum2-12b-a2.5b-instruct.ingraph-1chip"):
        loaded = manifest.load_cell(cell)
        moves = {m["name"] for m in loaded.end_to_end} - {"setup_s"}
        for m in loaded.per_layer:
            if m["name"] in NEW:
                assert m["source"] == "program_span"
                assert {m["moves"]} == moves
                listed.setdefault(m["name"], []).append(cell.split(".")[0])
    assert set(listed) == set(NEW)
    assert "gpt2-medium.ps-joint-1chip" not in str(listed)
    assert listed["step.remat_ms"] == ["gpt2-medium", "trinity-mini",
                                       "granite-4", "mellum2-12b-a2"]
    assert listed["img.step.bwd_ms"] == ["vgg16", "vgg16"]
    assert listed["moe.move_ms"] == ["trinity-mini", "mellum2-12b-a2"]
    assert listed["attn.around_kernel_ms"] == ["trinity-mini", "granite-4",
                                               "mellum2-12b-a2"]
    assert listed["mamba.scope_ms"] == ["granite-4"]
