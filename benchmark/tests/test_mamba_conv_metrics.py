"""`mamba.conv_ms` and `mamba.conv_kernel_share` on hand-made maps of a
step (`bps.get_step_scopes()`) laid over a hand-made window: the Mamba-2
mixers' convolution as the compiler's own fusions (the `jnp` form, until
PR 56) and as the program's two Pallas kernels (`ops/short_conv.py`
`mamba_conv`), and a cell without the scope."""

import dataclasses
from unittest import mock

import pytest

import byteps_tpu as bps
from benchmark.harness import manifest, readers, tracecap
from benchmark.reduce import conv_cost, flash_cost, ssd_cost, xplane

CELLS = ("granite-4.0-h-micro.ingraph-1chip",
         "nemotron-labs-twotower-30b-a3b-base.ingraph-1chip")
NEW = ("mamba.conv_ms", "mamba.conv_kernel_share")
# The two calls as the granite cell's step compiles them for a described
# v5e, and two of the fusions the jnp form compiles to.
FWD = ('%mamba_conv_fwd.1 = (bf16[8192,4096]{1,0:T(8,128)(2,1)}, '
       'bf16[8192,128]{1,0:T(8,128)(2,1)S(1)}, bf16[8192,128]{1,0:T(8,128)'
       '(2,1)}) custom-call(%slice_bitcast_fusion, %slice_bitcast_fusion, '
       '%dynamic-update-slice.5), custom_call_target="tpu_custom_call", '
       'operand_layout_constraints={bf16[8192,4352]{1,0}, '
       'bf16[8192,4352]{1,0}, f32[8,4352]{1,0}}')
BWD = ('%mamba_conv_bwd.1 = (bf16[8192,4352]{1,0:T(8,128)(2,1)}, '
       'f32[8,4352]{1,0:T(8,128)}) custom-call(%copy-done.1, %copy-done.1, '
       '%copy-done.1, %copy-done.10, %bitcast.107, %reduce.11, %reduce.12, '
       '%bitcast.108, %reduce.11, %reduce.12), '
       'custom_call_target="tpu_custom_call", operand_layout_constraints='
       '{bf16[8192,4352]{1,0}, bf16[8192,4352]{1,0}, bf16[8192,4352]{1,0}, '
       'f32[8,4352]{1,0}, bf16[8192,4096]{1,0}, bf16[8192,128]{1,0}}')
SLICE = ('%slice_bitcast_fusion = bf16[8192,4352]{1,0:T(8,128)(2,1)S(1)} '
         'fusion(%fusion.2), kind=kLoop, calls=%fused_computation.29')
JNP_FWD = ('%fusion.28 = (bf16[1,8192,4352]{2,1,0}, bf16[1,8192,4352]{2,1,0})'
           ' fusion(%copy-done.36, %slice.14), kind=kLoop, '
           'calls=%fused_computation.63')
JNP_BWD = ('%fusion.11 = (f32[4352]{0}, f32[4352]{0}, bf16[1,8192,4352]'
           '{2,1,0}) fusion(%fusion.15, %copy-done.1), kind=kLoop, '
           'calls=%fused_computation.29')
MATMUL = ('%convolution_bitcast_fusion = bf16[1,8192,8512]{2,1,0} '
          'fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.1')


def _entry(scope, which, primitive):
    return {"scope": scope, "pass": which,
            "op_name": f"jit(step)/{scope}/{primitive}"}


def _kernel(scope, which, call):
    return {"scope": scope, "pass": which,
            "op_name": f"jit(step)/{scope}/jit({call})/pallas_call"}


def _map(family, kernel):
    conv = f"{family}.mamba.conv"
    out = {"convolution_bitcast_fusion": _entry(
        f"{family}.mamba.in_proj", "forward", "dot_general")}
    if kernel:
        out.update({
            "slice_bitcast_fusion": _entry(conv, "recompute", "reshape"),
            "mamba_conv_fwd.1": _kernel(conv, "forward", "_mamba_fwd_call"),
            "mamba_conv_fwd.2": _kernel(conv, "recompute",
                                        "_mamba_fwd_call"),
            "mamba_conv_bwd.1": _kernel(conv, "backward",
                                        "_mamba_bwd_call")})
    else:
        out.update({"fusion.28": _entry(conv, "forward", "mul"),
                    "fusion.29": _entry(conv, "recompute", "mul"),
                    "fusion.11": _entry(conv, "backward", "mul")})
    return out


def _window(kernel):
    """Two steps: `in_proj`'s product, the convolution forward, again
    under remat, and backward."""
    if kernel:
        step = ((MATMUL, 5_000_000), (FWD, 400_000),
                (SLICE, 200_000), (FWD.replace("fwd.1", "fwd.2"), 400_000),
                (BWD, 800_000))
    else:
        step = ((MATMUL, 5_000_000), (JNP_FWD, 700_000),
                (JNP_FWD.replace("fusion.28", "fusion.29"), 700_000),
                (JNP_BWD, 1_600_000))
    ops, t = [], 0
    for _ in range(2):
        for text, ns in step:
            ops.append((text, t, t + ns))
            t += ns + 1000
    return xplane.Trace(ops=[ops], async_ops=[[]], host=[])


def _read(kernel, tmp_path, family="granite", scopes=None):
    tmp_path.mkdir(exist_ok=True)
    ctx = tracecap.Context(
        trace=_window(kernel), n_steps=2, first_step=3, n_chips=1,
        samples_per_step=1, family=None, peaks={}, extras={},
        dir=str(tmp_path))
    scopes = _map(family, kernel) if scopes is None else scopes
    with mock.patch.object(bps, "get_step_scopes", lambda: scopes,
                           create=True):
        return {name: readers.reader(name)(ctx) for name in NEW}


@pytest.mark.parametrize("family", ["granite", "nemotronh"])
def test_the_jnp_form_reads_0_and_the_kernels_100(tmp_path, family):
    parent = _read(False, tmp_path / "a", family)
    change = _read(True, tmp_path / "b", family)
    assert parent["mamba.conv_kernel_share"] == 0.0
    assert change["mamba.conv_kernel_share"] == 100.0
    assert parent["mamba.conv_ms"] == pytest.approx(3.0)
    # the copy that hands the call its operand is the scope's too
    assert change["mamba.conv_ms"] == pytest.approx(1.8)


def test_a_pass_left_on_the_jnp_form_lowers_the_share(tmp_path):
    scopes = _map("granite", True)
    del scopes["mamba_conv_fwd.2"]
    scopes["fusion.29"] = _entry("granite.mamba.conv", "recompute", "mul")
    got = _read(True, tmp_path, scopes=scopes)
    assert got["mamba.conv_kernel_share"] == pytest.approx(100.0 * 2 / 3)


def test_a_cell_without_the_scope_or_the_map_reads_nothing(tmp_path):
    other = {"fusion.1": _entry("transformer.mlp", "forward", "dot_general"),
             "mamba_conv_fwd.1": _kernel("lfm2.conv.gate_conv", "forward",
                                         "_fwd_call")}
    assert _read(True, tmp_path / "a", scopes=other) == dict.fromkeys(NEW)
    assert _read(True, tmp_path / "b", scopes={}) == dict.fromkeys(NEW)
    ctx = tracecap.Context(
        trace=_window(True), n_steps=2, first_step=3, n_chips=1,
        samples_per_step=1, family=None, peaks={}, extras={},
        dir=str(tmp_path))
    with mock.patch.object(bps, "get_step_scopes", lambda: None):
        assert [readers.reader(n)(dataclasses.replace(ctx))
                for n in NEW] == [None, None]


def test_an_older_program_without_the_map_reads_nothing(monkeypatch):
    monkeypatch.delattr(bps, "get_step_scopes", raising=False)
    monkeypatch.setattr(bps, "_HOME", {
        k: v for k, v in bps._HOME.items() if k != "get_step_scopes"})
    assert readers.reader("mamba.conv_kernel_share")(None) is None


def test_no_other_reader_takes_the_calls_for_its_own():
    """The gated convolution's reader tells its calls by `short_conv_*`,
    the scan's by `ssd_*_c<chunk>`, the attention readers by a flash
    call's 3-D results."""
    for line in (FWD, BWD):
        assert conv_cost.call(line) is None
        assert ssd_cost.scan_call(line) is None
        assert flash_cost.classify(line) is None


@pytest.mark.parametrize("cell", CELLS)
def test_the_manifest_lists_them_in_the_two_mamba_cells(cell):
    by_name = {m["name"]: m for m in manifest.load_cell(cell).per_layer}
    ms, share = by_name["mamba.conv_ms"], by_name["mamba.conv_kernel_share"]
    assert ms["source"] == "program_span" and ms["unit"] == "ms/step"
    assert ms["better"] == "lower" and ms["layer"] == "model step"
    assert share["source"] == "program_counter" and share["unit"] == "%"
    assert share["better"] == "higher" and share["layer"] == "kernels"
    assert ms["moves"] == share["moves"] == "tokens_per_s"


def test_cells_without_a_mamba_mixer_do_not_list_them():
    for cell in ("gpt2-medium.ingraph-1chip", "vgg16.ingraph-1chip",
                 "lfm2-24b-a2b.ingraph-1chip", "trinity-mini.ingraph-1chip"):
        assert not set(NEW) & {
            m["name"] for m in manifest.load_cell(cell).per_layer}
