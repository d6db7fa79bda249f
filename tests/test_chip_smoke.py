"""chip_smoke.py, rehearsed without the chip.

The script itself must refuse a machine with no accelerator; its phases
are plain functions, run here at tiny size on the CPU mesh — including
the PS phase against a real server child and the dp phase on 4 virtual
devices.  What only the chip can show (the Mosaic kernel in the program,
memory on every chip) is checked by the script when it runs there, and
by tests/test_tpu_aot_compile.py at compile time.
"""

import os
import shutil
import subprocess
import sys
import types

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from byteps_tpu.core import build, native  # noqa: E402
from byteps_tpu.models import transformer as tfm  # noqa: E402
from byteps_tpu.utils import compile_cache  # noqa: E402
from testutil import cpu_env  # noqa: E402

SMOKE = os.path.join(REPO, "chip_smoke.py")
TINY = chip_smoke.Sizes(per_chip_batch=2, seq=128, ref_slice=2, steps=3)


def _tiny_cfg():
    return tfm.get_config("tiny", causal=True, attn_impl="flash",
                          ce_chunk_rows=64)


@pytest.mark.parametrize("alone", [False, True],
                         ids=["cpu_platform", "script_alone"])
def test_script_fails_without_a_chip(tmp_path, alone):
    """Under the CPU platform — and in a directory that holds the script
    and nothing else of the repo — it exits non-zero and never prints a
    result line."""
    env = cpu_env()
    script, cwd = SMOKE, REPO
    if alone:
        script = shutil.copy(SMOKE, tmp_path)
        cwd = str(tmp_path)
        env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, script], env=env, cwd=cwd,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    if not alone:
        assert "no accelerator" in r.stderr


def _dev(platform="tpu", kind="TPU v5 lite"):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("devices,n_chips,error", [
    ([_dev()], 1, None),
    ([_dev()] * 4, 4, None),
    ([_dev("cpu", "cpu")], 1, "no accelerator"),
    ([_dev(kind="TPU v9 mystery")], 1, "PEAK_BF16"),   # unknown kind: error
    ([_dev()] * 4, 1, "expected 1 chip"),
    ([], 1, "empty"),
], ids=["one_v5e", "four_v5e", "cpu", "unknown_kind", "count", "empty"])
def test_phase_device(devices, n_chips, error):
    if error is None:
        assert chip_smoke.phase_device(devices, n_chips) == {
            "platform": "tpu", "kind": "TPU v5 lite", "count": n_chips}
    else:
        with pytest.raises(chip_smoke.SmokeError, match=error):
            chip_smoke.phase_device(devices, n_chips)


def test_phase_ingraph_tiny():
    out = chip_smoke.phase_ingraph(_tiny_cfg(), TINY)
    assert out["devices"] == jax.device_count()
    assert out["per_chip_batch"] == TINY.per_chip_batch
    assert len(out["losses"]) == 1 + TINY.steps
    assert out["losses"][-1] < out["losses"][0]
    assert abs(out["slice_loss"] - out["slice_loss_dense_full_logits"]) \
        < 1e-2
    # The interpreter is not the kernel, and the phase says so.
    assert out["kernel_in_hlo"] is False


def test_phase_ingraph_fails_on_a_wrong_loss(monkeypatch):
    """A wrong answer fails the phase: a reference that disagrees (here
    the dense path is made to return half the value) raises."""
    real = tfm.loss_fn
    monkeypatch.setattr(
        tfm, "loss_fn", lambda p, b, cfg, **kw:
        real(p, b, cfg, **kw) * (0.5 if cfg.attn_impl == "dense" else 1.0))
    with pytest.raises(chip_smoke.SmokeError, match="disagrees"):
        chip_smoke.phase_ingraph(_tiny_cfg(), TINY)


def test_phase_ps_tiny_against_a_real_server():
    before = dict(os.environ)
    out = chip_smoke.phase_ps(_tiny_cfg(), TINY)
    assert out["native_core"] is True
    assert out["rounds"] == 2 and len(out["round_s"]) == 2
    assert out["server_touched_accelerator"] is False
    assert out["tree_bytes"] == 4 * tfm.num_params(
        tfm.init_params(jax.random.key(0), _tiny_cfg()))
    assert dict(os.environ) == before       # PS-mode env restored


def test_server_entry_imports_no_jax():
    """The server tier is a host process: `python -m byteps_tpu.server`
    imports the package without importing jax, so a server child cannot
    reach for a chip its parent holds (and boots in milliseconds)."""
    code = ("import sys, byteps_tpu.server, byteps_tpu.core.build; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    r = subprocess.run([sys.executable, "-c", code], env=cpu_env(),
                       cwd=REPO, capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr[-2000:]


def test_phase_dp_on_four_virtual_devices():
    out = chip_smoke.phase_dp(_tiny_cfg(), TINY, 4)
    assert out["global_batch"] == 4 * TINY.per_chip_batch
    assert out["param_sharding_spans"] == 4
    assert out["dp"]["all_reduce_in_hlo"] is True
    assert out["one_device"]["all_reduce_in_hlo"] is False
    assert out["worst_rel_loss_diff"] < chip_smoke.BF16_LOSS_RTOL


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: that directory, and nothing is set
    in code.  Unset: the same in-checkout path on every call."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    assert compile_cache.enable() == str(tmp_path)
    assert calls == []

    monkeypatch.delenv(compile_cache.ENV)
    first, second = compile_cache.enable(), compile_cache.enable()
    assert first == second == compile_cache.cache_dir() \
        == os.path.join(REPO, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)] * 2
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()    # never committed


def test_compile_error_raises_with_the_compilers_message(tmp_path,
                                                         monkeypatch):
    """A failed compile is not a missing toolchain: build raises with
    g++'s own stderr, get_core lets it through, and only a host with no
    g++ gets the Python core."""
    for name in build._SOURCES:
        (tmp_path / name).write_text("int broken( { return no_such_name; }\n")
    monkeypatch.setattr(build, "_CORE_DIR", str(tmp_path))
    with pytest.raises(build.BuildError) as e:
        build.build(force=True)
    assert "error" in str(e.value) and "core.cc" in str(e.value)
    assert not os.path.exists(build.lib_path())
    assert sorted(os.listdir(tmp_path)) == sorted(build._SOURCES)  # no temp

    monkeypatch.setattr(native, "_core", None)
    with pytest.raises(build.BuildError):
        native.get_core()

    def no_toolchain(*a, **kw):
        raise FileNotFoundError("g++")

    monkeypatch.setattr(subprocess, "run", no_toolchain)
    assert isinstance(native.get_core(), native._PyCore)
