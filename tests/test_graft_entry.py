"""The driver's gates: entry() compile check + dryrun_multichip.

The round-1 failure mode was `dryrun_multichip` assuming the calling
process already had n devices (the driver's process sees one real chip).
These tests pin the self-provisioning behavior: a parent with a single CPU
device must still complete the 8-device dryrun by re-exec'ing onto a
virtual mesh (reference test pattern: tests/meta_test.py:26-84 fakes a
cluster on one machine the same way).
"""

import importlib.util
import os
import pytest
import re
import subprocess
import sys

# full multichip dryruns take minutes each (CI fast lane: -m 'not slow')
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENTRY = os.path.join(REPO, "__graft_entry__.py")

_spec = importlib.util.spec_from_file_location("_graft_entry_mod", ENTRY)
_graft = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_graft)


def _clean_env(n_parent_devices=None):
    env = _graft.virtual_cpu_env(1, REPO)
    if n_parent_devices is None:
        # Parent sees exactly one CPU device (no force flag at all).
        env["XLA_FLAGS"] = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "",
            env["XLA_FLAGS"]).strip()
    else:
        env = _graft.virtual_cpu_env(n_parent_devices, REPO)
    return env


def test_dryrun_multichip_self_provisions_from_single_device():
    # Parent: 1 CPU device (no force_host flag). dryrun_multichip(8) must
    # re-exec a child with 8 virtual devices and succeed.
    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu'); "
        "assert len(jax.devices()) == 1, jax.devices(); "
        "import importlib.util; "
        f"spec = importlib.util.spec_from_file_location('ge', {ENTRY!r}); "
        "m = importlib.util.module_from_spec(spec); "
        "spec.loader.exec_module(m); "
        "m.dryrun_multichip(8); print('SELF_PROVISION_OK')"
    )
    proc = subprocess.run([sys.executable, "-c", code],
                          env=_clean_env(), capture_output=True, text=True,
                          timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SELF_PROVISION_OK" in proc.stdout


def test_dryrun_never_touches_parent_backend():
    # A chip belongs to one process: a parent that initialized a backend
    # would hold it.  The gate must never import jax in the parent at
    # all.  Poison the parent's jax import (sys.modules[name]=None makes
    # `import jax` raise) and assert the dryrun still completes via its
    # CPU child, which imports the real jax from a fresh interpreter.
    code = (
        "import sys; sys.modules['jax'] = None; "
        "import importlib.util; "
        f"spec = importlib.util.spec_from_file_location('ge', {ENTRY!r}); "
        "m = importlib.util.module_from_spec(spec); "
        "spec.loader.exec_module(m); "
        "m.dryrun_multichip(2); print('PARENT_JAX_FREE_OK')"
    )
    proc = subprocess.run([sys.executable, "-c", code],
                          env=_clean_env(), capture_output=True, text=True,
                          timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "PARENT_JAX_FREE_OK" in proc.stdout


def test_entry_compiles_single_device():
    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu'); "
        "import importlib.util; "
        f"spec = importlib.util.spec_from_file_location('ge', {ENTRY!r}); "
        "m = importlib.util.module_from_spec(spec); "
        "spec.loader.exec_module(m); "
        "fn, args = m.entry(); out = jax.jit(fn)(*args); "
        "print('ENTRY_OK', out.shape)"
    )
    proc = subprocess.run([sys.executable, "-c", code],
                          env=_clean_env(), capture_output=True, text=True,
                          timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "ENTRY_OK" in proc.stdout
