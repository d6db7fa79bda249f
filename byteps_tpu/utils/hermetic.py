"""CPU subprocess environments.

A chip belongs to one process at a time, so a child that must stay off
the accelerator (a virtual-device dryrun, a host-only PS server) gets
an environment pinned to the CPU backend: `JAX_PLATFORMS=cpu`, with the
TPU discovery variables stripped so nothing in the child goes looking
for a chip.

Used by tests/testutil.cpu_env, __graft_entry__.virtual_cpu_env,
tools/wire_bench.py and benchmark/tests.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

# Direct TPU discovery vars.
_TPU_VARS = ("TPU_NAME", "TPU_WORKER_ID", "CLOUD_TPU_TASK_ID")


def cpu_subprocess_env(extra: Optional[Dict[str, str]] = None,
                       base: Optional[Dict[str, str]] = None
                       ) -> Dict[str, str]:
    """A copy of `base` (default os.environ) pinned to the CPU backend."""
    env = dict(os.environ if base is None else base)
    for k in _TPU_VARS:
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    if extra:
        env.update(extra)
    return env


def force_host_device_count(env: Dict[str, str], n: int) -> Dict[str, str]:
    """Pin XLA_FLAGS in `env` to exactly `n` virtual host devices, in place.

    Replaces any existing --xla_force_host_platform_device_count flag
    (appending blindly would leave two copies and XLA honors the first).
    """
    import re

    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()
    return env
