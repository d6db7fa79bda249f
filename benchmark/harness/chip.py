"""The chip check and the table of peaks.

`require` is what keeps a measurement off the CPU: no accelerator, fewer
chips than the cell asks for, or a `device_kind` the table does not know,
and the run ends non-zero before any work, with no result line.  (The
idea is bench.py's `_require_chip` and chip_smoke.py's `phase_device`.)
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


class NoChip(SystemExit):
    def __init__(self, why: str):
        super().__init__(f"benchmark: {why}")


def peaks_for(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise NoChip(f"device_kind {device_kind!r} is not in "
                     f"{_PEAKS}: a device without published peaks is an "
                     f"error, not a default")
    return table[device_kind]


def require(devices, n_chips: int) -> dict:
    """`devices` is what `jax.devices()` returned.  Returns the peaks of
    the chip, or ends the process."""
    if not devices or devices[0].platform == "cpu":
        raise NoChip("JAX found no accelerator (platform "
                     f"{devices[0].platform if devices else None!r}); the "
                     "benchmark does not fall back to the CPU")
    if len(devices) < n_chips:
        raise NoChip(f"the cell needs {n_chips} chip(s), JAX found "
                     f"{len(devices)}")
    return peaks_for(devices[0].device_kind)


class MemoryWatch:
    """The fullest chip's footprint, sampled where the caller says: a
    phase's end, when what the phase built is still there.

    A footprint is `bytes_in_use` plus `bytes_reserved`.  This runtime
    keeps a loaded program's temporaries in a reservation at the bottom
    of memory that `bytes_in_use` does not count (PR 23 found the peak of
    a b64 step equal to its arguments alone; the reservation is where the
    rest is).  The two counters are read at the same moment, so a sample
    is a footprint the chip really had; the largest sample is a lower
    bound of the true peak, which may have been higher between samples.
    (The sum of `peak_bytes_in_use` and `peak_bytes_reserved` would be an
    upper bound that can exceed the chip: the two peaks fall in different
    phases.)"""

    def __init__(self, devices):
        self.devices = list(devices)
        self.peak = None

    def sample(self) -> None:
        for d in self.devices:
            stats = d.memory_stats()
            if stats is not None:
                now = int(stats["bytes_in_use"]) + int(
                    stats.get("bytes_reserved", 0))
                self.peak = now if self.peak is None else max(self.peak, now)


def describe(devices, memory_peak_bytes) -> dict:
    """The `device` entry of the result line, as JAX reports it."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak_bytes}
