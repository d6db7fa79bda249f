"""The allocator policy of a PS worker on an accelerator
(docs/performance.md, "Host memory a PS worker keeps").

Every round a PS worker takes twice the tree's bytes of host memory (the
runtime's destination of each copy off the device, and the handles'
result buffers that `recv_into` fills) and lets them go before it
returns.  glibc serves sizes like these by `mmap` and gives them back by
`munmap`, so every round writes into pages the kernel has to hand out
anew, one page fault at a time.  With `mmap` off and trimming off, freed
memory stays on the heap and the next round finds it there.

Process-wide, and what stays is the heap's high-water mark, not the
round's bytes.  Only `api.init` calls this, for a PS worker whose JAX
backend is not the CPU: there the round's buffers are all that is large
on the host, and each lives for a round.  On the CPU backend XLA's own
arrays come from the same heap, aligned, and live as long as the caller
keeps them; glibc before 2.38 asks for an aligned block with padding and
cannot put it into the hole an equal one left, so with `mmap` off such a
process grows by about a tree a round without end (measured: 61 MB a
round for a 64 MB tree).  A process with no PS session, or on the CPU
backend, keeps the allocator's defaults.  A parameter the environment
already sets (`MALLOC_MMAP_MAX_` / `MALLOC_TRIM_THRESHOLD_`, or their
`GLIBC_TUNABLES` names) is the user's and is left alone.
"""

from __future__ import annotations

import ctypes
import os

# <malloc.h>
_M_TRIM_THRESHOLD = -1
_M_MMAP_MAX = -4

# (mallopt parameter, value, the environment's names for it).  The trim
# threshold is -1 and not INT_MAX: `mallopt` takes an int, glibc widens
# -1 to SIZE_MAX, and a round of over 2 GiB would pass INT_MAX and be
# trimmed at every free.
_POLICY = (
    (_M_MMAP_MAX, 0, ("MALLOC_MMAP_MAX_", "glibc.malloc.mmap_max")),
    (_M_TRIM_THRESHOLD, -1,
     ("MALLOC_TRIM_THRESHOLD_", "glibc.malloc.trim_threshold")),
)


def _set_by_user(names) -> bool:
    variable, tunable = names
    return (variable in os.environ
            or tunable + "=" in os.environ.get("GLIBC_TUNABLES", ""))


def keep_freed_memory() -> bool:
    """Tell glibc's malloc to keep what is freed, but for a parameter
    the user's environment sets; True if glibc took it.  A no-op
    (False) where the C library is not glibc or has no `mallopt`.
    Idempotent."""
    try:
        libc = ctypes.CDLL(None)
        libc.gnu_get_libc_version       # glibc's own: another libc's
        mallopt = libc.mallopt          # `mallopt` numbers differently
    except (OSError, AttributeError):
        return False
    mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
    mallopt.restype = ctypes.c_int
    told = True
    for param, value, names in _POLICY:
        if not _set_by_user(names):
            told = bool(mallopt(param, value)) and told
    return told
