"""Build the native host core (`libbyteps_core.so`).

The reference builds its C++ core through setup.py extensions
(reference: setup.py:249-337).  Here the core is framework-independent host
logic, so a plain g++ shared-object build is enough; it is (re)built lazily on
first import when the sources are newer than the binary.

Sanitizer variants (coverage the reference's CI never had, SURVEY §5):
`BYTEPS_TPU_TSAN=1` builds ThreadSanitizer, `BYTEPS_TPU_ASAN=1`
AddressSanitizer + UBSan.  Sanitizers apply ONLY to the standalone PS
server binary (server.serve() execs it): sanitizer runtimes cannot be
dlopen'd into a running interpreter — TSAN's dlopen fails loudly, ASan
init kills the process outright — so the in-process client/core library
is always the plain build.
"""

from __future__ import annotations

import os
import subprocess
import sys

_CORE_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ["core.cc", "server.cc"]
_LIB_NAME = "libbyteps_core.so"

# env var -> (-fsanitize value, artifact suffix)
_SANITIZERS = (
    ("BYTEPS_TPU_TSAN", "thread", "_tsan"),
    ("BYTEPS_TPU_ASAN", "address,undefined", "_asan"),
)


def _sanitizer():
    """(fsanitize_value, suffix) for the first enabled sanitizer, else
    (None, "")."""
    for env, value, suffix in _SANITIZERS:
        if os.environ.get(env, "0") == "1":
            return value, suffix
    return None, ""


def sanitized() -> bool:
    """True when any sanitizer variant is selected (server must exec the
    standalone binary)."""
    return _sanitizer()[0] is not None


def lib_path() -> str:
    # Always the PLAIN library: this .so is ctypes-loaded into running
    # interpreters, where a sanitizer runtime cannot initialize.
    return os.path.join(_CORE_DIR, _LIB_NAME)


def _needs_build() -> bool:
    lib = lib_path()
    if not os.path.exists(lib):
        return True
    lib_mtime = os.path.getmtime(lib)
    for src in _SOURCES:
        p = os.path.join(_CORE_DIR, src)
        if os.path.exists(p) and os.path.getmtime(p) > lib_mtime:
            return True
    return False


def _san_flags() -> list:
    value, _ = _sanitizer()
    if value is None:
        return []
    flags = ["-g", f"-fsanitize={value}"]
    if "address" in value:
        flags.append("-fno-omit-frame-pointer")
    if "undefined" in value:
        # UBSan checks are recoverable by default: the binary would print
        # a report and keep running, and with the test fixtures routing
        # server stderr to DEVNULL the finding would vanish.  Make UB
        # abort so the CI leg actually fails.
        flags.append("-fno-sanitize-recover=undefined")
    return flags


class BuildError(RuntimeError):
    """g++ ran and refused the sources; the message carries its stderr."""


def _compile(cmd: list, out: str, verbose: bool = False) -> str:
    """Run `cmd -o <temporary name>` and `os.replace` the result onto
    `out`: the server child and the worker may both build at first
    import, and neither may ever dlopen a half-written file.

    A missing toolchain raises FileNotFoundError (the one case
    core/native.py answers with the Python core); a compile error raises
    BuildError with the compiler's own message."""
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = [*cmd, "-o", tmp]
    if verbose:
        print(" ".join(cmd), file=sys.stderr)
    try:
        r = subprocess.run(cmd, capture_output=True, text=True)
        if r.returncode != 0:
            raise BuildError(
                f"{' '.join(cmd)} failed (rc={r.returncode}):\n{r.stderr}")
        if verbose and r.stderr:
            sys.stderr.write(r.stderr)
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return out


def build(force: bool = False, verbose: bool = False) -> str:
    """Compile the native core if needed; returns the .so path."""
    if not force and not _needs_build():
        return lib_path()
    srcs = [os.path.join(_CORE_DIR, s) for s in _SOURCES]
    # -O3: the wire-codec inner loops (onebit expand, dense level
    # gather) only vectorize at -O3; measured ~2x on the codec micros
    # with no change anywhere else.  -ffp-contract=off: the codec's
    # byte-/EF-state-parity contract with the numpy reference requires
    # numpy's two-step rounding for mu*m + x — on FMA-baseline targets
    # (aarch64) -O3 would otherwise legally contract it to fmadd and
    # drift the two paths.
    cmd = ["g++", "-O3", "-ffp-contract=off", "-std=c++17", "-shared",
           "-fPIC", "-pthread", "-fvisibility=hidden", *srcs]
    return _compile(cmd, lib_path(), verbose)


if __name__ == "__main__":
    build(force="--force" in sys.argv, verbose=True)
    print(lib_path())


_EXE_NAME = "bps_ps_server"


def exe_path() -> str:
    _, suffix = _sanitizer()
    return os.path.join(_CORE_DIR, f"{_EXE_NAME}{suffix}")


def build_server_exe(force: bool = False) -> str:
    """Standalone PS-server binary (required under sanitizers, usable
    generally)."""
    src = os.path.join(_CORE_DIR, "server.cc")
    out = exe_path()
    if not force and os.path.exists(out) \
            and os.path.getmtime(out) >= os.path.getmtime(src):
        return out
    cmd = ["g++", *_san_flags(), "-O3", "-ffp-contract=off", "-std=c++17",
           "-pthread", "-DBPS_SERVER_MAIN", src]
    return _compile(cmd, out)
