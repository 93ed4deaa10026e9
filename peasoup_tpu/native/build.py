"""Build libpeasoup_host.so with the system C++ toolchain.

Invoked lazily on first use (or explicitly: python -m
peasoup_tpu.native.build). No pybind11 — plain C ABI via ctypes.

The library's file name carries a hash of the committed source, the
compile command and the machine architecture, so a stale or foreign
``.so`` copied along with a checkout is never loaded: a mismatch means
a different file name, which is built afresh.
"""

from __future__ import annotations

import hashlib
import os
import platform
import subprocess
import sys

_DIR = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(_DIR, "src", "host_kernels.cpp")
_FLAGS = ["-O3", "-shared", "-fPIC", "-std=c++17"]


def _cxx() -> str:
    return os.environ.get("CXX", "g++")


def lib_path() -> str:
    """Where the library built from the current source lives."""
    h = hashlib.sha256()
    with open(SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join([_cxx(), *_FLAGS, platform.machine()]).encode())
    return os.path.join(_DIR, f"libpeasoup_host-{h.hexdigest()[:16]}.so")


def build(force: bool = False) -> str | None:
    """Compile the shared library; returns its path or None on failure.

    Compiles to a temp path and os.replace()s into place so concurrent
    first-use builds (e.g. many sharded-search workers on a cold
    checkout) never dlopen a half-written file.
    """
    lib = lib_path()
    if not force and os.path.exists(lib):
        return lib
    import tempfile

    fd, tmp = tempfile.mkstemp(dir=_DIR, suffix=".so.tmp")
    os.close(fd)
    try:
        subprocess.run(
            [_cxx(), *_FLAGS, SRC, "-o", tmp],
            check=True, capture_output=True, text=True,
        )
        os.replace(tmp, lib)
    except (subprocess.CalledProcessError, FileNotFoundError) as exc:
        import warnings

        if os.path.exists(tmp):
            os.unlink(tmp)
        detail = getattr(exc, "stderr", "") or str(exc)
        warnings.warn(f"native build failed, using Python fallback: {detail}")
        return None
    return lib


if __name__ == "__main__":
    path = build(force="--force" in sys.argv)
    sys.stdout.write(f"{path or 'BUILD FAILED'}\n")
    sys.exit(0 if path else 1)
