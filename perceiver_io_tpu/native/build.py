"""Build-on-demand for the native components.

Compiles ``<name>.cpp`` in this directory with g++ the first time it is
needed. The library is never in git (``*.so`` is ignored): it is named after
a hash of its source, ``lib<name>.<hash>.so``, so "is it built, and from THIS
source?" is one existence check — file times mean nothing after a checkout.
Raises on failure; callers treat any exception as "use the Python fallback".
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading

_NATIVE_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_LOCK = threading.Lock()


def load_library(name: str) -> ctypes.CDLL:
    src = os.path.join(_NATIVE_DIR, f"{name}.cpp")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    lib = os.path.join(_NATIVE_DIR, f"lib{name}.{digest}.so")
    with _BUILD_LOCK:
        if not os.path.exists(lib):
            # build beside the target and rename: another process (a test
            # worker) building the same library sees all of it or none
            fd, tmp = tempfile.mkstemp(
                dir=_NATIVE_DIR, prefix=f".lib{name}.", suffix=".so")
            os.close(fd)
            try:
                subprocess.run(
                    ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                     "-o", tmp, src],
                    check=True,
                    capture_output=True,
                )
                os.replace(tmp, lib)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
    return ctypes.CDLL(lib)
