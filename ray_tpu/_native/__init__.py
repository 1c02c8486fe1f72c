"""Native (C++) components, compiled on first use.

Parity: the reference builds its C++ core with Bazel into a Cython
extension (ray: python/setup.py → bazel → _raylet.pyx); here each native
component is a small C ABI library built with g++ and bound via ctypes
— no build step at install time, no toolchain beyond a C++ compiler.
"""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess
import threading
from typing import List, Optional

_NATIVE_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_NATIVE_DIR, "build")
_build_lock = threading.Lock()


def build_library(source: str, libname: str,
                  extra_flags: Optional[List[str]] = None) -> str:
    """Compile ``source`` (relative to this dir) into
    build/<libname>-<hash>.so and return that path.  The name carries a
    hash of the source and the compile command, so a library is reused
    exactly when it was built from this source: file times say nothing
    in a tree that was copied or checked out."""
    src = os.path.join(_NATIVE_DIR, source)
    flags = ["-O2", "-std=c++17", "-shared", "-fPIC", "-lpthread",
             "-lrt"] + (extra_flags or [])
    cmd = ["g++", src] + flags
    with open(src, "rb") as f:
        digest = hashlib.sha256(
            " ".join(flags).encode() + b"\0" + f.read()).hexdigest()[:16]
    out = os.path.join(_BUILD_DIR, f"{libname}-{digest}.so")
    with _build_lock:
        if os.path.exists(out):
            return out
        os.makedirs(_BUILD_DIR, exist_ok=True)
        # Build beside the target and rename into place: a driver and
        # its workers can all arrive here at once, and none may load a
        # half-written library.
        tmp = f"{out}.{os.getpid()}.tmp"
        proc = subprocess.run(cmd + ["-o", tmp], capture_output=True,
                              text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"native build failed: {' '.join(cmd)}\n{proc.stderr}"
            )
        os.replace(tmp, out)
        for stale in glob.glob(os.path.join(_BUILD_DIR, f"{libname}-*.so")):
            if stale != out:
                try:
                    os.unlink(stale)
                except OSError:
                    pass
    return out
