"""Compile-on-first-use loader for the native helpers."""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_CACHE: dict[str, ctypes.CDLL | None] = {}


def load_library(name: str) -> ctypes.CDLL | None:
    """Load `<name>.cpp` as a shared library, compiling if stale.

    Returns None when no working C++ toolchain is available (callers fall back
    to pure Python).
    """
    with _LOCK:
        if name in _CACHE:
            return _CACHE[name]
        src = os.path.join(_DIR, f"{name}.cpp")
        so = os.path.join(_DIR, f"_{name}.so")
        lib = None
        try:
            if (not os.path.exists(so)
                    or os.path.getmtime(so) < os.path.getmtime(src)):
                # build to a process-unique temp path and rename atomically so
                # concurrent processes never dlopen a half-written ELF
                tmp = f"{so}.{os.getpid()}.tmp"
                subprocess.run(
                    ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", src, "-o", tmp],
                    check=True, capture_output=True)
                os.replace(tmp, so)
            lib = ctypes.CDLL(so)
        except (OSError, subprocess.CalledProcessError) as e:
            detail = (getattr(e, "stderr", b"") or b"").decode(
                errors="replace").strip()[-300:]
            logging.getLogger("skellysim_tpu").warning(
                "native helper %s not built (%s%s); taking the Python path",
                name, e, f": {detail}" if detail else "")
            lib = None
        _CACHE[name] = lib
        return lib
