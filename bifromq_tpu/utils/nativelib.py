"""Shared native-library loader: compile-if-stale + cached-failure.

One definition of the pattern three modules grew independently
(models/native_tok.py, models/native_retained.py, kv/native.py):
g++-compile the .so when missing/stale, dlopen it, and cache FAILURE as
well as success so a host without a compiler raises a cheap, catchable
RuntimeError on every call after the first instead of re-spawning g++
or leaking the original FileNotFoundError/OSError to serving paths.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Dict, Optional, Sequence

_cache: Dict[str, object] = {}
_lock = threading.Lock()


def compile_and_load(src: str, so: str,
                     extra_flags: Sequence[str] = ()) -> ctypes.CDLL:
    """Return the CDLL for ``src``, compiling to ``so`` when stale.

    Raises RuntimeError on any failure; the failure is cached per ``so``
    so later calls fail fast without re-running the toolchain.
    """
    with _lock:
        cached = _cache.get(so)
        if isinstance(cached, ctypes.CDLL):
            return cached
        if cached is False:
            raise RuntimeError(f"native lib unavailable: {so}")
        try:
            if not (os.path.exists(so)
                    and os.path.getmtime(so) >= os.path.getmtime(src)):
                # atomic publish: a concurrent process must never dlopen
                # a half-written .so
                tmp = f"{so}.{os.getpid()}.tmp"
                subprocess.run(
                    ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
                     *extra_flags, src, "-o", tmp],
                    check=True, capture_output=True)
                os.replace(tmp, so)
            lib = ctypes.CDLL(so)
        except Exception as e:  # noqa: BLE001 — cache + normalize
            _cache[so] = False
            raise RuntimeError(f"native lib failed to build/load: {so}: "
                               f"{type(e).__name__}: {e}") from e
        _cache[so] = lib
        return lib


def status() -> Dict[str, str]:
    """``{library file name: "loaded" | "failed"}`` for every native
    library this process has tried — so a start-up report can NAME a
    library whose build failed instead of its Python twin serving
    unnoticed."""
    with _lock:
        return {os.path.basename(so): ("loaded" if lib is not False
                                       else "failed")
                for so, lib in _cache.items()}
