"""Lazy compile-and-load for the framework's C++ components.

The native pieces (RecordIO indexer, embedding KV store) ship as
single-file C++ sources compiled on first use with the host toolchain
and loaded over ctypes — no build step, no wheels, and a pure-Python
fallback wherever g++ is missing. This helper owns the once-only
compile/load/cache logic so every native component shares one
implementation of the source-hash naming and failure path.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Callable, Dict, Optional

from elasticdl_tpu.common.log_util import get_logger

logger = get_logger(__name__)

_lock = threading.Lock()
_cache: Dict[str, Optional[ctypes.CDLL]] = {}  # so path -> lib (or None)


def compile_and_load(
    src: str,
    so: str,
    configure: Callable[[ctypes.CDLL], None],
    what: str = "native library",
) -> Optional[ctypes.CDLL]:
    """Compile `src` into `so` named by a hash of the source
    (`libx.so` -> `libx-<sha256[:16]>.so`), load it, apply
    `configure(lib)` (restype/argtypes), cache by path. Only a library
    built from the `.cc` on disk is ever loaded: a copied tree resets
    mtimes, and a stale build product left by another checkout has
    another name. Returns None — once, with a warning — when the
    toolchain or load fails; callers fall back to their Python path."""
    with _lock:
        if so in _cache:
            return _cache[so]
        try:
            with open(src, "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()[:16]
            stem, ext = os.path.splitext(so)
            built = f"{stem}-{digest}{ext}"
            if not os.path.exists(built):
                os.makedirs(os.path.dirname(built), exist_ok=True)
                # processes of one job race to the first build: each
                # links its own file and renames it into place whole
                tmp = f"{built}.{os.getpid()}.tmp"
                subprocess.run(
                    ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", src, "-o", tmp],
                    check=True,
                    capture_output=True,
                )
                os.replace(tmp, built)
            lib = ctypes.CDLL(built)
            configure(lib)
            _cache[so] = lib
        except Exception as e:  # pragma: no cover - toolchain missing
            logger.warning("%s unavailable (%s); using Python path", what, e)
            _cache[so] = None
        return _cache[so]
