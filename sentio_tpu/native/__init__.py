"""Native (C++) host-side components, consumed via ctypes.

The reference leans on external native compute for its host-side hot loops
— Lucene/JVM BM25 through Pyserini (/root/reference/src/core/retrievers/
sparse.py:206-276) and Qdrant's Rust HNSW server. Here the native layer is
in-tree C++ built with the system toolchain on first use; every native
component has a pure-Python/numpy counterpart so the framework never
*requires* a compiler at runtime (``BM25_BACKEND=native`` makes a failed
build an error; the index reports which backend it is, ``/info`` shows it).

The shared object is git-ignored and built with ``-march=native``, so it is
only good for the host and flags it was built with. Its file name therefore
carries a key of (source, flags, this host's CPU): a binary that arrived
with a copied working tree — built on another machine, or before the source
changed — has another name and is never loaded; the library is rebuilt from
``bm25.cpp`` here instead.

``load_bm25()`` returns the ctypes library handle for the BM25 scoring core
(building it if needed) or None when unavailable.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading
from pathlib import Path
from typing import Optional

logger = logging.getLogger(__name__)

_SRC_DIR = Path(__file__).parent
_LOCK = threading.Lock()
_CACHE: dict[str, Optional[ctypes.CDLL]] = {}
_FLAGS = ("-O3", "-march=native", "-std=c++17", "-shared", "-fPIC")


def _host_cpu() -> str:
    """What ``-march=native`` resolves against: the CPU model and feature
    flags of the machine this process runs on."""
    lines = []
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                key = line.split(":", 1)[0].strip()
                if key in ("model name", "flags", "Features"):
                    lines.append(line.strip())
                if not line.strip() and lines:
                    break  # first processor block is enough
    except OSError:
        pass
    return platform.machine() + "|" + (" ".join(lines) or platform.processor())


def _build_key(src: Path) -> str:
    h = hashlib.sha256()
    for part in (src.read_bytes(), " ".join(_FLAGS).encode(),
                 _host_cpu().encode()):
        h.update(part)
        h.update(b"\0")
    return h.hexdigest()[:16]


def _build(name: str) -> Optional[Path]:
    src = _SRC_DIR / f"{name}.cpp"
    out = _SRC_DIR / f"lib{name}.{_build_key(src)}.so"
    if out.exists():
        return out
    # compile to a per-process temp name and os.replace into place: the
    # in-process _LOCK cannot serialize concurrent *processes* (multiple
    # server workers / pytest-xdist on a fresh checkout), and dlopen on a
    # half-written .so fails hard
    tmp = _SRC_DIR / f".lib{name}.{os.getpid()}.so"
    cmd = ["g++", *_FLAGS, str(src), "-o", str(tmp)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            # -march=native can fail on exotic hosts; retry portable
            proc = subprocess.run([c for c in cmd if c != "-march=native"],
                                  capture_output=True, text=True, timeout=120)
            if proc.returncode != 0:
                logger.warning("native %s build failed:\n%s", name, proc.stderr[-2000:])
                return None
        os.replace(tmp, out)
    except (OSError, subprocess.TimeoutExpired) as exc:
        logger.warning("native %s build skipped: %s", name, exc)
        return None
    finally:
        tmp.unlink(missing_ok=True)
    return out


def _load(name: str) -> Optional[ctypes.CDLL]:
    with _LOCK:
        if name in _CACHE:
            return _CACHE[name]
        lib: Optional[ctypes.CDLL] = None
        path = _build(name)
        if path is not None:
            try:
                lib = ctypes.CDLL(str(path))
            except OSError as exc:
                logger.warning("native %s load failed: %s", name, exc)
        _CACHE[name] = lib
        return lib


def load_bm25() -> Optional[ctypes.CDLL]:
    """The BM25 scoring core (native/bm25.cpp), with argtypes configured."""
    lib = _load("bm25")
    if lib is None or getattr(lib, "_sbm25_configured", False):
        return lib
    c = ctypes
    i32p, i64p, f32p = (c.POINTER(c.c_int32), c.POINTER(c.c_int64), c.POINTER(c.c_float))
    lib.sbm25_create.restype = c.c_void_p
    lib.sbm25_create.argtypes = [c.c_int32, c.c_int32, i64p, i32p, f32p, f32p,
                                 f32p, c.c_float, c.c_float]
    lib.sbm25_destroy.argtypes = [c.c_void_p]
    lib.sbm25_scores.argtypes = [c.c_void_p, i32p, c.c_int32, f32p]
    lib.sbm25_search.restype = c.c_int32
    lib.sbm25_search.argtypes = [c.c_void_p, i32p, c.c_int32, c.c_int32, i32p, f32p]
    lib.sbm25_version.restype = c.c_int32
    lib._sbm25_configured = True
    return lib
