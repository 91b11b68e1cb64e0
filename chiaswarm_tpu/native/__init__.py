"""Native (C++) host runtime — ctypes bindings with pure-Python fallback.

The artifact codec (csrc/artifact_codec.cc) natively implements the host
hot path the reference runs through Python/PIL at the GPU->host boundary
(swarm/output_processor.py:46-58,121-136): PNG encoding (measured ~2x PIL
at 1024px — the piece the envelope actually routes here), box-filter
thumbnailing, plus SHA-256 and base64 kept for completeness/testing —
the stdlib versions of those are already native and faster through
ctypes-free call paths, so the envelope uses hashlib/base64 for them.

``load()`` compiles the shared object on first use with the system g++
(no pip, no network — the image bakes the toolchain) into
``<checkout>/.native_cache/`` (``CHIASWARM_NATIVE_CACHE`` overrides).
An install that ships no ``csrc/`` gets None and the PIL/hashlib path;
a source that is present but does not BUILD raises — the worker calls
``load()`` at start-up so a broken toolchain stops it there instead of
quietly halving encode throughput on every job.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from pathlib import Path

log = logging.getLogger("chiaswarm.native")

_SOURCE = Path(__file__).resolve().parents[2] / "csrc" / "artifact_codec.cc"
_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_TRIED = False


def _cache_dir() -> Path:
    root = os.environ.get("CHIASWARM_NATIVE_CACHE")
    if root:
        return Path(root)
    return _SOURCE.parents[1] / ".native_cache"


def _build(source: Path, out: Path) -> None:
    out.parent.mkdir(parents=True, exist_ok=True)
    # pid-suffixed tmp: concurrent first-use builds across processes must
    # not interleave writes; os.replace keeps the install atomic
    tmp = out.with_suffix(f".so.tmp.{os.getpid()}")
    cmd = ["g++", "-O2", "-shared", "-fPIC", str(source), "-lz",
           "-o", str(tmp)]
    try:
        # one-time cold-path compile, deliberately under _LOCK: every
        # contender needs the library and must wait for the build anyway;
        # serializing here IS the double-checked init (load() re-checks
        # _LIB/_TRIED under the same lock). Never runs on the event loop.
        # swarmlens: allow-blocking-under-lock
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
    finally:
        tmp.unlink(missing_ok=True)


def load() -> ctypes.CDLL | None:
    """The artifact-codec library, building it on first call. None only
    when the install ships no source (callers use the PIL path); a build
    or load failure raises, on this and every later call."""
    global _LIB, _TRIED
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        if not _SOURCE.exists():
            _TRIED = True
            log.info("native codec source not found at %s", _SOURCE)
            return None
        so = _cache_dir() / "libartifact.so"
        try:
            if (not so.exists() or
                    so.stat().st_mtime < _SOURCE.stat().st_mtime):
                _build(_SOURCE, so)
            lib = ctypes.CDLL(str(so))
        except subprocess.CalledProcessError as exc:
            raise RuntimeError(
                f"native codec build failed: {exc.stderr.decode()[-2000:]}"
            ) from exc

        lib.sha256_hex.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                   ctypes.c_char_p]
        lib.b64_encode.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                   ctypes.c_char_p]
        lib.b64_encode.restype = ctypes.c_uint64
        lib.thumbnail_rgb.argtypes = [ctypes.c_char_p, ctypes.c_uint32,
                                      ctypes.c_uint32, ctypes.c_uint32,
                                      ctypes.c_uint32, ctypes.c_char_p]
        lib.png_encode_rgb.argtypes = [ctypes.c_char_p, ctypes.c_uint32,
                                       ctypes.c_uint32, ctypes.c_char_p,
                                       ctypes.c_uint64]
        lib.png_encode_rgb.restype = ctypes.c_uint64
        _LIB = lib
        log.info("native artifact codec loaded from %s", so)
        return _LIB


def sha256_hex(data: bytes) -> str:
    lib = load()
    if lib is None:
        import hashlib

        return hashlib.sha256(data).hexdigest()
    out = ctypes.create_string_buffer(65)
    lib.sha256_hex(data, len(data), out)
    return out.value.decode("ascii")


def b64_encode(data: bytes) -> str:
    lib = load()
    if lib is None:
        import base64

        return base64.b64encode(data).decode("ascii")
    out = ctypes.create_string_buffer(4 * ((len(data) + 2) // 3) + 1)
    n = lib.b64_encode(data, len(data), out)
    return out.raw[:n].decode("ascii")


def png_encode_rgb(arr) -> bytes | None:
    """uint8 (H, W, 3) -> PNG bytes, or None when the native path is
    unavailable (caller falls back to PIL)."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    arr = np.ascontiguousarray(arr, dtype=np.uint8)
    h, w = arr.shape[:2]
    cap = arr.nbytes + (1 << 16)
    out = ctypes.create_string_buffer(cap)
    n = lib.png_encode_rgb(arr.ctypes.data_as(ctypes.c_char_p),
                           w, h, out, cap)
    return out.raw[:n] if n else None


def thumbnail_rgb(arr, tw: int, th: int):
    """uint8 (H, W, 3) -> uint8 (th, tw, 3), or None (caller uses PIL)."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    arr = np.ascontiguousarray(arr, dtype=np.uint8)
    h, w = arr.shape[:2]
    out = np.empty((th, tw, 3), np.uint8)
    lib.thumbnail_rgb(arr.ctypes.data_as(ctypes.c_char_p), w, h, tw, th,
                      out.ctypes.data_as(ctypes.c_char_p))
    return out
