"""Native runtime loader: builds src/mxtpu into libmxtpu.so and binds it.

The reference ships a prebuilt libmxnet.so; here the small native runtime
(engine scheduler, pooled storage, recordio — src/mxtpu/) is compiled on
first use with the system toolchain and cached under build/. Loading is
best-effort: if no C++ toolchain is available the framework stays fully
functional on the pure-Python fallbacks (recordio.py, NaiveEngine).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_lock = threading.Lock()
_lib = None
_tried = False

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO, "src", "mxtpu")
_BUILD = os.path.join(_REPO, "build")
_SO = os.path.join(_BUILD, "libmxtpu.so")


_HASH = _SO + ".srchash"


def _src_hash() -> str:
    """sha256 over the names and contents of src/mxtpu's sources — what
    the .so beside it must have been built from.  A content hash, not an
    mtime: a copy of the tree (git checkout, the chip tool's disk copy)
    reorders mtimes, and an ignored-by-git ``build/`` can travel with a
    tree whose sources have since changed."""
    import hashlib

    h = hashlib.sha256()
    for fn in sorted(os.listdir(_SRC)):
        if fn.endswith((".cc", ".h")):
            h.update(fn.encode() + b"\0")
            with open(os.path.join(_SRC, fn), "rb") as f:
                h.update(f.read())
    return h.hexdigest()


def _needs_build() -> bool:
    if not os.path.isdir(_SRC):
        # no C++ tree (bare wheel, or source removed): a previously
        # built .so is still perfectly loadable — never rebuild, and
        # only "need" a build (which will fail gracefully) if no .so
        return not os.path.exists(_SO)
    if not os.path.exists(_SO):
        return True
    try:
        with open(_HASH) as f:
            built_from = f.read().strip()
    except OSError:
        return True     # a binary of unknown origin: rebuild, never trust
    return built_from != _src_hash()


def _build() -> bool:
    """Compile under an exclusive file lock, to a temp path, then rename
    atomically — concurrent processes (pytest workers, forked DataLoader
    workers) must never load a half-written .so."""
    import fcntl

    if not os.path.isdir(_SRC):
        import logging

        logging.getLogger(__name__).warning(
            "native runtime source (src/mxtpu) not present in this "
            "install; using Python fallbacks")
        return False
    os.makedirs(_BUILD, exist_ok=True)
    lock_path = os.path.join(_BUILD, ".mxtpu_build.lock")
    with open(lock_path, "w") as lock_fp:
        fcntl.flock(lock_fp, fcntl.LOCK_EX)
        try:
            if not _needs_build():  # another process finished while we waited
                return True
            tmp = f"{_SO}.tmp.{os.getpid()}"
            srcs = sorted(os.path.join(_SRC, f) for f in os.listdir(_SRC)
                          if f.endswith(".cc"))
            cmd = ["g++", "-std=c++17", "-O2", "-fPIC", "-shared",
                   "-pthread", "-Wall", "-o", tmp] + srcs
            try:
                res = subprocess.run(cmd, capture_output=True, text=True,
                                     timeout=300)
            except (OSError, subprocess.TimeoutExpired):
                return False
            if res.returncode != 0:
                import logging
                logging.getLogger(__name__).warning(
                    "native runtime build failed, using Python fallbacks:\n%s",
                    res.stderr[-2000:])
                return False
            os.rename(tmp, _SO)
            with open(_HASH + f".tmp.{os.getpid()}", "w") as f:
                f.write(_src_hash())
            os.rename(f.name, _HASH)
            return True
        finally:
            fcntl.flock(lock_fp, fcntl.LOCK_UN)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.MXTPUGetLastError.restype = c.c_char_p
    lib.MXTPUEngineCreate.restype = c.c_void_p
    lib.MXTPUEngineCreate.argtypes = [c.c_int]
    lib.MXTPUEngineFree.argtypes = [c.c_void_p]
    lib.MXTPUEngineNewVar.restype = c.c_void_p
    lib.MXTPUEngineNewVar.argtypes = [c.c_void_p]
    lib.MXTPUEngineDeleteVar.argtypes = [c.c_void_p, c.c_void_p]
    lib.MXTPUEnginePush.restype = c.c_int
    lib.MXTPUEnginePush.argtypes = [
        c.c_void_p, OP_FN, c.c_void_p, c.POINTER(c.c_void_p), c.c_int,
        c.POINTER(c.c_void_p), c.c_int, c.c_int]
    lib.MXTPUEnginePushNamed.restype = c.c_int
    lib.MXTPUEnginePushNamed.argtypes = [
        c.c_void_p, OP_FN, c.c_void_p, c.POINTER(c.c_void_p), c.c_int,
        c.POINTER(c.c_void_p), c.c_int, c.c_int, c.c_char_p]
    lib.MXTPUEngineProfileStart.argtypes = [c.c_void_p]
    lib.MXTPUEngineProfileStop.argtypes = [c.c_void_p]
    lib.MXTPUEngineProfileDump.restype = c.c_int64
    lib.MXTPUEngineProfileDump.argtypes = [c.c_void_p, c.c_char_p,
                                           c.c_int64]
    lib.MXTPUEngineWaitForVar.restype = c.c_int
    lib.MXTPUEngineWaitForVar.argtypes = [c.c_void_p, c.c_void_p]
    lib.MXTPUEngineWaitForAll.restype = c.c_int
    lib.MXTPUEngineWaitForAll.argtypes = [c.c_void_p]
    lib.MXTPUEngineOutstanding.restype = c.c_int64
    lib.MXTPUEngineOutstanding.argtypes = [c.c_void_p]
    lib.MXTPUStorageAlloc.restype = c.c_void_p
    lib.MXTPUStorageAlloc.argtypes = [c.c_int64]
    lib.MXTPUStorageFree.argtypes = [c.c_void_p]
    lib.MXTPUStorageStats.argtypes = [c.POINTER(c.c_int64)] * 4
    lib.MXTPURecordIOWriterCreate.restype = c.c_void_p
    lib.MXTPURecordIOWriterCreate.argtypes = [c.c_char_p]
    lib.MXTPURecordIOWriterWrite.restype = c.c_int64
    lib.MXTPURecordIOWriterWrite.argtypes = [c.c_void_p, c.c_char_p,
                                             c.c_uint32]
    lib.MXTPURecordIOWriterTell.restype = c.c_int64
    lib.MXTPURecordIOWriterTell.argtypes = [c.c_void_p]
    lib.MXTPURecordIOWriterClose.argtypes = [c.c_void_p]
    lib.MXTPURecordIOReaderCreate.restype = c.c_void_p
    lib.MXTPURecordIOReaderCreate.argtypes = [c.c_char_p]
    lib.MXTPURecordIOReaderNext.restype = c.c_void_p
    lib.MXTPURecordIOReaderNext.argtypes = [c.c_void_p,
                                            c.POINTER(c.c_uint32)]
    lib.MXTPURecordIOReaderSkip.restype = c.c_int64
    lib.MXTPURecordIOReaderSkip.argtypes = [c.c_void_p]
    lib.MXTPURecordIOReaderSeek.argtypes = [c.c_void_p, c.c_int64]
    lib.MXTPURecordIOReaderTell.restype = c.c_int64
    lib.MXTPURecordIOReaderTell.argtypes = [c.c_void_p]
    lib.MXTPURecordIOReaderClose.argtypes = [c.c_void_p]
    return lib


# engine op callback signature: (ctx, err_buf, err_buf_len, skipped) -> int.
# err_buf is POINTER(c_char), NOT c_char_p: ctypes would convert c_char_p
# to an immutable bytes copy, making the error write-back impossible.
# skipped=1 -> a dependency failed: release per-op state, do no real work.
OP_FN = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p,
                         ctypes.POINTER(ctypes.c_char), ctypes.c_int,
                         ctypes.c_int)


def get_lib():
    """The bound native library, or None if unavailable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if os.environ.get("MXTPU_DISABLE_NATIVE", "0") == "1":
            return None
        try:
            if _needs_build() and not _build():
                return None
            _lib = _bind(ctypes.CDLL(_SO))
        except (OSError, AttributeError):
            # AttributeError: a stale prebuilt .so (kept when src/ is
            # absent) may predate a symbol _bind expects — fall back to
            # the Python implementations rather than crash at setup
            _lib = None
        return _lib


def native_available() -> bool:
    return get_lib() is not None
