"""Build + load libds2native.so on demand.

Sources live in ``native/src`` at the repo root; the shared library is
compiled once into ``native/build/`` with g++ (baked into the image) and
rebuilt whenever the content of the sources differs from what the
binary was built from (a hash recorded beside it — a copied tree has
arbitrary mtimes). Concurrent builders (pytest-xdist, multi-process loaders) are serialized
with an fcntl lock and an atomic rename, so a half-written .so is never
loaded.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import Optional

_SRC_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native", "src")
_BUILD_DIR = os.path.join(os.path.dirname(_SRC_DIR), "build")
_LIB_PATH = os.path.join(_BUILD_DIR, "libds2native.so")
_HASH_PATH = _LIB_PATH + ".srchash"
_ABI_VERSION = 1

_CXXFLAGS = ["-O3", "-std=c++17", "-fPIC", "-shared", "-pthread", "-Wall"]

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_error: Optional[str] = None
_attempted = False


def _sources():
    return sorted(
        os.path.join(_SRC_DIR, f)
        for f in os.listdir(_SRC_DIR)
        if f.endswith(".cc"))


def _source_hash() -> str:
    """Hash of everything the binary is a function of: the bytes of
    every ``.cc``/``.h`` under ``native/src`` and the compiler flags."""
    h = hashlib.sha256(" ".join(_CXXFLAGS).encode())
    for name in sorted(os.listdir(_SRC_DIR)):
        if name.endswith((".cc", ".h")):
            with open(os.path.join(_SRC_DIR, name), "rb") as f:
                h.update(name.encode() + b"\0" + f.read() + b"\0")
    return h.hexdigest()


def _needs_build() -> bool:
    if not os.path.exists(_LIB_PATH):
        return True
    try:
        with open(_HASH_PATH) as f:
            built_from = f.read().strip()
    except OSError:
        return True
    return built_from != _source_hash()


def _build() -> None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    lock_path = os.path.join(_BUILD_DIR, ".build.lock")
    with open(lock_path, "w") as lock_f:
        fcntl.flock(lock_f, fcntl.LOCK_EX)
        try:
            if not _needs_build():  # another process built it meanwhile
                return
            src_hash = _source_hash()
            fd, tmp = tempfile.mkstemp(suffix=".so", dir=_BUILD_DIR)
            os.close(fd)
            cmd = ["g++", *_CXXFLAGS, "-I", _SRC_DIR, *_sources(), "-o", tmp]
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=300)
            if proc.returncode != 0:
                os.unlink(tmp)
                raise RuntimeError(
                    f"g++ failed ({proc.returncode}):\n{proc.stderr[-4000:]}")
            os.replace(tmp, _LIB_PATH)  # atomic: loaders never see partials
            # Hash lands after the binary: a crash in between leaves a
            # stale hash, which only costs a rebuild.
            with open(_HASH_PATH + ".tmp", "w") as f:
                f.write(src_hash + "\n")
            os.replace(_HASH_PATH + ".tmp", _HASH_PATH)
        finally:
            fcntl.flock(lock_f, fcntl.LOCK_UN)


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded library, building it first if needed; None on failure
    (reason via build_error())."""
    global _lib, _error, _attempted
    with _lock:
        if _lib is not None:
            return _lib
        if _attempted and _error is not None:
            return None
        _attempted = True
        try:
            if _needs_build():
                _build()
            lib = ctypes.CDLL(_LIB_PATH)
            lib.ds2n_abi_version.restype = ctypes.c_int
            got = lib.ds2n_abi_version()
            if got != _ABI_VERSION:
                raise RuntimeError(
                    f"ds2native ABI {got} != expected {_ABI_VERSION}")
            _lib = lib
            _error = None
            return _lib
        except (OSError, RuntimeError, subprocess.TimeoutExpired) as e:
            _error = str(e)
            return None


def available() -> bool:
    return get_lib() is not None


def build_error() -> Optional[str]:
    get_lib()
    return _error
