"""Native runtime library: build-on-first-import + ctypes binding.

The reference consumes its native muscle (cuDF/RMM/nvcomp/UCX) as
prebuilt JNI libraries; here the native layer is small enough to compile
from source at first import (g++ -O3 -shared), cached next to the source.
The binary on disk is used only while a stamp beside it ties it, by
content hash, to the source it was built from.  Where no compiler exists
the codec layer runs in its Python zlib mode — slower, still correct,
mirroring the reference's ability to run with compression disabled — and
says so once on stderr.  A compiler that fails is an error."""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import sys
import threading

_SRC = os.path.join(os.path.dirname(__file__), "src", "tpu_native.cpp")
_SO = os.path.join(os.path.dirname(__file__), "build", "libtpu_native.so")

_lock = threading.Lock()
_lib = None
_build_error: str = ""


def _sha256(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _built_from(so: str, src_sha: str) -> bool:
    """True when ``so`` is the binary its stamp says was built from the
    source with hash ``src_sha`` (an mtime says nothing of the kind: a
    copied tree resets it)."""
    try:
        with open(so + ".sha256") as f:
            return f.read().split() == [src_sha, _sha256(so)]
    except OSError:
        return False


def _user_cache_so() -> str:
    return os.path.join(
        os.environ.get("XDG_CACHE_HOME", os.path.expanduser("~/.cache")),
        "spark_rapids_tpu", "libtpu_native.so")


def _build() -> str:
    """Ensure the .so exists and came from the source: wheel installs
    ship it prebuilt without the source (setup.py); source checkouts
    compile on first import; read-only installs compile into a per-user
    cache dir.  Returns "" or, where no compiler exists, the reason for
    the zlib mode; a failed compile raises."""
    global _SO
    if not os.path.exists(_SRC):
        if os.path.exists(_SO):
            return ""
        raise RuntimeError(
            f"native build failed: neither {_SO} nor {_SRC} exists")
    src_sha = _sha256(_SRC)
    candidates = (_SO, _user_cache_so())
    for so in candidates:
        if _built_from(so, src_sha):
            _SO = so
            return ""
    if shutil.which("g++") is None:
        return "no C++ compiler (g++) on PATH"
    errors = []
    for target in candidates:
        try:
            os.makedirs(os.path.dirname(target), exist_ok=True)
            tmp = f"{target}.{os.getpid()}.tmp"
            with open(tmp, "wb"):
                pass
        except OSError as ex:
            errors.append(f"{target}: {ex}")
            continue
        try:
            r = subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                 "-o", tmp, _SRC],
                capture_output=True, text=True, timeout=120)
            if r.returncode != 0:
                raise RuntimeError(
                    f"native build failed: {r.stderr[-2000:]}")
            so_sha = _sha256(tmp)
            os.replace(tmp, target)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        with open(target + ".sha256", "w") as f:
            f.write(f"{src_sha} {so_sha}\n")
        _SO = target
        return ""
    raise RuntimeError("native build failed: no writable dir ("
                       + "; ".join(errors) + ")")


def get_lib():
    """The loaded native library, or None where no compiler exists (the
    reason is recorded and printed once)."""
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error:
            return _lib
        _build_error = _build()
        if _build_error:
            print(f"spark_rapids_tpu.native: {_build_error}; the codec "
                  f"runs in its Python zlib mode", file=sys.stderr)
            return None
        lib = ctypes.CDLL(_SO)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.tpu_lz4_bound.restype = ctypes.c_int64
        lib.tpu_lz4_bound.argtypes = [ctypes.c_int64]
        for fn in (lib.tpu_lz4_compress, lib.tpu_lz4_decompress):
            fn.restype = ctypes.c_int64
            fn.argtypes = [u8p, ctypes.c_int64, u8p, ctypes.c_int64]
        lib.tpu_arena_create.restype = ctypes.c_void_p
        lib.tpu_arena_create.argtypes = [ctypes.c_int64]
        lib.tpu_arena_alloc.restype = ctypes.c_int64
        lib.tpu_arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                        ctypes.c_int64]
        lib.tpu_arena_base.restype = ctypes.POINTER(ctypes.c_uint8)
        lib.tpu_arena_base.argtypes = [ctypes.c_void_p]
        for fn in (lib.tpu_arena_used, lib.tpu_arena_high_water,
                   lib.tpu_arena_allocs):
            fn.restype = ctypes.c_int64
            fn.argtypes = [ctypes.c_void_p]
        lib.tpu_arena_reset.restype = None
        lib.tpu_arena_reset.argtypes = [ctypes.c_void_p]
        lib.tpu_arena_destroy.restype = None
        lib.tpu_arena_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def build_error() -> str:
    return _build_error
