"""Native (C++) host runtime: compile-on-first-use, graceful fallback.

The reference binds prebuilt native libraries through JNA/Panama FFI
(reference behavior: libs/native/.../NativeAccess.java selecting zstd, POSIX
mlockall, systemd bindings at runtime). Here the native pieces compile from
source with the system toolchain on first use and load via ctypes; every
caller must work without them (pure-Python fallback), mirroring the
reference's NoopNativeAccess degradation.

Components:
  - packing.cpp  — index accumulator hot loop (tokenize/hash/postings)
  - zstd.py      — ctypes binding to system libzstd (WAL/blob compression)
  - posix.py     — mlockall / rlimit bootstrap checks
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_TRIED = False


class PackSizes(ctypes.Structure):
    _fields_ = [
        ("n_terms", ctypes.c_int64),
        ("term_bytes", ctypes.c_int64),
        ("n_postings", ctypes.c_int64),
        ("n_positions", ctypes.c_int64),
    ]


def _build_lib() -> ctypes.CDLL | None:
    src = os.path.join(_HERE, "packing.cpp")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    cache_dir = os.path.join(_HERE, "_build")
    os.makedirs(cache_dir, exist_ok=True)
    so_path = os.path.join(cache_dir, f"packing_{digest}.so")
    if not os.path.exists(so_path):
        tmp = so_path + f".tmp{os.getpid()}"
        cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", src, "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, so_path)
        except (subprocess.SubprocessError, OSError):
            return None
    try:
        lib = ctypes.CDLL(so_path)
    except OSError:
        return None
    lib.builder_new.restype = ctypes.c_void_p
    lib.builder_free.argtypes = [ctypes.c_void_p]
    lib.builder_add_text.restype = ctypes.c_int64
    lib.builder_add_text.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_int32,
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
    ]
    lib.route_ascii_ids.restype = None
    lib.route_ascii_ids.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_void_p,
    ]
    lib.builder_add_texts.restype = ctypes.c_int64
    lib.builder_add_texts.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_void_p, ctypes.c_char_p,
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int, ctypes.c_void_p,
    ]
    lib.builder_add_tokens.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_int32,
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.builder_add_field_len.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_int32, ctypes.c_int32,
    ]
    lib.builder_pack_sizes.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.POINTER(PackSizes),
    ]
    lib.builder_pack_fill.argtypes = [ctypes.c_void_p] + [ctypes.c_void_p] * 8
    lib.builder_field_len_count.restype = ctypes.c_int64
    lib.builder_field_len_count.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.builder_field_len_fill.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_void_p, ctypes.c_void_p,
    ]
    return lib


def get_lib() -> ctypes.CDLL | None:
    """The packing library, or None when the toolchain is unavailable or
    ES_TPU_NATIVE=0 disables native code."""
    global _LIB, _TRIED
    if os.environ.get("ES_TPU_NATIVE", "1") == "0":
        return None
    with _LOCK:
        if not _TRIED:
            _TRIED = True
            _LIB = _build_lib()
    return _LIB


def available() -> bool:
    return get_lib() is not None
