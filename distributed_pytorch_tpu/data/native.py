"""ctypes binding for the native C++ sampler (csrc/sampler.cpp), with a
bit-identical vectorized NumPy fallback.

Build model: the shared library is compiled on demand with g++ (no
pybind11 in this image; plain `extern "C"` + ctypes) and cached next to
the source, keyed by a content hash of the source plus the compiler
version — never by mtime, so a fresh clone always compiles from the
committed source and an edited sampler.cpp always rebuilds. The build
directory is untracked (.gitignore). Environments without a toolchain use
`philox_offsets` / pure-numpy gathers — the DataLoader behaves identically
either way because both implementations compute the same Philox4x32-10
stream (asserted by tests/test_native.py) — and which of the two a process
got is said once on stderr (`_say_sampler`).
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import os
import subprocess
import sys
import threading
from typing import Optional

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc", "sampler.cpp")
_BUILD_DIR = os.path.join(os.path.dirname(_SRC), "build")


@functools.lru_cache(maxsize=1)
def _lib_path() -> Optional[str]:
    """Cache path keyed on sha256(source) + g++ version: a stale or
    unverifiable committed binary can never shadow the committed source."""
    if not os.path.exists(_SRC):
        return None
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    try:
        ver = subprocess.run(["g++", "--version"], capture_output=True,
                             timeout=30).stdout.split(b"\n", 1)[0]
    except Exception:
        ver = b"no-gxx"
    h.update(ver)
    return os.path.join(_BUILD_DIR, f"libsampler-{h.hexdigest()[:16]}.so")

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_failed = False

_M0 = np.uint64(0xD2511F53)
_M1 = np.uint64(0xCD9E8D57)
_W0 = np.uint32(0x9E3779B9)
_W1 = np.uint32(0xBB67AE85)
_MASK32 = np.uint64(0xFFFFFFFF)


def philox_offsets(seed: int, step: int, rows: np.ndarray,
                   hi: int) -> np.ndarray:
    """Philox4x32-10 offsets in [0, hi) for global batch-row ids `rows` at
    (seed, step). Bit-identical to csrc/sampler.cpp sample_offset()."""
    rows = np.asarray(rows, np.uint32)
    c0 = rows.astype(np.uint64)
    c1 = np.full_like(c0, np.uint64(step & 0xFFFFFFFF))
    c2 = np.full_like(c0, np.uint64((step >> 32) & 0xFFFFFFFF))
    c3 = np.zeros_like(c0)
    k0 = seed & 0xFFFFFFFF          # python ints: explicit mod-2^32 adds
    k1 = (seed >> 32) & 0xFFFFFFFF
    for _ in range(10):
        p0 = _M0 * c0          # 64-bit products (c in [0, 2^32))
        p1 = _M1 * c2
        hi0, lo0 = p0 >> np.uint64(32), p0 & _MASK32
        hi1, lo1 = p1 >> np.uint64(32), p1 & _MASK32
        c0, c1, c2, c3 = (hi1 ^ c1 ^ np.uint64(k0), lo1,
                          hi0 ^ c3 ^ np.uint64(k1), lo0)
        k0 = (k0 + 0x9E3779B9) & 0xFFFFFFFF
        k1 = (k1 + 0xBB67AE85) & 0xFFFFFFFF
    u = (c1 << np.uint64(32)) | c0
    return (u % np.uint64(hi)).astype(np.int64)


def _build_lib() -> Optional[str]:
    """Compile csrc/sampler.cpp -> build/libsampler-<hash>.so if missing."""
    path = _lib_path()
    if path is None:
        return None
    if os.path.exists(path):
        return path
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = path + f".tmp{os.getpid()}"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
           _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, path)
        return path
    except Exception:
        if os.path.exists(tmp):
            os.unlink(tmp)
        return None


def _say_sampler(which: str) -> None:
    """Once per process (callers hold the load-once lock's first pass)."""
    print(f"[data] sampler: {which}", file=sys.stderr)


def _load_lib() -> Optional[ctypes.CDLL]:
    global _lib, _lib_failed
    with _lib_lock:
        if _lib is not None or _lib_failed:
            return _lib
        path = _build_lib()
        if path is None:
            _lib_failed = True
            _say_sampler("numpy (csrc/sampler.cpp did not build: no g++ "
                         "or the compile failed) — bit-identical, slower")
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            _lib_failed = True
            _say_sampler(f"numpy ({os.path.basename(path)} did not load: "
                         f"{e}) — bit-identical, slower")
            return None
        _say_sampler(f"native ({os.path.basename(path)})")
        lib.dl_open.restype = ctypes.c_void_p
        lib.dl_open.argtypes = [ctypes.c_char_p]
        lib.dl_close.argtypes = [ctypes.c_void_p]
        lib.dl_num_tokens.restype = ctypes.c_uint64
        lib.dl_num_tokens.argtypes = [ctypes.c_void_p]
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
        lib.dl_sample.restype = ctypes.c_int
        lib.dl_sample.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                  ctypes.c_uint64, ctypes.c_uint32,
                                  ctypes.c_uint32, i32p, i32p]
        lib.dl_sample_rows.restype = ctypes.c_int
        lib.dl_sample_rows.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                       ctypes.c_uint64, u32p,
                                       ctypes.c_uint32, ctypes.c_uint32,
                                       i32p, i32p]
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        lib.dl_sample_offsets.restype = None
        lib.dl_sample_offsets.argtypes = [ctypes.c_uint64, ctypes.c_uint64,
                                          u32p, ctypes.c_uint32,
                                          ctypes.c_uint64, i64p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load_lib() is not None


def native_offsets(seed: int, step: int, rows: np.ndarray,
                   hi: int) -> np.ndarray:
    """The C++ sample_offset() stream for `rows` — the native counterpart of
    `philox_offsets`, exported for direct bit-identity testing."""
    lib = _load_lib()
    if lib is None:
        raise OSError("native sampler library unavailable")
    rows = np.ascontiguousarray(rows, np.uint32)
    out = np.empty(len(rows), np.int64)
    lib.dl_sample_offsets(seed, step, rows, len(rows), hi, out)
    return out


class NativeSampler:
    """Handle over the C++ loader. Raises OSError if the library or file
    can't be opened — callers (DataLoader) decide on fallback."""

    def __init__(self, path: str):
        lib = _load_lib()
        if lib is None:
            raise OSError("native sampler library unavailable")
        self._lib = lib
        self._h = lib.dl_open(path.encode())
        if not self._h:
            raise OSError(f"dl_open failed for {path}")

    @property
    def n_tokens(self) -> int:
        return int(self._lib.dl_num_tokens(self._h))

    def sample(self, seed: int, step: int, n_rows: int, T: int):
        """Full contiguous global batch (rows 0..n_rows), with background
        prefetch of step+1 inside the library."""
        x = np.empty((n_rows, T), np.int32)
        y = np.empty((n_rows, T), np.int32)
        rc = self._lib.dl_sample(self._h, seed, step, n_rows, T, x, y)
        if rc != 0:
            raise ValueError("dataset too small for block size")
        return x, y

    def sample_rows(self, seed: int, step: int, rows: np.ndarray, T: int):
        """Arbitrary row subset (multi-host shard materialization)."""
        rows = np.ascontiguousarray(rows, np.uint32)
        n = len(rows)
        x = np.empty((n, T), np.int32)
        y = np.empty((n, T), np.int32)
        rc = self._lib.dl_sample_rows(self._h, seed, step, rows, n, T, x, y)
        if rc != 0:
            raise ValueError("dataset too small for block size")
        return x, y

    def close(self):
        if self._h:
            self._lib.dl_close(self._h)
            self._h = None

    def __del__(self):  # best-effort; close() is the real API
        try:
            self.close()
        except Exception:
            pass
