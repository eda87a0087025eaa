"""Native host kernels: C++ CRC32C + GF(2^8)/GF(2) region math, and
the denc codec's compiled walk.

Two binding tiers, fastest first:

  * a CPython extension module (pyext.cc) whose per-call overhead is a
    few hundred ns — the small-op path (a 4KiB-chunk stripe encodes in
    ~1.5us; a ctypes call alone costs more than that).  Beside the CRC
    and GF kernels it holds the codec of every frame and every stored
    blob: `denc_dumps` / `denc_loads` (utils/denc.py `dumps` / `loads`)
    and the messenger's one pass a frame each way, `denc_dumps_msg` /
    `denc_loads_msg` (msg/message.py `encode_iov` / `decode`: the
    payload and the segment lift in one walk of the fields);
  * a ctypes-loaded shared library as the fallback binding (kernels
    only: the codec needs the C API, and falls back to its Python walk).

Both are built on first import with one g++ invocation, cached next to
the sources with a source+flags hash in the filename — edits (and flag
changes) always rebuild and a stale or foreign-machine binary can never
be picked up.  Every entry point has a pure-Python/numpy fallback so
the framework still runs where no compiler exists.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import sysconfig
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SOURCES = [os.path.join(_HERE, "crc32c.cc"), os.path.join(_HERE, "gf.cc")]
_EXT_SOURCES = _SOURCES + [os.path.join(_HERE, "pyext.cc")]
# Portable vector ISA (SSE4.2 carries the crc32 instruction; pclmul
# the carry-less multiply) rather than -march=native, so a binary
# cached on a build box cannot SIGILL on an older deployment host
# sharing the tree.  If the compiler rejects these flags (non-x86),
# _build retries with the baseline flags alone.
_CXXFLAGS = ["-O3", "-shared", "-fPIC", "-funroll-loops"]
_ISA_FLAGS = ["-msse4.2", "-mpclmul", "-mavx2"]

_lib = None
_ext = None
_lock = threading.Lock()
_tried = False
_ext_tried = False


def _hash_path(sources, prefix: str, suffix: str) -> str:
    h = hashlib.sha256()
    for src in sources:
        with open(src, "rb") as f:
            h.update(f.read())
    h.update(" ".join(_CXXFLAGS + _ISA_FLAGS).encode())
    return os.path.join(_HERE, f"{prefix}.{h.hexdigest()[:16]}{suffix}")


def _so_path() -> str:
    return _hash_path(_SOURCES, "libceph_tpu_native", ".so")


def _ext_path() -> str:
    return _hash_path(_EXT_SOURCES, "_ceph_tpu_native", ".so")


def _compile(sources, so: str, extra_flags=()) -> bool:
    # per-pid tmp: concurrent first imports in separate processes must
    # not link into the same inode one of them then publishes
    tmp = f"{so}.{os.getpid()}.tmp"
    for flags in (_CXXFLAGS + _ISA_FLAGS, _CXXFLAGS):
        cmd = ["g++"] + flags + list(extra_flags) + ["-o", tmp] + sources
        try:
            subprocess.run(cmd, check=True, capture_output=True,
                           timeout=120)
        except (subprocess.SubprocessError, FileNotFoundError, OSError):
            continue
        try:
            os.replace(tmp, so)
        except OSError:
            return False
        prefix = os.path.basename(so).split(".")[0]
        for old in glob.glob(os.path.join(_HERE, f"{prefix}.*.so")):
            if old != so:
                try:
                    os.unlink(old)
                except OSError:
                    pass
        return True
    return False


def get_lib():
    """The ctypes-loaded native library, or None if unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            so = _so_path()
            if not os.path.exists(so) and not _compile(_SOURCES, so):
                return None
            lib = ctypes.CDLL(so)
        except OSError:
            return None
        lib.ceph_tpu_crc32c.restype = ctypes.c_uint32
        lib.ceph_tpu_crc32c.argtypes = [
            ctypes.c_uint32, ctypes.c_char_p, ctypes.c_size_t]
        lib.ceph_tpu_crc32c_hw.restype = ctypes.c_int
        lib.ceph_tpu_crc32c_batch.restype = None
        lib.ceph_tpu_crc32c_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_void_p]
        lib.ceph_tpu_gf_mad.restype = None
        lib.ceph_tpu_gf_mul_region.restype = None
        lib.ceph_tpu_gf_encode.restype = None
        lib.ceph_tpu_gf_has_avx2.restype = ctypes.c_int
        if lib.ceph_tpu_gf_has_avx2():
            lib.ceph_tpu_gf_encode_avx2.restype = None
        _lib = lib
        return _lib


def get_ext():
    """The CPython extension module (sub-us call overhead), or None."""
    global _ext, _ext_tried
    if _ext is not None or _ext_tried:
        return _ext
    with _lock:
        if _ext is not None or _ext_tried:
            return _ext
        _ext_tried = True
        so = _ext_path()
        inc = sysconfig.get_paths().get("include")
        if not os.path.exists(so):
            if not inc or not os.path.exists(
                    os.path.join(inc, "Python.h")):
                return None
            if not _compile(_EXT_SOURCES, so, extra_flags=[f"-I{inc}"]):
                return None
        try:
            import importlib.util
            spec = importlib.util.spec_from_file_location(
                "_ceph_tpu_native", so)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
        except Exception:
            return None
        _ext = mod
        return _ext


def available() -> bool:
    return get_ext() is not None or get_lib() is not None


def crc32c_hw() -> bool:
    """True when the hardware crc32 instruction tier is serving
    (SSE4.2 compiled in + CPU support) — perf observability."""
    lib = get_lib()
    if lib is not None:
        try:
            return bool(lib.ceph_tpu_crc32c_hw())
        except Exception:
            return False
    return False


def crc32c(seed: int, data) -> int | None:
    """Native CRC32C or None when the library is unavailable."""
    ext = get_ext()
    if ext is not None:
        buf = data if isinstance(data, (bytes, bytearray, memoryview,
                                        np.ndarray)) else bytes(data)
        if isinstance(buf, np.ndarray) and not buf.flags.c_contiguous:
            buf = np.ascontiguousarray(buf)
        return int(ext.crc32c(seed & 0xFFFFFFFF, buf))
    lib = get_lib()
    if lib is None:
        return None
    buf = data.tobytes() if isinstance(data, np.ndarray) else bytes(data)
    return int(lib.ceph_tpu_crc32c(seed & 0xFFFFFFFF, buf, len(buf)))


def crc32c_batch(seed: int, arr: np.ndarray) -> np.ndarray | None:
    """CRC32C per row of an (N, L) uint8 array in ONE native call
    (ceph_tpu_crc32c_batch), or None when no native library exists.
    Falls back to per-row CPython-ext calls (sub-us overhead) when
    only the extension is built."""
    arr = np.ascontiguousarray(arr, dtype=np.uint8)
    if arr.ndim != 2:
        raise ValueError(f"want (N, L), got {arr.shape}")
    N, L = arr.shape
    lib = get_lib()
    if lib is not None:
        out = np.empty(N, dtype=np.uint32)
        seeds = np.full(N, seed & 0xFFFFFFFF, dtype=np.uint32)
        lib.ceph_tpu_crc32c_batch(
            arr.ctypes.data, ctypes.c_size_t(N), ctypes.c_size_t(L),
            seeds.ctypes.data, out.ctypes.data)
        return out
    ext = get_ext()
    if ext is not None:
        return np.fromiter(
            (ext.crc32c(seed & 0xFFFFFFFF, arr[i]) for i in range(N)),
            dtype=np.uint32, count=N)
    return None


def gf_encode(matrix: np.ndarray, data: np.ndarray) -> np.ndarray | None:
    """parity = matrix (m x k) * data (k x L) over GF(2^8), or None.

    Uses the AVX2 pshufb kernel (the ISA-L analog) when built with
    AVX2, else the autovectorized nibble-table loop; dispatched through
    the extension when present (ctypes otherwise).
    """
    if matrix.dtype != np.uint8 or not matrix.flags.c_contiguous:
        matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    if data.dtype != np.uint8 or not data.flags.c_contiguous:
        data = np.ascontiguousarray(data, dtype=np.uint8)
    rows, k = matrix.shape
    length = data.shape[1]
    parity = np.empty((rows, length), dtype=np.uint8)
    ext = get_ext()
    if ext is not None:
        ext.gf_encode(matrix, rows, k, data, parity, length)
        return parity
    lib = get_lib()
    if lib is None:
        return None
    fn = (lib.ceph_tpu_gf_encode_avx2 if lib.ceph_tpu_gf_has_avx2()
          else lib.ceph_tpu_gf_encode)
    fn(matrix.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
       ctypes.c_size_t(rows), ctypes.c_size_t(k),
       data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
       parity.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
       ctypes.c_size_t(length))
    return parity


def gf_encode_batch(matrix: np.ndarray,
                    data: np.ndarray) -> np.ndarray | None:
    """Batched stripes: data (S, k, L) -> parity (S, m, L), one
    binding call for the whole batch (the per-object form the OSD's
    ECUtil dispatch uses), or None without the extension."""
    ext = get_ext()
    if ext is None:
        return None
    if matrix.dtype != np.uint8 or not matrix.flags.c_contiguous:
        matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    if data.dtype != np.uint8 or not data.flags.c_contiguous:
        data = np.ascontiguousarray(data, dtype=np.uint8)
    S, k, L = data.shape
    rows = matrix.shape[0]
    parity = np.empty((S, rows, L), dtype=np.uint8)
    ext.gf_encode_batch(matrix, rows, k, data, parity, L, S)
    return parity


def bitmatrix_encode(bits: np.ndarray, data: np.ndarray, w: int,
                     packetsize: int) -> np.ndarray | None:
    """Packetized GF(2) bitmatrix encode (jerasure XOR-schedule
    semantics, ops/gf.py bitmatrix_encode_np layout), or None when no
    native binding is available."""
    ext = get_ext()
    if ext is None:
        return None
    bits = np.ascontiguousarray(bits, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    mw, kw = bits.shape
    L = data.shape[1]
    if L % (w * packetsize) != 0 or data.shape[0] != kw // w:
        return None
    parity = np.empty((mw // w, L), dtype=np.uint8)
    ext.bitmatrix_encode(bits, mw, kw, data, parity, L, w, packetsize)
    return parity
