"""Native host runtime: ctypes bindings over the C++ library, with numpy
fallbacks so the engine runs without the compiled artifact.

Reference parity: SURVEY.md section 2.9 — the reference's native surface
is off-heap mmap buffers + JNI codec jars + bit-unpack hot loops; the
build-on-first-use .so here plays that role for the host side of the TPU
pipeline (the device side is XLA). See src/pinot_native.cpp.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "pinot_native.cpp")
_SO = os.path.join(_HERE, "libpinot_native.so")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_build_error: Optional[str] = None


def _build() -> bool:
    """Compile src/ into the (untracked) .so. The compiler writes a
    per-process temp file that is renamed into place, so two processes
    building at once (batch-ingestion workers) never load a torn file."""
    global _build_error
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC,
           "-o", tmp, "-lz", "-lzstd"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return True
    except subprocess.CalledProcessError as e:
        _build_error = e.stderr.decode(errors="replace")[-2000:]
    except (OSError, subprocess.TimeoutExpired) as e:
        _build_error = f"{type(e).__name__}: {e}"
    if os.path.exists(tmp):
        os.remove(tmp)
    return False


def build_error() -> Optional[str]:
    """Why the last build attempt failed (None: it did not run or it
    succeeded). The numpy fallbacks keep the engine running without a
    compiler; callers that must not run degraded check this."""
    return _build_error


def load() -> Optional[ctypes.CDLL]:
    """The shared library, building it on first use; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        src_exists = os.path.exists(_SRC)
        stale = (src_exists and os.path.exists(_SO)
                 and os.path.getmtime(_SRC) > os.path.getmtime(_SO))
        if not os.path.exists(_SO) or stale:
            # a prebuilt .so without src/ in the deployment loads as-is
            if not src_exists or not _build():
                if not os.path.exists(_SO):
                    return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        c_i64, c_i32, c_u8 = (ctypes.c_int64, ctypes.c_int32, ctypes.c_uint8)
        p_i32 = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        p_u8 = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        lib.fixedbit_pack.restype = c_i64
        lib.fixedbit_pack.argtypes = [p_i32, c_i64, ctypes.c_int, p_u8]
        lib.fixedbit_unpack.restype = None
        lib.fixedbit_unpack.argtypes = [p_u8, c_i64, ctypes.c_int, p_i32]
        for name in ("zlib_compress_chunk", "zstd_compress_chunk",
                     "lz4_compress_chunk", "snappy_compress_chunk"):
            fn = getattr(lib, name)
            fn.restype = c_i64
            fn.argtypes = [p_u8, c_i64, p_u8, c_i64, ctypes.c_int]
        for name in ("zlib_decompress_chunk", "zstd_decompress_chunk",
                     "lz4_decompress_chunk", "snappy_decompress_chunk"):
            fn = getattr(lib, name)
            fn.restype = c_i64
            fn.argtypes = [p_u8, c_i64, p_u8, c_i64]
        lib.compress_bound.restype = c_i64
        lib.compress_bound.argtypes = [c_i64]
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


# ---------------------------------------------------------------------------
# fixed-bit pack/unpack (numpy fallback mirrors the C++ exactly)
# ---------------------------------------------------------------------------

def bits_for(cardinality: int) -> int:
    return max(1, int(cardinality - 1).bit_length()) if cardinality > 1 else 1


def fixedbit_pack(ids: np.ndarray, bits: int) -> np.ndarray:
    ids = np.ascontiguousarray(ids, dtype=np.int32)
    n = len(ids)
    nbytes = (n * bits + 7) // 8
    lib = load()
    if lib is not None:
        out = np.zeros(nbytes + 8, dtype=np.uint8)  # +8: unpack window pad
        lib.fixedbit_pack(ids, n, bits, out)
        return out
    # numpy fallback: expand to a bit matrix then packbits (little-endian)
    shifts = np.arange(bits, dtype=np.uint32)
    bitmat = ((ids.astype(np.uint32)[:, None] >> shifts) & 1).astype(np.uint8)
    flat = bitmat.reshape(-1)
    out = np.packbits(flat, bitorder="little")
    padded = np.zeros(nbytes + 8, dtype=np.uint8)
    padded[: len(out)] = out
    return padded


def fixedbit_unpack(buf: np.ndarray, n: int, bits: int) -> np.ndarray:
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    lib = load()
    if lib is not None:
        out = np.empty(n, dtype=np.int32)
        lib.fixedbit_unpack(buf, n, bits, out)
        return out
    flat = np.unpackbits(buf, bitorder="little")[: n * bits]
    bitmat = flat.reshape(n, bits).astype(np.uint32)
    weights = (np.uint32(1) << np.arange(bits, dtype=np.uint32))
    return (bitmat * weights).sum(axis=1).astype(np.int32)


# ---------------------------------------------------------------------------
# chunk codecs
# ---------------------------------------------------------------------------

CODECS = ("ZSTD", "ZLIB", "LZ4", "SNAPPY", "PASS_THROUGH", "DELTA")


def compress(data: np.ndarray, codec: str = "ZSTD", level: int = 3
             ) -> np.ndarray:
    if codec == "PASS_THROUGH":
        return np.ascontiguousarray(data).view(np.uint8).reshape(-1).copy()
    if codec == "DELTA":
        return delta_pack(data)
    raw = np.ascontiguousarray(data).view(np.uint8).reshape(-1)
    lib = load()
    if lib is not None:
        cap = int(lib.compress_bound(len(raw)))
        out = np.empty(cap, dtype=np.uint8)
        fn = {"ZSTD": lib.zstd_compress_chunk,
              "ZLIB": lib.zlib_compress_chunk,
              "LZ4": lib.lz4_compress_chunk,
              "SNAPPY": lib.snappy_compress_chunk}[codec]
        sz = fn(raw, len(raw), out, cap, level)
        if sz < 0:
            raise RuntimeError(f"{codec} compression failed")
        return out[:sz].copy()
    if codec != "ZLIB":
        # never write a codec the metadata can't honor elsewhere: a silent
        # zlib stream labeled ZSTD is unreadable wherever the lib exists
        raise RuntimeError(f"native library unavailable; codec {codec!r} "
                           "needs it (use ZLIB for the pure-python path)")
    import zlib
    return np.frombuffer(zlib.compress(raw.tobytes(), level), dtype=np.uint8)


def decompress(data: np.ndarray, raw_size: int, codec: str = "ZSTD"
               ) -> np.ndarray:
    buf = np.ascontiguousarray(data, dtype=np.uint8)
    if codec == "PASS_THROUGH":
        return buf[:raw_size]
    if codec == "DELTA":
        out = delta_unpack(buf)
        if len(out) != raw_size:
            raise RuntimeError(
                f"DELTA decompression failed ({len(out)} != {raw_size})")
        return out
    lib = load()
    if lib is not None:
        out = np.empty(raw_size, dtype=np.uint8)
        fn = {"ZSTD": lib.zstd_decompress_chunk,
              "ZLIB": lib.zlib_decompress_chunk,
              "LZ4": lib.lz4_decompress_chunk,
              "SNAPPY": lib.snappy_decompress_chunk}[codec]
        sz = fn(buf, len(buf), out, raw_size)
        if sz != raw_size:
            raise RuntimeError(f"{codec} decompression failed ({sz})")
        return out
    if codec != "ZLIB":
        raise RuntimeError(f"native library unavailable; cannot decode "
                           f"{codec!r} column (rebuild the native lib)")
    import zlib
    return np.frombuffer(zlib.decompress(buf.tobytes()), dtype=np.uint8)


# ---------------------------------------------------------------------------
# DELTA codec: zigzag deltas + fixed-bit packing. Wins big on sorted /
# clustered integer columns (timestamps, auto-increment keys) where
# general codecs only see noise. The bit-pack hot loop is the same C++
# fixedbit path the dictionary forward index uses; delta/zigzag/cumsum
# are numpy vector ops.
# Layout: [1B itemsize][1B bits][8B n][8B first value][packed deltas].
# ---------------------------------------------------------------------------

_DELTA_HEADER = 18


def delta_pack(data: np.ndarray) -> np.ndarray:
    arr = np.ascontiguousarray(data)
    if arr.dtype.kind not in "iu" or arr.ndim != 1:
        raise RuntimeError("DELTA codec needs a 1-D integer column")
    a = arr.astype(np.int64)
    n = len(a)
    first = a[0] if n else np.int64(0)
    delta = np.diff(a)
    zz = ((delta << 1) ^ (delta >> 63)).astype(np.uint64)  # zigzag
    hi = int(zz.max()) if len(zz) else 0
    bits = max(int(hi).bit_length(), 1)
    if bits > 32:
        raise RuntimeError("DELTA deltas exceed 32 bits; use ZSTD")
    packed = fixedbit_pack(zz.astype(np.int64).astype(np.uint32)
                           .view(np.int32), bits)
    out = np.empty(_DELTA_HEADER + len(packed), dtype=np.uint8)
    out[0] = arr.dtype.itemsize
    out[1] = bits
    out[2:10] = np.frombuffer(np.int64(n).tobytes(), dtype=np.uint8)
    out[10:18] = np.frombuffer(np.int64(first).tobytes(), dtype=np.uint8)
    out[_DELTA_HEADER:] = packed
    return out


def delta_unpack(buf: np.ndarray) -> np.ndarray:
    itemsize = int(buf[0])
    bits = int(buf[1])
    n = int(np.frombuffer(buf[2:10].tobytes(), dtype=np.int64)[0])
    first = np.int64(np.frombuffer(buf[10:18].tobytes(),
                                   dtype=np.int64)[0])
    dtype = {1: np.int8, 2: np.int16, 4: np.int32, 8: np.int64}[itemsize]
    if n == 0:
        return np.zeros(0, dtype=np.uint8)
    zz = fixedbit_unpack(np.ascontiguousarray(buf[_DELTA_HEADER:]),
                         n - 1, bits).view(np.uint32).astype(np.uint64)
    delta = (zz >> 1).astype(np.int64) ^ -(zz & 1).astype(np.int64)
    out = np.empty(n, dtype=np.int64)
    out[0] = first
    out[1:] = first + np.cumsum(delta)
    return out.astype(dtype).view(np.uint8)
