"""ctypes bindings for the native data-path library (zoodata.cpp).

Built with g++ from the tracked source on first use and kept next to
it; all callers fall back to numpy when the toolchain is unavailable,
so the native path is an accelerator, never a dependency.  The binary
is never trusted across machines: it is built for the baseline ISA (no
``-march=native``), and one that fails to load is rebuilt once from
source before the numpy path is taken.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

log = logging.getLogger("analytics_zoo_tpu.native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "zoodata.cpp")
_LIB_PATH = os.path.join(_HERE, "libzoodata.so")
_lock = threading.Lock()
_lib = None
_tried = False


def _build() -> bool:
    cmd = ["g++", "-O3", "-shared", "-fPIC", _SRC,
           "-o", _LIB_PATH, "-lpthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        log.warning("native build failed (%s); using the numpy path", e)
        return False


def _load() -> Optional[ctypes.CDLL]:
    try:
        lib = ctypes.CDLL(_LIB_PATH)
        lib.gather_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int]
        lib.shuffle_indices.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint64]
        lib.u8_to_f32_scaled.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, ctypes.c_int]
        lib.crc32c_update.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint32]
        lib.crc32c_update.restype = ctypes.c_uint32
        return lib
    except (OSError, AttributeError) as e:
        log.warning("native lib failed to load (%s)", e)
        return None


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        # deliberate: the one-shot native build MUST be serialized (two
        # concurrent cc invocations would corrupt the artifact);
        # waiters need the lib anyway, and _tried caps this to one
        # attempt ever
        built = False
        if not os.path.exists(_LIB_PATH) or \
                os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC):
            # zoolint: disable=LOCK010 — serialized one-shot build
            if not _build():
                return None
            built = True
        _lib = _load()
        # a binary copied from another machine or build: rebuild it
        # here once instead of quietly pinning the numpy path
        # zoolint: disable=LOCK010 — serialized one-shot build
        if _lib is None and not built and _build():
            _lib = _load()
        return _lib


_N_THREADS = max(os.cpu_count() or 1, 1)


def gather_rows(src: np.ndarray, idx: np.ndarray,
                threads: Optional[int] = None) -> np.ndarray:
    """out[i] = src[idx[i]] — threaded memcpy when the native lib is
    available and the copy is big enough to amortise threads."""
    lib = get_lib()
    nbytes = src[0].nbytes * len(idx) if len(src) else 0
    if lib is None or not src.flags["C_CONTIGUOUS"] or nbytes < (1 << 20):
        return src[idx]
    idx64 = np.ascontiguousarray(idx, np.int64)
    out = np.empty((len(idx64),) + src.shape[1:], src.dtype)
    row_bytes = src[0].nbytes
    lib.gather_rows(
        src.ctypes.data, idx64.ctypes.data, len(idx64), row_bytes,
        out.ctypes.data, threads or _N_THREADS)
    return out


def shuffle_indices(n: int, seed: int) -> np.ndarray:
    """Seeded Fisher-Yates permutation.

    Deterministic per seed WITHIN each path, but the native (mt19937_64)
    and numpy-fallback permutations differ for the same seed — callers
    needing one order on every host regardless of toolchain (the
    FeatureSet epoch-shuffle contract) must use
    ``FeatureSet._epoch_perm``'s pure-numpy path instead.
    """
    lib = get_lib()
    if lib is None:
        return np.random.default_rng(seed).permutation(n)
    out = np.empty(n, np.int64)
    lib.shuffle_indices(out.ctypes.data, n, seed & 0xFFFFFFFFFFFFFFFF)
    return out


# ------------------------------------------------------------------ crc32c
_PY_CRC_TABLE = None


def _py_crc_table():
    global _PY_CRC_TABLE
    if _PY_CRC_TABLE is None:
        poly = 0x82F63B78        # reversed Castagnoli polynomial
        table = []
        for i in range(256):
            c = i
            for _ in range(8):
                c = (c >> 1) ^ poly if c & 1 else c >> 1
            table.append(c)
        # benign race: the table build is deterministic and the rebind
        # is atomic, so concurrent first calls at worst duplicate the
        # one-time build; a lock would serialize every cold crc32c call
        # zoolint: disable=RACE005 — benign idempotent lazy init
        _PY_CRC_TABLE = table
    return _PY_CRC_TABLE


def crc32c(data: bytes, crc: int = 0) -> int:
    """CRC-32C (Castagnoli) — the TFRecord / TensorBoard framing
    checksum, shared by feature/tfrecord.py and utils/tb_writer.py.
    Native when the data-path library is available (~100x on multi-MB
    payloads), pure-Python table loop otherwise."""
    lib = get_lib()
    if lib is not None:
        return int(lib.crc32c_update(data, len(data),
                                     ctypes.c_uint32(crc)))
    table = _py_crc_table()
    crc = crc ^ 0xFFFFFFFF
    for b in data:
        crc = table[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF
