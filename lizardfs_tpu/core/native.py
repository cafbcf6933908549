"""ctypes bindings for the native C++ EC kernels (native/libec_native.so).

Provides ``CppChunkEncoder`` — the ISA-L-class CPU backend: same bytes
as the golden numpy path, SIMD speed. Used as the default chunkserver/
client encoder when present.
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

from lizardfs_tpu.constants import MFSBLOCKSIZE
from lizardfs_tpu.core.encoder import ChunkEncoder
from lizardfs_tpu.ops import gf256

_LIB_PATHS = tuple(
    p for p in (
        # LZ_NATIVE_SO: load an alternate build (the ASAN/TSAN targets
        # in native/Makefile) without touching the production .so
        os.environ.get("LZ_NATIVE_SO", ""),
        os.path.join(
            os.path.dirname(__file__), "..", "..", "native",
            "libec_native.so",
        ),
        "libec_native.so",
    ) if p
)


def _load() -> ctypes.CDLL | None:
    for path in _LIB_PATHS:
        try:
            lib = ctypes.CDLL(os.path.abspath(path) if os.sep in path else path)
        except OSError:
            continue
        lib.lz_ec_encode.argtypes = [
            ctypes.c_size_t, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_void_p),
        ]
        lib.lz_ec_encode.restype = None
        lib.lz_crc32.argtypes = [
            ctypes.c_uint32, ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t
        ]
        lib.lz_crc32.restype = ctypes.c_uint32
        lib.lz_crc32_blocks.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint32),
        ]
        lib.lz_crc32_blocks.restype = None
        try:
            lib.lz_stripe_scatter.argtypes = [
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64,
                ctypes.c_uint32, ctypes.c_uint32,
                ctypes.POINTER(ctypes.c_uint8),
            ]
            lib.lz_stripe_scatter.restype = None
            lib.lz_stripe_gather.argtypes = [
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_uint32,
                ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint8),
            ]
            lib.lz_stripe_gather.restype = None
        except AttributeError:
            pass  # stale .so without the stripe helpers: numpy fallback
        try:
            lib.lz_ec_encode_mt.argtypes = [
                ctypes.c_size_t, ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.c_int,
            ]
            lib.lz_ec_encode_mt.restype = None
        except AttributeError:
            pass  # stale .so: single-threaded encode only
        return lib
    return None


_lib = _load()


def available() -> bool:
    return _lib is not None


def _ptr_array(arrays: list[np.ndarray]) -> ctypes.Array:
    ptrs = (ctypes.c_void_p * len(arrays))()
    for i, a in enumerate(arrays):
        ptrs[i] = a.ctypes.data_as(ctypes.c_void_p).value
    return ptrs


# worker threads for whole-chunk encodes (the C side stays single-
# threaded below 1 MiB, where spawn cost would dominate); bounded so
# encode never crowds out the network/serve thread pools
ENCODE_THREADS = max(1, min(4, (os.cpu_count() or 2) // 2))


def apply_matrix(
    matrix: np.ndarray, parts: list[np.ndarray], threads: int | None = None,
    out: list[np.ndarray] | None = None,
) -> list[np.ndarray]:
    """out[i] = XOR_j matrix[i,j] * parts[j] via the SIMD kernel.

    ``out``: optional caller-owned destination rows (each contiguous
    uint8 of the part size) — the kernel writes parity in place, so hot
    paths can encode straight into a send buffer."""
    assert _lib is not None
    rows, k = matrix.shape
    assert k == len(parts)
    size = parts[0].shape[0] if parts else 0
    if out is None:
        out = [np.empty(size, dtype=np.uint8) for _ in range(rows)]
    else:
        assert len(out) == rows and all(
            o.flags.c_contiguous and o.dtype == np.uint8
            and o.shape[0] == size
            for o in out
        )
    if size == 0 or rows == 0:
        return out
    mat = np.ascontiguousarray(matrix, dtype=np.uint8)
    srcs = [np.ascontiguousarray(p, dtype=np.uint8) for p in parts]
    nthreads = ENCODE_THREADS if threads is None else threads
    if nthreads > 1 and hasattr(_lib, "lz_ec_encode_mt"):
        _lib.lz_ec_encode_mt(
            size, k, rows,
            mat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            _ptr_array(srcs),
            _ptr_array(out),
            nthreads,
        )
        return out
    _lib.lz_ec_encode(
        size, k, rows,
        mat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        _ptr_array(srcs),
        _ptr_array(out),
    )
    return out


def crc32(data: bytes | np.ndarray, crc: int = 0) -> int:
    assert _lib is not None
    arr = np.frombuffer(bytes(data), dtype=np.uint8) if isinstance(data, (bytes, bytearray)) else np.ascontiguousarray(data, dtype=np.uint8)
    return int(
        _lib.lz_crc32(
            crc, arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), arr.size
        )
    )


def stripe_helpers_available() -> bool:
    return _lib is not None and hasattr(_lib, "lz_stripe_scatter")


def stripe_scatter(
    data: np.ndarray, d: int, blocks_per_part: int,
    out: np.ndarray | None = None,
) -> np.ndarray:
    """(nbytes,) chunk bytes -> (d, part_len) zero-padded part streams
    in one contiguous buffer, via the GIL-free native kernel. ``out``
    lets hot paths reuse a staging buffer (a fresh 64 MiB allocation
    pays its page faults inside the copy)."""
    assert stripe_helpers_available()
    part_len = blocks_per_part * MFSBLOCKSIZE
    if out is None:
        out = np.empty((d, part_len), dtype=np.uint8)
    assert (
        out.flags.c_contiguous and out.dtype == np.uint8
        and out.shape == (d, part_len)
    )
    data = np.ascontiguousarray(data, dtype=np.uint8)
    _lib.lz_stripe_scatter(
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        data.shape[0], d, blocks_per_part,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    return out


def stripe_gather(
    parts: list[np.ndarray], nbytes: int, out: np.ndarray | None = None
) -> np.ndarray:
    """d part streams (each contiguous, long enough to cover its share
    of ``nbytes``) -> (nbytes,) chunk bytes, no intermediate stacking."""
    assert stripe_helpers_available()
    srcs = [np.ascontiguousarray(p, dtype=np.uint8) for p in parts]
    if out is None:
        out = np.empty(nbytes, dtype=np.uint8)
    assert out.flags.c_contiguous and out.shape[0] >= nbytes
    _lib.lz_stripe_gather(
        _ptr_array(srcs), len(srcs), nbytes,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    return out


def crc32_blocks(blocks: np.ndarray) -> np.ndarray:
    assert _lib is not None
    blocks = np.ascontiguousarray(blocks, dtype=np.uint8)
    n, bs = blocks.shape
    out = np.empty(n, dtype=np.uint32)
    _lib.lz_crc32_blocks(
        blocks.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n, bs, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
    )
    return out


class CppChunkEncoder(ChunkEncoder):
    """SIMD C++ backend (ISA-L-equivalent technique), byte-identical to
    the golden path."""

    name = "cpp"

    def __init__(self):
        if _lib is None:
            raise RuntimeError(
                "libec_native.so not built — run `make -C native`"
            )

    def encode(self, k, m, data_parts):
        if len(data_parts) != k:
            raise ValueError(f"expected {k} data parts, got {len(data_parts)}")
        nonzero = [i for i, p in enumerate(data_parts) if p is not None]
        if not nonzero:
            raise ValueError("at least one data part must be non-None")
        mat = gf256.encoding_matrix(k, m)
        mat = gf256.reduce_columns(mat, nonzero)
        parts = [np.asarray(data_parts[i], dtype=np.uint8) for i in nonzero]
        return apply_matrix(mat, parts)

    def encode_into(self, k, m, data_parts, out):
        if len(data_parts) != k:
            raise ValueError(f"expected {k} data parts, got {len(data_parts)}")
        mat = gf256.encoding_matrix(k, m)
        parts = [np.asarray(p, dtype=np.uint8) for p in data_parts]
        apply_matrix(mat, parts, out=list(out))

    def recover(self, k, m, parts, wanted):
        used, mat = gf256.recovery_selection(k, m, list(parts.keys()), wanted)
        nonzero_pos = [j for j, i in enumerate(used) if parts[i] is not None]
        if not nonzero_pos:
            raise ValueError("at least one available part must be non-None")
        mat = gf256.reduce_columns(mat, nonzero_pos)
        in_parts = [np.asarray(parts[used[j]], dtype=np.uint8) for j in nonzero_pos]
        out = apply_matrix(mat, in_parts)
        return {w: out[i] for i, w in enumerate(wanted)}

    def checksum(self, blocks):
        return crc32_blocks(np.ascontiguousarray(blocks))

    def encode_with_checksums(self, k, m, data, block_size=MFSBLOCKSIZE):
        n = data.shape[1]
        nb = n // block_size
        parity = np.stack(self.encode(k, m, list(data)))
        data_crcs = self.checksum(data.reshape(k * nb, block_size)).reshape(k, nb)
        parity_crcs = self.checksum(parity.reshape(m * nb, block_size)).reshape(m, nb)
        return parity, data_crcs, parity_crcs
