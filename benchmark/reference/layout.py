"""How a file's bytes lie on the chunkservers, as the deployments state
it (upstream LizardFS: src/common/slice_traits.h, chunk_part_type.h,
src/chunkserver/chunk.h), written out for the benchmark's comparison.

  * a file is cut into chunks of ``chunk_bytes`` (64 MiB);
  * a chunk's 64 KiB blocks go round-robin over the k data parts: block
    i lies in data part i % k at slot i // k; the m parity parts are the
    Reed-Solomon parity of the k part streams, each padded with zeros
    to whole blocks;
  * part p of slice type t has the id t * 64 + p, with
    t = 10 + 32 * (k - 2) + (m - 1) for ec(k, m);
  * a part is one file ``chunk_<id:016X>_P<part:08X>_<version:08X>.liz``:
    a 1 KiB signature, a 4 KiB table of big-endian CRC32 words, one per
    64 KiB block, then the blocks.

numpy and zlib only; imports nothing of the program under test.
"""

from __future__ import annotations

import glob
import os
import zlib

import numpy as np

from . import gf256

HEADER_BYTES = 1024 + 4096


def ec_part_id(k: int, m: int, part: int) -> int:
    return (10 + 32 * (k - 2) + (m - 1)) * 64 + part


def chunk_spans(length: int, chunk_bytes: int) -> list[tuple[int, int]]:
    return [(a, min(a + chunk_bytes, length))
            for a in range(0, length, chunk_bytes)]


def part_lengths(k: int, m: int, chunk_len: int, block: int) -> list[int]:
    """Live bytes of each of the k + m parts of one chunk."""
    nblocks = -(-chunk_len // block)
    out = []
    for p in range(k):
        mine = list(range(p, nblocks, k))
        if not mine:
            out.append(0)
            continue
        last = mine[-1]
        tail = chunk_len - last * block if last == nblocks - 1 else block
        out.append((len(mine) - 1) * block + tail)
    return out + [max(out)] * m


def expected_parts(data: np.ndarray, k: int, m: int,
                   block: int) -> list[np.ndarray]:
    """The k + m part streams of one chunk, padded to whole blocks."""
    nblocks = -(-len(data) // block)
    slots = -(-nblocks // k)
    grid = np.zeros(slots * k * block, dtype=np.uint8)
    grid[:len(data)] = data
    grid = grid.reshape(slots, k, block)
    parts = [np.ascontiguousarray(grid[:, p, :]).reshape(-1) for p in range(k)]
    return parts + gf256.encode(k, m, parts)


def block_crcs(stream: np.ndarray, block: int) -> list[int]:
    return [zlib.crc32(stream[a:a + block].tobytes())
            for a in range(0, len(stream), block)]


def find_part_files(cs_dirs: list[str], chunk_id: int,
                    part_id: int) -> list[tuple[int, str]]:
    """(index of the chunkserver directory, path) of every copy: a
    part lies in the subfolder named by the low byte of its chunk id."""
    name = f"chunk_{chunk_id:016X}_P{part_id:08X}_*.liz"
    out = []
    for i, d in enumerate(cs_dirs):
        out.extend((i, p) for p in glob.glob(
            os.path.join(d, f"{chunk_id & 0xFF:02X}", name)))
    return out


def find_chunk_files(cs_dirs: list[str], chunk_id: int) -> list[tuple[int, str]]:
    """(part id, path) of every part file of the chunk, whatever its
    slice type and version."""
    out = []
    for d in cs_dirs:
        for p in glob.glob(os.path.join(
                d, f"{chunk_id & 0xFF:02X}", f"chunk_{chunk_id:016X}_P*.liz")):
            out.append((int(os.path.basename(p).split("_")[2][1:], 16), p))
    return out


def read_part_file(path: str, block: int) -> tuple[np.ndarray, list[int]]:
    """(stored bytes, stored CRC words of the blocks they cover)."""
    raw = np.fromfile(path, dtype=np.uint8)
    body = raw[HEADER_BYTES:]
    nblocks = -(-len(body) // block)
    table = raw[1024:1024 + 4 * nblocks].view(">u4")
    return body, [int(v) for v in table]
