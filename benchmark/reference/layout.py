"""How a file's bytes lie on the chunkservers, as the deployments state
it (upstream LizardFS: src/common/slice_traits.h, chunk_part_type.h,
src/chunkserver/chunk.h), written out for the benchmark's comparison.

  * a file is cut into chunks of ``chunk_bytes`` (64 MiB);
  * $ec(k,m): a chunk's 64 KiB blocks go round-robin over the k data
    parts: block i lies in data part i % k at slot i // k; the m parity
    parts k..k+m-1 are the Reed-Solomon parity of the k part streams,
    each padded with zeros to whole blocks;
  * xorN: the same round-robin over N data parts, which are parts
    1..N (block i in part 1 + i % N); part 0 is their XOR, block by
    block over the zero-padded streams;
  * N copies: N part files of the one id, each the chunk's bytes whole;
  * part p of slice type t has the id t * 64 + p, with
    t = 10 + 32 * (k - 2) + (m - 1) for ec(k, m), t = N for xorN and
    t = 0 (standard) for a copy, whose one part is part 0;
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
COPY_PART_ID = 0


def ec_part_id(k: int, m: int, part: int) -> int:
    return (10 + 32 * (k - 2) + (m - 1)) * 64 + part


def xor_part_id(n: int, part: int) -> int:
    return n * 64 + part


def part_ids(goal: dict) -> list[int]:
    """The id of every part file a chunk keeps under a configuration's
    goal (``{"k", "m"}``, ``{"xor": N}`` or ``{"copies": N}``), one a
    file: N copies are N files of the one id."""
    if "copies" in goal:
        return [COPY_PART_ID] * int(goal["copies"])
    if "xor" in goal:
        return [xor_part_id(int(goal["xor"]), p)
                for p in range(int(goal["xor"]) + 1)]
    k, m = int(goal["k"]), int(goal["m"])
    return [ec_part_id(k, m, p) for p in range(k + m)]


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


def goal_part_lengths(goal: dict, chunk_len: int, block: int) -> list[int]:
    """Live bytes of each part of one chunk under the goal, by part
    index: a xor parity part is as long as the longest data part, a
    copy holds the chunk whole."""
    if "copies" in goal:
        return [chunk_len]
    if "xor" in goal:
        data = part_lengths(int(goal["xor"]), 1, chunk_len, block)[:-1]
        return [max(data)] + data
    return part_lengths(int(goal["k"]), int(goal["m"]), chunk_len, block)


def data_streams(data: np.ndarray, d: int, block: int) -> list[np.ndarray]:
    """The d data part streams of one chunk, padded to whole blocks."""
    nblocks = -(-len(data) // block)
    slots = -(-nblocks // d)
    grid = np.zeros(slots * d * block, dtype=np.uint8)
    grid[:len(data)] = data
    grid = grid.reshape(slots, d, block)
    return [np.ascontiguousarray(grid[:, p, :]).reshape(-1) for p in range(d)]


def expected_parts(data: np.ndarray, k: int, m: int,
                   block: int) -> list[np.ndarray]:
    """The k + m part streams of one chunk, padded to whole blocks."""
    parts = data_streams(data, k, block)
    return parts + gf256.encode(k, m, parts)


def xor_parts(data: np.ndarray, n: int, block: int) -> list[np.ndarray]:
    """The N + 1 part streams of one xorN chunk: the parity first."""
    parts = data_streams(data, n, block)
    return [np.bitwise_xor.reduce(np.stack(parts))] + parts


def goal_parts(goal: dict, data: np.ndarray, block: int) -> list[np.ndarray]:
    """Every part stream of one chunk under the goal, by part index."""
    if "copies" in goal:
        return data_streams(data, 1, block)
    if "xor" in goal:
        return xor_parts(data, int(goal["xor"]), block)
    return expected_parts(data, int(goal["k"]), int(goal["m"]), block)


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
