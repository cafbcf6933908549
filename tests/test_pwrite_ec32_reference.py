"""``Client.pwrite`` at $ec(3,2) in transfers that are no multiple of
the stripe, held to the plain reference (``benchmark/reference``: numpy
GF(2^8), the part-file layout, zlib CRC32; it imports nothing of the
program).

A stripe of $ec(3,2) is 3 x 64 KiB = 192 KiB and a transfer of 2 MiB is
10 2/3 of them, so consecutive calls start 0, 128 KiB and 64 KiB into a
stripe: one call in three rewrites whole stripes of new data, two read
the head stripe's live blocks back first. A 64 MiB chunk's 1,024 blocks
are no multiple of 3 either: its last stripe holds one block, data
part 0 and both parities 342 blocks, data parts 1 and 2 341. Each case
compares the bytes read back, the five part files on the chunkservers'
disks (data, both parities, the stored CRC words), a read with a data
part's server stopped, and what the read-modify-write branch counted.
"""

import os
import sys

import numpy as np
import pytest

from lizardfs_tpu.constants import MFSBLOCKSIZE, MFSCHUNKSIZE
from lizardfs_tpu.core import geometry
from lizardfs_tpu.runtime.metrics import WRITE_COUNTS, phase_delta

from tests.test_cluster import Cluster, EC_GOAL, XOR_GOAL
from tests.test_write_phases import _find_part_files

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import layout  # noqa: E402

K, M = 3, 2
MiB = 2 ** 20
STRIPE = K * MFSBLOCKSIZE
TRANSFER = 2 * MiB

# name -> (first offset, calls of TRANSFER bytes)
RUNS = {
    # j mod 3 = 0, 1, 2, 0, 1, 2, 0: every alignment at least twice
    "three_alignments": (0, 7),
    # the last two transfers of a chunk and the first of the next, in a
    # fresh file: the one before the boundary starts inside a stripe
    # and ends in the chunk's one-block last stripe
    "chunk_boundary": (60 * MiB, 3),
}


def expected_counts(first: int, calls: int) -> dict:
    """What the branch has to count for sequential transfers, the first
    at the start of a stripe of a fresh file, from the geometry alone."""
    want = dict.fromkeys(WRITE_COUNTS, 0)
    for off in range(first, first + calls * TRANSFER, TRANSFER):
        coff = off % MFSCHUNKSIZE
        head = coff % STRIPE  # live bytes of the head stripe
        region_start = coff - head
        region_end = min(-(-(coff + TRANSFER) // STRIPE) * STRIPE,
                         MFSCHUNKSIZE)
        want["payload_bytes"] += TRANSFER
        want["ec_payload_bytes"] += TRANSFER  # acknowledged, at $ec
        want["rmw_region_bytes"] += region_end - region_start
        if head:
            want["rmw_reads"] += 1
            want["rmw_read_bytes"] += head
    return want


def compare_stored(cluster, chunk_id: int, data: np.ndarray,
                   written_from: int, k: int = K, m: int = M) -> None:
    """The chunk's k + m part files against the reference's parts of
    ``data`` (the chunk's bytes). Blocks below ``written_from`` (a
    chunk offset, whole stripes) were never written: they read as
    zeros, and their CRC words are the store's own business."""
    files = _find_part_files(cluster, chunk_id)
    want_ids = {layout.ec_part_id(k, m, p) for p in range(k + m)}
    assert set(files) == want_ids
    assert len({os.path.dirname(os.path.dirname(f))
                for f in files.values()}) == k + m, "distinct servers"
    want_parts = layout.expected_parts(data, k, m, MFSBLOCKSIZE)
    live = layout.part_lengths(k, m, len(data), MFSBLOCKSIZE)
    first_slot = written_from // (k * MFSBLOCKSIZE)
    for part_id, path in files.items():
        p = geometry.ChunkPartType.from_id(part_id).part
        body, table = layout.read_part_file(path, MFSBLOCKSIZE)
        want = want_parts[p]
        # whole stripes are rewritten, so a part may run one zero block
        # past its live length, never past what the reference pads to
        assert live[p] <= len(body) <= len(want), (p, len(body), live[p])
        assert np.array_equal(body, want[:len(body)]), f"part {p} bytes"
        slots = -(-live[p] // MFSBLOCKSIZE)
        crcs = layout.block_crcs(want[:slots * MFSBLOCKSIZE], MFSBLOCKSIZE)
        assert table[first_slot:slots] == crcs[first_slot:slots], \
            f"part {p} stored CRC words"
        assert not body[:first_slot * MFSBLOCKSIZE].any()


def stored_blocks(cluster, chunk_id: int) -> dict:
    """part -> whole blocks its file holds on disk."""
    return {geometry.ChunkPartType.from_id(pid).part:
            (os.path.getsize(path) - layout.HEADER_BYTES) // MFSBLOCKSIZE
            for pid, path in _find_part_files(cluster, chunk_id).items()}


async def stop_holder_of(cluster, chunk_id: int, part: int,
                         k: int = K, m: int = M) -> None:
    part_id = layout.ec_part_id(k, m, part)
    victim = next(cs for cs in cluster.chunkservers
                  for cf in cs.store.all_parts()
                  if cf.chunk_id == chunk_id and cf.part_id == part_id)
    await victim.stop()
    cluster.chunkservers.remove(victim)


@pytest.mark.asyncio
@pytest.mark.parametrize("seed", [26, 2147493626])
@pytest.mark.parametrize("run", sorted(RUNS))
async def test_unaligned_stream_matches_the_reference(tmp_path, run, seed):
    first, calls = RUNS[run]
    end = first + calls * TRANSFER
    rng = np.random.default_rng([seed, calls])
    model = np.zeros(end, dtype=np.uint8)
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start(health_interval=30.0)  # no rebuild under the test
    try:
        c = await cluster.client()
        f = await c.create(1, f"{run}.bin")
        await c.setgoal(f.inode, EC_GOAL)
        before = c.write_phases.snapshot()
        counters = {n: c.op_counters.get(n, 0) for n in WRITE_COUNTS}
        for off in range(first, end, TRANSFER):
            buf = rng.integers(0, 256, TRANSFER, dtype=np.uint8)
            model[off:off + TRANSFER] = buf
            await c.pwrite(f.inode, off, buf.tobytes())
        d = phase_delta(c.write_phases.snapshot(), before)

        # (a) the bytes written read back
        c.cache.invalidate(f.inode)
        got = np.frombuffer(
            await c.read_file(f.inode, first, end - first), np.uint8)
        assert np.array_equal(got, model[first:])

        # (b) the part files on the chunkservers' disks
        spans = layout.chunk_spans(end, MFSCHUNKSIZE)
        infos = [await c.chunk_info(f.inode, ci) for ci in range(len(spans))]
        for ci, (a, b) in enumerate(spans):
            compare_stored(cluster, infos[ci].chunk_id, model[a:b],
                           max(first - a, 0))
        if run == "chunk_boundary":
            assert stored_blocks(cluster, infos[0].chunk_id) == {
                0: 342, 1: 341, 2: 341, 3: 342, 4: 342}

        # what the branch counted, beside the phase rows and in op_counters
        want = expected_counts(first, calls)
        assert {n: d[n] for n in WRITE_COUNTS} == want
        assert {n: c.op_counters.get(n, 0) - counters[n]
                for n in WRITE_COUNTS} == want
        assert d["reps"] == calls
        assert d["rmw_read_ms"] > 0 and d["rmw_patch_ms"] > 0

        # (c) what was written, with a data part's server stopped: in
        # the first chunk that part is one of the two a block shorter
        await stop_holder_of(cluster, infos[0].chunk_id, 1)
        c.cache.invalidate(f.inode)
        got = np.frombuffer(
            await c.read_file(f.inode, first, end - first), np.uint8)
        assert np.array_equal(got, model[first:])
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_last_transfer_of_a_chunk_at_xor3(tmp_path):
    """xor3 stripes three data parts too: the transfer that ends a
    chunk ends in the same one-block stripe, with the parity in part
    0."""
    first, end = 60 * MiB, 66 * MiB
    rng = np.random.default_rng(26)
    model = np.zeros(end, dtype=np.uint8)
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        f = await c.create(1, "xor3.bin")
        await c.setgoal(f.inode, XOR_GOAL)
        for off in range(first, end, TRANSFER):
            buf = rng.integers(0, 256, TRANSFER, dtype=np.uint8)
            model[off:off + TRANSFER] = buf
            await c.pwrite(f.inode, off, buf.tobytes())
        c.cache.invalidate(f.inode)
        got = np.frombuffer(
            await c.read_file(f.inode, first, end - first), np.uint8)
        assert np.array_equal(got, model[first:])
        info = await c.chunk_info(f.inode, 0)
        assert stored_blocks(cluster, info.chunk_id) == {
            0: 342, 1: 342, 2: 341, 3: 341}
    finally:
        await cluster.stop()
