"""Four goals side by side through one client and one device encoder:
two copies, ``$xor3``, ``$ec(3,2)`` and ``$ec(8,4)``, a directory each,
as the ``mixed-goals-13cs`` deployment keeps them. Each goal's file is
written as a mount writes (sequential ``pwrite`` calls that are not
whole stripes, so ``$xor3`` and ``$ec(3,2)`` read stripes back) and as
the S3 gateway writes (one ``write_file``, whose striped chunk takes the
windowed write: ``xor_parity_into`` for ``$xor3``); then every chunk's
part files on the chunkservers' disks are compared with the benchmark's
plain reference, and the write path's counts are read per family."""

import os
import sys
import types

import numpy as np
import pytest

from lizardfs_tpu.client.client import Client
from lizardfs_tpu.constants import MFSBLOCKSIZE, MFSCHUNKSIZE
from lizardfs_tpu.core import native_io
from lizardfs_tpu.runtime.metrics import phase_delta
from tests.test_cluster import (
    EC_GOAL, STD2_GOAL, WIDE_EC_GOAL, XOR_GOAL, Cluster,
)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import checks  # noqa: E402
from reference import layout  # noqa: E402

MiB = 2 ** 20
N_CS = 13
# (goal id, the goal's form as a configuration states it, family)
GOALS = [(STD2_GOAL, {"copies": 2}, "copies"),
         (XOR_GOAL, {"xor": 3}, "xor"),
         (EC_GOAL, {"k": 3, "m": 2}, "ec"),
         (WIDE_EC_GOAL, {"k": 8, "m": 4}, "ec")]
FAMILIES = ("copies", "xor", "ec")
FAMILY_COUNTS = ("copies_payload_bytes", "xor_payload_bytes",
                 "ec_payload_bytes", "chain_parts")
TRANSFER = MiB            # five calls: 0, 1024, 2048, ... KiB into a file
STREAM = 5 * MiB
PUT = 9 * MiB + 77        # one chunk through the window (8 MiB at least)

NEEDS_NATIVE = pytest.mark.skipif(
    not native_io.parts_scatter_available(),
    reason="the windowed write needs the native library")


async def chunks_compare(cluster, client, inode: int, data: np.ndarray,
                         goal: dict) -> None:
    """Every chunk's part files against the reference: the places the
    goal keeps on distinct servers, every byte and CRC word."""
    dirs = [str(cluster.tmp_path / f"cs{i}") for i in range(N_CS)]
    for ci, (a, b) in enumerate(layout.chunk_spans(len(data), MFSCHUNKSIZE)):
        info = await client.chunk_info(inode, ci)
        ok, files = checks.stored_parts(info, goal, 0, dirs)
        assert ok, (goal, info.locations)
        per_part = checks.check_parts(data[a:b], goal, MFSBLOCKSIZE, files)
        assert len(per_part) == len(layout.part_ids(goal))
        assert all(b == 0 and c == 0 for _p, b, c in per_part), per_part


@NEEDS_NATIVE
@pytest.mark.asyncio
async def test_four_goals_through_one_client_and_one_encoder(tmp_path):
    from lizardfs_tpu.core.encoder import TpuChunkEncoder

    rng = np.random.default_rng(2147483842)
    cluster = Cluster(tmp_path, n_cs=N_CS)
    await cluster.start(health_interval=30.0)  # no rebuild under the test
    try:
        c = await cluster.client()
        c.encoder = TpuChunkEncoder(force_cpu=True)
        for gid, goal, family in GOALS:
            d = await c.mkdir(1, f"goal{gid}")
            await c.setgoal(d.inode, gid)
            stream = await c.create(d.inode, "stream.bin")
            put = await c.create(d.inode, "put.bin")
            s_data = rng.integers(0, 256, STREAM, dtype=np.uint8)
            p_data = rng.integers(0, 256, PUT, dtype=np.uint8)

            before = c.write_phases.snapshot()
            for off in range(0, STREAM, TRANSFER):
                await c.pwrite(stream.inode, off,
                               s_data[off:off + TRANSFER].tobytes())
            await c.write_file(put.inode, p_data)
            d_rows = phase_delta(c.write_phases.snapshot(), before)

            # what the chunkservers acknowledged, under the goal's family
            # alone; a copy goal's part goes through the relay chain of
            # its two holders, once a pwrite and once the PUT's chunk
            want = {f + "_payload_bytes": 0 for f in FAMILIES}
            want[family + "_payload_bytes"] = STREAM + PUT
            want["chain_parts"] = STREAM // TRANSFER + 1 \
                if family == "copies" else 0
            assert {n: d_rows[n] for n in want} == want, gid
            # xor's parity crossed the device boundary under its own
            # rows, in the pwrites' encode and in the window's segments
            xor_rows = [d_rows[f"xor_{r}_ms"] for r in (
                "boundary", "dev_stage", "dev_put", "dev_run", "dev_fetch")]
            if family == "xor":
                assert all(ms > 0 for ms in xor_rows)
                assert d_rows["boundary_ms"] == 0
                assert d_rows["window_chunks"] == 1
            else:
                assert not any(xor_rows)
            if family == "ec":
                assert d_rows["boundary_ms"] > 0
                assert d_rows["window_chunks"] == 1
            # a call that starts inside a stripe of live data reads it
            # back: at a stripe of 3 blocks (192 KiB) three of the five
            # calls; at $ec(8,4)'s 512 KiB, and for copies, none
            width = goal.get("xor") or goal.get("k")
            stripe = width * MFSBLOCKSIZE if width else 1
            assert d_rows["rmw_reads"] == sum(
                1 for off in range(0, STREAM, TRANSFER) if off % stripe)

            await chunks_compare(cluster, c, stream.inode, s_data, goal)
            await chunks_compare(cluster, c, put.inode, p_data, goal)
            c.cache.invalidate(stream.inode)
            got = await c.read_file(stream.inode, 0, STREAM)
            assert np.frombuffer(got, np.uint8).tobytes() == s_data.tobytes()
    finally:
        await cluster.stop()


@pytest.mark.parametrize("goal,family,chained", [
    ({"copies": 2}, "copies", 1),
    ({"copies": 3}, "copies", 1),
    ({"xor": 3}, "xor", 0),
    ({"xor": 9}, "xor", 0),
    ({"k": 3, "m": 2}, "ec", 0),
    ({"k": 8, "m": 4}, "ec", 0)], ids=str)
def test_an_acknowledged_chunk_counts_under_its_goals_family(goal, family,
                                                           chained):
    """The grant's places decide the family: N copies are N holders of
    part 0 (one part through a relay chain), xorN and $ec(k,m) one
    holder a part. Counted beside the phase rows and in op_counters."""
    c = Client("127.0.0.1", 1)
    locs = [types.SimpleNamespace(part_id=pid)
            for pid in layout.part_ids(goal)]
    c._count_acked(locs, 123457)
    c._count_acked(locs, 3)
    want = dict.fromkeys(FAMILY_COUNTS, 0)
    want[family + "_payload_bytes"] = 123460
    want["chain_parts"] = 2 * chained
    snap = c.write_phases.snapshot()
    assert {n: snap[n] for n in FAMILY_COUNTS} == want
    assert {n: c.op_counters.get(n, 0) for n in FAMILY_COUNTS} == want
