"""Positional writes (read-modify-write), cache, readahead."""

import asyncio

import numpy as np
import pytest

from lizardfs_tpu.client.cache import BlockCache, ReadaheadAdviser
from lizardfs_tpu.constants import MFSBLOCKSIZE
from lizardfs_tpu.core import geometry, native_io
from lizardfs_tpu.runtime import faults
from lizardfs_tpu.utils import data_generator, striping

from tests.test_cluster import Cluster, EC_GOAL, WIDE_EC_GOAL, XOR_GOAL
from tests.test_write_phases import _find_part_files, _read_part


def test_block_cache_lru_and_invalidate():
    c = BlockCache(max_bytes=3 * 10)
    c.put(1, 0, 0, b"x" * 10)
    c.put(1, 0, 1, b"y" * 10)
    c.put(1, 1, 0, b"z" * 10)
    assert c.get(1, 0, 0) == b"x" * 10
    c.put(2, 0, 0, b"w" * 10)  # evicts LRU (1,0,1)
    assert c.get(1, 0, 1) is None
    c.invalidate(1, 1)
    assert c.get(1, 1, 0) is None
    assert c.get(1, 0, 0) is not None
    c.invalidate(1)
    assert c.get(1, 0, 0) is None


def test_readahead_adviser_grows_and_resets():
    a = ReadaheadAdviser()
    assert a.advise(0, 100) == 0  # first access: no window
    w1 = a.advise(100, 100)  # sequential: window appears
    assert w1 > 0
    w2 = a.advise(200, 100)
    assert w2 >= w1
    assert a.advise(10_000_000, 100) == 0  # seek resets


@pytest.mark.parametrize("goal", [2, EC_GOAL, XOR_GOAL])
@pytest.mark.asyncio
async def test_pwrite_random_offsets(tmp_path, goal):
    """Shadow-model test: random pwrites vs a local bytearray."""
    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "rw.bin")
        await c.setgoal(f.inode, goal)
        size = 6 * MFSBLOCKSIZE + 1234
        base = data_generator.generate(0, size).tobytes()
        await c.write_file(f.inode, base)
        model = bytearray(base)

        rng = np.random.default_rng(42)
        for i in range(8):
            off = int(rng.integers(0, size - 1))
            ln = int(rng.integers(1, min(size - off, 3 * MFSBLOCKSIZE)))
            patch = data_generator.generate(10_000 + i, ln).tobytes()
            await c.pwrite(f.inode, off, patch)
            model[off : off + ln] = patch
            back = await c.read_file(f.inode)
            assert back == bytes(model), f"mismatch after patch {i} at {off}+{ln}"
    finally:
        await cluster.stop()


@pytest.mark.parametrize("mode", ["scatter", "fallback", "faults"])
@pytest.mark.parametrize("goal", [WIDE_EC_GOAL, EC_GOAL, XOR_GOAL])
@pytest.mark.asyncio
async def test_striped_pwrite_fan_out(tmp_path, monkeypatch, goal, mode):
    """A striped pwrite at a non-zero, stripe-unaligned offset (so the
    RMW read-back runs) sends its region's parts as ONE pooled scatter
    exchange; with the exchange made to fail the per-part sends serve;
    with fault rules armed the exchange is not tried. In every case
    the file and the part files on disk, parity included, are the
    golden codec's."""
    if not native_io.parts_scatter_available():
        pytest.skip("native parts scatter not built")
    cluster = Cluster(tmp_path, n_cs=12 if goal == WIDE_EC_GOAL else 6)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        f = await c.create(1, "fan.bin")
        await c.setgoal(f.inode, goal)
        d = {WIDE_EC_GOAL: 8, EC_GOAL: 3, XOR_GOAL: 3}[goal]
        stripe = d * MFSBLOCKSIZE
        size = 3 * stripe + 4321
        model = bytearray(data_generator.generate(5, size).tobytes())
        await c.write_file(f.inode, bytes(model))
        if mode == "fallback":
            def boom(*a, **k):
                raise native_io.NativeIOError(5, "injected scatter failure")

            monkeypatch.setattr(
                native_io, "write_parts_scatter_blocking", boom)
        elif mode == "faults":
            # a rule that never fires: armed is what stands the
            # uninstrumentable native exchange down
            faults.arm("chunkserver:disk_pwrite error,after=1000000")
        before = dict(c.op_counters)
        off = stripe // 2 + 777  # inside stripe 0, ends inside stripe 1
        patch = data_generator.generate(6, stripe).tobytes()
        await c.pwrite(f.inode, off, patch)
        model[off : off + len(patch)] = patch

        def moved(name):
            return c.op_counters.get(name, 0) - before.get(name, 0)

        assert moved("parts_scatter_write") == (mode == "scatter")
        assert moved("parts_scatter_fallback") == (mode == "fallback")
        c.cache.invalidate(f.inode)
        assert await c.read_file(f.inode) == bytes(model)
        info = await c.chunk_info(f.inode, 0)
        slice_type = geometry.ChunkPartType.from_id(
            info.locations[0].part_id).type
        golden = striping.split_chunk(
            np.frombuffer(bytes(model), dtype=np.uint8), slice_type)
        stored = _find_part_files(cluster, info.chunk_id)
        assert len(stored) == slice_type.expected_parts
        for part_id, path in stored.items():
            part = geometry.ChunkPartType.from_id(part_id).part
            data = np.frombuffer(_read_part(path)[0], dtype=np.uint8)
            assert len(data) >= striping.part_length(slice_type, part, size)
            assert (data == golden[part][: len(data)]).all(), part
    finally:
        faults.clear()
        await cluster.stop()


@pytest.mark.asyncio
async def test_pwrite_extends_file(tmp_path):
    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "ext.bin")
        await c.setgoal(f.inode, EC_GOAL)
        await c.write_file(f.inode, b"head")
        # write past EOF: hole of zeros in between
        await c.pwrite(f.inode, 2 * MFSBLOCKSIZE + 7, b"tail")
        attr = await c.getattr(f.inode)
        assert attr.length == 2 * MFSBLOCKSIZE + 7 + 4
        back = await c.read_file(f.inode)
        assert back[:4] == b"head"
        assert back[4 : 2 * MFSBLOCKSIZE + 7] == b"\0" * (2 * MFSBLOCKSIZE + 3)
        assert back[-4:] == b"tail"
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_read_cache_serves_repeat_reads(tmp_path):
    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "cache.bin")
        await c.setgoal(f.inode, EC_GOAL)
        payload = data_generator.generate(1, 4 * MFSBLOCKSIZE).tobytes()
        await c.write_file(f.inode, payload)
        a = await c.read_file(f.inode)
        hits0 = c.cache.hits
        b = await c.read_file(f.inode)
        assert b == payload == a
        assert c.cache.hits > hits0  # second read came from cache
        # write invalidates
        await c.pwrite(f.inode, 0, b"XY")
        back = await c.read_file(f.inode)
        assert back[:2] == b"XY" and back[2:] == payload[2:]
    finally:
        await cluster.stop()
