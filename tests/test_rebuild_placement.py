"""Where a rebuild puts a part when every surviving server already
holds one (master/server.py _replicate_part): an ec(3,2) chunk on four
servers, one lost. The three survivors can hold the five parts 2/2/1;
3/1/1 leaves a server whose loss takes the chunk, and the chunk
endangered for good (what tests/test_process_cluster.py
::test_sigkill_chunkserver_degraded_read then waited 20 s for in vain,
about one run in eight)."""

import asyncio
import collections
import random

import pytest

from lizardfs_tpu.utils import data_generator

from tests.test_cluster import EC_GOAL, Cluster


@pytest.mark.parametrize("lost_parts", [1, 2], ids=["one_part", "two_parts"])
@pytest.mark.parametrize("seed", [1, 2, 3, 4])
@pytest.mark.asyncio
async def test_rebuild_spreads_over_the_survivors(tmp_path, lost_parts, seed):
    """Whether the lost server held one part (one rebuild, which must
    not pick the survivor that holds two) or two (two rebuilds that
    pick at once, and must not pick the same survivor)."""
    cluster = Cluster(tmp_path, n_cs=4)
    await cluster.start(health_interval=0.1)
    try:
        c = await cluster.client()
        f = await c.create(1, "placed.bin")
        await c.setgoal(f.inode, EC_GOAL)
        payload = data_generator.generate(3, 3 * 65536 + 17).tobytes()
        await c.write_file(f.inode, payload)
        master = cluster.master
        chunk = master.meta.registry.chunk(
            master.meta.fs.file_node(f.inode).chunks[0])
        held = collections.Counter(cs for cs, _ in chunk.parts)
        assert sorted(held.values()) == [1, 1, 1, 2]
        gone = next(cs for cs, n in sorted(held.items()) if n == lost_parts)
        # the registry's choices are seeded: try several
        master.meta.registry._rng = random.Random(seed)
        port = master.meta.registry.servers[gone].port
        victim = next(cs for cs in cluster.chunkservers if cs.port == port)
        await victim.stop()
        cluster.chunkservers.remove(victim)
        for _ in range(200):
            await asyncio.sleep(0.05)
            if len(chunk.parts) == 5 and not master.rebuild.active:
                break
        held = collections.Counter(cs for cs, _ in chunk.parts)
        assert sorted(held.values()) == [1, 2, 2], held
        assert master.meta.registry.evaluate(chunk).is_safe
        c.cache.invalidate(f.inode)
        assert await c.read_file(f.inode) == payload
    finally:
        await cluster.stop()
