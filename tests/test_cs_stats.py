"""Per-chunkserver health scores (chunkserver_stats.cc analog)."""

import asyncio

import pytest

from lizardfs_tpu.core.cs_stats import ChunkserverStats, GLOBAL_STATS

from tests.test_cluster import Cluster


def test_decay_and_repair():
    t = [0.0]
    stats = ChunkserverStats(clock=lambda: t[0])
    a = ("10.0.0.1", 9422)
    assert stats.score(a) == 1.0
    stats.record_failure(a)
    stats.record_failure(a)
    assert stats.score(a) == pytest.approx(0.25)
    # defects decay with a 30 s half-life
    t[0] = 30.0
    assert stats.score(a) == pytest.approx(0.5, rel=0.01)
    t[0] = 300.0
    assert stats.score(a) > 0.95
    # successes actively repair
    stats.record_failure(a)
    for _ in range(10):
        stats.record_success(a)
    assert stats.score(a) > 0.95
    # score never hits zero even for a disaster server
    for _ in range(100):
        stats.record_failure(a)
    assert stats.score(a) > 0


@pytest.mark.asyncio
async def test_flaky_chunkserver_demoted(tmp_path):
    """Reads route away from a replica whose server accumulated
    defects, without waiting for a failure on THIS read."""
    cluster = Cluster(tmp_path, n_cs=2)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "twocopy")
        await c.setgoal(f.inode, 2)
        payload = b"z" * (1 << 20)
        await c.write_file(f.inode, payload)

        loc = await c.chunk_info(f.inode, 0)
        addrs = [(pl.addr.host, pl.addr.port) for pl in loc.locations]
        assert len(addrs) == 2

        for cs in cluster.chunkservers:
            assert cs.data_server is not None, \
                "native data plane failed to start (see chunkserver log)"

        def served_bytes():
            return {
                cs.data_server.port: cs.data_server.stats()["bytes_read"]
                for cs in cluster.chunkservers
            }

        # mark the master's preferred (first-listed) replica flaky
        for _ in range(6):
            GLOBAL_STATS.record_failure(addrs[0])
        before = served_bytes()
        for _ in range(3):
            c.cache.invalidate(f.inode)
            assert await c.read_file(f.inode) == payload
        healthy_port = addrs[1][1]
        flaky_port = addrs[0][1]
        # a serve thread counts its bytes after the last send returns:
        # the reader can hold the answer before the counter moves, so
        # wait for the count itself (it cost a whole run its exit code
        # when a loaded host parked that thread in between)
        deadline = asyncio.get_running_loop().time() + 20.0
        while True:
            after = served_bytes()
            delta = {p: after[p] - before[p] for p in after}
            if (delta[healthy_port] >= 3 * len(payload)
                    or asyncio.get_running_loop().time() >= deadline):
                break
            await asyncio.sleep(0.02)
        assert delta[healthy_port] >= 3 * len(payload)
        assert delta[flaky_port] == 0
    finally:
        # don't leak demotion into other tests sharing the registry
        GLOBAL_STATS._defects.clear()
        await cluster.stop()
