"""Direct-thread native read path (FUSE latency path): NativeReadPool
reads bytes through liblizardfs_client.so without the asyncio loop."""

import asyncio
import os
import time

import pytest

from lizardfs_tpu.client import native_client

from tests.test_cluster import Cluster, EC_GOAL

pytestmark = pytest.mark.asyncio


async def test_native_pool_reads_and_fallback(tmp_path):
    if not native_client.available():
        pytest.skip("liblizardfs_client.so not built")
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "hot.dat")
        blob = os.urandom(300_000)
        await c.write_file(f.inode, blob)

        pool = native_client.NativeReadPool(
            lambda: ("127.0.0.1", cluster.master.port)
        )
        try:
            # pool.read is a plain blocking call made from any thread
            got = await asyncio.to_thread(pool.read, f.inode, 0, 100_000)
            assert got == blob[:100_000]
            got = await asyncio.to_thread(pool.read, f.inode, 123_456, 4096)
            assert got == blob[123_456:127_552]
            # read past EOF truncates
            got = await asyncio.to_thread(
                pool.read, f.inode, len(blob) - 10, 4096
            )
            assert got == blob[-10:]
            # missing inode -> None (caller falls back to planner path)
            assert await asyncio.to_thread(pool.read, 999999, 0, 16) is None

            # degraded striped file -> None, planner path still serves it
            e = await c.create(1, "striped.dat")
            await c.setgoal(e.inode, EC_GOAL)
            sblob = os.urandom(200_000)
            await c.write_file(e.inode, sblob)
            locs = await c.chunk_info(e.inode, 0)
            kill_port = locs.locations[0].addr.port
            for cs in cluster.chunkservers:
                if cs.port == kill_port:
                    await cs.stop()
            nat = await asyncio.to_thread(pool.read, e.inode, 0, 1000)
            assert nat is None or nat == sblob[:1000]
            c.cache.invalidate(e.inode)
            assert (await c.read_file(e.inode, 0, 1000)) == sblob[:1000]
        finally:
            await asyncio.to_thread(pool.close)
    finally:
        await cluster.stop()


async def test_native_pool_latency_beats_loop_path(tmp_path):
    """The point of the pool: a small read through the C path costs
    less than the asyncio planner path (loop hop + python framing)."""
    if not native_client.available():
        pytest.skip("liblizardfs_client.so not built")
    cluster = Cluster(tmp_path, n_cs=2)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "lat.dat")
        blob = os.urandom(1 << 20)
        await c.write_file(f.inode, blob)
        pool = native_client.NativeReadPool(
            lambda: ("127.0.0.1", cluster.master.port)
        )
        try:
            def native_once(off):
                return pool.read(f.inode, off, 4096)

            # warm both paths
            assert (await asyncio.to_thread(native_once, 0)) == blob[:4096]
            await c.read_file(f.inode, 0, 4096)

            n = 50
            t0 = time.perf_counter()
            for i in range(n):
                await asyncio.to_thread(native_once, (i * 8192) % 900_000)
            native_s = time.perf_counter() - t0

            t0 = time.perf_counter()
            for i in range(n):
                c.cache.invalidate(f.inode)
                await c.read_file(f.inode, (i * 8192) % 900_000, 4096)
            loop_s = time.perf_counter() - t0
            # generous bound: just assert the native path isn't slower
            assert native_s < loop_s * 1.5, (native_s, loop_s)
        finally:
            await asyncio.to_thread(pool.close)
    finally:
        await cluster.stop()
