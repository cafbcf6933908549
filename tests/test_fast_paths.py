"""Direct tests for the whole-stripe native fast paths.

Round-3 shipped three fast paths that were only exercised incidentally
(an EC read had to hit exact alignment preconditions): the native
stripe scatter/gather kernels, the one-call multi-part gather read
(`lz_read_parts_gather`), and its abort path. These tests pin each
directly — a silent precondition miss now fails a test instead of
quietly forfeiting the 3x read win.

Reference analogs: the de-interleave lives in ReadPlan post-process
closures (reference: src/common/read_plan.h); the abort semantics
mirror the mount's read-task cancellation (src/mount/readdata.cc).
"""

import asyncio
import contextlib
import socket as socket_mod
import struct
import time as _time
import zlib

import numpy as np
import pytest

from lizardfs_tpu.constants import MFSBLOCKSIZE, MFSCHUNKSIZE
from lizardfs_tpu.core import native, native_io
from lizardfs_tpu.proto import framing, messages as m
from lizardfs_tpu.proto import status as st
from lizardfs_tpu.runtime import accounting
from lizardfs_tpu.runtime.metrics import phase_delta
from lizardfs_tpu.utils import data_generator, striping

from tests.test_cluster import EC_GOAL, Cluster
from tests.test_write_phases import _find_part_files, _read_part

pytestmark = pytest.mark.asyncio

B = MFSBLOCKSIZE


# --- (a) scatter/gather vs the numpy fallback, odd shapes -------------------

def _numpy_scatter(data: np.ndarray, d: int) -> np.ndarray:
    """The pure-numpy layout contract (striping.py fallback)."""
    nbytes = data.shape[0]
    nblocks = -(-nbytes // B)
    bpp = -(-nblocks // d)
    full = np.zeros(d * bpp * B, dtype=np.uint8)
    full[:nbytes] = data
    grid = full.reshape(bpp, d, B)
    return np.ascontiguousarray(grid.transpose(1, 0, 2)).reshape(d, bpp * B)


ODD_SHAPES = [
    # (d, nbytes) covering: trailing partial block, nblocks < d,
    # nblocks % d != 0, single block, exact multiples
    (3, 7 * B + 4242),       # partial tail, nblocks % d != 0
    (8, 3 * B),              # nblocks < d
    (5, 5 * B + 1),          # partial tail lands in part 0 slot 1
    (2, B - 17),             # single partial block
    (4, 16 * B),             # exact grid
    (3, 2 * B + B // 2),     # nblocks % d == 0 after pad
]


@pytest.mark.parametrize("d,nbytes", ODD_SHAPES)
def test_native_scatter_matches_numpy(d, nbytes):
    if not native.stripe_helpers_available():
        pytest.skip("native stripe helpers not built")
    data = np.frombuffer(
        data_generator.generate(d, nbytes).tobytes(), dtype=np.uint8
    )
    want = _numpy_scatter(data, d)
    got = native.stripe_scatter(data, d, want.shape[1] // B)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("d,nbytes", ODD_SHAPES)
def test_native_gather_matches_numpy(d, nbytes):
    if not native.stripe_helpers_available():
        pytest.skip("native stripe helpers not built")
    data = np.frombuffer(
        data_generator.generate(d + 100, nbytes).tobytes(), dtype=np.uint8
    )
    parts = _numpy_scatter(data, d)
    out = np.full(nbytes, 0xEE, dtype=np.uint8)
    native.stripe_gather(list(parts), nbytes, out=out)
    np.testing.assert_array_equal(out, data)


@pytest.mark.parametrize("d,nbytes", ODD_SHAPES)
def test_padded_data_parts_native_vs_fallback(d, nbytes, monkeypatch):
    """The public entry point must produce identical parts with and
    without the native kernel (the fallback is the spec)."""
    data = np.frombuffer(
        data_generator.generate(2 * d, nbytes).tobytes(), dtype=np.uint8
    )
    native_parts, plen_n = striping.padded_data_parts(data, d)
    monkeypatch.setattr(native, "stripe_helpers_available", lambda: False)
    numpy_parts, plen_f = striping.padded_data_parts(data, d)
    assert plen_n == plen_f
    for a, b in zip(native_parts, numpy_parts):
        np.testing.assert_array_equal(a, b)


# --- (b) whole-stripe gather engagement + fallback --------------------------

async def _write_aligned_ec_file(cluster, c, nbytes):
    f = await c.create(1, "stripe.bin")
    await c.setgoal(f.inode, EC_GOAL)  # ec(3,2)
    payload = data_generator.generate(3, nbytes).tobytes()
    await c.write_file(f.inode, payload)
    return f, payload


async def _read_into(c, inode, nbytes) -> bytes:
    back = np.zeros(nbytes, dtype=np.uint8)
    assert await c.read_file_into(inode, 0, back) == nbytes
    return back.tobytes()


async def _read_sized(c, inode, nbytes) -> bytes:
    return await c.read_file(inode, 0, nbytes)


# the two doors to the gather: a caller's buffer (read_file_into, a
# whole-file read_file) and the buffer _read_chunk_range makes for a
# sized bulk read inside one chunk (the S3 gateway's GET)
ENTRIES = pytest.mark.parametrize(
    "read", [_read_into, _read_sized], ids=["into", "sized"])


@ENTRIES
async def test_stripe_gather_fast_path_engages(tmp_path, read):
    """A slot-aligned bulk EC read must take the one-call native gather
    (counter proves it) and return the right bytes."""
    if not native_io.parts_gather_available():
        pytest.skip("native parts gather not built")
    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()
        # 6 MiB: 96 blocks, d=3 -> 32 whole slots, bulk (>= 4 MiB)
        f, payload = await _write_aligned_ec_file(cluster, c, 6 * 2**20)
        assert await read(c, f.inode, len(payload)) == payload
        assert c.op_counters.get("stripe_gather_fast", 0) >= 1, \
            "fast-path precondition silently missed"
        assert not c.op_counters.get("stripe_gather_fallback")
    finally:
        await cluster.stop()


@ENTRIES
async def test_stripe_gather_failure_falls_back_to_waves(
        tmp_path, monkeypatch, read):
    """A native gather failure must degrade to the wave executor and
    still return correct bytes (counter proves the degrade happened)."""
    if not native_io.parts_gather_available():
        pytest.skip("native parts gather not built")
    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()
        f, payload = await _write_aligned_ec_file(cluster, c, 6 * 2**20)

        def boom(*a, **k):
            raise native_io.NativeIOError(5, "injected gather failure")

        monkeypatch.setattr(native_io, "read_parts_gather_blocking", boom)
        assert await read(c, f.inode, len(payload)) == payload
        assert c.op_counters.get("stripe_gather_fallback", 0) >= 1
    finally:
        await cluster.stop()


@ENTRIES
async def test_stripe_gather_cs_death_still_reads(tmp_path, read):
    """With a data-part holder dead, the fast-path precondition fails
    (part missing) and the wave executor recovers the bytes."""
    if not native_io.parts_gather_available():
        pytest.skip("native parts gather not built")
    cluster = Cluster(tmp_path)
    await cluster.start(health_interval=30.0)  # no repair: raw recovery
    try:
        c = await cluster.client()
        f, payload = await _write_aligned_ec_file(cluster, c, 6 * 2**20)
        chunk = next(iter(cluster.master.meta.registry.chunks.values()))
        data_holder = next(cs for cs, p in sorted(chunk.parts) if p < 3)
        victim = next(
            s for s in cluster.chunkservers
            if s.port == cluster.master.meta.registry.servers[data_holder].port
        )
        await victim.stop()
        await asyncio.sleep(0.1)
        assert await read(c, f.inode, len(payload)) == payload
    finally:
        await cluster.stop()


# --- (c) abort path: no buffer writes after the caller resumes --------------

@contextlib.asynccontextmanager
async def _stalled_server():
    """A server that accepts, reads the request, and stalls until
    teardown (3.12's Server.wait_closed waits for handlers — an
    unconditional sleep here would hang the test's own cleanup).
    Yields its port and the event set once a request has been read."""
    stalled = asyncio.Event()
    teardown = asyncio.Event()

    async def stall_handler(reader, writer):
        try:
            await reader.read(4096)
            stalled.set()
            await teardown.wait()
        except (ConnectionError, asyncio.CancelledError):
            pass
        finally:
            writer.close()

    server = await asyncio.start_server(stall_handler, "127.0.0.1", 0)
    try:
        yield server.sockets[0].getsockname()[1], stalled
    finally:
        teardown.set()
        server.close()
        await server.wait_closed()


async def test_abort_parts_gather_quiesces_buffer(tmp_path):
    """abort_parts_gather must unblock the executor thread promptly,
    and once the caller observes completion NOTHING may touch the
    destination buffer again (the caller immediately reuses it)."""
    if not native_io.parts_gather_available():
        pytest.skip("native parts gather not built")
    async with _stalled_server() as (port, stalled):
        region_blocks = 6
        out = np.zeros(region_blocks * B, dtype=np.uint8)
        cell: dict = {}
        fut = asyncio.get_running_loop().run_in_executor(
            native_io.EXECUTOR,
            lambda: native_io.read_parts_gather_blocking(
                [("127.0.0.1", port)] * 3, 42, 1, [1, 2, 3], 0,
                region_blocks, out, cell,
            ),
        )
        await asyncio.wait_for(stalled.wait(), 10.0)
        t0 = asyncio.get_running_loop().time()
        native_io.abort_parts_gather(cell)
        with pytest.raises((native_io.NativeIOError, OSError)):
            await asyncio.wait_for(fut, 10.0)
        abort_latency = asyncio.get_running_loop().time() - t0
        assert abort_latency < 5.0, "abort did not unblock the thread"
        # the caller now owns the buffer again: reuse it and prove no
        # late writer clobbers it
        sentinel = np.frombuffer(
            data_generator.generate(99, out.nbytes).tobytes(), dtype=np.uint8
        )
        out[:] = sentinel
        await asyncio.sleep(0.3)
        np.testing.assert_array_equal(out, sentinel)


async def test_cancelled_sized_read_joins_its_gather(tmp_path, monkeypatch):
    """A sized bulk read lands in a buffer ``_read_chunk_range`` made
    for it: cancelled inside the gather, the native thread has left the
    call before the caller sees the cancel, nothing writes to that
    buffer afterwards, and the client reads on."""
    if not native_io.parts_gather_available():
        pytest.skip("native parts gather not built")
    async with _stalled_server() as (port, stalled):
        cluster = Cluster(tmp_path)
        await cluster.start()
        try:
            c = await cluster.client()
            f, payload = await _write_aligned_ec_file(cluster, c, 6 * 2**20)
            gather = native_io.read_parts_gather_blocking
            seen = {"entered": 0, "left": 0}

            def gather_from_a_stalled_holder(addrs, *args):
                # runs on the native-io worker thread
                seen["entered"] += 1
                seen["out"] = args[-2]
                try:
                    gather([("127.0.0.1", port)] * len(addrs), *args)
                finally:
                    seen["left"] += 1

            monkeypatch.setattr(native_io, "read_parts_gather_blocking",
                                gather_from_a_stalled_holder)
            read = asyncio.ensure_future(c.read_file(f.inode, 0, len(payload)))
            await asyncio.wait_for(stalled.wait(), 10.0)
            t0 = _time.monotonic()
            read.cancel()
            with pytest.raises(asyncio.CancelledError):
                await asyncio.wait_for(read, 10.0)
            assert (seen["entered"], seen["left"]) == (1, 1), \
                "the caller saw the cancel while the native thread still ran"
            assert _time.monotonic() - t0 < 5.0, \
                "abort did not unblock the thread"
            out = seen["out"]
            assert out.nbytes == len(payload)
            sentinel = np.frombuffer(
                data_generator.generate(98, out.nbytes).tobytes(), np.uint8)
            out[:] = sentinel
            await asyncio.sleep(0.3)
            np.testing.assert_array_equal(out, sentinel)
            assert not c.op_counters.get("stripe_gather_fallback")

            monkeypatch.setattr(
                native_io, "read_parts_gather_blocking", gather)
            assert await c.read_file(f.inode, 0, len(payload)) == payload
            assert c.op_counters.get("stripe_gather_fast", 0) == 1
        finally:
            await cluster.stop()


async def test_abort_before_dial_refuses_cleanly():
    """An abort that lands before the sockets are even registered must
    make the exchange refuse to start (no write to the buffer at all)."""
    if not native_io.parts_gather_available():
        pytest.skip("native parts gather not built")
    # unreachable port: acquire() would block in connect; abort first
    out = np.full(3 * B, 0x77, dtype=np.uint8)
    cell = {"aborted": True}
    sock = socket_mod.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.listen(8)  # accepts (all three dials) but nobody will speak
    try:
        with pytest.raises(native_io.NativeIOError):
            await native_io.run(
                native_io.read_parts_gather_blocking,
                [("127.0.0.1", port)] * 3, 7, 1, [1, 2, 3], 0, 3, out, cell,
            )
        assert np.all(out == 0x77)
    finally:
        sock.close()


# --- multi-part scatter WRITE fast path -------------------------------------

async def test_parts_scatter_write_engages(tmp_path):
    """Striped writes must take the one-call native multi-part path
    (counter proves it) and produce byte-identical data."""
    if not native_io.parts_scatter_available():
        pytest.skip("native parts scatter not built")
    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "scatterw.bin")
        await c.setgoal(f.inode, EC_GOAL)
        payload = data_generator.generate(11, 3 * 2**20 + 777).tobytes()
        await c.write_file(f.inode, payload)
        assert c.op_counters.get("parts_scatter_write", 0) >= 1, \
            "scatter write path not engaged"
        back = await c.read_file(f.inode, 0, len(payload))
        assert bytes(back) == payload
    finally:
        await cluster.stop()


async def test_parts_scatter_write_failure_falls_back(tmp_path, monkeypatch):
    """A native scatter failure degrades to per-part writes with the
    same bytes on disk."""
    if not native_io.parts_scatter_available():
        pytest.skip("native parts scatter not built")
    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()

        def boom(*a, **k):
            raise native_io.NativeIOError(5, "injected scatter failure")

        monkeypatch.setattr(native_io, "write_parts_scatter_blocking", boom)
        f = await c.create(1, "fallbackw.bin")
        await c.setgoal(f.inode, EC_GOAL)
        payload = data_generator.generate(12, 2 * 2**20).tobytes()
        await c.write_file(f.inode, payload)
        assert c.op_counters.get("parts_scatter_fallback", 0) >= 1
        back = await c.read_file(f.inode, 0, len(payload))
        assert bytes(back) == payload
    finally:
        await cluster.stop()


async def test_parts_scatter_skips_chained_copies(tmp_path):
    """goal-2 copies use relay chains (two holders per part) — the
    scatter path must stand aside and the chain path still work."""
    if not native_io.parts_scatter_available():
        pytest.skip("native parts scatter not built")
    cluster = Cluster(tmp_path, n_cs=4)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "chained.bin")
        await c.setgoal(f.inode, 2)  # 2 copies -> chain write
        payload = data_generator.generate(13, 1 * 2**20 + 55).tobytes()
        await c.write_file(f.inode, payload)
        back = await c.read_file(f.inode, 0, len(payload))
        assert bytes(back) == payload
    finally:
        await cluster.stop()


async def test_send_parts_stands_aside_for_a_chained_part(
    tmp_path, monkeypatch
):
    """One part with a second holder (a relay chain) and the whole
    batch takes the per-part sends: the exchange's frames carry no
    chain. The decision alone is driven: the per-part sender is a
    recorder, and the exchange must not be called at all."""
    if not native_io.parts_scatter_available():
        pytest.skip("native parts scatter not built")
    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "chain.bin")
        await c.setgoal(f.inode, EC_GOAL)
        await c.pwrite(f.inode, 0, b"x" * (3 * B))
        locs = (await c.chunk_info(f.inode, 0)).locations
        assert len(locs) == 5

        def never(*a, **k):
            raise AssertionError("exchange tried with a chained part")

        sent = []

        async def record(chunk_id, version, holders, payload, length, **kw):
            sent.append((len(holders), length, kw["part_offset"],
                         kw["skip_throttle"], kw["cell"]))

        monkeypatch.setattr(native_io, "write_parts_scatter_blocking", never)
        monkeypatch.setattr(c, "_write_part", record)
        pay = np.zeros(B, dtype=np.uint8)
        parts = [([loc], pay, B) for loc in locs[:4]]
        parts.append(([locs[4], locs[0]], pay, B))
        cells: list[dict] = []
        before = dict(c.op_counters)
        await c._send_parts(7, 1, parts, 2 * B, cells)
        assert [s[:4] for s in sent] == [(1, B, 2 * B, True)] * 4 + [
            (2, B, 2 * B, True)]
        assert [s[4] for s in sent] == cells  # an abort handle a send
        assert c.op_counters == before
    finally:
        await cluster.stop()


def _drop_idle_sockets() -> None:
    """Empty the process-wide socket pools. Earlier tests leave idle
    sockets to chunkservers that are gone; a later cluster that is
    handed one of their ports would take such a socket for its own, so
    a test that counts dials or redials starts from empty pools."""
    idle = []
    for pool in (native_io.POOL, native_io.RING_POOL):
        with pool._lock:
            idle += [s for bucket in pool._idle.values() for s in bucket]
            pool._idle.clear()
    for s in idle:
        native_io.shm_ring_drop(s)
        s.close()


async def test_pwrite_reuses_pooled_sockets_through_a_restart(tmp_path):
    """Two pwrites to one chunk dial each chunkserver once; a data
    plane restarted between two more costs one redial of the exchange
    and no error, and the call after it dials nothing again."""
    if not native_io.parts_scatter_available():
        pytest.skip("native parts scatter not built")
    from lizardfs_tpu.chunkserver import native_serve

    _drop_idle_sockets()
    cluster = Cluster(tmp_path)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        f = await c.create(1, "pool.bin")
        await c.setgoal(f.inode, EC_GOAL)
        stripe = 3 * B
        rows = [data_generator.generate(40 + i, stripe).tobytes()
                for i in range(4)]
        pool = native_io.POOL

        async def write(i):
            """Full stripe i (no read-back): (dials, hits) it cost."""
            d0, h0 = pool.dials, pool.hits
            await c.pwrite(f.inode, i * stripe, rows[i])
            return pool.dials - d0, pool.hits - h0

        assert await write(0) == (5, 0)
        assert await write(1) == (0, 5)
        for cs in cluster.chunkservers:
            port = cs.data_server.port
            await asyncio.to_thread(cs.data_server.stop)
            cs.data_server = native_serve.DataPlaneServer(
                [s.folder for s in cs.store.stores], cs.host, port)
        # five stale hits, then the one redial of all five
        assert await write(2) == (5, 5)
        assert await write(3) == (0, 5)
        assert c.op_counters.get("parts_scatter_write", 0) == 4
        assert c.op_counters.get("parts_scatter_fallback", 0) == 0
        c.cache.invalidate(f.inode)
        assert await c.read_file(f.inode) == b"".join(rows)
    finally:
        await cluster.stop()


def test_socket_pool_keeps_as_many_idle_as_were_out():
    """The idle bound of an address is derived: what has been out at
    once, never over ``MAX_IDLE``."""
    listener = socket_mod.socket()
    listener.bind(("127.0.0.1", 0))
    listener.listen(64)
    addr = listener.getsockname()
    pool = native_io._SocketPool()
    try:
        for out_at_once, kept in ((2, 2), (6, 6), (10, 10), (3, 10),
                                  (pool.MAX_IDLE + 3, pool.MAX_IDLE)):
            socks = [pool.acquire(addr) for _ in range(out_at_once)]
            for s in socks:
                pool.release(addr, s)
            assert len(pool._idle[addr]) == kept, out_at_once
        # 2 dialled, 4 more for 6, 4 for 10, none for 3, the rest for 35
        assert pool.dials == pool.MAX_IDLE + 3
        assert pool.hits == 2 + 6 + 3 + 10
        one = pool.acquire(addr, fresh=True)  # dials whatever is idle
        assert pool.dials == pool.MAX_IDLE + 4
        pool.discard(addr, one)
        assert pool._out[addr] == 0
    finally:
        for s in pool._idle.pop(addr, []):
            s.close()
        listener.close()


async def test_cancelled_pwrite_aborts_its_exchange(tmp_path, monkeypatch):
    """A cancelled striped pwrite kills the worker of its exchange:
    the cell is aborted and the thread stops reading the region."""
    if not native_io.parts_scatter_available():
        pytest.skip("native parts scatter not built")
    import threading
    import time as time_mod

    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "cancel.bin")
        await c.setgoal(f.inode, EC_GOAL)
        started = threading.Event()
        seen: list[dict] = []
        real = native_io._lib.lz_write_parts_exchange

        def stall(*args):
            """The C exchange, held until the abort shuts its sockets
            down: then the real call fails on them at once."""
            started.set()
            deadline = time_mod.monotonic() + 15.0
            while time_mod.monotonic() < deadline and not any(
                    cl.get("aborted") for cl in seen):
                time_mod.sleep(0.01)
            return real(*args)

        real_blocking = native_io.write_parts_scatter_blocking

        def spy(addrs, cid, ver, pids, payloads, lengths, off=0, cell=None):
            seen.append(cell)
            return real_blocking(addrs, cid, ver, pids, payloads, lengths,
                                 off, cell)

        monkeypatch.setattr(native_io, "write_parts_scatter_blocking", spy)
        monkeypatch.setattr(native_io._lib, "lz_write_parts_exchange", stall)
        task = asyncio.ensure_future(c.pwrite(f.inode, 0, b"y" * (3 * B)))
        await asyncio.wait_for(
            asyncio.get_running_loop().run_in_executor(None, started.wait, 10),
            15.0,
        )
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        assert len(seen) == 1 and seen[0].get("aborted")
        deadline = time_mod.monotonic() + 10.0
        while not seen[0].get("finished") and time_mod.monotonic() < deadline:
            await asyncio.sleep(0.01)
        assert seen[0].get("finished") is True, \
            "the worker still streams from the cancelled pwrite's region"
        assert "socks" not in seen[0]
        monkeypatch.undo()
        # the torn chunk is rewritten whole by the next call
        await c.pwrite(f.inode, 0, b"z" * (3 * B))
        c.cache.invalidate(f.inode)
        assert await c.read_file(f.inode) == b"z" * (3 * B)
    finally:
        await cluster.stop()


# --- write-abort path: zombie sender threads must die promptly --------------

async def test_abort_write_scatter_unblocks_thread():
    """abort_write must unblock a scatter-write executor thread stuck on
    an unresponsive chunkserver, and mark the cell finished so the
    caller knows the payload buffers are no longer being read."""
    if not native_io.parts_scatter_available():
        pytest.skip("native parts scatter not built")
    stalled = asyncio.Event()
    teardown = asyncio.Event()

    async def stall_handler(reader, writer):
        try:
            await reader.read(4096)  # swallow the WriteInit, never reply
            stalled.set()
            await teardown.wait()
        except (ConnectionError, asyncio.CancelledError):
            pass
        finally:
            writer.close()

    server = await asyncio.start_server(stall_handler, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    try:
        payloads = [np.zeros(B, dtype=np.uint8) for _ in range(3)]
        cell: dict = {"submitted": True}
        fut = asyncio.get_running_loop().run_in_executor(
            native_io.EXECUTOR,
            lambda: native_io.write_parts_scatter_blocking(
                [("127.0.0.1", port)] * 3, 42, 1, [1, 2, 3],
                payloads, [B] * 3, 0, cell,
            ),
        )
        await asyncio.wait_for(stalled.wait(), 10.0)
        t0 = asyncio.get_running_loop().time()
        native_io.abort_write(cell)
        with pytest.raises((native_io.NativeIOError, OSError)):
            await asyncio.wait_for(fut, 10.0)
        assert asyncio.get_running_loop().time() - t0 < 5.0, \
            "abort did not unblock the sender thread"
        assert cell.get("finished") is True
    finally:
        teardown.set()
        server.close()
        await server.wait_closed()


async def test_cancelled_striped_write_does_not_pool_staging(
    tmp_path, monkeypatch
):
    """A cancelled chunk write whose native sender may still be running
    must NOT return the staging buffer to the reuse pool (the zombie
    thread streams from it; pooling it lets the next chunk's scatter
    overwrite bytes mid-send) — and must abort the zombie's sockets."""
    if not (native_io.parts_scatter_available()
            and native.stripe_helpers_available()):
        pytest.skip("native fast paths not built")
    import threading
    import time as time_mod

    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()
        # pin the whole-part batches through _pipeline_eligible's own
        # input (a payload under the minimum): the windowed path has
        # its own session sender and is exercised below
        min_bytes = c.WRITE_PIPELINE_MIN_BYTES
        c.WRITE_PIPELINE_MIN_BYTES = MFSCHUNKSIZE + 1
        f = await c.create(1, "pool.bin")
        await c.setgoal(f.inode, EC_GOAL)
        full = data_generator.generate(21, MFSCHUNKSIZE).tobytes()
        # 1) clean full-chunk write pools its staging buffer
        await c.write_file(f.inode, full)
        pooled = sum(len(b) for b in c._stage_buffers.values())
        assert pooled >= 1, "full-chunk write should pool its stage"

        # 2) hung scatter + cancellation: the (reused) buffer must not
        # come back to the pool, and the cell must be aborted
        started = threading.Event()
        seen_cells: list[dict] = []

        def hang_until_abort(addrs, cid, ver, pids, payloads, lengths,
                             part_offset=0, cell=None):
            seen_cells.append(cell)
            started.set()
            deadline = time_mod.monotonic() + 15.0
            while time_mod.monotonic() < deadline:
                if cell is not None and cell.get("aborted"):
                    break
                time_mod.sleep(0.01)
            try:
                raise native_io.NativeIOError(-1, "hung exchange aborted")
            finally:
                if cell is not None:
                    cell["finished"] = True

        monkeypatch.setattr(
            native_io, "write_parts_scatter_blocking", hang_until_abort
        )
        g = await c.create(1, "pool2.bin")
        await c.setgoal(g.inode, EC_GOAL)
        task = asyncio.ensure_future(c.write_file(g.inode, full))
        await asyncio.wait_for(
            asyncio.get_running_loop().run_in_executor(None, started.wait, 10),
            15.0,
        )
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        assert sum(len(b) for b in c._stage_buffers.values()) == 0, \
            "staging buffer pooled while a zombie sender may hold it"
        assert any(cl and cl.get("aborted") for cl in seen_cells), \
            "cancelled write did not abort its in-flight sender"

        # 3) same invariant for the WINDOWED sender: a cancelled
        # session segment must abort its cell and keep both the stage
        # and the parity send buffer out of the pool
        monkeypatch.undo()
        c.WRITE_PIPELINE_MIN_BYTES = min_bytes
        started3 = threading.Event()
        cells3: list[dict] = []

        def hang_segment(self, payloads, lengths, part_offset, write_id):
            cells3.append(self.cell)
            started3.set()
            deadline = time_mod.monotonic() + 15.0
            while time_mod.monotonic() < deadline:
                if self.cell.get("aborted"):
                    break
                time_mod.sleep(0.01)
            self.close()
            raise native_io.NativeIOError(-1, "hung segment aborted")

        monkeypatch.setattr(
            native_io.PartsScatterSession, "send_segment_window",
            hang_segment,
        )
        h = await c.create(1, "pool3.bin")
        await c.setgoal(h.inode, EC_GOAL)
        task = asyncio.ensure_future(c.write_file(h.inode, full))
        await asyncio.wait_for(
            asyncio.get_running_loop().run_in_executor(
                None, started3.wait, 10
            ),
            15.0,
        )
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        assert sum(len(b) for b in c._stage_buffers.values()) == 0, \
            "buffers pooled while a zombie session sender may hold them"
        assert any(cl and cl.get("aborted") for cl in cells3), \
            "cancelled windowed write did not abort its session"
    finally:
        await cluster.stop()


# --- same-host unix-socket fast path ----------------------------------------

async def test_uds_fast_path_engages(tmp_path):
    """The same-host abstract-socket fast path must actually engage:
    this pins the name contract between native_io._blocking_socket and
    serve_native.cpp's uds_data_addr — a silent format drift would
    quietly fall back to TCP and forfeit the ~2.5x per-byte win."""
    if not native_io.available():
        pytest.skip("native io not built")
    before = native_io.UDS_CONNECTS
    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "uds.bin")
        await c.setgoal(f.inode, EC_GOAL)
        payload = data_generator.generate(17, 2 * 2**20).tobytes()
        await c.write_file(f.inode, payload)
        c.cache.invalidate(f.inode)
        back = await c.read_file(f.inode, 0, len(payload))
        assert bytes(back) == payload
        assert native_io.UDS_CONNECTS > before, \
            "no data-plane connection took the unix-socket fast path"
    finally:
        await cluster.stop()


# --- same-host shared-memory part rings (native/shm_ring.h) -----------------

async def _striped_roundtrip(cluster, c, name, nbytes, goal=None):
    f = await c.create(1, name)
    await c.setgoal(f.inode, goal if goal is not None else EC_GOAL)
    payload = data_generator.generate(29, nbytes).tobytes()
    await c.write_file(f.inode, payload)
    c.cache.invalidate(f.inode)
    back = await c.read_file(f.inode, 0, nbytes)
    assert bytes(back) == payload, "roundtrip corruption"
    return f


def test_shm_ring_unalloc_rollback_does_not_overlap_live_regions():
    """Rolling back a staged-but-failed allocation must retract the
    ring head, not advance the implied tail: a free()-based rollback
    leaves a hole the accounting stops covering, and a later alloc can
    hand out a region overlapping a sent-but-unacked segment's bytes
    (the server would then CRC-fail the descriptor it reads later)."""
    if not hasattr(native_io, "ShmRing"):
        pytest.skip("native shm ring not built")
    ring = native_io.ShmRing(native_io.shm_seg_bytes())
    try:
        ring.size = 100  # drive the allocator, not the mapping
        live = []
        for _ in range(2):  # seg1 [0,30), seg2 [30,60): sent, unacked
            off, cost = ring.alloc(30)
            live.append((off, off + 30))
        off3, cost3 = ring.alloc(20)  # seg3 staged [60,80)...
        ring.unalloc(off3, cost3, 20)  # ...then encode fails: roll back
        ring.free(30)  # seg1 acked (FIFO)
        live.pop(0)
        for nbytes in (20, 30, 20):
            got = ring.alloc(nbytes)
            if got is None:
                continue
            off, _cost = got
            for lo, hi in live:
                assert not (off < hi and off + nbytes > lo), (
                    f"alloc [{off},{off + nbytes}) overlaps "
                    f"live [{lo},{hi})"
                )
    finally:
        ring.close()


async def test_shm_ring_engages(tmp_path):
    """A same-host windowed striped write must negotiate memfd rings
    and move its parts as descriptor frames: client counters, the
    chunkserver's native shm stats, and the copy-free trace kind all
    prove the handoff — a silent precondition miss would quietly fall
    back to the socket-copy path and forfeit the send-phase win."""
    if not native_io.parts_shm_available():
        pytest.skip("native shm ring not built")
    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()
        c.WRITE_PIPELINE_MIN_BYTES = 1
        await _striped_roundtrip(cluster, c, "ring.bin", 8 * 2**20)
        assert c.op_counters.get("write_shm", 0) >= 1, \
            "shm ring path did not engage"
        assert c.metrics.series["shm_ring_segments_mapped"].total >= 1
        assert c.metrics.series["shm_ring_desc_parts"].total >= 1
        server_desc_ops = sum(
            cs.data_server.shm_stats()["desc_ops"]
            for cs in cluster.chunkservers
            if cs.data_server is not None
        )
        assert server_desc_ops >= 1, \
            "no chunkserver landed a ring descriptor"
    finally:
        await cluster.stop()


async def test_shm_ring_engages_on_asyncio_chunkserver(tmp_path):
    """Pure-Python chunkservers have no UDS listener, so their demux's
    only reachable transport is loopback TCP: a ring-capable client
    writing to an asyncio chunkserver over 127.0.0.1 must still
    negotiate segments and ship descriptors (the fd travels as a
    /proc/<pid>/fd name instead of SCM_RIGHTS) — otherwise the
    pure-Python fallback demux is dead code."""
    if not native_io.parts_shm_available():
        pytest.skip("native shm ring not built")
    cluster = Cluster(tmp_path, n_cs=6, native_data_plane=False)
    await cluster.start()
    try:
        c = await cluster.client()
        c.WRITE_PIPELINE_MIN_BYTES = 1
        await _striped_roundtrip(cluster, c, "pyring2.bin", 8 * 2**20)
        assert c.op_counters.get("write_shm", 0) >= 1, \
            "shm ring path did not engage against the asyncio plane"
        mapped = sum(
            cs.metrics.series["shm_segments_mapped"].total
            for cs in cluster.chunkservers
            if "shm_segments_mapped" in cs.metrics.series
        )
        assert mapped >= 1, "no asyncio chunkserver mapped a segment"
    finally:
        await cluster.stop()


async def test_shm_ring_segments_released_on_session_teardown(tmp_path):
    """After writes finish and pooled connections are discarded, every
    chunkserver's active-segment gauge returns to zero (segments are
    owned by the connection, never leaked across sessions)."""
    if not native_io.parts_shm_available():
        pytest.skip("native shm ring not built")
    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()
        c.WRITE_PIPELINE_MIN_BYTES = 1
        for rep in range(3):
            await _striped_roundtrip(
                cluster, c, f"seg{rep}.bin", 4 * 2**20
            )
        mapped = sum(
            cs.data_server.shm_stats()["segments_mapped"]
            for cs in cluster.chunkservers
            if cs.data_server is not None
        )
        assert mapped >= 1
        # pooled connections keep their segment mapped (that's the
        # point: no per-chunk renegotiation) — drop the pools and the
        # mappings must go with them (ring conns pool in RING_POOL)
        _drop_idle_sockets()
        deadline = asyncio.get_event_loop().time() + 10.0
        while asyncio.get_event_loop().time() < deadline:
            active = sum(
                cs.data_server.shm_stats()["active_segments"]
                for cs in cluster.chunkservers
                if cs.data_server is not None
            )
            if active == 0:
                break
            await asyncio.sleep(0.1)
        assert active == 0, f"{active} shm segments leaked past teardown"
    finally:
        await cluster.stop()


async def test_shm_ring_full_falls_back_to_scatterv(tmp_path, monkeypatch):
    """A ring too small for a segment must fall back to the vectored
    socket-copy send mid-stripe — same bytes, fallback counted."""
    if not native_io.parts_shm_available():
        pytest.skip("native shm ring not built")
    # 64 KiB segments: smaller than any padded parity region of the
    # striped segments below, so every staging attempt fails ring-full
    monkeypatch.setenv("LZ_SHM_RING_MB", "0.0625")
    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()
        c.WRITE_PIPELINE_MIN_BYTES = 1
        await _striped_roundtrip(cluster, c, "tiny_ring.bin", 8 * 2**20)
        fallbacks = c.metrics.series.get("shm_ring_fallbacks")
        assert fallbacks is not None and fallbacks.total >= 1, \
            "ring-full segments did not fall back to scatterv"
        # the socket-copy frames ride the SAME proactor-owned
        # connections the ring negotiated on — the windowed write must
        # survive the interleave, not degrade to the serial rewrite
        assert not c.op_counters.get("write_pipeline_fallback"), \
            "proactor rejected interleaved scatterv frames"
    finally:
        await cluster.stop()


async def test_shm_ring_kill_switch_stays_on_socket_path(tmp_path,
                                                         monkeypatch):
    """LZ_SHM_RING=0 must keep the windowed write on the PR-5 scatterv
    path: no handshake, no descriptors, no client-side ring series."""
    if not native_io.parts_shm_available():
        pytest.skip("native shm ring not built")
    monkeypatch.setenv("LZ_SHM_RING", "0")
    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()
        c.WRITE_PIPELINE_MIN_BYTES = 1
        await _striped_roundtrip(cluster, c, "killed.bin", 8 * 2**20)
        assert c.op_counters.get("write_window", 0) >= 1
        assert not c.op_counters.get("write_shm"), \
            "kill switch did not disable the ring path"
        assert "shm_ring_desc_parts" not in c.metrics.series
        assert all(
            cs.data_server.shm_stats()["segments_mapped"] == 0
            for cs in cluster.chunkservers
            if cs.data_server is not None
        )
    finally:
        await cluster.stop()


async def test_shm_ring_kill_switch_off_spelling_disables_server(
        tmp_path, monkeypatch):
    """LZ_SHM_RING=off must kill the native server's ring acceptance
    too — spelling parity between lzshm::ring_disabled and
    native_io.shm_ring_enabled.  The client side is forced eligible so
    only the server's C-side env parse is under test: the handshake
    must be refused and the write must fall back to scatterv."""
    if not native_io.parts_shm_available():
        pytest.skip("native shm ring not built")
    monkeypatch.setenv("LZ_SHM_RING", "off")
    monkeypatch.setattr(native_io, "shm_ring_enabled", lambda: True)
    cluster = Cluster(tmp_path)
    await cluster.start()
    try:
        c = await cluster.client()
        c.WRITE_PIPELINE_MIN_BYTES = 1
        await _striped_roundtrip(cluster, c, "killed_off.bin", 8 * 2**20)
        assert not c.op_counters.get("write_shm"), \
            "server accepted a ring despite LZ_SHM_RING=off"
        assert all(
            cs.data_server.shm_stats()["segments_mapped"] == 0
            for cs in cluster.chunkservers
            if cs.data_server is not None
        )
    finally:
        await cluster.stop()


async def test_shm_ring_asyncio_fallback_demux(tmp_path):
    """The pure-Python chunkserver demuxes the same descriptor frames:
    ShmInit maps the client's memfd via /proc (StreamReader drops the
    SCM_RIGHTS cmsg), ShmWritePart lands bytes read straight from the
    mapping, and the mapping is released when the connection closes."""
    import os

    from lizardfs_tpu.ops import crc32 as crc_mod
    from lizardfs_tpu.proto import framing
    from lizardfs_tpu.proto import messages as m
    from lizardfs_tpu.proto import status as st

    if not hasattr(os, "memfd_create"):
        pytest.skip("no memfd_create")
    cluster = Cluster(tmp_path, n_cs=3, native_data_plane=False)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "pyring.bin")
        # goal 1 plain copy: one part, part_id 0, easy to address
        payload = data_generator.generate(31, 2 * MFSBLOCKSIZE).tobytes()
        await c.write_file(f.inode, payload)  # creates the chunk
        loc = await c.chunk_info(f.inode, 0)
        part = loc.locations[0]

        ring = native_io.ShmRing(1 << 20)
        try:
            fresh = data_generator.generate(37, 2 * MFSBLOCKSIZE).tobytes()
            ring.arr[: len(fresh)] = np.frombuffer(fresh, dtype=np.uint8)
            reader, writer = await asyncio.open_connection(
                part.addr.host, part.addr.port
            )
            try:
                await framing.send_message(writer, m.CltocsShmInit(
                    req_id=1, pid=os.getpid(), mem_fd=ring.memfd,
                    seg_size=ring.size,
                ))
                ack = await framing.read_message(reader)
                assert isinstance(ack, m.CstoclWriteStatus)
                assert ack.status == st.OK, "asyncio ShmInit refused"
                await framing.send_message(writer, m.CltocsWriteInit(
                    req_id=2, chunk_id=loc.chunk_id, version=loc.version,
                    part_id=part.part_id, chain=[], create=False,
                ))
                ack = await framing.read_message(reader)
                assert ack.status == st.OK
                crcs = [
                    crc_mod.crc32(
                        fresh[i * MFSBLOCKSIZE:(i + 1) * MFSBLOCKSIZE]
                    )
                    for i in range(2)
                ]
                await framing.send_message(writer, m.CltocsShmWritePart(
                    req_id=3, chunk_id=loc.chunk_id, write_id=3,
                    part_id=part.part_id, part_offset=0, ring_off=0,
                    length=len(fresh), crcs=crcs,
                ))
                ack = await framing.read_message(reader)
                assert ack.status == st.OK, "descriptor write refused"
                await framing.send_message(writer, m.CltocsWriteEnd(
                    req_id=4, chunk_id=loc.chunk_id,
                ))
                ack = await framing.read_message(reader)
                assert ack.status == st.OK
            finally:
                writer.close()
                try:
                    await writer.wait_closed()
                except (ConnectionError, OSError):
                    pass
        finally:
            ring.close()
        c.cache.invalidate(f.inode)
        back = await c.read_file(f.inode, 0, len(fresh))
        assert bytes(back) == fresh, "ring bytes did not land"
    finally:
        await cluster.stop()


async def test_shm_init_refused_for_remote_peers(tmp_path):
    """Server-side enforcement of the same-host contract: a ShmInit
    arriving over TCP from a non-loopback peer is refused outright —
    remote peers must not drive the /proc fd mapping or pin 1 GiB
    server-side segments (the client's own AF_UNIX gate only protects
    well-behaved clients, not the server)."""
    import os

    from lizardfs_tpu.proto import framing
    from lizardfs_tpu.proto import messages as m
    from lizardfs_tpu.proto import status as st

    cluster = Cluster(tmp_path, n_cs=1, native_data_plane=False)
    await cluster.start()
    try:
        cs = cluster.chunkservers[0]

        class _RemoteWriter:
            """Quacks like a StreamWriter on a non-loopback TCP conn."""

            def __init__(self):
                self.buf = bytearray()
                self.sock = socket_mod.socket(
                    socket_mod.AF_INET, socket_mod.SOCK_STREAM
                )

            def get_extra_info(self, key):
                if key == "socket":
                    return self.sock
                if key == "peername":
                    return ("203.0.113.9", 54321)
                return None

            def write(self, data):
                self.buf += data

            async def drain(self):
                pass

        if not hasattr(os, "memfd_create"):
            pytest.skip("no memfd_create")
        # a real, mappable segment: the refusal must come from the
        # same-host gate, not from a failed /proc open
        memfd = os.memfd_create("lzshm-test")
        os.ftruncate(memfd, 1 << 20)
        w = _RemoteWriter()
        try:
            shm_state: dict = {}
            await cs._serve_shm_init(
                w,
                m.CltocsShmInit(
                    req_id=1, pid=os.getpid(), mem_fd=memfd,
                    seg_size=1 << 20,
                ),
                shm_state,
            )
        finally:
            w.sock.close()
            os.close(memfd)
        reader = asyncio.StreamReader()
        reader.feed_data(bytes(w.buf))
        reader.feed_eof()
        ack = await framing.read_message(reader)
        assert isinstance(ack, m.CstoclWriteStatus)
        assert ack.status == st.EINVAL, "remote ShmInit must be refused"
        assert "mm" not in shm_state, "remote peer mapped a segment"
    finally:
        await cluster.stop()


# --- the one-shot part exchange: three legs in one native call -------------

LEGS = ("init", "data", "end")
_LEG_OF_TYPE = {m.CltocsWriteInit.MSG_TYPE: "init",
                m.CltocsWriteBulk.MSG_TYPE: "data",
                m.CltocsWriteEnd.MSG_TYPE: "end"}
_WHAT = {"init": "write init", "data": "parts scatter write",
         "end": "write end"}


class _Peers:
    """Scripted chunkserver stand-ins on one listener: a connection is
    one part's socket (it says which in its WriteInit). Every frame is
    recorded per part, byte for byte, and answered as ``script(part_id,
    leg)`` says: ``"ok"``, a status code, ``"stall"`` (never, until the
    test is over) or a float (answer after that many seconds).
    ``events`` is the order the loop saw things in."""

    def __init__(self, script=lambda part_id, leg: "ok"):
        self.script = script
        self.wire: dict[int, bytearray] = {}
        self.events: list[tuple[str, int]] = []
        self.stalled = asyncio.Event()
        self.over = asyncio.Event()
        self.conns = 0

    async def __aenter__(self):
        self.server = await asyncio.start_server(
            self._serve, "127.0.0.1", 0)
        self.addr = ("127.0.0.1",
                     self.server.sockets[0].getsockname()[1])
        return self

    async def __aexit__(self, *exc):
        self.over.set()
        # pooled sockets first: wait_closed() waits for their handlers
        for s in native_io.POOL._idle.pop(self.addr, []):
            s.close()
        self.server.close()
        await self.server.wait_closed()

    async def _serve(self, reader, writer):
        self.conns += 1
        part_id = None
        try:
            while True:
                head = await reader.readexactly(8)
                msg_type, length = struct.unpack(">II", head)
                body = await reader.readexactly(length)
                msg = framing.decode(msg_type, body)
                leg = _LEG_OF_TYPE[msg_type]
                if leg == "init":
                    part_id = msg.part_id
                self.wire.setdefault(part_id, bytearray()).extend(
                    head + body)
                self.events.append((leg + "_seen", part_id))
                act = self.script(part_id, leg)
                if act == "stall":
                    self.stalled.set()
                    await self.over.wait()
                    return
                if isinstance(act, float):
                    await asyncio.sleep(act)
                wid = msg.write_id if leg == "data" else 0
                writer.write(framing.encode(m.CstoclWriteStatus(
                    req_id=msg.req_id, chunk_id=msg.chunk_id, write_id=wid,
                    status=st.OK if act == "ok" or isinstance(act, float)
                    else act)))
                self.events.append((leg + "_answered", part_id))
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            writer.close()

    def idle(self) -> int:
        return len(native_io.POOL._idle.get(self.addr, []))

    def out(self) -> int:
        return native_io.POOL._out.get(self.addr, 0)


def _exchange(peers, payloads, lengths, cell, part_offset=0,
              chunk_id=77, version=3, trace=None):
    """write_parts_scatter_blocking against the stand-ins, on the
    executor (the loop stays free to play the servers)."""
    n = len(payloads)
    args = (native_io.write_parts_scatter_blocking, [peers.addr] * n,
            chunk_id, version, list(range(1, n + 1)), payloads, lengths,
            part_offset, cell)
    if trace is not None:
        args = (native_io._call_under_trace, trace, 0) + args
    return asyncio.get_running_loop().run_in_executor(
        native_io.EXECUTOR, *args)


def _skip_without_exchange():
    if not native_io.parts_scatter_available():
        pytest.skip("native part exchange not built")


async def test_exchange_puts_the_parents_frames_on_the_wire(monkeypatch):
    """Per socket, byte for byte: the WriteInit Python encodes (trace id
    and session id riding it), the bulk frame with the golden CRCs, the
    WriteEnd, in that order; and no leg starts before every status of
    the one before is in (one server answers its init and its bulk ack
    late: nobody's data, nobody's End, overtakes it)."""
    _skip_without_exchange()
    monkeypatch.setattr(accounting, "_PROCESS_SESSION", 0x5E55)
    trace = 0x1234_5678_9ABC
    # lengths differ per part, as an RMW region ending inside a chunk
    lengths = [3 * B, 2 * B + 100, B]
    payloads = [np.frombuffer(
        data_generator.generate(70 + i, 3 * B).tobytes(), dtype=np.uint8)
        for i in range(3)]
    late = {(2, "init"): 0.15, (1, "data"): 0.15}
    async with _Peers(lambda p, leg: late.get((p, leg), "ok")) as peers:
        cell: dict = {}
        await asyncio.wait_for(
            _exchange(peers, payloads, lengths, cell, part_offset=2 * B,
                      trace=trace), 20.0)
        assert cell.get("native") is True and "redialled" not in cell
        assert cell["finished"] is True and "socks" not in cell
        for i, part_id in enumerate((1, 2, 3)):
            data = payloads[i][:lengths[i]].tobytes()
            want = framing.encode(m.CltocsWriteInit(
                req_id=1, chunk_id=77, version=3, part_id=part_id,
                chain=[], create=False, trace_id=trace,
                session_id=0x5E55,
            )) + framing.encode(m.CltocsWriteBulk(
                req_id=1, chunk_id=77, write_id=1, part_offset=2 * B,
                crcs=[zlib.crc32(data[o:o + B])
                      for o in range(0, len(data), B)],
                data=data,
            )) + framing.encode(m.CltocsWriteEnd(req_id=0, chunk_id=77))
            assert bytes(peers.wire[part_id]) == want, f"part {part_id}"
        order = [e for e, _ in peers.events]
        for before, after in (("init_answered", "data_seen"),
                              ("data_answered", "end_seen")):
            last = max(i for i, e in enumerate(order) if e == before)
            first = min(i for i, e in enumerate(order) if e == after)
            assert last < first, f"a {after} before the last {before}"
        # a clean End: all three sockets went back to the pool
        assert (peers.idle(), peers.out(), peers.conns) == (3, 0, 3)


@pytest.mark.parametrize("leg", LEGS)
async def test_exchange_refusal_names_its_leg(leg):
    """One server's refusal in a leg raises its status under the leg's
    own name (the per-part fallback keys on nothing else), though the
    round ends with the other parts still in flight (they answer that
    leg late), and no socket of the exchange goes back to the pool."""
    _skip_without_exchange()
    payloads = [np.zeros(B, dtype=np.uint8) for _ in range(3)]

    def refuse(part_id, at):
        if at != leg:
            return "ok"
        return st.ENOSPC if part_id == 2 else 0.3
    async with _Peers(refuse) as peers:
        cell: dict = {}
        with pytest.raises(native_io.NativeIOError) as e:
            await asyncio.wait_for(
                _exchange(peers, payloads, [B] * 3, cell), 20.0)
        assert e.value.code == st.ENOSPC
        assert _WHAT[leg] in str(e.value)
        assert (peers.idle(), peers.out()) == (0, 0)
        assert "native" not in cell and "redialled" not in cell
        assert cell["finished"] is True
        later = LEGS[LEGS.index(leg) + 1:]
        assert not [e for e in peers.events if e[0][:-5] in later], \
            "a frame of a later leg left after the refusal"


@pytest.mark.parametrize("native_plane", [True, False],
                         ids=["native-plane", "asyncio-plane"])
async def test_exchange_init_refused_by_a_chunkserver(tmp_path, native_plane):
    """Both chunkserver planes answer the one call's frames: a stale
    chunk version is refused at the init (WRONG_VERSION from every
    server), and the right one then writes through the same call."""
    _skip_without_exchange()
    cluster = Cluster(tmp_path, native_data_plane=native_plane)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "refused.bin")
        await c.setgoal(f.inode, EC_GOAL)
        await c.pwrite(f.inode, 0, b"a" * (3 * B))
        info = await c.chunk_info(f.inode, 0)
        addrs = [(loc.addr.host, loc.addr.port) for loc in info.locations]
        part_ids = [loc.part_id for loc in info.locations]
        payloads = [np.full(B, 9, dtype=np.uint8) for _ in addrs]
        pool = native_io.POOL
        for s in [s for a in addrs for s in pool._idle.pop(a, [])]:
            s.close()
        with pytest.raises(native_io.NativeIOError) as e:
            await native_io.run(
                native_io.write_parts_scatter_blocking, addrs,
                info.chunk_id, info.version + 7, part_ids, payloads,
                [B] * len(addrs), 0, {})
        assert e.value.code == st.WRONG_VERSION
        assert "write init" in str(e.value)
        assert not any(pool._idle.get(a) for a in addrs)
        cell: dict = {}
        await native_io.run(
            native_io.write_parts_scatter_blocking, addrs, info.chunk_id,
            info.version, part_ids, payloads, [B] * len(addrs), 0, cell)
        assert cell.get("native") is True
        assert all(len(pool._idle.get(a, [])) == 1 for a in addrs)
    finally:
        await cluster.stop()


async def test_init_refusal_falls_back_to_per_part_sends(
    tmp_path, monkeypatch
):
    """One chunkserver refuses one init: the exchange raises that
    status as "write init" with every socket discarded, ``_send_parts``
    counts the fallback and sends per part, and what lands on the
    disks is the golden split of the bytes (utils/striping.py)."""
    _skip_without_exchange()
    from lizardfs_tpu.chunkserver.chunk_store import ChunkStoreError
    from lizardfs_tpu.core import geometry

    cluster = Cluster(tmp_path, native_data_plane=False)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "refuse1.bin")
        await c.setgoal(f.inode, EC_GOAL)
        victim = cluster.chunkservers[2]
        real_require = victim.store.require
        refused = []

        def require_once(chunk_id, version, part_id):
            if not refused:
                refused.append(part_id)
                raise ChunkStoreError(st.ENOSPC, "injected")
            return real_require(chunk_id, version, part_id)

        monkeypatch.setattr(victim.store, "require", require_once)
        raised = []
        real_blocking = native_io.write_parts_scatter_blocking

        def spy(addrs, *args):
            try:
                return real_blocking(addrs, *args)
            except native_io.NativeIOError as e:
                raised.append((e, list(addrs)))
                raise

        monkeypatch.setattr(native_io, "write_parts_scatter_blocking", spy)
        payload = data_generator.generate(91, 6 * B + 4321).tobytes()
        await c.pwrite(f.inode, 0, payload)
        assert len(refused) == 1 and len(raised) == 1
        err, addrs = raised[0]
        assert err.code == st.ENOSPC and "write init" in str(err)
        pool = native_io.POOL
        assert not any(pool._idle.get(a) for a in addrs), "a socket pooled"
        assert all(pool._out.get(a, 0) == 0 for a in addrs)
        assert c.op_counters.get("parts_scatter_fallback", 0) == 1
        assert c.op_counters.get("parts_scatter_write", 0) == 0
        assert c.op_counters.get("parts_scatter_native", 0) == 0
        c.cache.invalidate(f.inode)
        assert bytes(await c.read_file(f.inode)) == payload
        files = _find_part_files(
            cluster, (await c.chunk_info(f.inode, 0)).chunk_id)
        assert len(files) == 5
        slice_type = geometry.ChunkPartType.from_id(next(iter(files))).type
        golden = striping.split_chunk(
            np.frombuffer(payload, dtype=np.uint8), slice_type)
        for part_id, path in files.items():
            part = geometry.ChunkPartType.from_id(part_id).part
            body, _ = _read_part(path)
            got = np.frombuffer(body, dtype=np.uint8)
            assert got.size and np.array_equal(
                got, golden[part][:got.size]), f"part {part}"
    finally:
        await cluster.stop()


async def test_exchange_redials_once_after_a_peer_restart(tmp_path):
    """One chunkserver's data plane restarts under a pooled socket: the
    stale socket shows inside the C call's init leg as a socket error,
    the exchange redials all five once and succeeds, and the client
    counts exactly that."""
    _skip_without_exchange()
    from lizardfs_tpu.chunkserver import native_serve

    _drop_idle_sockets()
    cluster = Cluster(tmp_path)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        f = await c.create(1, "redial.bin")
        await c.setgoal(f.inode, EC_GOAL)
        stripe = 3 * B
        rows = [data_generator.generate(50 + i, stripe).tobytes()
                for i in range(3)]
        await c.pwrite(f.inode, 0, rows[0])
        assert c.op_counters.get("parts_scatter_redial", 0) == 0
        holder = (await c.chunk_info(f.inode, 0)).locations[0].addr.port
        cs = next(cs for cs in cluster.chunkservers
                  if cs.data_server.port == holder)
        await asyncio.to_thread(cs.data_server.stop)
        cs.data_server = native_serve.DataPlaneServer(
            [s.folder for s in cs.store.stores], cs.host, holder)
        pool = native_io.POOL
        d0 = pool.dials
        await c.pwrite(f.inode, stripe, rows[1])
        assert pool.dials - d0 == 5, "the redial dials every part afresh"
        await c.pwrite(f.inode, 2 * stripe, rows[2])
        assert pool.dials - d0 == 5
        assert c.op_counters.get("parts_scatter_redial", 0) == 1
        assert c.op_counters.get("parts_scatter_fallback", 0) == 0
        assert (c.op_counters["parts_scatter_native"]
                == c.op_counters["parts_scatter_write"] == 3)
        c.cache.invalidate(f.inode)
        assert await c.read_file(f.inode) == b"".join(rows)
    finally:
        await cluster.stop()


@pytest.mark.parametrize("leg", LEGS)
async def test_abort_parts_scatter_during_each_leg(leg):
    """A server that goes silent in any of the three legs holds the C
    call in its poll; abort_parts_scatter() from another thread shuts
    the sockets, the call returns at once, the cell is finished and
    nothing is pooled (and no redial: the cell says aborted)."""
    _skip_without_exchange()
    payloads = [np.zeros(2 * B, dtype=np.uint8) for _ in range(3)]
    silent = lambda p, l: "stall" if (p, l) == (3, leg) else "ok"  # noqa: E731
    async with _Peers(silent) as peers:
        cell: dict = {"submitted": True}
        fut = _exchange(peers, payloads, [2 * B] * 3, cell)
        await asyncio.wait_for(peers.stalled.wait(), 10.0)
        await asyncio.sleep(0.05)  # the worker is inside the C call
        assert not fut.done() and len(cell["socks"]) == 3
        t0 = _time.monotonic()
        native_io.abort_parts_scatter(cell)
        with pytest.raises(native_io.NativeIOError) as e:
            await asyncio.wait_for(fut, 10.0)
        assert _time.monotonic() - t0 < 5.0, "abort did not end the call"
        assert e.value.code == -1 and _WHAT[leg] in str(e.value)
        assert cell["finished"] is True and "socks" not in cell
        assert "redialled" not in cell and peers.conns == 3
        assert (peers.idle(), peers.out()) == (0, 0)


async def test_exchange_end_never_answered_hits_the_deadline(monkeypatch):
    """A server that takes the data and never answers the End: the one
    deadline of the call ends it (twice: pooled sockets that die are
    redialled once), it raises as "write end", and not one socket goes
    back to the pool with an End unread."""
    _skip_without_exchange()
    monkeypatch.setattr(native_io, "_EXCHANGE_MAX_MS", 300)
    payloads = [np.zeros(B, dtype=np.uint8) for _ in range(3)]
    silent = lambda p, l: "stall" if (p, l) == (1, "end") else "ok"  # noqa: E731
    async with _Peers(silent) as peers:
        cell: dict = {}
        t0 = _time.monotonic()
        with pytest.raises(native_io.NativeIOError) as e:
            await asyncio.wait_for(
                _exchange(peers, payloads, [B] * 3, cell), 20.0)
        took = _time.monotonic() - t0
        assert 0.55 < took < 10.0, took
        assert e.value.code == -1 and "write end" in str(e.value)
        assert cell.get("redialled") is True and "native" not in cell
        assert peers.conns == 6
        assert (peers.idle(), peers.out()) == (0, 0)


@pytest.mark.parametrize("native_plane", [True, False],
                         ids=["native-plane", "asyncio-plane"])
async def test_exchange_legs_charge_the_phase_rows(tmp_path, native_plane):
    """The legs are timed inside the C call and laid under ``part``
    afterwards: after one pwrite ``write_phases`` holds ``part_init``,
    ``part_data`` and ``part_end`` above zero, together no more than
    ``part``, and the exchange counts as native."""
    _skip_without_exchange()
    cluster = Cluster(tmp_path, native_data_plane=native_plane)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "legs.bin")
        await c.setgoal(f.inode, EC_GOAL)
        await c.pwrite(f.inode, 0, b"w" * (3 * B))  # dials, warms
        before = c.write_phases.snapshot()
        counters = dict(c.op_counters)
        c.trace_ring.clear()
        await c.pwrite(f.inode, 3 * B, b"x" * (6 * B))
        row = phase_delta(c.write_phases.snapshot(), before)
        assert row["reps"] == 1
        legs = [row[f"{leg}_ms"]
                for leg in ("part_init", "part_data", "part_end")]
        assert all(ms > 0 for ms in legs), row
        assert sum(legs) <= row["part_ms"] + 1e-6, row
        assert c.op_counters["parts_scatter_write"] \
            == counters.get("parts_scatter_write", 0) + 1
        assert c.op_counters["parts_scatter_native"] \
            == c.op_counters["parts_scatter_write"]
        assert c.op_counters.get("parts_scatter_fallback", 0) == 0
        # and as spans: the three under the one part span, in order
        spans = c.trace_ring.dump()
        part = next(s for s in spans if s["name"] == "part")
        under = sorted((s for s in spans
                        if s["parent_id"] == part["span_id"]
                        and s["name"] in ("part_init", "part_data",
                                          "part_end")),
                       key=lambda s: s["t0"])
        assert [s["name"] for s in under] == [
            "part_init", "part_data", "part_end"]
        for a, b in zip(under, under[1:]):
            assert a["t1"] <= b["t0"] + 1e-4  # two clocks read a span
        assert part["t0"] <= under[0]["t0"] + 1e-4 and \
            under[-1]["t1"] <= part["t1"] + 1e-4
    finally:
        await cluster.stop()
