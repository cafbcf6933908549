"""A wave's part reads as ONE native call (``lz_read_parts_wave``).

``core/read_executor.py`` ``execute_plan`` hands a wave of two or more
bulk reads to ``native_io.read_parts_wave_blocking`` on one worker
thread; every other wave keeps its ``read_part_range`` task a part.
These tests drive both against real sockets on the native chunkserver
plane and hold the move to what it must keep: the bytes, the CRC
report, the wave timeout (a mute peer, a dial that hangs), the redial
of a dead pooled socket, the join on cancel, the counts.
"""

import asyncio
import socket as socket_mod
import time

import numpy as np
import pytest

from lizardfs_tpu.chunkserver.chunk_store import HEADER_SIZE
from lizardfs_tpu.constants import MFSBLOCKSIZE
from lizardfs_tpu.core import geometry, native_io, plans, read_executor
from lizardfs_tpu.core.read_executor import ReadError, execute_plan
from lizardfs_tpu.runtime import tracing
from lizardfs_tpu.runtime.metrics import phase_delta
from lizardfs_tpu.utils import data_generator, striping

from tests.test_cluster import EC_GOAL, WIDE_EC_GOAL, XOR_GOAL, Cluster
from tests.test_write_phases import _find_part_files

pytestmark = [
    pytest.mark.asyncio,
    pytest.mark.skipif(not native_io.parts_wave_available(),
                       reason="native parts wave not built"),
]

B = MFSBLOCKSIZE
GOALS = {"ec32": (EC_GOAL, 6), "ec84": (WIDE_EC_GOAL, 13),
         "xor3": (XOR_GOAL, 5)}


async def _write(cluster, c, goal: int, nbytes: int, seed: int = 5):
    f = await c.create(1, f"wave{seed}.bin")
    await c.setgoal(f.inode, goal)
    payload = data_generator.generate(seed, nbytes).tobytes()
    await c.write_file(f.inode, payload)
    return f.inode, payload


async def _located(c, inode, missing: int = 0):
    """-> (chunk info, slice type, locations of all but the first
    ``missing`` data parts)."""
    info = await c.chunk_info(inode, 0)
    locations = {}
    slice_type = None
    for pl in info.locations:
        cpt = geometry.ChunkPartType.from_id(pl.part_id)
        slice_type = cpt.type
        locations[cpt.part] = ((pl.addr.host, pl.addr.port), pl.part_id)
    first_data = 1 if slice_type.is_xor else 0
    for p in range(first_data, first_data + missing):
        del locations[p]
    return info, slice_type, locations


def _plan(c, slice_type, locations, lo_slot, nslots, file_length):
    first_data = 1 if slice_type.is_xor else 0
    wanted = [first_data + i for i in range(slice_type.data_parts)]
    part_sizes = {
        p: striping.part_length(slice_type, p, file_length)
        for p in range(slice_type.expected_parts)
    }
    planner = plans.SliceReadPlanner(
        slice_type, list(locations), encoder=c.encoder)
    return wanted, planner.build_plan(wanted, lo_slot, nslots, part_sizes)


def _region(buf, wanted, slice_type, nslots) -> bytes:
    bps = nslots * B
    return striping.assemble_chunk(
        {wanted[i]: buf[i * bps:(i + 1) * bps] for i in range(len(wanted))},
        slice_type, slice_type.data_parts * bps,
    ).tobytes()


def _warm(*addrs) -> None:
    """One idle pooled socket an address: a part rides the native call
    on such a socket alone, never on a dial."""
    socks = [native_io.POOL.acquire(addr) for addr in addrs]
    for addr, sock in zip(addrs, socks):
        native_io.POOL.release(addr, sock)


def _drain(*addrs) -> None:
    for addr in addrs:
        while (sock := native_io.POOL.try_acquire(addr)) is not None:
            native_io.POOL.discard(addr, sock)


def _holders(locations) -> list:
    return [addr for addr, _ in locations.values()]


class _Counts(dict):
    def __call__(self, name, n):
        self[name] = self.get(name, 0) + n


# region: (file length in slots, bytes cut off the end, lo_slot, nslots)
REGIONS = {
    "one_slot": (6, 0, 1, 1),       # 64 KiB a part: under the threshold
    "four_slots": (6, 0, 1, 4),
    "clipped_last_part": (6, 10_000, 2, 4),
}


@pytest.mark.parametrize("region", list(REGIONS))
@pytest.mark.parametrize("goal,missing", [
    ("ec32", 0), ("ec32", 1), ("ec32", 2),
    ("ec84", 0), ("ec84", 1), ("ec84", 2),
    ("xor3", 0), ("xor3", 1),
])
async def test_wave_call_and_per_part_tasks_read_the_same_bytes(
        tmp_path, monkeypatch, goal, missing, region):
    """``execute_plan``'s bytes with the one native call a wave and
    with a task a part, healthy and degraded, against the payload; the
    counts say which path served."""
    goal_id, n_cs = GOALS[goal]
    slots, cut, lo_slot, nslots = REGIONS[region]
    cluster = Cluster(tmp_path, n_cs=n_cs)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        d = 3 if goal != "ec84" else 8
        length = slots * d * B - cut
        inode, payload = await _write(cluster, c, goal_id, length)
        info, slice_type, locations = await _located(c, inode, missing)
        assert slice_type.data_parts == d
        want = payload[lo_slot * d * B:(lo_slot + nslots) * d * B]
        want += bytes(nslots * d * B - len(want))

        _warm(*_holders(locations))
        counts = _Counts()
        wanted, plan = _plan(c, slice_type, locations, lo_slot, nslots,
                             length)
        wave0 = [op for op in plan.read_operations if op.wave == 0]
        buf = await execute_plan(plan, info.chunk_id, info.version,
                                 locations, count=counts)
        assert _region(buf, wanted, slice_type, nslots) == want
        if region == "one_slot":
            assert not counts, "an op under the threshold: per-part tasks"
        else:
            assert counts == {"wave_native": 1,
                              "wave_native_parts": len(wave0)}

        monkeypatch.setattr(read_executor, "_wave_goes_native",
                            lambda ops: False)
        counts = _Counts()
        wanted, plan = _plan(c, slice_type, locations, lo_slot, nslots,
                             length)
        buf = await execute_plan(plan, info.chunk_id, info.version,
                                 locations, count=counts)
        assert _region(buf, wanted, slice_type, nslots) == want
        assert not counts
    finally:
        await cluster.stop()


async def _ec84_file(tmp_path, slots: int = 4):
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)
    c = await cluster.client()
    inode, payload = await _write(cluster, c, WIDE_EC_GOAL, slots * 8 * B)
    return cluster, c, inode, payload


async def test_corrupt_block_is_reported_and_a_fallback_wave_recovers(
        tmp_path):
    """One stored block of a data part is damaged on its server's disk:
    the wave call rejects that part alone (rc -3), ``on_part_failure``
    sees ``crc=True``, the other seven parts count, and a fallback
    wave's parity part finishes the read."""
    cluster, c, inode, payload = await _ec84_file(tmp_path)
    try:
        info, slice_type, locations = await _located(c, inode)
        path = _find_part_files(cluster, info.chunk_id)[locations[2][1]]
        with open(path, "r+b") as f:
            f.seek(HEADER_SIZE + B + 17)
            byte = f.read(1)
            f.seek(HEADER_SIZE + B + 17)
            f.write(bytes([byte[0] ^ 0x5A]))
        seen = []
        counts = _Counts()
        wanted, plan = _plan(c, slice_type, locations, 0, 4, len(payload))
        buf = await execute_plan(
            plan, info.chunk_id, info.version, locations, count=counts,
            on_part_failure=lambda part, wire, addr, exc: seen.append(
                (part, wire, addr, exc.crc)),
        )
        assert _region(buf, wanted, slice_type, 4) == payload
        assert seen == [(2, locations[2][1], locations[2][0], True)]
        assert counts == {"wave_native_fallback": 1, "wave_native_parts": 7}
        # end to end: the client reports the damaged part to the master
        c.cache.invalidate(inode)
        assert await c.read_file(inode, 0, len(payload)) == payload
        assert c.metrics.counter("damaged_parts_reported").total == 1
        assert c.op_counters["wave_native_fallback"] == 1
    finally:
        await cluster.stop()


class _MutePeer:
    """A plain TCP listener that accepts (the kernel does) and never
    answers: armed faults would force the asyncio plane instead."""

    def __enter__(self):
        self.sock = socket_mod.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(16)
        self.addr = ("127.0.0.1", self.sock.getsockname()[1])
        return self

    def __exit__(self, *exc):
        self.sock.close()


def _spy_on_waves(monkeypatch) -> tuple[list, list]:
    """Every PartsWave the executor hands to a worker, and those whose
    worker has returned."""
    waves, returned = [], []
    real = native_io.read_parts_wave_blocking

    def spy(wave):
        waves.append(wave)
        try:
            return real(wave)
        finally:
            returned.append(wave)

    monkeypatch.setattr(native_io, "read_parts_wave_blocking", spy)
    return waves, returned


async def test_mute_peer_leaves_the_wave_timeout_to_fire_the_next_wave(
        tmp_path, monkeypatch):
    """Part 3's holder accepts and never answers: at the wave timeout
    the seven parts the call has finished are harvested from the call
    still in flight, the next wave's parity part completes the plan,
    and the worker is joined (its sockets shut) before ``execute_plan``
    returns."""
    cluster, c, inode, payload = await _ec84_file(tmp_path)
    try:
        info, slice_type, locations = await _located(c, inode)
        waves, returned = _spy_on_waves(monkeypatch)
        with _MutePeer() as mute:
            locations[3] = (mute.addr, locations[3][1])
            _warm(*_holders(locations))
            counts = _Counts()
            wanted, plan = _plan(c, slice_type, locations, 0, 4,
                                 len(payload))
            t0 = time.monotonic()
            buf = await execute_plan(plan, info.chunk_id, info.version,
                                     locations, wave_timeout=0.3,
                                     count=counts)
            took = time.monotonic() - t0
        assert _region(buf, wanted, slice_type, 4) == payload
        assert 0.3 <= took < 3.0, took
        assert len(waves) == 1 and returned == waves
        assert [int(r.rc) for r in waves[0].reqs] == [0, 0, 0, -1, 0, 0, 0, 0]
        assert counts == {"wave_native_fallback": 1, "wave_native_parts": 7}
    finally:
        await cluster.stop()


async def test_mute_peer_reads_not_finished_at_the_calls_deadline():
    """The call's own deadline (the plan's total timeout): a part whose
    peer never answers reads -4 there, and the socket is discarded."""
    with _MutePeer() as mute:
        _warm(mute.addr, mute.addr)
        out = np.zeros(4 * B, dtype=np.uint8)
        wave = native_io.PartsWave(
            [mute.addr] * 2, 7, 1, [1, 2], [0, 0], [2 * B, 2 * B], out,
            [0, 2 * B], max_ms=200,
        )
        t0 = time.monotonic()
        await native_io.run(native_io.read_parts_wave_blocking, wave)
        assert 0.15 <= time.monotonic() - t0 < 3.0
        assert [wave.outcome(i) for i in range(2)] == [-4, -4]
        assert not out.any()
        assert native_io.POOL.try_acquire(mute.addr) is None, "discarded"
        assert "deadline" in str(native_io.NativeIOError(-4, "read"))


async def test_dead_pooled_socket_is_dialled_again_once(tmp_path):
    """A chunkserver's data plane restarts under the pooled sockets:
    the wave call sees a socket error on that part alone and hands it
    to a task of its own, which dials afresh once and reads it; the
    parts that had ended are not read again."""
    from lizardfs_tpu.chunkserver import native_serve

    cluster, c, inode, payload = await _ec84_file(tmp_path)
    try:
        info, slice_type, locations = await _located(c, inode)

        async def read():
            counts = _Counts()
            wanted, plan = _plan(c, slice_type, locations, 0, 4,
                                 len(payload))
            buf = await execute_plan(plan, info.chunk_id, info.version,
                                     locations, count=counts)
            assert _region(buf, wanted, slice_type, 4) == payload
            return counts

        _warm(*_holders(locations))
        assert await read() == {"wave_native": 1, "wave_native_parts": 8}
        port = locations[5][0][1]
        cs = next(cs for cs in cluster.chunkservers
                  if cs.data_server.port == port)
        await asyncio.to_thread(cs.data_server.stop)
        cs.data_server = native_serve.DataPlaneServer(
            [s.folder for s in cs.store.stores], cs.host, port)
        pool = native_io.POOL
        d0 = pool.dials
        assert await read() == {"wave_native_fallback": 1,
                                "wave_native_parts": 7}
        assert pool.dials - d0 == 1, "one part, one fresh dial"
        assert await read() == {"wave_native": 1, "wave_native_parts": 8}
        assert pool.dials - d0 == 1
    finally:
        await cluster.stop()


class _Op:
    def __init__(self, size):
        self.request_size = size


@pytest.mark.parametrize("sizes,loads", [
    ([256 * 1024] * 8, [8]),              # a 2 MiB read: one worker
    ([2**20] * 8, [8]),                   # 8 MiB: still one
    ([2 * 2**20] * 8, [4, 4]),            # 16 MiB: two
    ([8 * 2**20] * 8, [1] * 8),           # a rebuild: a worker a part
    ([8 * 2**20 + B] * 2, [1, 1]),        # a part over the budget
    ([6 * 2**20, 2**20, 2 * 2**20, 2**20], [2, 2]),  # in order, no sort
])
def test_worker_loads_follow_the_bytes_a_wave_holds(sizes, loads):
    got = read_executor._worker_loads([_Op(size) for size in sizes])
    assert [len(load) for load in got] == loads
    assert [op.request_size for load in got for op in load] == sizes


async def test_a_wave_over_the_worker_budget_is_split_between_workers(
        tmp_path, monkeypatch):
    """Eight parts of 256 KiB under a budget of 512 KiB: four native
    calls of two parts, one wave in the counts, the bytes right; a
    corrupted block fails its part alone and the other calls' parts
    count."""
    cluster, c, inode, payload = await _ec84_file(tmp_path)
    try:
        info, slice_type, locations = await _located(c, inode)
        monkeypatch.setattr(read_executor, "WAVE_WORKER_BYTES", 512 * 1024)
        waves, returned = _spy_on_waves(monkeypatch)

        async def read():
            _warm(*_holders(locations))
            del waves[:], returned[:]
            counts = _Counts()
            wanted, plan = _plan(c, slice_type, locations, 0, 4,
                                 len(payload))
            buf = await execute_plan(plan, info.chunk_id, info.version,
                                     locations, count=counts)
            assert _region(buf, wanted, slice_type, 4) == payload
            assert [len(w.reqs) for w in waves] == [2] * 4
            assert len(returned) == 4
            return counts

        assert await read() == {"wave_native": 1, "wave_native_parts": 8}
        path = _find_part_files(cluster, info.chunk_id)[locations[5][1]]
        with open(path, "r+b") as f:
            f.seek(HEADER_SIZE + 3)
            f.write(b"\xff\xfe")
        assert await read() == {"wave_native_fallback": 1,
                                "wave_native_parts": 7}
    finally:
        await cluster.stop()


async def test_cold_pool_reads_a_task_a_part_and_warms_the_pool(tmp_path):
    """No idle socket to any holder: the wave starts no worker of its
    own and reads a task a part, as before; their sockets go to the
    pool, and the next wave is one native call."""
    cluster, c, inode, payload = await _ec84_file(tmp_path)
    try:
        info, slice_type, locations = await _located(c, inode)
        _drain(*_holders(locations))
        for want in ({"wave_native_fallback": 1, "wave_native_parts": 0},
                     {"wave_native": 1, "wave_native_parts": 8}):
            counts = _Counts()
            wanted, plan = _plan(c, slice_type, locations, 0, 4,
                                 len(payload))
            buf = await execute_plan(plan, info.chunk_id, info.version,
                                     locations, count=counts)
            assert _region(buf, wanted, slice_type, 4) == payload
            assert counts == want
    finally:
        await cluster.stop()


async def test_a_dial_that_hangs_costs_its_part_alone(
        tmp_path, monkeypatch):
    """A holder that died silently: the pool holds no socket to it and
    the dial hangs. The part dials on a thread of its own, the seven
    others ride the native call at once, the wave timeout fires the
    next wave and the read ends there, not at the dial's end; and so
    does every later read."""
    cluster, c, inode, payload = await _ec84_file(tmp_path)
    try:
        info, slice_type, locations = await _located(c, inode)
        with _MutePeer() as gone:
            dead = gone.addr
        locations[4] = (dead, locations[4][1])
        real = native_io._blocking_socket

        def dial(addr, io_timeout):
            if addr == dead:
                time.sleep(4.0)
                raise TimeoutError("timed out")
            return real(addr, io_timeout)

        monkeypatch.setattr(native_io, "_blocking_socket", dial)
        _warm(*(a for a in _holders(locations) if a != dead))
        for _ in range(2):
            counts = _Counts()
            wanted, plan = _plan(c, slice_type, locations, 0, 4,
                                 len(payload))
            t0 = time.monotonic()
            buf = await execute_plan(plan, info.chunk_id, info.version,
                                     locations, wave_timeout=0.3,
                                     count=counts)
            took = time.monotonic() - t0
            assert _region(buf, wanted, slice_type, 4) == payload
            assert 0.3 <= took < 1.5, took
            assert counts == {"wave_native_fallback": 1,
                              "wave_native_parts": 7}
    finally:
        await cluster.stop()


async def test_cancelled_plan_has_joined_its_worker(tmp_path, monkeypatch):
    """Cancelling ``execute_plan`` while the wave call waits for a mute
    peer shuts the sockets and joins the worker: when the cancellation
    surfaces, nothing writes the plan buffer any more."""
    cluster, c, inode, payload = await _ec84_file(tmp_path)
    try:
        info, slice_type, locations = await _located(c, inode)
        waves, returned = _spy_on_waves(monkeypatch)
        with _MutePeer() as mute:
            locations[0] = (mute.addr, locations[0][1])
            _warm(*_holders(locations))
            wanted, plan = _plan(c, slice_type, locations, 0, 4,
                                 len(payload))
            buffer = np.zeros(plan.buffer_size, dtype=np.uint8)
            counts = _Counts()
            task = asyncio.ensure_future(execute_plan(
                plan, info.chunk_id, info.version, locations,
                wave_timeout=30.0, buffer=buffer, count=counts))
            for _ in range(200):  # seven parts land, the eighth never
                await asyncio.sleep(0.01)
                if waves and sum(r.rc == 0 for r in waves[0].reqs) == 7:
                    break
            assert not task.done()
            t0 = time.monotonic()
            task.cancel()
            with pytest.raises(asyncio.CancelledError):
                await task
            assert time.monotonic() - t0 < 5.0
        assert returned == waves, "the worker outlived the plan"
        assert not counts, "a cancelled plan's waves are not counted"
        sentinel = data_generator.generate(99, buffer.nbytes)
        buffer[:] = sentinel
        await asyncio.sleep(0.3)
        np.testing.assert_array_equal(buffer, sentinel)
    finally:
        await cluster.stop()


async def test_abort_before_the_worker_runs_reads_nothing():
    """An abort that lands before the worker runs finds the sockets
    already published: no request leaves, the buffer stays as it was,
    every part reads a socket error and its socket is discarded."""
    with _MutePeer() as mute:
        _warm(mute.addr, mute.addr)
        out = np.full(4 * B, 0x77, dtype=np.uint8)
        wave = native_io.PartsWave(
            [mute.addr] * 2, 7, 1, [1, 2], [0, 0], [2 * B, 2 * B], out,
            [0, 2 * B], max_ms=10_000,
        )
        assert len(wave.cell["socks"]) == 2
        native_io.abort_parts_gather(wave.cell)
        await native_io.run(native_io.read_parts_wave_blocking, wave)
        assert [wave.outcome(i) for i in range(2)] == [-1, -1]
        assert np.all(out == 0x77)
        assert native_io.POOL.try_acquire(mute.addr) is None


async def test_unreachable_holder_fails_its_part_alone(tmp_path):
    """A holder that refuses the dial costs its own part, on a task of
    its own: the others are read by the one call and a fallback wave
    completes the plan."""
    cluster, c, inode, payload = await _ec84_file(tmp_path)
    try:
        info, slice_type, locations = await _located(c, inode)
        with _MutePeer() as gone:
            dead = gone.addr
        locations[6] = (dead, locations[6][1])
        _warm(*(a for a in _holders(locations) if a != dead))
        counts = _Counts()
        seen = []
        wanted, plan = _plan(c, slice_type, locations, 0, 4, len(payload))
        buf = await execute_plan(
            plan, info.chunk_id, info.version, locations, count=counts,
            on_part_failure=lambda part, wire, addr, exc: seen.append(
                (part, exc.crc)),
        )
        assert _region(buf, wanted, slice_type, 4) == payload
        assert seen == [(6, False)]
        assert counts == {"wave_native_fallback": 1, "wave_native_parts": 7}
    finally:
        await cluster.stop()


async def test_too_many_failed_parts_raise(tmp_path):
    """Five of twelve holders unreachable at $ec(8,4): the plan cannot
    finish, and says so as the per-part path does."""
    cluster, c, inode, payload = await _ec84_file(tmp_path)
    try:
        info, slice_type, locations = await _located(c, inode)
        with _MutePeer() as gone:
            dead = gone.addr
        for p in (0, 1, 2, 8, 9):
            locations[p] = (dead, locations[p][1])
        wanted, plan = _plan(c, slice_type, locations, 0, 4, len(payload))
        with pytest.raises(ReadError):
            await execute_plan(plan, info.chunk_id, info.version, locations)
    finally:
        await cluster.stop()


async def test_a_plan_that_fails_where_its_wave_starts_has_joined_its_worker(
        tmp_path, monkeypatch):
    """Parts that read as failed the moment their wave is built (here
    every part, by a ``PartsWave`` that says so) make the plan raise
    from inside ``start_wave``: the worker, held by a mute peer, is
    aborted and joined before the error surfaces."""
    cluster, c, inode, payload = await _ec84_file(tmp_path)
    try:
        info, slice_type, locations = await _located(c, inode)
        waves, returned = _spy_on_waves(monkeypatch)

        class Failed(native_io.PartsWave):
            def outcome(self, i):
                return -2

        monkeypatch.setattr(native_io, "PartsWave", Failed)
        with _MutePeer() as mute:
            for p in list(locations):
                locations[p] = (mute.addr, locations[p][1])
            _warm(*_holders(locations))
            wanted, plan = _plan(c, slice_type, locations, 0, 4,
                                 len(payload))
            t0 = time.monotonic()
            with pytest.raises(ReadError):
                await execute_plan(plan, info.chunk_id, info.version,
                                   locations, wave_timeout=30.0)
            assert time.monotonic() - t0 < 5.0
            # while the peer is still mute: nothing else ends the call
            assert len(waves) == 1 and returned == waves
    finally:
        await cluster.stop()


async def test_wave_plane_span_tree_and_rows(tmp_path):
    """A degraded 2 MiB ``read_file`` at $ec(8,4): ``waves`` holds ONE
    ``hop`` (the one worker) and eight ``net`` spans laid from C's
    clock (``plane="wave"``, ``part``, ``bytes``), each inside
    ``waves``; the rows charge eight ``net`` a read, and the client
    counts the wave."""
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        inode, payload = await _write(cluster, c, WIDE_EC_GOAL, 4 * 2**20)
        info, slice_type, locations = await _located(c, inode)
        holder = locations[1][0][1]
        victim = next(cs for cs in cluster.chunkservers
                      if cs.data_server.port == holder)
        await victim.stop()
        for _ in range(100):
            await asyncio.sleep(0.05)
            if len((await c.chunk_info(inode, 0)).locations) == 11:
                break
        c.cache.invalidate(inode)
        assert await c.read_file(inode, 0, 2 * 2**20) == payload[:2 * 2**20]
        c.cache.invalidate(inode)
        c.trace_ring.clear()
        before = c.read_phases.snapshot()
        n0 = c.op_counters.get("wave_native", 0)
        got = await c.read_file(inode, 2 * 2**20, 2 * 2**20)
        assert got == payload[2 * 2**20:]
        assert c.op_counters["wave_native"] == n0 + 1
        assert c.op_counters["wave_native_parts"] % 8 == 0
        assert not c.op_counters.get("wave_native_fallback")
        spans = c.trace_ring.dump()
        waves = [s for s in spans if s["name"] == "waves"]
        assert len(waves) == 1
        under = [s for s in spans if s["parent_id"] == waves[0]["span_id"]]
        # one worker: one hop out, eight parts, one way back (PR 36)
        assert sorted(s["name"] for s in under) == (
            ["hop"] + ["net"] * 8 + ["wake"])
        wake = [s for s in under if s["name"] == "wake"][0]
        assert wake["attrs"]["after"] == "thread"
        last = max(s["t1"] for s in under if s["name"] == "net")
        assert abs(wake["t0"] - last) < 1e-3   # from where C saw it end
        for s in under:
            if s["name"] != "net":
                continue
            assert s["attrs"]["plane"] == "wave"
            assert s["attrs"]["bytes"] == 256 * 1024
            assert waves[0]["t0"] - 1e-3 <= s["t0"] <= s["t1"]
            assert s["t1"] <= waves[0]["t1"] + 1e-3
        assert len({s["attrs"]["part"] for s in under
                    if s["name"] == "net"}) == 8
        delta = phase_delta(c.read_phases.snapshot(), before)
        assert delta["reps"] == 1 and delta["net_ms"] > 0
        if tracing.enabled():
            assert delta["net_ms"] <= 8 * delta["waves_ms"] + 1.0
    finally:
        await cluster.stop()


@pytest.mark.parametrize("sizes,out_offsets", [
    ([2 * B, 2 * B], [0, 3 * B]),   # the second part ends past the buffer
    ([2 * B, 0], [0, 2 * B]),       # a part of no bytes never goes native
    ([2 * B, 2 * B], [0]),          # lists of different lengths
])
def test_a_wave_that_would_land_outside_its_buffer_is_refused(
        sizes, out_offsets):
    """C writes through raw pointers, so the bounds are checked where
    the pointers are made: no socket is taken, no thread started."""
    out = np.zeros(4 * B, dtype=np.uint8)
    with pytest.raises(ValueError):
        native_io.PartsWave([("127.0.0.1", 1)] * 2, 7, 1, [1, 2], [0, 0],
                            sizes, out, out_offsets, max_ms=100)
