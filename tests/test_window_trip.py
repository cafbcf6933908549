"""The windowed whole-chunk write takes one trip to a worker thread a
segment (``PartsScatterSession.window_trip``): the segment's encode, its
send and the reap of the oldest segments the window's depth no longer
allows run there in a row, the chunk's open before the first segment and
its finish after the last. The loop stages the ring views, takes the
credits before the trip and settles what the worker reaped. A full ring
or a shut credit gate still reaps on the loop, a trip of its own.

Each case holds the bytes on disk to the golden codec
(``striping.split_chunk``, zlib CRC32), the count ``window_trips`` to the
trips made, the credits to being returned exactly once, and the span
tree to the names and phases the three trips a segment had before.
"""

import asyncio
import threading

import numpy as np
import pytest

from lizardfs_tpu.client.write_window import WriteWindow
from lizardfs_tpu.constants import MFSBLOCKSIZE, MFSCHUNKSIZE
from lizardfs_tpu.core import native_io
from lizardfs_tpu.runtime import tracing
from lizardfs_tpu.runtime.metrics import phase_delta

from tests.test_cluster import Cluster
from tests.test_write_phases import (
    EC32_GOAL, EC84_GOAL, XOR3_GOAL, _assert_parts_match_oracle,
    _write_and_read_back,
)

MiB = 2 ** 20
# goal -> (goal id, chunkservers, data parts)
GOALS = {"ec84": (EC84_GOAL, 13, 8), "ec32": (EC32_GOAL, 6, 3),
         "xor3": (XOR3_GOAL, 4, 3)}
OBJECTS = {"whole_chunk": MFSCHUNKSIZE, "ten_mib": 10 * MiB}

pytestmark = pytest.mark.skipif(
    not native_io.parts_scatter_available(),
    reason="the windowed write needs the native library")


def _payload(nbytes: int, seed: int = 38) -> bytes:
    return np.random.default_rng([seed, nbytes]).integers(
        0, 256, nbytes, dtype=np.uint8).tobytes()


def _segments(length: int, data_parts: int) -> int:
    """The window's segments for a chunk of ``length`` bytes: at most
    eight, slot-aligned, over the longest part's blocks."""
    blocks = -(-(-(-length // MFSBLOCKSIZE)) // data_parts)
    seg = -(-blocks // min(8, blocks))
    return -(-blocks // seg)


def _loop_reaps(client) -> list:
    """Count the reaps the loop makes on trips of their own."""
    calls = []
    orig = client._window_collect

    async def counted(*args):
        calls.append(1)
        return await orig(*args)

    client._window_collect = counted
    return calls


def _tally_credits(win) -> dict:
    """Every credit the window hands out and takes back, by kind."""
    tally = {"taken": 0, "returned": 0, "bytes_taken": 0.0,
             "bytes_returned": 0.0}
    try_acquire, acquire, release = win.try_acquire, win.acquire, win.release

    def tried(addrs, nbytes):
        ok = try_acquire(addrs, nbytes)
        if ok:
            tally["taken"] += len(addrs)
            tally["bytes_taken"] += nbytes
        return ok

    async def waited(addrs, nbytes):
        await acquire(addrs, nbytes)
        tally["taken"] += len(addrs)
        tally["bytes_taken"] += nbytes

    def returned(addrs, nbytes):
        tally["returned"] += len(addrs)
        tally["bytes_returned"] += nbytes
        release(addrs, nbytes)

    win.try_acquire, win.acquire, win.release = tried, waited, returned
    return tally


def _assert_credits_home(win, tally) -> None:
    assert tally["taken"] > 0
    assert tally["returned"] == tally["taken"]
    assert tally["bytes_returned"] == tally["bytes_taken"]
    assert all(b.available == b.capacity for b in win._cs.values())
    assert win._budget.available == win._budget.capacity


@pytest.mark.asyncio
@pytest.mark.parametrize("depth", [1, 2, 4, 8])
@pytest.mark.parametrize("obj", sorted(OBJECTS))
@pytest.mark.parametrize("goal", sorted(GOALS))
async def test_one_trip_a_segment_stores_what_the_golden_codec_does(
        tmp_path, goal, obj, depth):
    goal_id, n_cs, data_parts = GOALS[goal]
    payload = _payload(OBJECTS[obj])
    cluster = Cluster(tmp_path, n_cs=n_cs)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        c.write_window.max_depth = c.write_window.depth = depth
        reaps = _loop_reaps(c)
        tally = _tally_credits(c.write_window)
        before = c.write_phases.snapshot()
        inode = await _write_and_read_back(
            cluster, c, goal_id, f"{goal}_{obj}_{depth}.bin", payload)
        d = phase_delta(c.write_phases.snapshot(), before)
        await _assert_parts_match_oracle(
            cluster, c, inode, payload, f"{goal} {obj} depth {depth}")
        assert (d["window_chunks"], d["fallback_chunks"]) == (1, 0)
        assert d["window_segments"] == _segments(len(payload), data_parts)
        # a trip a segment, and one for each reap the loop made itself
        assert d["window_trips"] == d["window_segments"] + len(reaps)
        _assert_credits_home(c.write_window, tally)
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_a_chunk_with_no_gate_shut_takes_a_trip_a_segment(tmp_path):
    """8 MiB at $ec(8,4), no ring full and no credit wait: eight trips,
    each a ``hop`` out and a ``wake`` back under the ``write_file``
    root, and under the root the spans the three trips a segment had:
    an ``encode`` and a ``send`` a segment, an ``ack`` a segment reaped,
    the open (``seg`` 0) and the finish (``seg`` -1) as ``send``."""
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        await _write_and_read_back(cluster, c, EC84_GOAL, "warm.bin",
                                   _payload(8 * MiB, 1))
        reaps = _loop_reaps(c)
        before = c.write_phases.snapshot()
        c.trace_ring.clear()
        await _write_and_read_back(cluster, c, EC84_GOAL, "trips.bin",
                                   _payload(8 * MiB))
        d = phase_delta(c.write_phases.snapshot(), before)
        assert not reaps and d["window_credit_waits"] == 0
        assert d["window_segments"] == d["window_trips"] == 8
        assert c.op_counters["window_trips"] >= 16
        spans = c.trace_ring.dump()
        root, = [s for s in spans if s["name"] == "write_file"]
        under = {}
        for s in spans:
            if s["parent_id"] == root["span_id"]:
                under.setdefault(s["name"], []).append(s)
        assert len(under["hop"]) == len(under["wake"]) == 8
        assert all(w["attrs"]["after"] == "thread" for w in under["wake"])
        assert sorted(s["attrs"]["seg"] for s in under["encode"]) == \
            list(range(1, 9))
        assert sorted(s["attrs"]["seg"] for s in under["send"]) == \
            [-1] + list(range(9))
        assert sorted(s["attrs"]["seg"] for s in under["ack"]) == \
            list(range(1, 9))
        assert "credit" not in under
        for phase in ("encode", "send", "ack", "hop", "wake"):
            assert d[f"{phase}_ms"] > 0.0, phase
    finally:
        await cluster.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("where", ["send", "reap"])
async def test_a_trip_that_raises_returns_every_credit_once(
        tmp_path, where, depth):
    """The third segment's send raises inside its trip, or the second
    reap raises after its trip's send: every credit the window took
    comes back once, the whole-part rewrite heals the torn chunk, and
    the file reads back whole."""
    payload = _payload(8 * MiB + 3 * MFSBLOCKSIZE)
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        c.write_window.max_depth = c.write_window.depth = depth
        tally = _tally_credits(c.write_window)
        name, nth = (("send_segment_window", 3) if where == "send"
                     else ("collect_acks", 2))
        orig = getattr(native_io.PartsScatterSession, name)
        calls = {"n": 0}

        def raises_once(self, *args, **kw):
            calls["n"] += 1
            if calls["n"] == nth:
                self.close()
                raise native_io.NativeIOError(-1, "injected")
            return orig(self, *args, **kw)

        setattr(native_io.PartsScatterSession, name, raises_once)
        before = c.write_phases.snapshot()
        try:
            inode = await _write_and_read_back(
                cluster, c, EC84_GOAL, f"torn_{where}.bin", payload)
        finally:
            setattr(native_io.PartsScatterSession, name, orig)
        d = phase_delta(c.write_phases.snapshot(), before)
        assert calls["n"] == nth
        assert (d["window_chunks"], d["fallback_chunks"]) == (0, 1)
        assert c.op_counters.get("write_pipeline_fallback") == 1
        _assert_credits_home(c.write_window, tally)
        await _assert_parts_match_oracle(cluster, c, inode, payload, where)
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_a_put_cancelled_mid_trip_aborts_and_pools_no_parity(
        tmp_path):
    """A whole chunk's PUT is cancelled while its second trip's send is
    held in the worker: the exchange is aborted, the worker sends
    nothing more once let go, and the parity buffer it could still read
    is not pooled (a write that ends pools it)."""
    part_len = MFSCHUNKSIZE // 8
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        await _write_and_read_back(cluster, c, EC84_GOAL, "whole.bin",
                                   _payload(MFSCHUNKSIZE))
        assert len(c._stage_buffers[(4, part_len)]) == 1
        f = await c.create(1, "cancelled.bin")
        await c.setgoal(f.inode, EC84_GOAL)
        orig = native_io.PartsScatterSession.send_segment_window
        held, let_go, cells, sent = (threading.Event(), threading.Event(),
                                     [], [])

        def hold_second(self, *args, **kw):
            if args[3] == 2:
                cells.append(self.cell)
                held.set()
                let_go.wait(30.0)
            orig(self, *args, **kw)
            sent.append(args[3])

        native_io.PartsScatterSession.send_segment_window = hold_second
        try:
            task = asyncio.ensure_future(
                c.write_file(f.inode, _payload(MFSCHUNKSIZE, 2)))
            while not held.is_set():
                await asyncio.sleep(0.01)
            task.cancel()
            with pytest.raises(asyncio.CancelledError):
                await task
            cell, = cells
            assert cell["aborted"]
            assert not c._stage_buffers[(4, part_len)]
            let_go.set()
            for _ in range(500):
                if cell.get("finished"):
                    break
                await asyncio.sleep(0.01)
        finally:
            let_go.set()
            native_io.PartsScatterSession.send_segment_window = orig
        assert cell["finished"] and sent == [1]
        assert not c._stage_buffers[(4, part_len)]
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_two_writes_that_exhaust_the_credits_both_finish(tmp_path):
    """One credit a chunkserver and two 10 MiB chunk writes side by
    side: each holds outstanding segments and reaps on the loop where
    the gate is shut, never waits holding credits, and both finish."""
    payload = _payload(10 * MiB)
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        c.write_window.cs_credits = 1
        tally = _tally_credits(c.write_window)
        reaps = _loop_reaps(c)
        before = c.write_phases.snapshot()

        async def one(name):
            f = await c.create(1, name)
            await c.setgoal(f.inode, EC84_GOAL)
            await c.write_file(f.inode, payload)
            return f.inode

        inodes = await asyncio.wait_for(
            asyncio.gather(one("a.bin"), one("b.bin")), 120.0)
        d = phase_delta(c.write_phases.snapshot(), before)
        assert d["window_chunks"] == 2 and d["window_credit_waits"] > 0
        assert d["window_trips"] == d["window_segments"] + len(reaps)
        assert reaps
        _assert_credits_home(c.write_window, tally)
        for inode in inodes:
            c.cache.invalidate(inode)
            assert await c.read_file(inode, 0, len(payload)) == payload
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_a_full_ring_reaps_on_the_loop_and_counts_that_trip(
        tmp_path, monkeypatch):
    """The third segment finds its ring full once: the loop reaps the
    oldest segment on a trip of its own (an ``ack`` under the root, a
    ninth ``hop`` and ``wake``) and stages again."""
    if not native_io.parts_shm_available():
        pytest.skip("native shm ring not built")
    orig = native_io.PartsScatterSession.ring_stage
    refused = []

    def full_once(self, write_id, *args, **kw):
        if write_id == 3 and not refused:
            refused.append(write_id)
            return None
        return orig(self, write_id, *args, **kw)

    monkeypatch.setattr(native_io.PartsScatterSession, "ring_stage",
                        full_once)
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        c.write_window.max_depth = c.write_window.depth = 2
        reaps = _loop_reaps(c)
        before = c.write_phases.snapshot()
        c.trace_ring.clear()
        inode = await _write_and_read_back(
            cluster, c, EC84_GOAL, "ring_full.bin", _payload(8 * MiB))
        d = phase_delta(c.write_phases.snapshot(), before)
        assert refused == [3] and len(reaps) == 1
        assert d["window_segments"] == 8 and d["window_trips"] == 9
        assert d["socket_parts"] == 0 and d["ring_parts"] == 8 * 12
        spans = c.trace_ring.dump()
        root, = [s for s in spans if s["name"] == "write_file"]
        wakes = [s for s in spans if s["name"] == "wake"
                 and s["parent_id"] == root["span_id"]]
        assert len(wakes) == 9
        await _assert_parts_match_oracle(cluster, c, inode,
                                         _payload(8 * MiB), "ring full")
    finally:
        await cluster.stop()


@pytest.mark.parametrize("ceiling,start,floor", [(8, 8, 2), (8, 2, 2),
                                                  (1, 1, 1)])
def test_an_encode_bound_window_stays_double_buffered(ceiling, start, floor):
    """The worker times the encode with its waits for the GIL in it, so
    the controller can read a window as encode-bound: it shrinks to two
    (a trip then reaps the segment before its own) and never to one,
    where each trip would wait for its own segment's acks; a ceiling of
    one stays one."""
    win = WriteWindow()
    win.max_depth, win.depth = ceiling, start
    for _ in range(40):
        win.observe(0.0193, 0.0143)
    assert win.depth == floor
    for _ in range(40):
        win.observe(0.001, 0.02)
    assert win.depth == ceiling


@pytest.mark.asyncio
async def test_untraced_the_trips_count_and_no_span_is_laid(tmp_path):
    """With tracing off (``LZ_TRACE=0``) the count and the rows still
    rise; the ring stays empty."""
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)
    was = tracing.enabled()
    try:
        c = await cluster.client()
        tracing.set_enabled(False)
        c.trace_ring.clear()
        before = c.write_phases.snapshot()
        await _write_and_read_back(cluster, c, EC84_GOAL, "untraced.bin",
                                   _payload(8 * MiB))
        d = phase_delta(c.write_phases.snapshot(), before)
        assert d["window_trips"] >= d["window_segments"] == 8
        assert d["encode_ms"] > 0.0 and d["send_ms"] > 0.0
        assert d.get("wake_ms", 0.0) == 0.0
        assert len(c.trace_ring) == 0
    finally:
        tracing.set_enabled(was)
        await cluster.stop()
