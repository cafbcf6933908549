"""The loop meter (PR 36): every turn of an asyncio loop stamped where
the loop polls, the hold a synchronous span puts on the loop, and the
way back from a thread or a reply as a ``wake`` span.

runtime/tracing.py ``LoopMeter`` / ``Span`` (``<name>_hold``) / ``Hop``
/ ``wake``; runtime/rpc.py stamps a reply where the pump hands it over;
client/client.py attaches the meter and lays the RPC's ``wake``;
runtime/daemon.py feeds ``loop_lag_ms`` and ``loop_busy_pct`` from it.
"""

import asyncio
import threading
import time

import pytest

from lizardfs_tpu.proto import framing, messages as m
from lizardfs_tpu.runtime import tracing
from lizardfs_tpu.runtime.metrics import PhaseBreakdown, phase_delta
from lizardfs_tpu.runtime.rpc import RpcConnection

from tests.test_cluster import Cluster, EC_GOAL


def _sink():
    rows = PhaseBreakdown("t", {"a": None})
    ring = tracing.SpanRing()
    return tracing.OpSink(rows, ring, "client"), rows, ring


def _busy(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _spans(ring, name):
    return [s for s in ring.dump() if s["name"] == name]


# --- part 1: the turns ---------------------------------------------------------


@pytest.mark.parametrize("planted_ms", [0, 30])
@pytest.mark.asyncio
async def test_meter_counts_turns_where_the_loop_polls(planted_ms):
    """A planted blocking callback is one turn of its length: busy and
    the sum of squares follow. An idle loop reads busy under 5 %."""
    meter = tracing.attach_meter()
    assert meter is tracing.attach_meter() is tracing.loop_meter()
    await asyncio.sleep(0.01)
    before, t0 = meter.counts(), time.perf_counter()
    if planted_ms:
        asyncio.get_running_loop().call_soon(time.sleep, planted_ms / 1e3)
    for _ in range(10):
        await asyncio.sleep(0.02)
    window_us = (time.perf_counter() - t0) * 1e6
    got = {k: v - before[k] for k, v in meter.counts().items()}
    assert got["loop_turns"] >= 10
    assert got["loop_offcpu_us"] <= got["loop_busy_us"]
    if not planted_ms:
        assert got["loop_busy_us"] < 0.05 * window_us
        return
    us = planted_ms * 1e3
    assert us <= got["loop_busy_us"] < us + 0.05 * window_us
    # one turn holds the whole sleep: the squares say so (ten turns of
    # a tenth each would sum to a tenth of this)
    assert got["loop_turn_sq_us2"] >= us * us
    assert got["loop_turn_sq_us2"] <= got["loop_busy_us"] ** 2
    # a sleeping thread is off the CPU: nearly all of that turn
    assert got["loop_offcpu_us"] >= 0.8 * us


@pytest.mark.asyncio
async def test_a_thread_that_spins_on_the_gil_raises_offcpu():
    """The loop thread's share of a turn it spent waiting for the GIL
    is wall less its own CPU time."""
    meter = tracing.attach_meter()
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            pass

    await asyncio.sleep(0)
    before = meter.counts()
    _busy(0.1)
    await asyncio.sleep(0)
    alone = {k: v - before[k] for k, v in meter.counts().items()}
    thread = threading.Thread(target=spin, daemon=True)
    thread.start()
    try:
        await asyncio.sleep(0)
        before = meter.counts()
        _busy(0.1)
        await asyncio.sleep(0)
        shared = {k: v - before[k] for k, v in meter.counts().items()}
    finally:
        stop.set()
        thread.join()
    assert alone["loop_busy_us"] >= 100_000 <= shared["loop_busy_us"]
    # a fifth of the turn at the least, against the GIL's 5 ms turns
    assert shared["loop_offcpu_us"] >= 20_000
    assert shared["loop_offcpu_us"] > alone["loop_offcpu_us"]


def test_two_loops_in_one_process_do_not_read_each_others_turns():
    seen = []

    async def one(turns):
        meter = tracing.attach_meter()
        for _ in range(turns):
            await asyncio.sleep(0)
        with tracing.span("op", sink=_sink()[0]) as sp:
            pass
        seen.append((meter, meter.turn, sp._meter))

    asyncio.run(one(50))
    assert tracing.loop_meter() is None     # gone with its loop
    ended = seen[0][0].counts()
    asyncio.run(one(5))
    (m1, turn1, of1), (m2, turn2, of2) = seen
    assert m1 is not m2 and of1 is m1 and of2 is m2
    assert turn1 >= 50 > turn2 >= 5
    assert m1.counts() == ended             # the first stood still
    assert tracing.loop_meter() is None


# --- part 2: the hold -----------------------------------------------------------


@pytest.mark.parametrize("case", ["synchronous", "suspends", "nested",
                                  "laid_after_the_fact", "on_a_worker"])
@pytest.mark.asyncio
async def test_a_span_that_never_gave_the_loop_back_charges_its_hold(case):
    tracing.attach_meter()
    tracing.clear_trace()
    sink, rows, ring = _sink()
    with tracing.span("op", sink=sink):
        if case == "synchronous":
            with tracing.span("work", phase="a"):
                _busy(0.004)
        elif case == "suspends":
            with tracing.span("work", phase="a"):
                _busy(0.004)
                await asyncio.sleep(0)
        elif case == "nested":
            with tracing.span("work", phase="a"):
                _busy(0.002)
                with tracing.span("inner"):
                    _busy(0.003)
        elif case == "laid_after_the_fact":
            t = time.perf_counter()
            tracing.span("work", phase="a").begin(at=t - 0.004).end()
        else:
            def work():
                with tracing.span("work", phase="a"):
                    _busy(0.004)
            await tracing.hop(work)
    snap = rows.snapshot()
    assert snap["a_ms"] >= 3.9
    assert "op_hold_ms" not in snap or case in ("synchronous", "nested",
                                                "laid_after_the_fact")
    if case == "synchronous":
        assert snap["work_hold_ms"] == pytest.approx(snap["a_ms"], abs=0.05)
    elif case == "nested":
        # each its self time: together the outer's length, once
        assert snap["inner_hold_ms"] >= 2.9
        assert snap["work_hold_ms"] + snap["inner_hold_ms"] == \
            pytest.approx(snap["a_ms"], abs=0.05)
    else:
        assert "work_hold_ms" not in snap


# --- part 3: the way back --------------------------------------------------------


@pytest.mark.parametrize("phase", ["hop", "hop_compute"])
@pytest.mark.asyncio
async def test_wake_after_a_thread_reads_the_callback_in_its_way(phase):
    """The worker ends 10 ms in; a callback planted 5 ms in holds the
    loop for 50 ms: the coroutine that waited runs again 45 ms after
    the work was done, and ``wake`` says so, under the span that
    waited, with the turns that opened meanwhile."""
    tracing.attach_meter()
    tracing.clear_trace()
    sink, rows, ring = _sink()
    with tracing.span("op", sink=sink):
        await tracing.hop(time.sleep, 0.0)     # the pool's thread is up
        ring.clear()
        asyncio.get_running_loop().call_later(0.005, time.sleep, 0.05)
        with tracing.span("waited"):
            await tracing.hop(time.sleep, 0.01, phase=phase)
    (wake,) = _spans(ring, "wake")
    (waited,) = _spans(ring, "waited")
    (out,) = _spans(ring, phase)
    assert wake["parent_id"] == out["parent_id"] == waited["span_id"]
    assert wake["bucket"] == out["bucket"] == "queue"
    assert wake["attrs"]["after"] == "thread"
    assert wake["attrs"]["turns"] >= 1
    assert 35.0 <= (wake["t1"] - wake["t0"]) * 1e3 <= 60.0
    snap = rows.snapshot()
    assert snap["wake_ms"] >= 35.0 and snap[phase + "_ms"] >= 0.0
    # wake is covered time: what is left of the span that waited is
    # the hop's bookkeeping, not the 45 ms
    assert waited["self_ms"] < 15.0


@pytest.mark.asyncio
async def test_wake_from_where_the_native_call_ended():
    """A worker whose native call reported its own end: ``wake`` opens
    there, and ``wake_gil`` under it runs to the worker's first reading
    after the call."""
    tracing.attach_meter()
    tracing.clear_trace()
    sink, rows, ring = _sink()
    marks = {}

    def work():
        t = time.perf_counter()
        time.sleep(0.004)       # "the GIL": the call ended at t
        marks["end"], marks["now"] = t, time.perf_counter()
        tracing.native_end(t, marks["now"])

    with tracing.span("op", sink=sink):
        with tracing.span("waited"):
            await tracing.hop(work)
    (wake,) = _spans(ring, "wake")
    (gil,) = _spans(ring, "wake_gil")
    assert gil["parent_id"] == wake["span_id"]
    assert gil["t0"] == pytest.approx(wake["t0"], abs=1e-4)
    assert (gil["t1"] - gil["t0"]) == pytest.approx(
        marks["now"] - marks["end"], abs=1e-4)
    assert wake["t1"] >= gil["t1"]
    assert rows.snapshot()["wake_gil_ms"] >= 3.9


async def _fake_master(replies):
    """A server that answers AdminCommands as ``replies`` tells it:
    a list of (how many requests to read, delay, how many to answer in
    one write)."""
    async def handle(reader, writer):
        try:
            for take, delay, answer in replies:
                reqs = [await framing.read_message(reader)
                        for _ in range(take)]
                handle.pending.extend(reqs)
                await asyncio.sleep(delay)
                out = b""
                for _ in range(answer):
                    req = handle.pending.pop(0)
                    out += framing.encode(m.AdminReply(
                        req_id=req.req_id, status=0, json=req.command))
                writer.write(out)
                await writer.drain()
            await reader.read()
        finally:
            writer.close()
    handle.pending = []
    server = await asyncio.start_server(handle, "127.0.0.1", 0)
    return server, server.sockets[0].getsockname()[1]


@pytest.mark.asyncio
async def test_pipelined_replies_keep_their_own_stamps_and_wake():
    """Two calls in flight: the replies come 30 ms apart and each
    carries the stamp of its own hand-over. Then two replies in one
    segment: the pump hands both over in one step, the first caller
    holds the loop for 30 ms as it runs again, and the second's
    ``wake`` reads that, one turn on."""
    tracing.attach_meter()
    tracing.clear_trace()
    sink, rows, ring = _sink()
    server, port = await _fake_master([(2, 0.0, 1), (0, 0.03, 1),
                                       (2, 0.0, 2)])
    conn = await RpcConnection.connect("127.0.0.1", port)
    try:
        a, b = await asyncio.gather(
            conn.call(m.AdminCommand, command="a", json=""),
            conn.call(m.AdminCommand, command="b", json=""))
        assert (a.json, b.json) == ("a", "b")
        assert 0.02 <= b.woke[0] - a.woke[0] <= 0.2
        assert b.woke[1] > a.woke[1] >= 0

        async def caller(name, hold):
            with tracing.span(name):
                reply = await conn.call(m.AdminCommand, command=name,
                                        json="")
                tracing.wake("rpc", reply.woke)
                _busy(hold)
                return reply

        with tracing.span("op", sink=sink):
            c, d = await asyncio.gather(caller("c", 0.03), caller("d", 0.0))
        assert c.woke[1] == d.woke[1]           # one step of the pump
        by_parent = {w["parent_id"]: w for w in _spans(ring, "wake")}
        first = by_parent[_spans(ring, "c")[0]["span_id"]]
        second = by_parent[_spans(ring, "d")[0]["span_id"]]
        assert first["attrs"] == {"after": "rpc", "turns": 1}
        assert second["attrs"] == {"after": "rpc", "turns": 1}
        assert (first["t1"] - first["t0"]) * 1e3 < 10.0
        assert 29.0 <= (second["t1"] - second["t0"]) * 1e3 <= 60.0
    finally:
        await conn.close()
        server.close()


@pytest.mark.asyncio
async def test_a_master_reply_lays_wake_under_its_rpc_span(tmp_path):
    cluster = Cluster(tmp_path, n_cs=1)
    await cluster.start()
    try:
        c = await cluster.client()
        c.trace_ring.clear()
        before = c.read_phases.snapshot()
        f = await c.create(1, "f")
        await c.lookup(1, "f")
        spans = c.trace_ring.dump()
        rpc = [s for s in spans if s["name"] == "CltomaLookup"][0]
        wake = [s for s in spans if s["name"] == "wake"
                and s["parent_id"] == rpc["span_id"]]
        assert len(wake) == 1
        assert wake[0]["attrs"]["after"] == "rpc"
        assert wake[0]["attrs"]["turns"] >= 1
        assert rpc["t0"] <= wake[0]["t0"] <= wake[0]["t1"] <= rpc["t1"] + 1e-4
        delta = phase_delta(c.read_phases.snapshot(), before)
        assert 0.0 < delta["wake_ms"] <= delta["lookup_ms"]
        assert f.inode
    finally:
        await cluster.stop()


# --- the rider: one loop, counted once ----------------------------------------------


def _loop_counts(clients):
    out = dict.fromkeys(tracing.LOOP_COUNTS, 0)
    for c in clients:
        for snap in (c.write_phases.snapshot(), c.read_phases.snapshot()):
            for k in out:
                out[k] += snap.get(k, 0)
    return out


@pytest.mark.asyncio
async def test_twenty_clients_on_one_loop_publish_its_counts_once(tmp_path):
    """The sum the benchmark's worker makes over every session's rows
    counts the loop once, and goes on doing so when the client that
    showed the counts closes and the next in line takes over."""
    cluster = Cluster(tmp_path, n_cs=1)
    await cluster.start()
    try:
        clients = [await cluster.client() for _ in range(20)]
        meter = tracing.loop_meter()
        assert all(c._loop_meter is meter for c in clients)
        shows = [c for c in clients
                 if "loop_turns" in c.read_phases.snapshot()]
        assert shows == clients[:1]
        s0, m0 = _loop_counts(clients), meter.counts()
        for _ in range(3):
            await asyncio.gather(*(c.getattr(1) for c in clients))
        s1, m1 = _loop_counts(clients), meter.counts()
        assert m1["loop_turns"] - m0["loop_turns"] >= 3
        assert {k: s1[k] - s0[k] for k in s1} == \
            {k: m1[k] - m0[k] for k in m1}
        await clients[0].close()
        cluster.clients.remove(clients[0])
        await asyncio.gather(*(c.getattr(1) for c in clients[1:]))
        s2, m2 = _loop_counts(clients), meter.counts()
        assert {k: s2[k] - s0[k] for k in s2} == \
            {k: m2[k] - m0[k] for k in m2}
        assert "loop_turns" in clients[1].read_phases.snapshot()
        assert clients[0].read_phases.snapshot()["loop_turns"] > 0
    finally:
        await cluster.stop()


# --- LZ_TRACE=0 and the tree ------------------------------------------------------


@pytest.mark.asyncio
async def test_lz_trace_off_keeps_the_counts_and_lays_no_hold_no_wake():
    meter = tracing.attach_meter()
    tracing.clear_trace()
    sink, rows, ring = _sink()
    meter.ride(rows)
    tracing.set_enabled(False)
    try:
        assert tracing.stamp() is None
        with tracing.span("op", sink=sink):
            with tracing.span("work", phase="a"):
                _busy(0.002)
            with tracing.span("waited"):
                await tracing.hop(time.sleep, 0.002, phase="hop_compute")
            tracing.wake("rpc", (time.perf_counter() - 0.01, 0))
    finally:
        tracing.set_enabled(True)
    snap = rows.snapshot()
    assert len(ring) == 0
    assert snap["a_ms"] >= 1.9 and snap["hop_compute_ms"] >= 0.0
    assert not [k for k in snap if k.endswith("_hold_ms")]
    assert "wake_ms" not in snap and "wake_gil_ms" not in snap
    assert snap["loop_turns"] >= 2 and snap["loop_busy_us"] >= 2000


@pytest.mark.asyncio
async def test_phases_still_sum_to_wall_with_wake_in_the_tree(tmp_path):
    """A striped write and a read back: ``wake`` hangs under the spans
    that waited (never under a root), after a thread and after a
    reply, so the top level still sums to the wall."""
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "f")
        await c.setgoal(f.inode, EC_GOAL)
        payload = bytes(range(256)) * 4096 * 3
        await c.pwrite(f.inode, 0, payload)          # warm
        c.trace_ring.clear()
        before = c.write_phases.snapshot(), c.read_phases.snapshot()
        await c.pwrite(f.inode, 0, payload)
        c.cache.invalidate(f.inode)
        assert await c.read_file(f.inode, 0, len(payload)) == payload
        spans = c.trace_ring.dump()
        by_id = {s["span_id"]: s for s in spans}
        wakes = [s for s in spans if s["name"] == "wake"]
        assert {w["attrs"]["after"] for w in wakes} == {"thread", "rpc"}
        for w in wakes:
            parent = by_id[w["parent_id"]]
            assert parent["parent_id"] != 0, parent["name"]
            assert parent["t0"] - 1e-3 <= w["t0"] <= w["t1"] \
                <= parent["t1"] + 1e-3
        for rows, snap0 in zip((c.write_phases, c.read_phases), before):
            d = phase_delta(rows.snapshot(), snap0)
            top = sum(d[p + "_ms"] for p in rows.top_level)
            assert d["reps"] == 1 and d["wake_ms"] > 0.0
            assert top + d["self_ms"] == pytest.approx(d["wall_ms"],
                                                       rel=0.02, abs=0.5)
            assert any(k.endswith("_hold_ms") and v > 0.0
                       for k, v in d.items())
    finally:
        await cluster.stop()


# --- part 4: the daemons ------------------------------------------------------------


@pytest.mark.asyncio
async def test_daemon_lag_and_busy_come_from_the_meter():
    from lizardfs_tpu.runtime.daemon import Daemon

    d = Daemon()
    await d.start()
    try:
        assert d._meter is tracing.loop_meter() and d in d._meter.watchers
        await d._sample_metrics()
        await asyncio.sleep(0.01)
        time.sleep(0.08)        # under the stall warning: lag, no stall
        await asyncio.sleep(0.01)
        await d._watchdog_tick()
        await d._sample_metrics()
        assert 80.0 <= d.metrics.gauge("loop_lag_ms").value < 250.0
        assert d.metrics.gauge("loop_busy_pct").value >= 50.0
        assert d.metrics.counter("loop_stalls").total == 0
        text = d.metrics.to_prometheus()
        assert "lizardfs_loop_busy_pct" in text
    finally:
        await d.stop()
    assert d not in d._meter.watchers
