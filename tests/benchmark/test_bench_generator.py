"""Each traffic mix is data the one generator reads, and its plan is a
pure function of the seed."""

import asyncio
import contextlib
import glob
import json
import os
import time
import types
from collections import Counter

import numpy as np
import pytest

import checks
import generator
import manifest
import redundancy
import rooflines
import worker
from reference.fsmodel import Model, make_pool
from tap import EncoderTap, TapCounts

M = manifest.load_manifest()
_lib = manifest.load_module("layers", "_lib.py")
MIXES = sorted({w["traffic"] for w in M["workloads"]})
SEEDS = (0, 7, 2147483659, 3000000001)


def mix(name):
    """The mix as a cell runs it: the configuration's values put in."""
    cell = next(w["name"] for w in M["workloads"] if w["traffic"] == name)
    return manifest.Cell(M, cell).mix


def test_every_traffic_file_is_some_cells():
    files = {os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(manifest.HERE, "traffic", "*.json"))}
    assert files == set(MIXES)


@pytest.mark.parametrize("name", MIXES)
def test_every_verb_and_fault_of_a_mix_is_a_file(name):
    m = mix(name)
    verbs = generator.verbs_of(m["steps"] + m["check"].get("make_live", []))
    assert verbs
    for v in verbs:
        mod = generator.load_verb(v)
        assert callable(mod.do)
        if getattr(mod, "METADATA", False):
            assert mod.CLASS
    assert {"retain_share", "retain_bytes", "disk_chunks",
            "readback_files"} <= set(m["check"])
    for action in m.get("faults", []):
        assert callable(generator.load_fault(action).apply)


def test_verbs_of_walks_nested_steps():
    steps = ["a", {"verb": "b", "x": 1},
             {"repeat": 2, "steps": ["c", {"each": "batch", "steps": ["d"]}]}]
    assert generator.verbs_of(steps) == ["a", "b", "c", "d"]


def test_barrier_releases_all_and_lets_a_session_leave():
    async def go():
        bar = generator.Barrier(3)
        order = []

        async def party(i, rounds):
            for r in range(rounds):
                await bar.wait()
                order.append((r, i))
            await bar.leave()

        # the third party stops after one round; the others go on
        await asyncio.wait_for(asyncio.gather(
            party(0, 3), party(1, 3), party(2, 1)), 5.0)
        return order

    order = asyncio.run(go())
    assert sorted(order) == [(0, 0), (0, 1), (0, 2), (1, 0), (1, 1),
                             (2, 0), (2, 1)]
    assert {r for r, _i in order[:3]} == {0}


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_plan_is_a_pure_function_of_the_seed(name, seed):
    a, b = generator.plan(mix(name), seed), generator.plan(mix(name), seed)
    assert a == b
    assert len(a.sessions) == mix(name)["sessions"]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes_in_another_order(name):
    plans = [generator.plan(mix(name), s) for s in SEEDS]
    assert all(p.sizes == plans[0].sizes for p in plans)
    if len(plans[0].sizes) > 1:
        orders = {tuple(p.sessions[0].size_order) for p in plans}
        assert len(orders) == len(SEEDS)
        for p in plans:
            for sp in p.sessions:
                assert Counter(sp.size_order) == Counter(
                    range(len(p.sizes)))


def test_loguniform_set_spans_its_ends():
    sizes = generator.size_set(
        {"loguniform": {"min": 16384, "max": 1048576, "count": 48}})
    assert sizes[0] == 16384 and sizes[-1] == 1048576 and len(sizes) == 48
    assert sizes == sorted(sizes)
    ratios = [b / a for a, b in zip(sizes, sizes[1:])]
    assert max(ratios) / min(ratios) < 1.01


def test_warm_loops_cover_every_size():
    m = {"sessions": 5, "sizes": {"loguniform": {
        "min": 1024, "max": 65536, "count": 12}}}
    p = generator.plan(m, 1)
    met = {(s + i * m["sessions"]) % len(p.sizes)
           for s in range(m["sessions"]) for i in range(p.warm_loops)}
    assert met == set(range(len(p.sizes)))


def test_the_mixes_take_their_sizes_from_the_configuration():
    sw, sf = mix("stream-write"), mix("small-files")
    assert sw["sizes"] == {"fixed": 268435456}
    assert sw["transfer_bytes"] == 2097152 and sw["sessions"] == 4
    assert sf["sizes"] == {"fixed": 3901} and sf["sessions"] == 12
    assert sf["steps"][0]["repeat"] == 8


@pytest.mark.parametrize("seed", SEEDS)
def test_pool_is_a_pure_function_of_the_seed(seed):
    a, b = make_pool(seed, 4096 + 3), make_pool(seed, 4096 + 3)
    assert a.dtype == np.uint8 and len(a) == 4099 and np.array_equal(a, b)
    assert not np.array_equal(a, make_pool(seed + 1, 4099))


def test_model_follows_create_write_unlink():
    m = Model(make_pool(5, 1 << 16))
    f = m.create("a", 11)
    assert m.bytes_of(f).size == 0
    m.write("a", 64, 1000)
    assert np.array_equal(m.bytes_of(f), m.pool[64:1064])
    assert np.array_equal(m.bytes_of(f, 900, 500), m.pool[964:1064])
    with pytest.raises(KeyError):
        m.create("a", 12)
    m.write("a", 64, 3000)          # a sequential write extends it
    assert np.array_equal(m.bytes_of(f), m.pool[64:3064])
    assert m.create("b", 12, dir=1).dir == 1 and f.dir == 0
    m.unlink("a")
    assert [g.name for g in m.live()] == ["b"]


def test_percentile_and_rates_are_over_all_the_work():
    ops = [generator.Op("write", 0.0, 1.0, 100_000_000, True),
           generator.Op("write", 0.5, 9.9, 100_000_000, True),
           generator.Op("write", 9.0, 10.4, 100_000_000, True),   # past the close
           generator.Op("read", 1.0, 2.0, 50_000_000, False)]     # failed
    e = worker.end_to_end(ops, 0.0, 10.0)
    assert e["write_MBps"] == pytest.approx(20.0)
    assert e["read_MBps"] == 0.0
    assert e["ops_per_s"] == pytest.approx(0.2)
    assert e["op_p95_ms"] == pytest.approx(9400.0)  # the failed op reads worst
    assert worker.percentile(list(range(1, 101)), 0.95) == 95


# -- PR 33 ------------------------------------------------------------
#
# The cell ``ec84-rebuild-under-write``: an event inside the window,
# the wait for full redundancy, ``rebuild_MBps`` and the readers of
# ``ctx["rebuild"]``, each on hand-made documents; and the tap read
# where the trace stops. Nothing here starts a daemon.

CELL = "ec84-rebuild-under-write"


# -- the cell as BENCHMARK.json names it ---------------------------------

def test_the_cell_and_its_metrics():
    entry = next(w for w in M["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "ec84-13cs", "stream-write-kill", 1)
    cell = manifest.Cell(M, CELL)
    # the foreground's rate through the loss spread wider than half of
    # write_MBps's first bound (0.10; 0.25 since the check refused that as
    # too tight in two accepted cells), so the cell reports it per layer
    assert {m["name"] for m in cell.end_to_end} == {"rebuild_MBps", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= {
        "rebuild_detect_ms", "rebuild_part_ms", "rebuild_after_close_pct",
        "write_MBps.through_loss", "write_slowdown_under_rebuild_pct",
        "write_grant_bumps_pct"}
    assert all(m["moves"] == "rebuild_MBps" for m in cell.per_layer)
    rate = next(m for m in M["end_to_end"] if m["name"] == "rebuild_MBps")
    assert CELL in rate["workloads"] and rate["better"] == "higher"
    assert rate["source"] == "host_clock" and rate["bound"] <= 0.25
    written = next(m for m in M["end_to_end"] if m["name"] == "write_MBps")
    assert CELL not in written["workloads"]


def test_the_mix_is_stream_write_with_one_event():
    kill = manifest.Cell(M, CELL).mix
    plain = manifest.Cell(M, "ec84-stream-write").mix
    for key in ("loop", "sessions", "steps", "sizes", "transfer_bytes"):
        assert kill[key] == plain[key], key
    # the kill fires on the bytes written, not on the clock: the parts
    # lost then do not hang on the writers' rate (half way through a
    # chunk of each of the four writers), with a fallback inside the window
    event, = kill["events"]
    assert event["fault"] == "kill_seeded" and "at_share" not in event
    assert event["at_bytes"] == 42 * 64 * 2**20
    assert event["at_bytes"] // int(kill["sessions"]) % (64 * 2**20) == \
        32 * 2**20
    assert 0.5 <= event["by_share"] <= 0.6
    assert "faults" not in kill, "no throttle: the master's defaults"
    assert kill["redundancy_cap_s"] == 120
    assert kill["check"] == dict(plain["check"], rebuilt_chunks=4)
    assert "events" not in plain


@pytest.mark.parametrize("seed", (0, 7, 2147483659, 3000000001))
def test_kill_seeded_takes_the_seeds_server_and_asks_nothing(seed):
    class Cluster:
        n_cs = 13
        asked = []

        async def kill9_soon(self, name):
            self.asked.append(name)
            return 123.5

        async def admin(self, *_a):
            raise AssertionError("the kill asks the master nothing")

    t = types.SimpleNamespace(seed=seed, cluster=Cluster(), victim=None,
                              kill_at=None, lost_part_chunks=set())
    asyncio.run(generator.load_fault("kill_seeded").apply(t))
    assert t.victim == f"cs{seed % 13}" and t.cluster.asked == [t.victim]
    assert t.kill_at == 123.5 and t.lost_part_chunks == set()


# -- an event inside the window ------------------------------------------

class Nap:
    """A verb that takes 10 ms and counts its calls."""
    calls = 0

    async def do(self, t, s, st, arg, warm):
        await asyncio.sleep(0.01)
        Nap.calls += 1


def traffic_of(events):
    mix = {"sessions": 2, "steps": [], "check": {}}
    t = generator.Traffic(mix, 5, [object(), object()], [], None, 1 << 26)
    t.mix["steps"], t.verbs["nap"] = ["nap"], Nap()
    t.events = events
    return t


def test_an_event_fires_once_at_its_share_beside_the_sessions():
    fired = []

    class Fault:
        async def apply(self, t):
            fired.append(time.monotonic())
            await asyncio.sleep(0.1)     # holds no session meanwhile
            fired.append(("calls", Nap.calls))

    t = traffic_of([(0.25, Fault())])
    Nap.calls = 0
    t_open, t_close = asyncio.run(t.run(0.4))
    assert t_close - t_open == pytest.approx(0.4)
    assert len(fired) == 2 and fired[0] - t_open == pytest.approx(0.1, abs=0.08)
    at_end = fired[1][1]
    assert Nap.calls > at_end > 5, "the sessions ran on beside the event"


def test_a_mix_without_events_starts_no_task():
    t = traffic_of([])
    seen = []

    class Spy(Nap):
        async def do(self, t, s, st, arg, warm):
            seen.append(len(asyncio.all_tasks()))
            await asyncio.sleep(0.01)

    t.verbs["nap"] = Spy()
    asyncio.run(t.run(0.1))
    # the run's own task and one a session, as before events existed
    assert set(seen) == {3}
    assert generator.Traffic({"sessions": 1, "steps": [], "check": {}}, 1,
                             [object()], [], None, 1 << 26).events == []


def test_an_event_that_fails_fails_the_run():
    class Fault:
        async def apply(self, t):
            raise RuntimeError("no such server")

    with pytest.raises(RuntimeError, match="no such server"):
        asyncio.run(traffic_of([(0.1, Fault())]).run(0.3))


class Write(Nap):
    """A verb that writes 1 MiB, acknowledged after 10 ms, timed."""

    async def do(self, t, s, st, arg, warm):
        await t.timed("write", 1 << 20, asyncio.sleep(0.01))


def byte_traffic(at_bytes, by_share, fired, verb=None):
    class Fault:
        async def apply(self, t):
            fired.append((time.monotonic(), t.written))

    t = traffic_of([])
    t.verbs["nap"] = verb or Write()
    t.byte_events = [(at_bytes, by_share, "spy", Fault())]
    return t


def test_an_event_at_bytes_fires_once_the_writes_reach_them():
    fired = []
    t = byte_traffic(12 << 20, 0.9, fired)
    t_open, _ = asyncio.run(t.run(0.5))
    (at, written), = fired
    # two sessions acknowledge 1 MiB every 10 ms each: 12 MiB by some
    # 60 ms, the event on the write that reaches them, not on a clock
    assert 12 << 20 <= written < 13 << 20
    assert at - t_open < 0.3
    assert t.written > written, "the sessions ran on beside the event"
    note, = t.notes
    assert note.startswith("event spy: at_bytes 12582912 reached, ")


def test_an_event_at_bytes_falls_back_on_its_share_and_says_so():
    fired = []
    t = byte_traffic(1 << 40, 0.5, fired)
    t_open, _ = asyncio.run(t.run(0.4))
    (at, written), = fired
    assert at - t_open == pytest.approx(0.2, abs=0.08)
    assert 0 < written < 1 << 40
    note, = t.notes
    assert "at_bytes not reached" in note and "fired on by_share" in note


def test_only_acknowledged_writes_of_the_window_count_towards_at_bytes():
    class Mixed(Nap):
        async def do(self, t, s, st, arg, warm):
            await t.timed("read", 1 << 20, asyncio.sleep(0.01))
            with contextlib.suppress(OSError):
                await t.timed("write", 1 << 20, failing())

    async def failing():
        await asyncio.sleep(0.005)
        raise OSError("lost")

    fired = []
    t = byte_traffic(1 << 20, 0.5, fired, Mixed())
    asyncio.run(t.setup())               # the warm-up run is no window
    t_open, _ = asyncio.run(t.run(0.2))
    (at, written), = fired
    assert written == t.written == 0 and at - t_open >= 0.09
    assert "at_bytes not reached" in t.notes[0]


def test_a_mix_names_an_event_by_its_share_or_by_its_bytes():
    mix = {"sessions": 1, "steps": [], "check": {}, "events": [
        {"at_share": 0.25, "fault": "kill_seeded"},
        {"at_bytes": 4096, "by_share": 0.5, "fault": "kill_seeded"}]}
    t = generator.Traffic(mix, 1, [object()], [], None, 1 << 26)
    (share, fault), = t.events
    (nbytes, by, name, fault2), = t.byte_events
    assert (share, nbytes, by, name) == (0.25, 4096, 0.5, "kill_seeded")
    assert fault.__file__ == fault2.__file__ and callable(fault2.apply)


# -- the wait for full redundancy ----------------------------------------

def status(completed=0, failed=0, nbytes=0, active=(), recent=(),
           queued=0, endangered=0):
    return {"queued": {"lost": 0, "endangered": queued, "rebalance": 0},
            "active": list(active), "completed": completed, "failed": failed,
            "bytes_rebuilt": nbytes, "recent": list(recent),
            "endangered_queue": endangered}


def rec(chunk, part, ms, ok=True, trace=0):
    return {"chunk_id": chunk, "part": part, "kind": "replicate",
            "class": "endangered", "ok": ok, "ms": ms, "bytes": 8 << 20,
            "trace_id": trace or chunk * 100 + part}


HEALTHY = {"healthy": 40, "endangered": 0, "lost": 0}
SHORT = {"healthy": 9, "endangered": 31, "lost": 0}


@pytest.mark.parametrize("st,health,want", [
    (status(), HEALTHY, True),
    (status(endangered=3), HEALTHY, False),
    (status(queued=1), HEALTHY, False),
    (status(active=[{"chunk_id": 1, "part": 2, "running_s": 0.1}]),
     HEALTHY, False),
    (status(), SHORT, False),
    (status(), {"healthy": 39, "endangered": 0, "lost": 1}, False),
    (status(failed=2), HEALTHY, True),   # failed, asked again, made whole
])
def test_whole_is_nothing_waiting_running_or_short(st, health, want):
    assert redundancy.whole(st, health) is want


# killed at 100.0; the close at 113.0; an old record from the set-up
OLD = rec(9, 9, 50.0)
POLLS = [
    (100.01, status(recent=[OLD]), HEALTHY),
    (100.11, status(recent=[OLD], endangered=31), SHORT),
    (101.21, status(recent=[OLD], endangered=23, active=[
        {"chunk_id": 1, "part": 4, "running_s": 0.2},
        {"chunk_id": 2, "part": 4, "running_s": 0.0}]), SHORT),
    (101.61, status(1, 0, 8 << 20, recent=[rec(1, 4, 400.0), OLD],
                    active=[{"chunk_id": 2, "part": 4, "running_s": 0.4}]),
     SHORT),
    (113.51, status(3, 1, 24 << 20, recent=[
        rec(3, 0, 800.0), rec(2, 4, 600.0, ok=False), rec(2, 4, 300.0),
        rec(1, 4, 400.0), OLD]), HEALTHY),
]


def test_reduce_gives_what_the_readers_take():
    rb = redundancy.reduce(POLLS, 100.0, 100.11, 113.6, 113.0)
    assert rb["first_start"] == pytest.approx(101.01)
    assert [(r["chunk_id"], r["part"]) for r in rb["records"]] == [
        (1, 4), (3, 0), (2, 4)]
    assert all(r["ok"] for r in rb["records"]), "one a (chunk, part), sound"
    assert (rb["bytes_master"], rb["completed"], rb["failed"]) == (
        24 << 20, 3, 1)
    assert rb["after_close"] == 2 and rb["polls"] == 5
    assert redundancy.rebuild_s(rb) == pytest.approx(13.6)
    # the master's count is no numerator: without the bytes the worker
    # found on the servers' disks there is no rate
    assert "bytes" not in rb and redundancy.rebuild_mbps(rb) is None
    assert redundancy.rebuild_mbps(dict(rb, bytes=20 << 20)) == \
        pytest.approx((20 << 20) / 1e6 / 13.6)
    assert redundancy.after_close_share(rb) == pytest.approx(0.6 / 13.6)


def test_no_rate_without_full_redundancy_or_without_bytes():
    never = redundancy.reduce(POLLS[:4], 100.0, 100.11, None, 113.0)
    assert redundancy.rebuild_s(never) is None
    assert redundancy.rebuild_mbps(never) is None
    nothing = dict(redundancy.reduce(POLLS[:1], 100.0, 100.01, 100.2, 113.0),
                   bytes=0)
    assert redundancy.rebuild_mbps(nothing) is None
    assert redundancy.rebuild_mbps(dict(never, bytes=8 << 20)) is None
    assert redundancy.rebuild_mbps(None) is None


def test_the_numerator_is_the_harness_own_reckoning():
    """The bytes of ``rebuild_MBps`` are the live bytes the reference's
    layout gives each rebuilt part for the chunk's length in the
    harness's model: not the master's count, which takes a part at its
    nominal 8 MiB whatever the chunk held."""
    full, block = 64 << 20, 65536
    ec84, ec32 = {"k": 8, "m": 4}, {"k": 3, "m": 2}
    chunks = {0x101: (ec84, full, block),
              0x202: (ec84, 5 << 20, block),      # a chunk cut short
              0x404: (ec32, 3901, block)}
    parts = {(0x101, 4), (0x101, 11), (0x202, 0), (0x202, 9), (0x404, 1),
             (0x303, 0)}                          # of no live file: nothing
    got = redundancy.rebuilt_live_bytes(parts, chunks)
    # 5 MiB are 80 blocks, 10 a data part; a parity part is as long as
    # the longest data part; 3,901 B lie in data part 0 alone
    assert got == (2 * (8 << 20) + 2 * 10 * block + 0, 5)
    assert redundancy.rebuilt_live_bytes(set(), chunks) == (0, 0)


def test_chunk_table_asks_the_master_for_the_id_alone():
    files = [types.SimpleNamespace(name="a", inode=11, length=(64 << 20) + 5,
                                   dir=0),
             types.SimpleNamespace(name="b", inode=12, length=0, dir=0),
             types.SimpleNamespace(name="c", inode=13, length=9, dir=0)]

    class Client:
        async def chunk_info(self, inode, ci):
            return types.SimpleNamespace(chunk_id=inode * 16 + ci)

    t = types.SimpleNamespace(
        model=types.SimpleNamespace(live=lambda: files), uncertain={"c"},
        dirs=[types.SimpleNamespace(goal={"k": 8, "m": 4})])
    cfg = {"block_bytes": 65536, "chunk_bytes": 64 << 20}
    goal = {"k": 8, "m": 4}
    assert asyncio.run(checks.chunk_table(t, Client(), cfg)) == {
        11 * 16: (goal, 64 << 20, 65536), 11 * 16 + 1: (goal, 5, 65536)}


def test_the_wait_is_selected_by_the_mixs_cap_and_by_nothing_else():
    kill = manifest.Cell(M, CELL).mix
    assert redundancy.asked_for(kill)
    # a later mix whose events restart or throttle and kill nothing
    # carries no cap: it runs as a plain window, with no edit to worker.py
    plain = {k: v for k, v in kill.items() if k != "redundancy_cap_s"}
    assert plain["events"] and not redundancy.asked_for(plain)
    for w in M["workloads"]:
        if w["name"] != CELL:
            assert not redundancy.asked_for(manifest.Cell(M, w["name"]).mix)


def test_a_mix_that_waits_and_killed_nothing_has_no_result():
    said = []
    t = types.SimpleNamespace(mix={"redundancy_cap_s": 1}, kill_at=None)
    orig, worker.say = worker.say, said.append
    try:
        assert asyncio.run(worker.wait_for_whole(
            t, None, None, 0.0, 1.0, None)) is None
    finally:
        worker.say = orig
    assert "none of its events killed a server" in said[0]


def test_watch_polls_from_the_kill_until_whole():
    docs = {"info": [{"chunkservers": [{"connected": True}] * 3}] * 2 + [
        {"chunkservers": [{"connected": True}] * 2 + [{"connected": False}]}],
        "rebuild-status": [p[1] for p in POLLS],
        "chunks-health": [p[2] for p in POLLS]}

    class Cluster:
        n_cs = 3
        asked = []

        async def admin(self, command, payload=None):
            self.asked.append(command)
            return docs[command].pop(0) if len(docs[command]) > 1 \
                else docs[command][0]

    t = types.SimpleNamespace(kill_at=None)

    async def go():
        watch = redundancy.Watch(Cluster(), t)
        await asyncio.sleep(0.15)
        assert not watch.polls, "nothing is asked before the kill"
        t.kill_at = time.monotonic()
        await asyncio.wait_for(watch.task, 5.0)
        return watch

    watch = asyncio.run(go())
    # the first poll reads whole, but the master had not seen the server
    # go: full redundancy is only believed once it has
    assert len(watch.polls) == 5 and watch.noticed_at is not None
    assert watch.t_whole >= watch.polls[-1][0]
    assert watch.cluster.asked.count("info") == 3


def test_a_poll_the_master_did_not_answer_is_made_again():
    class Cluster:
        n_cs = 2
        asks = 0

        async def admin(self, command, payload=None):
            self.asks += 1
            if self.asks == 2:
                raise ConnectionResetError("the master was busy")
            return {"info": {"chunkservers": [{"connected": True},
                                              {"connected": False}]},
                    "rebuild-status": status(), "chunks-health": HEALTHY
                    }[command]

    async def go():
        watch = redundancy.Watch(Cluster(), types.SimpleNamespace(
            kill_at=time.monotonic()))
        await asyncio.wait_for(watch.task, 5.0)
        return watch

    watch = asyncio.run(go())
    assert len(watch.polls) == 1 and watch.t_whole is not None
    assert watch.cluster.asks == 4      # info, a failed status; then both


def test_the_masters_counts_and_their_deltas():
    doc = {"write_grants": {"total": 3200.0},
           'write_grant_bumps{why="holder_lost"}': {"total": 3.0},
           'write_grant_bumps{why="copy_made"}': {"total": 28.0},
           "rebuilds_completed": {"total": 31.0}}
    got = redundancy.master_counts(doc)
    assert got["write_grants"] == 3200.0 and got["write_grant_bumps"] == 31.0
    assert got['write_grant_bumps{why="copy_made"}'] == 28.0
    assert "rebuilds_completed" not in got
    before = {"write_grants": 200.0}
    assert redundancy.counts_delta(before, got)["write_grants"] == 3000.0
    assert redundancy.counts_delta(before, got)["write_grant_bumps"] == 31.0


# -- the end-to-end metric and the readers -------------------------------

def op(end, nbytes=2 << 20, ok=True, cls="write"):
    return generator.Op(cls, end - 0.02, end, nbytes, ok)


def ctx_of(rebuild, ops=(), master=None):
    return {"window_s": 20.0, "phases": {"write": {}, "read": {}},
            "ops": list(ops), "tap": None, "trace": None, "config": {},
            "peaks": None, "t_open": 93.0, "t_close": 113.0,
            "master": master or {}, "rebuild": rebuild}


RB = dict(redundancy.reduce(POLLS, 100.0, 100.11, 113.6, 113.0),
          bytes=20 << 20)   # what the worker found on disk: one part short
# 10 writes a second before the kill, 6 a second after it, to the close
OPS = [op(93.0 + (i + 1) / 10) for i in range(70)] + \
      [op(100.0 + (i + 1) / 6) for i in range(78)] + \
      [op(113.2), op(105.0, ok=False), op(104.0, cls="create", nbytes=0)]
EXPECT = {
    "rebuild_detect_ms": 1010.0,
    "rebuild_part_ms": (400.0 + 300.0 + 800.0) / 3,
    "rebuild_after_close_pct": 100.0 * 0.6 / 13.6,
    "write_MBps.through_loss": 148 * (2 << 20) / 1e6 / 20.0,
    "write_slowdown_under_rebuild_pct": 40.0,
    "write_grant_bumps_pct": 100.0 * 31 / 3000,
}
MASTER = {"write_grants": 3000.0, "write_grant_bumps": 31.0}


def test_rebuild_rate_joins_the_end_to_end_numbers():
    e = worker.end_to_end(OPS, 93.0, 113.0, RB)
    assert e["rebuild_MBps"] == pytest.approx((20 << 20) / 1e6 / 13.6)
    assert e["write_MBps"] == pytest.approx(148 * (2 << 20) / 1e6 / 20.0)
    assert worker.end_to_end(OPS, 93.0, 113.0)["rebuild_MBps"] is None


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_on_known_documents(name):
    got = manifest.load_reader(name)(ctx_of(RB, OPS, MASTER))
    assert got == pytest.approx(EXPECT[name], rel=1e-3)


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_finds_nothing_where_no_server_was_killed(name):
    assert manifest.load_reader(name)(ctx_of(None, OPS)) is None


def test_readers_leave_out_what_was_not_seen():
    never = dict(RB, t_whole=None)
    read = manifest.load_reader
    assert read("rebuild_after_close_pct")(ctx_of(never, OPS)) is None
    assert read("write_slowdown_under_rebuild_pct")(ctx_of(never, OPS)) is None
    assert read("rebuild_detect_ms")(ctx_of(dict(RB, first_start=None))) is None
    assert read("rebuild_part_ms")(ctx_of(dict(RB, records=[]))) is None
    # a master without the counts (the parent of the PR that brought them)
    assert read("write_grant_bumps_pct")(ctx_of(RB, OPS, {})) is None
    # grants and none of them bumped: a share of 0, not nothing
    assert read("write_grant_bumps_pct")(
        ctx_of(None, OPS, {"write_grants": 3184.0})) == 0.0
    # the whole rebuild inside the window: no share after the close
    inside = dict(RB, t_whole=108.0)
    assert read("rebuild_after_close_pct")(ctx_of(inside, OPS)) == 0.0


# -- the comparison draws rebuilt chunks ---------------------------------

def test_rebuilt_picks_draws_from_the_rebuilt_chunks_beyond_those_taken():
    files = [types.SimpleNamespace(name=f"f{i}", inode=10 + i)
             for i in range(5)]
    chunks = [(f, ci) for f in files for ci in range(4)]
    ids = {(f.inode, ci): f.inode * 10 + ci for f, ci in chunks}

    class Client:
        async def chunk_info(self, inode, ci):
            return types.SimpleNamespace(chunk_id=ids[inode, ci])

    # f0 and f1 were written before the kill: a part of each chunk rebuilt
    t = types.SimpleNamespace(rebuilt_parts={
        (ids[f.inode, ci], 4) for f, ci in chunks[:8]})
    taken = chunks[:2]
    rng = np.random.default_rng(3)
    got = asyncio.run(checks.rebuilt_picks(t, Client(), chunks, taken, 4, rng))
    assert len(got) == 4 and all(c in chunks[2:8] for c in got)
    assert len({(f.name, ci) for f, ci in got}) == 4
    few = asyncio.run(checks.rebuilt_picks(
        t, Client(), chunks, chunks[:6], 4, np.random.default_rng(3)))
    assert few == chunks[6:8], "fewer than asked: the caller counts the rest"


# -- the tap is read where the trace stops -------------------------------

class FakeEncoder:
    def encode(self, k, m, data_parts):
        return [np.zeros(len(data_parts[0]), np.uint8) for _ in range(m)]

    def recover(self, k, m, parts, wanted):
        length = len(next(iter(parts.values())))
        return {w: np.zeros(length, np.uint8) for w in wanted}

    def xor_parity(self, parts):
        return np.bitwise_xor.reduce(np.stack(parts))

    def xor_parity_into(self, parts, out):
        out[...] = self.xor_parity(parts)


def test_a_recover_after_the_stop_is_seen_by_no_verdict_and_no_roofline():
    tap = EncoderTap(FakeEncoder())
    data = [np.zeros(262144, np.uint8)] * 8
    for _ in range(5):
        tap.encode(8, 4, data)
    counts = tap.snapshot()              # where the trace stops
    # the comparison's cold read-back meets a slow part and decodes
    tap.recover(8, 4, {i: data[0] for i in range(1, 9)}, [0])
    tap.encode(8, 4, data)
    assert len(tap.recover_calls) == 1 and len(tap.encode_calls) == 6
    assert len(counts.recover_calls) == 0 and len(counts.encode_calls) == 5
    span_device_s = {"bench.encode": 0.0004}     # no bench.recover traced
    assert worker.untraced_spans(counts, span_device_s) == []
    # the fault the test is for: read late, a sound run reads as broken
    assert worker.untraced_spans(tap.snapshot(), span_device_s) == [
        "bench.recover"]
    # a window whose calls left no device time is still refused
    assert worker.untraced_spans(counts, {}) == ["bench.encode"]
    peaks = manifest.peaks_for("TPU v5 lite")
    ctx = {"tap": counts, "trace": {"span_device_s": span_device_s},
           "peaks": peaks}
    late = dict(ctx, tap=tap.snapshot())
    encode = manifest.load_reader("encode_kernel_roofline")
    assert encode(ctx) == pytest.approx(encode(late) * 5 / 6)
    assert manifest.load_reader("recover_kernel_roofline")(ctx) is None
    assert manifest.load_reader("recover_boundary_MBps")(ctx) is None
    assert manifest.load_reader("recover_boundary_MBps")(late) is not None
    tap.remove()


def test_the_tap_counts_xor_calls_once_under_their_span():
    class Nested(FakeEncoder):
        def xor_parity_into(self, parts, out):
            out[...] = self.xor_parity(parts)   # the tap's, on this thread

    seen = []

    @contextlib.contextmanager
    def annotate(name):
        seen.append(name)
        yield

    for enc in (FakeEncoder(), Nested()):
        seen.clear()
        tap = EncoderTap(enc, annotate)
        parts = [np.full(65536, i, np.uint8) for i in (1, 2, 4)]
        assert (tap.enc.xor_parity(parts) == 7).all()
        out = np.zeros(65536, np.uint8)
        tap.enc.xor_parity_into(parts, out)
        assert (out == 7).all()
        counts = tap.snapshot()
        assert [c[:2] for c in counts.xor_calls] == [(3, 65536)] * 2
        assert seen == ["bench.xor"] * 2
        assert counts.encode_calls == counts.recover_calls == ()
        tap.remove()
        assert "xor_parity" not in vars(enc)


def test_parity_short_stores_a_xor_goals_parity_as_zeros():
    tap = EncoderTap(FakeEncoder(), control="parity-short")
    parts = [np.full(4096, i, np.uint8) for i in (1, 2)]
    assert not tap.enc.xor_parity(parts).any()
    out = np.ones(4096, np.uint8)
    tap.enc.xor_parity_into(parts, out)
    assert not out.any() and len(tap.xor_calls) == 2
    tap.remove()


def test_xor_calls_with_no_device_time_are_refused_and_read_bytes_bound():
    tap = EncoderTap(FakeEncoder())
    parts = [np.zeros(1 << 20, np.uint8)] * 3
    for _ in range(4):
        tap.enc.xor_parity(parts)
    counts = tap.snapshot()
    tap.remove()
    # the windowed write's xor_parity_into stays on the host today: its
    # calls under bench.xor with no device time refuse a traced run
    assert worker.untraced_spans(counts, {"bench.encode": 1e-3}) == [
        "bench.xor"]
    assert worker.untraced_spans(counts, {"bench.xor": 1e-4}) == []
    peaks = manifest.peaks_for("TPU v5 lite")
    ctx = {"tap": counts, "trace": {"span_device_s": {"bench.xor": 1e-4}},
           "peaks": peaks}
    # 3 parts read and one written, 1 MiB each, four calls, at 819 GB/s
    least = 4 * 4 * (1 << 20) / peaks["hbm_bytes_per_s"]
    assert _lib.xor_roofline(ctx) == pytest.approx(100 * least / 1e-4)
    assert rooflines.least_seconds(*rooflines.xor_cost(3, 1 << 20),
                                   peaks)[1] == "bytes"
    assert _lib.xor_roofline(dict(ctx, trace=None)) is None
    assert _lib.xor_roofline(dict(ctx, peaks=None)) is None
    assert _lib.xor_roofline(dict(ctx, tap=TapCounts((), ()))) is None


# -- the decode a slow part would force is warmed in set-up ---------------

def test_fallback_decodes_on_known_calls():
    enc = (3, 2, 3, 720896, 0.01), (3, 2, 3, 786432, 0.01), \
        (3, 2, 3, 720896, 0.02)
    counts = types.SimpleNamespace(encode_calls=enc, recover_calls=())
    # a warm-up that read parts back: one wanted part at each geometry
    assert worker.fallback_decodes(counts, 2) == [
        (3, 2, 3, 1, 720896), (3, 2, 3, 1, 786432)]
    # one that read nothing back warms nothing
    assert worker.fallback_decodes(counts, 0) == []
    # degraded reads drove one wanted part: a further slow part wants two;
    # what the warm-up drove itself is not driven again
    rec = (8, 4, 8, 1, 262144, 0.01), (8, 4, 8, 1, 131072, 0.01)
    counts = types.SimpleNamespace(encode_calls=(), recover_calls=rec)
    assert worker.fallback_decodes(counts, 9) == [
        (8, 4, 8, 2, 131072), (8, 4, 8, 2, 262144)]
    # a goal's m is as far as it goes
    full = types.SimpleNamespace(
        encode_calls=(), recover_calls=((3, 2, 3, 2, 65536, 0.01),))
    assert worker.fallback_decodes(full, 1) == []
    both = types.SimpleNamespace(
        encode_calls=((3, 2, 3, 65536, 0.01),),
        recover_calls=((3, 2, 3, 1, 65536, 0.01),))
    assert worker.fallback_decodes(both, 1) == [(3, 2, 3, 2, 65536)]


@pytest.mark.parametrize("geometry", [
    (3, 2, 3, 1, 720896), (3, 2, 1, 1, 65536), (8, 4, 8, 2, 262144)])
def test_warm_decode_crosses_the_boundary_at_the_geometry(geometry):
    k, m, rows, wanted, nbytes = geometry

    class Spy(FakeEncoder):
        def recover(self, k, m, parts, wanted):
            self.seen = parts, wanted
            return super().recover(k, m, {i: p for i, p in parts.items()
                                          if p is not None}, wanted)

    spy = Spy()
    tap = EncoderTap(spy)
    worker.warm_decode(spy, *geometry)
    # what the tap records of a call is what fallback_decodes asked for
    assert [c[:5] for c in tap.recover_calls] == [geometry]
    tap.remove()
    parts, asked = spy.seen
    assert asked == list(range(wanted)) and len(parts) == k
    assert not set(parts) & set(asked) and max(parts) < k + m
    live = [i for i, p in parts.items() if p is not None]
    assert len(live) == rows and max(parts) in live   # parity holds bytes
    assert all(len(parts[i]) == nbytes for i in live)


def test_setup_tells_the_caller_where_the_warm_up_starts():
    order = []

    class Fault:
        async def apply(self, t):
            order.append("fault")

    class Step(Nap):
        async def do(self, t, s, st, arg, warm):
            order.append("warm" if warm else "window")

    t = traffic_of([])
    t.faults, t.verbs["nap"] = [Fault()], Step()
    asyncio.run(t.setup(on_warm=lambda: order.append("on_warm")))
    assert order[:2] == ["fault", "on_warm"] and set(order[2:]) == {"warm"}
    order.clear()
    asyncio.run(t.setup())               # the hook is optional
    assert order[0] == "fault" and "on_warm" not in order
