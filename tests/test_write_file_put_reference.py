"""``Client.write_file`` of whole objects at $ec(8,4), the S3 gateway's
PUT and the CLI's copy, held to the plain reference
(``benchmark/reference``: numpy GF(2^8), the part-file layout, zlib
CRC32; it imports nothing of the program).

A striped chunk of 8 MiB or more goes through the windowed whole-chunk
write (``Client._push_striped_windowed``): scattered once into 8 part
streams, cut into at most 8 slot-aligned segments, each encoded and sent
unacknowledged under credits. A shorter one, or one whose window raises,
takes the whole-part sends. Each case compares the bytes read back, all
twelve part files of every chunk on the chunkservers' disks (data, the
four parities, the stored CRC words), a read with a data part's server
stopped, and what the window counted beside its phase rows against what
the geometry alone predicts.
"""

import asyncio
import os
import sys

import numpy as np
import pytest

from lizardfs_tpu.client.write_window import MAX_DEPTH
from lizardfs_tpu.constants import EATTR_NOCACHE, MFSBLOCKSIZE, MFSCHUNKSIZE
from lizardfs_tpu.core import native_io
from lizardfs_tpu.runtime.metrics import WRITE_COUNTS, phase_delta

from tests.test_cluster import EC_GOAL, STD2_GOAL, WIDE_EC_GOAL, Cluster
from tests.test_pwrite_ec32_reference import compare_stored, stop_holder_of

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import generator  # noqa: E402
import manifest  # noqa: E402
from reference import layout  # noqa: E402

K, M = 8, 4
MiB = 2 ** 20
PIPELINE_MIN = 8 * MiB  # Client.WRITE_PIPELINE_MIN_BYTES

OBJECTS = {
    "least_the_window_takes": PIPELINE_MIN,
    # a whole chunk, and a tail of five blocks that falls back
    "chunk_and_short_tail": MFSCHUNKSIZE + 5 * MFSBLOCKSIZE,
    "two_whole_chunks": 2 * MFSCHUNKSIZE,
    # warp's default object: 20 blocks a part, no whole number of the
    # window's segments (six of three blocks and one of two)
    "ten_mib_seven_segments": 10 * MiB,
}

pytestmark = pytest.mark.skipif(
    not native_io.parts_scatter_available(),
    reason="the windowed write needs the native library")


def expected_counts(length: int) -> dict:
    """What ``write_file`` of a fresh object of ``length`` bytes has to
    count, from the geometry alone. ``parts`` is ``ring_parts`` +
    ``socket_parts``: which plane carries a part-segment is the
    cluster's business (same host or not), their sum is not."""
    want = {"window_chunks": 0, "fallback_chunks": 0, "window_segments": 0,
            "parts": 0}
    for a, b in layout.chunk_spans(length, MFSCHUNKSIZE):
        if b - a < PIPELINE_MIN:
            want["fallback_chunks"] += 1
            continue
        want["window_chunks"] += 1
        live = layout.part_lengths(K, M, b - a, MFSBLOCKSIZE)
        blocks = max(live) // MFSBLOCKSIZE
        seg = -(-blocks // min(MAX_DEPTH, blocks)) * MFSBLOCKSIZE
        for lo in range(0, blocks * MFSBLOCKSIZE, seg):
            want["window_segments"] += 1
            want["parts"] += sum(1 for n in live if n > lo)
    return want


def counted(delta: dict) -> dict:
    got = {n: delta[n] for n in ("window_chunks", "fallback_chunks",
                                 "window_segments")}
    got["parts"] = delta["ring_parts"] + delta["socket_parts"]
    return got


async def ec84_file(client, name: str):
    f = await client.create(1, name)
    await client.setgoal(f.inode, WIDE_EC_GOAL)
    return f


async def compare_chunks(cluster, client, inode: int, data: np.ndarray):
    """Every chunk's twelve part files against the reference; returns
    the chunks' ids."""
    ids = []
    for ci, (a, b) in enumerate(layout.chunk_spans(len(data), MFSCHUNKSIZE)):
        info = await client.chunk_info(inode, ci)
        compare_stored(cluster, info.chunk_id, data[a:b], 0, K, M)
        ids.append(info.chunk_id)
    return ids


async def read_back(client, inode: int, length: int) -> np.ndarray:
    client.cache.invalidate(inode)
    return np.frombuffer(await client.read_file(inode, 0, length), np.uint8)


@pytest.mark.asyncio
@pytest.mark.parametrize("seed", [30, 2147493630])
@pytest.mark.parametrize("obj", sorted(OBJECTS))
async def test_whole_object_matches_the_reference(tmp_path, obj, seed):
    length = OBJECTS[obj]
    data = np.random.default_rng([seed, length]).integers(
        0, 256, length, dtype=np.uint8)
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)  # no rebuild under the test
    try:
        c = await cluster.client()
        f = await ec84_file(c, f"{obj}.bin")
        before = c.write_phases.snapshot()
        counters = {n: c.op_counters.get(n, 0) for n in WRITE_COUNTS}
        batches = c.op_counters.get("write_commit_batch", 0)
        await c.write_file(f.inode, data)
        d = phase_delta(c.write_phases.snapshot(), before)

        # (a) the bytes written read back
        assert np.array_equal(await read_back(c, f.inode, length), data)

        # (b) the part files on the chunkservers' disks
        chunk_ids = await compare_chunks(cluster, c, f.inode, data)

        # what the window counted, beside the phase rows and in op_counters
        want = expected_counts(length)
        assert counted(d) == want
        assert counted({n: c.op_counters.get(n, 0) - counters[n]
                        for n in WRITE_COUNTS}) == want
        segs = want["window_segments"]
        assert segs <= d["window_depth_sum"] <= MAX_DEPTH * segs
        assert 0 <= d["window_credit_waits"] <= segs
        assert (d["credit_ms"] > 0) == (d["window_credit_waits"] > 0)
        assert d["reps"] == 1 and d["ingest_ms"] > 0
        # two chunks fit write_file's two places: neither waits
        assert d["chunk_gate_ms"] == 0
        assert not any(d[n] for n in WRITE_COUNTS if n.startswith("rmw_"))
        # the chunks' ends went to the master as one batch
        assert c.op_counters.get("write_commit_batch", 0) - batches == 1

        # (c) what was written, with a data part's server stopped
        await stop_holder_of(cluster, chunk_ids[0], 1, K, M)
        assert np.array_equal(await read_back(c, f.inode, length), data)
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_a_window_that_raises_mid_chunk_is_healed(tmp_path):
    """The third segment's send fails with the first two on the
    chunkservers' disks: the whole-part sends that follow rewrite every
    part, so the part files come out as the reference's. The object's
    131 blocks end in a ragged segment (data parts 3 to 7 are a block
    shorter)."""
    length = PIPELINE_MIN + 3 * MFSBLOCKSIZE
    data = np.random.default_rng(30).integers(0, 256, length, dtype=np.uint8)
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        f = await ec84_file(c, "torn.bin")
        before = c.write_phases.snapshot()
        orig = native_io.PartsScatterSession.send_segment_window
        calls = {"n": 0}

        def raises_once(self, *args, **kw):
            calls["n"] += 1
            if calls["n"] == 3:
                self.close()
                raise native_io.NativeIOError(-1, "injected")
            return orig(self, *args, **kw)

        native_io.PartsScatterSession.send_segment_window = raises_once
        try:
            await c.write_file(f.inode, data)
        finally:
            native_io.PartsScatterSession.send_segment_window = orig
        d = phase_delta(c.write_phases.snapshot(), before)
        assert calls["n"] == 3, "the fallback sends no segment"
        assert counted(d) == {"window_chunks": 0, "fallback_chunks": 1,
                              "window_segments": 3, "parts": 2 * (K + M)}
        assert np.array_equal(await read_back(c, f.inode, length), data)
        await compare_chunks(cluster, c, f.inode, data)
    finally:
        await cluster.stop()


ARGUMENTS = {
    # what the S3 gateway (``req.body``) and the CLI (``f.read()``) hand it
    "bytes": bytes,
    "uint8_array": lambda a: a,
    # a buffer whose items are wider than a byte: its length is no byte count
    "uint32_view": lambda a: memoryview(a).cast("I"),
}


@pytest.mark.asyncio
@pytest.mark.parametrize("form", sorted(ARGUMENTS))
async def test_the_object_may_be_any_buffer(tmp_path, form):
    """``write_file`` takes its object's bytes from whatever buffer it
    is handed: the length it writes, counts and commits is the
    buffer's byte length, whatever its items are."""
    length = PIPELINE_MIN + 4 * MFSBLOCKSIZE
    data = np.random.default_rng(31).integers(0, 256, length, dtype=np.uint8)
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        f = await ec84_file(c, f"{form}.bin")
        before = c.write_phases.snapshot()
        c.trace_ring.clear()
        await c.write_file(f.inode, ARGUMENTS[form](data))
        d = phase_delta(c.write_phases.snapshot(), before)
        assert (await c.getattr(f.inode)).length == length
        assert np.array_equal(await read_back(c, f.inode, length), data)
        assert counted(d) == expected_counts(length)
        root, = [s for s in c.trace_ring.dump() if s["name"] == "write_file"]
        assert root["attrs"]["bytes"] == length
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_the_gates_are_spans_of_the_write_file_tree(tmp_path):
    """One credit a chunkserver: every segment but the first finds the
    gate shut and reaps its predecessor's acks inside a ``credit`` span.
    A third chunk finds both of ``write_file``'s places taken and waits
    in a ``chunk_gate`` span (plain copies: the gate is the same)."""
    rng = np.random.default_rng(30)
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        c.write_window.cs_credits = 1
        f = await ec84_file(c, "credits.bin")
        before = c.write_phases.snapshot()
        c.trace_ring.clear()
        await c.write_file(
            f.inode, rng.integers(0, 256, PIPELINE_MIN, dtype=np.uint8))
        d = phase_delta(c.write_phases.snapshot(), before)
        assert d["window_segments"] == 8 and d["window_credit_waits"] == 7
        assert d["window_chunks"] == 1 and d["credit_ms"] > 0
        spans = c.trace_ring.dump()
        root, = [s for s in spans if s["name"] == "write_file"]
        by_id = {s["span_id"]: s for s in spans}
        credit = [s for s in spans if s["name"] == "credit"]
        assert len(credit) == 7
        assert all(s["parent_id"] == root["span_id"]
                   and s["bucket"] == "queue" for s in credit)
        reaps = [s for s in spans if s["name"] == "ack"
                 and by_id[s["parent_id"]]["name"] == "credit"]
        assert len(reaps) == 7
        ingest, = [s for s in spans if s["name"] == "ingest"]
        assert ingest["parent_id"] == root["span_id"]
        assert not [s for s in spans if s["name"] == "chunk_gate"]

        g = await c.create(1, "three_chunks.bin")
        await c.setgoal(g.inode, STD2_GOAL)
        before = c.write_phases.snapshot()
        c.trace_ring.clear()
        await c.write_file(g.inode, bytes(2 * MFSCHUNKSIZE + MFSBLOCKSIZE))
        d = phase_delta(c.write_phases.snapshot(), before)
        assert d["chunk_gate_ms"] > 0 and d["reps"] == 1
        spans = c.trace_ring.dump()
        root, = [s for s in spans if s["name"] == "write_file"]
        gate, = [s for s in spans if s["name"] == "chunk_gate"]
        assert gate["parent_id"] == root["span_id"]
        assert gate["attrs"]["chunk"] == 2 and gate["bucket"] == "queue"
        # plain copies go through no window and no whole-part fallback;
        # they count their bytes, and each chunk's one part through the
        # relay chain of its two holders
        assert {n: d[n] for n in WRITE_COUNTS if d[n]} == {
            "copies_payload_bytes": 2 * MFSCHUNKSIZE + MFSBLOCKSIZE,
            "chain_parts": 3}
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_the_put_sequence_publishes_a_whole_object(tmp_path):
    """The benchmark's verb (``benchmark/traffic/verbs/put_whole.py``)
    under the generator, one PUT at the rehearsal's size: the staged
    name is gone, the key is listed in the bucket's directory with the
    object's length, the ETag reads back, and the model holds what the
    chunkservers do."""
    cell = manifest.Cell(manifest.load_manifest(), "ec84-put")
    manifest.rehearsal_of(cell)
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        goal, = cell.config["goals"]
        dirs = []
        for entry in cell.config["directories"]:
            d = await c.mkdir(1, entry["name"])
            await c.setgoal(d.inode, WIDE_EC_GOAL)
            dirs.append(generator.Directory(entry["name"], d.inode, goal))
        t = generator.Traffic(cell.mix, 30, [c], dirs, None,
                              int(cell.config["chunk_bytes"]))
        t.recording = True
        step, = cell.mix["steps"]
        verb = t.verbs[step["verb"]]
        await verb.do(t, 0, t._state(0), step, False)

        staging, bucket = dirs
        assert await c.readdir(staging.inode) == []
        listed, = await c.readdir(bucket.inode)
        f, = t.model.live()
        assert (listed.name, f.name, f.dir) == ("s0_0", "s0_0", 1)
        assert f.length == cell.config["object_bytes"] == 9 * MiB
        attr = await c.lookup(bucket.inode, "s0_0")
        assert (attr.inode, attr.length) == (f.inode, f.length)
        etag = await c.get_xattr(f.inode, verb.ETAG_XATTR)
        assert len(etag) == 32 and int(etag, 16) >= 0
        op, = t.ops
        assert (op.cls, op.nbytes, op.ok) == ("write", f.length, True)
        want = t.model.bytes_of(f)
        assert np.array_equal(await read_back(c, f.inode, f.length), want)
        await compare_chunks(cluster, c, f.inode, want)
        assert c.op_counters["window_chunks"] == 1
        assert not c.op_counters.get("fallback_chunks")
        assert not t.uncertain
    finally:
        await cluster.stop()


# -- warp mixed: GET, STAT, PUT and DELETE side by side -------------------

MIXED_GOALS = {
    "ec32": (EC_GOAL, {"id": EC_GOAL, "name": "ec32", "expr": "$ec(3,2)",
                       "k": 3, "m": 2}, 6),
    "ec84": (WIDE_EC_GOAL, None, 13),
}


async def mixed_traffic(cluster, goal_name: str, seed: int, sessions: int):
    """The cell ``ec84-s3-mixed`` at its rehearsal's size under the one
    generator, on an in-process cluster at the named goal."""
    cell = manifest.Cell(manifest.load_manifest(), "ec84-s3-mixed")
    manifest.rehearsal_of(cell)
    goal_id, goal, _n = MIXED_GOALS[goal_name]
    goal = goal or cell.config["goals"][0]
    clients = [await cluster.client() for _ in range(sessions)]
    dirs = []
    for entry in cell.config["directories"]:
        d = await clients[0].mkdir(1, entry["name"])
        await clients[0].setgoal(d.inode, goal_id)
        dirs.append(generator.Directory(entry["name"], d.inode, goal))
    mix = dict(cell.mix, sessions=sessions, objects=6)
    t = generator.Traffic(mix, seed, clients, dirs, None,
                          int(cell.config["chunk_bytes"]))
    return t, cell, goal


@pytest.mark.asyncio
@pytest.mark.parametrize("goal_name", sorted(MIXED_GOALS))
async def test_mixed_operations_match_the_reference(tmp_path, goal_name):
    """A seeded run of 200 mixed operations through the benchmark's
    verb (``traffic/verbs/warp_mixed_op.py``: the S3 gateway's Client
    calls) from four sessions side by side, after the set-up action has
    PUT the pool: every GET's answer byte for byte against the model,
    the shares exact, the bucket's listing after the DELETEs, the part
    files of three live objects against the reference, and what the
    read path counted."""
    cluster = Cluster(tmp_path, n_cs=MIXED_GOALS[goal_name][2])
    await cluster.start(health_interval=0.5)
    try:
        t, cell, goal = await mixed_traffic(cluster, goal_name, 34, 4)
        k, m_par = goal["k"], goal["m"]
        step, = cell.mix["steps"]
        verb = t.verbs[step["verb"]]
        for fault in t.faults:
            await fault.apply(t)
        assert len(t.model.live()) == len(verb.pool(t).live) == 6
        t.recording = True
        compared = []

        def compare_now(st, f, offset, size, data):
            want = t.model.bytes_of(f, offset, size)
            assert len(data) == size == f.length
            assert np.array_equal(np.frombuffer(data, np.uint8), want)
            compared.append(f.name)

        t.retain = compare_now
        before = [c.read_phases.snapshot() for c in t.clients]

        async def session(s: int) -> None:
            for _ in range(50):
                await verb.do(t, s, t._state(s), step, False)

        await asyncio.gather(*(session(s) for s in range(4)))
        by_class = {}
        for op in t.ops:
            assert op.ok
            by_class[op.cls] = by_class.get(op.cls, 0) + 1
        # two and a half blocks of 20 a session: 9 / 6 / 3 / 2 in every
        # whole block, the half block free
        assert sum(by_class.values()) == 200
        for cls, share in (("read", 9), ("stat", 6), ("write", 3),
                           ("delete", 2)):
            assert 8 * share <= by_class[cls] <= 8 * share + 40
        assert len(compared) == by_class["read"]
        assert not t.uncertain and not t.session_errors()
        assert all(seen == want for _n, seen, want in t.getattr_seen)
        assert len(t.getattr_seen) == by_class["read"] + by_class["stat"]

        c = t.clients[0]
        staging, bucket = t.dirs
        assert await c.readdir(staging.inode) == []
        listed = {e.name for e in await c.readdir(bucket.inode)}
        live = {f.name: f for f in t.model.live()}
        assert listed == set(live) == {f.name for f in verb.pool(t).live}
        assert len(t.unlinked) == by_class["delete"]
        assert not listed & set(t.unlinked)
        assert len(live) == 6 + by_class["write"] - by_class["delete"]
        for name in sorted(live)[:3]:
            f = live[name]
            info = await c.chunk_info(f.inode, 0)
            compare_stored(cluster, info.chunk_id, t.model.bytes_of(f), 0,
                           k, m_par)
        # every GET read one chunk's range, on one path or the other
        d = {}
        for cl, b in zip(t.clients, before):
            for key, val in phase_delta(cl.read_phases.snapshot(), b).items():
                d[key] = d.get(key, 0) + val
        assert d["reps"] == by_class["read"]
        assert d["gather_chunks"] + d["planned_chunks"] == by_class["read"]
        assert d["read_bytes"] == by_class["read"] * cell.config["object_bytes"]
        blocks = cell.config["object_bytes"] // MFSBLOCKSIZE
        assert d["cache_bypass_blocks"] == by_class["read"] * blocks
        assert d["cache_hit_blocks"] == d["cache_miss_blocks"] == 0
        assert d["lookups"] == d["get_xattrs"] == len(t.getattr_seen)
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_a_delete_never_takes_an_object_in_use(tmp_path):
    """While a GET holds an object, the pool hands a DELETE another
    one; with every object held it hands it none, and the verb then
    makes no call."""
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)
    try:
        t, cell, _goal = await mixed_traffic(cluster, "ec84", 35, 2)
        verb = t.verbs[cell.mix["steps"][0]["verb"]]
        pool = verb.pool(t)
        for _ in range(2):
            await verb.put(t, 0, t._state(0), True)
        a, b = pool.live
        rng = np.random.default_rng(1)
        held = pool.draw(rng)
        other = b if held is a else a
        for _ in range(20):
            taken = pool.take(rng)
            assert taken is other
            pool.add(taken)
        pool.held[other.name] = 1       # a second operation holds the other
        assert pool.take(rng) is None
        t.recording = True
        await verb.delete(t, 1, t._state(1), False)
        assert not t.ops and len(pool.live) == 2
        pool.release(other)
        pool.release(held)
        assert not pool.held
        await verb.delete(t, 1, t._state(1), False)
        op, = t.ops
        assert (op.cls, op.ok, op.metadata) == ("delete", True, True)
        gone, = t.unlinked
        assert [f.name for f in pool.live] == \
            [f.name for f in t.model.live()] != [gone]
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_the_read_path_counts_which_plan_served_a_chunk(tmp_path):
    """``gather_chunks`` + ``planned_chunks`` is the chunk ranges read:
    a whole-file read lands in its own buffer and takes the one native
    gather, and so does the gateway's sized read of an object inside
    one chunk, which gets a buffer of its own once the locate has
    taught its length; with a data part's holder stopped every range
    takes a plan."""
    length = MFSCHUNKSIZE + 16 * MFSBLOCKSIZE
    data = np.random.default_rng(36).integers(0, 256, length, dtype=np.uint8)
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        f = await ec84_file(c, "counted.bin")
        await c.write_file(f.inode, data)

        async def counted_read(*args):
            c.cache.invalidate(f.inode)
            before = c.read_phases.snapshot()
            got = await c.read_file(f.inode, *args)
            d = phase_delta(c.read_phases.snapshot(), before)
            assert d["read_bytes"] == len(got)
            return np.frombuffer(got, np.uint8), d

        got, d = await counted_read()
        assert np.array_equal(got, data)
        assert (d["gather_chunks"], d["planned_chunks"]) == (2, 0)
        got, d = await counted_read(0, 10 * MiB)
        assert np.array_equal(got, data[:10 * MiB])
        assert (d["gather_chunks"], d["planned_chunks"]) == (1, 0)
        assert d["cache_bypass_blocks"] == 160
        # under 4 MiB a read asks the BlockCache: a miss, then a hit
        before = c.read_phases.snapshot()
        for _ in range(2):
            assert await c.read_file(f.inode, 0, 3 * MFSBLOCKSIZE) == \
                data[:3 * MFSBLOCKSIZE].tobytes()
        d = phase_delta(c.read_phases.snapshot(), before)
        assert (d["cache_miss_blocks"], d["cache_hit_blocks"]) == (3, 3)
        assert d["gather_chunks"] + d["planned_chunks"] == 1

        info = await c.chunk_info(f.inode, 0)
        await stop_holder_of(cluster, info.chunk_id, 1, K, M)
        got, d = await counted_read()
        assert np.array_equal(got, data)
        assert d["gather_chunks"] + d["planned_chunks"] == 2
        assert d["planned_chunks"] >= 1
        assert c.op_counters["planned_chunks"] >= 3
    finally:
        await cluster.stop()


def boom(*args, **kwargs):
    raise native_io.NativeIOError(5, "injected gather failure")


# A sized read of 4 MiB or more inside one chunk (the S3 gateway's GET
# and ranged GET, the tape server's archive read) passes the BlockCache
# by and lands in a buffer `_read_chunk_range` makes for it, so the same
# conditions decide its path as decide a whole-file read's. Each case:
# the goal, the object's length, the (offset, size) read, the
# (gather_chunks, planned_chunks) it has to count, and what is done to
# the cluster first.
SIZED_READS = {
    # a ranged GET from a slot boundary over whole blocks: the gather
    "slot_aligned_range": (WIDE_EC_GOAL, 24 * MiB, (8 * MiB, 8 * MiB),
                           (1, 0), None),
    # an object that is no multiple of 64 KiB: the plan, de-interleaved
    # straight into the buffer
    "not_block_multiple": (WIDE_EC_GOAL, 10 * MiB + 5, (0, 10 * MiB + 5),
                           (0, 1), None),
    # block-aligned but three blocks into a slot of eight: the plan
    "off_a_slot_boundary": (WIDE_EC_GOAL, 24 * MiB,
                            (3 * MFSBLOCKSIZE, 8 * MiB), (0, 1), None),
    # off a block boundary the wire range is wider than the range
    # asked: no buffer is made, the plan's region is sliced
    "off_a_block_boundary": (WIDE_EC_GOAL, 24 * MiB, (5, 8 * MiB),
                             (0, 1), None),
    "clamped_at_eof": (WIDE_EC_GOAL, 10 * MiB, (2 * MiB, 16 * MiB),
                       (1, 0), None),
    "past_eof": (WIDE_EC_GOAL, 10 * MiB, (12 * MiB, 8 * MiB), (0, 0), None),
    "hole": (WIDE_EC_GOAL, 10 * MiB, (0, 10 * MiB), (0, 0), "hole"),
    # a copy goal's one part lands in place
    "standard_goal": (STD2_GOAL, 10 * MiB, (0, 10 * MiB), (0, 1), None),
    "gather_fails": (WIDE_EC_GOAL, 10 * MiB, (0, 10 * MiB), (0, 1),
                     "gather_fails"),
    "holder_stopped": (WIDE_EC_GOAL, 10 * MiB, (0, 10 * MiB), (0, 1),
                       "holder_stopped"),
    # a NOCACHE inode's reads are bulk at any size: one slot of eight
    # blocks takes the gather too
    "nocache_one_slot": (WIDE_EC_GOAL, 10 * MiB,
                         (16 * MFSBLOCKSIZE, 8 * MFSBLOCKSIZE), (1, 0),
                         "nocache"),
}


@pytest.mark.asyncio
@pytest.mark.parametrize("case", sorted(SIZED_READS))
async def test_a_sized_bulk_read_returns_what_was_written(
        tmp_path, monkeypatch, case):
    goal, length, (off, size), want, fault = SIZED_READS[case]
    data = np.random.default_rng([35, length]).integers(
        0, 256, length, dtype=np.uint8)
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)  # no rebuild under the test
    try:
        c = await cluster.client()
        f = await c.create(1, f"{case}.bin")
        await c.setgoal(f.inode, goal)
        if fault == "hole":
            data[:] = 0
            await c.truncate(f.inode, length)
        else:
            await c.write_file(f.inode, data)
        if fault == "gather_fails":
            monkeypatch.setattr(native_io, "read_parts_gather_blocking", boom)
        elif fault == "holder_stopped":
            info = await c.chunk_info(f.inode, 0)
            await stop_holder_of(cluster, info.chunk_id, 1, K, M)
        elif fault == "nocache":
            await c.seteattr(f.inode, EATTR_NOCACHE)
        recovers = []
        recover = c.encoder.recover
        monkeypatch.setattr(
            c.encoder, "recover",
            lambda *a, **kw: recovers.append(a[:2]) or recover(*a, **kw))
        c.cache.invalidate(f.inode)
        before = c.read_phases.snapshot()
        fallbacks = c.op_counters.get("stripe_gather_fallback", 0)
        got = await c.read_file(f.inode, off, size)
        d = phase_delta(c.read_phases.snapshot(), before)
        assert got == data[off:off + size].tobytes()
        assert (d["gather_chunks"], d["planned_chunks"]) == want
        assert d["read_bytes"] == len(got)
        assert d["cache_bypass_blocks"] == \
            (off + size - 1) // MFSBLOCKSIZE - off // MFSBLOCKSIZE + 1
        assert d["cache_miss_blocks"] == d["cache_hit_blocks"] == 0
        assert c.op_counters.get("stripe_gather_fallback", 0) - fallbacks \
            == (fault == "gather_fails")
        # a lost data part is recovered across the encoder boundary
        assert recovers == ([(K, M)] if fault == "holder_stopped" else [])
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_a_read_under_the_bypass_size_keeps_the_cache(tmp_path):
    """2 MiB is under ``CACHE_BYPASS_BYTES``: no buffer is made for it,
    it asks the BlockCache (a miss, then a hit) and the miss takes a
    read plan, as before sized bulk reads took the gather."""
    data = np.random.default_rng(352).integers(
        0, 256, 10 * MiB, dtype=np.uint8)
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        f = await ec84_file(c, "small_reads.bin")
        await c.write_file(f.inode, data)
        c.cache.invalidate(f.inode)
        before = c.read_phases.snapshot()
        for _ in range(2):
            assert await c.read_file(f.inode, 0, 2 * MiB) == \
                data[:2 * MiB].tobytes()
        d = phase_delta(c.read_phases.snapshot(), before)
        assert (d["cache_miss_blocks"], d["cache_hit_blocks"]) == (32, 32)
        assert (d["gather_chunks"], d["planned_chunks"]) == (0, 1)
        assert d["cache_bypass_blocks"] == 0
    finally:
        await cluster.stop()
