"""Phase-instrumented, windowed write path.

Pins two contracts:
  * the per-phase accounting (encode/stage/send/commit) is plumbed in
    the right units — phases are all exercised by a striped write and
    their busy-time sum lands in the same ballpark as the rep's wall
    clock (see tolerance notes),
  * the windowed whole-chunk write and its whole-part fallback store
    what the golden oracle says (striping.split_chunk for data AND
    parity, zlib CRC32 for the stored per-block tables).

Plus regressions for the r05 ADVICE satellites: trailing-field
default-fill at decode, and the locate-epoch clear generation.
"""

import asyncio
import os
import zlib

import numpy as np
import pytest

from lizardfs_tpu.chunkserver.chunk_store import HEADER_SIZE, SIGNATURE_SIZE
from lizardfs_tpu.client.client import Client
from lizardfs_tpu.constants import MFSBLOCKSIZE
from lizardfs_tpu.core import geometry
from lizardfs_tpu.proto import messages as m
from lizardfs_tpu.runtime.metrics import (
    WRITE_COUNTS, WRITE_PHASES, PhaseBreakdown, phase_delta,
)
from lizardfs_tpu.utils import striping

from tests.test_cluster import Cluster

EC84_GOAL = 13  # $ec(8,4) in tests.test_cluster.make_goals
EC32_GOAL = 10  # $ec(3,2)
XOR3_GOAL = 11  # $xor3


def _payload(nbytes: int) -> bytes:
    return np.random.default_rng(7).integers(
        0, 256, size=nbytes, dtype=np.uint8
    ).tobytes()


async def _write_and_read_back(cluster, client, goal, name, payload):
    f = await client.create(1, name)
    await client.setgoal(f.inode, goal)
    await client.write_file(f.inode, payload)
    client.cache.invalidate(f.inode)
    back = await client.read_file(f.inode, 0, len(payload))
    assert back == payload, "roundtrip corruption"
    return f.inode


def _find_part_files(cluster, chunk_id):
    """(part_id -> path) across every chunkserver's data dirs."""
    out = {}
    for cs in cluster.chunkservers:
        for cf in cs.store.all_parts():
            if cf.chunk_id == chunk_id:
                out[cf.part_id] = cf.path
    return out


def _read_part(path):
    """-> (data bytes, crc table bytes) of one on-disk part file."""
    with open(path, "rb") as f:
        blob = f.read()
    return blob[HEADER_SIZE:], blob[SIGNATURE_SIZE:HEADER_SIZE]


async def _assert_parts_match_oracle(cluster, client, inode, payload, what):
    """The part files of ``inode``'s chunk 0 on the chunkservers' disks
    against the golden oracle alone: every expected part is there, its
    bytes are ``striping.split_chunk``'s (data and parity) cut to the
    part's live length, and its stored CRC table holds zlib's CRC32 of
    each 64 KiB block (zero-padded, as the store keeps it)."""
    loc = await client.chunk_info(inode, 0)
    parts = _find_part_files(cluster, loc.chunk_id)
    assert parts, f"{what}: no part file found"
    slice_type = geometry.ChunkPartType.from_id(next(iter(parts))).type
    by_index = {
        geometry.ChunkPartType.from_id(pid).part: path
        for pid, path in parts.items()
    }
    assert sorted(by_index) == list(range(slice_type.expected_parts)), \
        f"{what}: part set {sorted(by_index)}"
    golden = striping.split_chunk(
        np.frombuffer(payload, dtype=np.uint8), slice_type
    )
    for part, path in sorted(by_index.items()):
        data, crc_table = _read_part(path)
        live = striping.part_length(slice_type, part, len(payload))
        blocks = -(-live // MFSBLOCKSIZE)
        assert len(data) == blocks * MFSBLOCKSIZE, \
            f"{what}: part {part} holds {len(data)} bytes for {live} live"
        want = np.zeros(len(data), dtype=np.uint8)
        want[:live] = golden[part][:live]
        assert data == want.tobytes(), \
            f"{what}: part {part} differs from the golden split"
        stored = np.frombuffer(crc_table, dtype=">u4")[:blocks]
        crcs = [
            zlib.crc32(want[i * MFSBLOCKSIZE:(i + 1) * MFSBLOCKSIZE])
            for i in range(blocks)
        ]
        assert stored.tolist() == crcs, \
            f"{what}: part {part} stored CRC table differs from zlib's"


# multi-stripe with a ragged tail; and a tail whose last 64 KiB segment
# lies past some parts' live length: 14 blocks over 3 data parts are 5,
# 5 (777 bytes live in the last) and 4 blocks, so the last of the five
# one-block segments sends part 2 nothing (seg_lengths yields a zero)
BIG = 12 * 2**20 + 12345
RAGGED3 = 13 * MFSBLOCKSIZE + 777


@pytest.mark.asyncio
@pytest.mark.parametrize("goal,path,nbytes", [
    (EC84_GOAL, "windowed", BIG), (EC84_GOAL, "fallback", BIG),
    (EC32_GOAL, "windowed", BIG), (EC32_GOAL, "fallback", BIG),
    (XOR3_GOAL, "windowed", BIG), (XOR3_GOAL, "fallback", BIG),
    (EC32_GOAL, "windowed", RAGGED3), (EC32_GOAL, "fallback", RAGGED3),
    (XOR3_GOAL, "windowed", RAGGED3), (XOR3_GOAL, "fallback", RAGGED3),
], ids=lambda v: {EC84_GOAL: "ec84", EC32_GOAL: "ec32", XOR3_GOAL: "xor3",
                  BIG: "big", RAGGED3: "ragged"}.get(v, v))
async def test_pipelined_write_byte_identical_to_serial(
    tmp_path, goal, path, nbytes
):
    """A whole-chunk striped write stores what the golden oracle says
    (data bytes, parity bytes, the stored per-block CRC tables), through
    the windowed path and through the overlapped whole-part fallback,
    entered as _pipeline_eligible enters it: a payload under the
    minimum."""
    from lizardfs_tpu.core import native_io

    if not native_io.parts_scatter_available():
        pytest.skip("native parts scatter not built")
    payload = _payload(nbytes)
    cluster = Cluster(tmp_path, n_cs=12 if goal == EC84_GOAL else 6)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        client.WRITE_PIPELINE_MIN_BYTES = (
            1 if path == "windowed" else nbytes + 1
        )
        inode = await _write_and_read_back(
            cluster, client, goal, f"{path}.bin", payload
        )
        took = {k: client.op_counters.get(k, 0) for k in (
            "write_window", "write_pipeline", "write_pipeline_fallback")}
        assert took == {
            "write_window": int(path == "windowed"),
            "write_pipeline": int(path == "windowed"),
            "write_pipeline_fallback": 0,
        }, took
        await _assert_parts_match_oracle(
            cluster, client, inode, payload, f"{path} {nbytes}"
        )
    finally:
        await cluster.stop()


@pytest.mark.parametrize("case,eligible", [
    ("all_there", True), ("no_library", False), ("armed_fault", False),
    ("small_payload", False), ("one_block_parts", False),
    ("missing_part", False), ("chained_part", False),
])
def test_pipeline_eligible_reads_what_it_observes(monkeypatch, case, eligible):
    """The windowed path is chosen by observables alone: the library,
    armed faults, the payload's size, a part's blocks, one holder a
    part. Each one turned alone sends the chunk to the fallback."""
    from lizardfs_tpu.core import native_io
    from lizardfs_tpu.runtime import faults

    client = Client("127.0.0.1", 0)
    monkeypatch.setattr(native_io, "parts_scatter_available", lambda: True)
    monkeypatch.setattr(faults, "ACTIVE", False)
    holders = {p: 1 for p in range(5)}
    nbytes, part_len = 9 * 2**20, 48 * MFSBLOCKSIZE
    if case == "no_library":
        monkeypatch.setattr(
            native_io, "parts_scatter_available", lambda: False)
    elif case == "armed_fault":
        monkeypatch.setattr(faults, "ACTIVE", True)
    elif case == "small_payload":
        nbytes = client.WRITE_PIPELINE_MIN_BYTES - 1
    elif case == "one_block_parts":
        part_len = MFSBLOCKSIZE
    elif case == "missing_part":
        holders[3] = 0
    elif case == "chained_part":
        holders[4] = 2
    slice_type = geometry.parse_goal_line("9 t : $ec(3,2)")[1].slices[0].type
    # by_part as _push_chunk_parts builds it: part -> granted locations
    by_part = {p: [object()] * n for p, n in holders.items() if n}
    assert client._pipeline_eligible(
        slice_type, by_part, np.zeros(nbytes, dtype=np.uint8), part_len
    ) is eligible


@pytest.mark.asyncio
async def test_phase_breakdown_sums_to_wall_clock(tmp_path):
    """Whole-chunk striped writes on the default path: every phase is
    populated and the busy-time sum is within tolerance of wall clock.
    Encode overlaps the sends, so the sum may exceed wall — but phases
    can't account for more than twice the wall, nor less than 0.4 of
    it (catches unit mistakes and unplumbed phases, the failure modes
    this accounting can actually have). That the top level plus the
    root's self time IS the wall holds in serial order only: pwrite's
    tree pins it (test_pwrite_yields_one_span_tree_that_sums_to_wall)."""
    payload = _payload(8 * 2**20)
    cluster = Cluster(tmp_path, n_cs=12)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        for goal in (EC84_GOAL, EC32_GOAL):
            before = client.write_phases.snapshot()
            await _write_and_read_back(
                cluster, client, goal, f"phases_{goal}.bin", payload
            )
            d = phase_delta(client.write_phases.snapshot(), before)
            assert d["reps"] == 1
            for phase in ("encode", "stage", "send", "commit"):
                assert d[f"{phase}_ms"] > 0.0, f"{phase} not recorded"
            # "ack" only accrues when the window runs deep enough to
            # reap — present in the snapshot, but may be ~0 here
            assert "ack_ms" in d
            total = sum(
                d[f"{p}_ms"]
                for p in ("encode", "stage", "send", "ack", "commit")
            )
            assert d["wall_ms"] > 0
            assert 0.4 * d["wall_ms"] <= total <= 2.0 * d["wall_ms"], (
                f"phase sum {total} vs wall {d['wall_ms']} out of range"
            )
            # the grant is a phase of its own, its server side inside it
            assert d["getattr_ms"] > 0.0 and d["grant_ms"] > 0.0
            assert 0.0 < d["grant_srv_ms"] <= d["grant_ms"]
    finally:
        await cluster.stop()


def encoder_for(backend: str):
    """The two backends a span tree is pinned under: the numpy golden
    encoder, and the device encoder on the CPU platform (the Pallas
    interpreter), which opens the four boundary spans."""
    from lizardfs_tpu.core.encoder import CpuChunkEncoder, TpuChunkEncoder

    if backend == "cpu":
        return CpuChunkEncoder()
    return TpuChunkEncoder(force_cpu=True, interpret=True)


def check_one_tree(spans, root_name, phases, delta, holder, backend):
    """One op's ring spans form ONE tree under ``root_name``; its
    top-level phases plus the root's self time equal the wall within
    2 %; under ``holder`` sits the call across the encoder boundary
    with its four spans, in order (the device backend only)."""
    ids = {s["span_id"]: s for s in spans}
    roots = [s for s in spans if s["parent_id"] not in ids]
    assert [s["name"] for s in roots] == [root_name], roots
    assert len({s["trace_id"] for s in spans}) == 1
    root = roots[0]
    wall = (root["t1"] - root["t0"]) * 1e3
    assert delta["reps"] == 1
    assert delta["wall_ms"] == pytest.approx(wall, abs=0.05)
    assert delta["self_ms"] == pytest.approx(root["self_ms"], abs=0.05)
    top = sum(delta[f"{p}_ms"] for p in phases.top_level)
    assert top + delta["self_ms"] == pytest.approx(wall, rel=0.02), (
        top, delta)
    # what the top level holds are the root's direct children
    direct = {s["name"] for s in spans if s["parent_id"] == root["span_id"]}
    assert direct <= set(phases.top_level) | {"throttle"}, direct

    def ancestors(s):
        while s["parent_id"] in ids:
            s = ids[s["parent_id"]]
            yield s["name"]

    boundary = [s for s in spans if s["name"] == "boundary"]
    if backend == "cpu":
        assert not boundary
        return
    assert boundary, "no call crossed the encoder boundary"
    for b in boundary:
        assert holder in list(ancestors(b)), list(ancestors(b))
        legs = sorted((s for s in spans if s["parent_id"] == b["span_id"]),
                      key=lambda s: s["t0"])
        assert [s["name"] for s in legs] == [
            "dev_stage", "dev_put", "dev_run", "dev_fetch"]
        assert {"op", "k", "m", "rows", "bytes"} <= set(b["attrs"])
        assert sum(s["t1"] - s["t0"] for s in legs) <= (
            b["t1"] - b["t0"]) + 1e-6
    for leg in ("dev_stage", "dev_put", "dev_run", "dev_fetch"):
        assert delta[f"{leg}_ms"] > 0.0
    assert delta["boundary_ms"] == pytest.approx(
        sum((b["t1"] - b["t0"]) * 1e3 for b in boundary), abs=0.05)


@pytest.mark.asyncio
@pytest.mark.parametrize("backend,plane", [
    ("cpu", "scatter"), ("tpu_interpret", "scatter"), ("cpu", "per_part"),
])
async def test_pwrite_yields_one_span_tree_that_sums_to_wall(
    tmp_path, monkeypatch, backend, plane
):
    """A 2 MiB pwrite of four full ec(8,4) stripes (the shape of the
    benchmark's streaming cell): root, getattr, lock, grant, encode
    (split, and under it the boundary's four spans), send (one part
    span for the pooled scatter exchange, its legs under it; with the
    exchange made to fail, a part span a part beside it) and commit,
    as one tree."""
    from lizardfs_tpu.core import native_io

    if not native_io.parts_scatter_available():
        pytest.skip("native parts scatter not built")
    payload = _payload(2 * 2**20)
    cluster = Cluster(tmp_path, n_cs=12)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        client.encoder = encoder_for(backend)
        if plane == "per_part":
            def boom(*a, **k):
                raise native_io.NativeIOError(5, "injected scatter failure")

            monkeypatch.setattr(
                native_io, "write_parts_scatter_blocking", boom)
        f = await client.create(1, f"tree_{backend}.bin")
        await client.setgoal(f.inode, EC84_GOAL)
        await client.pwrite(f.inode, 0, payload)  # chunk made, shape warm
        client.trace_ring.clear()
        before = client.write_phases.snapshot()
        await client.pwrite(f.inode, len(payload), payload)
        d = phase_delta(client.write_phases.snapshot(), before)
        spans = client.trace_ring.dump()
        check_one_tree(spans, "pwrite", client.write_phases, d, "encode",
                       backend)
        by_name: dict = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        for name in ("getattr", "grant", "encode", "split", "send",
                     "commit"):
            assert len(by_name[name]) == 1, name
        # the chunk's write lock was free: no wait, so no lock span
        assert "lock" not in by_name and d["lock_ms"] == 0.0
        assert by_name["split"][0]["parent_id"] == \
            by_name["encode"][0]["span_id"]
        parts = by_name["part"]
        assert all(p["parent_id"] == by_name["send"][0]["span_id"]
                   for p in parts)
        exchange = [p for p in parts if p["attrs"]["plane"] == "scatter"]
        assert len(exchange) == 1
        assert exchange[0]["attrs"]["parts"] == 12
        assert exchange[0]["attrs"]["bytes"] == 12 * 262144
        if plane == "scatter":
            # ONE exchange: one worker, one hop, every leg once, and
            # the way back from where C saw the last leg end (PR 36),
            # the worker's wait to get the GIL back under it
            assert len(parts) == 1
            legs = [s for s in spans
                    if s["parent_id"] == parts[0]["span_id"]]
            assert sorted(s["name"] for s in legs) == [
                "hop", "part_data", "part_dial", "part_end", "part_init",
                "wake"], legs
            wake = [s for s in legs if s["name"] == "wake"][0]
            assert wake["attrs"]["after"] == "thread"
            assert wake["attrs"]["turns"] >= 1
            gil = [s for s in spans if s["name"] == "wake_gil"
                   and s["parent_id"] == wake["span_id"]]
            assert len(gil) == 1 and gil[0]["t1"] <= wake["t1"] + 1e-4
            end = [s for s in legs if s["name"] == "part_end"][0]
            assert abs(wake["t0"] - end["t1"]) < 1e-3
            for leg in ("part", "hop", "part_init", "part_data",
                        "part_end"):
                assert d[f"{leg}_ms"] > 0.0, leg
            assert d["part_ack_ms"] == 0.0
        else:
            parts = [p for p in parts if p is not exchange[0]]
            assert len(parts) == 12
            assert {p["attrs"]["bytes"] for p in parts} == {262144}
            assert {p["attrs"]["plane"] for p in parts} <= {
                "native", "asyncio"}
            for p in parts:
                legs = {s["name"] for s in spans
                        if s["parent_id"] == p["span_id"]}
                want = ({"hop", "part_dial", "part_init", "part_data",
                         "part_end"} if p["attrs"]["plane"] == "native" else
                        {"part_dial", "part_init", "part_data", "part_ack",
                         "part_end"})
                assert want <= legs, (p["attrs"], legs)
        # nested phases split their parents: never more than them
        assert d["split_ms"] <= d["encode_ms"]
        assert d["grant_srv_ms"] <= d["grant_ms"]
        # two writers on one chunk: the second waits for the lock, and
        # the wait is a span of its own under its root
        client.trace_ring.clear()
        await asyncio.gather(
            client.pwrite(f.inode, 0, payload),
            client.pwrite(f.inode, len(payload), payload),
        )
        waits = [s for s in client.trace_ring.dump() if s["name"] == "lock"]
        assert len(waits) == 1 and waits[0]["bucket"] == "queue"
        assert (waits[0]["t1"] - waits[0]["t0"]) > 0.001
        client.cache.invalidate(f.inode)
        assert await client.read_file(f.inode, 0, 2 * len(payload)) == \
            payload * 2
    finally:
        await cluster.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("call,live_blocks,stripes", [(1, 2, 12), (2, 1, 11)])
async def test_partial_stripe_pwrite_reads_back_then_patches(
    tmp_path, call, live_blocks, stripes
):
    """The second and the third of sequential 2 MiB pwrites at ec(3,2)
    start 128 KiB and 64 KiB into a 192 KiB stripe: one tree whose
    top level runs getattr, grant, rmw_read (the head stripe's live
    blocks, the plan's waves under it), rmw_patch (the region), encode,
    send, commit; it sums to the wall, and the branch counts itself."""
    payload = _payload(2 * 2**20)
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        f = await client.create(1, f"rmw_{call}.bin")
        await client.setgoal(f.inode, EC32_GOAL)
        for j in range(call):
            await client.pwrite(f.inode, j * len(payload), payload)
        client.trace_ring.clear()
        before = client.write_phases.snapshot()
        await client.pwrite(f.inode, call * len(payload), payload)
        d = phase_delta(client.write_phases.snapshot(), before)
        spans = client.trace_ring.dump()
        check_one_tree(spans, "pwrite", client.write_phases, d, "encode",
                       "cpu")
        root = next(s for s in spans if s["name"] == "pwrite")
        top = sorted((s for s in spans if s["parent_id"] == root["span_id"]),
                     key=lambda s: s["t0"])
        assert [s["name"] for s in top] == [
            "getattr", "grant", "rmw_read", "rmw_patch", "encode", "send",
            "commit"]
        by_name = {s["name"]: s for s in top}
        region = stripes * 3 * MFSBLOCKSIZE
        assert by_name["rmw_read"]["attrs"] == {
            "bytes": live_blocks * MFSBLOCKSIZE, "stripes": 1}
        assert by_name["rmw_read"]["bucket"] == "net"
        assert by_name["rmw_patch"]["attrs"] == {"bytes": region}
        assert by_name["rmw_patch"]["bucket"] == "compute"
        waves = [s for s in spans if s["name"] == "waves"]
        assert [w["parent_id"] for w in waves] == [
            by_name["rmw_read"]["span_id"]]
        assert d["rmw_read_ms"] > 0.0 and d["rmw_patch_ms"] > 0.0
        assert d["waves_ms"] <= d["rmw_read_ms"]
        # the window's counts are write_file's: a pwrite leaves them at 0
        assert {n: d[n] for n in client.write_phases.counts} == dict(
            dict.fromkeys(WRITE_COUNTS, 0),
            rmw_reads=1, rmw_read_bytes=live_blocks * MFSBLOCKSIZE,
            rmw_region_bytes=region, payload_bytes=len(payload),
            ec_payload_bytes=len(payload))
        client.cache.invalidate(f.inode)
        assert await client.read_file(
            f.inode, 0, (call + 1) * len(payload)) == payload * (call + 1)
    finally:
        await cluster.stop()


def test_phase_breakdown_counts_beside_its_times():
    """Counts ride the snapshot under their own names, read 0 until
    charged, and a delta of two snapshots keeps them exact integers."""
    pb = PhaseBreakdown("t", {"a": None, "b": "a"}, counts=("calls", "bytes"))
    before = pb.snapshot()
    assert before == {"a_ms": 0.0, "b_ms": 0.0, "self_ms": 0.0,
                      "wall_ms": 0.0, "reps": 0, "calls": 0, "bytes": 0}
    pb.add("a", 0.0015)
    pb.count("calls")
    pb.count("bytes", 3 * 2**31)
    pb.add_wall(0.002, 0.0005)
    d = phase_delta(pb.snapshot(), before)
    assert d == {"a_ms": 1.5, "b_ms": 0.0, "self_ms": 0.5, "wall_ms": 2.0,
                 "reps": 1, "calls": 1, "bytes": 3 * 2**31}
    assert all(isinstance(d[k], int) for k in ("reps", "calls", "bytes"))
    # a snapshot taken before the program had the count (an older
    # client's pushed stats) subtracts as 0
    assert phase_delta(pb.snapshot(), {"a_ms": 1.0})["calls"] == 1


def test_top_renders_the_write_phases_and_the_rmw_counts(capsys):
    """`lizardfs-admin top` names a session's dominant write phase and,
    where its writers are not stripe-aligned, how many calls read back
    and what the branch moved beyond the payload; a session whose
    client predates the counts gets the phase line alone."""
    from lizardfs_tpu.tools.admin_cli import _print_top

    pb = PhaseBreakdown("client_write", WRITE_PHASES, WRITE_COUNTS)
    pb.add("send", 0.6)
    pb.add("rmw_read", 0.2)
    pb.add("part", 5.0)  # nested: never ranked against send
    for name, n in (("rmw_reads", 21), ("rmw_read_bytes", 2 * 2**20),
                    ("rmw_region_bytes", 69568 * 1024),
                    ("payload_bytes", 64 * 2**20)):
        pb.count(name, n)
    for _ in range(32):
        pb.add_wall(0.03)
    old = {k: v for k, v in pb.snapshot().items() if k not in WRITE_COUNTS}
    _print_top({"sessions": {
        "s1": {"info": "new", "write_phases": pb.snapshot()},
        "s2": {"info": "old", "write_phases": old}}})
    out = capsys.readouterr().out
    assert out.count("write phases (32 writes, wall 960ms) dominant send") == 2
    assert out.count("read-modify-write") == 1
    assert "21 of 32 writes read back, +9.3% bytes beyond the payload" in out


# --- adaptive write window ---------------------------------------------------


@pytest.mark.asyncio
async def test_windowed_write_byte_identity_depths(tmp_path):
    """The adaptive write window must store what the golden oracle says
    at every depth. Pinned for depths {1, 2, 8} on a 6-CS cluster —
    ec(8,4)'s 12 parts over 6 servers force the shared-connection
    multiplexing (part-addressed 1215 frames)."""
    payload = _payload(BIG)
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        client.WRITE_PIPELINE_MIN_BYTES = 1
        for depth in (1, 2, 8):
            client.write_window.max_depth = depth
            client.write_window.depth = min(2, depth)
            before = client.op_counters.get("write_window", 0)
            inode = await _write_and_read_back(
                cluster, client, EC84_GOAL, f"win{depth}.bin", payload
            )
            assert client.op_counters.get("write_window", 0) > before, \
                f"windowed path did not engage at depth {depth}"
            await _assert_parts_match_oracle(
                cluster, client, inode, payload, f"depth {depth}"
            )
        assert client.op_counters.get("write_pipeline_fallback", 0) == 0
    finally:
        await cluster.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("n_cs,depth,stage", [
    (6, 1, "send"), (6, 2, "send"), (6, 8, "send"),
    (6, 1, "ack"), (6, 2, "ack"), (6, 8, "ack"),
    (12, 8, "send"),  # a connection a part: nothing multiplexed
])
async def test_windowed_write_mid_stripe_failure_retries(
    tmp_path, n_cs, depth, stage
):
    """A mid-stripe transport failure on the windowed path — during a
    segment send or while collecting a window's acks — must fall back
    and still produce a correct file at every pinned depth (torn
    segments healed by the fallback's full-part rewrite)."""
    from lizardfs_tpu.core import native_io

    payload = _payload(9 * 2**20)
    cluster = Cluster(tmp_path, n_cs=n_cs)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        client.WRITE_PIPELINE_MIN_BYTES = 1
        client.write_window.max_depth = depth
        client.write_window.depth = min(2, depth)
        target = ("send_segment_window" if stage == "send"
                  else "collect_acks")
        orig = getattr(native_io.PartsScatterSession, target)
        calls = {"n": 0}

        def broken(self, *args, **kw):
            calls["n"] += 1
            if calls["n"] == 2:  # mid-chunk: segment 1 already landed
                self.close()
                raise native_io.NativeIOError(-1, "injected")
            return orig(self, *args, **kw)

        setattr(native_io.PartsScatterSession, target, broken)
        try:
            await _write_and_read_back(
                cluster, client, EC84_GOAL, f"wfb_{stage}{depth}.bin",
                payload,
            )
        finally:
            setattr(native_io.PartsScatterSession, target, orig)
        assert calls["n"] >= 2, "injection never hit the windowed path"
        assert client.op_counters.get("write_pipeline_fallback", 0) >= 1
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_windowed_write_no_deadlock_under_credit_pressure(tmp_path):
    """Credit exhaustion must reap acks, never block: with one frame
    credit per chunkserver and a deep window, a writer that blocked on
    credits while holding outstanding segments would wait on ITSELF
    (and two concurrent writers on each other) forever. Both a solo
    and a concurrent pair of striped writes must complete."""
    import asyncio as aio

    payload = _payload(10 * 2**20)
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        client.WRITE_PIPELINE_MIN_BYTES = 1
        client.write_window.cs_credits = 1  # worst-case starvation
        client.write_window.max_depth = 8

        async def one(name):
            f = await client.create(1, name)
            await client.setgoal(f.inode, EC84_GOAL)
            await client.write_file(f.inode, payload)
            return f.inode

        ino = await aio.wait_for(one("solo.bin"), 60.0)
        a, b = await aio.wait_for(
            aio.gather(one("pair_a.bin"), one("pair_b.bin")), 120.0
        )
        for inode in (ino, a, b):
            client.cache.invalidate(inode)
            assert await client.read_file(
                inode, 0, len(payload)
            ) == payload
        # starvation really happened (the scenario is exercised, not
        # accidentally dodged)
        assert client.metrics.series["write_window_credit_waits"].total > 0
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_commit_coalescing_multi_chunk_and_kill_switch(tmp_path):
    """A multi-chunk write pays ONE coalesced CltomaWriteChunkEndBatch
    per flush instead of a WriteChunkEnd handshake per chunk."""
    from lizardfs_tpu.constants import MFSCHUNKSIZE

    payload = _payload(MFSCHUNKSIZE + 2 * 2**20)  # 2 chunks
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        f = await client.create(1, "coalesced.bin")
        await client.write_file(f.inode, payload)  # goal 1: no EC cost
        assert client.op_counters.get("CltomaWriteChunkEndBatch", 0) == 1, \
            "multi-chunk write did not coalesce its commits"
        assert client.op_counters.get("CltomaWriteChunkEnd", 0) == 0, \
            "coalesced write still paid per-chunk end handshakes"
        assert (await client.getattr(f.inode)).length == len(payload)
        coalesced = client.metrics.series["write_commits_coalesced"].total
        assert coalesced >= 1, "coalesce counter not exported"
        client.cache.invalidate(f.inode)
        back = await client.read_file(f.inode, 0, len(payload))
        assert back == payload
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_commit_coalescing_failed_chunk_commits_immediately(tmp_path):
    """A failed chunk write must NOT coalesce its end: the EIO end goes
    out immediately (releasing the master's chunk lock before the retry
    takes a fresh grant), while clean chunks still batch."""
    from lizardfs_tpu.core import native_io  # noqa: F401

    payload = _payload(4 * 2**20)
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        orig = client._push_chunk_parts
        calls = {"n": 0}

        async def flaky(grant, chunk_data):
            calls["n"] += 1
            if calls["n"] == 1:
                raise ConnectionError("injected push failure")
            return await orig(grant, chunk_data)

        client._push_chunk_parts = flaky
        try:
            f = await client.create(1, "flaky.bin")
            await client.write_file(f.inode, payload)
        finally:
            client._push_chunk_parts = orig
        # attempt 1 failed -> immediate EIO end; retry succeeded -> its
        # clean end flushed through the batch path
        assert client.op_counters.get("CltomaWriteChunkEnd", 0) == 1
        assert client.op_counters.get("CltomaWriteChunkEndBatch", 0) == 1
        client.cache.invalidate(f.inode)
        assert await client.read_file(f.inode, 0, len(payload)) == payload
    finally:
        await cluster.stop()


# --- satellite regressions --------------------------------------------------


def test_decode_default_fills_missing_trailing_fields():
    """A version-skewed peer that predates a trailing field must still
    decode: the new probe u8 on CltomaIoLimitRequest (and any trailing
    tail generally) default-fills instead of failing strict parse."""
    msg = m.CltomaIoLimitRequest(req_id=3, group="grp", probe=1)
    old_wire = msg.pack_body()[:-1]  # sender without the probe field
    parsed = m.CltomaIoLimitRequest.parse(old_wire)
    assert (parsed.req_id, parsed.group, parsed.probe) == (3, "grp", 0)

    # several trailing fields missing at once, ending on a scalar/list/str
    reply = m.MatoclIoLimitReply(
        req_id=1, status=0, bytes_per_sec=10, renew_ms=500,
        subsystem="cg", limits_active=1,
    )
    full = reply.pack_body()
    # strip limits_active (u8) + subsystem (u32 len + 2 bytes)
    stripped = full[: -(1 + 4 + 2)]
    parsed = m.MatoclIoLimitReply.parse(stripped)
    assert parsed.renew_ms == 500
    assert parsed.subsystem == ""
    assert parsed.limits_active == 0

    # a field cut MID-VALUE is corruption, not skew: still refused
    # (renew_ms u32 left with 2 of its 4 bytes)
    with pytest.raises(Exception):
        m.MatoclIoLimitReply.parse(full[: -(1 + 4 + 2 + 2)])

    # a REQUIRED (pre-skew, verdict-bearing) field missing at an exact
    # boundary is also refused: tolerance covers only the additive
    # suffix, never e.g. renew_ms/bytes_per_sec/status — a reply
    # truncated there must not default-fill into "unlimited, OK"
    with pytest.raises(Exception):
        m.MatoclIoLimitReply.parse(full[: -(1 + 4 + 2 + 4)])

    # trailing EXTRA bytes stay rejected (newer-sender direction is
    # handled by the sender, not by silently eating bytes)
    with pytest.raises(ValueError):
        m.CltomaIoLimitRequest.parse(msg.pack_body() + b"x")

    # tolerance is OPT-IN: a non-tolerant message with a missing
    # trailing field must still FAIL the parse — default-filling a
    # truncated write ack's status u8 would read as st.OK and report a
    # commit no chunkserver ever acknowledged (fail-open)
    ack = m.CstoclWriteStatus(req_id=1, chunk_id=2, write_id=3, status=5)
    assert m.CstoclWriteStatus.SKEW_TOLERANT_FROM is None
    with pytest.raises(Exception):
        m.CstoclWriteStatus.parse(ack.pack_body()[:-1])


def test_locate_epoch_clear_bumps_generation():
    """_locate_epoch.clear() must never reset an inode to a
    previously-seen token: an in-flight locate that snapshotted the
    pre-clear token may not cache its (possibly pre-mutation) reply
    even if per-inode epochs climb back to the same numbers."""
    client = Client("127.0.0.1", 0)
    inode = 42
    client._drop_locates(inode)          # epoch 1
    token = client._locate_token(inode)  # in-flight locate snapshots this
    # invalidations on many other inodes overflow the table -> clear
    for other in range(70000):
        if other != inode:
            client._locate_epoch[other] = 1
    client._drop_locates(inode + 1)      # tips past the bound, clears
    assert not client._locate_epoch or len(client._locate_epoch) <= 2
    client._drop_locates(inode)          # per-inode epoch back to 1
    assert client._locate_token(inode) != token, (
        "post-clear token aliases the pre-clear token; a raced locate "
        "would cache a stale reply"
    )
    # and without a clear, tokens do still match across a quiet period
    quiet = client._locate_token(inode)
    assert client._locate_token(inode) == quiet


# --- same-host shared-memory part rings (native/shm_ring.h) -----------------


@pytest.mark.asyncio
async def test_shm_ring_byte_identity_on_off_depths(tmp_path, monkeypatch):
    """Windowed striped writes with the shm ring ON and OFF
    (LZ_SHM_RING=0) at depths {1, 2, 8} must store what the golden
    oracle says, chunk bytes and stored CRC tables. The copy-free
    descriptor path may only change HOW bytes move, never what lands
    on disk."""
    from lizardfs_tpu.core import native_io

    if not native_io.parts_shm_available():
        pytest.skip("native shm ring not built")
    payload = _payload(BIG)
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        client.WRITE_PIPELINE_MIN_BYTES = 1
        for ring_on in (True, False):
            if ring_on:
                monkeypatch.delenv("LZ_SHM_RING", raising=False)
            else:
                monkeypatch.setenv("LZ_SHM_RING", "0")
            for depth in (1, 2, 8):
                client.write_window.max_depth = depth
                client.write_window.depth = min(2, depth)
                before_shm = client.op_counters.get("write_shm", 0)
                inode = await _write_and_read_back(
                    cluster, client, EC84_GOAL,
                    f"shm_{ring_on}_{depth}.bin", payload,
                )
                engaged = client.op_counters.get("write_shm", 0) > before_shm
                assert engaged == ring_on, (
                    f"ring engagement mismatch at depth {depth}: "
                    f"on={ring_on} engaged={engaged}"
                )
                await _assert_parts_match_oracle(
                    cluster, client, inode, payload,
                    f"ring {ring_on} depth {depth}",
                )
        assert client.op_counters.get("write_pipeline_fallback", 0) == 0
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_shm_ring_mid_stripe_failure_falls_back(tmp_path):
    """A transport failure during a ring descriptor send mid-chunk must
    degrade — the whole-part fallback heals the torn segments — and
    still produce a correct file, with the fallback recorded."""
    from lizardfs_tpu.core import native_io

    if not native_io.parts_shm_available():
        pytest.skip("native shm ring not built")
    payload = _payload(9 * 2**20)
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        client.WRITE_PIPELINE_MIN_BYTES = 1
        orig = native_io.PartsScatterSession._ring_send_descs
        calls = {"n": 0}

        def broken(self, *args, **kw):
            calls["n"] += 1
            if calls["n"] == 2:  # mid-chunk: segment 1 already landed
                self.close()
                raise native_io.NativeIOError(-1, "injected")
            return orig(self, *args, **kw)

        native_io.PartsScatterSession._ring_send_descs = broken
        try:
            await _write_and_read_back(
                cluster, client, EC84_GOAL, "ring_fb.bin", payload
            )
        finally:
            native_io.PartsScatterSession._ring_send_descs = orig
        assert calls["n"] >= 2, "injection never hit the ring path"
        assert client.op_counters.get("write_pipeline_fallback", 0) >= 1
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_shm_ring_chunkserver_death_mid_write_recovers(tmp_path):
    """Killing a part holder in the middle of a ring write must not
    lose data: the windowed path fails, the client re-locates and
    rewrites through the fallback chain, and the bytes read back."""
    from lizardfs_tpu.core import native_io

    if not native_io.parts_shm_available():
        pytest.skip("native shm ring not built")
    payload = _payload(9 * 2**20)
    cluster = Cluster(tmp_path, n_cs=12)
    await cluster.start(health_interval=30.0)
    try:
        client = await cluster.client()
        client.WRITE_PIPELINE_MIN_BYTES = 1
        orig = native_io.PartsScatterSession.send_segment_window
        state = {"n": 0}

        def killing(self, *args, **kw):
            state["n"] += 1
            if state["n"] == 2:
                # emulate the holder dying mid-stripe: every ring
                # connection of this session drops (the proactor tears
                # its segments down exactly as on a real SIGKILL)
                self.close()
                raise native_io.NativeIOError(-1, "holder died")
            return orig(self, *args, **kw)

        native_io.PartsScatterSession.send_segment_window = killing
        try:
            await _write_and_read_back(
                cluster, client, EC84_GOAL, "ring_cs_death.bin", payload
            )
        finally:
            native_io.PartsScatterSession.send_segment_window = orig
        assert client.op_counters.get("write_pipeline_fallback", 0) >= 1
    finally:
        await cluster.stop()
