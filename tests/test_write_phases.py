"""Phase-instrumented, pipelined write path.

Pins the tentpole's two contracts:
  * the per-phase accounting (encode/stage/send/commit) is plumbed in
    the right units — phases are all exercised by a striped write and
    their busy-time sum lands in the same ballpark as the rep's wall
    clock (serial ordering keeps them comparable; see tolerance notes),
  * the segmented stripe pipeline is byte-identical to the serial path
    (parity AND per-block CRCs, verified against the golden
    striping.split_chunk oracle and against a serial write's on-disk
    part files), and the LZ_WRITE_PIPELINE=0 kill switch forces serial.

Plus regressions for the r05 ADVICE satellites: trailing-field
default-fill at decode, and the locate-epoch clear generation.
"""

import asyncio
import os

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


@pytest.mark.asyncio
@pytest.mark.parametrize("goal", [EC84_GOAL, EC32_GOAL])
async def test_pipelined_write_byte_identical_to_serial(tmp_path, goal):
    """Same payload written pipelined and serial (kill switch) must
    produce identical part files: data bytes, parity bytes, and the
    stored per-block CRC tables — and both must match the golden
    split_chunk oracle."""
    payload = _payload(12 * 2**20 + 12345)  # multi-stripe + ragged tail
    cluster = Cluster(tmp_path, n_cs=12)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        client.WRITE_PIPELINE_MIN_BYTES = 1  # engage on the small payload
        client.write_pipeline = True
        ino_pipe = await _write_and_read_back(
            cluster, client, goal, "pipe.bin", payload
        )
        assert client.op_counters.get("write_pipeline", 0) >= 1, \
            "pipelined path did not engage"
        client.write_pipeline = False  # the LZ_WRITE_PIPELINE=0 path
        ino_serial = await _write_and_read_back(
            cluster, client, goal, "serial.bin", payload
        )
        assert client.op_counters.get("write_pipeline", 0) == 1, \
            "kill switch did not force the serial path"

        loc_p = await client.chunk_info(ino_pipe, 0)
        loc_s = await client.chunk_info(ino_serial, 0)
        parts_p = _find_part_files(cluster, loc_p.chunk_id)
        parts_s = _find_part_files(cluster, loc_s.chunk_id)
        assert set(parts_p) == set(parts_s) and parts_p

        # golden oracle: client-side split of the same chunk bytes
        slice_type = geometry.ChunkPartType.from_id(
            next(iter(parts_p))
        ).type
        golden = striping.split_chunk(
            np.frombuffer(payload, dtype=np.uint8), slice_type
        )
        for part_id in sorted(parts_p):
            cpt = geometry.ChunkPartType.from_id(part_id)
            data_p, crcs_p = _read_part(parts_p[part_id])
            data_s, crcs_s = _read_part(parts_s[part_id])
            assert data_p == data_s, f"part {cpt.part} bytes differ"
            assert crcs_p == crcs_s, f"part {cpt.part} CRC tables differ"
            want = golden[cpt.part]
            assert (
                np.frombuffer(data_p, dtype=np.uint8)
                == want[: len(data_p)]
            ).all(), f"part {cpt.part} differs from the golden split"
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_phase_breakdown_sums_to_wall_clock(tmp_path):
    """Serial (kill-switch) writes: every phase is populated and the
    busy-time sum is within tolerance of wall clock. The serial path
    still overlaps the whole-chunk encode with the data-part sends, so
    the sum may exceed wall — but never by more than the double-counted
    encode; and phases can't account for more than all of wall plus
    that overlap, nor less than half of it (catches unit mistakes and
    unplumbed phases, the failure modes this accounting can actually
    have)."""
    payload = _payload(8 * 2**20)
    cluster = Cluster(tmp_path, n_cs=12)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        client.write_pipeline = False
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
            # the span tree's own statement of the same: the grant is a
            # phase of its own now, and on this strictly serial path
            # the top level plus the root's self time IS the wall
            assert d["getattr_ms"] > 0.0 and d["grant_ms"] > 0.0
            assert 0.0 < d["grant_srv_ms"] <= d["grant_ms"]
            top = sum(d[f"{p}_ms"] for p in client.write_phases.top_level)
            assert top + d["self_ms"] == pytest.approx(
                d["wall_ms"], rel=0.02
            ), (top, d)
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
            # ONE exchange: one worker, one hop, every leg once
            assert len(parts) == 1
            legs = [s["name"] for s in spans
                    if s["parent_id"] == parts[0]["span_id"]]
            assert sorted(legs) == ["hop", "part_data", "part_dial",
                                    "part_end", "part_init"], legs
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
        assert {n: d[n] for n in client.write_phases.counts} == {
            "rmw_reads": 1, "rmw_read_bytes": live_blocks * MFSBLOCKSIZE,
            "rmw_region_bytes": region, "payload_bytes": len(payload)}
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


@pytest.mark.asyncio
async def test_pipelined_write_survives_mid_write_fallback(tmp_path):
    """A pipeline transport failure must degrade to the serial path and
    still produce a correct file (torn segments healed by the full-part
    rewrite). Pins the PR-1 (window kill-switch) pipeline; the windowed
    path has its own failure test below."""
    from lizardfs_tpu.core import native_io

    payload = _payload(9 * 2**20)
    cluster = Cluster(tmp_path, n_cs=12)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        client.WRITE_PIPELINE_MIN_BYTES = 1
        client.write_window = None  # LZ_WRITE_WINDOW=0 path
        orig = native_io.PartsScatterSession.send_segment
        calls = {"n": 0}

        def broken(self, payloads, lengths, part_offset, write_id):
            calls["n"] += 1
            if calls["n"] == 2:  # fail mid-chunk, after segment 1 landed
                self.close()
                raise native_io.NativeIOError(-1, "injected")
            return orig(self, payloads, lengths, part_offset, write_id)

        native_io.PartsScatterSession.send_segment = broken
        try:
            await _write_and_read_back(
                cluster, client, EC84_GOAL, "fb.bin", payload
            )
        finally:
            native_io.PartsScatterSession.send_segment = orig
        assert client.op_counters.get("write_pipeline_fallback", 0) >= 1
    finally:
        await cluster.stop()


# --- adaptive write window (LZ_WRITE_WINDOW) --------------------------------


@pytest.mark.asyncio
async def test_windowed_write_byte_identity_depths(tmp_path):
    """The adaptive write window must stay byte-identical to the serial
    reference at every depth. Pinned for depths {1, 2, 8} on a 6-CS
    cluster — ec(8,4)'s 12 parts over 6 servers force the vectored
    path's shared-connection multiplexing (part-addressed 1215 frames)
    — plus the LZ_WRITE_WINDOW=0 kill switch (PR-1 double-buffered
    path) and the strictly serial golden reference."""
    payload = _payload(12 * 2**20 + 12345)  # multi-stripe + ragged tail
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        client.WRITE_PIPELINE_MIN_BYTES = 1
        assert client.write_window is not None, "window off by default?"
        inodes: dict[object, int] = {}
        for depth in (1, 2, 8):
            client.write_window.max_depth = depth
            client.write_window.depth = min(2, depth)
            before = client.op_counters.get("write_window", 0)
            inodes[depth] = await _write_and_read_back(
                cluster, client, EC84_GOAL, f"win{depth}.bin", payload
            )
            assert client.op_counters.get("write_window", 0) > before, \
                f"windowed path did not engage at depth {depth}"
        # kill switch: the PR-1 double-buffered pipeline, wire-exact
        # (per-part 1214 sockets, per-segment ack barriers)
        client.write_window = None
        before_win = client.op_counters.get("write_window", 0)
        inodes["pr1"] = await _write_and_read_back(
            cluster, client, EC84_GOAL, "win_pr1.bin", payload
        )
        assert client.op_counters.get("write_window", 0) == before_win, \
            "kill switch did not disable the windowed path"
        # strictly serial golden reference
        client.write_pipeline = False
        inodes["serial"] = await _write_and_read_back(
            cluster, client, EC84_GOAL, "win_serial.bin", payload
        )

        loc_ref = await client.chunk_info(inodes["serial"], 0)
        parts_ref = _find_part_files(cluster, loc_ref.chunk_id)
        assert parts_ref
        slice_type = geometry.ChunkPartType.from_id(
            next(iter(parts_ref))
        ).type
        import numpy as np_mod

        golden = striping.split_chunk(
            np_mod.frombuffer(payload, dtype=np_mod.uint8), slice_type
        )
        for variant, ino in inodes.items():
            if variant == "serial":
                continue
            loc = await client.chunk_info(ino, 0)
            parts = _find_part_files(cluster, loc.chunk_id)
            assert set(parts) == set(parts_ref), f"{variant}: part set"
            for part_id in sorted(parts):
                cpt = geometry.ChunkPartType.from_id(part_id)
                data_v, crcs_v = _read_part(parts[part_id])
                data_r, crcs_r = _read_part(parts_ref[part_id])
                assert data_v == data_r, \
                    f"{variant}: part {cpt.part} bytes differ from serial"
                assert crcs_v == crcs_r, \
                    f"{variant}: part {cpt.part} CRC tables differ"
                want = golden[cpt.part]
                assert (
                    np_mod.frombuffer(data_v, dtype=np_mod.uint8)
                    == want[: len(data_v)]
                ).all(), f"{variant}: part {cpt.part} vs golden split"
    finally:
        await cluster.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("depth", [1, 2, 8])
@pytest.mark.parametrize("stage", ["send", "ack"])
async def test_windowed_write_mid_stripe_failure_retries(
    tmp_path, depth, stage
):
    """A mid-stripe transport failure on the windowed path — during a
    segment send or while collecting a window's acks — must fall back
    and still produce a correct file at every pinned depth (torn
    segments healed by the serial full-part rewrite)."""
    from lizardfs_tpu.core import native_io

    payload = _payload(9 * 2**20)
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        client.WRITE_PIPELINE_MIN_BYTES = 1
        assert client.write_window is not None
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
        assert client.write_window is not None
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
    """A multi-chunk write under the window pays ONE coalesced
    CltomaWriteChunkEndBatch per flush instead of a WriteChunkEnd
    handshake per chunk; the kill switch restores the per-chunk
    commits. Both produce the same bytes and file length."""
    from lizardfs_tpu.constants import MFSCHUNKSIZE

    payload = _payload(MFSCHUNKSIZE + 2 * 2**20)  # 2 chunks
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        assert client.write_window is not None
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

        # kill switch: per-chunk end handshakes, no batch RPC
        client.write_window = None
        g = await client.create(1, "perchunk.bin")
        await client.write_file(g.inode, payload)
        assert client.op_counters.get("CltomaWriteChunkEndBatch", 0) == 1
        assert client.op_counters.get("CltomaWriteChunkEnd", 0) == 2, \
            "kill switch did not restore per-chunk commits"
        assert (await client.getattr(g.inode)).length == len(payload)
        client.cache.invalidate(g.inode)
        assert await client.read_file(g.inode, 0, len(payload)) == payload
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
        assert client.write_window is not None
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
    (LZ_SHM_RING=0) at depths {1, 2, 8} must produce identical chunk
    bytes and stored CRC tables — and match the strictly serial golden
    reference. The copy-free descriptor path may only change HOW bytes
    move, never what lands on disk."""
    from lizardfs_tpu.core import native_io

    if not native_io.parts_shm_available():
        pytest.skip("native shm ring not built")
    payload = _payload(12 * 2**20 + 12345)  # multi-stripe + ragged tail
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        client.WRITE_PIPELINE_MIN_BYTES = 1
        assert client.write_window is not None
        inodes: dict[object, int] = {}
        for ring_on in (True, False):
            if ring_on:
                monkeypatch.delenv("LZ_SHM_RING", raising=False)
            else:
                monkeypatch.setenv("LZ_SHM_RING", "0")
            for depth in (1, 2, 8):
                client.write_window.max_depth = depth
                client.write_window.depth = min(2, depth)
                before_shm = client.op_counters.get("write_shm", 0)
                key = ("ring" if ring_on else "sock", depth)
                inodes[key] = await _write_and_read_back(
                    cluster, client, EC84_GOAL,
                    f"shm_{ring_on}_{depth}.bin", payload,
                )
                engaged = client.op_counters.get("write_shm", 0) > before_shm
                assert engaged == ring_on, (
                    f"ring engagement mismatch at depth {depth}: "
                    f"on={ring_on} engaged={engaged}"
                )
        # strictly serial golden reference
        client.write_pipeline = False
        inodes["serial"] = await _write_and_read_back(
            cluster, client, EC84_GOAL, "shm_serial.bin", payload
        )
        loc_ref = await client.chunk_info(inodes["serial"], 0)
        parts_ref = _find_part_files(cluster, loc_ref.chunk_id)
        assert parts_ref
        for variant, ino in inodes.items():
            if variant == "serial":
                continue
            loc = await client.chunk_info(ino, 0)
            parts = _find_part_files(cluster, loc.chunk_id)
            assert set(parts) == set(parts_ref), f"{variant}: part set"
            for part_id in sorted(parts):
                cpt = geometry.ChunkPartType.from_id(part_id)
                data_v, crcs_v = _read_part(parts[part_id])
                data_r, crcs_r = _read_part(parts_ref[part_id])
                assert data_v == data_r, \
                    f"{variant}: part {cpt.part} bytes differ from serial"
                assert crcs_v == crcs_r, \
                    f"{variant}: part {cpt.part} CRC tables differ"
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_shm_ring_mid_stripe_failure_falls_back(tmp_path):
    """A transport failure during a ring descriptor send mid-chunk must
    degrade — scatterv/serial heal the torn segments — and still
    produce a correct file, with the fallback recorded."""
    from lizardfs_tpu.core import native_io

    if not native_io.parts_shm_available():
        pytest.skip("native shm ring not built")
    payload = _payload(9 * 2**20)
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start(health_interval=5.0)
    try:
        client = await cluster.client()
        client.WRITE_PIPELINE_MIN_BYTES = 1
        assert client.write_window is not None
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
        assert client.write_window is not None
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
