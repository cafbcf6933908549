"""Read-path microscope (ISSUE 18): phase-instrumented reads + the
latency attribution engine.

Two layers of pins:

* **Synthetic attribution**: ``tracing.attribute_timeline`` decomposes
  arbitrary merged timelines — overlapping spans, missing legs,
  clock-skewed rings, zero-duration ops — and must NEVER produce a
  negative bucket, a >100% split, or a sum that differs from the op's
  wall time. These are the failure modes a span-union engine can
  actually have.

* **Exactly-once phase accounting**: one LOGICAL read charges the
  client's ``read_phases`` wall/rep accounting exactly once no matter
  how many transient retries, CRC-rejected parts, or replica fallbacks
  the implementation burned underneath (phases may re-enter — busy
  time is real — but wall/reps may not). Each scenario runs under the
  deterministic scheduler across seeds so retry interleavings can't
  hide a double count.

Plus the ``make read-smoke`` end-to-end: a traced ec(8,4) degraded
read whose phases surface in the master's `top` rollup and whose SLO
breach rows carry a full attribution.
"""

import pytest

from lizardfs_tpu.runtime import detsched, faults, tracing
from lizardfs_tpu.runtime.metrics import phase_delta
from lizardfs_tpu.runtime.tracing import (
    ATTRIBUTION_BUCKETS,
    attribute_timeline,
    format_attribution,
    merge_timeline,
)
from lizardfs_tpu.utils import data_generator

# seed 1 rides tier-1; the rest of the matrix is slow-marked (each
# scenario boots a real in-process cluster under the deterministic
# loop — the full matrix belongs to `make racehunt`, not the fast gate)
SEEDS = (
    1,
    pytest.param(2, marks=pytest.mark.slow),
    pytest.param(3, marks=pytest.mark.slow),
)

READ_PHASES = ("locate", "dial", "wait", "net", "decode", "gather")


def _sum(attr: dict) -> float:
    return sum(attr["buckets_ms"].values())


def _assert_sane(attr: dict) -> None:
    """The invariants every attribution must hold: buckets sum exactly
    to wall, nothing negative, no bucket past 100%."""
    assert _sum(attr) == pytest.approx(attr["wall_ms"], abs=0.01)
    for b in ATTRIBUTION_BUCKETS:
        assert attr["buckets_ms"][b] >= 0.0, attr
        assert 0.0 <= attr["pct"][b] <= 100.0, attr
    assert attr["dominant"] in ATTRIBUTION_BUCKETS


# --- synthetic attribution engine -------------------------------------------


def test_attribution_overlapping_spans_cannot_exceed_wall():
    """Overlapping spans: every wall instant lands in ONE bucket, in
    priority order (queue > disk > net > compute)."""
    attr = attribute_timeline({
        "trace_id": 0x11, "wall_ms": 100.0, "segments": [
            {"role": "client", "name": "read:net", "bucket": "net",
             "start_ms": 0.0, "dur_ms": 80.0},
            {"role": "client", "name": "read:net", "bucket": "net",
             "start_ms": 10.0, "dur_ms": 80.0},   # overlaps the first
            {"role": "client", "name": "queue_wait:dial", "bucket": "queue",
             "start_ms": 0.0, "dur_ms": 50.0},    # overlaps both
            {"role": "client", "name": "read:decode", "bucket": "compute",
             "start_ms": 40.0, "dur_ms": 60.0},
        ],
    })
    _assert_sane(attr)
    # queue claims [0,50); net keeps only its unclaimed [50,90);
    # compute only [90,100) — nothing double-counted
    assert attr["buckets_ms"]["queue"] == pytest.approx(50.0, abs=0.01)
    assert attr["buckets_ms"]["net"] == pytest.approx(40.0, abs=0.01)
    assert attr["buckets_ms"]["compute"] == pytest.approx(10.0, abs=0.01)
    assert attr["buckets_ms"]["unattributed"] == pytest.approx(0.0,
                                                               abs=0.01)
    assert attr["dominant"] == "queue"


def test_attribution_missing_legs_surface_as_unattributed():
    """A timeline with instrumentation gaps (a leg that recorded no
    span) must say so — the gap lands in ``unattributed``, it is never
    smeared over the known buckets."""
    attr = attribute_timeline({
        "trace_id": 0x12, "wall_ms": 50.0, "segments": [
            {"role": "client", "name": "read:net", "bucket": "net",
             "start_ms": 0.0, "dur_ms": 10.0},
            # a span whose site named no bucket (or one this table does
            # not have) is not guessed at from its name: unattributed
            {"role": "client", "name": "read:net",
             "start_ms": 20.0, "dur_ms": 10.0},
            {"role": "client", "name": "send", "bucket": "wire",
             "start_ms": 30.0, "dur_ms": 10.0},
        ],
    })
    _assert_sane(attr)
    assert attr["buckets_ms"]["net"] == pytest.approx(10.0, abs=0.01)
    assert attr["buckets_ms"]["unattributed"] == pytest.approx(40.0,
                                                               abs=0.01)
    assert attr["dominant"] == "unattributed"
    # no segments at all: 100% unattributed, still sums to wall
    empty = attribute_timeline(
        {"trace_id": 0x13, "wall_ms": 25.0, "segments": []}
    )
    _assert_sane(empty)
    assert empty["buckets_ms"]["unattributed"] == pytest.approx(25.0,
                                                                abs=0.01)


def test_attribution_clock_skewed_rings_clamp_to_wall():
    """Cross-process rings skew: a chunkserver span can start before
    the client wall opened or end after it closed. Segments clamp to
    the wall window — never a negative gap, never a sum past wall."""
    attr = attribute_timeline({
        "trace_id": 0x14, "wall_ms": 100.0, "segments": [
            # starts 20 ms BEFORE the wall: only [0,10) counts
            {"role": "chunkserver", "name": "cs_read", "bucket": "net",
             "start_ms": -20.0, "dur_ms": 30.0},
            # runs 500 ms past the wall: only [90,100) counts
            {"role": "chunkserver", "name": "net:send", "bucket": "net",
             "start_ms": 90.0, "dur_ms": 500.0},
            # entirely outside the wall: contributes nothing
            {"role": "chunkserver", "name": "disk", "bucket": "disk",
             "start_ms": 200.0, "dur_ms": 50.0},
            # corrupt negative duration: skipped, not subtracted
            {"role": "client", "name": "read:net", "bucket": "net",
             "start_ms": 40.0, "dur_ms": -5.0},
        ],
    })
    _assert_sane(attr)
    assert attr["buckets_ms"]["net"] == pytest.approx(20.0, abs=0.01)
    assert attr["buckets_ms"]["disk"] == pytest.approx(0.0, abs=0.01)
    assert attr["buckets_ms"]["unattributed"] == pytest.approx(80.0,
                                                               abs=0.01)


def test_attribution_zero_duration_op():
    """A zero-wall op (cache hit timed under the clock's resolution)
    must come back all-zero — no division error, no negative gap."""
    attr = attribute_timeline({
        "trace_id": 0x15, "wall_ms": 0.0, "segments": [
            {"role": "client", "name": "read:net", "bucket": "net",
             "start_ms": 0.0, "dur_ms": 5.0},
        ],
    })
    assert _sum(attr) == 0.0
    assert all(attr["pct"][b] == 0.0 for b in ATTRIBUTION_BUCKETS)
    # the renderer handles it too
    assert "wall 0.00 ms" in format_attribution(attr)


def test_attribution_native_queue_disk_net_split():
    """A chunkserver span carrying the native plane's
    queue_us/disk_us/net_us attrs splits into synthetic sub-intervals
    (queue -> disk -> net from the segment start) instead of
    classifying its envelope — one cs_read feeds three buckets."""
    attr = attribute_timeline({
        "trace_id": 0x16, "wall_ms": 10.0, "segments": [
            {"role": "chunkserver", "name": "cs_read", "bucket": "disk",
             "start_ms": 0.0, "dur_ms": 10.0,
             "attrs": {"queue_us": 2000, "disk_us": 3000,
                       "net_us": 4000}},
        ],
    })
    _assert_sane(attr)
    assert attr["buckets_ms"]["queue"] == pytest.approx(2.0, abs=0.01)
    assert attr["buckets_ms"]["disk"] == pytest.approx(3.0, abs=0.01)
    assert attr["buckets_ms"]["net"] == pytest.approx(4.0, abs=0.01)
    assert attr["buckets_ms"]["unattributed"] == pytest.approx(1.0,
                                                               abs=0.01)
    # attrs lying past the envelope clamp to it: a skewed native clock
    # cannot inflate the split past the span's own duration
    over = attribute_timeline({
        "trace_id": 0x17, "wall_ms": 10.0, "segments": [
            {"role": "chunkserver", "name": "cs_read", "bucket": "disk",
             "start_ms": 0.0, "dur_ms": 4.0,
             "attrs": {"queue_us": 9_000_000, "disk_us": 9_000_000,
                       "net_us": 9_000_000}},
        ],
    })
    _assert_sane(over)
    assert over["buckets_ms"]["queue"] == pytest.approx(4.0, abs=0.01)
    assert over["buckets_ms"]["disk"] == pytest.approx(0.0, abs=0.01)


def test_attribution_composes_with_merge_timeline():
    """End-to-end through the real merge: raw spans (client root +
    cross-role legs) -> merge_timeline(wall_name=...) ->
    attribute_timeline still sums exactly to the merged wall."""
    tid = 0x18
    spans = [
        {"trace_id": tid, "span_id": 1, "parent_id": 0, "role": "client",
         "name": "read_file", "t0": 100.0, "t1": 100.1},
        {"trace_id": tid, "span_id": 2, "parent_id": 0, "role": "client",
         "name": "read:locate", "bucket": "net", "t0": 100.0, "t1": 100.01},
        {"trace_id": tid, "span_id": 3, "parent_id": 0, "role": "client",
         "name": "queue_wait:dial", "bucket": "queue", "t0": 100.01, "t1": 100.02},
        {"trace_id": tid, "span_id": 4, "parent_id": 0,
         "role": "chunkserver", "name": "cs_read", "bucket": "disk",
         "t0": 100.02, "t1": 100.07,
         "attrs": {"queue_us": 10_000, "disk_us": 20_000,
                   "net_us": 15_000}},
        {"trace_id": tid, "span_id": 5, "parent_id": 0, "role": "client",
         "name": "read:decode", "bucket": "compute", "t0": 100.07, "t1": 100.09},
    ]
    timeline = merge_timeline(spans, tid, wall_name="read_file")
    attr = attribute_timeline(timeline)
    _assert_sane(attr)
    assert attr["wall_ms"] == pytest.approx(100.0, abs=0.5)
    for b in ("queue", "disk", "net", "compute"):
        assert attr["buckets_ms"][b] > 0.0, (b, attr)
    rendered = format_attribution(attr)
    assert f"0x{tid:x}" in rendered and "dominant" in rendered


# --- exactly-once read-phase accounting (detsched seed matrix) --------------


async def _transient_retry_scenario(tmp_path, seed: int):
    """A striped read whose first part serve errors once: the read
    recovers underneath and the LOGICAL read charges wall/reps ONCE."""
    from tests.test_cluster import Cluster, EC_GOAL

    cluster = Cluster(tmp_path, n_cs=5, native_data_plane=False)
    await cluster.start()
    try:
        # armed BEFORE any data IO: while rules are armed the client's
        # native fast paths stand down, which the deterministic loop
        # REQUIRES (detsched runs executor jobs inline; a blocking
        # native socket call against the in-process CS would deadlock)
        faults.install(
            "seed=%d; chunkserver:serve_read error,limit=1" % seed
        )
        c = await cluster.client()
        f = await c.create(1, "ret.bin")
        await c.setgoal(f.inode, EC_GOAL)
        payload = data_generator.generate(3, 5 * 65536 + 17).tobytes()
        await c.write_file(f.inode, payload)
        c.cache.invalidate(f.inode)
        c._locate_cache.clear()
        before = c.read_phases.snapshot()
        data = await c.read_file(f.inode, 0, len(payload))
        assert data == payload
        return phase_delta(c.read_phases.snapshot(), before)
    finally:
        faults.clear()
        await cluster.stop()


async def _crc_reject_scenario(tmp_path, seed: int):
    """A read that receives one bit-flipped part (advertised CRC is the
    stored one, so only the client's piece-CRC check catches it): the
    damaged part is rejected, parity recovery decodes around it, and
    the logical read still counts ONCE."""
    from tests.test_cluster import Cluster, EC_GOAL

    cluster = Cluster(tmp_path, n_cs=5, native_data_plane=False)
    await cluster.start()
    try:
        # never-firing placeholder keeps native paths down for the
        # write; the real one-shot flip arms before the read under test
        faults.install(
            "seed=%d; chunkserver:disk_pread flip,after=1000000" % seed
        )
        c = await cluster.client()
        f = await c.create(1, "crc.bin")
        await c.setgoal(f.inode, EC_GOAL)
        payload = data_generator.generate(5, 6 * 65536 + 321).tobytes()
        await c.write_file(f.inode, payload)
        c.cache.invalidate(f.inode)
        c._locate_cache.clear()
        faults.install(
            "seed=%d; chunkserver:disk_pread flip,limit=1" % seed
        )
        before = c.read_phases.snapshot()
        data = await c.read_file(f.inode, 0, len(payload))
        assert data == payload, "decode recovery returned wrong bytes"
        rejected = c.metrics.counter("damaged_parts_reported").total
        return phase_delta(c.read_phases.snapshot(), before), rejected
    finally:
        faults.clear()
        await cluster.stop()


async def _replica_fallback_locate_scenario(tmp_path, seed: int):
    """A read whose locate leg routes to a shadow replica that REFUSES
    (follow link down): the locate falls back to the primary and the
    logical read counts ONCE, with the locate phase populated."""
    import asyncio

    from lizardfs_tpu.chunkserver.server import ChunkServer
    from lizardfs_tpu.client.client import Client
    from lizardfs_tpu.master.server import MasterServer
    from tests.test_cluster import EC_GOAL, make_goals

    active = MasterServer(str(tmp_path / "m1"), goals=make_goals())
    await active.start()
    shadow = MasterServer(
        str(tmp_path / "m2"), goals=make_goals(),
        personality="shadow", active_addr=("127.0.0.1", active.port),
    )
    await shadow.start()
    addrs = [("127.0.0.1", active.port), ("127.0.0.1", shadow.port)]
    servers = []
    for i in range(5):
        cs = ChunkServer(str(tmp_path / f"cs{i}"), master_addr=addrs,
                         heartbeat_interval=0.2,
                         native_data_plane=False)
        await cs.start()
        servers.append(cs)
    # a rule that never fires keeps the client's native fast paths
    # down (detsched inlines executor jobs — see transient scenario)
    faults.install(
        "seed=%d; chunkserver:disk_pwrite error,after=1000000" % seed
    )
    c = Client("", 0, master_addrs=addrs)
    await c.connect()
    try:
        f = await c.create(1, "fb.bin")
        await c.setgoal(f.inode, EC_GOAL)
        payload = data_generator.generate(7, 4 * 65536 + 5).tobytes()
        await c.write_file(f.inode, payload)
        deadline = asyncio.get_running_loop().time() + 10
        while (shadow.changelog.version != active.changelog.version
               and asyncio.get_running_loop().time() < deadline):
            await asyncio.sleep(0.05)
        # prime the replica link, then break the follow stream so the
        # next replica-routed locate is REFUSED -> primary fallback
        assert (await c.getattr(f.inode)).inode == f.inode
        shadow._shadow_task.cancel()
        await asyncio.sleep(0.2)
        assert not shadow._replica_ready()
        c.cache.invalidate(f.inode)
        c._locate_cache.clear()
        before = c.read_phases.snapshot()
        fallbacks0 = c.metrics.counter("shadow_fallbacks").total
        data = await c.read_file(f.inode, 0, len(payload))
        assert data == payload
        return (phase_delta(c.read_phases.snapshot(), before),
                c.metrics.counter("shadow_fallbacks").total - fallbacks0)
    finally:
        faults.clear()
        await c.close()
        for cs in servers:
            await cs.stop()
        await shadow.stop()
        await active.stop()


@pytest.mark.parametrize("seed", SEEDS)
def test_read_phases_count_once_across_transient_retry(tmp_path, seed):
    d = detsched.run(_transient_retry_scenario(tmp_path, seed), seed=seed)
    assert d["reps"] == 1, f"seed {seed}: wall/reps charged {d['reps']}x"
    assert d["wall_ms"] > 0.0
    for phase in ("locate", "net"):
        assert d[f"{phase}_ms"] > 0.0, f"seed {seed}: {phase} unplumbed"
    # every phase cell exists in the snapshot even when idle this rep
    for phase in READ_PHASES:
        assert f"{phase}_ms" in d


@pytest.mark.parametrize("seed", SEEDS)
def test_read_phases_count_once_across_crc_reject_decode(tmp_path, seed):
    d, rejected = detsched.run(
        _crc_reject_scenario(tmp_path, seed), seed=seed
    )
    assert rejected >= 1, f"seed {seed}: the flip never hit the read"
    assert d["reps"] == 1, f"seed {seed}: wall/reps charged {d['reps']}x"
    assert d["decode_ms"] > 0.0, "decode recovery left no decode time"
    assert d["net_ms"] > 0.0


@pytest.mark.parametrize("seed", SEEDS)
def test_read_phases_count_once_across_replica_fallback(tmp_path, seed):
    d, fallbacks = detsched.run(
        _replica_fallback_locate_scenario(tmp_path, seed), seed=seed
    )
    assert fallbacks >= 1, f"seed {seed}: replica fallback never engaged"
    assert d["reps"] == 1, f"seed {seed}: wall/reps charged {d['reps']}x"
    assert d["locate_ms"] > 0.0, "fallback locate left no locate time"


# --- one span tree per logical read ------------------------------------------


@pytest.mark.asyncio
@pytest.mark.parametrize("backend", ["cpu", "tpu_interpret"])
async def test_degraded_read_yields_one_span_tree_that_sums_to_wall(
    tmp_path, backend
):
    """A 2 MiB read of an ec(8,4) chunk that has lost a data part:
    root, locate, the throttle's wait, plan, waves (the parallel part
    reads, net a part), decode (holding the recover call's four
    boundary spans), gather and copy, as one tree whose top level plus
    self time is the wall."""
    from tests.test_cluster import WIDE_EC_GOAL, Cluster
    from tests.test_write_phases import check_one_tree, encoder_for

    cluster = Cluster(tmp_path, n_cs=12)
    # no rebuild while the reads run: these chunkservers share the
    # process, and so the client's socket pool, and a replicator's wave
    # would take the idle sockets the read's native wave rides on
    await cluster.start(health_interval=30.0)
    try:
        c = await cluster.client()
        c.encoder = encoder_for(backend)
        f = await c.create(1, f"tree_{backend}.bin")
        await c.setgoal(f.inode, WIDE_EC_GOAL)
        payload = data_generator.generate(13, 2 * 2**20).tobytes()
        await c.write_file(f.inode, payload)
        chunk_id = (await c.chunk_info(f.inode, 0)).chunk_id
        victim = next(
            cs for cs in cluster.chunkservers
            if any(cf.chunk_id == chunk_id and cf.part_id % 64 == 0
                   for cf in cs.store.all_parts()))
        await victim.stop()
        c.cache.invalidate(f.inode)
        c._locate_cache.clear()
        # the first read learns the holder is gone (and warms the
        # recover shape); the one under test plans round it
        assert await c.read_file(f.inode, 0, len(payload)) == payload
        c.cache.invalidate(f.inode)
        c.trace_ring.clear()
        before = c.read_phases.snapshot()
        assert await c.read_file(f.inode, 0, len(payload)) == payload
        d = phase_delta(c.read_phases.snapshot(), before)
        spans = c.trace_ring.dump()
        check_one_tree(spans, "read_file", c.read_phases, d, "decode",
                       backend)
        names = {s["name"] for s in spans}
        assert {"plan", "waves", "net", "decode", "gather", "copy"} <= names
        waves = [s for s in spans if s["name"] == "waves"]
        assert len(waves) == 1
        nets = [s for s in spans if s["name"] == "net"]
        assert len(nets) >= 8 and all(
            s["parent_id"] == waves[0]["span_id"] for s in nets)
        # net sums the parallel parts (one row a part on every plane;
        # on the wave plane a part's net is short beside `waves`, which
        # also holds the wait for the loop)
        durs = [(s["t1"] - s["t0"]) * 1e3 for s in nets]
        assert d["net_ms"] == pytest.approx(sum(durs), abs=0.5)
        assert min(durs) > 0.0
        # what `waves` holds that is no part's `net` on the wave plane
        # (PR 32): the wait for the one worker, a `hop` that ends
        # before any part's net opens (on the native plane a part's net
        # held its own hop), and the loop's wake-up after the last
        # part's end, which is `waves`' self time
        on_wave = [s for s in nets if s["attrs"]["plane"] == "wave"]
        hops = [s for s in spans if s["name"] == "hop"
                and s["parent_id"] == waves[0]["span_id"]]
        assert len(on_wave) == 8 and len(hops) == 1
        assert all(s["t0"] >= hops[0]["t1"] - 1e-4 for s in on_wave)
        assert waves[0]["t1"] >= max(s["t1"] for s in on_wave)
        if backend != "cpu":
            rec = [s for s in spans if s["name"] == "boundary"]
            assert rec[0]["attrs"]["op"] == "recover"
            assert rec[0]["attrs"]["rows"] == 8
    finally:
        await cluster.stop()


# --- end-to-end smoke (`make read-smoke`) -----------------------------------


@pytest.mark.asyncio
async def test_read_smoke_degraded_ec84_top_and_slowops(tmp_path):
    """The acceptance path in one run: a traced ec(8,4) DEGRADED read
    (one part holder down, parity recovery live) whose phase breakdown
    surfaces in the master's `top` rollup, whose SLO breach rows embed
    a full attribution, and whose merged trace attributes with buckets
    summing exactly to wall."""
    from tests.test_cluster import WIDE_EC_GOAL, Cluster

    cluster = Cluster(tmp_path, n_cs=12, native_data_plane=False)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "smoke.bin")
        await c.setgoal(f.inode, WIDE_EC_GOAL)  # $ec(8,4)
        payload = data_generator.generate(11, 2 * 2**20 + 321).tobytes()
        await c.write_file(f.inode, payload)

        # degrade: one part holder gone, locations go stale
        await cluster.chunkservers[0].stop()
        c.cache.invalidate(f.inode)
        c._locate_cache.clear()
        # drop the pooled connections the write warmed up so the read
        # pays (and charges) real pool-miss dials
        from lizardfs_tpu.core.conn_pool import GLOBAL_POOL
        GLOBAL_POOL.close_all()
        # force every cs_read over its objective so the breach rows
        # (and their attributions) are guaranteed to exist
        for cs in cluster.chunkservers[1:]:
            cs.slo.set_threshold("read", 0.01)

        # a never-firing rule stands the client's native gather down:
        # the smoke pins the fully-instrumented wave path (pool dials,
        # per-part waits) — the native plane's queue-wait slot contract
        # has its own pins in tests/test_native_serve.py
        faults.install(
            "seed=1; chunkserver:disk_pwrite error,after=1000000"
        )
        tid = tracing.start_trace()
        try:
            data = await c.read_file(f.inode, 0, len(payload))
        finally:
            tracing.clear_trace()
            faults.clear()
        assert data == payload, "degraded ec(8,4) read corrupted data"
        assert tid, "tracing disabled — smoke needs LZ_TRACE on"

        # 1) phases surface per-session in the master's top rollup
        d = c.read_phases.snapshot()
        assert d["reps"] >= 1 and d["wall_ms"] > 0.0
        await c.push_session_stats()
        report = cluster.master.top_report()
        entry = report["sessions"][f"s{c.session_id}"]
        assert entry["read_phases"]["reps"] >= 1
        busy = {p: entry["read_phases"][f"{p}_ms"] for p in READ_PHASES}
        assert max(busy.values()) > 0.0, busy

        # 2) the merged trace attributes: buckets sum exactly to wall
        spans = list(c.trace_ring.dump(tid))
        for cs in cluster.chunkservers[1:]:
            spans.extend(cs.trace_ring.dump(tid))
        timeline = merge_timeline(spans, tid, wall_name="read_file")
        assert timeline["segments"], "traced read recorded no spans"
        attr = attribute_timeline(timeline)
        _assert_sane(attr)
        assert _sum(attr) == pytest.approx(timeline["wall_ms"], abs=0.01)
        rendered = format_attribution(attr)
        assert f"0x{tid:x}" in rendered and "dominant" in rendered

        # 3) the SLO breach rows carry the attribution (slowops embed)
        rows = []
        for cs in cluster.chunkservers[1:]:
            rows.extend(cs.slo.recorder.slowops())
        ours = [e for e in rows if e.get("trace_id") == tid]
        assert ours, "no slowops row recorded for the traced read"
        attributed = [e for e in ours if e.get("attribution")]
        assert attributed, "slowops rows lost the attribution embed"
        a = attributed[0]["attribution"]
        assert a["dominant"] in ATTRIBUTION_BUCKETS
        assert sum(a["buckets_ms"].values()) == pytest.approx(
            a["wall_ms"], abs=0.01
        )

        # 4) the queue-wait gate family is live on the client registry
        # (pool-miss dials / dead-holder dial failures charge it)
        cells = c.metrics.labeled_timings.get("queue_wait", {})
        assert any(
            dict(k).get("gate") == "dial" for k in cells
        ), "dial queue-wait gate never charged"
    finally:
        await cluster.stop()
