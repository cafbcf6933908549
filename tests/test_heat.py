"""The cluster heat loop (ISSUE 17): HeatTracker sketch mechanics,
adaptive goal boost/demote through the changelog, observatory-driven
placement loads, the SLO→QoS auto-arm chain, and the LZ_HEAT
kill-switch off-equivalence (four spellings).
"""

import asyncio
import json
import time

import pytest

from lizardfs_tpu.constants import OFF_SPELLINGS
from lizardfs_tpu.master.heat import EVICT_EPSILON, HeatTracker
from lizardfs_tpu.proto import messages as m
from lizardfs_tpu.runtime import qos
from lizardfs_tpu.utils import data_generator

from tests.test_cluster import Cluster

pytestmark = pytest.mark.asyncio


# --- tracker mechanics (pure data structure, no cluster) --------------------


async def test_sketch_bounded_and_space_saving():
    """The table never exceeds capacity; a newcomer at a full table
    evicts the coldest cell and inherits its decayed score (the
    Space-Saving over-estimate, never an under-estimate)."""
    t = HeatTracker(capacity=4)
    for cid in range(4):
        t.charge("chunk", cid, nbytes=float((cid + 1) * 1000))
    # key 0 is coldest (1000); newcomer inherits its score
    t.charge("chunk", 99, nbytes=500.0)
    table = t._tables["chunk"]
    assert len(table) == 4
    assert 0 not in table
    assert table[99].nbytes == 1000.0 + 500.0
    assert t.evictions == 1
    # raw totals are per-tracking-run, not inherited
    assert table[99].bytes_total == 500.0


async def test_decay_and_cell_retirement():
    """tick() halves scores per half-life and drops cells that decay
    below the epsilon floor (a quiet cluster's heat page empties)."""
    t = HeatTracker(capacity=8, half_life_s=1.0)
    t.charge("chunk", 1, nbytes=8.0)
    t.tick(100.0)  # first tick only stamps the clock
    t.tick(101.0)  # one half-life
    assert t.heat_of("chunk", 1) == pytest.approx(4.0)
    t.tick(111.0)  # ten more half-lives: below EVICT_EPSILON
    assert t.heat_of("chunk", 1) == 0.0
    assert 1 not in t._tables["chunk"]
    assert EVICT_EPSILON >= 0.0


async def test_boost_decisions_hysteresis_and_cap():
    """Boost above heat_boost_bytes, demote only below
    heat_demote_bytes (the band between them never thrashes), hottest
    first under the heat_max_boosted cap."""
    t = HeatTracker(capacity=16)
    t._boost_bytes.value = 100
    t._demote_bytes.value = 10
    t._max_boosted.value = 2
    t._boost_copies.value = 2
    t.charge("chunk", 1, nbytes=500.0)
    t.charge("chunk", 2, nbytes=200.0)
    t.charge("chunk", 3, nbytes=150.0)
    to_boost, to_demote = t.boost_decisions({})
    # cap 2: only the two hottest boost, in heat order
    assert to_boost == [(1, 2), (2, 2)]
    assert to_demote == []
    # mid-band chunk (between demote and boost thresholds) stays
    # boosted: hysteresis, not thrash
    t._tables["chunk"][1].nbytes = 50.0
    to_boost, to_demote = t.boost_decisions({1: 2, 2: 2})
    assert to_demote == []
    # below the demote floor it demotes, freeing cap room for chunk 3
    t._tables["chunk"][1].nbytes = 5.0
    to_boost, to_demote = t.boost_decisions({1: 2, 2: 2})
    assert to_demote == [1]
    assert to_boost == [(3, 2)]


async def test_server_loads_composition():
    """Placement load = heat share + degraded-health penalty + queue
    pressure, each signal clamped."""
    t = HeatTracker(capacity=8)
    t.charge("server", 1, nbytes=300.0)
    t.charge("server", 2, nbytes=100.0)
    loads = t.server_loads(
        {1: {"status": "ok"}, 2: {"status": "degraded"}, 3: {}},
        waiting={3: 32 * 1024 * 1024},
    )
    assert loads[1] == pytest.approx(0.75)
    assert loads[2] == pytest.approx(0.25 + 0.5)
    assert loads[3] == pytest.approx(0.5)  # half of the 64 MiB clamp


async def test_fold_cs_charges_chunks_and_server():
    """A heartbeat heat fold charges every chunk row plus the server's
    own total; malformed rows are skipped, not fatal."""
    t = HeatTracker(capacity=8)
    t.fold_cs(7, {"chunks": [[11, 2, 1000], [12, 1, 500], ["bad"], None]})
    assert t.heat_of("chunk", 11) == 1000.0
    assert t.heat_of("chunk", 12) == 500.0
    assert t.heat_of("server", 7) == 1500.0


# --- the closed loop on a live cluster --------------------------------------


async def _until(cond, timeout=15.0, what=""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        await asyncio.sleep(0.05)
    raise AssertionError(f"never converged: {what}")


async def test_hot_chunk_boost_and_demote_live(tmp_path):
    """A read-hammered chunk crosses the (drill-sized) boost threshold:
    the master commits goal_boost through the changelog, extra copies
    materialize via the RebuildEngine, the heat surfaces (metrics,
    health, admin `heat`) all name it — and once heat decays, the
    goal_demote lands and the boost clears."""
    cluster = Cluster(tmp_path, n_cs=2, native_data_plane=False)
    await cluster.start(health_interval=0.1)
    try:
        master = cluster.master
        assert master.tweaks.set("heat_boost_bytes", str(256 * 1024))
        assert master.tweaks.set("heat_demote_bytes", str(64 * 1024))
        c = await cluster.client()
        f = await c.create(1, "hot.bin")
        payload = data_generator.generate(11, 128 * 1024 + 7).tobytes()
        await c.write_file(f.inode, payload)
        loc = await c.chunk_info(f.inode, 0)
        chunk = master.meta.registry.chunk(loc.chunk_id)
        # storm: repeated full reads; CS folds ride forced heartbeats
        for _ in range(8):
            c.cache.invalidate(f.inode)
            assert await c.read_file(f.inode) == payload
            for cs in cluster.chunkservers:
                await cs._heartbeat()
        await _until(lambda: chunk.boost > 0, what="goal boost")
        assert loc.chunk_id in master.meta.registry.boosted
        # the boost means real replication work: with 2 servers and
        # base goal 1, a second copy appears
        await _until(
            lambda: len({cs for cs, _ in chunk.parts}) >= 2,
            timeout=30.0, what="boosted copy materialized",
        )
        # surfaces: prometheus families, health heat section, admin doc
        prom = master.metrics.to_prometheus()
        assert "lizardfs_heat_bytes_total{" in prom
        assert "lizardfs_heat_ops_total{" in prom
        health = master.cluster_health()
        assert health["heat"]["boosted"], health["heat"]
        reply = await master._admin_command(
            m.AdminCommand(req_id=1, command="heat", json="{}")
        )
        doc = json.loads(reply.json)
        assert doc["enabled"] is True
        assert doc["boosted"]
        assert doc["thresholds"]["heat_boost_bytes"] == 256 * 1024
        assert any(r["key"] == loc.chunk_id for r in doc["chunks"])
        # placement inputs are live: the busy fleet has load scores
        assert isinstance(master.meta.registry.server_load, dict)
        # storm over: collapse the half-life, heat decays, demote lands
        assert master.tweaks.set("heat_half_life_s", "0.1")
        await _until(lambda: chunk.boost == 0, timeout=30.0, what="demote")
        assert loc.chunk_id not in master.meta.registry.boosted
        # data held through the whole cycle (zero acknowledged-op loss)
        c.cache.invalidate(f.inode)
        assert await c.read_file(f.inode) == payload
    finally:
        await cluster.stop()


@pytest.mark.parametrize("native", [True, False])
async def test_a_chunk_being_written_earns_no_heat_and_no_boost(tmp_path,
                                                                native):
    """Heat buys a hot chunk copies to read from; a chunk that is only
    written gains nothing from them (every write then goes to each copy,
    and the copies are made while its bytes still change). Writes far
    past the boost threshold, on either data plane, leave the chunk
    cold and its copies as the goal says; reads of it heat it."""
    cluster = Cluster(tmp_path, n_cs=3, native_data_plane=native)
    await cluster.start(health_interval=0.1)
    try:
        master = cluster.master
        assert master.tweaks.set("heat_boost_bytes", str(256 * 1024))
        c = await cluster.client()
        f = await c.create(1, "written.bin")
        await c.setgoal(f.inode, 2)
        payload = data_generator.generate(12, 2 * 1024 * 1024).tobytes()
        for off in range(0, 8 * len(payload), len(payload)):
            await c.pwrite(f.inode, off, payload)
        for cs in cluster.chunkservers:
            cs._fold_native_trace()
            assert cs._heat == {}
            await cs._heartbeat()
        await asyncio.sleep(0.5)  # health ticks: nothing to boost
        loc = await c.chunk_info(f.inode, 0)
        chunk = master.meta.registry.chunk(loc.chunk_id)
        assert master.heat.heat_of("chunk", loc.chunk_id) == 0.0
        assert chunk.boost == 0 and master.meta.registry.boosted == set()
        assert len({cs for cs, _ in chunk.parts}) == 2
        for _ in range(2):
            c.cache.invalidate(f.inode)
            await c.read_file(f.inode, 0, len(payload))
        for cs in cluster.chunkservers:
            cs._fold_native_trace()
            await cs._heartbeat()
        await _until(
            lambda: master.heat.heat_of("chunk", loc.chunk_id) > 0,
            what="read heat")
    finally:
        await cluster.stop()


async def test_slo_qos_auto_arm_and_expiry(tmp_path):
    """The second auto-arm action: an SLO breach squeezes the top
    offender's fair-share weight (counted, named), and the health tick
    restores the weight when the pressure window expires."""
    cluster = Cluster(tmp_path, n_cs=1, native_data_plane=False)
    await cluster.start(health_interval=0.1)
    try:
        master = cluster.master
        master._qos_apply_config(qos.parse_config(json.dumps({
            "tenants": {"batch": {"weight": 2, "match": ["batch*"]}},
            "rates": {"locate": 10_000},
        })))
        from lizardfs_tpu.client.client import Client

        c = Client("127.0.0.1", master.port, wave_timeout=0.2)
        await c.connect(info="batch-train")
        cluster.clients.append(c)
        f = await c.create(1, "offender.bin")
        await c.write_file(
            f.inode, data_generator.generate(3, 65536).tobytes()
        )
        for _ in range(10):
            await c.chunk_info(f.inode, 0)
        assert master.sessions[c.session_id]["tenant"] == "batch"
        master._slo_qos_arm("locate", 0xBEEF)
        assert master.qos.weights["batch"] == pytest.approx(1.0)  # halved
        assert "batch" in master._heat_qos_pressure
        assert "lizardfs_slo_qos_armed_total{" in (
            master.metrics.to_prometheus()
        )
        # rate limit: an immediate second breach does not double-squeeze
        master._slo_qos_arm("locate", 0xBEEF)
        assert master.qos.weights["batch"] == pytest.approx(1.0)
        # expiry: backdate the window; the health tick restores
        restore, _ = master._heat_qos_pressure["batch"]
        master._heat_qos_pressure["batch"] = (restore, 0.0)
        await _until(
            lambda: master.qos.weights.get("batch") == 2.0,
            what="pressure expiry restore",
        )
        assert "batch" not in master._heat_qos_pressure
    finally:
        await cluster.stop()


# --- LZ_HEAT kill switch: four-spelling off equivalence ---------------------


@pytest.mark.parametrize("spelling", list(OFF_SPELLINGS))
async def test_lz_heat_off_spelling_equivalence(tmp_path, monkeypatch,
                                                spelling):
    """Every documented off spelling kills the whole loop: the tracker
    is never charged, heartbeats carry heat_json="" (byte-identical
    wire), no goal mutation is ever committed, placement reverts to
    free-space weighting, and the metrics page carries no heat
    families."""
    monkeypatch.setenv("LZ_HEAT", spelling)
    cluster = Cluster(tmp_path, n_cs=1, native_data_plane=False)
    await cluster.start(health_interval=0.1)
    try:
        master = cluster.master

        def forbidden(*a, **k):  # pragma: no cover — the assert IS the test
            raise AssertionError("heat loop ran with LZ_HEAT off")

        monkeypatch.setattr(master.heat, "charge", forbidden)
        monkeypatch.setattr(master.heat, "boost_decisions", forbidden)
        c = await cluster.client()
        f = await c.create(1, "cold.bin")
        payload = data_generator.generate(4, 65536).tobytes()
        await c.write_file(f.inode, payload)
        for _ in range(5):
            c.cache.invalidate(f.inode)
            assert await c.read_file(f.inode) == payload
        cs = cluster.chunkservers[0]
        # the CS never accumulates and the heartbeat fold is empty —
        # the wire stays byte-identical to the pre-heat tree
        assert cs._heat == {}
        assert cs._heat_fold_json() == ""
        await cs._heartbeat()
        await asyncio.sleep(0.3)  # a few health ticks
        loc = await c.chunk_info(f.inode, 0)
        assert master.meta.registry.chunk(loc.chunk_id).boost == 0
        assert master.meta.registry.boosted == set()
        assert master.meta.registry.server_load == {}
        assert "heat_" not in master.metrics.to_prometheus()
        assert master.cluster_health()["heat"] == {}
    finally:
        await cluster.stop()
