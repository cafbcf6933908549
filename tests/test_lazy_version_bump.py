"""A chunk's version rises when a copy may have missed a write, not at
every write (master/chunks.py WriteState, master/server.py _write_chunk).

A write grant on an existing chunk raises the version (MatocsSetVersion
to every holder, a ``bump_chunk_version`` line) only where the master
cannot vouch that every holder has every acknowledged write: the first
grant of a chunk in this master's active life, after an end with an
error status, after a grant nobody ended, after any change of the
holder set. Cluster tests in the style of tests/test_cluster.py, each
over a copies goal, xor3 and $ec(3,2); the SIGKILL cases run the
chunkservers as real processes beside an in-process master, so that
its registry and counters are in reach.
"""

import asyncio
import os
import signal
import subprocess
import sys

import pytest

from lizardfs_tpu.chunkserver.server import ChunkServer
from lizardfs_tpu.client.client import Client
from lizardfs_tpu.constants import MFSBLOCKSIZE
from lizardfs_tpu.master.chunks import ChunkRegistry
from lizardfs_tpu.master.server import MasterServer
from lizardfs_tpu.proto import messages as m
from lizardfs_tpu.proto import status as st
from lizardfs_tpu.utils import data_generator

from tests.test_cluster import (
    EC_GOAL, STD2_GOAL, XOR_GOAL, Cluster, make_goals,
)
from tests.test_process_cluster import _free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# goal -> parts a chunk has under it
PARTS = {STD2_GOAL: 2, XOR_GOAL: 4, EC_GOAL: 5}
GOALS = pytest.mark.parametrize(
    "goal", [STD2_GOAL, XOR_GOAL, EC_GOAL], ids=["copies2", "xor3", "ec32"]
)
# a write that is no multiple of any stripe here (xor3: 192 KiB, ec(3,2):
# 192 KiB), so the striped goals take their read-modify-write path too
PIECE = 3 * MFSBLOCKSIZE + 4321


def piece(i: int) -> bytes:
    return data_generator.generate(100 + i, PIECE).tobytes()


class Model:
    """What the file should read back as."""

    def __init__(self):
        self.data = bytearray()

    def write(self, offset: int, data: bytes) -> None:
        if len(self.data) < offset + len(data):
            self.data.extend(bytes(offset + len(data) - len(self.data)))
        self.data[offset:offset + len(data)] = data


def bumps(master) -> dict[str, int]:
    """write_grant_bumps{why} as the master's registry has them."""
    return {
        dict(key)["why"]: int(series.total)
        for key, series in master.metrics.labeled.get(
            "write_grant_bumps", {}
        ).items()
    }


def grants(master) -> int:
    return int(master.metrics.counter("write_grants").total)


def grant_spans(master) -> list[int]:
    """``bumped`` of every traced CltomaWriteChunk in the master's ring."""
    return [
        s["attrs"]["bumped"] for s in master.trace_ring.dump()
        if s["name"] == "CltomaWriteChunk" and "attrs" in s
    ]


def the_chunk(master, inode: int):
    return master.meta.registry.chunk(master.meta.fs.file_node(inode).chunks[0])


@pytest.fixture
def set_versions(monkeypatch):
    """Every MatocsSetVersion an in-process chunkserver is commanded."""
    seen: list[tuple[int, int, int]] = []
    inner = ChunkServer._cmd_set_version

    async def counting(self, msg):
        seen.append((msg.chunk_id, msg.old_version, msg.new_version))
        await inner(self, msg)

    monkeypatch.setattr(ChunkServer, "_cmd_set_version", counting)
    return seen


async def start_file(cluster, goal: int, writes: int = 2):
    """A file under ``goal`` with ``writes`` clean pwrites behind it."""
    c = await cluster.client()
    f = await c.create(1, "lazy.bin")
    await c.setgoal(f.inode, goal)
    model = Model()
    for i in range(writes):
        await c.pwrite(f.inode, i * PIECE, piece(i))
        model.write(i * PIECE, piece(i))
    return c, f.inode, model


# --- the rule itself, on the registry ----------------------------------------


def _registry_with_chunk():
    reg = ChunkRegistry()
    chunk = reg.create_chunk(1)
    reg.record_part(chunk, 1, 0)
    reg.record_part(chunk, 2, 0)
    return reg, chunk


def _clean(reg, chunk, sid=7):
    reg.note_grant(chunk, sid)
    reg.note_write_end(chunk, sid, True)
    assert reg.grant_needs_bump(chunk) == ""


def test_state_fresh_chunk_is_first_grant():
    reg, chunk = _registry_with_chunk()
    assert chunk.writes is None
    assert reg.grant_needs_bump(chunk) == "first_grant"


def test_state_clean_end_of_own_grant_cleans():
    reg, chunk = _registry_with_chunk()
    reg.note_grant(chunk, 7)
    assert reg.grant_needs_bump(chunk) == "no_end"  # in flight
    reg.note_write_end(chunk, 7, True)
    assert reg.grant_needs_bump(chunk) == ""


def test_state_error_end_from_anyone_dirties():
    reg, chunk = _registry_with_chunk()
    _clean(reg, chunk)
    reg.note_write_end(chunk, 99, False)  # no grant of its own
    assert reg.grant_needs_bump(chunk) == "error_end"
    # and takes the outstanding grant with it: a clean end after an
    # error end on the same chunk vouches for nothing
    reg.note_grant(chunk, 7)
    reg.note_write_end(chunk, 8, False)
    reg.note_write_end(chunk, 7, True)
    assert reg.grant_needs_bump(chunk) == "error_end"


def test_state_foreign_clean_end_does_not_clean():
    reg, chunk = _registry_with_chunk()
    reg.note_grant(chunk, 7)
    reg.note_write_end(chunk, 8, True)
    assert reg.grant_needs_bump(chunk) == "no_end"
    reg.note_write_end(chunk, 7, True)  # the holder's own still does
    assert reg.grant_needs_bump(chunk) == ""


def test_state_overtaken_grant_cannot_clean():
    reg, chunk = _registry_with_chunk()
    reg.note_grant(chunk, 7)  # its lock runs out
    reg.note_grant(chunk, 8)
    reg.note_write_end(chunk, 7, True)  # late
    assert reg.grant_needs_bump(chunk) == "no_end"
    reg.note_write_end(chunk, 8, True)
    assert reg.grant_needs_bump(chunk) == ""


@pytest.mark.parametrize("mutate", [
    lambda reg, chunk: reg.record_part(chunk, 3, 0),
    lambda reg, chunk: reg.record_part(chunk, 1, 0),  # re-reported as it is
    lambda reg, chunk: reg.unregister_parts(chunk, {(2, 0)}),
    lambda reg, chunk: reg.drop_part(chunk.chunk_id, 2, 64 * 1 + 0),
    lambda reg, chunk: reg.server_disconnected(1),
    lambda reg, chunk: reg.reset_server_parts(2),
    lambda reg, chunk: reg.touch(chunk),  # a copy started from the holders
], ids=["record_new", "record_same", "unregister", "drop", "disconnect",
        "mirror_reset", "copy_started"])
def test_state_any_holder_change_dirties(mutate):
    # between two writes
    reg, chunk = _registry_with_chunk()
    _clean(reg, chunk)
    mutate(reg, chunk)
    assert reg.grant_needs_bump(chunk) == "holders_changed"
    assert reg.audit_index() == []
    # and under a write: its clean end finds the set touched
    reg, chunk = _registry_with_chunk()
    reg.note_grant(chunk, 7)
    mutate(reg, chunk)
    reg.note_write_end(chunk, 7, True)
    assert reg.grant_needs_bump(chunk) == "holders_changed"


def test_state_forgotten_with_the_active_life():
    reg, chunk = _registry_with_chunk()
    _clean(reg, chunk)
    reg.note_grant(chunk, 7)
    reg.forget_writes()  # demoted, another master granted, promoted again
    assert reg.grant_needs_bump(chunk) == "first_grant"
    reg.note_write_end(chunk, 7, True)  # an end of the life before
    assert reg.grant_needs_bump(chunk) == "first_grant"
    _clean(reg, chunk)


def test_state_is_not_in_the_image():
    """Volatile: neither the sections a shadow downloads nor a restart
    carry the mark."""
    from lizardfs_tpu.master.metadata import MetadataStore

    meta = MetadataStore()
    meta.apply({"op": "create_chunk", "chunk_id": 5, "slice_type": 1,
                "version": 3, "copies": 2, "goal_id": 2})
    _clean(meta.registry, meta.registry.chunk(5))
    again = MetadataStore()
    again.load_sections(meta.to_sections())
    chunk = again.registry.chunk(5)
    assert chunk.version == 3 and chunk.writes is None
    assert again.registry.grant_needs_bump(chunk) == "first_grant"


# --- in-process clusters -----------------------------------------------------


@GOALS
@pytest.mark.asyncio
async def test_clean_writes_keep_the_version(tmp_path, goal, set_versions):
    """N clean sequential pwrites to one chunk leave the version where
    the first left it, command no chunkserver and journal no bump."""
    cluster = Cluster(tmp_path, n_cs=PARTS[goal] + 1)
    await cluster.start(health_interval=30.0)
    try:
        c, inode, model = await start_file(cluster, goal, writes=1)
        master = cluster.master
        chunk = the_chunk(master, inode)
        assert (chunk.version, len(chunk.parts)) == (1, PARTS[goal])
        log0 = master.changelog.version
        for i in range(1, 7):
            # new ground, and twice over bytes that are there
            offset = (i % 4) * PIECE
            await c.pwrite(inode, offset, piece(i))
            model.write(offset, piece(i))
            assert (await c.chunk_info(inode, 0)).version == 1
        assert set_versions == []
        assert grants(master) == 7 and bumps(master) == {}
        assert grant_spans(master) == [0] * 6  # a new chunk's grant has none
        assert master.meta.registry.grant_needs_bump(chunk) == ""
        # one set_length line a write and nothing else
        assert master.changelog.version - log0 == 6
        c.cache.invalidate(inode)
        assert await c.read_file(inode) == bytes(model.data)
    finally:
        await cluster.stop()


@GOALS
@pytest.mark.asyncio
async def test_error_end_makes_next_grant_bump(tmp_path, goal, set_versions):
    cluster = Cluster(tmp_path, n_cs=PARTS[goal] + 1)
    await cluster.start(health_interval=30.0)
    try:
        c, inode, model = await start_file(cluster, goal)
        master = cluster.master
        # an attempt that failed on the client's side of a chunkserver
        grant = await c._grant(inode, 0)
        assert grant.version == 1
        await c._call(
            m.CltomaWriteChunkEnd, chunk_id=grant.chunk_id, inode=inode,
            chunk_index=0, file_length=len(model.data), status=st.EIO,
        )
        assert bumps(master) == {} and set_versions == []
        master.trace_ring.clear()
        await c.pwrite(inode, PIECE // 2, piece(5))
        model.write(PIECE // 2, piece(5))
        assert bumps(master) == {"error_end": 1}
        assert sorted(set_versions) == [(grant.chunk_id, 1, 2)] * PARTS[goal]
        assert grant_spans(master) == [1]
        # and the clean end of that write vouches again
        await c.pwrite(inode, 0, piece(6))
        model.write(0, piece(6))
        assert bumps(master) == {"error_end": 1}
        assert grant_spans(master) == [1, 0]
        assert (await c.chunk_info(inode, 0)).version == 2
        assert grants(master) == 5
        c.cache.invalidate(inode)
        assert await c.read_file(inode) == bytes(model.data)
    finally:
        await cluster.stop()


@GOALS
@pytest.mark.asyncio
async def test_grant_never_ended_bumps(tmp_path, goal, set_versions):
    """A client that died with a grant, its lock run out: no code marks
    the chunk, the grant itself did."""
    cluster = Cluster(tmp_path, n_cs=PARTS[goal] + 1)
    await cluster.start(health_interval=30.0)
    try:
        c, inode, model = await start_file(cluster, goal)
        master = cluster.master
        dead = await cluster.client()
        grant = await dead._grant(inode, 0)
        assert grant.version == 1 and set_versions == []
        with pytest.raises(st.StatusError) as busy:
            await c._grant(inode, 0)
        assert busy.value.code == st.CHUNK_BUSY
        the_chunk(master, inode).locked_until = 1e-9  # thirty seconds on
        await c.pwrite(inode, 0, piece(3))
        model.write(0, piece(3))
        assert bumps(master) == {"no_end": 1}
        assert len(set_versions) == PARTS[goal]
        await c.pwrite(inode, PIECE, piece(4))
        model.write(PIECE, piece(4))
        assert bumps(master) == {"no_end": 1}
        assert (await c.chunk_info(inode, 0)).version == 2
        c.cache.invalidate(inode)
        assert await c.read_file(inode) == bytes(model.data)
    finally:
        await cluster.stop()


@GOALS
@pytest.mark.asyncio
async def test_foreign_clean_end_does_not_clean(tmp_path, goal):
    """A clean end from a session that does not hold the grant unlocks
    the chunk, as it always did, and vouches for nothing."""
    cluster = Cluster(tmp_path, n_cs=PARTS[goal] + 1)
    await cluster.start(health_interval=30.0)
    try:
        c, inode, model = await start_file(cluster, goal)
        master = cluster.master
        other = await cluster.client()
        grant = await c._grant(inode, 0)
        await other._call(
            m.CltomaWriteChunkEnd, chunk_id=grant.chunk_id, inode=inode,
            chunk_index=0, file_length=len(model.data), status=st.OK,
        )
        chunk = the_chunk(master, inode)
        assert master.meta.registry.grant_needs_bump(chunk) == "no_end"
        await other.pwrite(inode, 0, piece(3))
        assert bumps(master) == {"no_end": 1}
        assert chunk.version == 2
    finally:
        await cluster.stop()


@GOALS
@pytest.mark.asyncio
async def test_rebuild_that_adds_a_holder_bumps(tmp_path, goal):
    cluster = Cluster(tmp_path, n_cs=PARTS[goal] + 1)
    await cluster.start(health_interval=0.2)
    try:
        c, inode, model = await start_file(cluster, goal)
        master = cluster.master
        chunk = the_chunk(master, inode)
        before = set(chunk.parts)
        gone = min(cs_id for cs_id, _ in before)
        port = master.meta.registry.servers[gone].port
        victim = next(cs for cs in cluster.chunkservers if cs.port == port)
        await victim.stop()
        cluster.chunkservers.remove(victim)
        for _ in range(200):
            await asyncio.sleep(0.05)
            if len(chunk.parts) == PARTS[goal] and chunk.parts != before:
                break
        assert len(chunk.parts) == PARTS[goal] and chunk.parts != before
        assert chunk.version == 1
        assert master.meta.registry.grant_needs_bump(chunk) == \
            "holders_changed"
        await c.pwrite(inode, PIECE // 3, piece(7))
        model.write(PIECE // 3, piece(7))
        assert bumps(master) == {"holders_changed": 1}
        assert chunk.version == 2 and len(chunk.parts) == PARTS[goal]
        c.cache.invalidate(inode)
        assert await c.read_file(inode) == bytes(model.data)
    finally:
        await cluster.stop()


@GOALS
@pytest.mark.asyncio
async def test_master_restart_bumps_first_grant(tmp_path, goal):
    master = MasterServer(str(tmp_path / "master"), goals=make_goals(),
                          health_interval=30.0)
    await master.start()
    addr = ("127.0.0.1", master.port)
    servers = [
        ChunkServer(str(tmp_path / f"cs{i}"), master_addr=addr,
                    heartbeat_interval=0.2, wave_timeout=0.2)
        for i in range(PARTS[goal])
    ]
    for cs in servers:
        await cs.start()
    c = Client(*addr, wave_timeout=0.2)
    await c.connect()
    try:
        f = await c.create(1, "restart.bin")
        await c.setgoal(f.inode, goal)
        model = Model()
        for i in range(3):
            await c.pwrite(f.inode, i * PIECE, piece(i))
            model.write(i * PIECE, piece(i))
        assert bumps(master) == {} and the_chunk(master, f.inode).version == 1
        await master.stop()
        master = MasterServer(
            str(tmp_path / "master"), goals=make_goals(), port=addr[1],
            health_interval=30.0,
        )
        await master.start()
        for _ in range(100):
            await asyncio.sleep(0.1)
            if len(master.cs_links) == PARTS[goal]:
                break
        chunk = the_chunk(master, f.inode)
        assert len(chunk.parts) == PARTS[goal] and chunk.writes is None
        assert chunk.version == 1
        await c.pwrite(f.inode, 0, piece(5))
        model.write(0, piece(5))
        await c.pwrite(f.inode, PIECE, piece(6))
        model.write(PIECE, piece(6))
        assert bumps(master) == {"first_grant": 1} and grants(master) == 2
        assert chunk.version == 2
        c.cache.invalidate(f.inode)
        assert await c.read_file(f.inode) == bytes(model.data)
    finally:
        await c.close()
        for cs in servers:
            await cs.stop()
        await master.stop()


@GOALS
@pytest.mark.asyncio
async def test_promoted_shadow_bumps_first_grant(tmp_path, goal):
    """A shadow replays no grant and no clean end: what it is promoted
    with vouches for no chunk, though the active's versions stood still
    under its last writes."""
    active = MasterServer(str(tmp_path / "m1"), goals=make_goals(),
                          health_interval=30.0)
    await active.start()
    shadow = MasterServer(
        str(tmp_path / "m2"), goals=make_goals(), health_interval=30.0,
        personality="shadow", active_addr=("127.0.0.1", active.port),
    )
    await shadow.start()
    addrs = [("127.0.0.1", active.port), ("127.0.0.1", shadow.port)]
    servers = [
        ChunkServer(str(tmp_path / f"cs{i}"), master_addr=addrs,
                    heartbeat_interval=0.2, wave_timeout=0.2)
        for i in range(PARTS[goal])
    ]
    for cs in servers:
        await cs.start()
    c = Client("", 0, master_addrs=addrs, wave_timeout=0.2)
    await c.connect()
    try:
        f = await c.create(1, "ha.bin")
        await c.setgoal(f.inode, goal)
        model = Model()
        for i in range(3):
            await c.pwrite(f.inode, i * PIECE, piece(i))
            model.write(i * PIECE, piece(i))
        assert bumps(active) == {} and the_chunk(active, f.inode).version == 1
        for _ in range(100):
            await asyncio.sleep(0.05)
            if shadow.changelog.version == active.changelog.version:
                break
        await active.stop()
        shadow.promote()
        for _ in range(100):
            await asyncio.sleep(0.1)
            if len(shadow.cs_links) == PARTS[goal]:
                break
        assert len(shadow.cs_links) == PARTS[goal]
        await c.pwrite(f.inode, 0, piece(5))
        model.write(0, piece(5))
        await c.pwrite(f.inode, PIECE, piece(6))
        model.write(PIECE, piece(6))
        assert bumps(shadow) == {"first_grant": 1}
        assert the_chunk(shadow, f.inode).version == 2
        c.cache.invalidate(f.inode)
        assert await c.read_file(f.inode) == bytes(model.data)
    finally:
        await c.close()
        for cs in servers:
            await cs.stop()
        await shadow.stop()


# --- chunkservers as processes: SIGKILL --------------------------------------


class KillableCluster:
    """An in-process master (its registry and counters in reach) with
    chunkservers as real processes, to SIGKILL and bring back."""

    def __init__(self, tmp_path, n_cs: int):
        self.tmp = tmp_path
        self.n_cs = n_cs
        self.master: MasterServer | None = None
        self.procs: dict[int, subprocess.Popen] = {}  # LISTEN_PORT -> proc
        self.cfgs: dict[int, str] = {}
        self.clients: list[Client] = []

    def spawn(self, port: int) -> None:
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
        self.procs[port] = subprocess.Popen(
            [sys.executable, "-m", "lizardfs_tpu.chunkserver",
             self.cfgs[port]],
            stdout=open(self.tmp / f"cs{port}.log", "ab"),
            stderr=subprocess.STDOUT, env=env,
        )

    async def start(self) -> None:
        self.master = MasterServer(
            str(self.tmp / "master"), goals=make_goals(),
            health_interval=30.0,  # no rebuild: the holder set is the test's
        )
        await self.master.start()
        for i in range(self.n_cs):
            port = _free_port()
            cfg = self.tmp / f"cs{i}.cfg"
            cfg.write_text(
                f"DATA_PATH = {self.tmp}/cs{i}\n"
                f"LISTEN_PORT = {port}\n"
                f"MASTER_PORT = {self.master.port}\n"
                "HEARTBEAT_INTERVAL = 0.3\n"
            )
            self.cfgs[port] = str(cfg)
            self.spawn(port)
        await self.registered(self.n_cs)

    async def registered(self, n: int) -> None:
        for _ in range(300):
            if len(self.master.cs_links) == n:
                return
            await asyncio.sleep(0.1)
        raise AssertionError(f"{len(self.master.cs_links)} of {n} registered")

    async def client(self) -> Client:
        c = Client("127.0.0.1", self.master.port, wave_timeout=0.3)
        await c.connect()
        self.clients.append(c)
        return c

    def kill9(self, port: int) -> None:
        self.procs[port].send_signal(signal.SIGKILL)
        self.procs[port].wait(timeout=10)

    def port_of(self, cs_id: int) -> int:
        return self.master.meta.registry.servers[cs_id].port

    async def stop(self) -> None:
        for c in self.clients:
            await c.close()
        for p in self.procs.values():
            if p.poll() is None:
                p.terminate()
        for p in self.procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        if self.master is not None:
            await self.master.stop()


@GOALS
@pytest.mark.asyncio
async def test_sigkill_between_writes_bumps_and_refuses_the_stale_part(
        tmp_path, goal):
    cluster = KillableCluster(tmp_path, n_cs=PARTS[goal])
    try:
        await cluster.start()
        c, inode, model = await start_file(cluster, goal)
        master = cluster.master
        chunk = the_chunk(master, inode)
        assert len(chunk.parts) == PARTS[goal] and bumps(master) == {}
        victim = min(chunk.parts)
        port = cluster.port_of(victim[0])
        cluster.kill9(port)
        await cluster.registered(PARTS[goal] - 1)
        assert victim not in chunk.parts
        assert master.meta.registry.grant_needs_bump(chunk) == \
            "holders_changed"
        # the survivors take the write at a version the dead one never saw
        await c.pwrite(inode, PIECE // 2, piece(5))
        model.write(PIECE // 2, piece(5))
        assert bumps(master) == {"holders_changed": 1}
        assert chunk.version == 2
        # it returns with its part at version 1: refused, never re-adopted
        cluster.spawn(port)
        await cluster.registered(PARTS[goal])
        assert victim not in chunk.parts
        assert len(chunk.parts) == PARTS[goal] - 1
        # and the next write, the set unchanged since, is vouched for
        await c.pwrite(inode, 0, piece(6))
        model.write(0, piece(6))
        assert bumps(master) == {"holders_changed": 1}
        c.cache.invalidate(inode)
        assert await c.read_file(inode) == bytes(model.data)
    finally:
        await cluster.stop()


@GOALS
@pytest.mark.asyncio
async def test_sigkill_during_write_retry_bumps_and_reads_back(
        tmp_path, goal):
    """Killed between a grant that raised nothing and the bytes: the
    attempt fails and ends with an error, the retry's grant raises the
    version on the survivors and drops the dead holder, and the file
    reads back byte-identically from them."""
    cluster = KillableCluster(tmp_path, n_cs=PARTS[goal])
    try:
        await cluster.start()
        c, inode, model = await start_file(cluster, goal)
        master = cluster.master
        chunk = the_chunk(master, inode)
        victim = max(chunk.parts)
        versions: list[int] = []
        grant_of = c._grant

        async def grant_then_kill(inode_, ci):
            grant = await grant_of(inode_, ci)
            versions.append(grant.version)
            if len(versions) == 1:
                cluster.kill9(cluster.port_of(victim[0]))
            return grant

        c._grant = grant_then_kill
        await c.pwrite(inode, PIECE // 2, piece(5))
        model.write(PIECE // 2, piece(5))
        c._grant = grant_of
        # first attempt on the version that stood, the retry one up
        assert versions[0] == 1 and versions[-1] == 2
        assert bumps(master) == {"error_end": 1}
        assert chunk.version == 2 and victim not in chunk.parts
        assert len(chunk.parts) == PARTS[goal] - 1
        await c.pwrite(inode, 2 * PIECE, piece(6))
        model.write(2 * PIECE, piece(6))
        assert bumps(master) == {"error_end": 1}
        c.cache.invalidate(inode)
        assert await c.read_file(inode) == bytes(model.data)
    finally:
        await cluster.stop()
