"""Cross-session client data-cache coherence.

The reference master invalidates mount data caches on mutation
(reference: src/master/matoclserv.cc client service) and mounts
revalidate cached chunk data against the version returned by
fs_readchunk (reference: src/mount/chunk_locator.h,
src/mount/mastercomm.h:67). These tests pin both layers plus the
last-resort TTL:

1. master push: B rewrites -> A's cached blocks drop well inside the TTL;
2. version revalidation: even with pushes suppressed, the next locate A
   performs drops blocks cached under the old (chunk_id, version);
3. BlockCache unit semantics for the version tagging.
"""

import asyncio

import pytest

from lizardfs_tpu.client.cache import BlockCache
from lizardfs_tpu.constants import MFSBLOCKSIZE
from lizardfs_tpu.proto import messages as m

from tests.test_cluster import Cluster

pytestmark = pytest.mark.asyncio


async def test_cross_session_write_invalidates_reader_cache(tmp_path):
    """Client A reads (cache fills), client B rewrites, client A re-reads
    within 1 s and must see the new bytes — the 3 s TTL alone would
    serve stale data here."""
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    try:
        a = await cluster.client()
        b = await cluster.client()
        f = await a.create(1, "shared.dat")
        old = b"A" * (2 * MFSBLOCKSIZE)
        await a.write_file(f.inode, old)

        # A reads -> fills its block cache (small read, below bulk bypass)
        got = await a.read_file(f.inode, 0, 4096)
        assert got == old[:4096]
        # the fast path really is armed: a repeat read hits the cache
        hits_before = a.cache.hits
        await a.read_file(f.inode, 0, 4096)
        assert a.cache.hits > hits_before

        # B rewrites through a different session
        await b.pwrite(f.inode, 0, b"FRESHBYTES")
        # one scheduler breath for the push task; far below the 3 s TTL
        await asyncio.sleep(0.2)
        got = await a.read_file(f.inode, 0, 10)
        assert got == b"FRESHBYTES"
        assert a.op_counters.get("cache_invalidate", 0) >= 1
    finally:
        await cluster.stop()


async def test_version_revalidation_catches_missed_push(tmp_path):
    """If the invalidation push is lost (handler suppressed here), the
    next locate A performs — for ANY range of the chunk — drops blocks
    cached under the old (chunk_id, version, content_gen) tag. B's
    grant follows A's clean write and raises no version: what differs
    is the file's content generation, and the locate is the one A
    sends before it serves a block cached before that miss
    (Client._revalidate_blocks)."""
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    try:
        a = await cluster.client()
        b = await cluster.client()
        f = await a.create(1, "unpushed.dat")
        old = bytes(range(256)) * ((4 * MFSBLOCKSIZE) // 256)
        await a.write_file(f.inode, old)

        # A caches block 0
        assert await a.read_file(f.inode, 0, 4096) == old[:4096]
        # simulate a lost push: drop A's handler registration
        a.master._push_handlers.pop(m.MatoclCacheInvalidate, None)

        await b.pwrite(f.inode, 0, b"NEWDATA!")
        await asyncio.sleep(0.2)

        # A reads a DIFFERENT block -> miss -> locate -> note_version
        # sees the raised content generation and drops the stale block 0
        await a.read_file(f.inode, 3 * MFSBLOCKSIZE, 4096)
        # re-read of block 0 within the TTL must now miss and refetch
        assert (await a.read_file(f.inode, 0, 8)) == b"NEWDATA!"
    finally:
        await cluster.stop()


async def test_revalidation_where_the_version_stood_still(tmp_path):
    """The same missed push, the overwrite being B's third clean pwrite
    in a row: the chunk's version provably never moved, and A's next
    locate still drops the block."""
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    try:
        a = await cluster.client()
        b = await cluster.client()
        f = await a.create(1, "still.dat")
        old = bytes(range(256)) * ((4 * MFSBLOCKSIZE) // 256)
        await a.write_file(f.inode, old)
        await b.pwrite(f.inode, MFSBLOCKSIZE, b"one")
        await b.pwrite(f.inode, 2 * MFSBLOCKSIZE, b"two")
        version = (await a.chunk_info(f.inode, 0)).version

        assert await a.read_file(f.inode, 0, 4096) == old[:4096]
        a.master._push_handlers.pop(m.MatoclCacheInvalidate, None)
        await b.pwrite(f.inode, 0, b"NEWDATA!")
        assert (await b.chunk_info(f.inode, 0)).version == version == 1
        assert "write_grant_bumps" not in cluster.master.metrics.labeled

        # still served from the cache: nothing told A yet
        assert await a.read_file(f.inode, 0, 8) == old[:8]
        await a.read_file(f.inode, 3 * MFSBLOCKSIZE, 4096)
        assert (await a.read_file(f.inode, 0, 8)) == b"NEWDATA!"
    finally:
        await cluster.stop()


async def test_revalidation_is_per_file_not_per_chunk(tmp_path, monkeypatch):
    """The tag's generation is the inode's: a write to ANOTHER chunk of
    the file drops this chunk's cached blocks at the next locate too,
    though their bytes are as they were. Pinned as intended: the price
    of a version that no longer rises with every write."""
    from lizardfs_tpu.client import client as client_mod

    # two chunks without 64 MiB of data: the client's chunk arithmetic
    # is all that has to agree on the size
    monkeypatch.setattr(client_mod, "MFSCHUNKSIZE", 4 * MFSBLOCKSIZE)
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    try:
        a = await cluster.client()
        b = await cluster.client()
        f = await a.create(1, "twochunks.dat")
        body = bytes(range(256)) * ((8 * MFSBLOCKSIZE) // 256)
        await a.pwrite(f.inode, 0, body)
        assert len(cluster.master.meta.fs.file_node(f.inode).chunks) == 2

        assert await a.read_file(f.inode, 0, 4096) == body[:4096]
        assert a.cache.get(f.inode, 0, 0) is not None
        a.master._push_handlers.pop(m.MatoclCacheInvalidate, None)
        await b.pwrite(f.inode, 5 * MFSBLOCKSIZE, b"elsewhere")  # chunk 1

        hits = a.cache.hits
        await a.read_file(f.inode, 2 * MFSBLOCKSIZE, 4096)  # chunk 0, a miss
        assert await a.read_file(f.inode, 0, 4096) == body[:4096]
        assert a.cache.hits == hits  # block 0 was dropped and read anew
    finally:
        await cluster.stop()


def test_blockcache_version_tagging():
    # call order mirrors the client: every locate note_version()s BEFORE
    # any put() of the blocks it fetched
    c = BlockCache(max_age=1000.0)
    c.note_version(7, 0, (11, 1))
    c.put(7, 0, 0, b"x" * 100, version=(11, 1))
    c.put(7, 0, 1, b"y" * 100, version=(11, 1))
    c.note_version(7, 1, (12, 1))
    c.put(7, 1, 0, b"z" * 100, version=(12, 1))  # other chunk untouched
    assert c.get(7, 0, 0) == b"x" * 100

    # same identity re-noted: nothing drops
    c.note_version(7, 0, (11, 1))
    assert c.get(7, 0, 1) == b"y" * 100

    # version bump drops only that chunk's blocks
    c.note_version(7, 0, (11, 2))
    assert c.get(7, 0, 0) is None and c.get(7, 0, 1) is None
    assert c.get(7, 1, 0) == b"z" * 100

    # chunk_id swap (truncate + regrow) also invalidates
    c.note_version(7, 1, (99, 1))
    assert c.get(7, 1, 0) is None


def test_blockcache_blocks_before_an_unlocated_fetch_are_suspect():
    """A read that goes to the chunkservers on a cached locate makes
    the blocks filled before it suspect, until a locate SENT no earlier
    than that read vouches for their tag; blocks filled by the read
    itself, other chunks, and a chunk with nothing cached are not."""
    clock = [100.0]
    c = BlockCache(max_age=1000.0)
    c._now = lambda: clock[0]
    c.note_version(7, 0, (11, 1, 5))
    c.put(7, 0, 0, b"x" * 100, version=(11, 1, 5))
    assert not c.is_suspect(7, 0, 0, 3)
    c.note_unlocated_fetch(7, 1)  # nothing cached there: nothing to doubt
    assert not c.is_suspect(7, 1, 0, 3)

    clock[0] = 101.0
    c.note_unlocated_fetch(7, 0)
    clock[0] = 101.5
    c.put(7, 0, 3, b"y" * 100, version=(11, 1, 5))  # what that read fetched
    assert c.is_suspect(7, 0, 0, 0) and c.is_suspect(7, 0, 0, 3)
    assert not c.is_suspect(7, 0, 3, 3) and not c.is_suspect(7, 0, 1, 2)

    # a cached reply vouches for nothing, nor does a locate sent before
    c.note_version(7, 0, (11, 1, 5))
    c.note_version(7, 0, (11, 1, 5), asked=100.5)
    assert c.is_suspect(7, 0, 0, 0)
    # one sent after it does: same tag, the block stays and is trusted
    c.note_version(7, 0, (11, 1, 5), asked=102.0)
    assert not c.is_suspect(7, 0, 0, 0) and c.get(7, 0, 0) == b"x" * 100

    # and where the tag moved, the doubt was right: the blocks drop
    clock[0] = 103.0
    c.note_unlocated_fetch(7, 0)
    c.note_version(7, 0, (11, 1, 6), asked=103.5)
    assert c.get(7, 0, 0) is None and not c.is_suspect(7, 0, 0, 3)


def test_blockcache_put_refuses_revoked_version():
    """An in-flight read finishing after an invalidation must not
    re-insert blocks under the revoked version tag — that would
    resurrect exactly the staleness the push removed."""
    c = BlockCache(max_age=1000.0)
    c.note_version(7, 2, (50, 1))
    # invalidation push lands while a read (tagged (50,1)) is in flight
    c.invalidate(7, 2)
    c.put(7, 2, 0, b"stale" * 20, version=(50, 1))  # late arrival
    assert c.get(7, 2, 0) is None
    # a put under a tag superseded by a newer locate is refused too
    c.note_version(7, 2, (50, 2))
    c.put(7, 2, 0, b"old" * 30, version=(50, 1))
    assert c.get(7, 2, 0) is None
    # the current tag caches normally
    c.put(7, 2, 0, b"new" * 30, version=(50, 2))
    assert c.get(7, 2, 0) == b"new" * 30


def test_blockcache_version_notes_bounded():
    c = BlockCache(max_age=1000.0)
    c.max_version_notes = 16
    for ino in range(100):
        c.note_version(ino, 0, (ino, 1))
    assert len(c._versions) == 16
    # an evicted note only costs a skipped fill, never a wrong read
    c.put(0, 0, 0, b"q" * 10, version=(0, 1))
    assert c.get(0, 0, 0) is None


async def test_locate_cache_hits_and_write_invalidation(tmp_path):
    """Repeat sized reads of an unchanged chunk serve their location
    from the client's locate cache (chunk_locator.h analog — one
    master RPC for the first read, zero after); any write to the inode
    drops the cached location so the next read re-locates."""
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    try:
        c = await cluster.client()
        c.locate_cache_ttl = 60.0  # pin behavior, not wall-clock speed
        from lizardfs_tpu.utils import data_generator

        f = await c.create(1, "loc.bin")
        payload = data_generator.generate(4, 8 << 20).tobytes()
        await c.write_file(f.inode, payload)
        # bulk-sized reads bypass the block cache, so every one needs a
        # location — only the FIRST may pay a master RPC
        got = await c.read_file(f.inode, 0, 4 << 20)
        assert bytes(got) == payload[: 4 << 20]
        before = dict(c.op_counters)
        for i in range(3):
            off = i * (1 << 20)
            got = await c.read_file(f.inode, off, 4 << 20)
            assert bytes(got) == payload[off: off + (4 << 20)]
        delta_locates = (
            c.op_counters.get("CltomaReadChunk", 0)
            - before.get("CltomaReadChunk", 0)
        )
        hits = (
            c.op_counters.get("locate_cache_hit", 0)
            - before.get("locate_cache_hit", 0)
        )
        assert delta_locates == 0, f"{delta_locates} extra locates"
        assert hits == 3
        # a write drops the cached location (version moved)
        await c.pwrite(f.inode, 0, b"Z" * 8192)
        before = dict(c.op_counters)
        got = await c.read_file(f.inode, 0, 4096)
        assert bytes(got) == b"Z" * 4096
        assert (
            c.op_counters.get("CltomaReadChunk", 0)
            - before.get("CltomaReadChunk", 0)
        ) == 1, "write did not invalidate the locate cache"
    finally:
        await cluster.stop()


async def test_locate_cached_mid_write_dropped_at_write_end(tmp_path):
    """A locate performed while a write to the same inode is in flight
    (between its grant and its WriteChunkEnd) reflects pre-write
    length/identity; it must not be served from the locate cache after
    the write returns (r05 review finding: the master's end-of-write
    push excludes the mutator's own session, so the client drops its
    own locates at write end)."""
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    try:
        c = await cluster.client()
        c.locate_cache_ttl = 60.0
        # EXTENSION is the sharp case: file length only grows at
        # WriteChunkEnd, so a mid-write locate caches file_length=0
        # and a post-write sized read would clamp to it, returning b""
        f = await c.create(1, "race.bin")
        mid_read: list[bytes] = []
        orig = c._push_chunk_parts

        async def hooked(grant, chunk_data):
            await orig(grant, chunk_data)
            # data pushed, WriteChunkEnd NOT yet sent: a concurrent
            # reader locates now and caches a pre-end location
            mid_read.append(bytes(await c.read_file(f.inode, 0, 8)))

        c._push_chunk_parts = hooked
        try:
            await c.write_file(f.inode, b"B" * 65536)
        finally:
            c._push_chunk_parts = orig
        assert mid_read == [b""], mid_read  # pre-end view: length 0
        got = await c.read_file(f.inode, 0, 8)
        assert bytes(got) == b"B" * 8, \
            "read clamped to a locate cached mid-write (stale length 0)"
    finally:
        await cluster.stop()
