"""NFSv3 gateway end-to-end: a real cluster behind the gateway, exercised
by an ONC-RPC client speaking wire-format NFS3/MOUNT3 (the analog of the
reference's Ganesha FSAL tests, src/nfs-ganesha/).

The RpcClient builds real RFC 1813 XDR frames, so both directions of the
gateway's codec are exercised against the spec, not against itself.
"""

import struct

import pytest

from lizardfs_tpu.nfs import server as nfs
from lizardfs_tpu.nfs.client import Nfs3Client
from lizardfs_tpu.nfs.xdr import Packer

from tests.test_cluster import Cluster

pytestmark = pytest.mark.asyncio


async def gateway_cluster(tmp_path):
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    gw = nfs.NfsGateway("127.0.0.1", cluster.master.port)
    await gw.start()
    return cluster, gw


async def test_nfs_mount_and_metadata(tmp_path):
    cluster, gw = await gateway_cluster(tmp_path)
    try:
        async with Nfs3Client("127.0.0.1", gw.port) as c:
            root = await c.mnt("/")
            assert nfs.fh_unpack(root) == 1
            # FSINFO sanity
            u = await c.call(19, Packer().opaque(root).bytes())
            assert u.u32() == nfs.NFS3_OK
            c.skip_post_op(u)
            assert u.u32() >= 1 << 16  # rtmax
            d = await c.mkdir(root, "docs")
            code, fh = await c.create(d, "a.txt")
            assert code == nfs.NFS3_OK
            # lookup + dots
            code, fh2, attr = await c.lookup(d, "a.txt")
            assert code == nfs.NFS3_OK and fh2 == fh
            assert attr["ftype"] == 1 and attr["mode"] == 0o644
            code, dot, _ = await c.lookup(d, "..")
            assert code == nfs.NFS3_OK and nfs.fh_unpack(dot) == 1
            # readdir both flavors
            assert await c.readdir(d) == [".", "..", "a.txt"]
            assert await c.readdir(root, plus=True) == [".", "..", "docs"]
            # rename + remove
            args = (Packer().opaque(d).string("a.txt")
                    .opaque(root).string("b.txt").bytes())
            u = await c.call(14, args)
            assert u.u32() == nfs.NFS3_OK
            code, _, _ = await c.lookup(root, "b.txt")
            assert code == nfs.NFS3_OK
            u = await c.call(12, Packer().opaque(root).string("b.txt").bytes())
            assert u.u32() == nfs.NFS3_OK
            code, _, _ = await c.lookup(root, "b.txt")
            assert code == nfs.NFS3ERR_NOENT
            # rmdir
            u = await c.call(13, Packer().opaque(root).string("docs").bytes())
            assert u.u32() == nfs.NFS3_OK
    finally:
        await gw.stop()
        await cluster.stop()


async def test_nfs_read_write_roundtrip(tmp_path):
    cluster, gw = await gateway_cluster(tmp_path)
    try:
        async with Nfs3Client("127.0.0.1", gw.port) as c:
            root = await c.mnt("/")
            code, fh = await c.create(root, "data.bin")
            assert code == nfs.NFS3_OK
            blob = b"".join(
                struct.pack(">I", (i * 2654435761) & 0xFFFFFFFF)
                for i in range(50_000)
            )[:150_000]
            # chunked writes like a kernel client (64k wsize)
            for off in range(0, len(blob), 65536):
                part = blob[off : off + 65536]
                assert await c.write(fh, off, part) == len(part)
            attr = await c.getattr(fh)
            assert attr["size"] == len(blob)
            # reads: offset, middle, tail+eof
            got, eof = await c.read(fh, 0, 70_000)
            assert got == blob[:70_000] and not eof
            got, eof = await c.read(fh, 70_000, 70_000)
            assert got == blob[70_000:140_000]
            got, eof = await c.read(fh, 140_000, 70_000)
            assert got == blob[140_000:] and eof
            # sparse overwrite
            await c.write(fh, 100, b"HELLO")
            got, _ = await c.read(fh, 98, 9)
            assert got == blob[98:100] + b"HELLO" + blob[105:107]
            # FSSTAT reflects real cluster space
            u = await c.call(18, Packer().opaque(root).bytes())
            assert u.u32() == nfs.NFS3_OK
            c.skip_post_op(u)
            total, free = u.u64(), u.u64()
            assert total > 0 and 0 < free <= total
    finally:
        await gw.stop()
        await cluster.stop()


async def test_nfs_identity_enforcement(tmp_path):
    cluster, gw = await gateway_cluster(tmp_path)
    try:
        admin = await cluster.client()
        await admin.setattr(1, 1, mode=0o1777)  # root dir: world-writable
        async with Nfs3Client("127.0.0.1", gw.port, uid=1000, gid=1000) as alice:
            root = await alice.mnt("/")
            code, fh = await alice.create(root, "private.txt")
            assert code == nfs.NFS3_OK
            assert await alice.write(fh, 0, b"secret") == 6
            attr = await alice.getattr(fh)
            assert attr["uid"] == 1000
            # chmod 0600 via SETATTR
            args = (Packer().opaque(fh)
                    .boolean(True).u32(0o600)
                    .boolean(False).boolean(False).boolean(False)
                    .u32(0).u32(0)
                    .boolean(False).bytes())
            u = await alice.call(2, args)
            assert u.u32() == nfs.NFS3_OK
        async with Nfs3Client("127.0.0.1", gw.port, uid=2000, gid=2000) as bob:
            root = await bob.mnt("/")
            code, fh, _ = await bob.lookup(root, "private.txt")
            assert code == nfs.NFS3_OK
            # ACCESS denies read+modify for bob
            u = await bob.call(4, Packer().opaque(fh).u32(
                nfs.ACCESS3_READ | nfs.ACCESS3_MODIFY).bytes())
            assert u.u32() == nfs.NFS3_OK
            bob.skip_post_op(u)
            assert u.u32() == 0
            # direct write is refused
            await bob.write(fh, 0, b"x", expect=nfs.NFS3ERR_ACCES)
    finally:
        await gw.stop()
        await cluster.stop()


async def test_nfs_readdir_paging_and_export_jail(tmp_path):
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    admin = await cluster.client()
    sub = await admin.mkdir(1, "sub")
    for i in range(20):
        await admin.create(sub.inode, f"f{i:02d}")
    gw = nfs.NfsGateway(
        "127.0.0.1", cluster.master.port, exports={"/sub": "/sub"}
    )
    await gw.start()
    try:
        async with Nfs3Client("127.0.0.1", gw.port) as c:
            root = await c.mnt("/sub")
            assert nfs.fh_unpack(root) == sub.inode
            # paged listing across several small windows
            names = await c.readdir(root, maxcount=256)
            assert names == [".", ".."] + [f"f{i:02d}" for i in range(20)]
            # ".." at the export root clamps to the export root
            code, fh, _ = await c.lookup(root, "..")
            assert code == nfs.NFS3_OK and nfs.fh_unpack(fh) == sub.inode
            # readdir reports ".." as the export root too
            u = await c.call(16, Packer().opaque(root).u64(0)
                             .fixed(b"\x00" * 8).u32(4096).bytes())
            assert u.u32() == nfs.NFS3_OK
            c.skip_post_op(u)
            u.fixed(8)
            assert u.boolean() and u.u64() == sub.inode  # "." fileid
            assert u.string(255) == "."
            u.u64()
            assert u.boolean() and u.u64() == sub.inode  # ".." fileid
            # stale cookie after a directory change -> BAD_COOKIE
            p = Packer().opaque(root).u64(0).fixed(b"\x00" * 8).u32(256)
            u = await c.call(16, p.bytes())
            assert u.u32() == nfs.NFS3_OK
            c.skip_post_op(u)
            verf = u.fixed(8)
            cookie = 0
            while u.boolean():
                u.u64()
                u.string(255)
                cookie = u.u64()
            await admin.unlink(sub.inode, "f00")
            p = Packer().opaque(root).u64(cookie).fixed(verf).u32(256)
            u = await c.call(16, p.bytes())
            assert u.u32() == nfs.NFS3ERR_BAD_COOKIE
    finally:
        await gw.stop()
        await admin.close()
        await cluster.stop()


async def test_nfs_symlink_link_and_errors(tmp_path):
    cluster, gw = await gateway_cluster(tmp_path)
    try:
        async with Nfs3Client("127.0.0.1", gw.port) as c:
            root = await c.mnt("/")
            code, fh = await c.create(root, "target")
            # SYMLINK
            args = (Packer().opaque(root).string("ln")
                    .boolean(False).boolean(False).boolean(False)
                    .boolean(False).u32(0).u32(0)
                    .string("/target").bytes())
            u = await c.call(10, args)
            assert u.u32() == nfs.NFS3_OK
            assert u.boolean()
            lfh = u.opaque(64)
            # READLINK
            u = await c.call(5, Packer().opaque(lfh).bytes())
            assert u.u32() == nfs.NFS3_OK
            c.skip_post_op(u)
            assert u.string(4096) == "/target"
            # LINK
            u = await c.call(15, Packer().opaque(fh).opaque(root)
                             .string("hard").bytes())
            assert u.u32() == nfs.NFS3_OK
            attr = await c.getattr(fh)
            assert attr["nlink"] == 2
            # errors: bad handle, stale inode, unsupported mknod
            u = await c.call(1, Packer().opaque(b"XXXXXXXX").bytes())
            assert u.u32() == nfs.NFS3ERR_BADHANDLE
            u = await c.call(1, Packer().opaque(nfs.fh_pack(999999)).bytes())
            assert u.u32() == nfs.NFS3ERR_NOENT
            u = await c.call(11, Packer().opaque(root).string("dev").u32(3)
                             .bytes())
            assert u.u32() == nfs.NFS3ERR_NOTSUPP
            # guarded create of existing file fails, unchecked succeeds
            code, _ = await c.create(root, "target", how=1)
            assert code == nfs.NFS3ERR_EXIST
            code, fh2 = await c.create(root, "target", how=0)
            assert code == nfs.NFS3_OK and fh2 == fh
            # exclusive create: a retransmit with the same verifier
            # succeeds idempotently; a different verifier gets EEXIST
            v1 = b"\x01\x02\x03\x04\x05\x06\x07\x08"
            code, xfh = await c.create(root, "excl", how=2, verf=v1)
            assert code == nfs.NFS3_OK
            code, xfh2 = await c.create(root, "excl", how=2, verf=v1)
            assert code == nfs.NFS3_OK and xfh2 == xfh
            code, _ = await c.create(root, "excl", how=2, verf=b"\xff" * 8)
            assert code == nfs.NFS3ERR_EXIST
    finally:
        await gw.stop()
        await cluster.stop()


async def test_nfs_multi_gateway_coherence(tmp_path):
    """The documented NFS scale-out model: N stateless gateways over one
    cluster. A write through gateway A must be visible through gateway B
    well inside the client-cache TTL (the master pushes invalidations to
    every gateway session — doc/migration.md "NFS scale-out")."""
    import asyncio

    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    gw_a = nfs.NfsGateway("127.0.0.1", cluster.master.port)
    gw_b = nfs.NfsGateway("127.0.0.1", cluster.master.port)
    await gw_a.start()
    await gw_b.start()
    try:
        async with Nfs3Client("127.0.0.1", gw_a.port) as a, \
                Nfs3Client("127.0.0.1", gw_b.port) as b:
            root_a = await a.mnt("/")
            root_b = await b.mnt("/")
            code, fh_a = await a.create(root_a, "shared.txt")
            assert code == nfs.NFS3_OK
            await a.write(fh_a, 0, b"from-gateway-A!!" * 4096)  # 64 KiB
            # B sees the file and its content
            code, fh_b, _ = await b.lookup(root_b, "shared.txt")
            assert code == nfs.NFS3_OK
            got, _ = await b.read(fh_b, 0, 16)
            assert got == b"from-gateway-A!!"
            # B rewrites; A re-reads within 1 s and must see fresh bytes
            # (before master-push invalidation, A could serve stale
            # cached blocks for the full 3 s TTL)
            await b.write(fh_b, 0, b"B-OVERWROTE-THIS")
            await asyncio.sleep(0.3)
            got, _ = await a.read(fh_a, 0, 16)
            assert got == b"B-OVERWROTE-THIS"
    finally:
        await gw_a.stop()
        await gw_b.stop()
        await cluster.stop()


async def test_nfs_unstable_write_gathering(tmp_path):
    """UNSTABLE writes gather server-side and become durable at COMMIT
    (RFC 1813 §3.3.7/21) — with read-your-own-writes, size visibility,
    and truncate ordering all forcing the flush."""
    import asyncio

    cluster, gw = await gateway_cluster(tmp_path)
    try:
        async with Nfs3Client("127.0.0.1", gw.port) as c:
            root = await c.mnt("/")
            code, fh = await c.create(root, "gathered.bin")
            assert code == nfs.NFS3_OK
            blob = bytes(range(256)) * 2048  # 512 KiB
            # sequential UNSTABLE stream (kernel-client pattern)
            for off in range(0, len(blob), 65536):
                n = await c.write(fh, off, blob[off:off + 65536], stable=0)
                assert n == 65536
            # the gather holds ONE coalesced run pre-commit
            inode = nfs.fh_unpack(fh)
            assert gw._gather[inode].nbytes == len(blob)
            assert len(gw._gather[inode].segs) == 1
            verf = await c.commit(fh)
            assert verf == gw.write_verf and inode not in gw._gather
            got, _ = await c.read(fh, 0, 1 << 20)
            assert got == blob

            # read-your-own-writes flushes without an explicit COMMIT
            await c.write(fh, 0, b"FRESH", stable=0)
            got, _ = await c.read(fh, 0, 5)
            assert got == b"FRESH" and inode not in gw._gather

            # getattr shows the gathered size (flush-on-getattr)
            await c.write(fh, len(blob), b"tail!", stable=0)
            attr = await c.getattr(fh)
            assert attr["size"] == len(blob) + 5

            # out-of-order + bridging segments coalesce correctly
            code, fh2 = await c.create(root, "bridge.bin")
            await c.write(fh2, 131072, b"C" * 65536, stable=0)
            await c.write(fh2, 0, b"A" * 65536, stable=0)
            await c.write(fh2, 65536, b"B" * 65536, stable=0)  # bridges
            inode2 = nfs.fh_unpack(fh2)
            assert len(gw._gather[inode2].segs) == 1
            await c.commit(fh2)
            got, _ = await c.read(fh2, 0, 196608)
            assert got == b"A" * 65536 + b"B" * 65536 + b"C" * 65536

            # idle sweep flushes without any dependent op
            await c.write(fh2, 196608, b"idle-flush", stable=0)
            for _ in range(40):
                if inode2 not in gw._gather:
                    break
                await asyncio.sleep(0.1)
            assert inode2 not in gw._gather, "idle sweep never flushed"
    finally:
        await gw.stop()
        await cluster.stop()


async def test_nfs_gather_overlap_keeps_newest_bytes(tmp_path):
    """An UNSTABLE write overlapping buffered segments must not let
    stale buffered bytes win: w3 spans w1's range after an adjacent
    merge — flush order must leave w3's bytes on disk."""
    cluster, gw = await gateway_cluster(tmp_path)
    try:
        async with Nfs3Client("127.0.0.1", gw.port) as c:
            root = await c.mnt("/")
            code, fh = await c.create(root, "overlap.bin")
            assert code == nfs.NFS3_OK
            await c.write(fh, 131072, b"1" * 65536, stable=0)   # w1
            await c.write(fh, 0, b"2" * 65536, stable=0)        # w2
            await c.write(fh, 65536, b"3" * 131072, stable=0)   # w3 over w1
            await c.commit(fh)
            got, _ = await c.read(fh, 0, 196608)
            assert got == b"2" * 65536 + b"3" * 131072
    finally:
        await gw.stop()
        await cluster.stop()


async def test_nfs_gather_requeues_on_flush_failure(tmp_path):
    """Acked UNSTABLE bytes must survive a failed flush (same verifier
    => the client is allowed to discard its copy): the gather requeues
    and a later COMMIT lands the data."""
    from lizardfs_tpu.proto import status as st_mod

    cluster, gw = await gateway_cluster(tmp_path)
    try:
        async with Nfs3Client("127.0.0.1", gw.port) as c:
            root = await c.mnt("/")
            code, fh = await c.create(root, "requeue.bin")
            assert code == nfs.NFS3_OK
            await c.write(fh, 0, b"precious!" * 7000, stable=0)

            real_pwrite = gw.client.pwrite
            fails = {"n": 1}

            async def flaky(*a, **k):
                if fails["n"]:
                    fails["n"] -= 1
                    raise st_mod.StatusError(st_mod.EIO, "injected")
                return await real_pwrite(*a, **k)

            gw.client.pwrite = flaky
            try:
                u = await c.call(
                    21, __import__("lizardfs_tpu.nfs.xdr", fromlist=["Packer"])
                    .Packer().opaque(fh).u64(0).u32(0).bytes()
                )
                assert u.u32() != nfs.NFS3_OK  # commit reports the failure
                inode = nfs.fh_unpack(fh)
                assert inode in gw._gather, "data dropped on failed flush"
                verf = await c.commit(fh)  # retry succeeds
                assert verf == gw.write_verf
            finally:
                gw.client.pwrite = real_pwrite
            got, _ = await c.read(fh, 0, 63000)
            assert got == b"precious!" * 7000
    finally:
        await gw.stop()
        await cluster.stop()


async def test_nfs_readahead_span_and_coherence(tmp_path):
    """Sequential READs warm the gateway's server-side readahead span
    (one back-end fetch serves the following wire READs); any write
    must drop the span via the BlockCache invalidate-listener so no
    READ ever serves pre-overwrite bytes from it."""
    import asyncio

    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    gw = nfs.NfsGateway("127.0.0.1", cluster.master.port)
    gw_b = nfs.NfsGateway("127.0.0.1", cluster.master.port)
    await gw.start()
    await gw_b.start()
    try:
        async with Nfs3Client("127.0.0.1", gw.port) as c, \
                Nfs3Client("127.0.0.1", gw_b.port) as cb:
            root = await c.mnt("/")
            _, fh = await c.create(root, "ra.bin")
            blob = bytes(range(256)) * 2048  # 512 KiB
            await c.write(fh, 0, blob)
            # sequential stream: span appears and serves hits
            got = bytearray()
            for off in range(0, len(blob), 65536):
                piece, _ = await c.read(fh, off, 65536)
                got += piece
            assert bytes(got) == blob
            assert gw._ra, "sequential stream did not warm a span"
            inode = next(iter(gw._ra))
            # local write through the SAME gateway drops the span
            await c.write(fh, 0, b"\xff" * 16)
            assert inode not in gw._ra, "local write left a stale span"
            piece, _ = await c.read(fh, 0, 16)
            assert piece == b"\xff" * 16
            # re-warm, then a write through ANOTHER gateway must
            # invalidate via the master push within the TTL
            for off in range(0, len(blob), 65536):
                await c.read(fh, off, 65536)
            assert gw._ra
            _, fh_b, _ = await cb.lookup(await cb.mnt("/"), "ra.bin")
            await cb.write(fh_b, 0, b"\xee" * 16)
            await asyncio.sleep(0.3)
            piece, _ = await c.read(fh, 0, 16)
            assert piece == b"\xee" * 16, "served stale readahead bytes"
    finally:
        await gw.stop()
        await gw_b.stop()
        await cluster.stop()


async def test_nfs_pipelined_reads_one_connection(tmp_path):
    """8 concurrent READs on ONE RPC connection (xid demux) return the
    right bytes — the kernel-client rsize pipeline pattern."""
    import asyncio

    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    gw = nfs.NfsGateway("127.0.0.1", cluster.master.port)
    await gw.start()
    try:
        async with Nfs3Client("127.0.0.1", gw.port) as c:
            root = await c.mnt("/")
            _, fh = await c.create(root, "pipe.bin")
            blob = bytes([i % 251 for i in range(1 << 20)])
            await c.write(fh, 0, blob)
            got = bytearray(len(blob))
            sem = asyncio.Semaphore(8)

            async def rslice(off):
                async with sem:
                    piece, _ = await c.read(fh, off, 65536)
                    got[off: off + len(piece)] = piece

            await asyncio.gather(*(
                rslice(off) for off in range(0, len(blob), 65536)
            ))
            assert bytes(got) == blob
    finally:
        await gw.stop()
        await cluster.stop()


async def test_nfs_chmod_drops_cached_access_immediately(tmp_path):
    """The gateway caches access decisions (META_TTL_S); a SETATTR
    through the SAME gateway must drop them synchronously — a chmod-000
    followed by a READ inside the TTL has to refuse, not serve from a
    pre-chmod cache entry."""
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    gw = nfs.NfsGateway("127.0.0.1", cluster.master.port)
    await gw.start()
    try:
        async with Nfs3Client("127.0.0.1", gw.port) as r, \
                Nfs3Client("127.0.0.1", gw.port, uid=1000, gid=1000) as c:
            pub = await r.mkdir(await r.mnt("/"), "pub", mode=0o777)
            root = await c.mnt("/")
            code, fh, _ = await c.lookup(root, "pub")
            assert code == nfs.NFS3_OK
            code, fh = await c.create(fh, "locked.bin", mode=0o644)
            assert code == nfs.NFS3_OK, code
            await c.write(fh, 0, b"secret-bytes!")
            piece, _ = await c.read(fh, 0, 13)  # warms the access cache
            assert piece == b"secret-bytes!"
            assert await c.setattr(fh, mode=0) == nfs.NFS3_OK
            # immediately inside the TTL: must be refused now
            from lizardfs_tpu.nfs.xdr import Packer

            u = await c.call(
                6, Packer().opaque(fh).u64(0).u32(13).bytes()
            )
            assert u.u32() == nfs.NFS3ERR_ACCES, \
                "READ served from a stale access-cache entry after chmod"
            # and chmod back restores service (owner can always chmod)
            assert await c.setattr(fh, mode=0o644) == nfs.NFS3_OK
            piece, _ = await c.read(fh, 0, 13)
            assert piece == b"secret-bytes!"
    finally:
        await gw.stop()
        await cluster.stop()


async def test_nfs_cross_gateway_chmod_revokes_cached_access(tmp_path):
    """ADVICE r05 #4 residual: a chmod through gateway A must revoke
    gateway B's cached access decisions via a master invalidation push
    — NOT after META_TTL_S. With the TTL cranked far above the test's
    lifetime, only the push can make B refuse."""
    import asyncio as aio

    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    gw_a = nfs.NfsGateway("127.0.0.1", cluster.master.port)
    gw_b = nfs.NfsGateway("127.0.0.1", cluster.master.port)
    await gw_a.start()
    await gw_b.start()
    # the TTL alone may NOT rescue revocation in this test
    gw_a.META_TTL_S = 300.0
    gw_b.META_TTL_S = 300.0
    try:
        async with Nfs3Client("127.0.0.1", gw_a.port) as r, \
                Nfs3Client("127.0.0.1", gw_a.port, uid=1000, gid=1000) as a, \
                Nfs3Client("127.0.0.1", gw_b.port, uid=1000, gid=1000) as b:
            pub = await r.mkdir(await r.mnt("/"), "pub", mode=0o777)
            root_a = await a.mnt("/")
            code, dir_a, _ = await a.lookup(root_a, "pub")
            assert code == nfs.NFS3_OK
            code, fh = await a.create(dir_a, "locked.bin", mode=0o644)
            assert code == nfs.NFS3_OK, code
            await a.write(fh, 0, b"secret-bytes!")
            # warm gateway B's attr + access caches for the inode
            root_b = await b.mnt("/")
            code, dir_b, _ = await b.lookup(root_b, "pub")
            assert code == nfs.NFS3_OK
            code, fh_b, _ = await b.lookup(dir_b, "locked.bin")
            assert code == nfs.NFS3_OK
            piece, _ = await b.read(fh_b, 0, 13)
            assert piece == b"secret-bytes!"
            # revoke through gateway A
            assert await a.setattr(fh, mode=0) == nfs.NFS3_OK
            # the push rides master -> B's client session -> the
            # gateway's invalidate listener; poll briefly (it is one
            # in-process hop, nowhere near the 300 s TTL)
            from lizardfs_tpu.nfs.xdr import Packer

            deadline = aio.get_event_loop().time() + 5.0
            refused = False
            while aio.get_event_loop().time() < deadline:
                u = await b.call(
                    6, Packer().opaque(fh_b).u64(0).u32(13).bytes()
                )
                if u.u32() == nfs.NFS3ERR_ACCES:
                    refused = True
                    break
                await aio.sleep(0.05)
            assert refused, (
                "cross-gateway chmod never revoked B's cached access "
                "inside the TTL (invalidation push missing)"
            )
    finally:
        await gw_a.stop()
        await gw_b.stop()
        await cluster.stop()


async def test_nfs_trace_propagation_to_chunkserver(tmp_path):
    """NFS joins the trace domain (PR 3): a wire READ starts a trace at
    the gateway's dispatch boundary and the id propagates through the
    shared Client into the master RPCs and the chunkserver data plane —
    end to end into the CS span ring (satellite coverage)."""
    from lizardfs_tpu.runtime import tracing

    cluster = Cluster(tmp_path, n_cs=3, native_data_plane=False)
    await cluster.start()
    gw = nfs.NfsGateway("127.0.0.1", cluster.master.port)
    await gw.start()
    try:
        async with Nfs3Client("127.0.0.1", gw.port) as c:
            root = await c.mnt("/")
            code, fh = await c.create(root, "traced.bin")
            assert code == nfs.NFS3_OK
            payload = b"t" * 200_000
            assert await c.write(fh, 0, payload, stable=2) == len(payload)
            # drop caches so the READ reaches the chunkservers
            inode = nfs.fh_unpack(fh)
            gw.client.cache.invalidate(inode)
            gw._ra_drop(inode)
            data, _eof = await c.read(fh, 0, 65536)
            assert data == payload[:65536]
        # the gateway recorded the op boundary span under role "nfs"
        reads = [
            s for s in gw.client.trace_ring.dump()
            if s["name"] == "nfs_read" and s["role"] == "nfs"
        ]
        assert reads, "gateway recorded no nfs_read boundary span"
        tid = reads[-1]["trace_id"]
        assert tid != 0
        # the same id reached the master's RPC ring...
        master_spans = cluster.master.trace_spans(tid)
        assert any(
            s["name"] == "CltomaReadChunk" for s in master_spans
        ), master_spans
        # ...and a chunkserver's span ring (the data plane)
        cs_spans = [
            s for cs in cluster.chunkservers for s in cs.trace_spans(tid)
        ]
        assert cs_spans, "trace id never reached a chunkserver ring"
        assert all(s["role"] == "chunkserver" for s in cs_spans)
        # merged, the timeline attributes the op across all three roles
        merged = tracing.merge_timeline(
            gw.client.trace_ring.dump(tid) + master_spans + cs_spans,
            tid, wall_name="nfs_read",
        )
        assert merged["wall_ms"] > 0
        assert {"chunkserver", "master"} <= set(merged["by_role_ms"])
        # the nfs SLO class accounted the dispatched procs
        assert gw.slo.objectives["nfs"].ops > 0
    finally:
        await gw.stop()
        await cluster.stop()


async def test_nfs_native_c_client_roundtrip(tmp_path):
    """The non-Python measuring client: the C NFS3 client
    (native/client_native.cpp liz_nfs_* over ONC-RPC/AUTH_SYS) drives
    MNT/CREATE/WRITE/COMMIT/LOOKUP/READ against the gateway and the
    bytes roundtrip — a real wire client, not this package's own
    asyncio codec."""
    import asyncio

    from lizardfs_tpu.nfs import cnfs

    if not cnfs.available():
        pytest.skip("liblizardfs_client.so not built with liz_nfs_*")
    cluster, gw = await gateway_cluster(tmp_path)
    try:
        blob = bytes(range(256)) * 1024  # 256 KiB

        def drive() -> bytes:
            with cnfs.CNfs3Client("127.0.0.1", gw.port) as c:
                root = c.mnt("/")
                fh = c.create(root, "cclient.bin")
                for off in range(0, len(blob), 65536):
                    piece = blob[off:off + 65536]
                    assert c.write(fh, off, piece, stable=0) == len(piece)
                c.commit(fh)
                assert c.lookup(root, "cclient.bin") == fh
                out = b""
                while len(out) < len(blob):
                    out += c.read(fh, len(out), 65536)
                return out

        got = await asyncio.to_thread(drive)
        assert got == blob
        # and the file is the same one the Python stack sees
        async with Nfs3Client("127.0.0.1", gw.port) as pc:
            root = await pc.mnt("/")
            code, fh, _attr = await pc.lookup(root, "cclient.bin")
            assert code == nfs.NFS3_OK
            data, _eof = await pc.read(fh, 0, 1024)
            assert data == blob[:1024]
    finally:
        await gw.stop()
        await cluster.stop()
