"""PUT one whole object as the S3 gateway does (``s3/server.py``
``_op_put_object``: ``_write_staged``, ``_set_etag``, ``_publish``), in
its order: ``create`` under a token name in the directory ``staging``,
``settrashtime(inode, 0)``, ``write_file`` of the whole body,
``set_xattr`` of the ETag, ``rename`` into the directory ``bucket`` as
the key ``s<session>_<seq>``. One timed write, the object's size, that
is acknowledged when the rename returns; only then does the model learn
the key. The token and the ETag are seeded, not random and not an md5.
A PUT that fails leaves both its names out of the comparison.
``warm_chunks`` cuts a warm-up object to that many chunks.

``write_file`` is handed ``bytes``, as the gateway hands it ``req.body``
and the CLI ``f.read()``. How the body came to be in hand is the front
door's work and not this cell's, so the harness makes none inside the
window: a run's objects are ``BODIES`` seeded windows of the pool, cut
once at the first call, in set-up (a fresh 128 MiB ``bytes`` an object
holds the GIL for 0.1 s, and would be the cell's first limit)."""

import numpy as np

CLASS = "write"
ETAG_XATTR = "lizardfs.s3.etag"
BODIES = 8


def directory(t, name: str):
    return next(d for d in t.dirs if d.name == name)


def bodies(t) -> list:
    """The run's (offset in the pool, body) pairs, a function of the
    seed; kept on the traffic's ``shared`` under a name of this verb."""
    made = t.shared.get("put_whole.bodies")
    if made is None:
        rng = np.random.default_rng([int(t.seed), 0x707574])
        size = max(t.plan.sizes)
        made = t.shared["put_whole.bodies"] = [
            (base, t.model.pool[base:base + size].tobytes())
            for base in (int(b) * 64 for b in rng.integers(
                0, t.plan.slack // 64, BODIES))]
    return made


async def do(t, s, st, arg, warm):
    c = t.clients[s]
    staging, bucket = directory(t, "staging"), directory(t, "bucket")
    size = t.next_size(s, st, warm)
    made = bodies(t)
    base, body = made[int(st["rng"].integers(0, len(made)))]
    if warm and "warm_chunks" in arg:
        size = min(size, int(arg["warm_chunks"]) * t.chunk_bytes)
    if size != len(body):
        body = body[:size]
    token = "put-" + st["rng"].bytes(12).hex()
    etag = st["rng"].bytes(16).hex()
    key = f"s{s}_{st['seq']}"
    st["seq"] += 1

    async def put():
        attr = await c.create(staging.inode, token)
        await c.settrashtime(attr.inode, 0)
        await c.write_file(attr.inode, body)
        await c.set_xattr(attr.inode, ETAG_XATTR, etag.encode())
        await c.rename(staging.inode, token, bucket.inode, key)
        return attr

    try:
        attr = await t.timed(CLASS, size, put())
    except Exception:
        t.uncertain.update((token, key))
        raise
    t.model.create(key, attr.inode, t.dirs.index(bucket))
    t.model.write(key, base, size)
