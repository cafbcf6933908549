"""One operation of MinIO warp's mixed benchmark, as the S3 gateway's
``Client`` calls (``s3/server.py``): the session takes its next class
from a seeded shuffle of one block of the mix's ``distribution`` (9
GET, 6 STAT, 3 PUT and 2 DELETE of every 20: the source's 45 / 30 / 15
/ 10 % held exactly in every block, whatever the seed; a fresh shuffle
for every block) and makes it on the run's pool of live objects:

  GET     ``_op_get_object``: ``lookup`` of a uniformly drawn key in
          the bucket's directory, ``get_xattr`` of its ETag,
          ``read_file(inode, 0, length)``; a timed read of the object's
          size, a seeded share of the answers kept for the comparison
  STAT    ``_op_head_object``: the first two; a timed ``stat``
  PUT     ``_op_put_object``: ``put_whole``'s five calls and bodies; a
          timed write; the key joins the pool when its rename returns
  DELETE  ``_op_delete_object``: ``unlink`` of a key drawn among those
          that no operation under way holds, taken out of the pool
          first; a timed ``delete``

The bucket's own lookup is left out, as ``put_whole`` leaves it out. A
warm-up step makes one operation of each class, the PUT first."""

import manifest

ETAG_XATTR = "lizardfs.s3.etag"
CLASSES = ("get", "stat", "put", "delete")
PUT = manifest.load_module("traffic", "verbs", "put_whole.py")


class Pool:
    """The keys that are listed in the bucket, in the order they came,
    and how many operations under way hold each."""

    def __init__(self):
        self.live = []              # the model's File records
        self.held = {}              # name -> operations under way on it

    def add(self, f) -> None:
        self.live.append(f)

    def draw(self, rng):
        """A uniformly drawn object, held until ``release``."""
        f = self.live[int(rng.integers(0, len(self.live)))]
        self.held[f.name] = self.held.get(f.name, 0) + 1
        return f

    def release(self, f) -> None:
        self.held[f.name] -= 1
        if not self.held[f.name]:
            del self.held[f.name]

    def take(self, rng):
        """A uniformly drawn object that nothing holds, out of the
        pool; None where every object is held."""
        free = [i for i, f in enumerate(self.live) if f.name not in self.held]
        if not free:
            return None
        return self.live.pop(free[int(rng.integers(0, len(free)))])


def pool(t) -> Pool:
    return t.shared.setdefault("warp_mixed.pool", Pool())


def block_of(rng, distribution: dict) -> list:
    """One block's classes in a seeded order: each class as often as
    the distribution says."""
    block = [cls for cls in CLASSES for _ in range(int(distribution[cls]))]
    rng.shuffle(block)
    return block


def next_class(st, distribution: dict) -> str:
    left = st.setdefault("warp_mixed.block", [])
    if not left:
        left.extend(block_of(st["rng"], distribution))
    return left.pop()


async def head(t, c, f):
    """The two calls HEAD and GET share; the length ``lookup`` reports
    is kept for the comparison."""
    attr = await c.lookup(PUT.directory(t, "bucket").inode, f.name)
    await c.get_xattr(attr.inode, ETAG_XATTR)
    if t.recording:
        t.getattr_seen.append((f.name, int(attr.length), f.length))
    return attr


async def get(t, s, st, warm):
    c, f = t.clients[s], pool(t).draw(st["rng"])

    async def whole():
        attr = await head(t, c, f)
        return await c.read_file(attr.inode, 0, attr.length)

    try:
        data = await t.timed("read", f.length, whole())
    finally:
        pool(t).release(f)
    t.retain(st, f, 0, f.length, data)


async def stat(t, s, st, warm):
    f = pool(t).draw(st["rng"])
    try:
        await t.timed("stat", 0, head(t, t.clients[s], f), True)
    finally:
        pool(t).release(f)


async def put(t, s, st, warm):
    await PUT.do(t, s, st, {}, warm)
    pool(t).add(t.model.files[f"s{s}_{st['seq'] - 1}"])


async def delete(t, s, st, warm):
    f = pool(t).take(st["rng"])
    if f is None:
        return
    try:
        await t.timed("delete", 0, t.clients[s].unlink(
            PUT.directory(t, "bucket").inode, f.name), True)
    except Exception:
        t.uncertain.add(f.name)
        raise
    t.model.unlink(f.name)
    t.unlinked[f.name] = f


OPS = {"get": get, "stat": stat, "put": put, "delete": delete}


async def do(t, s, st, arg, warm):
    if warm:
        for cls in ("put", "get", "stat", "delete"):
            await OPS[cls](t, s, st, warm)
        return
    await OPS[next_class(st, arg["distribution"])](t, s, st, warm)
