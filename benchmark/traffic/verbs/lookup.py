"""stat(2) of the current file by its path, as a mount does it for a
name it has not seen: one ``lookup(parent, name)``, which returns the
attributes. The length it reports is kept for the comparison."""

CLASS = "stat"
METADATA = True


async def do(t, s, st, arg, warm):
    f = st["cur"]
    if f is None:
        return
    attr = await t.timed(CLASS, 0, t.clients[s].lookup(
        t.dirs[f.dir].inode, f.name), True)
    if t.recording:
        t.getattr_seen.append((f.name, int(attr.length), f.length))
