"""Remove the current file's name from its directory."""

CLASS = "unlink"
METADATA = True


async def do(t, s, st, arg, warm):
    f = st["cur"]
    if f is None:
        return
    await t.timed(CLASS, 0, t.clients[s].unlink(
        t.dirs[f.dir].inode, f.name), True)
    t.model.unlink(f.name)
    t.unlinked[f.name] = f
    st["cur"] = None
