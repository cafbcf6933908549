"""Create the session's next file in its directory (a mount's
open(O_CREAT)); it becomes the current file and joins the batch the
session is making."""

CLASS = "create"
METADATA = True


async def do(t, s, st, arg, warm):
    d = t.dir_of(s)
    name = f"s{s}_{st['seq']}"
    st["seq"] += 1
    attr = await t.timed(CLASS, 0, t.clients[s].create(d.inode, name), True)
    st["cur"] = t.model.create(name, attr.inode, t.dirs.index(d))
    st["mine"].append(st["cur"])
    st["made"].append(st["cur"])
