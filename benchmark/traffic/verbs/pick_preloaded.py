"""Make a preloaded file the current one: on pass p the session takes
file (s + shift + p) % n, so no session reads the file it loaded (IOR's
``-C`` task reordering) and each pass moves on to the next. The client's
cached blocks of the file are dropped, as the source drops caches before
it reads."""


async def do(t, s, st, arg, warm):
    n = len(t.preloaded)
    i = (s + int(arg.get("shift", 0)) + st["file_pos"]) % n
    st["file_pos"] += 1
    st["cur"] = t.preloaded[i]
    t.clients[s].cache.invalidate(st["cur"].inode)
