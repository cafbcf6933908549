"""Read the current file from offset 0 to its end in sequential
``read_file(inode, offset, size)`` calls of the mix's
``transfer_bytes`` (a mount's read(2)), each a timed read; a seeded
share of the answers is kept for the comparison."""

CLASS = "read"


async def do(t, s, st, arg, warm):
    f = st["cur"]
    if f is None:
        return
    transfer = int(t.mix.get("transfer_bytes") or max(f.length, 1))
    for off in range(0, f.length, transfer):
        if not warm and not t.running():
            return
        size = min(transfer, f.length - off)
        data = await t.timed(CLASS, size, t.clients[s].read_file(
            f.inode, off, size))
        t.retain(st, f, off, size, data)
