"""Write the current file from offset 0 to its size in sequential
``pwrite`` calls of the mix's ``transfer_bytes`` (a mount's write(2):
``fuse_mount`` hands each to ``Client.pwrite``), each a timed write
that is acknowledged when it returns. The size is the next of the
mix's sizes. ``warm_transfers`` cuts a warm-up file to that many calls:
every call has the same shape."""

CLASS = "write"


async def do(t, s, st, arg, warm):
    f = st["cur"]
    if f is None:
        return
    size = t.next_size(s, st, warm)
    transfer = int(t.mix.get("transfer_bytes") or size)
    if warm and "warm_transfers" in arg:
        size = min(size, transfer * int(arg["warm_transfers"]))
    base = int(st["rng"].integers(0, t.plan.slack // 64)) * 64
    for off in range(0, size, transfer):
        if not warm and not t.running():
            return
        take = min(transfer, size - off)
        await t.timed(CLASS, take, t.clients[s].pwrite(
            f.inode, off, t.model.pool[base + off:base + off + take]))
        t.model.write(f.name, base, off + take)
