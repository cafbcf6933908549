"""Hold rebuilds back with the master's own throttle: one rebuild may
start, and it then waits for 1 byte/s of budget; nothing else is
launched. Stands for upstream's REPLICATIONS_DELAY_DISCONNECT (3,600 s
by default), which keeps a lost server's chunks unrebuilt for the hour
the cell is about."""


async def apply(t):
    for name, value in (("rebuild_concurrency", 1), ("rebuild_bps", 1)):
        await t.cluster.admin("tweaks-set", {"name": name, "value": value})
