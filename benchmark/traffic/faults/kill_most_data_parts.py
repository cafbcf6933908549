"""SIGKILL the chunkserver that holds the most data parts of the live
files, and wait until the master has noticed."""

import asyncio
import time


async def apply(t):
    c = t.clients[0]
    holds: dict[int, set] = {}
    any_part: dict[int, set] = {}
    witness: dict[int, tuple] = {}
    for f in t.model.live():
        k = int(t.dirs[f.dir].goal["k"])
        for ci in range(-(-f.length // t.chunk_bytes)):
            info = await c.chunk_info(f.inode, ci)
            for loc in info.locations:
                any_part.setdefault(loc.addr.port, set()).add((f.name, ci))
                if loc.part_id % 64 < k:
                    holds.setdefault(loc.addr.port, set()).add((f.name, ci))
                    witness[loc.addr.port] = (info.chunk_id, loc.part_id)
    port = max(sorted(holds), key=lambda p: len(holds[p]))
    t.victim = t.cluster.server_holding(*witness[port])
    t.degraded_chunks = holds[port]
    t.lost_part_chunks = any_part[port]
    t.cluster.kill9(t.victim)
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        info = await t.cluster.admin("info")
        if sum(1 for srv in info["chunkservers"] if srv["connected"]) \
                < t.cluster.n_cs:
            return
        await asyncio.sleep(0.1)
    raise RuntimeError("the master never noticed the killed chunkserver")
