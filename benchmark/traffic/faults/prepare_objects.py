"""Set-up of MinIO warp's mixed benchmark: PUT the pool of ``objects``
objects the window's GETs, STATs and DELETEs draw from, through the
PUT's five ``Client`` calls (``warp_mixed_op``'s own, so ``put_whole``'s
bodies and keys), the sessions side by side, untimed. Then drive the
decodes a GET falls back on where a chunkserver stands still for longer
than the client's read plans wait (``benchmark/README.md``): one and two
of an object's data parts recovered at the part's own length, a
geometry the worker's own warming, which goes by the encode calls'
shapes, does not reach (a PUT encodes seven segments, a GET reads whole
parts)."""

import asyncio

from reference import layout
from worker import warm_decode


async def apply(t):
    verb = t.verbs["warp_mixed_op"]
    lanes, n = len(t.clients), int(t.mix["objects"])

    async def lane(s: int) -> None:
        for _ in range(s, n, lanes):
            await verb.put(t, s, t._state(s), True)

    await asyncio.gather(*(lane(s) for s in range(lanes)))
    goal = verb.PUT.directory(t, "bucket").goal
    k, m = int(goal["k"]), int(goal["m"])
    part = max(layout.part_lengths(k, m, max(t.plan.sizes),
                                   int(t.mix["block_bytes"])))
    for wanted in (1, 2):
        warm_decode(t.clients[0].encoder, k, m, k, wanted, part)
