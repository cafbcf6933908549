"""SIGKILL chunkserver ``cs<seed mod n>`` where it stands: an event of
the window, so nothing is asked of the master first (no sweep of the
chunks' places) and nobody waits for the master to notice. Records the
victim and the moment of the signal. It does not fill
``lost_part_chunks``: once the master has rebuilt what the victim held,
the comparison has to find k + m parts of every chunk on live servers.
"""


async def apply(t):
    t.victim = f"cs{t.seed % t.cluster.n_cs}"
    t.kill_at = await t.cluster.kill9_soon(t.victim)
