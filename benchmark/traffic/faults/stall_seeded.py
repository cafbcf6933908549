"""Chunkserver ``cs<seed mod n>`` stands still where it is, twelve
spells of 0.6 s with 0.15 s between them (SIGSTOP, SIGCONT): what the
machine's file system does to a daemon now and then, for longer than
the clients' read plans wait for a part (their wave timeout, 0.3 s).
Nothing is lost and nothing is told to the master: a read that needs
the server meanwhile asks for parity and decodes, a write waits. An
event of a window (``benchmark/tests/stalled_manifest.py``); no cell of
``BENCHMARK.json`` carries it.
"""

import asyncio
import signal

SPELLS, STOPPED_S, RUNNING_S = 12, 0.6, 0.15


async def apply(t):
    proc = t.cluster.procs[f"cs{t.seed % t.cluster.n_cs}"]
    try:
        for _ in range(SPELLS):
            proc.send_signal(signal.SIGSTOP)
            await asyncio.sleep(STOPPED_S)
            proc.send_signal(signal.SIGCONT)
            await asyncio.sleep(RUNNING_S)
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGCONT)
