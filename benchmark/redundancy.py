"""The wait for full redundancy after a server was killed inside the
window, through the operator's channel alone: what ``lizardfs-admin``
shows (``info``, ``rebuild-status``, ``chunks-health``), polled from
the moment of the kill until the master says that nothing is left to
rebuild. The polled documents are reduced to what the end-to-end
metric ``rebuild_MBps`` and the per-layer readers take
(``ctx["rebuild"]``). Never imports jax.
"""

from __future__ import annotations

import asyncio
import time

from reference import layout

POLL_S = 0.1
COUNTERS = ("write_grants", "write_grant_bumps")


def asked_for(mix: dict) -> bool:
    """The wait is selected by one key of the mix, its cap, and by
    nothing else: not by the events the mix carries nor by what they
    do. A mix without it runs as a plain window."""
    return "redundancy_cap_s" in mix


def whole(status: dict, health: dict) -> bool:
    """Nothing waits, nothing runs, nothing is short of a part."""
    return (not status["endangered_queue"] and not status["active"]
            and not any(status["queued"].values())
            and not health["endangered"] and not health["lost"])


def master_counts(doc: dict) -> dict:
    """The master's own counts of its ``metrics`` document that the
    readers take, labelled series summed under their family and kept
    apart under their full name."""
    out: dict[str, float] = {}
    for name, series in doc.items():
        family = name.split("{", 1)[0]
        if family in COUNTERS:
            out[family] = out.get(family, 0.0) + series["total"]
            if name != family:
                out[name] = series["total"]
    return out


def counts_delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


class Watch:
    """Polls the master from the kill until redundancy is whole, as a
    task beside the sessions, started where the object is made (inside
    a running loop)."""

    def __init__(self, cluster, traffic):
        self.cluster, self.traffic = cluster, traffic
        self.noticed_at: float | None = None   # the master saw the server go
        self.t_whole: float | None = None
        self.polls: list[tuple[float, dict, dict]] = []
        self.task = asyncio.create_task(self._run())

    async def _run(self) -> None:
        t = self.traffic
        while t.kill_at is None:
            await asyncio.sleep(POLL_S / 2)
        while True:
            at = time.monotonic()
            try:
                if await self._poll(at):
                    self.t_whole = time.monotonic()
                    return
            except (ConnectionError, OSError, asyncio.TimeoutError):
                pass    # a master too busy to answer once is asked again
            await asyncio.sleep(max(at + POLL_S - time.monotonic(), 0.0))

    async def _poll(self, at: float) -> bool:
        if self.noticed_at is None:
            info = await self.cluster.admin("info")
            if sum(1 for s in info["chunkservers"] if s["connected"]) \
                    < self.cluster.n_cs:
                self.noticed_at = at
        status = await self.cluster.admin("rebuild-status")
        health = await self.cluster.admin("chunks-health")
        self.polls.append((at, status, health))
        return self.noticed_at is not None and whole(status, health)


def reduce(polls: list, kill_at: float, noticed_at: float | None,
           t_whole: float | None, t_close: float) -> dict:
    """The polled documents as the readers take them. A rebuild's start
    is the first poll that saw it run, less how long it had run by
    then; its end is the first poll that listed it among the completed
    (so both are late by up to one poll)."""
    first = polls[0][1]
    last = polls[-1][1]
    before = {(r["chunk_id"], r["part"], r["trace_id"])
              for r in first["recent"]}
    starts: dict[tuple, float] = {}
    records: dict[tuple, dict] = {}
    for at, status, _health in polls:
        for rb in status["active"]:
            key = (rb["chunk_id"], rb["part"])
            starts[key] = min(starts.get(key, at), at - rb["running_s"])
        for rec in status["recent"]:
            key = (rec["chunk_id"], rec["part"])
            if rec["ok"] and key not in records and (
                    *key, rec["trace_id"]) not in before:
                records[key] = dict(rec, seen_at=at)
                starts.setdefault(key, at - rec["ms"] / 1e3)
    done = sorted(records.values(), key=lambda r: r["seen_at"])
    return {
        "kill_at": kill_at, "noticed_at": noticed_at, "t_whole": t_whole,
        "t_close": t_close,
        "first_start": min(starts.values(), default=None),
        "records": done,
        # the program's own count, for the log beside the harness's
        "bytes_master": last["bytes_rebuilt"] - first["bytes_rebuilt"],
        "completed": last["completed"] - first["completed"],
        "failed": last["failed"] - first["failed"],
        "after_close": sum(1 for r in done if r["seen_at"] > t_close),
        "polls": len(polls),
    }


def rebuilt_live_bytes(parts: set, chunks: dict) -> tuple[int, int]:
    """(bytes, parts found) of the parts made whole again, reckoned by
    the harness itself: for each (chunk id, part index) of the sound
    records, the live bytes the reference's layout gives that part for
    the length the harness's own model has of the chunk (``chunks``:
    chunk id -> (goal, chunk length, block)). Not the master's count,
    which takes a part at its nominal size, and not the part files'
    sizes, which the chunkserver writes out to that size whatever the
    chunk holds. A record of a chunk no live file holds counts nothing."""
    total = found = 0
    for chunk_id, part in parts:
        if chunk_id in chunks:
            goal, length, block = chunks[chunk_id]
            total += layout.goal_part_lengths(goal, length, block)[part]
            found += 1
    return total, found


def rebuild_s(rb: dict) -> float | None:
    if not rb or rb.get("t_whole") is None:
        return None
    return rb["t_whole"] - rb["kill_at"]


def rebuild_mbps(rb: dict) -> float | None:
    """Bytes of parts made whole again (``rb["bytes"]``: the worker's
    own reckoning, ``rebuilt_live_bytes``) over the time from the kill
    to full redundancy, under whatever the foreground did meanwhile."""
    secs = rebuild_s(rb)
    if not secs or rb.get("bytes", 0) <= 0:
        return None
    return rb["bytes"] / 1e6 / secs


def after_close_share(rb: dict) -> float | None:
    secs = rebuild_s(rb)
    if not secs:
        return None
    return max(rb["t_whole"] - max(rb["t_close"], rb["kill_at"]), 0.0) / secs
