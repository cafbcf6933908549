"""Readers of what the worker's polls of the operator's channel saw
between a server's kill inside the window and full redundancy
(``ctx["rebuild"]``, ``benchmark/redundancy.py``), and of the master's
own counts over the window (``ctx["master"]``). A run that killed no
server carries None there, and a program whose master lacks a count
leaves it out: the reader gives None and the metric is left out."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import redundancy  # noqa: E402


def detect_ms(ctx):
    """From the kill to the first rebuild the master started."""
    rb = ctx.get("rebuild")
    if not rb or rb.get("first_start") is None:
        return None
    return max(rb["first_start"] - rb["kill_at"], 0.0) * 1e3


def part_ms(ctx):
    """Mean of the master's own ``ms`` over its records of completed
    rebuilds, one a (chunk, part): a part's read, recover and write on
    the chunkserver that made it, and the command's round trip."""
    rb = ctx.get("rebuild")
    if not rb or not rb.get("records"):
        return None
    return sum(r["ms"] for r in rb["records"]) / len(rb["records"])


def after_close_pct(ctx):
    """Share of the time from the kill to full redundancy that lay
    after the close, where no writer contends."""
    share = redundancy.after_close_share(ctx.get("rebuild"))
    return None if share is None else 100.0 * share


def write_slowdown_pct(ctx):
    """100 x (1 - the writers' rate between the kill and full
    redundancy or the close, whichever is first, over their rate from
    the open to the kill), by where each acknowledged write ended."""
    rb = ctx.get("rebuild")
    if not rb or rb.get("t_whole") is None:
        return None
    t_open, kill = ctx["t_open"], rb["kill_at"]
    end = min(rb["t_whole"], rb["t_close"])
    if kill <= t_open or end <= kill:
        return None
    done = [o for o in ctx["ops"] if o.ok and o.cls == "write"]
    before = sum(o.nbytes for o in done if t_open < o.end <= kill)
    during = sum(o.nbytes for o in done if kill < o.end <= end)
    if not before:
        return None
    return 100.0 * (1.0 - (during / (end - kill)) / (before / (kill - t_open)))


def write_through_loss_mbps(ctx):
    """The foreground's rate over the whole window, through the loss:
    the bytes of the writes acknowledged inside a window in which a
    server was killed, over its seconds, as the end-to-end
    ``write_MBps`` is taken in the cells that report it."""
    if not ctx.get("rebuild"):
        return None
    t_open, t_close = ctx["t_open"], ctx["t_close"]
    done = sum(o.nbytes for o in ctx["ops"]
               if o.ok and o.cls == "write" and o.end <= t_close)
    return done / 1e6 / (t_close - t_open) if done else None


def grant_bumps_pct(ctx):
    """Share of the window's write grants that raised the chunk's
    version first (the master's ``write_grant_bumps`` over its
    ``write_grants``)."""
    m = ctx.get("master") or {}
    if not m.get("write_grants"):
        return None
    return 100.0 * m.get("write_grant_bumps", 0.0) / m["write_grants"]
