"""Readers of the client's loop meter (``runtime/tracing.LoopMeter``):
the turns of the one asyncio loop every session of the worker shares,
stamped where the loop polls. ONE client of the loop shows the counts
beside its rows (``loop_turns``, ``loop_busy_us``, ``loop_turn_sq_us2``,
``loop_offcpu_us``), so ``ctx["phases"]``, the sessions' deltas over
the window summed, counts the loop once; which side they ride on is
the program's business, and that side may have closed no op: a key is
summed over both sides and no side is asked for ``reps``. A program
without the counts (the parent of the PR that brought them) gives
None, and the metric is left out."""

from __future__ import annotations


def total(ctx, key: str):
    """The key over both sides, or None where neither side has it."""
    found = [ph[key] for ph in ctx["phases"].values() if key in ph]
    return sum(found) if found else None


def busy_pct(ctx):
    """Share of the window the loop was away from its poll."""
    busy_us = total(ctx, "loop_busy_us")
    if busy_us is None:
        return None
    return 100.0 * busy_us / 1e6 / ctx["window_s"]


def delay_ms(ctx):
    """What an event that becomes ready at a random instant of the
    window waits for the loop's next poll: Σ turn² / (2 × window)."""
    sq_us2 = total(ctx, "loop_turn_sq_us2")
    if sq_us2 is None:
        return None
    return sq_us2 / (2.0 * ctx["window_s"] * 1e6) / 1e3


def offcpu_pct(ctx):
    """Share of the loop's busy time its thread was not on a CPU: it
    waited for the GIL, or blocked in a call that is not the poll."""
    off_us, busy_us = total(ctx, "loop_offcpu_us"), total(ctx, "loop_busy_us")
    if off_us is None or not busy_us:
        return None
    return 100.0 * off_us / busy_us


def named_pct(ctx):
    """Share of the loop's busy time that has a name: the ``*_hold``
    rows, what the spans that never gave the loop back held it for."""
    busy_us = total(ctx, "loop_busy_us")
    if not busy_us:
        return None
    held_ms = sum(v for ph in ctx["phases"].values()
                  for k, v in ph.items() if k.endswith("_hold_ms"))
    return 100.0 * held_ms * 1e3 / busy_us


def wake_ms(ctx):
    """The way back from a thread or a reply (the ``wake`` row) over
    the ops the window closed: reads and writes, and the metadata
    calls that are ops of their own."""
    if total(ctx, "loop_turns") is None:
        return None
    ops = sum(total(ctx, key) or 0
              for key in ("reps", "lookups", "get_xattrs", "unlinks"))
    if not ops:
        return None
    return (total(ctx, "wake_ms") or 0.0) / ops
