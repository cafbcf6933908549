"""Readers of what the program counts beside its phase rows
(``PhaseBreakdown.count``): ``ctx["phases"]`` carries the counts under
their own names, as the clients' deltas over the window summed over the
sessions, next to ``reps``. A program that has no such count (the
parent of the PR that brought it) gives None, and the metric is left
out."""

from __future__ import annotations


def counts(ctx, side: str, *names: str):
    """The named counts of the side, or None where it closed no op in
    the window or the program lacks one of them."""
    ph = ctx["phases"][side]
    if not ph.get("reps") or any(n not in ph for n in names):
        return None
    return [ph[n] for n in names]
