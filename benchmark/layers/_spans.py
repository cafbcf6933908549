"""Readers of the program's own span tree (``runtime/tracing.span``):
the phase rows every span charges, which ``ctx["phases"]`` carries as
the clients' ``write_phases`` / ``read_phases`` deltas over the window,
summed over the sessions. A program that has no such phase (the parent
of the PR that brought them) gives None, and the metric is left out."""

from __future__ import annotations


def phase_ms(ctx, side: str, *names: str):
    """Summed milliseconds of the named phases, or None where the side
    closed no op in the window or the program lacks one of them."""
    ph = ctx["phases"][side]
    if not ph.get("reps") or any(n + "_ms" not in ph for n in names):
        return None
    return sum(ph[n + "_ms"] for n in names)


def busy_pct(ctx, side: str, *names: str, less: tuple[str, ...] = ()):
    """Busy time of the phases (less the ``less`` ones nested in them)
    over the window, summed over the sessions, so it can pass 100."""
    ms, off = phase_ms(ctx, side, *names), phase_ms(ctx, side, *less)
    if ms is None or off is None:
        return None
    return 100.0 * (ms - off) / 1e3 / ctx["window_s"]


def share_pct(ctx, side: str, part: tuple[str, ...], whole: tuple[str, ...]):
    """The ``part`` phases' share of the ``whole`` phases' time."""
    num, den = phase_ms(ctx, side, *part), phase_ms(ctx, side, *whole)
    if num is None or not den:
        return None
    return 100.0 * num / den


def ms_per_op(ctx, side: str, *names: str):
    ms = phase_ms(ctx, side, *names)
    return None if ms is None else ms / ctx["phases"][side]["reps"]


BOUNDARY = ("dev_stage", "dev_put", "dev_run", "dev_fetch")
