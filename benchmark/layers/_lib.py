"""Shared arithmetic of the per-layer readers. A reader takes the run's
context (window, ops, phase deltas, the encoder tap, the reduced trace,
the configuration, the peaks) and returns a number, or None where it
finds nothing to read; the harness then leaves the metric out."""

from __future__ import annotations

import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import rooflines  # noqa: E402


def busy_pct(ctx, side: str, *phase_names: str):
    """Busy time of client phases over the window, summed over the
    sessions (overlapping sessions can pass 100)."""
    ph = ctx["phases"][side]
    if not ph.get("reps"):
        return None
    ms = sum(ph.get(p + "_ms", 0.0) for p in phase_names)
    return 100.0 * ms / 1e3 / ctx["window_s"]


def idle_pct(ctx):
    return ctx["trace"]["idle_pct"] if ctx.get("trace") else None


def encode_boundary_mbps(ctx):
    calls = ctx["tap"].encode_calls
    secs = sum(c[4] for c in calls)
    if not calls or secs <= 0:
        return None
    return sum(c[2] * c[3] for c in calls) / 1e6 / secs


def recover_boundary_mbps(ctx):
    calls = ctx["tap"].recover_calls
    secs = sum(c[5] for c in calls)
    if not calls or secs <= 0:
        return None
    return sum(c[3] * c[4] for c in calls) / 1e6 / secs


def encode_call_ms(ctx):
    calls = ctx["tap"].encode_calls
    return statistics.median(c[4] for c in calls) * 1e3 if calls else None


def span_device_seconds(ctx, span: str) -> float:
    """Device time of the traced window that lies under the benchmark's
    span of that name (``bench.encode`` / ``bench.recover`` /
    ``bench.xor``, which the tap opens round every call across the
    boundary): by where the time
    lies, not by what the program that spent it is called, so a kernel
    that replaces today's is read the same way."""
    tr = ctx.get("trace")
    return tr["span_device_s"].get(span, 0.0) if tr else 0.0


def _roofline(ctx, calls, span, cost=rooflines.gf_product_cost):
    """Share of the roofline of the window's calls (GF products, each
    (in_rows, out_rows, length), by default) over the device time
    under ``span``."""
    if not ctx.get("peaks"):
        return None
    got = rooflines.roofline_share_pct(
        calls, span_device_seconds(ctx, span), ctx["peaks"], cost)
    return got[0] if got else None


def encode_roofline(ctx):
    return _roofline(ctx, [(rows, m, length) for _k, m, rows, length, _s
                           in ctx["tap"].encode_calls], "bench.encode")


def recover_roofline(ctx):
    return _roofline(ctx, [(rows, wanted, length) for _k, _m, rows, wanted,
                           length, _s in ctx["tap"].recover_calls],
                     "bench.recover")


def xor_roofline(ctx):
    """The XOR parity of xor goals: bytes-bound, N parts read and one
    written a call, over the device time under ``bench.xor``."""
    return _roofline(ctx, [(n, length) for n, length, _s
                           in ctx["tap"].xor_calls], "bench.xor",
                     rooflines.xor_cost)


def master_rpc_ms_per_op(ctx):
    done = [o for o in ctx["ops"] if o.ok]
    if not done:
        return None
    meta = [o for o in done if o.metadata]
    return sum((o.end - o.start) for o in meta) * 1e3 / len(done)
