"""From a profiler trace to numbers. Two steps, so that the second can
be checked on a small recorded trace kept beside this file:

  extract(xplane_path) -> {"device": {plane: [[name, start_ns, dur_ns]]},
                           "programs": [[name, start_ns, dur_ns]],
                           "host": [[name, start_ns, dur_ns]]}
      device     the operations of each device plane (its "XLA Ops"
                 line; where a plane has none, every line but the
                 step and module lines)
      programs   the "XLA Modules" line of the first device plane: one
                 event per run of a compiled program
      host       the benchmark's own ``bench.*`` annotations
  reduce(events, t0_ns, t1_ns) -> busy union, idle share, per-program
      sums, the device's busy time under each of the benchmark's spans,
      top device operations, longest idle gaps by what the host was
      doing.

All times are the profiler's own clock, nanoseconds.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"
NOT_OPS = ("Steps", "XLA Modules", "XLA TraceMe", "Source code")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(xplane_path: str, device_prefix: str = DEVICE_PREFIX) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    out = {"device": {}, "programs": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or [
                ln for ln in lines if ln.name not in NOT_OPS]
            out["device"][plane.name] = [
                [e.name, int(e.start_ns), int(e.duration_ns)]
                for ln in ops for e in ln.events
                if not e.name.startswith("bench.")]
            if not out["programs"]:
                out["programs"] = [
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for ln in lines if ln.name == "XLA Modules"
                    for e in ln.events]
        if plane.name.startswith(HOST_PREFIX):
            out["host"].extend(
                [e.name, int(e.start_ns), int(e.duration_ns)]
                for ln in plane.lines for e in ln.events
                if e.name.startswith("bench."))
    return out


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def overlap(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> int:
    """Total length of the intersection of two merged interval lists."""
    total = i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def clip(events, t0: int, t1: int) -> list[tuple[str, int, int]]:
    out = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((name, a, b))
    return out


def window_of(events: dict) -> tuple[int, int]:
    """Where no window is given: from the first to the last event."""
    every = [e for evs in events["device"].values() for e in evs]
    every += events["host"]
    if not every:
        return 0, 0
    return (min(e[1] for e in every), max(e[1] + e[2] for e in every))


def _strip(name: str) -> str:
    # "jit_apply_gf(1234567)" and "jit_apply_gf" are one program
    return name.split("(")[0]


def _short(name: str) -> str:
    """An HLO instruction's text, cut to its name, opcode and result:
    "%fusion.10 fusion u8[4,1048576]"."""
    if " = " not in name:
        return name[:96]
    left, right = name.split(" = ", 1)
    shape = right.split("{")[0].lstrip("(").split(" ")[0]
    op = re.search(r"[\]})] ([a-z][a-z0-9-]*)\(", right)
    return " ".join(x for x in (left, op.group(1) if op else "", shape)
                    if x)[:96]


def reduce(events: dict, t0: int | None = None, t1: int | None = None) -> dict:
    if t0 is None or t1 is None:
        t0, t1 = window_of(events)
    window_s = (t1 - t0) / 1e9
    busy_by_plane = {}
    merged_by_plane = {}
    for plane, evs in events["device"].items():
        merged = union([(a, b) for _n, a, b in clip(evs, t0, t1)])
        merged_by_plane[plane] = merged
        busy_by_plane[plane] = sum(b - a for a, b in merged) / 1e9
    n = max(len(busy_by_plane), 1)
    busy_s = sum(busy_by_plane.values()) / n
    op_s: dict[str, float] = {}
    for evs in events["device"].values():
        for name, a, b in clip(evs, t0, t1):
            name = _short(name)
            op_s[name] = op_s.get(name, 0.0) + (b - a) / 1e9
    program_s: dict[str, float] = {}
    program_n: dict[str, int] = {}
    for name, a, b in clip(events["programs"], t0, t1):
        key = _strip(name)
        program_s[key] = program_s.get(key, 0.0) + (b - a) / 1e9
        program_n[key] = program_n.get(key, 0) + 1
    host = clip(events["host"], t0, t1)
    gaps = []
    first = next(iter(merged_by_plane.values()), [])
    edges = [t0] + [x for ab in first for x in ab] + [t1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((a, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    named_gaps = []
    for a, b in gaps[:10]:
        # what the host was doing: the benchmark's span that covers
        # most of the gap, a call across the encoder boundary before an
        # op of the traffic where it covers half of the gap or more
        cover: dict[str, int] = {}
        for name in {h[0] for h in host} - {"bench.window"}:
            inside = union([(max(a, ha), min(b, hb)) for n2, ha, hb in host
                            if n2 == name and min(b, hb) > max(a, ha)])
            if inside:
                cover[name] = sum(y - x for x, y in inside)
        boundary = {n: c for n, c in cover.items()
                    if not n.startswith("bench.op.") and 2 * c >= b - a}
        pick = boundary or cover
        what = max(sorted(pick), key=pick.get) if pick else "no bench span"
        named_gaps.append([what, (b - a) / 1e9])
    host_span_s: dict[str, float] = {}
    for name, a, b in host:
        host_span_s[name] = host_span_s.get(name, 0.0) + (b - a) / 1e9
    # the device's busy time while a span of that name was open on the
    # host, averaged over the planes: whatever program does a call's
    # work runs inside the call's span, since the call waits for it.
    # Where spans of two names are open at once the time counts for
    # both, which can only understate a share of a roofline
    span_device_s: dict[str, float] = {}
    for name in {h[0] for h in host}:
        spans = union([(a, b) for n2, a, b in host if n2 == name])
        span_device_s[name] = sum(
            overlap(merged, spans) for merged in merged_by_plane.values()
        ) / n / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s) if window_s > 0 else None,
        "program_s": program_s,
        "program_n": program_n,
        "device_ops": [[k, v] for k, v in sorted(
            op_s.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": named_gaps,
        "host_span_s": host_span_s,
        "span_device_s": span_device_s,
    }
