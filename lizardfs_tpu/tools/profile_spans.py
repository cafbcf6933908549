"""The program's spans beside the device's operations, from one jax
profile.

Once the process that owns the chip has built a ``TpuChunkEncoder``,
every ``runtime/tracing.span`` is also a ``TraceAnnotation`` named
``lz.<layer>.<name>``, so a profile taken round a workload
(``jax.profiler.start_trace`` ... ``stop_trace``; the benchmark's
``--trace 1 --keep-trace DIR``) holds them on its host planes, on the
same clock as the device's operations. This reads such a profile
(``.xplane.pb``) with nothing but jax and answers what the benchmark's
own reduction, which keeps only its ``bench.*`` names, cannot:

  clock     the offset of the profile's clock from ``time.time_ns()``,
            from the ``t_ns`` every op root carries (the daemons' span
            rings stay on CLOCK_REALTIME: add the offset to lay them on
            the profile's axis)
  nesting   how many ``lz.*`` spans lie inside a span of the caller
            (``--outer bench.op.``)
  launches  whether each run of a device program lies inside the
            ``dev_fetch`` span of the call that launched it, between
            its ``dev_run`` and the end of its ``dev_fetch``, or at
            least inside its ``boundary`` span; and by how much the
            device's clock leads the host's (a run that starts before
            the ``dev_run`` that launched it shows the two planes'
            clocks apart: they agree within a millisecond or so, and
            differently in every session)
  gaps      the longest intervals in which the device ran nothing, each
            named by the innermost program span that covers most of it

    python -m lizardfs_tpu.tools.profile_spans TRACE.xplane.pb
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys

DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"
NOT_OPS = ("Steps", "XLA Modules", "XLA TraceMe", "Source code")


def extract(xplane_path: str, device_prefix: str = DEVICE_PREFIX) -> dict:
    """``{"host": [[name, start_ns, dur_ns, stats]], "device": [[name,
    start_ns, dur_ns]], "programs": [[name, start_ns, dur_ns]]}``: the
    ``lz.*`` and ``bench.*`` annotations of the host planes, the first
    device plane's operations and its runs of compiled programs."""
    from jax.profiler import ProfileData

    out = {"host": [], "device": [], "programs": []}
    for plane in ProfileData.from_file(xplane_path).planes:
        lines = list(plane.lines)
        if plane.name.startswith(device_prefix) and not out["device"]:
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or [
                ln for ln in lines if ln.name not in NOT_OPS]
            out["device"] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                             for ln in ops for e in ln.events]
            out["programs"] = [
                [e.name, int(e.start_ns), int(e.duration_ns)]
                for ln in lines if ln.name == "XLA Modules"
                for e in ln.events]
        elif plane.name.startswith(HOST_PREFIX):
            out["host"].extend(
                [e.name, int(e.start_ns), int(e.duration_ns), dict(e.stats)]
                for ln in lines for e in ln.events
                if e.name.startswith(("lz.", "bench.")))
    return out


def clock(host: list) -> dict | None:
    """Offset of the profile's clock from ``time.time_ns()``: over the
    op roots, ``t_ns`` (taken just before the annotation opened) less
    the annotation's ``start_ns``."""
    offs = [int(st["t_ns"]) - start for _n, start, _d, st in host
            if "t_ns" in st]
    if not offs:
        return None
    med = statistics.median_low(offs)  # an int: a float loses the ns
    return {"roots": len(offs), "offset_ns": med,
            "spread_ns": [min(offs) - med, max(offs) - med]}


def nesting(host: list, outer_prefix: str) -> dict:
    """How many ``lz.*`` spans lie wholly inside some span whose name
    starts with ``outer_prefix``."""
    outers = sorted((s, s + d) for n, s, d, _st in host
                    if n.startswith(outer_prefix))
    inside = total = 0
    outside: dict[str, int] = {}
    for n, s, d, _st in host:
        if not n.startswith("lz."):
            continue
        total += 1
        if any(a <= s and s + d <= b for a, b in outers):
            inside += 1
        else:
            outside[n] = outside.get(n, 0) + 1
    return {"lz_spans": total, "inside": inside, "outside_by_name": outside}


def launches(host: list, programs: list) -> dict:
    """Each run of a device program against the boundary calls' spans:
    inside a ``dev_fetch``, else between a ``dev_run``'s start and the
    end of the ``dev_fetch`` that follows it, else inside a
    ``boundary`` span, else astray. ``lead_ms`` is, over the runs, the
    start of the nearest ``dev_run`` of a call that holds the run less
    the run's own start (min, quartiles, max): above 0 the device's
    clock leads the host's by at least that."""
    def of(name):
        return sorted((s, s + d) for n, s, d, _st in host
                      if n == "lz.encoder." + name)

    fetch, run, boundary = of("dev_fetch"), of("dev_run"), of("boundary")
    calls = []  # (dev_run start, end of the next dev_fetch)
    for s, _e in run:
        nxt = next(((a, b) for a, b in fetch if a >= s), None)
        if nxt:
            calls.append((s, nxt[1]))
    out = {"runs": len(programs), "in_fetch": 0, "run_to_fetch_end": 0,
           "in_boundary": 0, "astray": 0}
    lead = []
    for _n, s, d in programs:
        holds = [(a, b) for a, b in boundary if a <= s and s + d <= b]
        starts = [r for r, _e in run if any(a <= r <= b for a, b in holds)]
        if starts:
            lead.append((min(starts, key=lambda r: abs(r - s)) - s) / 1e6)
        if any(a <= s and s + d <= b for a, b in fetch):
            out["in_fetch"] += 1
        elif any(a <= s and s + d <= b for a, b in calls):
            out["run_to_fetch_end"] += 1
        elif holds:
            out["in_boundary"] += 1
        else:
            out["astray"] += 1
    if len(lead) >= 2:
        out["lead_ms"] = [min(lead), *statistics.quantiles(lead, n=4),
                          max(lead)]
    return out


def gaps(host: list, device: list, t0: int, t1: int, top: int = 10) -> list:
    """The ``top`` longest idle intervals of the device inside
    [t0, t1], each with the innermost ``lz.*`` span (the shortest one)
    among those that cover at least half of it."""
    busy = sorted((max(s, t0), min(s + d, t1)) for _n, s, d in device
                  if s + d > t0 and s < t1)
    idle, cur = [], t0
    for a, b in busy:
        if a > cur:
            idle.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        idle.append((cur, t1))
    idle.sort(key=lambda g: g[0] - g[1])
    spans = [(n, s, s + d) for n, s, d, _st in host if n.startswith("lz.")]
    out = []
    for a, b in idle[:top]:
        best = None
        for n, s, e in spans:
            if 2 * (min(b, e) - max(a, s)) >= b - a and (
                    best is None or e - s < best[2] - best[1]):
                best = (n, s, e)
        out.append({"gap_ms": (b - a) / 1e6,
                    "span": best[0] if best else "no lz span",
                    "span_ms": (best[2] - best[1]) / 1e6 if best else None})
    return out


def report(events: dict, outer_prefix: str = "bench.op.",
           window: str = "bench.window") -> dict:
    host = events["host"]
    win = [(s, s + d) for n, s, d, _st in host if n == window]
    every = [(s, s + d) for _n, s, d, *_ in host + events["device"]]
    t0, t1 = win[0] if win else (
        (min(a for a, _ in every), max(b for _, b in every))
        if every else (0, 0))
    names: dict[str, list] = {}
    for n, _s, d, _st in host:
        if n.startswith("lz."):
            row = names.setdefault(n, [0, 0])
            row[0] += 1
            row[1] += d
    return {
        "window_ns": [t0, t1],
        "clock": clock(host),
        "nesting": nesting(host, outer_prefix),
        "launches": launches(host, events["programs"]),
        "gaps": gaps(host, events["device"], t0, t1),
        "lz_spans": {n: {"n": c, "ms": ns / 1e6}
                     for n, (c, ns) in sorted(names.items())},
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("xplane")
    p.add_argument("--outer", default="bench.op.")
    p.add_argument("--device-prefix", default=DEVICE_PREFIX)
    args = p.parse_args(argv)
    json.dump(report(extract(args.xplane, args.device_prefix), args.outer),
              sys.stdout, indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
