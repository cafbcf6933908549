"""The process that owns the chip: the client side of the deployment.

Started by run.py (which never imports jax). Brings the deployment up,
runs the cell's set-up, opens the measured window, closes it, compares
what the window produced with the plain reference, tears everything
down and prints the one result line. Exits non-zero with no result
line when jax reports no TPU of a kind in peaks.json, fewer chips than
the cell asks for, an encoder resolved to anything but the device's, or
a compile inside the window.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, REPO)

import checks  # noqa: E402
import manifest  # noqa: E402
import redundancy  # noqa: E402
import resultline  # noqa: E402
from cluster import Cluster  # noqa: E402
from generator import Directory, Traffic  # noqa: E402
from reference import layout  # noqa: E402
from tap import EncoderTap  # noqa: E402

FAULTS_CLIENT = ("write-noop", "write-half", "read-flip")
FAULT_REBUILT = "rebuilt-flip"
# the control of a copy goal, in the client (tap.py's are the encoder's)
CONTROL_CLIENT = "copy-flip"


T_START = time.time()


def say(msg: str) -> None:
    print(f"[{time.time() - T_START:6.1f}s] {msg}", flush=True)


class CompileCounter:
    """Programs this process asked the backend to compile or load, from
    jax.monitoring (copied from chip_smoke.py's)."""

    def __init__(self):
        from jax import monitoring

        self.programs = 0
        self.compile_s = 0.0
        self.cache_misses = self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.compile_s += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def break_client(client, fault: str) -> None:
    """The timed path broken underneath the harness: a fault (tests
    only), or the control ``copy-flip``, which breaks a copy goal's
    guarantee of identical copies: where a write goes to two or more
    copies (a relay chain), the last copy is sent apart with one byte
    altered."""
    pwrite, read_file = client.pwrite, client.read_file
    write_part = client._write_part
    if fault == "write-noop":
        async def noop(inode, offset, data):
            return None
        client.pwrite = noop
    elif fault == "write-half":
        async def half(inode, offset, data):
            return await pwrite(inode, offset, data[:len(data) // 2])
        client.pwrite = half
    elif fault == "read-flip":
        async def flip(inode, offset=0, size=None):
            got = bytearray(await read_file(inode, offset, size))
            if got:
                got[len(got) // 2] ^= 1
            return bytes(got)
        client.read_file = flip
    elif fault == CONTROL_CLIENT:
        async def one_copy_off(chunk_id, version, locs, payload, length,
                               *a, **kw):
            if len(locs) < 2 or length <= 0:
                return await write_part(chunk_id, version, locs, payload,
                                        length, *a, **kw)
            await write_part(chunk_id, version, locs[:-1], payload, length,
                             *a, **kw)
            off = np.array(payload[:length], dtype=np.uint8)
            off[length // 2] ^= 1
            return await write_part(chunk_id, version, locs[-1:], off,
                                    length, *a, **kw)
        client._write_part = one_copy_off


def flip_rebuilt(cluster, parts: set) -> int:
    """Tests only: one byte altered in every part file the master's
    records say was rebuilt, where the rebuild left it."""
    flipped = 0
    for chunk_id in sorted({cid for cid, _p in parts}):
        for pid, path in layout.find_chunk_files(cluster.live_cs_dirs(),
                                                 chunk_id):
            if (chunk_id, pid % 64) in parts:
                with open(path, "r+b") as f:
                    f.seek(layout.HEADER_BYTES)
                    byte = f.read(1)
                    f.seek(layout.HEADER_BYTES)
                    f.write(bytes([byte[0] ^ 1]))
                flipped += 1
    return flipped


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of all the values."""
    s = sorted(values)
    if not s:
        return float("nan")
    return s[max(math.ceil(round(q * len(s), 9)) - 1, 0)]


def untraced_spans(counts, span_device_s: dict) -> list[str]:
    """The benchmark's spans under which calls crossed the encoder
    boundary while the profile ran and the trace holds no device time:
    the kernels' rooflines cannot be read then. ``counts`` are the
    tap's as they stood where the trace stopped: what crosses the
    boundary later (the comparison's read-back decoding a slow part)
    is in no trace and is no fault of the run."""
    return [span for span, calls in (("bench.encode", counts.encode_calls),
                                     ("bench.recover", counts.recover_calls),
                                     ("bench.xor", counts.xor_calls))
            if calls and not span_device_s.get(span)]


def fallback_decodes(counts, plans_run: int) -> list[tuple]:
    """The decodes a part read that is slow or lost inside the window
    would fall back on, as (k, m, live rows, wanted, part bytes): the
    client's read plans ask for parity once a part has not answered
    within their wave timeout (0.3 s; the machine's file system stops a
    chunkserver for longer now and then) and recover the part across
    the boundary, at the geometry of the region read: a program of its
    own, which a sound warm-up never drives, so that the first such
    read compiled inside the window and the run ended 4 with the
    program sound. ``counts`` are the tap's over the warm-up run,
    ``plans_run`` how many read plans its clients executed (reads, and
    the read-backs of writes that are not whole stripes): where none
    ran, the window reads nothing back and there is nothing to warm.
    Where they did: one wanted part at each geometry the warm-up
    encoded at, and one part more than the warm-up's own degraded
    reads wanted, as far as the goal's m reaches."""
    if not plans_run:
        return []
    driven = {c[:5] for c in counts.recover_calls}
    want = {c[:3] + (1, c[3]) for c in counts.encode_calls}
    want |= {c[:3] + (c[3] + 1, c[4]) for c in counts.recover_calls
             if c[3] < c[1]}
    return sorted(want - driven)


def warm_decode(enc, k: int, m: int, rows: int, wanted: int,
                nbytes: int) -> None:
    """The first ``wanted`` data parts recovered from the k parts that
    follow them, ``rows`` of these holding bytes (the others lie past
    the file's end, as in a short file's last stripe), the last of them
    parity."""
    zeros = np.zeros(nbytes, dtype=np.uint8)
    given = range(wanted, wanted + k)
    parts = {i: (zeros if j >= k - min(rows, k) else None)
             for j, i in enumerate(given)}
    enc.recover(k, m, parts, list(range(wanted)))


def end_to_end(ops, t_open: float, t_close: float,
               rebuild: dict | None = None) -> dict:
    """The cell's end-to-end numbers, each over all the work and all
    the time of the window; ``rebuild_MBps`` over all the time from the
    kill to full redundancy, where a server was killed."""
    window = t_close - t_open
    inside = [o for o in ops if o.ok and o.end <= t_close]
    lat = [(o.end - o.start) * 1e3 if o.ok else float("inf") for o in ops]
    finite = [v for v in lat if v != float("inf")]
    worst = max(finite, default=0.0)
    return {
        "write_MBps": sum(o.nbytes for o in inside if o.cls == "write")
        / 1e6 / window,
        "read_MBps": sum(o.nbytes for o in inside if o.cls == "read")
        / 1e6 / window,
        "ops_per_s": len(inside) / window,
        # a failed op misses any limit: it reads as the slowest seen
        "op_p95_ms": percentile([v if v != float("inf") else worst
                                 for v in lat], 0.95),
        "rebuild_MBps": redundancy.rebuild_mbps(rebuild),
    }


async def wait_for_whole(traffic, cluster, watch, t_open: float,
                         t_close: float, chunks) -> dict | None:
    """After the close of a mix that asks for the wait (its
    ``redundancy_cap_s``): wait until the master reports full redundancy
    again, that many seconds at the most. Returns what the polls saw,
    reduced, with the bytes made whole again as the harness reckons
    them (``chunks``: an awaitable giving ``checks.chunk_table``); None
    (and the daemons' log tails) where the cap passed, no server was
    killed or nothing was rebuilt."""
    cap = float(traffic.mix["redundancy_cap_s"])
    if traffic.kill_at is None:
        say("FAIL: the mix waits for full redundancy (redundancy_cap_s) "
            "and none of its events killed a server")
        return None
    done, _ = await asyncio.wait(
        [watch.task], timeout=max(t_close + cap - time.monotonic(), 0.0))
    if done:
        watch.task.result()
    rb = redundancy.reduce(watch.polls, traffic.kill_at, watch.noticed_at,
                           watch.t_whole, t_close)
    traffic.rebuilt_parts = {(r["chunk_id"], r["part"])
                             for r in rb["records"]}
    rb["bytes"], found = redundancy.rebuilt_live_bytes(
        traffic.rebuilt_parts, await chunks() if rb["records"] else {})
    secs = redundancy.rebuild_s(rb)
    say(f"{traffic.victim} SIGKILLed {traffic.kill_at - t_open:.3f}s into the "
        f"window; the master saw it go after "
        f"{((watch.noticed_at or math.nan) - traffic.kill_at) * 1e3:.0f} ms, "
        f"the first rebuild started after "
        f"{((rb['first_start'] or math.nan) - traffic.kill_at) * 1e3:.0f} ms; "
        f"rebuilds completed {rb['completed']} ({len(rb['records'])} seen in "
        f"{rb['polls']} polls), failed {rb['failed']}, "
        f"{rb['after_close']} ended after the close; {found} rebuilt parts "
        f"of live files' chunks hold {rb['bytes']} B by the reference's "
        f"layout (the numerator; the master's bytes_rebuilt says "
        f"{rb['bytes_master']})")
    if redundancy.rebuild_mbps(rb) is None:
        say("FAIL: " + (f"no full redundancy {cap:.0f}s after the close"
                        if secs is None else "nothing was rebuilt")
            + f"; the last poll: {json.dumps(watch.polls[-1:])[:1500]}")
        for name in ["master"] + sorted(
                n for n in cluster.procs if n not in ("master",
                                                      traffic.victim))[:3]:
            say(f"{name}: {cluster.log_tail(name)}")
        return None
    say(f"full redundancy {secs:.3f}s after the kill (rebuild_s), "
        f"{100.0 * redundancy.after_close_share(rb):.1f} % of it after the "
        f"close: rebuild_MBps {redundancy.rebuild_mbps(rb):.3f}")
    return rb


async def run_cell(args, cell, enc, tap, counter, annotate, t0_epoch) -> int:
    cfg, mix = cell.config, cell.mix
    work = tempfile.mkdtemp(prefix="lizardfs_bench_")
    cluster = Cluster(REPO, work, cfg["goals"], int(cfg["chunkservers"]))
    clients = []
    watch = None
    import jax

    from lizardfs_tpu.client.client import Client

    try:
        await cluster.start()
        say(f"cluster up: 1 master + {cluster.n_cs} chunkservers "
            f"(real processes, default settings) in {work}")
        for s in range(int(mix["sessions"]) + 1):  # the last one checks
            c = Client("127.0.0.1", cluster.master_port, encoder=enc)
            await asyncio.wait_for(c.connect(info=f"bench{s}"), 60.0)
            if c.encoder is not enc:
                raise SystemExit("a client did not take the device encoder")
            clients.append(c)
        checker = clients.pop()
        for broken in {args.fault, args.control} & {*FAULTS_CLIENT,
                                                     CONTROL_CLIENT}:
            for c in clients:
                break_client(c, broken)
        goals = {g["name"]: g for g in cfg["goals"]}
        dirs = []
        for entry in cfg["directories"]:
            d = await checker.mkdir(1, entry["name"])
            await checker.setgoal(d.inode, int(goals[entry["goal"]]["id"]))
            dirs.append(Directory(entry["name"], d.inode,
                                  goals[entry["goal"]]))
        traffic = Traffic(mix, args.seed, clients, dirs, cluster,
                          int(cfg["chunk_bytes"]),
                          annotate=annotate if args.trace else None)
        await traffic.setup(on_warm=tap.reset)
        if cluster.dead():
            raise RuntimeError(f"daemons died in set-up: {cluster.dead()}")
        decodes = fallback_decodes(tap.snapshot(), sum(
            c.read_phases.snapshot()["reps"]
            + c.write_phases.snapshot().get("rmw_reads", 0) for c in clients))
        for geometry in decodes:
            warm_decode(enc, *geometry)
        if decodes:
            say(f"warmed the decode a slow or lost part would force, at "
                f"(k, m, live rows, wanted, part bytes) {decodes}")
        say(f"set-up done: {len(traffic.model.live())} files live, "
            f"{len(traffic.preloaded)} preloaded, victim={traffic.victim}, "
            f"{len(traffic.degraded_chunks)} chunks lost a data part; "
            f"{counter.programs} programs so far ({counter.compile_s:.1f}s, "
            f"{counter.cache_hits} from the persistent cache)")

        rebuilds0 = (await cluster.admin("rebuild-status")).get("completed", 0)
        master0 = redundancy.master_counts(await cluster.admin("metrics"))
        if redundancy.asked_for(mix):
            watch = redundancy.Watch(cluster, traffic)
        tap.reset()
        programs0 = counter.programs
        before = [(c.write_phases.snapshot(), c.read_phases.snapshot())
                  for c in clients]
        trace_dir = os.path.join(work, "trace")
        if args.trace:
            try:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            except (AttributeError, TypeError):
                jax.profiler.start_trace(trace_dir)
        setup_s = time.time() - t0_epoch
        with annotate("bench.window") if args.trace \
                else contextlib.nullcontext():
            t_open, t_close = await traffic.run(args.seconds)
        t_done = time.monotonic()
        if args.trace:
            jax.profiler.stop_trace()
        # the tap as it stands where the trace stops: what the rooflines,
        # the boundary's rates and the verdict on the trace read
        counts = tap.snapshot()
        disk_peak = cluster.disk_bytes()
        if counter.programs != programs0:
            say(f"FAIL: {counter.programs - programs0} programs were "
                "compiled or loaded inside the measured window; the calls "
                "across the boundary there, by shape: encode (k, m, rows, "
                f"part bytes) {sorted({c[:4] for c in counts.encode_calls})}"
                ", recover (k, m, rows used, wanted, part bytes) "
                f"{sorted({c[:5] for c in counts.recover_calls})}")
            return 4
        phases = {"write": {}, "read": {}}
        for c, (w0, r0) in zip(clients, before):
            for key, snap0, snap1 in (
                    ("write", w0, c.write_phases.snapshot()),
                    ("read", r0, c.read_phases.snapshot())):
                for name, val in snap1.items():
                    phases[key][name] = phases[key].get(name, 0) + val \
                        - snap0.get(name, 0)
        rebuilds1 = (await cluster.admin("rebuild-status")).get("completed", 0)
        master = redundancy.counts_delta(master0, redundancy.master_counts(
            await cluster.admin("metrics")))
        dev = enc.device
        stats = dev.memory_stats() or {}
        mem_peak = int(stats.get("peak_bytes_in_use", 0))

        ops = traffic.ops
        by_class: dict[str, int] = {}
        for o in ops:
            by_class[o.cls] = by_class.get(o.cls, 0) + 1
        say(f"window {t_close - t_open:.3f}s: ops by class {by_class}; last "
            f"op under way ended {max(t_done - t_close, 0):.3f}s after the "
            f"close; {len(counts.encode_calls)} encode, "
            f"{len(counts.recover_calls)} recover and "
            f"{len(counts.xor_calls)} xor calls on {dev}; bytes on "
            f"disk at the close {disk_peak}; rebuilds completed inside the "
            f"window {rebuilds1 - rebuilds0}; retained for the comparison "
            f"{len(traffic.retained)} answers ({traffic.retained_bytes} B, "
            f"{sum(1 for r in traffic.retained if r.degraded)} degraded)")
        for note in traffic.notes:
            say(note)
        for cls in sorted(by_class):
            lat = sorted((o.end - o.start) * 1e3 for o in ops
                         if o.cls == cls and o.ok)
            if lat:
                say(f"  {cls}: {len(lat)} ops, median "
                    f"{lat[len(lat) // 2]:.2f} ms, p95 "
                    f"{percentile(lat, 0.95):.2f} ms")
        bins = max(int(round((t_close - t_open) / 5.0)), 1)
        width = (t_close - t_open) / bins
        moved = [0.0] * bins
        for o in ops:
            if o.ok and o.end <= t_close and o.cls in ("read", "write"):
                moved[min(int((o.end - t_open) / width), bins - 1)] += o.nbytes
        say("MB/s of reads and writes by the 5 s of the window they ended "
            f"in: {[round(b / 1e6 / width, 1) for b in moved]}; the master's "
            f"counts over the window: {master}")
        rebuild = None
        if watch is not None:
            rebuild = await wait_for_whole(
                traffic, cluster, watch, t_open, t_close,
                lambda: checks.chunk_table(traffic, checker, cfg))
            if rebuild is None:
                return 6
            if args.fault == FAULT_REBUILT:
                say(f"fault: {flip_rebuilt(cluster, traffic.rebuilt_parts)} "
                    "rebuilt part files altered by a byte")
        made = await traffic.make_live()
        if made:
            say(f"the window left too little to compare: {made} files made "
                "after the close through the same verbs")
        if traffic.preloaded:
            total = sum(-(-f.length // int(cfg["chunk_bytes"]))
                        for f in traffic.preloaded)
            say(f"share of chunks read degraded: "
                f"{len(traffic.degraded_chunks)}/{total}")
        for e in traffic.session_errors()[:5]:
            say(f"op failed: {e}")

        say("comparing with the reference")
        notes: dict = {}
        compared = await checks.compare(traffic, checker, cfg, args.seed,
                                        notes)
        correct = checks.all_within(compared)
        after = tap.snapshot()
        say(f"compared; the comparison and what else ran after the close "
            f"made {len(after.encode_calls) - len(counts.encode_calls)} "
            f"encode and {len(after.recover_calls) - len(counts.recover_calls)}"
            " recover calls across the boundary, outside every metric")
        if rebuild is not None:
            r = notes["rebuilt"]
            say(f"of the chunks compared {r['chunks']} had a part rebuilt: "
                f"{r['parts']} rebuilt part files hold {r['wrong_bytes']} "
                f"wrong bytes and {r['wrong_crcs']} wrong CRC words")
        if cluster.dead():
            say(f"FAIL: daemons died: {cluster.dead()}")
            correct = False
        if cluster.maps_libtpu():
            raise SystemExit(f"daemons map libtpu: {cluster.maps_libtpu()}")

        metrics: dict[str, dict] = {}
        breakdown = None
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": cell.chips, "memory_peak_bytes": mem_peak}
        if not args.trace:
            e2e = end_to_end(ops, t_open, t_close, rebuild)
            e2e["setup_s"] = setup_s
            for mdef in cell.end_to_end:
                metrics[mdef["name"]] = {"value": e2e[mdef["name"]],
                                         "unit": mdef["unit"]}
        else:
            tr = manifest.load_module("trace", "reduce.py")
            events = tr.extract(
                tr.find_xplane(trace_dir),
                "/host:CPU" if args.rehearse_cpu else tr.DEVICE_PREFIX)
            win = [e for e in events["host"] if e[0] == "bench.window"]
            w0, w1 = (win[0][1], win[0][1] + win[0][2]) if win \
                else tr.window_of(events)
            red = tr.reduce(events, w0, w1)
            for span in untraced_spans(counts, red["span_device_s"]):
                say(f"FAIL: calls crossed the encoder boundary inside the "
                    f"traced window and the trace has no device time under "
                    f"{span}: the kernels' rooflines cannot be read")
                return 4
            ctx = {
                "window_s": t_done - t_open, "ops": ops, "phases": phases,
                "tap": counts, "trace": red, "config": cfg,
                "t_open": t_open, "t_close": t_close, "master": master,
                "rebuild": rebuild,
                "peaks": manifest.peaks_for(dev.device_kind)
                if not args.rehearse_cpu else None,
            }
            for mdef in cell.per_layer:
                value = manifest.load_reader(mdef["name"])(ctx)
                if value is not None:
                    metrics[mdef["name"]] = {"value": value,
                                             "unit": mdef["unit"]}
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = red
            say(f"traced window {red['window_s']:.3f}s, device busy "
                f"{red['busy_s']:.3f}s; programs {red['program_s']}")
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                with open(os.path.join(args.keep_trace,
                                       f"{cell.name}.events.json"), "w") as f:
                    json.dump(events, f)
                shutil.copy(tr.find_xplane(trace_dir), os.path.join(
                    args.keep_trace, f"{cell.name}.xplane.pb"))
        result = resultline.build(
            correct, len(ops), sum(1 for o in ops if not o.ok), metrics,
            device, compared, breakdown)
    finally:
        if watch is not None:
            watch.task.cancel()
        for c in clients:
            with contextlib.suppress(Exception):
                await asyncio.wait_for(c.close(), 10.0)
        with contextlib.suppress(Exception):
            await asyncio.wait_for(checker.close(), 10.0)
        if cluster.dead():
            for name in cluster.dead():
                say(f"{name} exited: {cluster.log_tail(name)}")
        say("stopping the daemons")
        cluster.stop()
        shutil.rmtree(work, ignore_errors=True)
        say("work directory removed")

    for name, c in compared.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    if args.rehearse_cpu:
        say("rehearsal line (not a result): " + json.dumps(result))
        say("REHEARSAL (cpu) — not a chip result")
        return 0 if correct else 5
    say("done")
    print(resultline.dumps(result), flush=True)
    return 0


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--t0", type=float, required=True)
    p.add_argument("--manifest")
    p.add_argument("--rehearse-cpu", action="store_true")
    p.add_argument("--control")
    p.add_argument("--fault")
    p.add_argument("--keep-trace")
    args = p.parse_args()
    cell = manifest.Cell(manifest.load_manifest(args.manifest), args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        say("REHEARSAL (cpu) — not a chip result")
        manifest.rehearsal_of(cell)
    import jax

    devices = jax.devices()
    dev = devices[0]
    say(f"jax {jax.__version__} platform={dev.platform} "
        f"device_kind={dev.device_kind!r} count={len(devices)}")
    if not args.rehearse_cpu:
        if dev.platform != "tpu":
            say(f"FAIL: no TPU: jax reports platform={dev.platform}")
            return 2
        manifest.peaks_for(dev.device_kind)
        if len(devices) < cell.chips:
            say(f"FAIL: the cell asks for {cell.chips} chips, jax reports "
                f"{len(devices)}")
            return 2
    counter = CompileCounter()
    # the encoder the configuration names: benchmark/encoders/<name>.py
    enc = manifest.load_module(
        "encoders", cell.config["encoder"] + ".py").make(args.rehearse_cpu, say)
    say(f"encoder={enc.name} device={enc.device}")

    def annotate(name: str):
        return jax.profiler.TraceAnnotation(name)

    tap = EncoderTap(enc, annotate if args.trace else None,
                     control=args.control, fault=args.fault)
    try:
        return asyncio.run(
            run_cell(args, cell, enc, tap, counter, annotate, args.t0))
    finally:
        tap.remove()


if __name__ == "__main__":
    sys.exit(main())
