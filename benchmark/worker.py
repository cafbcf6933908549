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

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, REPO)

import checks  # noqa: E402
import manifest  # noqa: E402
import resultline  # noqa: E402
from cluster import Cluster  # noqa: E402
from generator import Directory, Traffic  # noqa: E402
from tap import EncoderTap  # noqa: E402

FAULTS_CLIENT = ("write-noop", "write-half", "read-flip")


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
    """Tests only: the timed path broken underneath the harness."""
    pwrite, read_file = client.pwrite, client.read_file
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


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of all the values."""
    s = sorted(values)
    if not s:
        return float("nan")
    return s[max(math.ceil(round(q * len(s), 9)) - 1, 0)]


def end_to_end(ops, t_open: float, t_close: float) -> dict:
    """The cell's end-to-end numbers, each over all the work and all
    the time of the window."""
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
    }


async def run_cell(args, cell, enc, tap, counter, annotate, t0_epoch) -> int:
    cfg, mix = cell.config, cell.mix
    work = tempfile.mkdtemp(prefix="lizardfs_bench_")
    cluster = Cluster(REPO, work, cfg["goals"], int(cfg["chunkservers"]))
    clients = []
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
        if args.fault in FAULTS_CLIENT:
            for c in clients:
                break_client(c, args.fault)
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
        await traffic.setup()
        if cluster.dead():
            raise RuntimeError(f"daemons died in set-up: {cluster.dead()}")
        say(f"set-up done: {len(traffic.model.live())} files live, "
            f"{len(traffic.preloaded)} preloaded, victim={traffic.victim}, "
            f"{len(traffic.degraded_chunks)} chunks lost a data part; "
            f"{counter.programs} programs so far ({counter.compile_s:.1f}s, "
            f"{counter.cache_hits} from the persistent cache)")

        rebuilds0 = (await cluster.admin("rebuild-status")).get("completed", 0)
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
        disk_peak = cluster.disk_bytes()
        if counter.programs != programs0:
            say(f"FAIL: {counter.programs - programs0} programs were "
                "compiled or loaded inside the measured window")
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
        dev = enc.device
        stats = dev.memory_stats() or {}
        mem_peak = int(stats.get("peak_bytes_in_use", 0))

        ops = traffic.ops
        by_class: dict[str, int] = {}
        for o in ops:
            by_class[o.cls] = by_class.get(o.cls, 0) + 1
        say(f"window {t_close - t_open:.3f}s: ops by class {by_class}; last "
            f"op under way ended {max(t_done - t_close, 0):.3f}s after the "
            f"close; {len(tap.encode_calls)} encode and "
            f"{len(tap.recover_calls)} recover calls on {dev}; bytes on "
            f"disk at the close {disk_peak}; rebuilds completed inside the "
            f"window {rebuilds1 - rebuilds0}; retained for the comparison "
            f"{len(traffic.retained)} answers ({traffic.retained_bytes} B, "
            f"{sum(1 for r in traffic.retained if r.degraded)} degraded)")
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
            f"in: {[round(b / 1e6 / width, 1) for b in moved]}")
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
        compared = await checks.compare(traffic, checker, cfg, args.seed)
        correct = checks.all_within(compared)
        say("compared")
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
            e2e = end_to_end(ops, t_open, t_close)
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
            for span, calls in (("bench.encode", tap.encode_calls),
                                ("bench.recover", tap.recover_calls)):
                if calls and not red["span_device_s"].get(span):
                    say(f"FAIL: {len(calls)} calls crossed the encoder "
                        f"boundary and the trace has no device time under "
                        f"{span}: the kernels' rooflines cannot be read")
                    return 4
            ctx = {
                "window_s": t_done - t_open, "ops": ops, "phases": phases,
                "tap": tap, "trace": red, "config": cfg,
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
