"""Master metadata-plane storm bench: locate QPS at fleet scale.

The instrument for ISSUE 7's tentpole: every open/lookup/locate from
"millions of users" funnels through the master, so this bench spawns a
REAL primary (+ optionally a shadow read replica) as separate
processes, bulk-loads a synthetic namespace (``synth-populate`` admin
command — one changelog op per 10k files, so the shadow converges on
the same million-inode tree), registers a wave of real-socket
chunkserver connections (heartbeat fan-in / registration-ingest cost),
and then hammers the metadata plane with locate/getattr/lookup load
from separate WORKER PROCESSES (the measuring side must not share the
master's GIL).

A/B topology: the same storm runs primary-only and primary+shadow
(half the workers route reads to the replica via LZ_SHADOW_READS);
the aggregate locate QPS ratio is the tentpole's acceptance number
(target >= 1.8x on a box with cores to spare).

    python benches/bench_master_storm.py [--files 100000] [--servers 1000]
        [--secs 5] [--workers N] [--no-replica-arm]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from lizardfs_tpu.core import geometry  # noqa: E402
from lizardfs_tpu.proto import framing  # noqa: E402
from lizardfs_tpu.proto import messages as m  # noqa: E402
from lizardfs_tpu.proto import status as st  # noqa: E402

# wire part id of a standard-slice part 0 (what a real chunkserver
# reports for a plain replicated chunk)
STD_PART_ID = geometry.ChunkPartType(
    geometry.SliceType(geometry.STANDARD), 0
).id


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


async def _admin(port: int, command: str, payload: str = "{}",
                 timeout: float = 600.0):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        await framing.send_message(
            writer, m.AdminCommand(req_id=1, command=command, json=payload)
        )
        return await asyncio.wait_for(framing.read_message(reader), timeout)
    finally:
        writer.close()


async def _wait_port(port: int, timeout: float = 30.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            _, w = await asyncio.open_connection("127.0.0.1", port)
            w.close()
            return
        except (ConnectionError, OSError):
            await asyncio.sleep(0.1)
    raise RuntimeError(f"port {port} never came up")


def _spawn_master(tmp: str, name: str, port: int,
                  active_port: int | None = None,
                  extra_lines: list[str] | None = None,
                  env_extra: dict | None = None) -> subprocess.Popen:
    cfg = os.path.join(tmp, f"{name}.cfg")
    lines = [
        f"DATA_PATH = {tmp}/{name}",
        f"LISTEN_PORT = {port}",
        "HEALTH_INTERVAL = 0.5",
        "IMAGE_INTERVAL = 3600",
        "LOG_LEVEL = WARNING",
    ]
    if active_port is not None:
        lines += [
            "PERSONALITY = shadow",
            f"ACTIVE_MASTER = 127.0.0.1:{active_port}",
        ]
    lines += list(extra_lines or [])
    with open(cfg, "w") as f:
        f.write("\n".join(lines) + "\n")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               **(env_extra or {}))
    return subprocess.Popen(
        [sys.executable, "-m", "lizardfs_tpu.master", cfg],
        stdout=open(os.path.join(tmp, f"{name}.log"), "wb"),
        stderr=subprocess.STDOUT, env=env,
    )


# --------------------------------------------------------------------------
# synthetic chunkserver wave: registration ingest + heartbeat fan-in
# --------------------------------------------------------------------------


async def _register_cs_wave(
    port: int, n: int, parts_each: int, base_chunk: int,
    heartbeat_s: float = 2.0,
) -> tuple[list, float]:
    """Open ``n`` real chunkserver registrations (each reporting
    ``parts_each`` synthetic parts) against the master and keep them
    heartbeating. Returns (writers, ingest wall seconds)."""
    writers = []
    t0 = time.perf_counter()

    async def one(i: int):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        chunks = [
            m.ChunkPartInfo(chunk_id=base_chunk + ((i * 17 + j) % parts_each),
                            version=1, part_id=STD_PART_ID)
            for j in range(parts_each)
        ] if parts_each else []
        await framing.send_message(writer, m.CstomaRegister(
            req_id=1, addr=m.Addr(host="127.0.0.1", port=40000 + i),
            label="_", chunks=chunks, total_space=1 << 40, used_space=0,
            data_port=0,
        ))
        reply = await framing.read_message(reader)
        assert reply.status == st.OK, f"cs register refused: {reply.status}"
        writers.append((reader, writer, reply.cs_id))

    # bounded concurrency: the point is master-side ingest cost, not
    # how many sockets this driver can dial at once
    sem = asyncio.Semaphore(64)

    async def guarded(i):
        async with sem:
            await one(i)

    await asyncio.gather(*(guarded(i) for i in range(n)))
    ingest_s = time.perf_counter() - t0

    async def heartbeats():
        k = 0
        while True:
            await asyncio.sleep(heartbeat_s / max(len(writers), 1))
            if not writers:
                continue
            _, writer, cs_id = writers[k % len(writers)]
            k += 1
            try:
                framing.write_message(writer, m.CstomaHeartbeat(
                    req_id=2, cs_id=cs_id, total_space=1 << 40,
                    used_space=0, health_json="",
                ))
            except (ConnectionError, RuntimeError):
                pass

    hb_task = asyncio.ensure_future(heartbeats())
    return [(hb_task, writers)], ingest_s


# --------------------------------------------------------------------------
# worker process: the load generator
# --------------------------------------------------------------------------


async def _worker_main(args) -> None:
    from lizardfs_tpu.client.client import Client

    addrs = [tuple(a.rsplit(":", 1)) for a in args.addrs.split(",")]
    addrs = [(h, int(p)) for h, p in addrs]
    client = Client("", 0, master_addrs=addrs)
    await client.connect(info=f"storm{args.index}")
    base, files = args.base_inode, args.files
    dir_inode = args.dir_inode
    stop_at = time.monotonic() + args.secs
    ops = 0
    locates = 0
    lat: list[float] = []  # locate latencies only (the headline metric)
    rng = (args.index * 2654435761 + 12345) & 0xFFFFFFFF

    def nxt() -> int:
        nonlocal rng
        rng = (rng * 1103515245 + 12345) & 0x7FFFFFFF
        return rng

    async def conn_loop():
        nonlocal ops, locates
        while time.monotonic() < stop_at:
            inode = base + nxt() % files
            roll = nxt() % 10
            t0 = time.perf_counter()
            try:
                if roll < 7:
                    await client.chunk_info(inode, 0)
                    lat.append(time.perf_counter() - t0)
                    locates += 1
                elif roll < 9:
                    await client.getattr(inode)
                else:
                    await client.lookup(dir_inode, f"sf{inode}")
            except Exception:  # noqa: BLE001 — errors end the worker loudly
                raise
            ops += 1

    t0 = time.perf_counter()
    await asyncio.gather(*(conn_loop() for _ in range(args.conns)))
    wall = time.perf_counter() - t0
    lat.sort()
    # bounded sample for the parent's merged percentiles
    step = max(len(lat) // 500, 1)
    out = {
        "ops": ops, "locates": locates, "wall_s": wall,
        "lat_sample_ms": [round(v * 1e3, 3) for v in lat[::step]],
        "shadow_reads": 0.0, "stale_retries": 0.0,
    }
    s = client.metrics.series.get("shadow_reads")
    if s is not None:
        out["shadow_reads"] = s.total
        out["stale_retries"] = client.metrics.series[
            "shadow_stale_retries"
        ].total
    await client.close()
    print(json.dumps(out), flush=True)


def _spawn_worker(index: int, addrs: list[tuple[str, int]], secs: float,
                  conns: int, base_inode: int, files: int, dir_inode: int,
                  shadow_reads: bool, tmp: str) -> subprocess.Popen:
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               LZ_SHADOW_READS="1" if shadow_reads else "0")
    return subprocess.Popen(
        [
            sys.executable, os.path.abspath(__file__), "--worker",
            "--index", str(index),
            "--addrs", ",".join(f"{h}:{p}" for h, p in addrs),
            "--secs", str(secs), "--conns", str(conns),
            "--base-inode", str(base_inode), "--files", str(files),
            "--dir-inode", str(dir_inode),
        ],
        stdout=subprocess.PIPE,
        stderr=open(os.path.join(tmp, f"worker{index}.log"), "wb"),
        env=env,
    )


def _collect(procs: list[subprocess.Popen]) -> dict:
    total_ops = total_locates = 0
    wall = 0.0
    lats: list[float] = []
    shadow_reads = stale = 0.0
    for p in procs:
        out, _ = p.communicate(timeout=600)
        row = json.loads(out.decode().strip().splitlines()[-1])
        total_ops += row["ops"]
        total_locates += row["locates"]
        wall = max(wall, row["wall_s"])
        lats.extend(row["lat_sample_ms"])
        shadow_reads += row["shadow_reads"]
        stale += row["stale_retries"]
    lats.sort()

    def pct(p: float) -> float:
        if not lats:
            return 0.0
        return round(lats[min(int(len(lats) * p), len(lats) - 1)], 2)

    return {
        "ops_per_s": round(total_ops / wall, 1) if wall else 0.0,
        "locate_qps": round(total_locates / wall, 1) if wall else 0.0,
        "locate_p50_ms": pct(0.50),
        "locate_p99_ms": pct(0.99),
        "shadow_reads": int(shadow_reads),
        "stale_retries": int(stale),
    }


# --------------------------------------------------------------------------
# per-tenant QoS A/B: abuser vs victim under fair-share admission
# --------------------------------------------------------------------------

# the bench's tenant policy: the victim holds 3x the abuser's weight
# over a 300 locate/s class budget, so a flooding abuser is shed while
# the victim's paced load sits far inside its contended share
QOS_BENCH_CFG = json.dumps({
    "tenants": {
        "victim": {"weight": 3, "match": ["qos-victim*"]},
        "abuser": {"weight": 1, "match": ["qos-abuser*"]},
    },
    "rates": {"locate": 300},
})
QOS_VICTIM_P99_BOUND_MS = 250.0


async def _qos_worker_main(args) -> None:
    """Tenant worker: ``abuser`` floods locates as fast as the client
    admits them (sheds retried inside the client); ``victim`` paces at
    ``--rate`` and records per-op latency."""
    from lizardfs_tpu.client.client import Client

    host, _, port = args.addrs.rpartition(":")
    client = Client(host, int(port))
    await client.connect(info=args.info)
    inode = args.base_inode + (args.index % max(args.files, 1))
    lat: list[float] = []
    t0 = time.perf_counter()
    for i in range(args.count):
        op0 = time.perf_counter()
        await client.chunk_info(inode, 0)
        lat.append(time.perf_counter() - op0)
        if args.rate > 0:
            # paced arrivals: sleep out the remainder of this op's slot
            slot = (i + 1) / args.rate
            behind = slot - (time.perf_counter() - t0)
            if behind > 0:
                await asyncio.sleep(behind)
    wall = time.perf_counter() - t0
    lat.sort()
    step = max(len(lat) // 500, 1)
    out = {
        "ops": args.count, "wall_s": wall,
        "qps": round(args.count / wall, 1) if wall else 0.0,
        "p99_ms": round(lat[int(len(lat) * 0.99)] * 1e3, 2) if lat else 0.0,
        "lat_sample_ms": [round(v * 1e3, 3) for v in lat[::step]],
        "busy_waits": client.metrics.counter("qos_busy_waits").total,
    }
    await client.close()
    print(json.dumps(out), flush=True)


def _spawn_qos_worker(index: int, port: int, info: str, count: int,
                      rate: float, base_inode: int, files: int,
                      tmp: str) -> subprocess.Popen:
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [
            sys.executable, os.path.abspath(__file__), "--qos-worker",
            "--index", str(index), "--addrs", f"127.0.0.1:{port}",
            "--info", info, "--count", str(count), "--rate", str(rate),
            "--base-inode", str(base_inode), "--files", str(files),
        ],
        stdout=subprocess.PIPE,
        stderr=open(os.path.join(tmp, f"qosworker{index}.log"), "wb"),
        env=env,
    )


async def run_qos_ab(
    files: int = 2_000,
    abuser_ops: int = 600,
    victim_ops: int = 200,
    victim_rate: float = 25.0,
) -> dict:
    """The per-tenant split: the SAME abuser-flood + paced-victim storm
    runs twice — LZ_QOS=0 (pre-QoS behavior) and LZ_QOS=1 with the
    bench tenant policy — and the verdict is the victim's p99 with the
    abuser flooding, QoS on vs off. Returns one bench row dict."""
    row: dict = {
        "goal": "qos noisy neighbor", "files": files,
        "abuser_ops": abuser_ops, "victim_ops": victim_ops,
        "victim_rate": victim_rate,
    }
    for arm, qos_env in (("off", "0"), ("on", "1")):
        tmp = tempfile.mkdtemp(prefix=f"lizqos{arm}")
        port = _free_port()
        proc = None
        try:
            with open(os.path.join(tmp, "qos.cfg"), "w") as f:
                f.write(QOS_BENCH_CFG)
            proc = _spawn_master(
                tmp, "primary", port,
                extra_lines=[f"QOS_CFG = {tmp}/qos.cfg"],
                env_extra={"LZ_QOS": qos_env},
            )
            await _wait_port(port)
            reply = await _admin(port, "synth-populate", json.dumps({
                "files": files, "servers": 0, "copies": 1,
            }))
            assert reply.status == st.OK, reply.json
            pop = json.loads(reply.json)
            base_inode = pop["dir_inode"] + 1
            workers = [
                _spawn_qos_worker(0, port, "qos-abuser", abuser_ops,
                                  0.0, base_inode, files, tmp),
                _spawn_qos_worker(1, port, "qos-victim", victim_ops,
                                  victim_rate, base_inode, files, tmp),
            ]
            outs = []
            for p in workers:
                raw, _ = await asyncio.to_thread(p.communicate, None, 600)
                outs.append(json.loads(raw.decode().strip().splitlines()[-1]))
            row[arm] = {
                "abuser": outs[0], "victim": outs[1],
            }
        finally:
            if proc is not None and proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
            shutil.rmtree(tmp, ignore_errors=True)
    on_v = row["on"]["victim"]
    off_v = row["off"]["victim"]
    row["qos_ab"] = {
        "victim_p99_off_ms": off_v["p99_ms"],
        "victim_p99_on_ms": on_v["p99_ms"],
        "victim_qps_on": on_v["qps"],
        "abuser_qps_off": row["off"]["abuser"]["qps"],
        "abuser_qps_on": row["on"]["abuser"]["qps"],
        "abuser_busy_waits_on": row["on"]["abuser"]["busy_waits"],
        "victim_busy_waits_on": on_v["busy_waits"],
        "bound_ms": QOS_VICTIM_P99_BOUND_MS,
        "target_met": bool(
            on_v["p99_ms"] <= QOS_VICTIM_P99_BOUND_MS
            and on_v["busy_waits"] == 0
            and row["on"]["abuser"]["busy_waits"] > 0
        ),
    }
    return row


# --------------------------------------------------------------------------
# the orchestrated storm
# --------------------------------------------------------------------------


async def run_storm(
    files: int = 100_000,
    servers: int = 1_000,
    secs: float = 5.0,
    workers: int | None = None,
    conns: int = 4,
    real_cs: int = 128,
    parts_per_cs: int = 2_000,
    replica_arm: bool = True,
) -> dict:
    """Run the full storm; returns one bench row dict."""
    if workers is None:
        workers = max(min((os.cpu_count() or 2) - 1, 4), 2)
    tmp = tempfile.mkdtemp(prefix="lizstorm")
    primary_port, shadow_port = _free_port(), _free_port()
    procs: list[subprocess.Popen] = []
    row: dict = {
        "goal": "locate storm", "files": files, "servers": servers,
        "workers": workers, "conns": conns,
    }
    try:
        procs.append(_spawn_master(tmp, "primary", primary_port))
        await _wait_port(primary_port)
        if replica_arm:
            procs.append(
                _spawn_master(tmp, "shadow", shadow_port, primary_port)
            )
            await _wait_port(shadow_port)

        # --- populate: one admin call, batched commits master-side ----
        t0 = time.perf_counter()
        reply = await _admin(primary_port, "synth-populate", json.dumps({
            "files": files, "servers": servers, "copies": 1,
        }))
        assert reply.status == st.OK, reply.json
        pop = json.loads(reply.json)
        row["populate_s"] = round(time.perf_counter() - t0, 2)
        dir_inode = pop["dir_inode"]
        base_inode = dir_inode + 1  # batches allocate contiguously after
        version = pop["version"]

        # --- heartbeat fan-in: real-socket registration wave ----------
        stalls_before = json.loads(
            (await _admin(primary_port, "health")).json
        )["master"].get("loop_stalls", 0)
        keepers, ingest_s = await _register_cs_wave(
            primary_port, real_cs, min(parts_per_cs, files),
            base_chunk=pop["chunks"] - files + 1,
        )
        row["cs_ingest"] = {
            "real_cs": real_cs, "parts_each": min(parts_per_cs, files),
            "ingest_s": round(ingest_s, 2),
        }

        # --- shadow catch-up / replication lag ------------------------
        if replica_arm:
            caught = False
            h = {"summary": {}}
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                try:
                    h = json.loads(
                        (await _admin(primary_port, "health")).json
                    )
                    shadows = h.get("shadows", [])
                    if shadows and all(
                        s["version"] >= version for s in shadows
                    ):
                        caught = True
                        break
                except (ConnectionError, OSError):
                    pass
                await asyncio.sleep(0.25)
            row["shadow_caught_up"] = caught
            row["shadow_lag"] = h["summary"].get("shadow_lag_max", -1)

        # --- arm A: primary-only ---------------------------------------
        wprocs = [
            _spawn_worker(
                i, [("127.0.0.1", primary_port)], secs, conns,
                base_inode, files, dir_inode, shadow_reads=False, tmp=tmp,
            )
            for i in range(workers)
        ]
        row["primary_only"] = await asyncio.to_thread(_collect, wprocs)

        # --- arm B: primary + shadow (half the workers replica-route) --
        if replica_arm:
            addrs = [("127.0.0.1", primary_port), ("127.0.0.1", shadow_port)]
            wprocs = [
                _spawn_worker(
                    100 + i,
                    addrs if i % 2 else [("127.0.0.1", primary_port)],
                    secs, conns, base_inode, files, dir_inode,
                    shadow_reads=bool(i % 2), tmp=tmp,
                )
                for i in range(workers)
            ]
            row["with_replica"] = await asyncio.to_thread(_collect, wprocs)
            a = row["primary_only"]["locate_qps"]
            b = row["with_replica"]["locate_qps"]
            row["locate_qps_x"] = round(b / a, 2) if a else 0.0
            row["locate_qps_target_x"] = 1.8
            row["locate_qps_target_met"] = bool(
                row["locate_qps_x"] >= 1.8
            )

        # --- post-storm master health ---------------------------------
        h = json.loads((await _admin(primary_port, "health")).json)
        row["loop_stalls"] = (
            h["master"].get("loop_stalls", 0) - stalls_before
        )
        for task, writers in keepers:
            task.cancel()
            for _, w, _cs in writers:
                w.close()
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        shutil.rmtree(tmp, ignore_errors=True)
    return row


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--files", type=int, default=100_000)
    p.add_argument("--servers", type=int, default=1_000)
    p.add_argument("--secs", type=float, default=5.0)
    p.add_argument("--workers", type=int, default=None)
    p.add_argument("--conns", type=int, default=4)
    p.add_argument("--real-cs", type=int, default=128)
    p.add_argument("--no-replica-arm", action="store_true")
    p.add_argument("--json", action="store_true")
    p.add_argument("--qos", action="store_true",
                   help="run the per-tenant QoS A/B instead of the "
                        "locate storm")
    # worker mode (internal)
    p.add_argument("--worker", action="store_true")
    p.add_argument("--qos-worker", action="store_true")
    p.add_argument("--index", type=int, default=0)
    p.add_argument("--addrs", default="")
    p.add_argument("--base-inode", type=int, default=0)
    p.add_argument("--dir-inode", type=int, default=0)
    p.add_argument("--info", default="qos-abuser")
    p.add_argument("--count", type=int, default=100)
    p.add_argument("--rate", type=float, default=0.0)
    args = p.parse_args(argv)
    if args.worker:
        asyncio.run(_worker_main(args))
        return 0
    if args.qos_worker:
        asyncio.run(_qos_worker_main(args))
        return 0
    if args.qos:
        row = asyncio.run(run_qos_ab())
        q = row["qos_ab"]
        if args.json:
            print(json.dumps(row, indent=2))
        else:
            print(f"victim p99: off {q['victim_p99_off_ms']} ms -> on "
                  f"{q['victim_p99_on_ms']} ms (bound {q['bound_ms']}); "
                  f"abuser {q['abuser_qps_off']} -> {q['abuser_qps_on']} "
                  f"q/s, {q['abuser_busy_waits_on']:.0f} busy waits; "
                  f"target_met={q['target_met']}")
        return 0
    row = asyncio.run(run_storm(
        files=args.files, servers=args.servers, secs=args.secs,
        workers=args.workers, conns=args.conns, real_cs=args.real_cs,
        replica_arm=not args.no_replica_arm,
    ))
    if args.json:
        print(json.dumps(row, indent=2))
    else:
        a = row.get("primary_only", {})
        b = row.get("with_replica", {})
        print(f"populate {row['files']} files: {row['populate_s']}s;"
              f" cs ingest {row['cs_ingest']['ingest_s']}s"
              f" ({row['cs_ingest']['real_cs']} servers)")
        print(f"primary-only : {a.get('locate_qps', 0):>9.1f} locate/s  "
              f"p99 {a.get('locate_p99_ms', 0)} ms")
        if b:
            print(f"with replica : {b.get('locate_qps', 0):>9.1f} locate/s  "
                  f"p99 {b.get('locate_p99_ms', 0)} ms  "
                  f"({row.get('locate_qps_x', 0)}x, "
                  f"shadow served {b.get('shadow_reads', 0)})")
        print(f"loop stalls during storm: {row.get('loop_stalls', 0)};"
              f" shadow lag {row.get('shadow_lag', '-')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
