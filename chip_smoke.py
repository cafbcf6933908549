#!/usr/bin/env python3
"""chip_smoke.py — does lizardfs-tpu still start on the chip?

    python3 chip_smoke.py            # on a TPU host; exit 0 = every leg OK

Drives the main path once through the entry points a user calls, at
BASELINE.json config 3's geometry (goal $ec(8,4), 64 KiB blocks, 64 MiB
chunks), then the kernels, then (four or more devices) the mesh:

  leg 1  cluster   one master + 13 chunkservers as real processes
                   (`python -m lizardfs_tpu.master cfg`, default
                   settings, native data plane, native library rebuilt
                   from source first), a `Client` in this process with
                   its encoder resolved to "tpu": write 1 GiB + one
                   odd-length file, read both back cold, SIGKILL a
                   chunkserver holding data parts, read both back
                   degraded (recover on the device), wait for the
                   master to report full redundancy again, and check no
                   child process maps libtpu.
  leg 6  kernels   every ChunkEncoder entry point and every fused
                   kernel configuration through get_encoder("tpu") at
                   64 MiB-chunk size, no interpret mode, byte-for-byte
                   against the numpy golden codec. Run a second time in
                   a fresh process, which must find every program in
                   the persistent compilation cache.
  leg 7  mesh      ec(32,8) wide-stripe encode+CRC and kill-one-part
                   reconstruct over four devices; skipped, and said so,
                   on a one-device host.

(The leg numbers are the issue's that introduced this file.)

Exits non-zero, printing no result line, when jax reports no TPU, when
run outside a checkout, or when any leg fails; nothing here catches a
device, compile or kernel error and carries on. Wall times printed are
smoke timings, not metrics. The last stdout line of a passing run is
one JSON object naming the device as jax reports it.

One process owns the chip: this launcher never imports jax; it runs the
legs in a worker process (the client, which owns the chip), then the
fresh-process cache pass in a second worker after the first has exited.
Every daemon is exec'd with JAX_PLATFORMS=cpu.

`--dry-run-cpu` is for tests/test_chip_smoke.py only: the same legs at
toy size on the CPU platform (the same cluster, 10 MB of files, small
kernel blocks, Pallas interpret=True passed by name). It prints
"DRY RUN (cpu) — not a chip result" and never a result line.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
MIB = 2**20

# the deployment: goal $ec(8,4) (goal 12 of the harness's goals.cfg) on
# k + m chunkservers plus one spare for the rebuilt parts
K, M, GOAL, N_CS = 8, 4, 12, 13

# what a user of this deployment would call real (leg sizes), and the
# toy sizes the tier-1 dry run uses to keep this file from rotting
REAL = dict(
    big_bytes=16 * 64 * MIB,            # 16 full chunks = 16x the BlockCache
    odd_bytes=100 * MIB + 47_008,       # tail chunk, off-stripe length
    block=64 * 1024, chunk_bytes=64 * MIB,
    mesh=dict(),                        # dryrun_multichip's own defaults
    mesh_part_bytes=2 * MIB,            # 32 parts = one 64 MiB logical chunk
    rebuild_bound_s=420.0,
)
TOY = dict(
    big_bytes=9 * MIB,                  # over the write pipeline's 8 MiB floor
    odd_bytes=1 * MIB + 47_008,
    block=4096, chunk_bytes=128 * 1024,
    mesh=dict(block_size=4096, min_logical_mib=1),
    mesh_part_bytes=16 * 1024,
    rebuild_bound_s=90.0,
)


def say(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def leg(name: str):
    """One OK/FAIL line per leg; a failure propagates (fail fast)."""
    notes: list[str] = []
    t0 = time.monotonic()
    try:
        yield notes.append
    except BaseException as e:
        say(f"FAIL {name}: {type(e).__name__}: {e}")
        raise
    detail = f" — {'; '.join(notes)}" if notes else ""
    say(f"OK   {name}{detail} [{time.monotonic() - t0:.1f}s smoke timing]")


# --- launcher (no jax in this process) ---------------------------------


def launcher(args) -> int:
    if not os.path.isdir(os.path.join(HERE, "lizardfs_tpu")):
        say("FAIL: chip_smoke.py is not inside a lizardfs-tpu checkout "
            f"(no {HERE}/lizardfs_tpu)")
        return 2
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    work = tempfile.mkdtemp(prefix="lizardfs_chip_smoke_")
    deadline = time.monotonic() + 1150.0
    groups: list[int] = []
    try:
        results = {}
        for mode in ("cold", "warm"):
            out = os.path.join(work, f"{mode}.json")
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--worker", mode, "--work", work, "--result", out]
            if args.dry_run_cpu:
                cmd.append("--dry-run-cpu")
            # own session: whatever the worker leaves behind (it stops
            # its daemons itself) dies with the group below
            proc = subprocess.Popen(cmd, start_new_session=True)
            groups.append(proc.pid)
            try:
                rc = proc.wait(timeout=max(deadline - time.monotonic(), 1.0))
            except subprocess.TimeoutExpired:
                say(f"FAIL: {mode} pass exceeded the smoke's time limit")
                return 3
            if rc != 0:
                say(f"FAIL: {mode} pass exited {rc}")
                return rc if 0 < rc < 126 else 1
            with open(out) as f:
                results[mode] = json.load(f)
        # a backend_compile event is one program asked of the backend:
        # compiled on a persistent-cache miss, loaded on a hit
        for mode, r in results.items():
            say(f"backend compilations, {mode} pass: {r['programs']} "
                f"programs, {r['compile_s']:.1f}s; persistent cache "
                f"{r['cache_misses']} misses (compiled), "
                f"{r['cache_hits']} hits (loaded)")
        cold, warm = results["cold"], results["warm"]
        say(f"leg 1 alone: {cold['leg1_programs']} distinct programs "
            "compiled inside client writes and reads")
        if warm["cache_misses"] or not warm["cache_hits"]:
            say("FAIL: the fresh-process pass compiled; the persistent "
                f"cache at {cold['cache_dir']} did not serve it")
            return 4
        if args.dry_run_cpu:
            say("DRY RUN (cpu) — not a chip result")
            return 0
        say(json.dumps({"ok": True, "device": cold["device"]}))
        return 0
    finally:
        for pgid in groups:
            with contextlib.suppress(ProcessLookupError, PermissionError):
                os.killpg(pgid, signal.SIGKILL)
        shutil.rmtree(work, ignore_errors=True)


# --- worker: the process that owns the chip ----------------------------


class CompileCounter:
    """Programs this process asked the backend for, and how the
    persistent cache answered, from jax.monitoring (instrumentation of
    the smoke, not of the product)."""

    def __init__(self):
        from jax import monitoring

        self.programs = 0
        self.compile_s = 0.0
        self.events = {"cache_misses": 0, "cache_hits": 0}
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.compile_s += seconds

    def _event(self, event: str, **_kw) -> None:
        key = event.rpartition("/")[2]
        if event.startswith("/jax/compilation_cache/") and key in self.events:
            self.events[key] += 1


def worker(args) -> int:
    dry = args.dry_run_cpu
    size = TOY if dry else REAL
    if dry:
        # the virtual 8-device CPU mesh of tests/conftest.py, so the
        # mesh leg runs too; set before jax makes its CPU client
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
        say("DRY RUN (cpu) — not a chip result")
    import importlib.metadata

    import jax

    devices = jax.devices()
    dev = devices[0]
    say(f"jax {jax.__version__} libtpu "
        f"{importlib.metadata.version('libtpu')} platform={dev.platform} "
        f"device_kind={dev.device_kind!r} count={len(devices)} "
        f"[{args.worker} pass]")
    if not dry and dev.platform != "tpu":
        say(f"FAIL: no TPU visible — jax reports platform={dev.platform} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
            "nothing was built or launched")
        return 2
    counter = CompileCounter()
    from lizardfs_tpu.runtime.jaxcache import configure_compile_cache

    cache_dir = configure_compile_cache()
    say(f"compile cache: {cache_dir}")

    if args.worker == "cold":
        with leg("native library built from source") as note:
            # -B: rebuild even if a stale .so rode along in the copy;
            # the dry run only builds what is missing (the test process
            # has the library mapped)
            make = ["make", "-C", os.path.join(HERE, "native")]
            subprocess.run(make if dry else make + ["-B"], check=True,
                           stdout=subprocess.DEVNULL)
            from lizardfs_tpu.core import native

            if not native.available():
                raise RuntimeError("libec_native.so built but not loadable")
            note("core.native.available()")

    enc = device_encoder(dry)
    say(f"encoder={enc.name} device={enc.device}")
    leg1_programs = 0
    if args.worker == "cold":
        asyncio.run(leg_cluster(size, enc, args.work))
        leg1_programs = counter.programs
    leg_kernels(size, enc, dry)
    if args.worker == "cold":
        if len(devices) >= 4:
            leg_mesh(size, dry)
        else:
            say(f"SKIP mesh: {len(devices)} device, mesh leg needs 4")
    with open(args.result, "w") as f:
        json.dump({
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devices)},
            "programs": counter.programs, "compile_s": counter.compile_s,
            "leg1_programs": leg1_programs, "cache_dir": cache_dir,
            **counter.events,
        }, f)
    return 0


def device_encoder(dry: bool):
    if dry:
        from lizardfs_tpu.core.encoder import TpuChunkEncoder

        return TpuChunkEncoder(force_cpu=True, interpret=True)
    from lizardfs_tpu.core.encoder import get_encoder

    return get_encoder("tpu")  # by name: never the auto resolution


# --- leg 1: the served path --------------------------------------------


def make_data(offset: int, size: int):
    """utils/data_generator.generate, in pieces (its index temporaries
    are 8 bytes a byte)."""
    import numpy as np

    from lizardfs_tpu.utils import data_generator

    out = np.empty(size, dtype=np.uint8)
    for a in range(0, size, 16 * MIB):
        b = min(a + 16 * MIB, size)
        out[a:b] = data_generator.generate(offset + a, b - a)
    return out


async def leg_cluster(size: dict, enc, work: str) -> None:
    import numpy as np

    from lizardfs_tpu.client.client import Client
    from lizardfs_tpu.constants import MFSCHUNKSIZE
    from lizardfs_tpu.core import geometry
    from lizardfs_tpu.core.conn_pool import GLOBAL_POOL
    from lizardfs_tpu.tools.chaos import ChaosCluster, admin

    seed = 21
    # count what crosses the encoder boundary (on the instance: the
    # client and its read plans call through it)
    calls = {"encode": 0, "encode_bytes": 0, "encode_into": 0,
             "recover": 0, "recover_bytes": 0, "recover_shapes": set()}
    encode, encode_into, recover = enc.encode, enc.encode_into, enc.recover

    def counted_encode(k_, m_, data_parts):
        calls["encode"] += 1
        calls["encode_bytes"] += sum(len(p) for p in data_parts)
        return encode(k_, m_, data_parts)

    def counted_encode_into(k_, m_, data_parts, out):
        calls["encode_into"] += 1  # the pipelined write's entry; it
        return encode_into(k_, m_, data_parts, out)  # lands in encode

    def counted_recover(k_, m_, parts, wanted):
        got = recover(k_, m_, parts, wanted)
        calls["recover"] += 1
        calls["recover_bytes"] += sum(len(v) for v in got.values())
        calls["recover_shapes"].update(len(v) for v in got.values())
        return got

    enc.encode, enc.encode_into = counted_encode, counted_encode_into
    enc.recover = counted_recover
    # the repo's real-process harness, every daemon exec'd with
    # JAX_PLATFORMS=cpu; stock: configs hold only paths and ports
    cluster = ChaosCluster(work, n_cs=N_CS, stock=True)
    client = victim = None
    try:
        with leg(f"cluster up: 1 master + {N_CS} chunkservers "
                 "(real processes, default settings)"):
            await cluster.start()
            client = Client("127.0.0.1", cluster.master_port, encoder=enc)
            await asyncio.wait_for(client.connect(info="chip-smoke"), 60.0)

        files = {}
        offset = seed
        for name, nbytes in (("big", size["big_bytes"]),
                             ("odd", size["odd_bytes"])):
            files[name] = [0, make_data(offset, nbytes)]
            offset += nbytes
        written = sum(len(d) for _, d in files.values())
        say(f"data: {written} bytes from data_generator.generate, "
            f"seed {seed}")

        with leg(f"write at goal ec({K},{M})") as note:
            for name, entry in files.items():
                f = await client.create(1, name)
                await client.setgoal(f.inode, GOAL)
                await asyncio.wait_for(
                    client.write_file(f.inode, entry[1]), 600.0
                )
                entry[0] = f.inode
            if not calls["encode_into"]:
                raise RuntimeError("no segment went through encode_into")
            if calls["encode_bytes"] < written:
                raise RuntimeError(
                    f"encode saw {calls['encode_bytes']} of {written} bytes"
                )
            note(f"{written} bytes in {len(files)} files; "
                 f"{calls['encode']} encode calls on {enc.device} "
                 f"({calls['encode_into']} via encode_into) over "
                 f"{calls['encode_bytes']} data bytes")

        async def read_back(what: str) -> int:
            # cold: nothing cached in the client, no pooled connection.
            # Both files at once: after the kill below, the first chunk
            # reads of BOTH files then locate their parts before any
            # rebuild can have finished
            for inode, _ in files.values():
                client.cache.invalidate(inode)
            GLOBAL_POOL.close_all()
            got = await asyncio.wait_for(asyncio.gather(
                *(client.read_file(inode) for inode, _ in files.values())
            ), 600.0)
            for (name, (_, data)), read in zip(files.items(), got):
                if not np.array_equal(np.frombuffer(read, np.uint8), data):
                    raise RuntimeError(f"{what}: {name} differs from "
                                       "what was written")
            return sum(len(read) for read in got)

        with leg("read both back cold, byte-identical") as note:
            note(f"{await read_back('cold read')} bytes")

        # the victim: holds a data part of the big file's first chunk
        # AND of the odd file's tail chunk (two K-of-N_CS sets, which
        # meet because 2K > N_CS) — the chunks both reads start with,
        # so both shapes are recovered whatever the rebuild's pace —
        # and, among those, the most data parts overall
        nchunks = {n: -(-len(d) // MFSCHUNKSIZE) for n, (_, d) in files.items()}
        holds: dict[int, set[tuple[str, int]]] = {}
        for name, (inode, _) in files.items():
            for ci in range(nchunks[name]):
                info = await client.chunk_info(inode, ci)
                if len(info.locations) != K + M:
                    raise RuntimeError(
                        f"{name} chunk {ci}: {len(info.locations)} parts"
                    )
                for loc in info.locations:
                    if geometry.ChunkPartType.from_id(loc.part_id).is_data:
                        holds.setdefault(loc.addr.port, set()).add((name, ci))
        must = {("big", 0), ("odd", nchunks["odd"] - 1)}
        port = max((p for p in holds if must <= holds[p]),
                   key=lambda p: len(holds[p]))
        # part locations name the native data plane's port; the master
        # knows which control (LISTEN_PORT) port that server has
        info = json.loads((await admin(cluster.master_port, "info")).json)
        listen = next(s["port"] for s in info["chunkservers"]
                      if port in (s["data_port"], s["port"]))
        victim = f"cs{cluster.cs_ports.index(listen)}"

        with leg(f"SIGKILL {victim}, read both back degraded, "
                 "byte-identical") as note:
            cluster.kill9(victim)
            before = calls["recover"]
            total = await read_back("degraded read")
            if len(calls["recover_shapes"]) < 2:
                raise RuntimeError(
                    "recover saw part lengths "
                    f"{sorted(calls['recover_shapes'])}: not both the "
                    "full chunk's and the tail chunk's"
                )
            note(f"{total} bytes; {victim} held data parts of "
                 f"{len(holds[port])} chunks; {calls['recover'] - before} "
                 f"recover calls on {enc.device} rebuilt "
                 f"{calls['recover_bytes']} bytes in part lengths "
                 f"{sorted(calls['recover_shapes'])} (chunks read after "
                 "their rebuild landed were whole again)")

        with leg("master reports full redundancy again") as note:
            want = sum(nchunks.values())
            deadline = time.monotonic() + size["rebuild_bound_s"]
            while True:
                doc = json.loads(
                    (await admin(cluster.master_port, "chunks-health")).json
                )
                if (doc["healthy"] == want and not doc["endangered"]
                        and not doc["lost"]):
                    break
                if time.monotonic() > deadline:
                    raise RuntimeError(f"not within the bound: {doc}")
                await asyncio.sleep(1.0)
            for name, (inode, _) in files.items():
                for ci in range(nchunks[name]):
                    info = await client.chunk_info(inode, ci)
                    ports = {loc.addr.port for loc in info.locations}
                    if len(ports) != K + M or port in ports:
                        raise RuntimeError(f"{name} chunk {ci}: parts on "
                                           f"{sorted(ports)}")
            status = json.loads(
                (await admin(cluster.master_port, "rebuild-status")).json
            )
            note(f"{want} chunks healthy, {K + M} parts each on live "
                 f"servers; {status.get('completed')} rebuilds by the "
                 "chunkservers' replicators (CPU)")

        with leg("no child process maps libtpu") as note:
            live = {n: p for n, p in cluster.procs.items() if n != victim}
            for name, p in live.items():
                if p.poll() is not None:
                    raise RuntimeError(f"{name} exited {p.returncode}")
                with open(f"/proc/{p.pid}/maps") as f:
                    if "libtpu" in f.read():
                        raise RuntimeError(f"{name} maps libtpu")
            note(f"{len(live)} daemons checked in /proc/<pid>/maps")
    except BaseException:
        for name, p in cluster.procs.items():
            if name != victim and p.poll() is not None:
                with open(os.path.join(work, f"{name}.log")) as f:
                    say(f"{name} exited {p.returncode}: {f.read()[-1500:]}")
        raise
    finally:
        del enc.encode, enc.encode_into, enc.recover
        if client is not None:
            with contextlib.suppress(Exception):
                await asyncio.wait_for(client.close(), 10.0)
        cluster.stop()


# --- leg 6: every kernel, against the golden codec ---------------------


def leg_kernels(size: dict, enc, dry: bool) -> None:
    import jax
    import numpy as np

    from lizardfs_tpu.core.encoder import CpuChunkEncoder
    from lizardfs_tpu.ops import gf256, jax_ec, pallas_ec

    gold = CpuChunkEncoder()
    bs, chunk = size["block"], size["chunk_bytes"]
    rng = np.random.default_rng(6)
    interp = dict(interpret=True) if dry else {}

    def same(got, want) -> None:
        for g, w in zip(got, want, strict=True):
            if not np.array_equal(np.asarray(g), np.asarray(w)):
                raise RuntimeError("differs from the golden codec")

    chunks = {}
    for k, m in ((8, 4), (3, 2)):
        nb = -(-chunk // (k * bs))  # blocks per part of one full chunk
        data = rng.integers(0, 256, size=(k, nb * bs), dtype=np.uint8)
        want = gold.encode_with_checksums(k, m, data, block_size=bs)
        chunks[(k, m)] = (data, want)
        with leg(f"encode_with_checksums ec({k},{m}), "
                 f"{data.nbytes} B, {bs} B blocks"):
            same(enc.encode_with_checksums(k, m, data, block_size=bs), want)
        bigm = jax.device_put(jax_ec.encoding_bitmatrix(k, m), enc.device)
        on_dev = jax.device_put(data, enc.device)
        for tag, cfg in (("BIG_TILE_CONFIG", pallas_ec.BIG_TILE_CONFIG),
                         ("ROOFLINE_CONFIG", pallas_ec.ROOFLINE_CONFIG)):
            with leg(f"fused_encode_crc ec({k},{m}) {tag}"):
                same(pallas_ec.fused_encode_crc(
                    bigm, on_dev, bs, **cfg, **interp), want)

    data, (parity, dcrc, pcrc) = chunks[(8, 4)]
    k, m = 8, 4
    with leg(f"checksum over {data.size // bs} x {bs} B blocks"):
        same([enc.checksum(data.reshape(-1, bs))], [dcrc.reshape(-1)])
    allparts = np.concatenate([data, parity])
    allcrcs = np.concatenate([dcrc, pcrc])
    for lost in ([2], [0, 3, 5, 9]):
        have = {i: allparts[i] for i in range(k + m) if i not in lost}
        with leg(f"recover {len(lost)} lost part(s) of ec({k},{m})"):
            got = enc.recover(k, m, have, lost)
            same([got[i] for i in lost], [allparts[i] for i in lost])
        used, _ = gf256.recovery_selection(k, m, list(have), lost)
        big_rec = jax_ec.recovery_bitmatrix(k, m, tuple(used), tuple(lost))
        with leg(f"pallas fused_decode_verify, {len(lost)} lost"):
            rec, crcs, ok = pallas_ec.fused_decode_verify(
                jax.device_put(big_rec, enc.device),
                jax.device_put(allparts[list(used)], enc.device),
                allcrcs[lost], bs, **interp,
            )
            same([rec, crcs], [allparts[lost], allcrcs[lost]])
            if not np.asarray(ok).all():
                raise RuntimeError("CRC verify flagged a good block")
    with leg(f"pallas encode ec({k},{m})"):
        same([pallas_ec.encode(
            jax.device_put(jax_ec.encoding_bitmatrix(k, m), enc.device),
            jax.device_put(data, enc.device), **interp)], [parity])
    xor_parts = list(chunks[(3, 2)][0])
    with leg(f"xor_parity xor3, {len(xor_parts[0])} B parts"):
        same([enc.xor_parity(xor_parts)], [gold.xor_parity(xor_parts)])


# --- leg 7: four chips -------------------------------------------------


def leg_mesh(size: dict, dry: bool) -> None:
    import numpy as np

    import __graft_entry__ as graft
    from lizardfs_tpu.core.encoder import CpuChunkEncoder

    with leg("dryrun_multichip(4): wide-stripe encode+CRC and "
             "kill-one-part reconstruct") as note:
        got = graft.dryrun_multichip(4, **size["mesh"])
        note(f"ec({got['k']},{got['m']}), {got['logical_bytes']} B logical "
             f"chunk; shards on {got['platform']} devices "
             f"{got['shard_devices']}")
    if dry:
        from lizardfs_tpu.core.encoder import ShardedTpuChunkEncoder

        enc = ShardedTpuChunkEncoder(force_cpu=True)
    else:
        from lizardfs_tpu.core.encoder import get_encoder

        enc = get_encoder("auto")
        if enc.name != "sharded":
            raise RuntimeError(f"auto resolved to {enc.name} on this host")
    k, m, kill = 32, 8, 16
    with leg(f"{type(enc).__name__}.recover ec({k},{m}), part {kill} "
             "killed") as note:
        gold = CpuChunkEncoder()
        data = np.random.default_rng(7).integers(
            0, 256, size=(k, size["mesh_part_bytes"]), dtype=np.uint8)
        allparts = list(data) + gold.encode(k, m, list(data))
        have = {i: p for i, p in enumerate(allparts) if i != kill}
        rebuilt = enc.recover(k, m, have, [kill])[kill]
        if not np.array_equal(rebuilt, allparts[kill]):
            raise RuntimeError("differs from the golden codec")
        note(f"{data.nbytes} B logical chunk; single-chip programs of "
             f"this encoder stay on {enc.device}")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--dry-run-cpu", action="store_true",
                   help="tests only: toy sizes on the CPU platform")
    p.add_argument("--worker", choices=("cold", "warm"),
                   help=argparse.SUPPRESS)
    p.add_argument("--work", help=argparse.SUPPRESS)
    p.add_argument("--result", help=argparse.SUPPRESS)
    args = p.parse_args()
    return worker(args) if args.worker else launcher(args)


if __name__ == "__main__":
    sys.exit(main())
