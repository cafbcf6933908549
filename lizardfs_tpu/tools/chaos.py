"""Chaos harness: seeded fault schedules against REAL process clusters.

    python -m lizardfs_tpu.tools.chaos --schedule bitflip-read --seed 42
    python -m lizardfs_tpu.tools.chaos --all --seeds 1,2,3

Each schedule boots a multi-process cluster (master [+ shadow] + N
chunkservers as subprocesses — the reference's system-test tier,
tests/tools/lizardfs.sh), injects faults mid-traffic (SIGKILL, rules
armed over the admin channel into runtime/faults.py, frame partitions),
and asserts the standing invariants:

  * byte identity — every read returns exactly what was written;
  * bounded time — the whole schedule completes inside its budget
    (a wedged session is a failure, not a hang);
  * rebuild convergence — injected damage drains through the
    RebuildEngine;
  * observability — health/`faults` output NAMES the injected fault.

Determinism: the seed steers every choice (victim selection, kill
timing, fault-rule seeds) through one ``random.Random(seed)``, and the
armed rules' own draws are seeded server-side, so a failing run replays
exactly:  the driver prints the seed + replay command on failure.

Schedules:
  kill-write     SIGKILL a chunkserver mid-windowed-write
  bitflip-read   flip a stored ec(3,2) part bit under a live read
                 (client CRC-rejects, decodes, reports; master rebuilds)
  stall-acks     delay write acks on one chunkserver (adaptive window
                 back-pressure; no wedged sessions)
  shadow-stale   partition the chunkserver->shadow mirror plane so the
                 shadow serves stale locates; clients recover through
                 the primary
  s3-multipart   SIGKILL a chunkserver mid-multipart-upload; the S3
                 gateway completes byte-identically or fails cleanly
                 (no torn object visible to GET)
  noisy-neighbor one tenant floods the master's locate plane while a
                 victim tenant keeps reading: fair-share admission
                 sheds ONLY the abuser (BUSY, retried — never errored),
                 the victim's p99 and goodput hold within bounds, and
                 health/metrics NAME the throttled tenant
  hot-spot       one file goes viral: the heat loop goal-boosts the hot
                 chunk (real extra replicas via the RebuildEngine),
                 read p99 holds through the storm with byte identity,
                 and demotion lands once the heat decays
  kill-primary   SIGKILL the ACTIVE master of an elected master+shadow+
                 metalogger quorum with a windowed ec(8,4) write stream,
                 a rebuild, and a multipart upload all in flight: the
                 survivor SELF-promotes (no operator), chunkservers and
                 clients converge on it, zero acknowledged writes are
                 lost, and the detect->elect->promote->first-acked-write
                 outage is measured and bounded
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import signal
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# per-schedule wall-clock budget: "bounded-time completion" is an
# asserted invariant, not a hope
BUDGET_S = 180.0


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


async def admin(port: int, command: str, payload: str = "{}"):
    from lizardfs_tpu.proto import framing
    from lizardfs_tpu.proto import messages as m

    r, w = await asyncio.wait_for(
        asyncio.open_connection("127.0.0.1", port), 5.0
    )
    try:
        if command == "info":
            await framing.send_message(w, m.AdminInfo(req_id=1))
        else:
            await framing.send_message(
                w, m.AdminCommand(req_id=1, command=command, json=payload)
            )
        return await framing.read_message(r)
    finally:
        w.close()


class ChaosCluster:
    """Master (+ optional shadow) + N chunkservers as subprocesses.

    Chunkservers run with NATIVE_DATA_PLANE=false: fault rules armed
    over the admin channel mid-run must bite, and the C++ plane is not
    instrumentable (the same stand-down the servers apply themselves
    when rules are armed at startup). ``stock=True`` (chip_smoke.py)
    writes configs that hold only paths and ports instead: every
    daemon at its default settings, native data plane on, as README's
    quickstart starts them."""

    def __init__(self, tmp: str, n_cs: int = 4, shadow: bool = False,
                 qos_cfg: str | None = None, ha: bool = False,
                 stock: bool = False):
        self.tmp = tmp
        self.n_cs = n_cs
        self.stock = stock
        # ha: full autopilot quorum — master + shadow masters running
        # FailoverControllers plus a vote-only metalogger, all wired
        # through ELECTION_* config. Whoever wins the boot election is
        # the active; use active_master_port() to find it.
        self.ha = ha
        self.want_shadow = shadow or ha
        # JSON QoS config (runtime/qos.py parse_config schema): written
        # to disk and wired as the master's QOS_CFG
        self.qos_cfg = qos_cfg
        self.master_port = _free_port()
        self.shadow_port = _free_port() if self.want_shadow else None
        self.cs_ports: list[int] = []
        self.procs: dict[str, subprocess.Popen] = {}

    def _spawn(self, name: str, module: str, cfg_text: str) -> None:
        cfg = os.path.join(self.tmp, f"{name}.cfg")
        with open(cfg, "w") as f:
            f.write(cfg_text)
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
        env.pop("LZ_FAULTS", None)  # schedules arm rules explicitly
        self.procs[name] = subprocess.Popen(
            [sys.executable, "-m", module, cfg],
            stdout=open(os.path.join(self.tmp, f"{name}.log"), "wb"),
            stderr=subprocess.STDOUT, env=env,
        )

    def _ha_cfg(self, node_id: str) -> str:
        """ELECTION_*/MASTER_PEERS lines for one quorum member (na =
        the boot master, nb = the boot shadow, nw = the metalogger)."""
        peers = ",".join(
            f"{nid}=127.0.0.1:{port}"
            for nid, port in self.election_ports.items() if nid != node_id
        )
        return (
            f"ELECTION_ID = {node_id}\n"
            f"ELECTION_LISTEN = 127.0.0.1:{self.election_ports[node_id]}\n"
            f"ELECTION_PEERS = {peers}\n"
            f"MASTER_PEERS = na=127.0.0.1:{self.master_port},"
            f"nb=127.0.0.1:{self.shadow_port}\n"
            # RTO knobs: roomy enough that a loaded CI box's scheduling
            # hiccups don't trigger spurious elections mid-drill
            "ELECTION_TIMEOUT_MIN = 0.3\n"
            "ELECTION_TIMEOUT_MAX = 0.6\n"
            "HEARTBEAT_INTERVAL = 0.1\n"
        )

    async def start(self) -> None:
        with open(os.path.join(self.tmp, "goals.cfg"), "w") as f:
            f.write("1 one : _\n5 ec32 : $ec(3,2)\n12 ec84 : $ec(8,4)\n")
        qos_line = ""
        if self.qos_cfg is not None:
            with open(os.path.join(self.tmp, "qos.cfg"), "w") as f:
                f.write(self.qos_cfg)
            qos_line = f"QOS_CFG = {self.tmp}/qos.cfg\n"
        if self.ha:
            self.election_ports = {
                nid: _free_port() for nid in ("na", "nb", "nw")
            }
        self._spawn(
            "master", "lizardfs_tpu.master",
            f"DATA_PATH = {self.tmp}/master\n"
            f"LISTEN_PORT = {self.master_port}\n"
            f"GOALS_CFG = {self.tmp}/goals.cfg\n"
            + ("" if self.stock else "HEALTH_INTERVAL = 0.3\n") + qos_line
            + (self._ha_cfg("na") if self.ha else ""),
        )
        await self._wait_port(self.master_port)
        if self.want_shadow:
            self._spawn(
                "shadow", "lizardfs_tpu.master",
                f"DATA_PATH = {self.tmp}/shadow\n"
                f"LISTEN_PORT = {self.shadow_port}\n"
                f"GOALS_CFG = {self.tmp}/goals.cfg\n"
                "PERSONALITY = shadow\n"
                f"ACTIVE_MASTER = 127.0.0.1:{self.master_port}\n"
                "HEALTH_INTERVAL = 0.3\n"
                + (self._ha_cfg("nb") if self.ha else ""),
            )
            await self._wait_port(self.shadow_port)
        if self.ha:
            self._spawn(
                "metalogger", "lizardfs_tpu.metalogger",
                f"DATA_PATH = {self.tmp}/metalogger\n"
                f"MASTER_ADDRS = 127.0.0.1:{self.master_port},"
                f"127.0.0.1:{self.shadow_port}\n"
                "IMAGE_INTERVAL = 5.0\n" + self._ha_cfg("nw"),
            )
            # the boot election must settle before chunkservers spawn:
            # they register with whichever master holds the leadership
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if await self.active_master_port() is not None:
                    break
                await asyncio.sleep(0.2)
            else:
                raise AssertionError("boot election never settled")
        addrs = f"127.0.0.1:{self.master_port}"
        if self.want_shadow:
            addrs += f",127.0.0.1:{self.shadow_port}"
        for i in range(self.n_cs):
            port = _free_port()
            self.cs_ports.append(port)
            self._spawn(
                f"cs{i}", "lizardfs_tpu.chunkserver",
                f"DATA_PATH = {self.tmp}/cs{i}\n"
                f"LISTEN_PORT = {port}\n"
                f"MASTER_ADDRS = {addrs}\n"
                + ("" if self.stock else
                   "HEARTBEAT_INTERVAL = 0.3\n"
                   "NATIVE_DATA_PLANE = false\n"),
            )
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if await self._cs_count() >= self.n_cs:
                return
            await asyncio.sleep(0.1)
        raise AssertionError("chunkservers never registered")

    async def active_master_port(self) -> int | None:
        """The service port of whichever master currently holds the
        leadership (HA topologies only; either may have won)."""
        for port in (self.master_port, self.shadow_port):
            if port is None:
                continue
            try:
                doc = json.loads((await admin(port, "ha")).json)
            except (ConnectionError, OSError, asyncio.TimeoutError):
                continue
            # both conditions: a boot master that just LOST the first
            # election still reports personality=master for a beat
            if doc.get("personality") == "master" \
                    and doc.get("state") == "leader":
                return port
        return None

    async def _cs_count(self) -> int:
        port = self.master_port
        if self.ha:
            port = await self.active_master_port()
            if port is None:
                return 0
        try:
            reply = await admin(port, "info")
            return sum(
                1 for s in json.loads(reply.json)["chunkservers"]
                if s["connected"] and not s.get("mirror")
            )
        except (ConnectionError, OSError):
            return 0

    async def _wait_port(self, port: int, timeout: float = 20.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                _, w = await asyncio.wait_for(
                    asyncio.open_connection("127.0.0.1", port), 2.0
                )
                w.close()
                return
            except (ConnectionError, OSError, asyncio.TimeoutError):
                await asyncio.sleep(0.1)
        raise AssertionError(f"port {port} never came up")

    async def arm(self, port: int, rule: str) -> None:
        reply = await admin(port, "faults-arm", json.dumps({"rule": rule}))
        assert getattr(reply, "status", 1) == 0, f"faults-arm failed: {rule}"

    async def faults(self, port: int) -> dict:
        reply = await admin(port, "faults")
        return json.loads(reply.json)

    def kill9(self, name: str) -> None:
        self.procs[name].send_signal(signal.SIGKILL)
        self.procs[name].wait(timeout=10)

    def stop(self) -> None:
        for p in self.procs.values():
            if p.poll() is None:
                p.terminate()
        for p in self.procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


async def _client(cluster: ChaosCluster, shadow: bool = False,
                  info: str = "chaos"):
    from lizardfs_tpu.client.client import Client

    addrs = [("127.0.0.1", cluster.master_port)]
    if shadow and cluster.shadow_port:
        addrs.append(("127.0.0.1", cluster.shadow_port))
    c = Client(*addrs[0], wave_timeout=0.3, master_addrs=addrs)
    # lint: waive(unbounded-await): delegates to Client.connect — dials via the 5 s-bounded RpcConnection.connect and a 30 s-capped register RPC
    await c.connect(info=info)
    return c


async def _wait_rebuilt(cluster: ChaosCluster, min_completed: int = 1,
                        timeout: float = 60.0) -> dict:
    """Rebuild convergence invariant: the engine completed >= N
    rebuilds and nothing is left in flight."""
    deadline = time.monotonic() + timeout
    doc: dict = {}
    while time.monotonic() < deadline:
        reply = await admin(cluster.master_port, "rebuild-status")
        doc = json.loads(reply.json)
        if (
            doc.get("completed", 0) >= min_completed
            and not doc.get("active")
        ):
            return doc
        await asyncio.sleep(0.3)
    raise AssertionError(f"rebuild never converged: {doc}")


async def _wait_redundant(c, inode: int, expected_parts: int,
                          timeout: float = 90.0) -> None:
    """Rebuild convergence via the source of truth: the chunk's locate
    reply lists ``expected_parts`` distinct parts on live servers."""
    deadline = time.monotonic() + timeout
    seen: set = set()
    while time.monotonic() < deadline:
        loc = await c.chunk_info(inode, 0)
        seen = {l.part_id for l in loc.locations}
        if len(seen) >= expected_parts:
            return
        await asyncio.sleep(0.3)
    raise AssertionError(
        f"redundancy never restored: {len(seen)}/{expected_parts} parts"
    )


def _payload(seed: int, n: int) -> bytes:
    from lizardfs_tpu.utils import data_generator

    return data_generator.generate(seed, n).tobytes()


# --- schedules --------------------------------------------------------------


async def run_kill_write(cluster: ChaosCluster, rng: random.Random,
                         log) -> None:
    """SIGKILL a chunkserver mid-windowed-write: the write completes
    through retries, reads stay byte-identical, rebuild restores
    redundancy."""
    c = await _client(cluster)
    try:
        f = await c.create(1, "victim.bin")
        await c.setgoal(f.inode, 5)  # ec(3,2)
        payload = _payload(rng.randrange(1 << 20), 5 * 2**20 + 333)
        victim = rng.randrange(cluster.n_cs)
        delay = rng.uniform(0.02, 0.25)

        async def killer():
            await asyncio.sleep(delay)
            log(f"  SIGKILL cs{victim} after {delay * 1e3:.0f} ms")
            cluster.kill9(f"cs{victim}")

        kill_task = asyncio.ensure_future(killer())
        await c.write_file(f.inode, payload)
        await kill_task
        c.cache.invalidate(f.inode)
        got = await c.read_file(f.inode)
        assert got == payload, "byte identity after SIGKILL mid-write"
        # rebuild convergence: all 5 ec(3,2) parts live again on the
        # 3 survivors (victim may or may not have held parts — the
        # locate reply, not the engine's counters, is the invariant)
        await _wait_redundant(c, f.inode, expected_parts=5)
    finally:
        await c.close()


async def run_bitflip_read(cluster: ChaosCluster, rng: random.Random,
                           log) -> None:
    """Flip one stored-part bit under a live read: the client
    CRC-rejects the part, recovers the stripe via decode, reports the
    damage, and the master re-queues the part through the
    RebuildEngine."""
    from lizardfs_tpu.runtime import faults as faultsmod

    # sentinel rule in the DRIVER process: never matches (no such
    # site) but sets ACTIVE, standing the client's native fast paths
    # down so the CRC rejection takes the deterministic Python path
    faultsmod.arm("client:__sentinel__ delay=1")
    c = await _client(cluster)
    try:
        f = await c.create(1, "flip.bin")
        await c.setgoal(f.inode, 5)  # ec(3,2)
        payload = _payload(rng.randrange(1 << 20), 3 * 2**20 + 17)
        await c.write_file(f.inode, payload)
        victim = rng.randrange(cluster.n_cs)
        port = cluster.cs_ports[victim]
        await cluster.arm(
            port, "chunkserver:disk_pread flip,limit=1"
        )
        log(f"  armed disk_pread flip on cs{victim}")
        c.cache.invalidate(f.inode)
        got = await c.read_file(f.inode)
        assert got == payload, "byte identity through CRC-reject + decode"
        # the fault actually fired, and the CS's health names it
        doc = await cluster.faults(port)
        assert any(r["fired"] for r in doc["rules"]), doc
        health = json.loads((await admin(port, "health")).json)
        assert "disk_pread" in json.dumps(health.get("faults", {})), health
        # detection -> report -> rebuild: the client told the master,
        # the engine re-replicated the part
        assert c.metrics.counter("damaged_parts_reported").total >= 1
        await _wait_rebuilt(cluster, min_completed=1, timeout=90.0)
        # prometheus surface: the CS exported the labeled fire counter
        prom = json.loads((await admin(port, "metrics-prom")).json)["text"]
        assert 'lizardfs_faults_injected_total{' in prom, "faults counter"
    finally:
        faultsmod.clear()
        await c.close()


async def run_stall_acks(cluster: ChaosCluster, rng: random.Random,
                         log) -> None:
    """Delay write-status acks on one chunkserver: back-pressure must
    slow the windowed write, never wedge it; bytes stay identical."""
    c = await _client(cluster)
    try:
        victim = rng.randrange(cluster.n_cs)
        delay_ms = rng.choice((40, 60, 80))
        await cluster.arm(
            cluster.cs_ports[victim],
            f"chunkserver:frame_send:CstoclWriteStatus delay={delay_ms},p=0.5",
        )
        log(f"  armed {delay_ms} ms ack stall (p=0.5) on cs{victim}")
        f = await c.create(1, "stall.bin")
        await c.setgoal(f.inode, 5)
        payload = _payload(rng.randrange(1 << 20), 4 * 2**20 + 999)
        await c.write_file(f.inode, payload)
        c.cache.invalidate(f.inode)
        got = await c.read_file(f.inode)
        assert got == payload, "byte identity under ack stalls"
        doc = await cluster.faults(cluster.cs_ports[victim])
        assert any(r["fired"] for r in doc["rules"]), doc
    finally:
        await c.close()


async def run_shadow_stale(cluster: ChaosCluster, rng: random.Random,
                           log) -> None:
    """Partition the chunkserver->shadow mirror plane: the shadow keeps
    serving (increasingly stale) locates; clients detect missing
    locations and recover through the primary. Reads stay correct the
    whole time."""
    c = await _client(cluster, shadow=True)
    try:
        f = await c.create(1, "stale.bin")
        await c.setgoal(f.inode, 5)
        payload = _payload(rng.randrange(1 << 20), 2 * 2**20 + 5)
        await c.write_file(f.inode, payload)
        # let the shadow catch up + serve a few replica reads
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            await c.getattr(f.inode)
            if c.metrics.counter("shadow_reads").total > 0:
                break
            await asyncio.sleep(0.2)
        assert c.metrics.counter("shadow_reads").total > 0, \
            "shadow never served"
        # partition: every mirror registration/report into the shadow
        # drops at the frame boundary from now on
        await cluster.arm(
            cluster.shadow_port, "master:frame_recv:CstomaRegister drop"
        )
        await cluster.arm(
            cluster.shadow_port, "master:frame_recv:CstomaChunkNew drop"
        )
        log("  mirror plane partitioned at the shadow")
        # new data written AFTER the partition: the shadow's changelog
        # still flows (follow link untouched) but it has no locations
        # for the new chunks — replica locates come back empty and the
        # client re-locates through the primary
        f2 = await c.create(1, "post-partition.bin")
        await c.setgoal(f2.inode, 5)
        payload2 = _payload(rng.randrange(1 << 20), 2 * 2**20 + 77)
        await c.write_file(f2.inode, payload2)
        c.cache.invalidate(f.inode)
        c.cache.invalidate(f2.inode)
        assert await c.read_file(f.inode) == payload, "pre-partition file"
        assert await c.read_file(f2.inode) == payload2, \
            "post-partition file readable despite stale shadow locates"
        doc = await cluster.faults(cluster.shadow_port)
        assert doc["active"], doc
    finally:
        await c.close()


async def run_s3_multipart(cluster: ChaosCluster, rng: random.Random,
                           log) -> None:
    """SIGKILL a chunkserver mid-multipart-upload: the S3 gateway's
    CompleteMultipartUpload either yields the byte-identical object
    (appendchunks assembly over the survivors) or fails cleanly — a
    GET must never observe a torn object."""
    from lizardfs_tpu.s3.client import S3Client, S3Error
    from lizardfs_tpu.s3.server import S3Gateway

    c = await _client(cluster)
    gw = S3Gateway("127.0.0.1", cluster.master_port)
    await gw.start()
    s3 = S3Client("127.0.0.1", gw.port)
    try:
        await s3.create_bucket("chaos")
        # force ec(3,2) on both the bucket AND the gateway's staging
        # area (part/assembly files live there): every object byte must
        # survive one chunkserver loss
        await s3.put_object("chaos", "warmup", b"x")
        for path in ("/chaos", "/.s3mpu"):
            node = await c.resolve(path)
            await c.setgoal(node.inode, 5)
        parts = [
            _payload(rng.randrange(1 << 20), 2 * 2**20 + rng.randrange(999))
            for _ in range(3)
        ]
        upload = await s3.create_multipart("chaos", "obj")
        victim = rng.randrange(cluster.n_cs)
        delay = rng.uniform(0.02, 0.4)

        async def killer():
            await asyncio.sleep(delay)
            log(f"  SIGKILL cs{victim} after {delay * 1e3:.0f} ms")
            cluster.kill9(f"cs{victim}")

        kill_task = asyncio.ensure_future(killer())
        etags: list[tuple[int, str]] = []
        completed = False
        try:
            for i, p in enumerate(parts):
                etags.append(
                    (i + 1,
                     await s3.upload_part("chaos", "obj", upload, i + 1, p))
                )
            await s3.complete_multipart("chaos", "obj", upload, etags)
            completed = True
        except S3Error as e:
            log(f"  upload failed cleanly: HTTP {e.status} {e.code}")
        await kill_task
        if completed:
            got = await s3.get_object("chaos", "obj")
            assert got.body == b"".join(parts), \
                "multipart byte identity after SIGKILL"
            log("  completed; object byte-identical through the loss")
        else:
            # clean failure: the key must not exist at all — a torn
            # object visible to GET is the invariant violation
            try:
                await s3.get_object("chaos", "obj")
                raise AssertionError(
                    "torn object visible after failed complete"
                )
            except S3Error as e:
                assert e.status == 404, f"torn object state: {e}"
    finally:
        await s3.close()
        await gw.stop()
        await c.close()


# QoS config the noisy-neighbor drill arms on its master: the victim
# tenant holds 3x the abuser's weight; 150 locates/s total means the
# flood is shed hard while the victim's paced 20/s sits far under its
# ~112/s contended share
NOISY_QOS_CFG = json.dumps({
    "tenants": {
        "victim": {"weight": 3, "match": ["nn-victim*"], "p99_ms": 1000},
        "abuser": {"weight": 1, "match": ["nn-abuser*"]},
    },
    "rates": {"locate": 150},
    "data_inflight_mb": 32,
})

# the drill's victim-side bounds (asserted, not hoped): paced-locate
# p99 and total wall clock vs the unconstrained ideal
NOISY_VICTIM_P99_MS = 250.0
NOISY_VICTIM_OPS = 120
NOISY_VICTIM_PACE_S = 0.05
NOISY_ABUSER_OPS = 250


async def run_noisy_neighbor(cluster: ChaosCluster, rng: random.Random,
                             log) -> None:
    """One tenant floods the master's locate plane; fair-share
    admission sheds ONLY the abuser (as transient BUSY the client
    retries — never an error), the victim's p99 and goodput hold
    within the configured bounds, and health + Prometheus NAME the
    throttled tenant."""
    victim = await _client(cluster, info="nn-victim")
    abuser = await _client(cluster, info="nn-abuser")
    try:
        fv = await victim.create(1, "victim.bin")
        fa = await abuser.create(1, "abuser.bin")
        pay = _payload(rng.randrange(1 << 20), 128 * 1024 + 7)
        await victim.write_file(fv.inode, pay)
        await abuser.write_file(fa.inode, pay)
        # seed-steered start skew: the flood may lead or trail the
        # victim's first paced op
        skew = rng.uniform(0.0, 0.3)
        lat: list[float] = []

        async def flood():
            await asyncio.sleep(skew)
            for _ in range(NOISY_ABUSER_OPS):
                # every shed is retried inside the client (BUSY
                # backoff); an exception here fails the drill
                await abuser.chunk_info(fa.inode, 0)

        async def paced():
            for _ in range(NOISY_VICTIM_OPS):
                t0 = time.monotonic()
                await victim.chunk_info(fv.inode, 0)
                lat.append(time.monotonic() - t0)
                await asyncio.sleep(NOISY_VICTIM_PACE_S)

        t0 = time.monotonic()
        await asyncio.gather(flood(), paced())
        victim_wall = time.monotonic() - t0
        lat.sort()
        p99_ms = lat[int(len(lat) * 0.99)] * 1e3
        ideal = NOISY_VICTIM_OPS * NOISY_VICTIM_PACE_S
        log(f"  victim p99 {p99_ms:.1f} ms, wall {victim_wall:.1f}s "
            f"(ideal {ideal:.1f}s); abuser busy-waits "
            f"{abuser.metrics.counter('qos_busy_waits').total:.0f}")
        # victim p99 holds within the configured bound
        assert p99_ms <= NOISY_VICTIM_P99_MS, f"victim p99 {p99_ms:.1f}ms"
        # victim goodput within 2x of its unconstrained fair share
        assert victim_wall <= 2.0 * ideal + 2.0, victim_wall
        # the abuser WAS shed and retried through it
        assert abuser.metrics.counter("qos_busy_waits").total > 0, \
            "flood was never shed"
        assert victim.metrics.counter("qos_busy_waits").total == 0, \
            "victim was shed"
        # master side: sheds labeled abuser only; health + prom NAME it
        prom = json.loads(
            (await admin(cluster.master_port, "metrics-prom")).json
        )["text"]
        shed_lines = [
            line for line in prom.splitlines()
            if "lizardfs_qos_shed_total{" in line
        ]
        assert any('tenant="abuser"' in line for line in shed_lines), \
            "shed counter family missing from /metrics"
        assert all('tenant="victim"' not in line for line in shed_lines), \
            f"victim shed on the master: {shed_lines}"
        health = json.loads((await admin(cluster.master_port, "health")).json)
        assert "abuser" in health.get("qos", {}).get("throttled", []), health
        qos_doc = json.loads(
            (await admin(cluster.master_port, "qos")).json
        )
        assert qos_doc["sheds"].get("abuser", {}).get("count", 0) > 0
    finally:
        await victim.close()
        await abuser.close()


# hot-spot drill bounds: the viral file's read p99 must hold through
# the storm (generous — a shared CI box still has to clear it), and the
# boost must land within the storm window
HOTSPOT_READ_P99_MS = 2000.0
HOTSPOT_READERS = 3
HOTSPOT_STORM_S = 30.0
HOTSPOT_DEMOTE_S = 60.0


async def run_hot_spot(cluster: ChaosCluster, rng: random.Random,
                       log) -> None:
    """One file goes viral: a read storm hammers a single goal-1 chunk.
    The heat loop must goal-boost it (extra replicas appear through the
    RebuildEngine), fleet read p99 must hold through the storm with
    every read byte-identical (zero acknowledged-op loss), and once the
    storm ends and heat decays, the demotion must land and shed the
    extra copies."""
    c = await _client(cluster, info="hotspot-writer")
    try:
        f = await c.create(1, "viral.bin")
        payload = _payload(
            rng.randrange(1 << 20), 2 * 2**20 + rng.randrange(4096)
        )
        await c.write_file(f.inode, payload)
        # drill-sized thresholds via the operator path (admin
        # tweaks-set): boost after ~4 MiB of decayed heat, demote
        # under 1 MiB
        for name, value in (("heat_boost_bytes", 4 * 2**20),
                            ("heat_demote_bytes", 1 * 2**20)):
            reply = await admin(
                cluster.master_port, "tweaks-set",
                json.dumps({"name": name, "value": value}),
            )
            assert getattr(reply, "status", 1) == 0, f"tweaks-set {name}"
        lat: list[float] = []
        boosted: dict = {}
        stop = asyncio.Event()

        async def reader(idx: int) -> None:
            rdr = await _client(cluster, info=f"hotspot-r{idx}")
            try:
                while not stop.is_set():
                    t0 = time.monotonic()
                    rdr.cache.invalidate(f.inode)
                    got = await rdr.read_file(f.inode)
                    lat.append(time.monotonic() - t0)
                    # zero acknowledged-op loss: every read returns the
                    # acknowledged bytes, boost/demote never tears one
                    assert got == payload, "viral read byte identity"
            finally:
                await rdr.close()

        async def watch_boost() -> None:
            deadline = time.monotonic() + HOTSPOT_STORM_S
            while time.monotonic() < deadline:
                doc = json.loads(
                    (await admin(cluster.master_port, "heat")).json
                )
                if doc.get("boosted"):
                    boosted.update(doc["boosted"])
                    return
                await asyncio.sleep(0.3)

        readers = [
            asyncio.ensure_future(reader(i))
            for i in range(HOTSPOT_READERS)
        ]
        try:
            await watch_boost()
        finally:
            stop.set()
            await asyncio.gather(*readers)
        assert boosted, "viral chunk never goal-boosted under the storm"
        lat.sort()
        p99_ms = lat[int(len(lat) * 0.99)] * 1e3
        log(f"  boosted {boosted}; {len(lat)} storm reads, "
            f"p99 {p99_ms:.1f} ms")
        assert p99_ms <= HOTSPOT_READ_P99_MS, f"storm read p99 {p99_ms:.1f}ms"
        # the boost is real replication, not bookkeeping: extra copies
        # of the viral chunk appear through the RebuildEngine
        loc = await c.chunk_info(f.inode, 0)
        deadline = time.monotonic() + HOTSPOT_DEMOTE_S
        copies = 1
        while time.monotonic() < deadline:
            loc = await c.chunk_info(f.inode, 0)
            copies = len({(l.addr.host, l.addr.port) for l in loc.locations})
            if copies >= 2:
                break
            await asyncio.sleep(0.3)
        assert copies >= 2, f"boost never materialized ({copies} copies)"
        log(f"  {copies} live copies of the viral chunk")
        # the health rollup NAMES the hot spot while boosted
        health = json.loads(
            (await admin(cluster.master_port, "health")).json
        )
        assert health.get("heat", {}).get("boosted"), health.get("heat")
        # storm over: collapse the decay half-life (operator knob) and
        # the demotion must follow the heat down
        reply = await admin(
            cluster.master_port, "tweaks-set",
            json.dumps({"name": "heat_half_life_s", "value": 1.0}),
        )
        assert getattr(reply, "status", 1) == 0
        deadline = time.monotonic() + HOTSPOT_DEMOTE_S
        while time.monotonic() < deadline:
            doc = json.loads(
                (await admin(cluster.master_port, "heat")).json
            )
            if not doc.get("boosted"):
                break
            await asyncio.sleep(0.5)
        else:
            raise AssertionError("goal demote never landed after the storm")
        log("  demotion landed after the storm")
        # the file is still byte-identical after boost + demote
        c.cache.invalidate(f.inode)
        assert await c.read_file(f.inode) == payload, "post-storm identity"
    finally:
        await c.close()


# kill-primary bound: the whole detect -> elect -> promote -> first-
# acked-write outage, wall clock, on a loaded CI box (the election
# itself settles in ~1s with the drill's 0.3-0.6s timeouts; the rest is
# client redial + re-register + the first windowed write completing)
KILL_PRIMARY_RTO_S = 45.0


async def run_kill_primary(cluster: ChaosCluster, rng: random.Random,
                           log) -> dict:
    """SIGKILL the ACTIVE master of an elected master+shadow+metalogger
    quorum while a windowed ec(8,4) write stream, a rebuild, and a
    multipart upload are ALL in flight. The survivor must SELF-promote
    (no operator command anywhere), chunkservers and clients must
    converge on it, ZERO acknowledged writes may be lost, the fenced
    epoch must be claimed, and the detect->elect->promote->first-acked-
    write outage must fit inside KILL_PRIMARY_RTO_S. Returns the RTO
    doc.
    """
    from lizardfs_tpu.proto import status as st
    from lizardfs_tpu.s3.client import S3Client, S3Error
    from lizardfs_tpu.s3.server import S3Gateway

    active_port = await cluster.active_master_port()
    assert active_port is not None, "no elected active master"
    active_name = (
        "master" if active_port == cluster.master_port else "shadow"
    )
    survivor_port = (
        cluster.shadow_port if active_name == "master"
        else cluster.master_port
    )
    log(f"  active is the '{active_name}' process (:{active_port})")

    c = await _client(cluster, shadow=True)
    # S3 gateway for the mid-multipart leg: its embedded client must
    # know BOTH masters or it can never converge after the kill
    gw = S3Gateway("127.0.0.1", cluster.master_port)
    gw.client.master_addrs = [
        ("127.0.0.1", cluster.master_port),
        ("127.0.0.1", cluster.shadow_port),
    ]
    await gw.start()
    s3 = S3Client("127.0.0.1", gw.port)
    acked: list[tuple[str, bytes]] = []
    stop_writes = asyncio.Event()
    t_kill = [0.0]
    t_first_ack = [0.0]
    try:
        # --- continuous windowed ec(8,4) write stream ------------------
        async def writer() -> None:
            seq = 0
            while not stop_writes.is_set():
                name = f"wr_{seq}.bin"
                # payload derived from seq, not rng: draws inside a
                # concurrent task would make the schedule's rng stream
                # depend on kill timing and break seeded replay
                payload = _payload(1000 + seq, 192 * 1024 + 7 * seq)
                while not stop_writes.is_set():
                    try:
                        try:
                            f = await c.create(1, name)
                        except st.StatusError as e:
                            # created on the old master before it died:
                            # the name exists, the bytes may not
                            if e.code != st.EEXIST:
                                raise
                            f = await c.lookup(1, name)
                        await c.setgoal(f.inode, 12)  # ec(8,4), windowed
                        await c.write_file(f.inode, payload)
                    except (ConnectionError, OSError, st.StatusError,
                            asyncio.TimeoutError):
                        await asyncio.sleep(0.1)
                        continue
                    # ACKNOWLEDGED: from here on this write may never
                    # be lost, whatever dies
                    acked.append((name, payload))
                    if t_kill[0] and not t_first_ack[0]:
                        t_first_ack[0] = time.monotonic()
                    break
                seq += 1
                await asyncio.sleep(0.05)

        writer_task = asyncio.ensure_future(writer())
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and len(acked) < 3:
            await asyncio.sleep(0.1)
        assert len(acked) >= 3, "baseline write stream never flowed"

        # --- mid-multipart leg: upload part 1 of 3, then the kill ------
        await s3.create_bucket("chaos")
        await s3.put_object("chaos", "warmup", b"x")
        mpu_client_root = await c.resolve("/chaos")
        await c.setgoal(mpu_client_root.inode, 12)
        staging = await c.resolve("/.s3mpu")
        await c.setgoal(staging.inode, 12)
        parts = [
            _payload(rng.randrange(1 << 20), 2 * 2**20 + rng.randrange(999))
            for _ in range(3)
        ]
        upload = await s3.create_multipart("chaos", "obj")
        etags = [(1, await s3.upload_part("chaos", "obj", upload, 1,
                                          parts[0]))]

        # --- mid-rebuild leg: lose a chunkserver just before the kill --
        cs_victim = rng.randrange(cluster.n_cs)
        cluster.kill9(f"cs{cs_victim}")
        log(f"  SIGKILL cs{cs_victim} (rebuild in flight at the kill)")
        await asyncio.sleep(0.3)

        # --- THE KILL --------------------------------------------------
        log(f"  SIGKILL the active '{active_name}' master")
        t_kill[0] = time.monotonic()
        cluster.kill9(active_name)

        # the survivor must promote ITSELF: no admin command from here
        promote_s = None
        deadline = time.monotonic() + KILL_PRIMARY_RTO_S
        while time.monotonic() < deadline:
            try:
                doc = json.loads((await admin(survivor_port, "ha")).json)
            except (ConnectionError, OSError, asyncio.TimeoutError):
                doc = {}
            if doc.get("personality") == "master" \
                    and doc.get("state") == "leader":
                promote_s = time.monotonic() - t_kill[0]
                break
            await asyncio.sleep(0.1)
        assert promote_s is not None, "survivor never self-promoted"
        assert doc.get("promotions", 0) >= 1, doc
        assert doc.get("epoch", 0) >= 1, f"promotion not fenced: {doc}"
        epoch = doc["epoch"]

        # first acknowledged write AFTER the kill: the measured RTO
        while time.monotonic() < deadline and not t_first_ack[0]:
            await asyncio.sleep(0.05)
        assert t_first_ack[0], "write stream never resumed"
        rto_s = t_first_ack[0] - t_kill[0]
        log(f"  promote {promote_s:.2f}s, first acked write {rto_s:.2f}s")
        assert rto_s <= KILL_PRIMARY_RTO_S, f"RTO {rto_s:.1f}s"

        # the in-flight multipart upload completes byte-identically
        # through the promoted master (the gateway's client redials)
        mpu_deadline = time.monotonic() + 60.0
        for part_n in (2, 3):
            while True:
                try:
                    etags.append((part_n, await s3.upload_part(
                        "chaos", "obj", upload, part_n, parts[part_n - 1]
                    )))
                    break
                except S3Error:
                    assert time.monotonic() < mpu_deadline, \
                        "multipart upload never recovered"
                    await asyncio.sleep(0.3)
        while True:
            try:
                await s3.complete_multipart("chaos", "obj", upload, etags)
                break
            except S3Error:
                assert time.monotonic() < mpu_deadline, \
                    "multipart complete never recovered"
                await asyncio.sleep(0.3)
        got = await s3.get_object("chaos", "obj")
        assert got.body == b"".join(parts), \
            "multipart byte identity across the failover"

        # every surviving chunkserver re-registers with the new active
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if await cluster._cs_count() >= cluster.n_cs - 1:
                break
            await asyncio.sleep(0.2)
        assert await cluster._cs_count() >= cluster.n_cs - 1, \
            "chunkservers never converged on the new active"

        # stop the stream; ZERO acknowledged-write loss: every acked
        # file reads back byte-identical through the new active (the
        # cs kill leg makes some of these degraded ec(8,4) reads)
        stop_writes.set()
        await writer_task
        for name, payload in acked:
            node = await c.lookup(1, name)
            c.cache.invalidate(node.inode)
            got = await c.read_file(node.inode)
            assert got == payload, f"acked write {name} lost or torn"
        log(f"  all {len(acked)} acknowledged writes intact")

        # rebuild convergence on the NEW master: the first stream
        # file's redundancy is restored to all 12 ec(8,4) parts
        first = await c.lookup(1, acked[0][0])
        await _wait_redundant(c, first.inode, expected_parts=12,
                              timeout=90.0)

        # observability: the promoted master's health names the HA
        # standing, and the metrics page exports the epoch gauge
        health = json.loads((await admin(survivor_port, "health")).json)
        assert health.get("ha", {}).get("epoch") == epoch, health.get("ha")
        prom = json.loads(
            (await admin(survivor_port, "metrics-prom")).json
        )["text"]
        assert "lizardfs_ha_epoch" in prom, "ha gauges missing"
        return {
            "rto_s": round(rto_s, 2),
            "promote_s": round(promote_s, 2),
            "epoch": epoch,
            "acked_writes": len(acked),
            "lost_writes": 0,
            "rto_budget_s": KILL_PRIMARY_RTO_S,
        }
    finally:
        stop_writes.set()
        await s3.close()
        await gw.stop()
        await c.close()


SCHEDULES = {
    "kill-write": (run_kill_write, dict(n_cs=4)),
    "bitflip-read": (run_bitflip_read, dict(n_cs=3)),
    "stall-acks": (run_stall_acks, dict(n_cs=3)),
    "shadow-stale": (run_shadow_stale, dict(n_cs=3, shadow=True)),
    "s3-multipart": (run_s3_multipart, dict(n_cs=4)),
    "noisy-neighbor": (run_noisy_neighbor,
                       dict(n_cs=2, qos_cfg=NOISY_QOS_CFG)),
    "hot-spot": (run_hot_spot, dict(n_cs=3)),
    "kill-primary": (run_kill_primary, dict(n_cs=5, ha=True)),
}


async def run_schedule(name: str, seed: int, workdir: str | None = None,
                       log=print):
    """Run one schedule at one seed; raises on any invariant violation.
    The whole run sits under the bounded-time budget. Returns whatever
    the schedule returns (kill-primary its RTO doc; the rest None)."""
    fn, topo = SCHEDULES[name]
    rng = random.Random(seed)
    tmp_ctx = (
        tempfile.TemporaryDirectory(prefix=f"chaos-{name}-")
        if workdir is None else None
    )
    tmp = workdir if workdir is not None else tmp_ctx.name
    cluster = ChaosCluster(tmp, **topo)
    try:
        return await asyncio.wait_for(
            _run_body(cluster, fn, rng, log), BUDGET_S
        )
    finally:
        cluster.stop()
        if tmp_ctx is not None:
            tmp_ctx.cleanup()


async def _run_body(cluster, fn, rng, log):
    await cluster.start()
    return await fn(cluster, rng, log)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="chaos", description=__doc__)
    p.add_argument("--schedule", choices=sorted(SCHEDULES),
                   help="one schedule (default: --all)")
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--seeds", default="1,2,3",
                   help="comma-separated seed list for --all runs")
    p.add_argument("--all", action="store_true",
                   help="run every schedule at every seed")
    p.add_argument("--workdir", default=None,
                   help="keep cluster state/logs here instead of a tmpdir")
    args = p.parse_args(argv)

    names = [args.schedule] if args.schedule else sorted(SCHEDULES)
    if args.seed is not None:
        seeds = [args.seed]
    else:
        seeds = [int(s) for s in args.seeds.split(",") if s.strip()]
    failed = 0
    for name in names:
        for seed in seeds:
            t0 = time.monotonic()
            print(f"=== {name} seed={seed}")
            try:
                asyncio.run(run_schedule(name, seed,
                                         workdir=args.workdir))
                print(f"=== {name} seed={seed} PASS "
                      f"({time.monotonic() - t0:.1f}s)")
            except (KeyboardInterrupt, SystemExit):
                raise  # an interrupted matrix must stop, not keep booting
            except BaseException as e:  # noqa: BLE001 — report + replay line
                failed += 1
                print(f"=== {name} seed={seed} FAIL: {e!r}")
                print(f"    replay: python -m lizardfs_tpu.tools.chaos "
                      f"--schedule {name} --seed {seed}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
