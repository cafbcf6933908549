"""Multi-PROCESS system tests: real daemons, real kill -9.

The reference's system tier launches masters + chunkservers as separate
processes and kills them mid-IO (reference: tests/tools/lizardfs.sh
setup_local_empty_lizardfs; ShortSystemTests/test_cs_failure_during_
xor_read.sh). The in-process Cluster helper can only stop daemons
gracefully — SIGKILL semantics (no clean goodbye, kernel-closed
sockets, heartbeat-timeout paths, image+changelog replay on restart)
only show up with real processes."""

import asyncio
import os
import signal
import socket
import subprocess
import sys

import pytest

from lizardfs_tpu.client.client import Client
from lizardfs_tpu.proto import status as st
from lizardfs_tpu.utils import data_generator

pytestmark = pytest.mark.asyncio

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class ProcCluster:
    """master + N chunkservers as subprocesses on localhost."""

    def __init__(self, tmp_path, n_cs=3):
        self.tmp = tmp_path
        self.n_cs = n_cs
        self.master_port = _free_port()
        self.procs: dict[str, subprocess.Popen] = {}

    def _spawn(self, name: str, module: str, cfg_text: str) -> None:
        cfg = self.tmp / f"{name}.cfg"
        cfg.write_text(cfg_text)
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
        self.procs[name] = subprocess.Popen(
            [sys.executable, "-m", module, str(cfg)],
            stdout=open(self.tmp / f"{name}.log", "wb"),
            stderr=subprocess.STDOUT, env=env,
        )

    async def start(self) -> None:
        (self.tmp / "goals.cfg").write_text(
            "1 one : _\n5 ec32 : $ec(3,2)\n"
        )
        self._spawn(
            "master", "lizardfs_tpu.master",
            f"DATA_PATH = {self.tmp}/master\n"
            f"LISTEN_PORT = {self.master_port}\n"
            f"GOALS_CFG = {self.tmp}/goals.cfg\n"
            "HEALTH_INTERVAL = 0.3\n",
        )
        await self._wait_port(self.master_port)
        for i in range(self.n_cs):
            self._spawn(
                f"cs{i}", "lizardfs_tpu.chunkserver",
                f"DATA_PATH = {self.tmp}/cs{i}\n"
                f"LISTEN_PORT = {_free_port()}\n"
                f"MASTER_PORT = {self.master_port}\n"
                "HEARTBEAT_INTERVAL = 0.3\n",
            )
        # all chunkservers registered
        for _ in range(100):
            if await self._cs_count() >= self.n_cs:
                return
            await asyncio.sleep(0.1)
        raise AssertionError("chunkservers never registered")

    async def _cs_count(self) -> int:
        import json

        from lizardfs_tpu.proto import framing
        from lizardfs_tpu.proto import messages as m

        try:
            r, w = await asyncio.open_connection("127.0.0.1", self.master_port)
            await framing.send_message(w, m.AdminInfo(req_id=1))
            reply = await framing.read_message(r)
            w.close()
            return sum(
                1 for s in json.loads(reply.json)["chunkservers"]
                # mirror=True entries are a shadow's passive location
                # feed — counting them would mistake a mirror-fed
                # shadow for the active during active-discovery
                if s["connected"] and not s.get("mirror")
            )
        except (ConnectionError, OSError):
            return 0

    async def _wait_port(self, port: int, timeout=15.0) -> None:
        for _ in range(int(timeout / 0.1)):
            try:
                _, w = await asyncio.open_connection("127.0.0.1", port)
                w.close()
                return
            except (ConnectionError, OSError):
                await asyncio.sleep(0.1)
        raise AssertionError(f"port {port} never came up")

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


async def test_sigkill_chunkserver_degraded_read(tmp_path):
    """kill -9 a chunkserver mid-cluster: EC reads recover through the
    survivors, and the health engine re-replicates."""
    cluster = ProcCluster(tmp_path, n_cs=4)
    try:
        await cluster.start()  # inside try: a failed start must not leak
        c = Client("127.0.0.1", cluster.master_port, wave_timeout=0.3)
        await c.connect()
        f = await c.create(1, "victim.bin")
        await c.setgoal(f.inode, 5)  # ec(3,2)
        payload = data_generator.generate(1, 5 * 2**20 + 333).tobytes()
        await c.write_file(f.inode, payload)

        cluster.kill9("cs0")  # no goodbye, no flush
        got = await c.read_file(f.inode)
        assert got == payload, "degraded read after SIGKILL"
        # health engine restores full redundancy on the 3 survivors:
        # every part of the ec(3,2) chunks reappears somewhere live
        from lizardfs_tpu.proto import framing
        from lizardfs_tpu.proto import messages as m

        async def endangered_count() -> int:
            import json

            r, w = await asyncio.open_connection(
                "127.0.0.1", cluster.master_port
            )
            await framing.send_message(
                w, m.AdminCommand(req_id=1, command="chunks-health", json="{}")
            )
            reply = await framing.read_message(r)
            w.close()
            doc = json.loads(reply.json)
            return int(doc.get("endangered", 0)) + int(doc.get("lost", 0))

        for _ in range(200):
            if await endangered_count() == 0:
                break
            await asyncio.sleep(0.1)
        else:
            raise AssertionError("health engine never restored redundancy")
        await c.close()
    finally:
        cluster.stop()


async def test_sigkill_master_restart_replays(tmp_path):
    """kill -9 the master (no image dump): the restart replays the
    changelog and serves the same namespace and bytes."""
    cluster = ProcCluster(tmp_path, n_cs=3)
    try:
        await cluster.start()  # inside try: a failed start must not leak
        c = Client("127.0.0.1", cluster.master_port, wave_timeout=0.3)
        await c.connect()
        f = await c.create(1, "durable.bin")
        await c.setgoal(f.inode, 5)
        payload = data_generator.generate(2, 2 * 2**20).tobytes()
        await c.write_file(f.inode, payload)
        await c.mkdir(1, "docs")
        await c.close()

        cluster.kill9("master")
        cluster._spawn(
            "master", "lizardfs_tpu.master",
            f"DATA_PATH = {tmp_path}/master\n"
            f"LISTEN_PORT = {cluster.master_port}\n"
            f"GOALS_CFG = {tmp_path}/goals.cfg\n"
            "HEALTH_INTERVAL = 0.3\n",
        )
        await cluster._wait_port(cluster.master_port)
        # chunkservers reconnect on their heartbeat (0.3 s interval)
        for _ in range(200):
            if await cluster._cs_count() >= 3:
                break
            await asyncio.sleep(0.1)
        else:
            raise AssertionError("chunkservers never re-registered")

        c2 = Client("127.0.0.1", cluster.master_port, wave_timeout=0.3)
        await c2.connect()
        attr = await c2.lookup(1, "durable.bin")
        assert attr.length == len(payload)
        assert (await c2.lookup(1, "docs")).inode > 0
        got = await c2.read_file(attr.inode)
        assert got == payload, "bytes lost across master SIGKILL"
        await c2.close()
    finally:
        cluster.stop()


async def test_sigkill_active_master_shadow_process_promotes(tmp_path):
    """Real-process HA failover (reference: uraftcontroller.cc +
    lizardfs-uraft-helper.in, minus the floating IP — clients and
    chunkservers carry the full master address list instead): SIGKILL
    the ACTIVE master process mid-write-stream; a shadow PROCESS wins
    the election, promotes, chunkservers re-register to it, the client
    fails over via its address list, and every acknowledged write is
    readable byte-identically afterwards."""
    cluster = ProcCluster(tmp_path, n_cs=3)
    pa, pb, pc = _free_port(), _free_port(), _free_port()
    ea, eb, ec = _free_port(), _free_port(), _free_port()
    peers = {"a": (pa, ea), "b": (pb, eb), "c": (pc, ec)}

    def master_cfg(me: str) -> str:
        port, eport = peers[me]
        others = ",".join(
            f"{pid}=127.0.0.1:{ep}" for pid, (_, ep) in peers.items()
            if pid != me
        )
        service = ",".join(
            f"{pid}=127.0.0.1:{p}" for pid, (p, _) in peers.items()
        )
        cfg = (
            f"DATA_PATH = {tmp_path}/master_{me}\n"
            f"LISTEN_PORT = {port}\n"
            f"GOALS_CFG = {tmp_path}/goals.cfg\n"
            "HEALTH_INTERVAL = 0.3\n"
            f"ELECTION_ID = {me}\n"
            f"ELECTION_LISTEN = 127.0.0.1:{eport}\n"
            f"ELECTION_PEERS = {others}\n"
            f"MASTER_PEERS = {service}\n"
        )
        if me != "a":
            cfg += (
                "PERSONALITY = shadow\n"
                f"ACTIVE_MASTER = 127.0.0.1:{pa}\n"
            )
        return cfg

    (tmp_path / "goals.cfg").write_text("1 one : _\n5 ec32 : $ec(3,2)\n")

    async def wait_active(exclude: int | None = None) -> int:
        """Port of the master every chunkserver is registered with —
        any node may win any election, so the leader is DISCOVERED,
        never assumed."""
        for _ in range(150):
            for port, _ep in peers.values():
                if port == exclude:
                    continue
                cluster.master_port = port
                if await cluster._cs_count() >= cluster.n_cs:
                    return port
            await asyncio.sleep(0.1)
        raise AssertionError("no master has all chunkservers registered")

    # ALL spawns happen inside try/finally: a failure during setup
    # (wait_port/wait_active raising) must still tear every spawned
    # process down — early versions leaked whole clusters on failure
    try:
        for me in ("a", "b", "c"):
            cluster._spawn(
                f"master_{me}", "lizardfs_tpu.master", master_cfg(me)
            )
        await cluster._wait_port(pa)
        addrs = ",".join(f"127.0.0.1:{p}" for p, _ in peers.values())
        for i in range(cluster.n_cs):
            cluster._spawn(
                f"cs{i}", "lizardfs_tpu.chunkserver",
                f"DATA_PATH = {tmp_path}/cs{i}\n"
                f"LISTEN_PORT = {_free_port()}\n"
                f"MASTER_ADDRS = {addrs}\n"
                "HEARTBEAT_INTERVAL = 0.3\n",
            )
        active = await wait_active()
        leader_name = next(
            f"master_{pid}" for pid, (p, _) in peers.items() if p == active
        )
        c = Client(
            "127.0.0.1", active, wave_timeout=0.3,
            master_addrs=[("127.0.0.1", p) for p, _ in peers.values()],
        )
        await c.connect("ha-e2e")
        payload = data_generator.generate(7, 1 * 2**20 + 17).tobytes()
        acked: list[str] = []
        for i in range(6):  # acked BEFORE the kill
            f = await c.create(1, f"pre_{i}.bin")
            await c.setgoal(f.inode, 5)
            await c.write_file(f.inode, payload)
            acked.append(f"pre_{i}.bin")

        async def version_of(port: int) -> int:
            import json

            from lizardfs_tpu.proto import framing
            from lizardfs_tpu.proto import messages as m

            try:
                r, w = await asyncio.open_connection("127.0.0.1", port)
                await framing.send_message(w, m.AdminInfo(req_id=1))
                reply = await framing.read_message(r)
                w.close()
                return int(json.loads(reply.json)["version"])
            except (ConnectionError, OSError):
                return -1

        # replication catch-up barrier: replica divergence is visible
        # operator state (AdminInfo version) and healthy failover
        # assumes synced shadows — same rule as the reference's
        # uraft tests. The controller's leader-following keeps every
        # replica on the live leader's stream, so this converges fast.
        for _ in range(100):
            versions = [await version_of(p) for p, _ in peers.values()]
            if len(set(versions)) == 1 and versions[0] > 0:
                break
            await asyncio.sleep(0.1)
        else:
            raise AssertionError(f"replicas never converged: {versions}")

        cluster.kill9(leader_name)

        # writes CONTINUE through failover: the client retries via its
        # address list; each op that returns is an acknowledged write
        for i in range(4):
            f = await c.create(1, f"post_{i}.bin")
            await c.setgoal(f.inode, 5)
            await c.write_file(f.inode, payload)
            acked.append(f"post_{i}.bin")
        assert c.current_master_addr[1] != active, \
            "client did not fail over to a promoted shadow"

        # chunkservers re-registered with the new active master
        new_active = await wait_active(exclude=active)
        assert new_active == c.current_master_addr[1]

        # every acknowledged write survives, byte-identical
        for name in acked:
            attr = await c.lookup(1, name)
            got = await c.read_file(attr.inode)
            assert got == payload, f"acknowledged write {name} lost"
        await c.close()
    finally:
        cluster.stop()


async def test_sigkill_rebuild_engine_status_and_trace(tmp_path):
    """The RebuildEngine acceptance e2e with a REAL kill -9: a
    SIGKILLed chunkserver's ec(3,2) parts are rebuilt under a
    byte/s throttle; `rebuild-status` shows the progress, the master's
    span ring carries per-rebuild `rebuild` spans, and the replicate
    SLO class accounted the work — all over the admin wire, like an
    operator would see it."""
    import json

    from lizardfs_tpu.proto import framing
    from lizardfs_tpu.proto import messages as m

    async def admin(port, command, payload="{}"):
        r, w = await asyncio.open_connection("127.0.0.1", port)
        try:
            await framing.send_message(
                w, m.AdminCommand(req_id=1, command=command, json=payload)
            )
            return await framing.read_message(r)
        finally:
            w.close()

    cluster = ProcCluster(tmp_path, n_cs=4)
    try:
        await cluster.start()
        # throttle: generous enough to finish fast, but every rebuild
        # pays the token bucket; cap at 2 concurrent
        for name, value in (("rebuild_bps", "200000000"),
                            ("rebuild_concurrency", "2")):
            reply = await admin(
                cluster.master_port, "tweaks-set",
                json.dumps({"name": name, "value": value}),
            )
            assert reply.status == st.OK, (name, reply.json)

        c = Client("127.0.0.1", cluster.master_port, wave_timeout=0.3)
        await c.connect()
        f = await c.create(1, "rebuildme.bin")
        await c.setgoal(f.inode, 5)  # ec(3,2)
        payload = data_generator.generate(3, 4 * 2**20 + 99).tobytes()
        await c.write_file(f.inode, payload)

        cluster.kill9("cs1")  # no goodbye: heartbeat-timeout path

        async def status_doc() -> dict:
            reply = await admin(cluster.master_port, "rebuild-status")
            assert reply.status == st.OK
            return json.loads(reply.json)

        for _ in range(300):
            doc = await status_doc()
            if doc["completed"] >= 1 and doc["endangered_queue"] == 0 \
                    and not doc["active"]:
                break
            await asyncio.sleep(0.1)
        else:
            raise AssertionError(f"rebuild never finished: {doc}")

        assert doc["bytes_rebuilt"] > 0
        assert doc["throttle"] == {
            "rebuild_bps": 200000000, "rebuild_concurrency": 2,
        }
        assert doc["recent"] and any(e["ok"] for e in doc["recent"])

        # the scheduler span is in the master's ring, named by the id
        # rebuild-status reported
        tid = next(e["trace_id"] for e in doc["recent"] if e["ok"])
        reply = await admin(
            cluster.master_port, "trace-dump",
            json.dumps({"trace_id": tid}),
        )
        spans = json.loads(reply.json)["spans"]
        assert any(s["name"] == "rebuild" for s in spans), spans

        # SLO integration: the master's replicate class saw the work
        reply = await admin(cluster.master_port, "health")
        master_snap = json.loads(reply.json)["master"]
        assert master_snap["slo"]["replicate"]["ops"] >= 1

        # and the bytes still read back whole (degraded or rebuilt)
        got = await c.read_file(f.inode)
        assert got == payload
        await c.close()
    finally:
        cluster.stop()


def _lzshm_mappings(pid: int) -> int:
    """Count memfd ring segments currently mapped by a process (the
    memfd is created under the name "lzshm" — native/shm_ring.h)."""
    try:
        with open(f"/proc/{pid}/maps") as f:
            return sum(1 for line in f if "lzshm" in line)
    except OSError:
        return 0


def _data_uds_ports() -> set[str]:
    """Abstract data-plane listener ports visible on this host
    (serve_native.cpp binds @lzfs-data-<host>-<port>)."""
    out = set()
    try:
        with open("/proc/net/unix") as f:
            for line in f:
                marker = "@lzfs-data-127.0.0.1-"
                idx = line.find(marker)
                if idx >= 0:
                    out.add(line[idx + len(marker):].strip())
    except OSError:
        pass
    return out


async def _cs_data_port(cluster) -> int:
    """The data port the master hands out for the cluster's one
    chunkserver. Asked of the master, not read off the host's socket
    table: /proc/net/unix lists every worker's chunkservers, and a
    listener another test bound meanwhile was taken for this one's."""
    import json

    from lizardfs_tpu.proto import framing
    from lizardfs_tpu.proto import messages as m

    r, w = await asyncio.open_connection("127.0.0.1", cluster.master_port)
    try:
        await framing.send_message(w, m.AdminInfo(req_id=1))
        reply = await asyncio.wait_for(framing.read_message(r), 10.0)
    finally:
        w.close()
    (cs,) = [s for s in json.loads(reply.json)["chunkservers"]
             if s["connected"] and not s.get("mirror")]
    return cs["data_port"]


async def test_shm_segment_lifecycle_survives_peer_sigkill(tmp_path):
    """Ring segments are owned by the connection: a client that mapped
    a segment and got SIGKILLed (no goodbye) must leave the chunkserver
    with ZERO lingering memfd mappings once the kernel closes the
    socket — and repeated map/kill cycles must not accumulate any."""
    from lizardfs_tpu.core import native_io

    if not native_io.parts_shm_available():
        pytest.skip("native shm ring not built")
    cluster = ProcCluster(tmp_path, n_cs=1)
    try:
        await cluster.start()
        port = await _cs_data_port(cluster)
        assert str(port) in _data_uds_ports(), \
            "chunkserver bound no abstract data listener"
        cs_pid = cluster.procs["cs0"].pid
        assert _lzshm_mappings(cs_pid) == 0

        helper_src = (
            "import sys, time\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "from lizardfs_tpu.core import native_io\n"
            f"sock = native_io._blocking_socket(('127.0.0.1', {port}), 30.0)\n"
            "ring = native_io.shm_ring_handshake(sock)\n"
            "assert ring is not None, 'handshake refused'\n"
            "print('MAPPED', flush=True)\n"
            "time.sleep(60)\n"
        )
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
        for cycle in range(2):
            helper = subprocess.Popen(
                [sys.executable, "-c", helper_src],
                stdout=subprocess.PIPE, env=env,
            )
            try:
                line = await asyncio.wait_for(
                    asyncio.to_thread(helper.stdout.readline), 30.0
                )
                assert b"MAPPED" in line, "helper never mapped a ring"
                # the segment is live in the SERVER's address space now
                for _ in range(300):
                    if _lzshm_mappings(cs_pid) > 0:
                        break
                    await asyncio.sleep(0.1)
                assert _lzshm_mappings(cs_pid) > 0, \
                    f"cycle {cycle}: server never mapped the segment"
            finally:
                helper.send_signal(signal.SIGKILL)
                helper.wait(timeout=10)
            for _ in range(300):
                if _lzshm_mappings(cs_pid) == 0:
                    break
                await asyncio.sleep(0.1)
            assert _lzshm_mappings(cs_pid) == 0, (
                f"cycle {cycle}: segment leaked past peer SIGKILL "
                "(proactor did not unmap on disconnect)"
            )
    finally:
        cluster.stop()


async def test_shadow_replica_reads_process_level(tmp_path):
    """ISSUE 7 e2e with real processes: a primary + shadow master pair,
    chunkservers mirror-registering to both, a client routing read RPCs
    to the shadow replica (tokened replies — counters climb on the
    client), the primary's admin `health` naming the shadow with its
    replication lag, and a SIGKILL of the shadow mid-reads degrading to
    primary-only without one failed read."""
    import json

    from lizardfs_tpu.proto import framing
    from lizardfs_tpu.proto import messages as m

    cluster = ProcCluster(tmp_path, n_cs=2)
    pp, sp = _free_port(), _free_port()
    (tmp_path / "goals.cfg").write_text("1 one : _\n5 ec32 : $ec(3,2)\n")

    async def admin(port: int, command: str) -> dict:
        r, w = await asyncio.open_connection("127.0.0.1", port)
        await framing.send_message(
            w, m.AdminCommand(req_id=1, command=command, json="{}")
        )
        reply = await framing.read_message(r)
        w.close()
        return json.loads(reply.json)

    try:
        cluster._spawn(
            "primary", "lizardfs_tpu.master",
            f"DATA_PATH = {tmp_path}/primary\n"
            f"LISTEN_PORT = {pp}\n"
            f"GOALS_CFG = {tmp_path}/goals.cfg\n"
            "HEALTH_INTERVAL = 0.3\n",
        )
        await cluster._wait_port(pp)
        cluster._spawn(
            "shadow", "lizardfs_tpu.master",
            f"DATA_PATH = {tmp_path}/shadow\n"
            f"LISTEN_PORT = {sp}\n"
            f"GOALS_CFG = {tmp_path}/goals.cfg\n"
            "HEALTH_INTERVAL = 0.3\n"
            "PERSONALITY = shadow\n"
            f"ACTIVE_MASTER = 127.0.0.1:{pp}\n",
        )
        await cluster._wait_port(sp)
        for i in range(cluster.n_cs):
            cluster._spawn(
                f"cs{i}", "lizardfs_tpu.chunkserver",
                f"DATA_PATH = {tmp_path}/cs{i}\n"
                f"LISTEN_PORT = {_free_port()}\n"
                f"MASTER_ADDRS = 127.0.0.1:{pp},127.0.0.1:{sp}\n"
                "HEARTBEAT_INTERVAL = 0.3\n",
            )
        cluster.master_port = pp
        for _ in range(100):
            if await cluster._cs_count() >= cluster.n_cs:
                break
            await asyncio.sleep(0.1)

        addrs = [("127.0.0.1", pp), ("127.0.0.1", sp)]
        c = Client("", 0, master_addrs=addrs, wave_timeout=0.3)
        await c.connect("shadow-e2e")
        assert c.shadow_reads
        f = await c.create(1, "rep.bin")
        payload = data_generator.generate(3, 2 * 65536 + 5).tobytes()
        await c.write_file(f.inode, payload)

        # reads route to the replica once it is caught up; the client
        # only accepts tokens >= its floor, so every answer is current
        for _ in range(150):
            a = await c.getattr(f.inode)
            assert a.length == len(payload)
            assert (await c.lookup(1, "rep.bin")).inode == f.inode
            if c.metrics.series["shadow_reads"].total >= 2:
                break
            await asyncio.sleep(0.1)
        assert c.metrics.series["shadow_reads"].total >= 2, \
            "client never engaged the shadow replica"

        # the PRIMARY's health rollup names the shadow and its lag
        # (MltomaAck plane, throttled to ~1/s — poll briefly)
        shadows = []
        for _ in range(50):
            h = await admin(pp, "health")
            shadows = h.get("shadows", [])
            if shadows and any(s["lag"] == 0 for s in shadows):
                break
            await asyncio.sleep(0.1)
        assert shadows, "primary health never reported the shadow"
        assert h["summary"]["shadows"] >= 1
        assert any(s["serving"] for s in shadows)

        # SIGKILL the shadow mid-reads: every read keeps answering
        # (primary fallback), fallbacks counter climbs
        cluster.kill9("shadow")
        before = c.metrics.series["shadow_fallbacks"].total
        for _ in range(20):
            a = await c.getattr(f.inode)
            assert a.length == len(payload)
            await asyncio.sleep(0.02)
        assert (await c.read_file(f.inode)) == payload
        assert c.metrics.series["shadow_fallbacks"].total > before
        await c.close()
    finally:
        cluster.stop()
