"""The benchmark's own launcher of a deployment: one master and N
chunkservers as real processes through the documented entry points
(``python -m lizardfs_tpu.master <cfg>``, ``python -m
lizardfs_tpu.chunkserver <cfg>``), every daemon with
``JAX_PLATFORMS=cpu`` so that only the worker holds the chip. The
configs hold paths, ports and the goals the configuration file states,
nothing else: every daemon runs at its default settings, native data
plane on. Never imports jax.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time

from reference import layout


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class Cluster:
    def __init__(self, repo: str, work: str, goals: list[dict], n_cs: int):
        self.repo, self.work = repo, work
        self.goals = goals          # each {"id", "name", "expr", ...}
        self.n_cs = n_cs
        self.master_port = 0
        self.procs: dict[str, subprocess.Popen] = {}
        self.killed: set[str] = set()

    def cs_dir(self, i: int) -> str:
        return os.path.join(self.work, f"cs{i}")

    def _spawn(self, name: str, module: str, cfg_text: str) -> None:
        cfg = os.path.join(self.work, f"{name}.cfg")
        with open(cfg, "w") as f:
            f.write(cfg_text)
        env = dict(os.environ, PYTHONPATH=self.repo, JAX_PLATFORMS="cpu")
        for k in ("LZ_FAULTS", "LIZARDFS_TPU_ENCODER"):
            env.pop(k, None)
        with open(os.path.join(self.work, f"{name}.log"), "wb") as log:
            self.procs[name] = subprocess.Popen(
                [sys.executable, "-m", module, cfg], stdout=log,
                stderr=subprocess.STDOUT, env=env, cwd=self.work,
            )

    async def admin(self, command: str, payload: dict | None = None) -> dict:
        """One admin command to the master (the operator's channel,
        what ``lizardfs-admin`` sends)."""
        from lizardfs_tpu.proto import framing
        from lizardfs_tpu.proto import messages as m

        r, w = await asyncio.wait_for(
            asyncio.open_connection("127.0.0.1", self.master_port), 5.0)
        try:
            if command == "info":
                await framing.send_message(w, m.AdminInfo(req_id=1))
            else:
                await framing.send_message(w, m.AdminCommand(
                    req_id=1, command=command,
                    json=json.dumps(payload or {})))
            reply = await asyncio.wait_for(framing.read_message(r), 30.0)
        finally:
            w.close()
        if getattr(reply, "status", 0) != 0:
            raise RuntimeError(f"admin {command} {payload}: status "
                               f"{reply.status}")
        return json.loads(reply.json)

    async def _wait_port(self, port: int, name: str) -> None:
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if self.procs[name].poll() is not None:
                raise RuntimeError(f"{name} exited "
                                   f"{self.procs[name].returncode}: "
                                   f"{self.log_tail(name)}")
            try:
                _, w = await asyncio.wait_for(
                    asyncio.open_connection("127.0.0.1", port), 2.0)
                w.close()
                return
            except (ConnectionError, OSError, asyncio.TimeoutError):
                await asyncio.sleep(0.05)
        raise RuntimeError(f"{name}: port {port} never came up")

    async def start(self) -> None:
        goals = os.path.join(self.work, "goals.cfg")
        with open(goals, "w") as f:
            for g in self.goals:
                f.write(f"{g['id']} {g['name']} : {g['expr']}\n")
        for attempt in range(3):
            # a port found free can be taken before the master binds it
            self.master_port = free_port()
            self._spawn("master", "lizardfs_tpu.master",
                        f"DATA_PATH = {self.work}/master\n"
                        f"LISTEN_PORT = {self.master_port}\n"
                        f"GOALS_CFG = {goals}\n")
            try:
                await self._wait_port(self.master_port, "master")
                break
            except RuntimeError:
                if attempt == 2 or "in use" not in self.log_tail("master"):
                    raise
        # no LISTEN_PORT: a chunkserver binds a port of the kernel's
        # choosing and registers it, so none is picked here and lost
        # to another daemon before its owner binds it
        for i in range(self.n_cs):
            self._spawn(f"cs{i}", "lizardfs_tpu.chunkserver",
                        f"DATA_PATH = {self.cs_dir(i)}\n"
                        f"MASTER_ADDRS = 127.0.0.1:{self.master_port}\n")
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            for name, p in self.procs.items():
                if p.poll() is not None:
                    raise RuntimeError(f"{name} exited {p.returncode}: "
                                       f"{self.log_tail(name)}")
            try:
                info = await self.admin("info")
                if sum(1 for s in info["chunkservers"]
                       if s["connected"]) >= self.n_cs:
                    return
            except (ConnectionError, OSError, asyncio.TimeoutError):
                pass
            await asyncio.sleep(0.1)
        raise RuntimeError("chunkservers never registered")

    def server_holding(self, chunk_id: int, part_id: int) -> str:
        """The daemon whose data directory holds that part."""
        found = [i for i, _path in layout.find_part_files(
            [self.cs_dir(i) for i in range(self.n_cs)], chunk_id, part_id)]
        if len(found) != 1:
            raise RuntimeError(f"part {part_id} of chunk {chunk_id:X} is "
                               f"held by servers {found}")
        return f"cs{found[0]}"

    def kill9(self, name: str) -> None:
        self.procs[name].send_signal(signal.SIGKILL)
        self.procs[name].wait(timeout=10)
        self.killed.add(name)

    async def kill9_soon(self, name: str) -> float:
        """SIGKILL from inside a running window: returns the moment of
        the signal (monotonic) once the process is gone, and never
        blocks the loop the sessions run on."""
        self.killed.add(name)
        at = time.monotonic()
        self.procs[name].send_signal(signal.SIGKILL)
        while self.procs[name].poll() is None:
            await asyncio.sleep(0.005)
        return at

    def live_cs_dirs(self) -> list[str]:
        return [self.cs_dir(i) for i in range(self.n_cs)
                if f"cs{i}" not in self.killed]

    def log_tail(self, name: str, n: int = 1500) -> str:
        try:
            with open(os.path.join(self.work, f"{name}.log"), "rb") as f:
                return f.read()[-n:].decode("utf-8", "replace")
        except OSError:
            return ""

    def dead(self) -> list[str]:
        return [n for n, p in self.procs.items()
                if n not in self.killed and p.poll() is not None]

    def maps_libtpu(self) -> list[str]:
        out = []
        for name, p in self.procs.items():
            if p.poll() is None:
                with open(f"/proc/{p.pid}/maps") as f:
                    if "libtpu" in f.read():
                        out.append(name)
        return out

    def disk_bytes(self) -> int:
        total = 0
        for i in range(self.n_cs):
            for root, _dirs, files in os.walk(self.cs_dir(i)):
                for fn in files:
                    try:
                        total += os.path.getsize(os.path.join(root, fn))
                    except OSError:
                        pass
        return total

    def stop(self) -> None:
        for p in self.procs.values():
            if p.poll() is None:
                p.terminate()
        deadline = time.monotonic() + 10.0
        for p in self.procs.values():
            try:
                p.wait(timeout=max(deadline - time.monotonic(), 0.1))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
