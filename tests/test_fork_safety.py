"""Fork safety of the master's CoW metadata dump.

The reference forks its metadata dumper from a single-threaded event
loop (reference: src/master/metadata_dumper.h:37). Forking a process
that carries XLA/torch runtime threads risks a child deadlocked on a
mutex some pool thread held at fork time, so the master (a) must never
import jax itself and (b) must refuse to fork when a thread-heavy
native runtime is loaded anyway (colocated test processes), falling
back to on-loop serialization.
"""

import os
import subprocess
import sys

import pytest

from lizardfs_tpu.master.changelog import load_image
from lizardfs_tpu.master.server import MasterServer, _fork_safe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_master_package_never_imports_jax():
    """Importing the whole master package (and its transitive deps)
    must not pull jax/jaxlib into sys.modules: the production master's
    fork-based dumper depends on the process staying free of XLA
    threads. Runs in a clean interpreter (-E: no PYTHON* variables)
    so nothing the test process loaded can mask a regression."""
    code = (
        "import sys; sys.path.insert(0, {repo!r});\n"
        "import lizardfs_tpu.master.server\n"
        "import lizardfs_tpu.master.fs\n"
        "import lizardfs_tpu.master.chunks\n"
        "import lizardfs_tpu.master.metadata\n"
        "import lizardfs_tpu.master.changelog\n"
        "import lizardfs_tpu.master.tasks\n"
        "import lizardfs_tpu.master.assignment\n"
        "bad = sorted(m for m in sys.modules\n"
        "             if m.split('.')[0] in ('jax', 'jaxlib', 'torch'))\n"
        "assert not bad, f'master pulled in {{bad[:5]}}'\n"
        "print('clean')\n"
    ).format(repo=REPO)
    out = subprocess.run(
        [sys.executable, "-E", "-c", code],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert "clean" in out.stdout


def test_fork_safe_gate_detects_jax():
    """In this test process jax IS loaded (conftest imports it up
    front), so the gate must refuse to fork."""
    import jax  # noqa: F401 — make the precondition explicit

    assert _fork_safe() is False


@pytest.mark.asyncio
async def test_dump_with_jax_threads_does_not_fork(tmp_path, monkeypatch):
    """Image dump with jax imported and its runtime threads live must
    complete without calling os.fork (the deadlock-prone path) and
    produce a loadable image."""
    import threading

    import jax
    import jax.numpy as jnp

    # make "threads live" real: run a computation so XLA spins up its
    # thread pools, and keep a Python thread running through the dump
    jnp.ones((8, 8)).sum().block_until_ready()

    def boom():  # pragma: no cover - failure path
        raise AssertionError("os.fork called with jax loaded")

    monkeypatch.setattr(os, "fork", boom)

    stop = threading.Event()
    t = threading.Thread(target=lambda: stop.wait(30.0), daemon=True)
    t.start()
    assert threading.active_count() >= 2, "no live thread beside main"
    master = MasterServer(str(tmp_path / "master"))
    await master.start()
    try:
        inode = master.meta.fs.alloc_inode()
        master.commit({
            "op": "mknode", "parent": 1, "name": "d", "inode": inode,
            "ftype": 2, "mode": 0o755, "uid": 0, "gid": 0, "ts": 0,
            "goal": 1, "trash_time": 86400,
        })
        await master._dump_image()
    finally:
        stop.set()
        await master.stop()
    version, sections = load_image(str(tmp_path / "master"))
    assert sections, "dump produced an empty image"


def test_fork_path_used_when_clean(tmp_path):
    """A clean interpreter (no jax) must take the CoW fork path: run a
    master + dump in a subprocess with -E and verify os.fork was hit
    by counting children through a wrapper."""
    code = """
import asyncio, os, sys
sys.path.insert(0, {repo!r})
from lizardfs_tpu.master import server as msrv
assert msrv._fork_safe(), 'gate should allow fork in a clean process'
forks = []
real_fork = os.fork
os.fork = lambda: forks.append(1) or real_fork()

async def main():
    m = msrv.MasterServer({data!r})
    await m.start()
    inode = m.meta.fs.alloc_inode()
    m.commit(dict(op='mknode', parent=1, name='d', inode=inode, ftype=2,
                  mode=0o755, uid=0, gid=0, ts=0, goal=1, trash_time=86400))
    await m._dump_image()
    await m.stop()

asyncio.run(main())
assert forks, 'clean master did not use the CoW fork dump'
print('forked-ok')
""".format(repo=REPO, data=str(tmp_path / "master"))
    out = subprocess.run(
        [sys.executable, "-E", "-c", code],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert "forked-ok" in out.stdout


def test_cpu_chunkserver_never_imports_jax_through_a_rebuild(tmp_path):
    """A chunkserver configured ENCODER = cpu (the default) must stay
    off jax even after serving a MatocsReplicate: on a TPU host the
    client process owns the chip, and a replicator that reached for a
    device backend on its first rebuild would race it for the chip.
    Master, six chunkservers and a cpu-encoder client share one clean
    interpreter; one chunkserver stops, the master orders the rebuild,
    a survivor replicates — and jax is still not in sys.modules."""
    code = """
import asyncio, sys
sys.path.insert(0, {repo!r})
from lizardfs_tpu.chunkserver.server import ChunkServer
from lizardfs_tpu.client.client import Client
from lizardfs_tpu.core import geometry
from lizardfs_tpu.core.encoder import get_encoder
from lizardfs_tpu.master.server import MasterServer
from lizardfs_tpu.utils import data_generator

async def main():
    goals = geometry.default_goals()
    goals[10] = geometry.parse_goal_line('10 ec : $ec(3,2)')[1]
    master = MasterServer({data!r} + '/master', goals=goals,
                          health_interval=0.2)
    await master.start()
    servers = []
    for i in range(6):
        cs = ChunkServer({data!r} + f'/cs{{i}}', encoder_name='cpu',
                         master_addr=('127.0.0.1', master.port),
                         wave_timeout=0.2, native_data_plane=False)
        await cs.start()
        servers.append(cs)
    c = Client('127.0.0.1', master.port, encoder=get_encoder('cpu'),
               wave_timeout=0.2)
    await c.connect()
    f = await c.create(1, 'f')
    await c.setgoal(f.inode, 10)
    await c.write_file(f.inode, data_generator.generate(3, 400_000))
    port = (await c.chunk_info(f.inode, 0)).locations[0].addr.port
    victim = next(cs for cs in servers if cs.port == port)
    await victim.stop()
    servers.remove(victim)
    for _ in range(300):
        if sum(cs.metrics.counter('replications').total
               for cs in servers) >= 1:
            break
        await asyncio.sleep(0.1)
    else:
        raise AssertionError('no chunkserver served a MatocsReplicate')
    await c.close()
    for cs in servers:
        await cs.stop()
    await master.stop()

asyncio.run(main())
bad = sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'jaxlib'))
assert not bad, f'replicating chunkserver pulled in {{bad[:5]}}'
print('replicated-clean')
""".format(repo=REPO, data=str(tmp_path))
    out = subprocess.run(
        [sys.executable, "-E", "-c", code],
        capture_output=True, text=True, timeout=180,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "replicated-clean" in out.stdout
