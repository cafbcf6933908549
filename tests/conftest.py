"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Tests validate numerics and multi-chip sharding on host CPU devices
(tier-1 also sets JAX_PLATFORMS=cpu from outside); the chip is driven
by chip_smoke.py through the chip tool, never by pytest. Both
variables must be set before jax creates its CPU client, so this file
sets them before importing anything that imports jax.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# every test process carries the XLA runtime from the start: in-process
# masters then serialize their image dumps on the loop instead of
# forking a pytest process full of threads (master/server.py _fork_safe)
import jax  # noqa: E402,F401

# --- minimal async test support (pytest-asyncio is not in the image) -------
import asyncio  # noqa: E402
import inspect  # noqa: E402


def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        # racehunt mode (tools/racehunt.py): LZ_DETSCHED=<seed> runs
        # every async test under the seeded deterministic event loop so
        # each seed explores one reproducible interleaving
        from lizardfs_tpu.runtime import detsched

        seed = detsched.detsched_seed()
        if seed is not None:
            detsched.run(fn(**kwargs), seed=seed)
        else:
            asyncio.run(fn(**kwargs))
        return True
    return None


def _build_native() -> None:
    """``make -C native`` once a session, before anything collects: a
    checkout with no ``*.so`` (they are never committed) then counts
    the tests a warm one does, instead of skipping or failing whatever
    needs the native plane until some test happens to build it. The
    controller gets here before it starts its workers; every worker
    comes through too, one at a time under a lock on the directory,
    and finds nothing left to make. A tree that cannot build is left
    to the tests' own skips."""
    import fcntl
    import subprocess

    native = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          os.pardir, "native")
    try:
        fd = os.open(native, os.O_RDONLY)
    except OSError:
        return
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        subprocess.run(["make", "-C", native], capture_output=True)
    except OSError:
        pass  # no make here
    finally:
        os.close(fd)


def pytest_configure(config):
    _build_native()
    config.addinivalue_line("markers", "asyncio: run test in an event loop")
    config.addinivalue_line(
        "markers",
        "slow: heavy variants excluded from tier-1 (-m 'not slow')",
    )
