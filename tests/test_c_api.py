"""The C embedding API: an EXTERNAL C program (no Python in its
process) round-trips files through the cluster.

Reference analog: src/mount/client/lizardfs_c_api.h consumers.
"""

import os
import subprocess

import pytest

from tests.test_cluster import Cluster

NATIVE = os.path.join(os.path.dirname(__file__), "..", "native")
LIB = os.path.join(NATIVE, "liblizardfs_client.so")


@pytest.fixture(scope="module")
def demo_binary(tmp_path_factory):
    if not os.path.exists(LIB):  # tests/conftest.py builds native/
        pytest.skip("native client library not buildable")
    out = tmp_path_factory.mktemp("cdemo") / "liz_demo"
    r = subprocess.run(
        ["gcc", os.path.join(NATIVE, "examples", "liz_demo.c"),
         "-o", str(out), "-L", NATIVE, "-llizardfs_client",
         f"-Wl,-rpath,{os.path.abspath(NATIVE)}"],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr
    return str(out)


@pytest.mark.asyncio
async def test_external_c_program_roundtrip(tmp_path, demo_binary):
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    try:
        import asyncio

        proc = await asyncio.create_subprocess_exec(
            demo_binary, "127.0.0.1", str(cluster.master.port),
            stdout=asyncio.subprocess.PIPE, stderr=asyncio.subprocess.PIPE,
        )
        out, err = await asyncio.wait_for(proc.communicate(), 120)
        assert proc.returncode == 0, f"stdout={out!r} stderr={err!r}"
        assert b"round trip OK" in out
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_c_api_interops_with_python_client(tmp_path, demo_binary):
    """Data written by the Python client is readable through the C API
    and vice versa (same wire formats, same CRC discipline)."""
    import asyncio
    import ctypes

    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "from_python.bin")
        payload = bytes(range(256)) * 5000  # 1.28 MB
        await c.write_file(f.inode, payload)

        lib = ctypes.CDLL(LIB)
        lib.liz_init.restype = ctypes.c_void_p
        lib.liz_init.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                 ctypes.c_char_p]
        lib.liz_lookup.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                   ctypes.c_char_p, ctypes.c_void_p]
        lib.liz_read.restype = ctypes.c_int64
        lib.liz_read.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                 ctypes.c_uint64, ctypes.c_uint64,
                                 ctypes.POINTER(ctypes.c_uint8)]
        lib.liz_write.restype = ctypes.c_int64
        lib.liz_write.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                  ctypes.c_uint64, ctypes.c_uint64,
                                  ctypes.POINTER(ctypes.c_uint8)]
        lib.liz_destroy.argtypes = [ctypes.c_void_p]

        def run_c_side():
            fs = lib.liz_init(b"127.0.0.1", cluster.master.port, None)
            assert fs

            class Attr(ctypes.Structure):
                _fields_ = [
                    ("inode", ctypes.c_uint32), ("ftype", ctypes.c_uint8),
                    ("mode", ctypes.c_uint16), ("uid", ctypes.c_uint32),
                    ("gid", ctypes.c_uint32), ("atime", ctypes.c_uint32),
                    ("mtime", ctypes.c_uint32), ("ctime", ctypes.c_uint32),
                    ("nlink", ctypes.c_uint32), ("length", ctypes.c_uint64),
                    ("goal", ctypes.c_uint8), ("trash_time", ctypes.c_uint32),
                ]

            a = Attr()
            assert lib.liz_lookup(fs, 1, b"from_python.bin",
                                  ctypes.byref(a)) == 0
            buf = (ctypes.c_uint8 * len(payload))()
            n = lib.liz_read(fs, a.inode, 0, len(payload), buf)
            assert n == len(payload), n
            assert bytes(buf) == payload
            # C writes, Python reads back
            patch = (ctypes.c_uint8 * 4)(0xDE, 0xAD, 0xBE, 0xEF)
            assert lib.liz_write(fs, a.inode, 1000, 4, patch) == 4
            lib.liz_destroy(fs)

        await asyncio.to_thread(run_c_side)
        c.cache.invalidate(f.inode)
        back = await c.read_file(f.inode)
        assert back[1000:1004] == b"\xde\xad\xbe\xef"
        assert back[:1000] == payload[:1000]
        assert back[1004:] == payload[1004:]
    finally:
        await cluster.stop()
