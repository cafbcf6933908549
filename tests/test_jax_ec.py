"""TPU (JAX) kernels vs numpy golden path: byte-identical parity and CRCs.

Runs on the virtual CPU mesh in tests; same code path runs on real TPU.
xor2..xor9 parity takes the staging path the GF(2^8) products take
(stack, put, kernel, fetch) and charges rows of its own.
"""

import os
import sys

import numpy as np
import pytest

from lizardfs_tpu.constants import MFSBLOCKSIZE
from lizardfs_tpu.core.encoder import CpuChunkEncoder, TpuChunkEncoder, get_encoder
from lizardfs_tpu.ops import crc32, rs
from lizardfs_tpu.runtime import tracing
from lizardfs_tpu.runtime.metrics import (
    WRITE_COUNTS, WRITE_PHASES, PhaseBreakdown,
)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import layout  # noqa: E402

LEGS = ("dev_stage", "dev_put", "dev_run", "dev_fetch")
RS_ROWS = ("boundary",) + LEGS
XOR_ROWS = tuple("xor_" + r for r in RS_ROWS)
# a whole number of 64 KiB blocks, and a length that is not
LENGTHS = (2 * MFSBLOCKSIZE, 3 * MFSBLOCKSIZE + 77)


@pytest.fixture(scope="module")
def tpu_enc():
    # force_cpu + interpret, both by name: numerics tests run on the
    # virtual CPU mesh by design, where the Pallas entry points only
    # run interpreted; get_encoder("auto") never lands here on a
    # CPU-only box (see test_encoder_ladder)
    return TpuChunkEncoder(force_cpu=True, interpret=True)


cpu_enc = CpuChunkEncoder()


@pytest.mark.parametrize("k,m", [(2, 1), (3, 2), (8, 4), (8, 5), (32, 8)])
def test_encode_byte_identical(tpu_enc, k, m):
    rng = np.random.default_rng(0)
    size = 4096
    data = [rng.integers(0, 256, size, dtype=np.uint8) for _ in range(k)]
    want = cpu_enc.encode(k, m, data)
    got = tpu_enc.encode(k, m, data)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


def test_encode_with_zero_elision(tpu_enc):
    rng = np.random.default_rng(1)
    k, m = 5, 3
    size = 1024
    data = [rng.integers(0, 256, size, dtype=np.uint8) for _ in range(k)]
    data[1] = None
    data[4] = None
    dense = [d if d is not None else np.zeros(size, np.uint8) for d in data]
    want = cpu_enc.encode(k, m, dense)
    got = tpu_enc.encode(k, m, data)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("k,m", [(3, 2), (8, 4), (32, 8)])
def test_recover_byte_identical(tpu_enc, k, m):
    rng = np.random.default_rng(2)
    size = 2048
    data = [rng.integers(0, 256, size, dtype=np.uint8) for _ in range(k)]
    parity = cpu_enc.encode(k, m, data)
    allparts = data + parity
    erased = sorted(rng.choice(k + m, size=m, replace=False).tolist())
    avail = {i: allparts[i] for i in range(k + m) if i not in erased}
    got = tpu_enc.recover(k, m, avail, erased)
    for i in erased:
        np.testing.assert_array_equal(got[i], allparts[i], err_msg=f"part {i}")


def test_checksum_matches_golden(tpu_enc):
    rng = np.random.default_rng(3)
    for bs in (512, 65536):
        blocks = rng.integers(0, 256, size=(8, bs), dtype=np.uint8)
        np.testing.assert_array_equal(
            tpu_enc.checksum(blocks), crc32.block_crcs_golden(blocks)
        )


def test_fused_encode_crc(tpu_enc):
    rng = np.random.default_rng(4)
    k, m, bs, nb = 8, 4, 4096, 4
    data = rng.integers(0, 256, size=(k, nb * bs), dtype=np.uint8)
    parity, dcrc, pcrc = tpu_enc.encode_with_checksums(k, m, data, block_size=bs)
    w_parity, w_dcrc, w_pcrc = cpu_enc.encode_with_checksums(k, m, data, block_size=bs)
    np.testing.assert_array_equal(parity, w_parity)
    np.testing.assert_array_equal(dcrc, w_dcrc)
    np.testing.assert_array_equal(pcrc, w_pcrc)


def test_xor_parity(tpu_enc):
    rng = np.random.default_rng(5)
    parts = [rng.integers(0, 256, 777, dtype=np.uint8) for _ in range(4)]
    np.testing.assert_array_equal(
        tpu_enc.xor_parity(parts), cpu_enc.xor_parity(parts)
    )


def test_registry():
    assert get_encoder("cpu").name == "cpu"
    # auto: tpu needs an accelerator — on the test box JAX is
    # importable but CPU-platform, so auto must resolve to the native
    # SIMD backend (or numpy if the .so is absent), never XLA-on-CPU
    e = get_encoder(None)
    assert e.name in ("cpp", "cpu")


def parts_of(n: int, length: int, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng([seed, n, length])
    return [rng.integers(0, 256, length, dtype=np.uint8) for _ in range(n)]


@pytest.mark.parametrize("length", LENGTHS)
@pytest.mark.parametrize("n", range(2, 10))
def test_device_xor_equals_the_golden_codec(tpu_enc, n, length):
    parts = parts_of(n, length, 42)
    want = rs.xor_parity(parts)
    got = tpu_enc.xor_parity(parts)
    assert got.dtype == np.uint8 and np.array_equal(got, want)
    # into a buffer the caller holds: a row of a send buffer, and a
    # strided view (every other byte of a larger one)
    rows = np.full((2, length), 0xA5, dtype=np.uint8)
    tpu_enc.xor_parity_into(parts, rows[1])
    assert np.array_equal(rows[1], want) and (rows[0] == 0xA5).all()
    wide = np.full(2 * length, 0x5A, dtype=np.uint8)
    tpu_enc.xor_parity_into(parts, wide[::2])
    assert np.array_equal(wide[::2], want) and (wide[1::2] == 0x5A).all()


@pytest.mark.parametrize("chunk_len", [9 * MFSBLOCKSIZE,
                                       7 * MFSBLOCKSIZE + 1234])
@pytest.mark.parametrize("n", range(2, 10))
def test_device_xor_equals_the_reference_layout(tpu_enc, n, chunk_len):
    """A chunk of xorN as the benchmark's reference lays it out (parity
    part 0, data parts 1..N, zero-padded to whole blocks): the device's
    parity of the data parts is the reference's part 0."""
    data = np.random.default_rng([7, n, chunk_len]).integers(
        0, 256, chunk_len, dtype=np.uint8)
    streams = layout.goal_parts({"xor": n}, data, MFSBLOCKSIZE)
    assert np.array_equal(tpu_enc.xor_parity(streams[1:]), streams[0])
    out = np.empty_like(streams[0])
    tpu_enc.xor_parity_into(streams[1:], out)
    assert np.array_equal(out, streams[0])


def test_xor_rows_are_its_own_and_leave_the_products_rows(tpu_enc):
    """One xor call charges ``xor_boundary`` and its four legs, under a
    ``boundary`` span that says ``op="xor"`` and holds the four legs'
    spans; the products' rows stay where they were, and a product moves
    no xor row."""
    rows = PhaseBreakdown("client_write", WRITE_PHASES, WRITE_COUNTS)
    ring = tracing.SpanRing()
    sink = tracing.OpSink(rows, ring, "client")
    parts = parts_of(3, 4 * MFSBLOCKSIZE, 1)
    with tracing.span("pwrite", sink=sink):
        with tracing.span("encode", phase="encode", bucket="compute"):
            tpu_enc.xor_parity(parts)
    after_xor = rows.snapshot()
    assert all(after_xor[r + "_ms"] > 0 for r in XOR_ROWS)
    assert all(after_xor[r + "_ms"] == 0 for r in RS_ROWS)
    spans = ring.dump()
    boundary, = [s for s in spans if s["name"] == "boundary"]
    assert boundary["attrs"]["op"] == "xor"
    assert (boundary["attrs"]["rows"], boundary["attrs"]["bytes"]) == (
        3, 4 * MFSBLOCKSIZE)
    legs = sorted((s for s in spans
                   if s["parent_id"] == boundary["span_id"]),
                  key=lambda s: s["t0"])
    assert [s["name"] for s in legs] == list(LEGS)

    with tracing.span("pwrite", sink=sink):
        with tracing.span("encode", phase="encode", bucket="compute"):
            tpu_enc.encode(3, 2, parts)
    after_rs = rows.snapshot()
    assert all(after_rs[r + "_ms"] > 0 for r in RS_ROWS)
    assert {r: after_rs[r + "_ms"] for r in XOR_ROWS} == {
        r: after_xor[r + "_ms"] for r in XOR_ROWS}
    # the xor rows nest under encode, as the products' boundary does
    assert rows.phases["xor_boundary"] == "encode"
    assert all(rows.phases["xor_" + leg] == "xor_boundary" for leg in LEGS)
    assert "xor_boundary" not in rows.top_level


def test_the_host_encoders_write_xor_into_the_callers_buffer():
    parts = parts_of(5, 3 * MFSBLOCKSIZE + 9, 3)
    out = np.zeros(3 * MFSBLOCKSIZE + 9, dtype=np.uint8)
    CpuChunkEncoder().xor_parity_into(parts, out)
    assert np.array_equal(out, rs.xor_parity(parts))
