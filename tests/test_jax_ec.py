"""TPU (JAX) kernels vs numpy golden path: byte-identical parity and CRCs.

Runs on the virtual CPU mesh in tests; same code path runs on real TPU.
"""

import numpy as np
import pytest

from lizardfs_tpu.core.encoder import CpuChunkEncoder, TpuChunkEncoder, get_encoder
from lizardfs_tpu.ops import crc32, rs


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
