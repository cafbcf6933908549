"""Pallas kernels in interpret mode (CPU): byte parity with the golden
path. Interpret mode is asked for by name in every call; the same
kernels run compiled on the chip, where chip_smoke.py checks them at
full geometry."""

import numpy as np
import pytest

import lizardfs_tpu.ops.pallas_ec as pe
from lizardfs_tpu.core.encoder import CpuChunkEncoder
from lizardfs_tpu.ops import jax_ec

cpu = CpuChunkEncoder()


def test_interpret_mode_is_never_inferred():
    """Without interpret=True a call on the CPU platform must raise —
    not run interpreted, not pick another program."""
    data = np.zeros((3, 8192), dtype=np.uint8)
    bigm = jax_ec.encoding_bitmatrix(3, 2)
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        pe.fused_encode_crc(bigm, data, 8192)
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        pe.block_crcs(data.reshape(-1, 4096), 4096)
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        pe.encode(bigm, np.zeros((3, 16384), dtype=np.uint8))


@pytest.mark.parametrize("k,m", [(3, 2), (8, 4)])
def test_pallas_encode_byte_identical(k, m):
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=(k, 2 * 16384), dtype=np.uint8)
    bigm = jax_ec.encoding_bitmatrix(k, m)
    parity = np.asarray(pe.encode(bigm, data, interpret=True))
    want = np.stack(cpu.encode(k, m, list(data)))
    np.testing.assert_array_equal(parity, want)


def test_pallas_crcs_byte_identical():
    rng = np.random.default_rng(1)
    # 18 blocks: not a multiple of the per-step group (16) -> padding path
    blocks = rng.integers(0, 256, size=(18, 4096), dtype=np.uint8)
    got = np.asarray(pe.block_crcs(blocks, 4096, interpret=True))
    from lizardfs_tpu.ops import crc32

    np.testing.assert_array_equal(got, crc32.block_crcs_golden(blocks))


def test_pallas_fused_byte_identical():
    rng = np.random.default_rng(2)
    k, m, bs, nb = 8, 4, 8192, 4
    data = rng.integers(0, 256, size=(k, nb * bs), dtype=np.uint8)
    bigm = jax_ec.encoding_bitmatrix(k, m)
    p, dc, pc = pe.fused_encode_crc(bigm, data, bs, interpret=True)
    wp, wd, wpc = cpu.encode_with_checksums(k, m, data, block_size=bs)
    np.testing.assert_array_equal(np.asarray(p), wp)
    np.testing.assert_array_equal(np.asarray(dc), wd)
    np.testing.assert_array_equal(np.asarray(pc), wpc)


def test_pallas_fused_multichunk_blocks():
    """Blocks wider than one kernel tile: the XLA epilogue combines
    per-chunk registers with shift matrices — exercise cpb > 1."""
    rng = np.random.default_rng(5)
    k, m, bs, nb = 3, 2, 65536, 3
    data = rng.integers(0, 256, size=(k, nb * bs), dtype=np.uint8)
    bigm = jax_ec.encoding_bitmatrix(k, m)
    p, dc, pc = pe.fused_encode_crc(bigm, data, bs, interpret=True)  # tile < bs here
    wp, wd, wpc = cpu.encode_with_checksums(k, m, data, block_size=bs)
    np.testing.assert_array_equal(np.asarray(p), wp)
    np.testing.assert_array_equal(np.asarray(dc), wd)
    np.testing.assert_array_equal(np.asarray(pc), wpc)


def test_pallas_fused_decode_verify():
    """Reconstruct lost parts and CRC-verify them in the same pass."""
    from lizardfs_tpu.ops import gf256

    rng = np.random.default_rng(6)
    k, m, bs, nb = 4, 2, 8192, 2
    data = rng.integers(0, 256, size=(k, nb * bs), dtype=np.uint8)
    bigm = jax_ec.encoding_bitmatrix(k, m)
    parity, dcrc, _pcrc = pe.fused_encode_crc(bigm, data, bs, interpret=True)
    allparts = np.concatenate([data, np.asarray(parity)], axis=0)
    lost = [1, 3]
    have = [i for i in range(k + m) if i not in lost]
    used, _ = gf256.recovery_selection(k, m, have, lost)
    big_rec = jax_ec.recovery_bitmatrix(k, m, tuple(used), tuple(lost))
    survivors = allparts[list(used)]
    want_crcs = np.asarray(dcrc)[lost]
    rec, crcs, ok = pe.fused_decode_verify(
        np.asarray(big_rec), survivors, want_crcs, bs, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(rec), data[lost])
    assert bool(np.all(np.asarray(ok)))
    # corrupt expectation -> verify trips
    bad = want_crcs.copy()
    bad[0, 0] ^= 1
    _, _, ok2 = pe.fused_decode_verify(
        np.asarray(big_rec), survivors, bad, bs, interpret=True
    )
    assert not bool(np.asarray(ok2)[0, 0]) and bool(np.asarray(ok2)[1, 1])


@pytest.mark.parametrize("tile", [32768, 65536])
def test_pallas_fused_large_tiles_byte_identical(tile):
    """The grid-step reduction (``BIG_TILE_CONFIG``, ROOFLINE #1) runs the same
    kernel at 32/64 KiB tiles — bytes must not depend on tile size."""
    rng = np.random.default_rng(7)
    k, m, bs = 8, 4, 65536
    data = rng.integers(0, 256, size=(k, 2 * bs), dtype=np.uint8)
    bigm = jax_ec.encoding_bitmatrix(k, m)
    p, dc, pc = pe.fused_encode_crc(
        bigm, data, bs, tile=tile, vmem_budget=64 * 2**20,
        interpret=True,
    )
    wp, wd, wpc = cpu.encode_with_checksums(k, m, data, block_size=bs)
    np.testing.assert_array_equal(np.asarray(p), wp)
    np.testing.assert_array_equal(np.asarray(dc), wd)
    np.testing.assert_array_equal(np.asarray(pc), wpc)


def test_pallas_default_tile_shrinks_to_fit():
    """Default args must keep working for every supported geometry and
    for N smaller than the starting tile (the shrink loop now also
    respects N-divisibility)."""
    rng = np.random.default_rng(8)
    for k, m, bs, nb in ((8, 4, 16384, 2), (3, 2, 8192, 3), (8, 2, 65536, 1)):
        data = rng.integers(0, 256, size=(k, nb * bs), dtype=np.uint8)
        bigm = jax_ec.encoding_bitmatrix(k, m)
        p, dc, pc = pe.fused_encode_crc(bigm, data, bs, interpret=True)
        wp, wd, wpc = cpu.encode_with_checksums(k, m, data, block_size=bs)
        np.testing.assert_array_equal(np.asarray(p), wp)
        np.testing.assert_array_equal(np.asarray(dc), wd)
        np.testing.assert_array_equal(np.asarray(pc), wpc)


@pytest.mark.parametrize("wide,reuse", [
    (True, False), (False, True), (True, True),
])
@pytest.mark.parametrize("k,m", [(3, 2), (8, 4)])
def test_pallas_roofline_config_byte_identical(k, m, wide, reuse):
    """ROOFLINE items #2 (reuse_planes: CRC consumes the encode's
    unpacked bit planes) and #3 (wide_crc: 128-lane stage-1 + 4-group
    fold) must be byte-identical to the golden path in every
    combination — only their SPEED is a silicon question."""
    rng = np.random.default_rng(11)
    bs = 65536
    data = rng.integers(0, 256, size=(k, 2 * bs), dtype=np.uint8)
    bigm = jax_ec.encoding_bitmatrix(k, m)
    p, dc, pc = pe.fused_encode_crc(
        bigm, data, bs, tile=65536, vmem_budget=64 * 2**20,
        wide_crc=wide, reuse_planes=reuse, interpret=True,
    )
    wp, wd, wpc = cpu.encode_with_checksums(k, m, data, block_size=bs)
    np.testing.assert_array_equal(np.asarray(p), wp)
    np.testing.assert_array_equal(np.asarray(dc), wd)
    np.testing.assert_array_equal(np.asarray(pc), wpc)


def test_pallas_roofline_small_tile_falls_back():
    """Tiles too small for the 4-group fold (sc < 4) or for whole
    groups per quarter must still produce golden bytes (the flags
    silently downgrade rather than mis-compute)."""
    rng = np.random.default_rng(12)
    k, m, bs = 8, 4, 65536
    data = rng.integers(0, 256, size=(k, bs), dtype=np.uint8)
    bigm = jax_ec.encoding_bitmatrix(k, m)
    p, dc, pc = pe.fused_encode_crc(
        bigm, data, bs, tile=512, vmem_budget=64 * 2**20,
        wide_crc=True, reuse_planes=True, interpret=True,
    )
    wp, wd, wpc = cpu.encode_with_checksums(k, m, data, block_size=bs)
    np.testing.assert_array_equal(np.asarray(p), wp)
    np.testing.assert_array_equal(np.asarray(dc), wd)
    np.testing.assert_array_equal(np.asarray(pc), wpc)


def test_pallas_decode_verify_roofline_config_byte_identical():
    """fused_decode_verify must accept the staged ROOFLINE config and
    recover byte-identically through a RECOVERY bitmatrix (the encode
    parity tests cover only generator-matrix shapes)."""
    from lizardfs_tpu.ops import gf256

    rng = np.random.default_rng(13)
    k, m, bs, nb = 8, 4, 65536, 2
    data = rng.integers(0, 256, size=(k, nb * bs), dtype=np.uint8)
    bigm = jax_ec.encoding_bitmatrix(k, m)
    parity, dcrc, _pcrc = pe.fused_encode_crc(bigm, data, bs, interpret=True)
    allparts = np.concatenate([data, np.asarray(parity)], axis=0)
    lost = [0]
    have = [i for i in range(k + m) if i not in lost]
    used, _ = gf256.recovery_selection(k, m, have, lost)
    big_rec = jax_ec.recovery_bitmatrix(k, m, tuple(used), tuple(lost))
    rec, _crcs, ok = pe.fused_decode_verify(
        np.asarray(big_rec), allparts[list(used)],
        np.asarray(dcrc)[lost], bs, interpret=True,
        **pe.ROOFLINE_CONFIG,
    )
    np.testing.assert_array_equal(np.asarray(rec), data[lost])
    assert np.asarray(ok).all()
