"""Mesh-sharded reconstruction vs the golden CPU codec.

Pins the decode half of the multichip story: for random erasure
patterns up to m parts of ec(k<=32, m<=32), the psum-scatter rebuild
(parallel/recovery.py) is byte-identical to CpuChunkEncoder.recover,
its post-rebuild CRCs match the stored per-block CRCs, the encoder
auto-ladder's sharded backend routes through it, and
``LZ_SHARDED_RECOVERY=0`` short-circuits the whole subsystem.
"""

import numpy as np
import pytest

from lizardfs_tpu.core.encoder import CpuChunkEncoder, ShardedTpuChunkEncoder
from lizardfs_tpu.parallel import recovery
from lizardfs_tpu.parallel.sharded import make_mesh, make_mesh_2d


@pytest.fixture(scope="module")
def mesh():
    import jax

    assert len(jax.devices()) == 8, "conftest must provide 8 CPU devices"
    return make_mesh()


def _encode_all(cpu, k, m, data, bs):
    parity, dcrc, pcrc = cpu.encode_with_checksums(k, m, data, block_size=bs)
    return np.concatenate([data, parity]), np.concatenate([dcrc, pcrc])


@pytest.mark.parametrize("k,m,seed", [(32, 8, 0), (16, 16, 1), (8, 4, 2)])
def test_random_erasures_byte_identical(mesh, k, m, seed):
    """Random erasure patterns (1..m lost parts, data+parity mixed):
    mesh rebuild == cpu recover, and the rebuilt blocks checksum to the
    stored CRCs (the post-rebuild verify)."""
    bs, nb = 512, 16
    rng = np.random.default_rng(seed)
    cpu = CpuChunkEncoder()
    data = rng.integers(0, 256, size=(k, nb * bs), dtype=np.uint8)
    all_parts, all_crcs = _encode_all(cpu, k, m, data, bs)
    for _ in range(4):
        nlost = int(rng.integers(1, m + 1))
        lost = sorted(
            int(i) for i in rng.choice(k + m, size=nlost, replace=False)
        )
        avail = [i for i in range(k + m) if i not in lost]
        rec, rcrc, ok = recovery.sharded_reconstruct_verify(
            mesh, k, m, avail, lost,
            {i: all_parts[i] for i in avail}, bs,
            expected_crcs=all_crcs[lost],
        )
        assert ok, (k, m, lost)
        np.testing.assert_array_equal(rec, all_parts[lost])
        want = cpu.recover(
            k, m, {i: all_parts[i] for i in avail}, lost
        )
        for j, w in enumerate(lost):
            np.testing.assert_array_equal(rec[j], want[w])


def test_reconstruct_2d_mesh(mesh):
    """The stripe x block mesh factorization rebuilds identically."""
    k, m, bs = 8, 4, 512
    nb = 16
    rng = np.random.default_rng(3)
    cpu = CpuChunkEncoder()
    data = rng.integers(0, 256, size=(k, nb * bs), dtype=np.uint8)
    all_parts, all_crcs = _encode_all(cpu, k, m, data, bs)
    lost = [2, 9]
    avail = [i for i in range(k + m) if i not in lost]
    rec, _, ok = recovery.sharded_reconstruct_verify(
        make_mesh_2d(4, 2), k, m, avail, lost,
        {i: all_parts[i] for i in avail}, bs,
        expected_crcs=all_crcs[lost],
    )
    assert ok
    np.testing.assert_array_equal(rec, all_parts[lost])


def test_reconstruct_rejects_bad_geometry(mesh):
    with pytest.raises(ValueError):
        recovery.sharded_reconstruct_with_crcs(
            mesh, 12, 4, list(range(12)), [12], 512
        )


def test_sharded_encoder_recover_byte_identical(mesh):
    """The auto-ladder's sharded backend: recover() through the
    encoder boundary matches the golden path (the replicator's seam)."""
    enc = ShardedTpuChunkEncoder(mesh, force_cpu=True)
    cpu = CpuChunkEncoder()
    k, m, bs = 16, 4, 512
    rng = np.random.default_rng(4)
    data = rng.integers(0, 256, size=(k, 8 * bs), dtype=np.uint8)
    all_parts, _ = _encode_all(cpu, k, m, data, bs)
    lost = [0, 18]
    parts = {
        i: all_parts[i] for i in range(k + m) if i not in lost
    }
    got = enc.recover(k, m, parts, lost)
    want = cpu.recover(k, m, parts, lost)
    for w in lost:
        np.testing.assert_array_equal(got[w], want[w])
    # non-dividing geometry falls back to the single-chip path and
    # stays correct (k=6 does not divide the 8-way mesh)
    k2, m2 = 6, 2
    data2 = rng.integers(0, 256, size=(k2, 4 * bs), dtype=np.uint8)
    all2, _ = _encode_all(cpu, k2, m2, data2, bs)
    parts2 = {i: all2[i] for i in range(k2 + m2) if i != 1}
    got2 = enc.recover(k2, m2, parts2, [1])
    np.testing.assert_array_equal(got2[1], all2[1])


def test_kill_switch_short_circuits(mesh, monkeypatch):
    """LZ_SHARDED_RECOVERY=0: the backend refuses to construct, a live
    instance degrades to the single-chip path (still byte-identical),
    and the auto ladder never lands on 'sharded'."""
    enc = ShardedTpuChunkEncoder(mesh, force_cpu=True)
    cpu = CpuChunkEncoder()
    k, m, bs = 8, 4, 512
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, size=(k, 8 * bs), dtype=np.uint8)
    all_parts, _ = _encode_all(cpu, k, m, data, bs)
    parts = {i: all_parts[i] for i in range(k + m) if i != 3}

    monkeypatch.setenv("LZ_SHARDED_RECOVERY", "0")
    assert not recovery.enabled()
    with pytest.raises(RuntimeError):
        ShardedTpuChunkEncoder(mesh, force_cpu=True)
    # the live instance must not touch the mesh path: poison the step
    # cache accessor so a mesh attempt fails loudly
    monkeypatch.setattr(
        enc, "_mesh_recover_step",
        lambda *a, **kw: (_ for _ in ()).throw(
            AssertionError("mesh path used despite kill switch")
        ),
    )
    got = enc.recover(k, m, parts, [3])
    np.testing.assert_array_equal(got[3], all_parts[3])

    from lizardfs_tpu.core import encoder as enc_mod

    monkeypatch.setattr(enc_mod, "_ENCODERS", {})
    assert enc_mod.get_encoder("auto").name != "sharded"


def test_dryrun_multichip_small_mesh():
    """Tier-1-safe dryrun: both MULTICHIP legs (encode, then kill one
    part and reconstruct byte-identically) on the 8-device CPU mesh at
    small shapes — the same code path the driver captures."""
    import __graft_entry__ as graft

    got = graft.dryrun_multichip(8, block_size=4096, min_logical_mib=1)
    # every device of the mesh held a shard of both legs' outputs
    assert got["shard_devices"] == {
        "encode": list(range(8)), "reconstruct": list(range(8)),
    }
    # asked for more devices than jax reports: an error, not a
    # substitute mesh
    with pytest.raises(RuntimeError, match="jax reports 8 cpu"):
        graft.dryrun_multichip(16, block_size=4096, min_logical_mib=1)
