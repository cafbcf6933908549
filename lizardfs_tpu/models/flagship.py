"""Flagship pipelines: the chunkserver write-path compute as one program.

Two entry points, matching BASELINE.json configs:

* :func:`single_chip_step` — fused ec(k,m) encode + per-block CRC32 of a
  whole 64 MiB chunk on one chip (BASELINE config 3: ec(8,4), batch =
  128 x 64 KiB stripes => 1024 data blocks + 512 parity blocks).
* :func:`multichip_step` — wide-stripe ec(32,8) with the stripe axis
  sharded over a device mesh and parity reduce-scattered by block
  (BASELINE config 5).

These are what ``__graft_entry__.py`` exposes to the driver.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from lizardfs_tpu.constants import MFSBLOCKSIZE
from lizardfs_tpu.ops import jax_ec
from lizardfs_tpu.parallel import sharded


def make_single_chip_step(
    k: int, m: int, block_size: int = MFSBLOCKSIZE, interpret: bool = False
):
    """Returns a jittable fn(data (k, N) uint8) -> (parity, dcrc, pcrc):
    the fused Pallas kernel (bits stay in VMEM). It needs a TPU
    backend; ``interpret=True`` runs it in the Pallas interpreter."""
    from lizardfs_tpu.ops import pallas_ec

    bigm = np.asarray(jax_ec.encoding_bitmatrix(k, m))

    def step(data: jnp.ndarray):
        return pallas_ec.fused_encode_crc(
            jnp.asarray(bigm), data, block_size, interpret=interpret
        )

    return step


def make_multichip_step(
    mesh, k: int = 32, m: int = 8, block_size: int = MFSBLOCKSIZE
):
    """Wide-stripe sharded encode+CRC step over ``mesh`` (see parallel.sharded)."""
    return sharded.sharded_encode_with_crcs(mesh, k, m, block_size)


def make_multichip_reconstruct_step(
    mesh, k: int, m: int, available: list[int], wanted: list[int],
    block_size: int = MFSBLOCKSIZE,
):
    """Mesh-sharded rebuild of ``wanted`` lost parts from survivors —
    the decode leg of the multichip story (see parallel.recovery)."""
    from lizardfs_tpu.parallel import recovery

    return recovery.sharded_reconstruct_with_crcs(
        mesh, k, m, available, wanted, block_size
    )


def example_chunk(k: int, nbytes_per_part: int, seed: int = 0) -> np.ndarray:
    """Deterministic example data (k, nbytes_per_part) uint8."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, size=(k, nbytes_per_part), dtype=np.uint8)
