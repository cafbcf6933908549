"""lizardfs_tpu — a TPU-native distributed file system framework.

A brand-new implementation of the LizardFS capability set
(master/chunkserver/client distributed POSIX-ish file system with N-copy,
xor2-9 and Reed-Solomon ec(k,m) replication goals) whose erasure-coding
data plane (GF(2^8) RS encode/decode, XOR parity, CRC32 checksumming)
dispatches through a pluggable ``ChunkEncoder`` boundary to JAX/XLA/Pallas
kernels on TPU, with a numpy golden path kept byte-identical for
verification.

Layout:
  ops/         compute kernels: GF(2^8) math, CRC32, bit-plane JAX kernels
  core/        ChunkEncoder boundary, slice/goal geometry
  parallel/    multi-chip sharded encode (jax.sharding.Mesh / shard_map)
  proto/       wire protocol: framing + typed serializers
  runtime/     daemon harness: event loop, config, logging
  master/      metadata server
  chunkserver/ data server
  client/      client library (read/write paths)
  models/      flagship end-to-end pipelines behind the graft entry
  utils/       shared helpers (deterministic data generator, etc.)
"""

__version__ = "0.1.0"

from lizardfs_tpu.constants import (
    MFSBLOCKSIZE,
    MFSBLOCKSINCHUNK,
    MFSCHUNKSIZE,
    CRC_POLY,
)
