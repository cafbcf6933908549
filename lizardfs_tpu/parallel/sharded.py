"""Wide-stripe erasure coding sharded over a TPU device mesh.

The reference scales one 64 MiB chunk across up to 64 servers with wide
stripes (ec(32,8), ec(32,32): src/common/slice_traits.h:143-146). The
TPU-native analog maps the **stripe axis onto the device mesh**:

  * data parts are sharded over mesh axis "stripe" (k/n parts per chip),
  * each chip computes a *partial* parity bit-matmul with its column
    slice of the expanded generator matrix,
  * partial sums meet in a ``psum_scatter`` (reduce-scatter) over the
    block axis — parity lands already sharded by block for local CRC —
    riding ICI, the analog of the reference's parity all-gather
    (BASELINE config 5),
  * per-block CRCs are computed locally on whichever chip owns the
    block; no further communication.

GF(2) addition is XOR, which commutes with integer summation followed by
``& 1`` — so XLA's native int32 psum IS the field reduction. This is the
whole trick that makes wide-stripe EC a textbook SPMD matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from lizardfs_tpu.ops import jax_ec


def make_mesh(devices=None, axis: str = "stripe") -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), (axis,))


def make_mesh_2d(
    stripe: int, block: int, devices=None,
    axes: tuple[str, str] = ("stripe", "block"),
) -> Mesh:
    """2-D mesh: stripe-parallel x block-parallel.

    The stripe axis is the tensor-parallel analog (parts of one stripe
    spread over chips, joined by the parity reduce-scatter); the block
    axis is the data-parallel analog (disjoint block ranges, no
    communication at all). On multi-host topologies put the stripe axis
    within a slice (ICI) and the block axis across hosts (DCN) — the
    block axis never communicates, so DCN bandwidth is irrelevant.
    """
    devices = devices if devices is not None else jax.devices()
    if stripe * block != len(devices):
        raise ValueError(
            f"mesh {stripe}x{block} needs {stripe * block} devices, "
            f"have {len(devices)}"
        )
    return Mesh(np.array(devices).reshape(stripe, block), axes)


def sharded_encode_with_crcs(mesh: Mesh, k: int, m: int, block_size: int):
    """Build a jitted wide-stripe encode+CRC step over ``mesh``.

    Returns ``step(bigm, data)`` where data is (k, nb*block_size) with the
    part axis sharded over the mesh; outputs are
    (parity (m, nb, block_size) block-sharded, data_crcs (k, nb),
    parity_crcs (m, nb)). nb and k must be divisible by the mesh size.
    """
    stripe_axis = mesh.axis_names[0]
    n_stripe = mesh.shape[stripe_axis]
    block_axis = mesh.axis_names[1] if len(mesh.axis_names) > 1 else None
    n_block = mesh.shape[block_axis] if block_axis else 1
    n_dev = n_stripe
    axis = stripe_axis
    if k % n_stripe:
        raise ValueError(f"k={k} not divisible by stripe axis {n_stripe}")

    def local_step(bigm_local, data_local):
        # data_local: (k/n, N); bigm_local: (8m, 8k/n) column slice
        nloc, nbytes = data_local.shape
        nb = nbytes // block_size
        bits = jax_ec._unpack_bits_rows(data_local)
        partial = jax.lax.dot_general(
            bigm_local,
            bits,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (8m, N) partial GF sums
        partial = partial.reshape(8 * m, nb, block_size)
        # reduce-scatter over the block axis: parity arrives block-sharded
        partial = jax.lax.psum_scatter(
            partial, axis, scatter_dimension=1, tiled=True
        )  # (8m, nb/n, block_size)
        nb_loc = partial.shape[1]
        parity_bits = (partial & 1).reshape(8 * m, nb_loc * block_size)
        parity_local = jax_ec._pack_bits_rows(parity_bits)  # (m, nb_loc*bs)
        parity_local = parity_local.reshape(m, nb_loc, block_size)
        dcrc = jax_ec.block_crcs(
            data_local.reshape(nloc * nb, block_size), block_size
        ).reshape(nloc, nb)
        pcrc = jax_ec.block_crcs(
            parity_local.reshape(m * nb_loc, block_size), block_size
        ).reshape(m, nb_loc)
        return parity_local, dcrc, pcrc

    if block_axis is None:
        in_specs = (P(None, axis), P(axis, None))
        out_specs = (P(None, axis, None), P(axis, None), P(None, axis))
    else:
        # 2-D: parts over 'stripe', block ranges over 'block' (pure data
        # parallelism, zero communication on that axis). The scattered
        # parity's block dim is partitioned by 'block' first, then by
        # the reduce-scatter within each block group.
        in_specs = (P(None, stripe_axis), P(stripe_axis, block_axis))
        out_specs = (
            P(None, (block_axis, stripe_axis), None),
            P(stripe_axis, block_axis),
            P(None, (block_axis, stripe_axis)),
        )

    step = jax.jit(
        jax.shard_map(
            local_step, mesh=mesh, in_specs=in_specs, out_specs=out_specs
        )
    )

    def run(data):
        nb = data.shape[1] // block_size
        if data.shape[1] % block_size or nb % (n_stripe * n_block):
            raise ValueError(
                f"data bytes per part must be nb*{block_size} with nb "
                f"divisible by mesh extent {n_stripe * n_block}; got "
                f"{data.shape[1]}"
            )
        bigm = jnp.asarray(jax_ec.encoding_bitmatrix(k, m))
        return step(bigm, data)

    return run
