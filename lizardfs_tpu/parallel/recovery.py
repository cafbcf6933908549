"""Mesh-sharded wide-stripe reconstruction: rebuild lost parts over ICI.

The decode half of :mod:`lizardfs_tpu.parallel.sharded` — the multichip
story was encode-only while rebuilding lost parts is the reference
replicator's hot loop (reference: src/common/ec_read_plan.h:113-146
recovery read plans, src/chunkserver/slice_recovery_planner.h:29-38).
The formulation is the SAME psum-scatter SPMD matmul as
``sharded_encode_with_crcs``, driven by the *recovery* bit-matrix
instead of the generator:

  * the k surviving parts (chosen by :func:`gf256.recovery_selection`,
    the shared dispatch rule — CPU/TPU/mesh stay byte-identical by
    construction) are sharded over mesh axis "stripe",
  * each chip multiplies its survivor slice by its column slice of the
    expanded (8w, 8k) recovery matrix — a *partial* GF(2) sum,
  * partials meet in a ``psum_scatter`` over the block dimension, so
    the rebuilt parts land block-sharded for the post-rebuild CRC
    (computed locally on whichever chip owns the block),
  * the caller compares those CRCs against the stored per-block CRCs
    of the lost parts — the ReadPlanExecutor's post-recovery verify.

This mirrors the efficient-decoding line of Cauchy MDS array codes
(arxiv 1611.09968: decode is the same bit-matrix product as encode,
with a different constant matrix) — which is exactly what makes the
encode program reusable: only the (8w, 8k) constant changes.

``LZ_SHARDED_RECOVERY=0`` is the subsystem kill switch: the encoder
auto-ladder skips the sharded backend and every ``enabled()`` check
short-circuits to the single-chip paths.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from lizardfs_tpu.ops import gf256, jax_ec


def enabled() -> bool:
    """The subsystem kill switch (``LZ_SHARDED_RECOVERY=0`` disables)."""
    from lizardfs_tpu.constants import env_flag

    return env_flag("LZ_SHARDED_RECOVERY")


def sharded_reconstruct_with_crcs(
    mesh, k: int, m: int, available: list[int], wanted: list[int],
    block_size: int,
):
    """Build a jitted mesh-sharded reconstruct+CRC step.

    Parts are globally indexed 0..k+m-1 (data first).  ``available``
    are the live part indices (>= k of them), ``wanted`` the lost ones
    (up to m).  Returns ``run(survivors)`` where ``survivors`` is
    (k, nb*block_size) holding the **used** parts (``run.used`` — the
    selection rule's choice, ascending) stacked in that order; outputs
    are (recovered (w, nb, block_size) block-sharded, crcs (w, nb)) —
    byte-identical to the cpu/cpp/jax single-chip recover for any
    erasure pattern.  nb and k must divide the mesh like the encode
    step.
    """
    stripe_axis = mesh.axis_names[0]
    n_stripe = mesh.shape[stripe_axis]
    block_axis = mesh.axis_names[1] if len(mesh.axis_names) > 1 else None
    n_block = mesh.shape[block_axis] if block_axis else 1
    if k % n_stripe:
        raise ValueError(f"k={k} not divisible by stripe axis {n_stripe}")
    used, _ = gf256.recovery_selection(k, m, list(available), list(wanted))
    w = len(wanted)
    bigm_host = jax_ec.recovery_bitmatrix(
        k, m, tuple(used), tuple(wanted)
    )  # (8w, 8k) over the used parts, ascending

    def local_step(bigm_local, surv_local):
        # surv_local: (k/n, N) used-part slice; bigm_local: (8w, 8k/n)
        bits = jax_ec._unpack_bits_rows(surv_local)
        partial = jax.lax.dot_general(
            bigm_local,
            bits,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (8w, N) partial GF sums
        nb = surv_local.shape[1] // block_size
        partial = partial.reshape(8 * w, nb, block_size)
        partial = jax.lax.psum_scatter(
            partial, stripe_axis, scatter_dimension=1, tiled=True
        )  # (8w, nb/n, block_size)
        nb_loc = partial.shape[1]
        rec_bits = (partial & 1).reshape(8 * w, nb_loc * block_size)
        rec_local = jax_ec._pack_bits_rows(rec_bits)  # (w, nb_loc*bs)
        rec_local = rec_local.reshape(w, nb_loc, block_size)
        rcrc = jax_ec.block_crcs(
            rec_local.reshape(w * nb_loc, block_size), block_size
        ).reshape(w, nb_loc)
        return rec_local, rcrc

    if block_axis is None:
        in_specs = (P(None, stripe_axis), P(stripe_axis, None))
        out_specs = (P(None, stripe_axis, None), P(None, stripe_axis))
    else:
        in_specs = (P(None, stripe_axis), P(stripe_axis, block_axis))
        out_specs = (
            P(None, (block_axis, stripe_axis), None),
            P(None, (block_axis, stripe_axis)),
        )

    step = jax.jit(
        jax.shard_map(
            local_step, mesh=mesh, in_specs=in_specs, out_specs=out_specs
        )
    )

    def run(survivors):
        if survivors.shape[0] != k:
            raise ValueError(
                f"need the {k} used parts stacked, got {survivors.shape[0]}"
            )
        nb = survivors.shape[1] // block_size
        if survivors.shape[1] % block_size or nb % (n_stripe * n_block):
            raise ValueError(
                f"part bytes must be nb*{block_size} with nb divisible "
                f"by mesh extent {n_stripe * n_block}; got "
                f"{survivors.shape[1]}"
            )
        return step(jnp.asarray(bigm_host), survivors)

    run.used = used
    return run


def sharded_reconstruct_verify(
    mesh, k: int, m: int, available: list[int], wanted: list[int],
    survivors_by_part: dict[int, np.ndarray], block_size: int,
    expected_crcs: np.ndarray | None = None,
):
    """One-shot reconstruct + post-rebuild CRC verify.

    ``survivors_by_part`` maps live global part index -> byte stream;
    ``expected_crcs`` (w, nb) are the stored per-block CRCs of the lost
    parts.  Returns (recovered (w, N) np.uint8, crcs (w, nb) np.uint32,
    ok bool) — ``ok`` is True when every rebuilt block checksums to its
    stored CRC (or no expectation was given).
    """
    run = sharded_reconstruct_with_crcs(
        mesh, k, m, available, wanted, block_size
    )
    stacked = np.stack([
        np.asarray(survivors_by_part[i], dtype=np.uint8) for i in run.used
    ])
    rec, rcrc = run(stacked)
    rec_np = np.asarray(rec).reshape(len(wanted), -1)
    rcrc_np = np.asarray(rcrc).astype(np.uint32)
    ok = True
    if expected_crcs is not None:
        ok = bool(
            np.array_equal(rcrc_np, np.asarray(expected_crcs, np.uint32))
        )
    return rec_np, rcrc_np, ok
