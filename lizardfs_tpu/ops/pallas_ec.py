"""Pallas TPU kernels: fused GF(2^8) encode and batched CRC32.

Why Pallas here: the XLA bit-plane path materializes the 8x bit
expansion in HBM (512 MiB of int8 bits per 64 MiB chunk) and pays for
small-matmul launches; these kernels unpack bits **inside VMEM**, run
the GF(2) matmuls on the MXU as s8 x s8 -> s32 (0/1 values: exact, and
int8 runs at twice the bf16 rate), and write only real bytes back — HBM
traffic collapses to data-in + parity-out.

Kernels:
  * :func:`encode` — grid over column tiles of the (k, N) part streams;
    each step unpacks a (k, T) byte tile to (8k, T) bit planes,
    multiplies by the expanded (8m, 8k) generator matrix, reduces mod 2
    and packs to (m, T) parity bytes.
  * :func:`block_crcs` — grid over 64 KiB blocks; each step unpacks one
    block to (1024, 512) sub-block bit rows, multiplies by the constant
    (512, 32) sub-block CRC matrix, then folds the 1024 partial
    registers with a 10-level log-tree of 32x32 shift matrices
    (:mod:`lizardfs_tpu.ops.crc32` machinery).

Numerics are byte-identical to the golden path (tests enforce it).

Every entry point compiles through Mosaic and so needs a TPU backend;
``interpret=True`` — passed explicitly, by tests on the CPU platform —
runs the same kernel in the Pallas interpreter. Nothing here infers it
from the platform: a product call on a device without Mosaic raises.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lizardfs_tpu.constants import MFSBLOCKSIZE
from lizardfs_tpu.ops import crc32 as crc_host

CRC_SUBBLOCK = 64


def _unpack_tile(bytes_tile: jnp.ndarray) -> jnp.ndarray:
    """(r, T) uint8 -> (8r, T) int8 bit planes; row j*8+b = bit b."""
    r, t = bytes_tile.shape
    x = bytes_tile.astype(jnp.int32)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (r, 8, t), 1)
    bits = (x[:, None, :] >> shifts) & 1
    return bits.reshape(8 * r, t).astype(jnp.int8)


def _stack_q(m: int, tile: int, max_groups: int) -> int:
    """Column-stacking factor q (see _encode_tile): doubles while the
    stacked matmul's M dim stays within _ENC_STACK_MAX, quarters stay
    lane-aligned, and q stays within ``max_groups`` (the fused kernel
    also caps q by its CRC group count so both see the same quarters).
    Pure in (m, tile, max_groups) so the VMEM budget can price the
    stacked generator before committing to a tile size."""
    q = 1
    while (
        2 * q * 8 * m <= _ENC_STACK_MAX
        and tile % (2 * q * 128) == 0
        and 2 * q <= max_groups
    ):
        q *= 2
    return q


def _stack_generator(bigm, k: int, m: int, tile: int, max_groups: int):
    """Build the block-diagonal (q*8m, q*8k) generator for q column
    quarters stacked along the contraction dim."""
    q = _stack_q(m, tile, max_groups)
    bigm_q = jnp.zeros((q * 8 * m, q * 8 * k), dtype=jnp.int8)
    for i in range(q):
        bigm_q = bigm_q.at[
            i * 8 * m:(i + 1) * 8 * m, i * 8 * k:(i + 1) * 8 * k
        ].set(bigm.astype(jnp.int8))
    return q, bigm_q


def _encode_kernel(bigm_ref, data_ref, parity_ref, *, m: int, q: int):
    parity_ref[:] = _encode_tile(bigm_ref, data_ref[:], m, q)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def encode(bigm: jnp.ndarray, data: jnp.ndarray, tile: int = 16384,
           interpret: bool = False) -> jnp.ndarray:
    """Fused bit-plane RS encode: (k, N) uint8 -> (m, N) uint8 parity.

    ``bigm`` is the (8m, 8k) expanded generator/recovery matrix.
    Serves both encode and recover (the matrix decides).
    """
    k, n = data.shape
    m = bigm.shape[0] // 8
    # keep bits (int8) + accumulator (int32) + tiles within a
    # conservative VMEM budget
    while tile > 512 and (9 * k + 33 * m) * tile > 8 * 2**20:
        tile //= 2
    if n % tile:
        raise ValueError(f"N={n} not a multiple of tile={tile}")
    q, bigm_q = _stack_generator(bigm, k, m, tile, max_groups=tile // 128)
    grid = (n // tile,)
    return pl.pallas_call(
        functools.partial(_encode_kernel, m=m, q=q),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.uint8),
        grid=grid,
        in_specs=[
            pl.BlockSpec((q * 8 * m, q * 8 * k), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((m, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
        interpret=interpret,
    )(bigm_q, data)


CRC_BLOCKS_PER_STEP = 16


def _crc_partial_kernel(csub_ref, subs_ref, out_ref):
    """Per-sub-block CRC registers: the heavy stage, MXU-bound.

    Sub-blocks are 128 bytes (full vreg lane width). Each bit plane is
    extracted in the uint8 domain and immediately contracted against its
    (128, 32) slice of the sub-block matrix; partial registers go back
    to HBM and a cheap XLA log-tree folds them (32-wide data: the fold
    is ~0.1% of the input volume, not worth fighting Mosaic layouts).
    """
    x = subs_ref[:]  # (rows, 128) uint8
    rows = x.shape[0]
    acc = jnp.zeros((rows, 32), jnp.float32)
    for b in range(8):
        plane = ((x & jnp.uint8(1 << b)) != 0).astype(jnp.bfloat16)
        acc += jax.lax.dot_general(
            plane, csub_ref[b],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    out_ref[:] = acc.astype(jnp.int32) & 1  # exact: sums <= 1024


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def block_crcs(blocks: jnp.ndarray, block_size: int = MFSBLOCKSIZE,
               interpret: bool = False) -> jnp.ndarray:
    """CRC32 of each row of (B, block_size) uint8 -> (B,) uint32."""
    b = blocks.shape[0]
    sub = 2 * CRC_SUBBLOCK  # 128-byte sub-blocks: full lane width
    nsub = block_size // sub
    assert nsub & (nsub - 1) == 0, "block size must give power-of-two sub-blocks"
    g = CRC_BLOCKS_PER_STEP
    bp = (b + g - 1) // g * g  # pad block count to the per-step group size
    if bp != b:
        blocks = jnp.concatenate(
            [blocks, jnp.zeros((bp - b, block_size), jnp.uint8)], axis=0
        )
    c_sub, levels, k_const = crc_host.block_crc_matrices(block_size, sub)
    # per-bit-plane slices of C^T: row t of plane b = column for bit b of
    # byte t (C^T row order is 8*t + b)
    csub_t = np.asarray(c_sub.T, dtype=np.float32)  # (8*sub, 32)
    csub_planes = np.stack([csub_t[bb::8, :] for bb in range(8)])  # (8, sub, 32)

    subs = blocks.reshape(bp * nsub, sub)
    partial = pl.pallas_call(
        _crc_partial_kernel,
        out_shape=jax.ShapeDtypeStruct((bp * nsub, 32), jnp.int32),
        grid=(bp // g,),
        in_specs=[
            pl.BlockSpec(csub_planes.shape, lambda i: (0, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((g * nsub, sub), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((g * nsub, 32), lambda i: (i, 0), memory_space=pltpu.VMEM),
        interpret=interpret,
    )(jnp.asarray(csub_planes, dtype=jnp.bfloat16), subs)

    # XLA log-tree fold + finalize (tiny: 32 ints per sub-block)
    part = partial.reshape(bp, nsub, 32)
    for mat in levels:
        part = part.reshape(bp, -1, 2, 32)
        left = jax.lax.dot_general(
            part[:, :, 0, :], jnp.asarray(mat.T, dtype=jnp.int32),
            dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        ) & 1
        part = left ^ part[:, :, 1, :]
    reg = part.reshape(bp, 32).astype(jnp.uint32)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    crc = (reg * weights[None, :]).sum(axis=1, dtype=jnp.uint32)
    return (crc ^ jnp.uint32(k_const))[:b]


# ---------------------------------------------------------------------------
# single-pass fused encode + CRC
#
# One pallas_call per column chunk: the data tile is read from HBM once;
# parity is computed on the MXU and written out; CRC partial registers
# for BOTH the data rows and the fresh parity rows are computed and
# folded to one 32-bit register per (row, chunk) while everything is
# still in VMEM. Only the registers (32 ints per row per chunk — ~0.1%
# of the data volume) leave the kernel; a tiny XLA epilogue combines the
# per-chunk registers of each 64 KiB block and applies the affine
# constant. Semantics match the reference's encode + per-block mycrc32
# (src/common/reed_solomon.h:134-155, crc.cc:49-64).

CRC_SUB = 128  # sub-block bytes = one full vreg lane width


def _fused_vmem_bytes(k: int, m: int, tile: int, wide: bool = False) -> int:
    rows = k + m
    kp, mp = -(-k // 8) * 8, -(-m // 8) * 8
    sg = max(tile // CRC_GROUP, 1)
    q = _stack_q(m, tile, max_groups=sg)
    return (
        2 * k * tile            # data in (x2 pipeline)
        + 2 * m * tile          # parity out (x2 pipeline)
        + 8 * k * tile          # unpacked bits, int8 (q-stacked: same)
        + 32 * m * tile         # encode accumulator, int32
        + m * tile              # packed parity bytes
        + 8 * rows * tile       # crc stacked bit planes, int8
        + rows * sg * 32 * 8    # crc acc + scan registers, int32
        + (kp * k + mp * m) * sg      # selection matrices, int8
        + 16 * 32 * 32          # shift stack, int8
        + 64 * q * q * k * m    # block-diagonal bigm_q (q*8m x q*8k int8)
        # wide CRC (ROOFLINE #3): 128-lane stage-1 acc (4x) + 4x W
        + (rows * sg * 32 * 16 + 3 * 8 * CRC_GROUP * 32 if wide else 0)
    )


CRC_GROUP = 512  # stage-1 group bytes: M = rows*T/512 fills MXU sublanes
_ENC_STACK_MAX = 128  # cap on q*8m when stacking column quarters


def _chunk_registers(x, w_ref, shifts_ref, sel_ref, group: int,
                     wide: bool = False):
    """(rows, T) uint8 tile -> (rp, 32) GF(2) CRC registers (rp = rows
    padded to x8 by the selection matrix). Extracts the bit planes and
    delegates to :func:`_registers_from_planes`."""
    rows, t = x.shape
    sc = t // group
    groups = x.reshape(rows * sc, group)
    planes = jnp.concatenate(
        [((groups & jnp.uint8(1 << b)) != 0).astype(jnp.int8)
         for b in range(8)],
        axis=1,
    )  # (n, 8G), plane-major along lanes (W rows match this order)
    return _registers_from_planes(planes, w_ref, shifts_ref, sel_ref,
                                  sc, wide)


def _registers_from_planes(planes, w_ref, shifts_ref, sel_ref, sc: int,
                           wide: bool):
    """(rows*sc, 8G) bit planes -> (rp, 32) GF(2) CRC registers.

    Stage 1 (MXU): one matmul computes the CRC register of every
    ``group``-byte span: the 8 bit planes are concatenated along the
    contraction dim and W has the per-byte-position shift matrices
    folded in, so (rows*Sc, 8G) @ (8G, 32) runs at full M and K
    utilisation (vs. 8 thin matmuls + a long fold in earlier
    revisions). Stage 2: Hillis-Steele suffix scan over each row's Sc
    group registers — level l combines spans of 2^l groups with one
    shared 32x32 shift matmul plus a sublane roll and an iota mask (no
    lane/sublane shape casts, which Mosaic cannot lower). Stage 3
    (MXU): a 0/1 selection matmul extracts each row's j=0 register
    straight into the padded output layout. All in VMEM: no
    partial-register round trip through HBM (the round-1 bottleneck).

    ``wide`` (ROOFLINE #3): stage 1's natural N=32 output fills only a
    quarter of the MXU's 128-lane output tile. The wide path multiplies
    against a (8G, 128) W whose four 32-column blocks are the register
    PRE-SHIFTED by 3G/2G/1G/0 bytes — same MXU tile count, 4x useful
    output — then folds each aligned run of 4 group registers with one
    lane select + two roll/XOR levels, replacing the first two scan
    LEVELS' 32x32 matmuls and shrinking the scan to sc/4 spans.
    """
    n = planes.shape[0]
    acc = jax.lax.dot_general(
        planes, w_ref[:],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # s8 x s8 -> s32 MXU: exact, 2x the bf16 rate, half the VMEM
    if not wide:
        g = acc & 1  # (n, 32) group registers (i32: pltpu.roll needs 32b)
        j = jax.lax.broadcasted_iota(jnp.int32, (n, 32), 0) & (sc - 1)
        levels = sc.bit_length() - 1
        span = 1  # groups per scan element
    else:
        g128 = acc & 1  # (n, 128): lane block v = register << (v*G bytes)
        # row for group s needs block 3 - s%4 (its position inside the
        # 4-group span); select it into lanes 0..31 and XOR the 4
        # consecutive rows together -> span register at rows s%4 == 0
        j128 = jax.lax.broadcasted_iota(jnp.int32, (n, 128), 0) & (sc - 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (n, 128), 1)
        want = 3 - (j128 & 3)
        vals = jnp.where((lane >> 5) == want, g128, 0)
        masked = (vals[:, :32] ^ vals[:, 32:64]
                  ^ vals[:, 64:96] ^ vals[:, 96:128])
        r1 = masked ^ pltpu.roll(masked, n - 1, axis=0)
        g = r1 ^ pltpu.roll(r1, n - 2, axis=0)  # rows s%4==0: span regs
        j = jax.lax.broadcasted_iota(jnp.int32, (n, 32), 0) & (sc - 1)
        j = j >> 2  # span index; garbage rows never feed valid ones
        sc = sc // 4
        levels = sc.bit_length() - 1
        span = 4
    for l in range(levels):
        h = 1 << l
        # g'_j = g_j @ S^(span*G*h bytes)  ^  g_{j+h}  (0 past row end)
        shifted = jax.lax.dot_general(
            g.astype(jnp.int8), shifts_ref[l],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        ) & 1
        nxt = pltpu.roll(g, n - span * h, axis=0)  # g[i+span*h] at i
        nxt = jnp.where(j < sc - h, nxt, 0)
        g = shifted ^ nxt
    reg = jax.lax.dot_general(
        sel_ref[:], g.astype(jnp.int8),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (rp, 32); exact: one 1 per selection row
    return reg & 1


def _encode_tile(bigm_ref, data, m: int, q: int):
    """RS-encode one (k, T) tile -> (m, T) parity bytes.

    ``q`` column quarters are stacked along the contraction dim against
    a block-diagonal generator (q*8m, q*8k): the parity matmul's M dim
    grows from 8m (as low as 8) to q*8m ~ 128, filling the MXU's output
    tile instead of wasting 7/8 of it. (The unused bit-plane outputs
    are dead-code-eliminated under tracing.)
    """
    packed, _bits, _pbits = _encode_tile_bits(bigm_ref, data, m, q)
    return packed


def _encode_tile_bits(bigm_ref, data, m: int, q: int):
    """_encode_tile variant that also returns the UNPACKED bit planes
    of both the data ((q*8k, Tq) int8) and the parity ((q*8m, Tq)
    int8), so the CRC stage can consume them instead of re-deriving
    planes from packed bytes (ROOFLINE #2: the re-extraction costs ~8
    VPU ops per byte over all k+m rows)."""
    k, t = data.shape
    tq = t // q
    if q == 1:
        bits = _unpack_tile(data)
    else:
        bits = jnp.concatenate(
            [_unpack_tile(data[:, i * tq:(i + 1) * tq]) for i in range(q)],
            axis=0,
        )  # (q*8k, Tq)
    acc = jax.lax.dot_general(
        bigm_ref[:], bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (q*8m, Tq)
    pbits = acc & 1
    weights = jax.lax.broadcasted_iota(jnp.int32, (q * m, 8, tq), 1)
    packed = (pbits.reshape(q * m, 8, tq) << weights).sum(axis=1)
    packed = packed.astype(jnp.uint8)  # (q*m, Tq), quarter-major rows
    if q != 1:
        packed = jnp.concatenate(
            [packed[i * m:(i + 1) * m, :] for i in range(q)], axis=1
        )  # (m, T)
    return packed, bits, pbits.astype(jnp.int8)


def _planes_from_bits(bits, rows: int, q: int, tq: int, group: int):
    """(q*8rows, Tq) quarter-major bit rows -> (rows*sc, 8G) group-major
    CRC planes, by pure in-VMEM relayout (no re-extraction). Element
    mapping: bit b of byte (row j, abs col i_q*Tq + s_local*G + p) lives
    at bits[i_q*8rows + j*8 + b, s_local*G + p] and must land at
    planes[j*sc + (i_q*scq + s_local), b*G + p]."""
    scq = tq // group
    b = bits.reshape(q, rows, 8, scq, group)
    b = b.transpose(1, 0, 3, 2, 4)  # (rows, q, scq, 8, G)
    return b.reshape(rows * q * scq, 8 * group)


def _fused_kernel(bigm_ref, w_ref, shifts_ref, seld_ref, selp_ref,
                  data_ref, parity_ref, dreg_ref, preg_ref,
                  *, m: int, q: int, group: int, wide: bool = False,
                  reuse: bool = False):
    data = data_ref[:]
    k, t = data.shape
    if reuse:
        tq = t // q
        parity, bits, pbits = _encode_tile_bits(bigm_ref, data, m, q)
        parity_ref[:] = parity
        sc = t // group
        dreg_ref[:] = _registers_from_planes(
            _planes_from_bits(bits, k, q, tq, group),
            w_ref, shifts_ref, seld_ref, sc, wide,
        )
        preg_ref[:] = _registers_from_planes(
            _planes_from_bits(pbits, m, q, tq, group),
            w_ref, shifts_ref, selp_ref, sc, wide,
        )
        return
    parity = _encode_tile(bigm_ref, data, m, q)
    parity_ref[:] = parity
    dreg_ref[:] = _chunk_registers(
        data, w_ref, shifts_ref, seld_ref, group, wide
    )
    preg_ref[:] = _chunk_registers(
        parity, w_ref, shifts_ref, selp_ref, group, wide
    )


# Three configurations: the default below, BIG_TILE_CONFIG and
# ROOFLINE_CONFIG. All three compiled through Mosaic and matched the
# golden codec at 64 KiB blocks / 64 MiB chunks for ec(8,4) and ec(3,2)
# on a TPU v5e (jax 0.9.0, libtpu 0.0.34; chip_smoke.py compiles them
# on every run). Which is fastest has not been measured; production
# callers use the default until a chip run arbitrates (ROADMAP S4).
_FUSED_VMEM_BUDGET = 10 * 2**20
# 11.5 MiB of ~16 MiB physical: ec(8,4) fits tile=32 KiB (10.1 MiB ->
# 256 steps/chunk, 2x fewer), ec(3,2) a full 64 KiB block
BIG_TILE_CONFIG = {"tile": 65536, "vmem_budget": 11_534_336}
# ROOFLINE items 2+3 on top of the big tiles: wide_crc fills the CRC
# stage-1 matmul's 128-lane output tile (4 pre-shifted register
# variants) and removes two scan levels; reuse_planes feeds the CRC
# stage from the encode's already-unpacked bit planes via in-VMEM
# relayout instead of re-extracting (~8 VPU ops/byte over k+m rows).
# Byte parity of every combination is pinned in interpret mode
# (tests/test_pallas.py).
ROOFLINE_CONFIG = {
    "tile": 65536, "vmem_budget": 11_534_336,
    "wide_crc": True, "reuse_planes": True,
}


@functools.partial(
    jax.jit, static_argnames=(
        "block_size", "tile", "interpret", "vmem_budget", "wide_crc",
        "reuse_planes",
    )
)
def fused_encode_crc(
    bigm: jnp.ndarray,
    data: jnp.ndarray,
    block_size: int = MFSBLOCKSIZE,
    tile: int = 16384,
    interpret: bool = False,
    vmem_budget: int = _FUSED_VMEM_BUDGET,
    wide_crc: bool = False,
    reuse_planes: bool = False,
):
    """Single-pass fused RS encode + per-block CRC32.

    (k, N) uint8 -> (parity (m, N) uint8, dcrc (k, nb) u32, pcrc (m, nb)
    u32), byte-identical to jax_ec.fused_encode_crc / the golden codec.

    ``tile`` shrinks until it fits the VMEM budget, divides the block
    size, and divides N. Pass ``**BIG_TILE_CONFIG`` (ROOFLINE #1) or
    ``**ROOFLINE_CONFIG`` (#1+#2+#3: + wide 128-lane CRC stage-1,
    + bit-plane reuse) for the staged alternatives.
    """
    k, n = data.shape
    m = bigm.shape[0] // 8
    rows = k + m
    while tile > 2 * CRC_SUB and (
        _fused_vmem_bytes(k, m, tile, wide_crc) > vmem_budget
        or block_size % tile or n % tile
    ):
        tile //= 2
    if n % tile:
        raise ValueError(f"N={n} not a multiple of tile={tile}")
    if block_size % tile:
        raise ValueError(f"tile={tile} must divide block_size={block_size}")
    if tile & (tile - 1):
        raise ValueError(
            f"tile={tile} must be a power of two (the CRC scan doubles "
            f"span lengths per level and quarters must stay lane-aligned)"
        )
    nchunks = n // tile
    cpb = block_size // tile  # chunks per 64 KiB block
    nb = n // block_size

    group = min(CRC_GROUP, tile)
    sg = tile // group  # group registers per row per tile
    # the wide fold needs aligned runs of 4 group registers per row
    wide = bool(wide_crc) and sg % 4 == 0 and sg >= 4
    c_sub, _levels, k_const = crc_host.block_crc_matrices(block_size, group)
    # W rows match the kernel's plane-major lane concat: row b*G+p = bit
    # b of byte position p (row 8p+b of C_G^T)
    ct = np.asarray(c_sub.T, dtype=np.float32)  # (8G, 32), rows 8p+b
    w = np.concatenate([ct[b::8, :] for b in range(8)], axis=0)
    if wide:
        # (8G, 128): column block v = the group register pre-shifted by
        # v*G bytes (W @ S(vG)^T over GF(2)); the kernel's lane select
        # assigns block 3 - s%4 to group s
        w64 = w.astype(np.int64)
        w = np.concatenate([
            (w64 @ crc_host.shift_matrix(v * group).T.astype(np.int64)) % 2
            for v in range(4)
        ], axis=1).astype(np.float32)
    # scan shift matrices: level l combines spans of 2^l scan elements
    # (4 groups per element on the wide path), so every row uses the
    # SAME shift matrix at that level
    span_bytes = group * (4 if wide else 1)
    levels = (sg // (4 if wide else 1)).bit_length() - 1
    shifts = np.zeros((max(levels, 1), 32, 32), dtype=np.float32)
    for l in range(levels):
        shifts[l] = crc_host.shift_matrix(span_bytes * (1 << l)).T
    kp, mp = -(-k // 8) * 8, -(-m // 8) * 8  # register rows padded to x8
    # 0/1 selection matrices: row r of the padded output takes the
    # scanned register at sub-row r*sg (row r's full-span register)
    seld = np.zeros((kp, k * sg), dtype=np.float32)
    seld[np.arange(k), np.arange(k) * sg] = 1.0
    selp = np.zeros((mp, m * sg), dtype=np.float32)
    selp[np.arange(m), np.arange(m) * sg] = 1.0
    q, bigm_q = _stack_generator(bigm, k, m, tile, max_groups=sg)
    # plane reuse needs whole groups inside each stacked quarter
    reuse = bool(reuse_planes) and (tile // q) % group == 0 and tile >= group
    # G: combines the cpb chunk registers of one block in XLA (tiny)
    comb = np.zeros((cpb * 32, 32), dtype=np.int32)
    for c in range(cpb):
        comb[c * 32:(c + 1) * 32, :] = \
            crc_host.shift_matrix(tile * (cpb - 1 - c)).T

    kernel = functools.partial(
        _fused_kernel, m=m, q=q, group=group, wide=wide, reuse=reuse
    )
    parity, dreg, preg = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((m, n), jnp.uint8),
            jax.ShapeDtypeStruct((nchunks * kp, 32), jnp.int32),
            jax.ShapeDtypeStruct((nchunks * mp, 32), jnp.int32),
        ),
        grid=(nchunks,),
        in_specs=[
            pl.BlockSpec(bigm_q.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(w.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(shifts.shape, lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(seld.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(selp.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, tile), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((m, tile), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((kp, 32), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((mp, 32), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ),
        interpret=interpret,
    )(
        bigm_q,
        jnp.asarray(w, dtype=jnp.int8),
        jnp.asarray(shifts, dtype=jnp.int8),
        jnp.asarray(seld, dtype=jnp.int8),
        jnp.asarray(selp, dtype=jnp.int8),
        data,
    )

    def finalize(regs, nrows, npad):
        # (nchunks*npad, 32) -> (nrows, nb) final CRC values
        r = regs.reshape(nb, cpb, npad, 32)[:, :, :nrows, :]
        r = r.transpose(2, 0, 1, 3)
        r = r.reshape(nrows, nb, cpb * 32)
        folded = jax.lax.dot_general(
            r, jnp.asarray(comb),
            dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        ) & 1  # (nrows, nb, 32)
        w = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
        crc = (folded.astype(jnp.uint32) * w).sum(axis=2, dtype=jnp.uint32)
        return crc ^ jnp.uint32(k_const)

    return parity, finalize(dreg, k, kp), finalize(preg, m, mp)


@functools.partial(
    jax.jit, static_argnames=(
        "block_size", "interpret", "tile", "vmem_budget", "wide_crc",
        "reuse_planes",
    )
)
def fused_decode_verify(
    bigm_rec: jnp.ndarray,
    survivors: jnp.ndarray,
    expected_crcs: jnp.ndarray,
    block_size: int = MFSBLOCKSIZE,
    interpret: bool = False,
    tile: int = 16384,
    vmem_budget: int = _FUSED_VMEM_BUDGET,
    wide_crc: bool = False,
    reuse_planes: bool = False,
):
    """Fused reconstruct + CRC verify of the recovered parts.

    ``bigm_rec`` is the (8r, 8k) recovery matrix mapping survivor rows
    to the r missing parts (gf256.recovery matrix via the encoder
    boundary); returns (recovered (r, N) uint8, crcs (r, nb) u32,
    ok (r, nb) bool) where ok compares against ``expected_crcs`` — the
    stored per-block CRCs of the lost parts (ReadPlanExecutor's
    post-recovery verify, reference read_plan_executor.cc + crc.cc).
    """
    recovered, _scrc, rcrc = fused_encode_crc(
        bigm_rec, survivors, block_size, interpret=interpret,
        tile=tile, vmem_budget=vmem_budget, wide_crc=wide_crc,
        reuse_planes=reuse_planes,
    )
    return recovered, rcrc, rcrc == expected_crcs.astype(jnp.uint32)
