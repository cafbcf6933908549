"""The ChunkEncoder plugin boundary — the seam between the file system and
the erasure-coding compute backend.

Per the north star, everything in the framework that touches EC math
(client write path computing parity, client read path recovering erased
parts, chunkserver replicator rebuilding parts, chunkserver CRC
verify/update) dispatches through this interface, with interchangeable
backends:

  * ``CpuChunkEncoder`` — numpy golden path
    (:mod:`lizardfs_tpu.ops.rs`), byte-identical to the reference's
    ISA-L/galois_field codec. Correctness oracle and small-request path.
  * ``TpuChunkEncoder`` — JAX bit-plane kernels: plain XLA programs
    (:mod:`lizardfs_tpu.ops.jax_ec`) for encode/recover/xor, Pallas
    kernels (:mod:`lizardfs_tpu.ops.pallas_ec`) for checksum and the
    fused encode+CRC entry point.

The API mirrors the surface of the reference's ``ReedSolomon`` +
``mycrc32`` pair (reference: src/common/reed_solomon.h:87-155,
src/common/crc.h) with batching over whole parts, plus the fused
encode+checksum entry point used by the chunkserver write pipeline.
"""

from __future__ import annotations

import abc
import logging
import os

import numpy as np

from lizardfs_tpu.constants import MFSBLOCKSIZE
from lizardfs_tpu.ops import crc32, rs
from lizardfs_tpu.runtime import tracing

log = logging.getLogger("lizardfs.encoder")


class ChunkEncoder(abc.ABC):
    """EC compute backend interface.

    Parts are equal-length 1-D uint8 arrays (byte streams of chunk
    parts); part indices are global: 0..k-1 data, k..k+m-1 parity.
    """

    name: str

    @abc.abstractmethod
    def encode(
        self, k: int, m: int, data_parts: list[np.ndarray | None]
    ) -> list[np.ndarray]:
        """Compute the m parity parts from the k data parts (None = zeros)."""

    @abc.abstractmethod
    def recover(
        self,
        k: int,
        m: int,
        parts: dict[int, np.ndarray | None],
        wanted: list[int],
    ) -> dict[int, np.ndarray]:
        """Recover ``wanted`` global part indices from any >=k available parts."""

    @abc.abstractmethod
    def checksum(self, blocks: np.ndarray) -> np.ndarray:
        """CRC32 of each row of a (n, block_size) uint8 array -> (n,) uint32."""

    @abc.abstractmethod
    def encode_with_checksums(
        self, k: int, m: int, data: np.ndarray, block_size: int = MFSBLOCKSIZE
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Fused parity + per-block CRCs of data and parity.

        data: (k, N) with N a multiple of block_size. Returns
        (parity (m, N), data_crcs (k, N//bs), parity_crcs (m, N//bs)).
        """

    def xor_parity(self, parts: list[np.ndarray]) -> np.ndarray:
        """XOR parity (xor2..xor9 goals)."""
        return rs.xor_parity(parts)

    def encode_into(
        self,
        k: int,
        m: int,
        data_parts: list[np.ndarray],
        out: list[np.ndarray],
    ) -> None:
        """``encode`` writing the m parity streams into caller buffers.

        ``out`` holds m contiguous uint8 arrays (typically row slices of
        one send buffer) each the length of a data part. Backends that
        can emit parity in place override this to skip the staging copy
        (the client's pipelined write path sends straight from ``out``);
        this default stays correct everywhere else.
        """
        parity = self.encode(k, m, data_parts)
        for dst, src in zip(out, parity):
            np.copyto(dst, src)

    def xor_parity_into(
        self, parts: list[np.ndarray], out: np.ndarray
    ) -> None:
        """``xor_parity`` writing into a caller buffer (see encode_into)."""
        np.copyto(out, parts[0])
        for p in parts[1:]:
            np.bitwise_xor(out, p, out=out)


class CpuChunkEncoder(ChunkEncoder):
    """Golden numpy backend (reference-identical bytes)."""

    name = "cpu"

    def encode(self, k, m, data_parts):
        return rs.encode(k, m, data_parts)

    def recover(self, k, m, parts, wanted):
        return rs.recover(k, m, parts, wanted)

    def checksum(self, blocks):
        return crc32.block_crcs_golden(np.ascontiguousarray(blocks))

    def encode_with_checksums(self, k, m, data, block_size=MFSBLOCKSIZE):
        n = data.shape[1]
        nb = n // block_size
        parity = rs.encode(k, m, list(data))
        parity_arr = np.stack(parity)
        data_crcs = self.checksum(data.reshape(k * nb, block_size)).reshape(k, nb)
        parity_crcs = self.checksum(parity_arr.reshape(m * nb, block_size)).reshape(
            m, nb
        )
        return parity_arr, data_crcs, parity_crcs


# The phase rows of a call across the device boundary, the boundary's
# own and its four legs' (runtime.metrics: _BOUNDARY_PHASES): a GF(2^8)
# product's (encode, recover) and xor parity's, apart, so that a xor
# call moves no reader of the products' rows. The spans keep their
# names either way (``_LEG_SPANS``; the boundary's says ``op``).
_LEG_SPANS = ("dev_stage", "dev_put", "dev_run", "dev_fetch")
_RS_ROWS = ("boundary",) + _LEG_SPANS
_XOR_ROWS = tuple("xor_" + row for row in _RS_ROWS)


def _tpu_allow_cpu() -> bool:
    """LZ_TPU_ALLOW_CPU escape hatch (default OFF). Routed through the
    one spelling-parity accessor: the old bare-truthiness read meant
    ``LZ_TPU_ALLOW_CPU=0`` *enabled* the hatch (set, therefore truthy)
    — the exact inversion the kill-switch lint exists to prevent."""
    from lizardfs_tpu.constants import env_flag

    return env_flag("LZ_TPU_ALLOW_CPU", default=False)


class TpuChunkEncoder(ChunkEncoder):
    """JAX backend: bit-plane MXU matmuls, fused encode+CRC.

    Lazily imports jax so pure-CPU deployments never pay for it.

    Refuses to bind a CPU-platform JAX device unless explicitly forced
    (``force_cpu=True`` or ``LZ_TPU_ALLOW_CPU=1``): on a box without
    an accelerator the XLA bit-plane path is the slowest correct
    backend, so "tpu" must mean TPU — the auto resolution picks
    cpp/cpu there instead of landing here.

    The Pallas entry points (``checksum``, ``encode_with_checksums``)
    compile through Mosaic; a device without it is an error, never a
    quiet switch to another program. ``interpret=True`` (tests on the
    CPU platform) runs them in the Pallas interpreter instead.
    """

    name = "tpu"

    def __init__(self, device=None, *, force_cpu: bool = False,
                 interpret: bool = False):
        import jax

        from lizardfs_tpu.ops import jax_ec
        from lizardfs_tpu.runtime.jaxcache import configure_compile_cache

        self._jax = jax
        self._ops = jax_ec
        self._interpret = interpret
        # this process owns the chip: from now on its spans are also
        # annotations in the profiler's trace, beside the device's
        # operations (a daemon never builds this class or imports jax)
        tracing.register_annotator(
            jax.profiler.TraceAnnotation,
            jax.profiler.TraceAnnotation.is_enabled,
        )
        self._device = device if device is not None else jax.devices()[0]
        if getattr(self._device, "platform", "cpu") != "cpu":
            # before this process's first compile. Not for a CPU-bound
            # instance (tests): XLA:CPU's cached programs are tied to
            # the CPU features of the host that built them
            configure_compile_cache()
        if (
            not force_cpu
            and not _tpu_allow_cpu()
            and getattr(self._device, "platform", "cpu") == "cpu"
        ):
            raise RuntimeError(
                "TpuChunkEncoder bound a CPU-platform JAX device — the "
                "XLA bit-plane path is slower than the native SIMD "
                "backend on CPUs; pass force_cpu=True (tests/numerics) "
                "or set LZ_TPU_ALLOW_CPU=1 to override"
            )

    @property
    def device(self):
        """The jax device single-chip programs are placed on."""
        return self._device

    def _put(self, arr: np.ndarray):
        return self._jax.device_put(np.ascontiguousarray(arr), self._device)

    def _across(self, phases: tuple, stage, run, out=None,
                **attrs) -> np.ndarray:
        """One call across the boundary on the device: a ``boundary``
        span holding four, ``dev_stage`` (``stage()`` on the host: the
        operands as host arrays, the parts stacked last), ``dev_put``
        (each ``device_put``, until it returns), ``dev_run`` (``run``
        on the device operands, until it returns) and ``dev_fetch``
        (``np.asarray``, which blocks until upload, kernel and download
        are done; into ``out`` where the caller gives a buffer). No
        synchronisation is added: the device trace under ``dev_fetch``
        gives the kernel, the rest of it is transfer and wake-up.
        ``phases`` names the rows the five are charged to (``_RS_ROWS``
        or ``_XOR_ROWS``), so that one kind of call never moves the
        other's."""
        boundary, *legs = phases

        def leg(i: int):
            return tracing.span(_LEG_SPANS[i], layer="encoder",
                                phase=legs[i], bucket="compute")

        with tracing.span("boundary", layer="encoder", phase=boundary,
                          bucket="compute", **attrs) as sp:
            with leg(0):
                operands = stage()
                sp.attrs["rows"], sp.attrs["bytes"] = operands[-1].shape
            with leg(1):
                operands = [self._put(a) for a in operands]
            with leg(2):
                res = run(*operands)
            with leg(3):
                if out is None:
                    return np.asarray(res)
                np.copyto(out, np.asarray(res))
                return out

    def _apply_gf(self, op: str, k: int, m: int, matrix, rows) -> np.ndarray:
        """One GF(2^8) product across the boundary: ``matrix()`` times
        ``rows`` (the input parts in the matrix's column order, None =
        all zeros, whose columns are dropped); ``dev_stage`` builds the
        bit matrix and stacks the parts, ``dev_put`` puts both,
        ``dev_run`` is ``apply_gf`` (:meth:`_across`)."""
        live = [j for j, r in enumerate(rows) if r is not None]
        if not live:
            raise ValueError("at least one input part must be non-None")

        def stage():
            bigm = matrix()
            if len(live) < len(rows):
                bigm = bigm[:, np.concatenate(
                    [np.arange(8 * j, 8 * j + 8) for j in live])]
            return bigm, np.stack([np.asarray(rows[j]) for j in live])

        return self._across(_RS_ROWS, stage, self._ops.apply_gf,
                            op=op, k=k, m=m)

    def encode(self, k, m, data_parts):
        if len(data_parts) != k:
            raise ValueError(f"expected {k} data parts, got {len(data_parts)}")
        return list(self._apply_gf(
            "encode", k, m, lambda: self._ops.encoding_bitmatrix(k, m),
            data_parts,
        ))

    def recover(self, k, m, parts, wanted):
        from lizardfs_tpu.ops import gf256

        used, _ = gf256.recovery_selection(k, m, list(parts.keys()), wanted)
        out = self._apply_gf(
            "recover", k, m,
            lambda: self._ops.recovery_bitmatrix(
                k, m, tuple(used), tuple(wanted)),
            [parts[i] for i in used],
        )
        return {w: out[i] for i, w in enumerate(wanted)}

    def checksum(self, blocks):
        from lizardfs_tpu.ops import pallas_ec

        blocks = np.ascontiguousarray(blocks)
        return np.asarray(
            pallas_ec.block_crcs(
                self._put(blocks), blocks.shape[1],
                interpret=self._interpret,
            )
        ).astype(np.uint32)

    def _xor(self, parts, out=None) -> np.ndarray:
        """The XOR of xorN's parts across the boundary, through the
        staging ``_apply_gf`` takes: the parts stacked on the host,
        put, ``xor_reduce`` (a plain XOR needs no bit planes) and the
        fetch, charged to the ``xor_`` rows."""
        return self._across(
            _XOR_ROWS, lambda: (np.stack([np.asarray(p) for p in parts]),),
            self._ops.xor_reduce, out, op="xor", k=len(parts), m=1)

    def xor_parity(self, parts):
        return self._xor(parts)

    def xor_parity_into(self, parts, out):
        self._xor(parts, out)

    def encode_with_checksums(self, k, m, data, block_size=MFSBLOCKSIZE):
        from lizardfs_tpu.ops import pallas_ec

        bigm = self._ops.encoding_bitmatrix(k, m)
        parity, dcrc, pcrc = pallas_ec.fused_encode_crc(
            self._put(bigm), self._put(data), block_size,
            interpret=self._interpret,
        )
        return (
            np.asarray(parity),
            np.asarray(dcrc).astype(np.uint32),
            np.asarray(pcrc).astype(np.uint32),
        )


class ShardedTpuChunkEncoder(TpuChunkEncoder):
    """Mesh-sharded wide-stripe backend: ``recover`` rides the device
    mesh (parallel/recovery.py psum-scatter reconstruct) whenever the
    geometry divides it, falling back to the single-chip TPU kernels
    otherwise.  "auto" resolves to it when jax reports two or more
    accelerator devices — a chunkserver configured ``ENCODER = auto``
    (or ``sharded``) on a multichip box rebuilds through it;
    ``LZ_SHARDED_RECOVERY=0`` kills it (the constructor refuses AND a
    live instance degrades to single-chip at call time, so the switch
    works mid-flight).
    """

    name = "sharded"

    def __init__(self, mesh=None, *, force_cpu: bool = False):
        from lizardfs_tpu.parallel import recovery as rec

        if not rec.enabled():
            raise RuntimeError("sharded recovery disabled "
                               "(LZ_SHARDED_RECOVERY=0)")
        super().__init__(force_cpu=force_cpu)
        if mesh is None:
            if len(self._jax.devices()) < 2:
                raise RuntimeError("mesh-sharded recovery needs >= 2 "
                                   "devices")
            from lizardfs_tpu.parallel import sharded as sh

            mesh = sh.make_mesh()
        self._mesh = mesh
        self._n_mesh = int(np.prod(list(self._mesh.shape.values())))
        # reconstruct step cache: the shard_map closure (and its jit
        # cache) is reused per (geometry, erasure pattern) — the
        # replicator's steady state is a handful of patterns
        self._rec_steps: dict[tuple, object] = {}

    def _mesh_recover_step(self, k, m, avail, wanted, block_size):
        key = (k, m, avail, wanted, block_size)
        step = self._rec_steps.get(key)
        if step is None:
            from lizardfs_tpu.parallel import recovery as rec

            step = rec.sharded_reconstruct_with_crcs(
                self._mesh, k, m, list(avail), list(wanted), block_size
            )
            if len(self._rec_steps) > 64:
                self._rec_steps.clear()  # unbounded-pattern guard
            self._rec_steps[key] = step
        return step

    def recover(self, k, m, parts, wanted):
        from lizardfs_tpu.parallel import recovery as rec

        nbytes = next(
            (len(p) for p in parts.values() if p is not None), 0
        )
        # the mesh path needs: the kill switch open, k parts dividing
        # the stripe axis, byte length dividing the mesh into CRC-able
        # (64-byte multiple) blocks, and no elided (None) inputs
        block = nbytes // self._n_mesh if self._n_mesh else 0
        if (
            not rec.enabled()
            or k % self._n_mesh
            or nbytes == 0
            or nbytes % self._n_mesh
            or block % 64
            or any(p is None for p in parts.values())
        ):
            return super().recover(k, m, parts, wanted)
        avail = tuple(sorted(parts.keys()))
        wanted = list(wanted)
        step = self._mesh_recover_step(k, m, avail, tuple(wanted), block)
        stacked = np.stack([np.asarray(parts[i]) for i in step.used])
        out, _crcs = step(stacked)
        out = np.asarray(out).reshape(len(wanted), -1)
        return {w: out[i] for i, w in enumerate(wanted)}


_ENCODERS: dict[str, ChunkEncoder] = {}


def _resolve_auto() -> str:
    """Backend name "auto" stands for, decided from what jax reports.

    An accelerator is visible -> the device backend ("sharded" on two
    or more devices, else "tpu"); building it may fail and that error
    propagates — a broken device backend never turns into a CPU one.
    jax absent or CPU-only -> the host backend, logged with the reason.
    """
    from lizardfs_tpu.core import native

    host = "cpp" if native.available() else "cpu"
    try:
        import jax
    except ImportError:
        log.info("encoder auto -> %s: jax is not installed", host)
        return host
    try:
        devices = jax.devices()
    except RuntimeError as e:
        # a backend of the platform list would not start (on a TPU
        # host jax sets the list itself); libtpu's words for a chip
        # another process holds speak of a lockfile, so say what they
        # mean here
        raise RuntimeError(
            "encoder auto: jax could not start the accelerator backend "
            f"({e}). One process owns a chip at a time: if another "
            "process of this host holds it, start this one with "
            "JAX_PLATFORMS=cpu (or LIZARDFS_TPU_ENCODER=cpp)"
        ) from e
    if devices[0].platform == "cpu":
        log.info("encoder auto -> %s: jax reports only CPU devices", host)
        return host
    from lizardfs_tpu.parallel import recovery

    name = "sharded" if len(devices) >= 2 and recovery.enabled() else "tpu"
    log.info(
        "encoder auto -> %s: jax reports %d x %s (%s)", name,
        len(devices), devices[0].device_kind, devices[0].platform,
    )
    return name


def export_backend(metrics, encoder: ChunkEncoder) -> None:
    """Publish the backend a process resolved on its metrics registry
    (``encoder_backend{name="tpu"} 1``), so a scrape — and every bench
    cell — can say which backend did the work."""
    metrics.labeled_counter(
        "encoder_backend", {"name": encoder.name},
        help="EC compute backend this process resolved (1 = in use)",
    ).inc()


def get_encoder(name: str | None = None) -> ChunkEncoder:
    """Encoder registry. ``name``: "cpu", "cpp", "tpu", "sharded", or
    None/"auto" (None honors the LIZARDFS_TPU_ENCODER env override).

    "auto" is decided once per process by :func:`_resolve_auto`: the
    device backend where jax reports an accelerator, else cpp (native
    SIMD) or cpu (numpy golden) — the analog of the reference keeping
    ISA-L as default with the plugin boundary on top. A named backend
    is built as asked or raises; nothing here catches a device, compile
    or kernel error and carries on with another backend.
    """
    if name is None:
        name = os.environ.get("LIZARDFS_TPU_ENCODER", "auto")
    if name not in _ENCODERS:
        if name == "auto":
            _ENCODERS[name] = get_encoder(_resolve_auto())
        elif name == "cpu":
            _ENCODERS[name] = CpuChunkEncoder()
        elif name == "cpp":
            from lizardfs_tpu.core.native import CppChunkEncoder

            _ENCODERS[name] = CppChunkEncoder()
        elif name == "tpu":
            _ENCODERS[name] = TpuChunkEncoder()
        elif name == "sharded":
            _ENCODERS[name] = ShardedTpuChunkEncoder()
        else:
            raise ValueError(f"unknown encoder backend {name!r}")
    return _ENCODERS[name]
