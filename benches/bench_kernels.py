"""Kernel benchmark table: every BASELINE.md config measured.

  1. ec(3,2) encode, 64 MiB chunk, CPU reference (C++ SIMD + golden numpy)
  2. ec(8,2) encode, TPU single chip
  3. ec(8,4) encode+CRC32 fused, batch = 128 x 64 KiB stripes, TPU (primary)
  4. ec(8,4) single-shard reconstruct (decode), TPU
  5. ec(32,8) wide-stripe encode, sharded over the device mesh

Timing uses the in-jit serialized-loop methodology (see bench.py). The
device rows run the Pallas kernels and need a TPU: with none visible
the script exits non-zero. Prints a human table, or one JSON line per
config.

    python benches/bench_kernels.py [--json]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import functools
import json
import time

import numpy as np

BLOCK = 64 * 1024
CHUNK_MIB = 64.0


def _loop_timer(fn_builder, n_iters=16):
    """Build loop(n) via fn_builder, measure floor + amortized per-iter."""
    import jax

    loop = fn_builder()

    def timed(n):
        t0 = time.perf_counter()
        float(loop(n))
        return time.perf_counter() - t0

    timed(1)
    timed(n_iters)
    floor = min(timed(1) for _ in range(3))
    total = min(timed(n_iters) for _ in range(3))
    return max((total - floor) / (n_iters - 1), 1e-9)


def bench_cpu_ec32() -> dict:
    from lizardfs_tpu.core import native
    from lizardfs_tpu.core.encoder import CpuChunkEncoder

    k, m = 3, 2
    rng = np.random.default_rng(0)
    n = 8 * 2**20 * 8 // k // 8  # ~64MiB total data across k parts
    n = (64 * 2**20) // k
    data = [rng.integers(0, 256, n, dtype=np.uint8) for _ in range(k)]
    results = {}
    if native.available():
        enc = native.CppChunkEncoder()
        enc.encode(k, m, data)
        t0 = time.perf_counter()
        enc.encode(k, m, data)
        dt = time.perf_counter() - t0
        results["cpp_simd"] = CHUNK_MIB / dt
    golden = CpuChunkEncoder()
    slice_ = [d[: n // 8] for d in data]
    t0 = time.perf_counter()
    golden.encode(k, m, slice_)
    dt = (time.perf_counter() - t0) * 8
    results["numpy_golden"] = CHUNK_MIB / dt
    return {
        "config": "1: ec(3,2) encode 64MiB, CPU reference",
        "value": round(results.get("cpp_simd", results["numpy_golden"]), 1),
        "unit": "MiB/s",
        "detail": {k2: round(v, 1) for k2, v in results.items()},
    }


def _tpu_encode_bench(k: int, m: int) -> float:
    import jax
    import jax.numpy as jnp

    from lizardfs_tpu.ops import jax_ec, pallas_ec

    enc = pallas_ec.encode
    rng = np.random.default_rng(0)
    data = jax.device_put(
        rng.integers(0, 256, size=(k, (64 * 2**20) // k), dtype=np.uint8)
    )
    bigm = jax.device_put(jax_ec.encoding_bitmatrix(k, m))

    def build():
        @functools.partial(jax.jit, static_argnums=(0,))
        def loop(n):
            def body(i, x):
                p = enc(bigm, x)
                return x.at[:m, :].set(x[:m, :] ^ p[:m, :])

            return jax.lax.fori_loop(0, n, body, data).sum(dtype=jnp.int32)

        return loop

    per = _loop_timer(build)
    return CHUNK_MIB / per


def bench_tpu_ec82() -> dict:
    v = _tpu_encode_bench(8, 2)
    return {
        "config": "2: ec(8,2) encode 64MiB, TPU single chip",
        "value": round(v, 1), "unit": "MiB/s",
    }


def bench_tpu_fused() -> dict:
    import jax
    import jax.numpy as jnp

    from lizardfs_tpu.ops import jax_ec, pallas_ec

    k, m = 8, 4
    fused = pallas_ec.fused_encode_crc
    rng = np.random.default_rng(0)
    data = jax.device_put(
        rng.integers(0, 256, size=(k, 128 * BLOCK), dtype=np.uint8)
    )
    bigm = jax.device_put(jax_ec.encoding_bitmatrix(k, m))

    def build():
        @functools.partial(jax.jit, static_argnums=(0,))
        def loop(n):
            def body(i, x):
                p, dc, pc = fused(bigm, x, BLOCK)
                mix = (dc.sum(dtype=jnp.uint32) ^ pc.sum(dtype=jnp.uint32)) & 0xFF
                x = x.at[:m, :].set(x[:m, :] ^ p)
                return x.at[0, 0].set(x[0, 0] ^ mix.astype(jnp.uint8))

            return jax.lax.fori_loop(0, n, body, data).sum(dtype=jnp.int32)

        return loop

    per = _loop_timer(build)
    return {
        "config": "3: ec(8,4) fused encode+CRC32, batch=128x64KiB, TPU (primary)",
        "value": round(CHUNK_MIB / per, 1), "unit": "MiB/s",
    }


def bench_tpu_decode() -> dict:
    """Reconstruct one erased data shard from 8 surviving parts."""
    import jax
    import jax.numpy as jnp

    from lizardfs_tpu.ops import jax_ec, pallas_ec

    k, m = 8, 4
    # shard 0 erased; sources = parts 1..8 (7 data + 1 parity)
    available = tuple(range(1, 9))
    bigm = jax_ec.recovery_bitmatrix(k, m, available, (0,))
    rng = np.random.default_rng(0)
    sources = jax.device_put(
        rng.integers(0, 256, size=(8, 128 * BLOCK), dtype=np.uint8)
    )
    dbigm = jax.device_put(bigm)
    enc = pallas_ec.encode

    def build():
        @functools.partial(jax.jit, static_argnums=(0,))
        def loop(n):
            def body(i, x):
                r = enc(dbigm, x)  # (1, N) recovered shard
                return x.at[0, :].set(x[0, :] ^ r[0, :])

            return jax.lax.fori_loop(0, n, body, sources).sum(dtype=jnp.int32)

        return loop

    per = _loop_timer(build)
    shard_mib = 128 * BLOCK / 2**20
    return {
        "config": "4: ec(8,4) single-shard reconstruct @64MiB chunk, TPU",
        "value": round(per * 1e3, 2), "unit": "ms latency",
        "detail": {"shard_MiB_per_s": round(shard_mib / per, 1)},
    }


def bench_wide_stripe() -> dict:
    import jax

    from lizardfs_tpu.core.encoder import CpuChunkEncoder
    from lizardfs_tpu.parallel.sharded import make_mesh, sharded_encode_with_crcs

    k, m = 32, 8
    ndev = len(jax.devices())
    mesh = make_mesh()
    bs = BLOCK
    nb = max(ndev, 8)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=(k, nb * bs), dtype=np.uint8)
    run = sharded_encode_with_crcs(mesh, k, m, bs)
    out = run(data)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = run(data)
    jax.block_until_ready(out)
    float(np.asarray(out[1]).sum())
    dt = time.perf_counter() - t0
    total_mib = data.nbytes / 2**20
    return {
        "config": f"5: ec(32,8) wide-stripe encode+CRC over {ndev}-device mesh",
        "value": round(total_mib / dt, 1), "unit": "MiB/s",
        "detail": {"devices": ndev, "note": "includes dispatch round trip"},
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)
    import jax

    from lizardfs_tpu.runtime.jaxcache import configure_compile_cache

    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench_kernels: no TPU visible (jax reports {dev.platform})",
              file=sys.stderr)
        return 1
    for fn in (bench_cpu_ec32, bench_tpu_ec82, bench_tpu_fused,
               bench_tpu_decode, bench_wide_stripe):
        r = fn()
        r["device"] = f"{dev.platform}/{dev.device_kind}x{len(jax.devices())}"
        if args.json:
            print(json.dumps(r))
        else:
            extra = f"  {r['detail']}" if "detail" in r else ""
            print(f"{r['config']}: {r['value']} {r['unit']}{extra}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
