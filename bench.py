"""Benchmark: fused ec(8,4) RS encode + CRC32 of a 64 MiB chunk on TPU.

BASELINE config 3 (the primary target): ec(8,4) encode+CRC32 fused,
batch = 128 x 64 KiB stripes (one full 64 MiB chunk: 1024 data blocks in
8 parts, 512 parity blocks in 4 parts), single chip. Baseline = the CPU
reference path (vectorized numpy golden codec, the stand-in for the
reference's ISA-L `ec_encode_data` + table CRC until the native C++
baseline lands).

Prints the full row as one JSON line, then a compact summary line:
  {"metric": ..., "value": N, "unit": "MiB/s", "vs_baseline": N}

Runs on an accelerator only: with no TPU visible it exits non-zero and
prints no row. Every kernel configuration is compiled and measured; a
compile, kernel or cluster error ends the run with that error.

How the kernel row is timed: an in-jit lax.fori_loop whose body feeds
all outputs back into the carry (nothing DCE-able) runs L iterations in
one dispatch; the cost of one dispatch is measured with an L=1 loop of
the same program and subtracted. ROADMAP S1 replaces this file.
"""

import functools
import json
import time

import numpy as np

K, M = 8, 4
BLOCK = 64 * 1024
NBLOCKS_PER_PART = 128  # 8 parts x 128 blocks x 64 KiB = 64 MiB data
DATA_MIB = K * NBLOCKS_PER_PART * BLOCK / 2**20


def _fused_encode():
    """The fused encode+CRC kernel (Pallas; compiles through Mosaic)."""
    from lizardfs_tpu.ops import pallas_ec

    return pallas_ec.fused_encode_crc


def _cpu_encoder():
    """Best CPU encoder: native SIMD codec when built, numpy golden
    otherwise."""
    from lizardfs_tpu.core import native
    from lizardfs_tpu.core.encoder import CpuChunkEncoder

    return native.CppChunkEncoder() if native.available() else CpuChunkEncoder()


def tpu_throughput(k: int = K, m: int = M,
                   nblocks_per_part: int = NBLOCKS_PER_PART) -> float:
    import jax
    import jax.numpy as jnp

    from lizardfs_tpu.ops import jax_ec

    fused = _fused_encode()
    data_mib = k * nblocks_per_part * BLOCK / 2**20
    bigm = jax.device_put(np.asarray(jax_ec.encoding_bitmatrix(k, m)))
    data = jax.device_put(
        np.random.default_rng(0).integers(
            0, 256, size=(k, nblocks_per_part * BLOCK), dtype=np.uint8
        )
    )

    def make_loop(fused_call):
        @functools.partial(jax.jit, static_argnums=(2,))
        def loop(bigm, x, n):
            def body(i, x):
                p, dc, pc = fused_call(bigm, x, BLOCK)
                mix = (
                    dc.sum(dtype=jnp.uint32) ^ pc.sum(dtype=jnp.uint32)
                ) & 0xFF
                x = x.at[:m, :].set(x[:m, :] ^ p)
                return x.at[0, 0].set(x[0, 0] ^ mix.astype(jnp.uint8))

            return jax.lax.fori_loop(0, n, body, x).sum(dtype=jnp.int32)

        return loop

    # the three kernel configurations, most aggressive first
    # (benches/ROOFLINE.md #1-3); chip_smoke.py proves each compiles
    # and matches the golden codec, so a failure here is an error
    global KERNEL_CONFIG_USED, KERNEL_CFG, KERNEL_LADDER
    from lizardfs_tpu.ops.pallas_ec import BIG_TILE_CONFIG, ROOFLINE_CONFIG

    ladder = [
        (ROOFLINE_CONFIG, "roofline-64K/wide-crc/reuse-planes"),
        (BIG_TILE_CONFIG, "big-tile-64K/11.5M"),
        (None, "default-16K/10M"),
    ]

    def timed(n):
        t0 = time.perf_counter()
        float(loop(bigm, data, n))
        return time.perf_counter() - t0

    def measure() -> float:
        timed(L)  # compile L=16
        vals, totals = [], []
        # several measurement rounds: the first reads low until
        # clocks warm up. Rounds where the L-iter run does not clearly
        # exceed its own dispatch floor are discarded; the result is
        # the median of the last surviving rounds.
        for _ in range(5):
            floor = min(timed(1) for _ in range(3))
            total = min(timed(L) for _ in range(3))
            totals.append(total)
            if total < floor * 1.1:
                continue
            vals.append(data_mib / ((total - floor) / (L - 1)))
        if vals:
            return statistics.median(vals[-3:])
        # every round was filtered: the kernel is fast relative to
        # dispatch (floor-dominated). Report the conservative
        # no-floor-subtraction number from the best round instead of
        # failing the bench.
        return data_mib / (min(totals) / L)

    import statistics

    L = 16
    headline = (k, m, nblocks_per_part) == (K, M, NBLOCKS_PER_PART)
    headline_val = None
    for cfg, tag in ladder:
        call = functools.partial(fused, **cfg) if cfg else fused
        loop = make_loop(call)
        timed(1)  # compile L=1
        val = measure()
        if headline_val is None:
            # the headline number is the first configuration's; the
            # wide (32,8) row reuses this function and must not clobber
            # the tag
            headline_val = val
            if headline:
                KERNEL_CONFIG_USED = tag
                KERNEL_CFG = cfg or {}
        if not headline:
            return headline_val
        # headline shape: measure every configuration, so one chip
        # run arbitrates them (ROOFLINE.md #1-3, ROADMAP S4)
        KERNEL_LADDER[tag] = round(val, 1)
    return headline_val


def cpu_baseline_throughput() -> float:
    """CPU reference: the native C++ SIMD encoder (ISA-L-equivalent
    nibble-shuffle technique), single thread, full 64 MiB chunk. Falls
    back to the numpy golden path (scaled 1/16 slice) if the shared
    library is not built."""
    import importlib
    import os
    import subprocess

    from lizardfs_tpu.core import native

    if not native.available():
        # build the shared library on first run (fresh checkout)
        subprocess.run(
            ["make", "-C", os.path.join(os.path.dirname(__file__), "native")],
            check=False, capture_output=True,
        )
        importlib.reload(native)

    if native.available():
        enc = native.CppChunkEncoder()
        data = np.random.default_rng(0).integers(
            0, 256, size=(K, NBLOCKS_PER_PART * BLOCK), dtype=np.uint8
        )
        enc.encode_with_checksums(K, M, data, block_size=BLOCK)  # warm
        dt = min(
            _timed(lambda: enc.encode_with_checksums(K, M, data, block_size=BLOCK))
            for _ in range(3)
        )
        return DATA_MIB / dt

    from lizardfs_tpu.core.encoder import CpuChunkEncoder

    enc = CpuChunkEncoder()
    frac = 16
    n = NBLOCKS_PER_PART * BLOCK // frac
    data = np.random.default_rng(0).integers(0, 256, size=(K, n), dtype=np.uint8)
    enc.encode_with_checksums(K, M, data, block_size=BLOCK // frac)  # warm tables
    t0 = time.perf_counter()
    enc.encode_with_checksums(K, M, data, block_size=BLOCK // frac)
    dt = time.perf_counter() - t0
    return (DATA_MIB / frac) / dt


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def tpu_reconstruct_latency_ms() -> float:
    """BASELINE config 4: single-shard reconstruct latency of a 64 MiB
    ec(8,4) chunk (part 0 lost, rebuilt from 8 survivors), including the
    host fetch of the rebuilt 8 MiB part — that transfer IS part of a
    real repair (reference: src/common/ec_read_plan.h:113-146 recovery +
    src/chunkserver/chunk_replicator.cc:139-197 writes the part back)."""
    import statistics

    import jax

    from lizardfs_tpu.ops import gf256, jax_ec

    fused = _fused_encode()
    lost = [0]
    avail = [i for i in range(K + M) if i not in lost]
    used, _ = gf256.recovery_selection(K, M, avail, lost)
    bigm = jax.device_put(np.asarray(
        jax_ec.recovery_bitmatrix(K, M, tuple(used), tuple(lost))
    ))
    survivors = jax.device_put(
        np.random.default_rng(1).integers(
            0, 256, size=(len(used), NBLOCKS_PER_PART * BLOCK), dtype=np.uint8
        )
    )

    def once(call) -> float:
        t0 = time.perf_counter()
        rec, _dc, _rc = call(bigm, survivors, BLOCK)
        np.asarray(rec)  # force device->host of the rebuilt part
        return (time.perf_counter() - t0) * 1e3

    call = functools.partial(fused, **KERNEL_CFG) if KERNEL_CFG else fused
    once(call)  # compile
    once(call)  # warm
    return statistics.median(once(call) for _ in range(7))


def cpu_reconstruct_ms() -> float:
    """CPU reference for config 4: same repair through the encoder
    boundary."""
    enc = _cpu_encoder()
    n = NBLOCKS_PER_PART * BLOCK
    rng = np.random.default_rng(1)
    parts = {
        i: rng.integers(0, 256, size=n, dtype=np.uint8)
        for i in range(1, K + M)
    }
    enc.recover(K, M, parts, [0])  # warm
    return min(
        _timed(lambda: enc.recover(K, M, parts, [0])) for _ in range(3)
    ) * 1e3


def tpu_ec82_batch1_us() -> float:
    """BASELINE config 2: ec(8,2) encode+CRC of ONE stripe (8 x 64 KiB
    blocks). batch=1 is a latency row — it exposes the dispatch floor a
    single-stripe write pays, which the batch=128 headline amortizes."""
    import statistics

    import jax

    from lizardfs_tpu.ops import jax_ec

    fused = _fused_encode()
    bigm = jax.device_put(np.asarray(jax_ec.encoding_bitmatrix(8, 2)))
    data = jax.device_put(
        np.random.default_rng(2).integers(
            0, 256, size=(8, BLOCK), dtype=np.uint8
        )
    )

    def once() -> float:
        t0 = time.perf_counter()
        # ONE combined fetch of the three outputs: the row is about
        # the dispatch floor, not three sequential device->host copies
        jax.device_get(fused(bigm, data, BLOCK))
        return (time.perf_counter() - t0) * 1e6

    once()
    once()
    return statistics.median(once() for _ in range(9))


def cpu_ec82_batch1_us() -> float:
    enc = _cpu_encoder()
    data = np.random.default_rng(2).integers(
        0, 256, size=(8, BLOCK), dtype=np.uint8
    )
    enc.encode_with_checksums(8, 2, data, block_size=BLOCK)  # warm
    return min(
        _timed(lambda: enc.encode_with_checksums(8, 2, data, block_size=BLOCK))
        for _ in range(5)
    ) * 1e6


def cluster_throughput() -> dict:
    """Whole-system localhost bench: 12-chunkserver cluster (native C++
    data plane), 128 MiB dd-style write + cold read per goal. The
    cluster rows run with the "cpp" encoder: none drives the device
    (ROADMAP S1/S2)."""
    import asyncio
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from benches.bench_cluster import run_bench

    rows = asyncio.run(run_bench(128, 12, "cpp"))
    out = {}
    for r in rows:
        key = (
            r["goal"].replace(" ", "_").replace("(", "").replace(")", "")
            .replace(",", "_")
        )
        if "write_MBps" in r:
            out[f"cluster_{key}_write_MBps"] = r["write_MBps"]
            out[f"cluster_{key}_read_MBps"] = r["read_MBps"]
            out[f"cluster_{key}_spread_pct"] = max(
                r.get("write_spread_pct", 0), r.get("read_spread_pct", 0)
            )
            # per-rep raw values + target/met verdicts (r04 #6: a
            # miss must be readable from the artifact alone)
            for extra in (
                "write_reps_MBps", "read_reps_MBps",
                "write_target_MBps", "write_target_met",
                "read_target_MBps", "read_target_met",
            ):
                if extra in r:
                    out[f"cluster_{key}_{extra}"] = r[extra]
            if "write_phases_ms" in r:
                # per-phase (encode/stage/send/commit) busy-time
                # over the row's write reps — the instrument the
                # 4-round ec(8,4) miss has been waiting for
                out[f"cluster_{key}_write_phases"] = r["write_phases_ms"]
            if "read_phases_ms" in r:
                # the read-side twin (locate/dial/wait/net/decode/
                # gather busy-time; `dominant` names the roofline)
                out[f"cluster_{key}_read_phases"] = r["read_phases_ms"]
            if "write_window" in r:
                # adaptive write-window fiducials (depth settled,
                # segments sent, credit stalls, coalesced commits)
                out[f"cluster_{key}_write_window"] = r["write_window"]
        elif "read_MBps" in r:
            # read-only rows (the ec(8,4) degraded-read fiducial):
            # parity-recovery throughput + its phase breakdown
            out[f"cluster_{key}_read_MBps"] = r["read_MBps"]
            out[f"cluster_{key}_spread_pct"] = r.get(
                "read_spread_pct", 0
            )
            if "read_reps_MBps" in r:
                out[f"cluster_{key}_read_reps_MBps"] = (
                    r["read_reps_MBps"]
                )
            if "read_phases_ms" in r:
                out[f"cluster_{key}_read_phases"] = r["read_phases_ms"]
        elif "coverage_pct" in r:
            # cross-role trace attribution of one ec(8,4) write rep
            # (benches/bench_cluster.py traced rep): wall, how much
            # of it named segments cover, and the per-role split
            out[f"cluster_{key}"] = {
                "rep_MBps": r.get("rep_MBps", 0),
                "wall_ms": r["wall_ms"],
                "coverage_pct": r["coverage_pct"],
                "by_role_ms": r.get("by_role_ms", {}),
                "spans": r.get("spans", 0),
            }
        elif "shm_on_MBps" in r:
            # shm-ring A/B: the same-host shared-memory data plane
            # vs the LZ_SHM_RING=0 scatterv path, interleaved reps
            out["cluster_ec8_4_write_shm"] = {
                "on_MBps": r["shm_on_MBps"],
                "off_MBps": r["shm_off_MBps"],
                "delta_pct": r["shm_delta_pct"],
                "desc_parts": r.get("shm_desc_parts", 0),
                "engaged": r.get("shm_engaged", False),
            }
        elif "health_status" in r:
            # SLO/flight-recorder fiducials (the "slo health" row):
            # breach counts make a co-located-load rep attributable
            # from the tail alone
            out["cluster_health_status"] = r["health_status"]
            out["cluster_slo_breaches"] = r["slo_breaches"]
            out["cluster_slow_ops"] = r["slow_ops"]
            if r.get("breaches_by_class"):
                out["cluster_slo_breaches_by_class"] = (
                    r["breaches_by_class"]
                )
        elif "ops_per_s" in r:
            out[f"cluster_{key}_MBps"] = r["MBps"]
            out[f"cluster_{key}_ops_per_s"] = r["ops_per_s"]
            out[f"cluster_{key}_spread_pct"] = r.get("spread_pct", 0)
            for extra in ("MBps_reps", "ops_reps"):
                if extra in r:
                    out[f"cluster_{key}_{extra}"] = r[extra]
        elif "put_MBps" in r:
            # S3 gateway row (ROADMAP 3): object PUT/GET MB/s plus
            # the ListObjectsV2 ops rate over a populated bucket
            out["cluster_s3_put_MBps"] = r["put_MBps"]
            out["cluster_s3_get_MBps"] = r["get_MBps"]
            out["cluster_s3_list_ops"] = r["list_ops"]
            out["cluster_s3_spread_pct"] = max(
                r.get("put_spread_pct", 0), r.get("get_spread_pct", 0),
                r.get("list_spread_pct", 0),
            )
            for extra in ("put_reps_MBps", "get_reps_MBps",
                          "list_ops_reps"):
                if extra in r:
                    out[f"cluster_s3_{extra}"] = r[extra]
        elif "rebuild_MBps" in r:
            # RebuildEngine convergence after a chunkserver loss
            out["cluster_rebuild_MBps"] = r["rebuild_MBps"]
            out["cluster_rebuild_s"] = r["rebuild_s"]
            out["cluster_rebuild_parts"] = r["parts_rebuilt"]
        elif "primary_only" in r:
            # locate storm (ISSUE 7): aggregate locate QPS primary-
            # only vs primary+shadow, p99, replica engagement + lag
            a, b = r["primary_only"], r.get("with_replica", {})
            out["cluster_locate_qps"] = {
                "primary": a["locate_qps"],
                "replica_topo": b.get("locate_qps", 0),
                "x": r.get("locate_qps_x", 0),
                "target_x": r.get("locate_qps_target_x", 1.8),
                "target_met": r.get("locate_qps_target_met", False),
                "shadow_served": b.get("shadow_reads", 0),
                "stale_retries": b.get("stale_retries", 0),
            }
            out["cluster_locate_p99_ms"] = {
                "primary": a["locate_p99_ms"],
                "replica_topo": b.get("locate_p99_ms", 0),
            }
            out["cluster_locate_storm_detail"] = {
                "files": r.get("files", 0),
                "servers": r.get("servers", 0),
                "populate_s": r.get("populate_s", 0),
                "cs_ingest": r.get("cs_ingest", {}),
                "loop_stalls": r.get("loop_stalls", 0),
                "shadow_lag": r.get("shadow_lag", 0),
            }
        elif "qos_ab" in r:
            # per-tenant QoS A/B (ISSUE 15): the victim's p99 with
            # an abuser flooding, LZ_QOS off vs on, plus whether
            # sheds landed only on the abuser (full per-arm worker
            # stats live in BENCH_FULL.json)
            q = r["qos_ab"]
            out["cluster_qos_victim_p99_ms"] = {
                "off": q.get("victim_p99_off_ms", 0),
                "on": q.get("victim_p99_on_ms", 0),
                "bound_ms": q.get("bound_ms", 0),
                "abuser_sheds": q.get("abuser_busy_waits_on", 0),
                "target_met": q.get("target_met", False),
            }
        elif "hotspot" in r:
            # hot-spot A/B (ISSUE 17): aggregate read MB/s on one
            # 1-copy chunk with the heat loop off vs on — verdict
            # is the adaptive goal boost landing (copies, time to
            # boost) without costing read throughput
            h = r["hotspot"]
            out["cluster_hotspot_read_MBps"] = {
                "off": h.get("read_off_MBps", 0),
                "on": h.get("read_on_MBps", 0),
                "copies": h.get("copies", 1),
                "boost_s": h.get("boost_s", 0),
                "target_met": h.get("target_met", False),
            }
        elif "failover" in r:
            # failover RTO (ISSUE 19): SIGKILL the elected active
            # master under a windowed ec(8,4) write — the verdict
            # is the detect->elect->promote->first-acked-write
            # outage plus the zero-acked-loss count the drill
            # asserts (kill-primary chaos schedule, real processes)
            fo = r["failover"]
            out["cluster_failover_rto_s"] = {
                "rto_s": fo.get("rto_s", 0),
                "promote_s": fo.get("promote_s", 0),
                "epoch": fo.get("epoch", 0),
                "acked": fo.get("acked_writes", 0),
                "lost": fo.get("lost_writes", 0),
                "target_met": fo.get("target_met", False),
            }
        elif "native_read_us" in r:
            out["cluster_4k_read_native_us"] = r["native_read_us"]
            out["cluster_4k_read_loop_us"] = r["loop_read_us"]
            out["cluster_4k_spread_pct"] = max(
                r.get("native_spread_pct", 0), r.get("loop_spread_pct", 0)
            )
    return out


KERNEL_CONFIG_USED = ""  # set by tpu_throughput; shipped via the queue
KERNEL_CFG: dict = {}  # the winning staged config; other rows reuse it
KERNEL_LADDER: dict = {}  # tag -> MiB/s per config


def tpu_rows() -> dict:
    """Every device row, in this process (it owns the chip; the cluster
    rows' daemons run in-process on the CPU encoder). No TPU -> exit
    non-zero before anything is measured."""
    import jax

    from lizardfs_tpu.runtime.jaxcache import configure_compile_cache

    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py: no TPU visible (jax reports {dev.platform}); "
            "device rows are measured on the chip or not at all"
        )
    return {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "ok": tpu_throughput(),
        "cfg": KERNEL_CONFIG_USED,
        "ladder": KERNEL_LADDER,
        # wide-stripe single-chip row (BASELINE config 5 precursor)
        "wide": tpu_throughput(k=32, m=8, nblocks_per_part=32),
        "rec": tpu_reconstruct_latency_ms(),   # BASELINE config 4
        "ec82": tpu_ec82_batch1_us(),          # BASELINE config 2
    }


def box_health() -> dict:
    """Tiny CPU/memory fiducials so round-over-round drift in every
    other row is attributable: the r02-r04 'CPU kernel drifts down'
    mystery (1826->1643->1486 MiB/s) and the r05 write-row swings were
    BOX state (co-located load; the hypervisor slow-faults after ~4-5
    GB resident and recovers only partially), not code. Comparing rows
    across rounds without normalizing by these numbers compares boxes,
    not software."""
    import os

    a = np.ones(128 * 2**20, dtype=np.uint8)
    b = np.empty_like(a)
    np.copyto(b, a)  # fault everything in first
    t0 = time.perf_counter()
    for _ in range(8):
        np.copyto(b, a)
    memcpy = 8 * 128 / 1024 / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    x = 1.0
    for _ in range(2_000_000):
        x = x * 1.0000001 + 1e-9
    pyloop_ms = (time.perf_counter() - t0) * 1e3
    return {
        "box_cpus": os.cpu_count(),
        "box_memcpy_GBps": round(memcpy, 2),
        "box_pyloop_ms": round(pyloop_ms, 1),
    }


# --- bench trajectory: round files + regression guard ----------------------
#
# Every run self-records its full row as BENCH_r<NN>.json (numbered past
# the highest existing round file, parseable or not) and compares its
# fiducials against the newest loadable previous round — the recorded
# trajectory was empty before this because the driver-captured files
# hold only a truncated stdout tail (r05's is cut mid-JSON).

# round-over-round comparable fiducials by suffix; "value" compares only
# when the metric row names the same kernel
_HIGHER_BETTER = ("_MBps", "_GBps", "_ops_per_s", "_list_ops")
_LOWER_BETTER = ("_ms", "_us")

# default tolerance before a delta flags as a regression: these boxes
# are noisy (see box_health — the r02-r04 "drift" was hypervisor state),
# so the guard flags order-of-magnitude story changes, not run jitter
BENCH_DELTA_TOL = 0.25


def _round_files(bench_dir):
    """[(round number, path)] of every BENCH_r*.json, sorted."""
    import glob
    import os
    import re

    out = []
    for path in glob.glob(os.path.join(bench_dir, "BENCH_r*.json")):
        mt = re.search(r"BENCH_r(\d+)\.json$", path)
        if mt:
            out.append((int(mt.group(1)), path))
    return sorted(out)


def _row_from_tail(tail: str):
    """Best-effort fiducial row from a driver-captured stdout tail:
    the LAST parseable JSON object line wins (the summary line prints
    last by design). A tail cut mid-JSON yields nothing."""
    best = None
    for line in tail.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict):
            best = doc
    return best


def _load_prev_round(bench_dir):
    """(round number, fiducial row) of the newest loadable previous
    round, or None. Self-recorded files carry the full row under
    "row"; driver-captured files are mined from their stdout tail."""
    for n, path in reversed(_round_files(bench_dir)):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict):
            continue
        row = doc.get("row") if isinstance(doc.get("row"), dict) else None
        if row is None and isinstance(doc.get("tail"), str):
            row = _row_from_tail(doc["tail"])
        if row:
            return n, row
    return None


def bench_deltas(row: dict, prev: dict, tol: float = BENCH_DELTA_TOL):
    """(per-fiducial delta %, regressed keys) vs a previous round.
    Only direction-known scalar fiducials compare; a regression is a
    move past ``tol`` in the bad direction."""
    deltas: dict[str, float] = {}
    regressions: list[str] = []
    for key, new in row.items():
        if isinstance(new, bool) or not isinstance(new, (int, float)):
            continue
        old = prev.get(key)
        if isinstance(old, bool) or not isinstance(old, (int, float)):
            continue
        if old == 0:
            continue
        if key == "value":
            if prev.get("metric") != row.get("metric"):
                continue
            higher, lower = True, False
        else:
            higher = key.endswith(_HIGHER_BETTER)
            lower = key.endswith(_LOWER_BETTER)
        if not higher and not lower:
            continue
        deltas[key] = round((new - old) / old * 100.0, 1)
        if (higher and new < old * (1 - tol)) or (
            lower and new > old * (1 + tol)
        ):
            regressions.append(key)
    return deltas, sorted(regressions)


def _bench_guard(row: dict, bench_dir: str) -> None:
    """Compare against the newest loadable round, fold the verdict
    into the row (summary carries ``bench_regressions``), print human
    delta lines, and self-record this round's full row. Never fatal —
    a broken trajectory must not kill the bench line."""
    import os

    try:
        prev = _load_prev_round(bench_dir)
        if prev is not None:
            prev_n, prev_row = prev
            deltas, regs = bench_deltas(row, prev_row)
            row["bench_prev_round"] = prev_n
            row["bench_deltas_pct"] = deltas
            if regs:
                row["bench_regressions"] = regs
            for key in sorted(deltas):
                flag = "  REGRESSION" if key in regs else ""
                print(
                    f"DELTA vs r{prev_n:02d}: {key} "
                    f"{deltas[key]:+.1f}%{flag}"
                )
        else:
            # empty/unloadable trajectory: this run is the fresh
            # baseline — say so explicitly (and mark the row) instead
            # of silently printing no DELTA lines at all, which reads
            # as "guard never ran" in the driver tail
            row["bench_prev_round"] = 0
            print("DELTA: no loadable prior round -- recording fresh "
                  "baseline")
        files = _round_files(bench_dir)
        n_next = (files[-1][0] + 1) if files else 1
        path = os.path.join(bench_dir, f"BENCH_r{n_next:02d}.json")
        with open(path, "w") as f:
            json.dump({"n": n_next, "self_recorded": True, "row": row}, f,
                      indent=1)
            f.write("\n")
    except Exception as e:  # noqa: BLE001
        row["bench_guard_error"] = str(e)[:160]


def main():
    tpu = tpu_rows()
    baseline = cpu_baseline_throughput()
    row = {
        "metric": "ec(8,4) fused encode+CRC32, 64 MiB chunk, single chip",
        "value": round(tpu["ok"], 1),
        "unit": "MiB/s",
        "vs_baseline": round(tpu["ok"] / baseline, 2),
        "device": tpu["device"],
        "kernel_config": tpu["cfg"],
        # per-config throughput of the three kernel configurations
        # (ROOFLINE.md #1-3): one chip run arbitrates them
        "kernel_ladder": tpu["ladder"],
        "ec32_8_single_chip_MiBps": round(tpu["wide"], 1),
    }
    # BASELINE config 4: reconstruct-1-shard latency
    cpu_rec = cpu_reconstruct_ms()
    row["reconstruct_1shard_cpu_ms"] = round(cpu_rec, 2)
    row["reconstruct_1shard_ms"] = round(tpu["rec"], 2)
    row["reconstruct_vs_cpu"] = round(cpu_rec / tpu["rec"], 2)
    # BASELINE config 2: ec(8,2) single-stripe encode latency
    cpu82 = cpu_ec82_batch1_us()
    row["ec8_2_batch1_cpu_us"] = round(cpu82, 1)
    row["ec8_2_batch1_us"] = round(tpu["ec82"], 1)
    row["ec8_2_batch1_vs_cpu"] = round(cpu82 / tpu["ec82"], 2)
    row.update(box_health())
    row.update(cluster_throughput())
    # regression guard + round self-record (delta lines print before
    # the JSON so the tail-surviving summary still lands last)
    import os

    _bench_guard(row, os.path.dirname(os.path.abspath(__file__)))
    # full row set first (humans, driver logs), then the durable copy on
    # disk, then the COMPACT summary as the very last stdout line: the
    # driver records only a ~2000-byte stdout tail, and r05's artifact
    # landed parsed:null because the single fat line was cut mid-JSON.
    # Whatever happens above, the last complete line must be valid JSON
    # that carries the verdict-bearing fields.
    print(json.dumps(row))
    summary = _summary_row(row)
    try:
        import os

        full_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_FULL.json"
        )
        with open(full_path, "w") as f:
            json.dump(row, f, indent=1)
            f.write("\n")
    except OSError as e:
        summary["full_write_error"] = str(e)[:120]
    print(json.dumps(summary))


def _summary_row(row: dict) -> dict:
    """The tail-surviving one-liner: kernel row + config tag, box
    fiducials, every tracked target verdict, and the ec write phase
    rows — everything needed to judge the round from the tail alone,
    budgeted to stay well under the driver's ~2000-byte stdout tail.
    Full detail (per-rep arrays, spreads, attempts log) lives in
    BENCH_FULL.json."""
    s = {"summary": 1, "full": "BENCH_FULL.json"}
    for key in (
        "metric", "value", "unit", "vs_baseline", "kernel_config",
        "kernel_ladder",
        "reconstruct_1shard_cpu_ms", "reconstruct_1shard_ms",
        "ec8_2_batch1_cpu_us", "ec8_2_batch1_us",
        "box_cpus", "box_memcpy_GBps", "box_pyloop_ms",
        # slo/flight-recorder fiducials: nonzero breaches on a slow
        # round name the degraded role+class from the tail alone
        "cluster_health_status", "cluster_slo_breaches",
        "cluster_slow_ops", "cluster_slo_breaches_by_class",
        # rebuild subsystem fiducials: how fast a lost chunkserver's
        # parts came back through the RebuildEngine (part count lives
        # in BENCH_FULL.json)
        "cluster_rebuild_MBps", "cluster_rebuild_s",
        # s3 gateway row (ROADMAP 3): the third front door's object
        # PUT/GET MB/s + listing ops rate (reps in BENCH_FULL.json)
        "cluster_s3_put_MBps", "cluster_s3_get_MBps",
        "cluster_s3_list_ops",
        # bench-trajectory regression guard: which fiducials moved past
        # tolerance vs the previous recorded round (full per-key delta
        # map lives in BENCH_FULL.json / this round's BENCH_r file)
        "bench_prev_round", "bench_regressions", "bench_guard_error",
    ):
        if key in row:
            s[key] = row[key]
    if "cluster_locate_qps" in row:
        # locate storm (ISSUE 7): the metadata-plane A/B verdict —
        # aggregate locate QPS primary-only vs +shadow with its 1.8x
        # target_met flag, compacted to the verdict-bearing fields
        # (engagement counters + storm detail live in BENCH_FULL.json)
        q = row["cluster_locate_qps"]
        s["cluster_locate_qps"] = {
            "primary": q.get("primary", 0),
            "replica_topo": q.get("replica_topo", 0),
            "x": q.get("x", 0), "target_met": q.get("target_met", False),
        }
    if "cluster_locate_p99_ms" in row:
        s["cluster_locate_p99_ms"] = row["cluster_locate_p99_ms"]
    if "cluster_qos_victim_p99_ms" in row:
        # per-tenant QoS verdict (ISSUE 15): victim p99 off->on under
        # an abuser flood + its bound + shed placement
        s["cluster_qos_victim_p99_ms"] = row["cluster_qos_victim_p99_ms"]
    if "cluster_hotspot_read_MBps" in row:
        # hot-spot verdict (ISSUE 17): did the heat loop boost the
        # viral chunk, how fast, and did read throughput hold
        s["cluster_hotspot_read_MBps"] = row["cluster_hotspot_read_MBps"]
    if "cluster_failover_rto_s" in row:
        # failover verdict (ISSUE 19): how long the cluster was down
        # across a SIGKILL of the elected active, and the acked-loss
        # count (always 0 or the drill itself failed)
        s["cluster_failover_rto_s"] = row["cluster_failover_rto_s"]
    targeted = {
        key[: -len("_target_met")]
        for key in row
        if key.endswith("_target_met")
    }
    for key, value in row.items():
        if not key.startswith("cluster_"):
            continue
        if key.startswith("cluster_nfs_gateway_C_client"):
            # decision-note input (Python-vs-C measuring client), not a
            # target verdict: BENCH_FULL.json + benches/README.md carry
            # it; the tail budget goes to verdict-bearing rows
            continue
        if key.endswith((
            "_write_MBps", "_read_MBps", "_target_MBps", "_target_met",
        )) or key in ("cluster_dbench8_MBps", "cluster_dbench8_ops_per_s"):
            s[key] = value
        elif key.endswith("_spread_pct") and any(
            t.startswith(key[: -len("_spread_pct")]) for t in targeted
        ):
            # spreads only for rows carrying a target verdict (noise
            # context for the verdict); the rest live in the full file
            s[key] = value
        elif key.endswith("_write_phases") and (
            "_ec8_4_" in key or "_ec3_2_" in key
        ):
            # the phase instrument the ec(8,4) target miss exists for
            # (+ ec(3,2) as its cross-check), integer ms to stay lean —
            # except the send/encode ratio, whose verdict lives in its
            # decimals (<= 1.0 is the ISSUE 6 target)
            s[key] = {
                k: (int(round(v))
                    if isinstance(v, float) and k != "send_over_encode"
                    else v)
                for k, v in value.items()
            }
        elif key.endswith("_read_phases") and "_ec8_4" in key:
            # the read-side twin (ISSUE 18): cluster_ec8_4_read_phases
            # + its degraded-read variant, integer ms with the named
            # dominant phase (the roofline verdict) — xor3/ec3_2 read
            # phases stay in BENCH_FULL.json
            s[key] = {
                k: (int(round(v)) if isinstance(v, float) else v)
                for k, v in value.items()
            }
        elif key == "cluster_ec8_4_write_shm" and isinstance(value, dict):
            # the shm on/off A/B delta: THE instrument of this round's
            # send-phase attack
            s[key] = value
        elif key.endswith("_write_window") and "_ec8_4_" in key:
            # window fiducials for the target row: did the adaptive
            # depth actually deepen, and did credits ever stall it
            s[key] = value
        elif key.endswith("_write_trace") and isinstance(value, dict):
            # the traced rep's verdict: coverage + per-role split,
            # integer ms (segment detail lives in BENCH_FULL.json)
            s[key] = {
                "coverage_pct": value.get("coverage_pct", 0),
                "wall_ms": int(round(value.get("wall_ms", 0))),
                "by_role_ms": {
                    r: int(round(v))
                    for r, v in value.get("by_role_ms", {}).items()
                },
            }
    return _fit_summary(s)


# the driver records only a ~2000-byte stdout tail; leave margin for
# the trailing newline + any stderr interleaving. Structural guard:
# tests/test_bench_summary.py pins that a worst-case row set fits.
# (1900 -> 1925 when the hot-spot A/B fiducial joined; 1925 -> 1950
# when the read-phase fiducials joined: a worst-case round carries two
# more phase dicts + their drop records, and the ladder must still
# stop before the ec(8,4) write-phases rung — drop records now strip
# the cluster_ prefix to pay for most of it; 1950 -> 1975 when the
# failover RTO fiducial joined: a worst-case round must fit its drop
# record while the ladder still stops short of that same rung. 1975
# keeps ~25 bytes of slack under the hard window.)
SUMMARY_BUDGET_BYTES = 1975

# dropped (in order) when a fat round outgrows the budget — ordered
# least-verdict-bearing first; each drop is recorded so the tail shows
# WHAT was cut instead of cutting mid-JSON like r05
_SUMMARY_DROP_ORDER = (
    "cluster_slo_breaches_by_class", "cluster_locate_p99_ms",
    "cluster_hotspot_read_MBps",
    "cluster_qos_victim_p99_ms",
    "bench_regressions",
    "kernel_ladder",
    "cluster_ec3_2_write_phases", "cluster_ec8_4_write_window",
    # spreads are noise CONTEXT for the target verdicts, not verdicts:
    # the whole suffix family drops as one recorded unit
    "*_spread_pct",
    # the s3 row drops as ONE unit (prefix entry, one drop record)
    # before the ec(8,4) instruments the standing write target depends on
    "cluster_s3_*",
    # the degraded-read phase dict drops before the healthy-read one:
    # parity-recovery cost is diagnosis, the healthy roofline is the
    # standing fiducial (ISSUE 18)
    "cluster_ec8_4_degraded_read_read_phases",
    "cluster_ec8_4_write_trace",
    # this round's headline verdict drops late: an RTO that silently
    # vanished from the tail would read as "failover never measured"
    "cluster_failover_rto_s",
    "cluster_ec8_4_write_shm", "cluster_locate_qps",
    "cluster_ec8_4_read_phases",
    "cluster_ec8_4_write_phases",
)


def _fit_summary(s: dict) -> dict:
    dropped = []
    for key in _SUMMARY_DROP_ORDER:
        if len(json.dumps(s)) <= SUMMARY_BUDGET_BYTES:
            break
        if key.endswith("*") or key.startswith("*"):
            # prefix/suffix entry: a whole key family drops as one unit
            # with ONE drop record (per-key records would eat the
            # savings)
            if key.endswith("*"):
                family = [k for k in s if k.startswith(key[:-1])]
            else:
                family = [k for k in s if k.endswith(key[1:])]
            if not family:
                continue
            for k in family:
                del s[k]
        elif key in s:
            del s[key]
        else:
            continue
        # records strip the redundant cluster_ prefix: on a worst-case
        # round a dozen-plus drop records ride the tail, and the prefix
        # alone would cost ~100 bytes of the budget they exist to save
        dropped.append(
            key[len("cluster_"):] if key.startswith("cluster_") else key
        )
        s["dropped"] = dropped  # idempotent re-assign, stays last
    return s


if __name__ == "__main__":
    main()
