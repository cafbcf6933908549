"""bench.py tail-surviving summary line: budget regression guard.

The r05 artifact landed ``parsed: null`` because the single JSON output
line outgrew the driver's ~2000-byte stdout tail and was cut mid-JSON.
bench.py now prints a compact summary LAST; this pins that the summary
stays inside the budget even as the schema grows — structurally (the
_fit_summary drop ladder), not by hoping.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def _fat_row() -> dict:
    """A worst-case-ish full row: every key family the bench can emit,
    with realistically wide values (r05-shaped)."""
    row = {
        "metric": "ec_encode_8_4_64MiB", "value": 11943.2, "unit": "MiB/s",
        "vs_baseline": 1.07,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "kernel_config": "roofline-64K/wide-crc/reuse-planes",
        "kernel_ladder": {
            "roofline-64K/wide-crc/reuse-planes": 11943.2,
            "big-tile-64K/11.5M": 11001.4,
            "default-16K/10M": 10211.9,
        },
        "reconstruct_1shard_cpu_ms": 123.45,
        "reconstruct_1shard_ms": 9.87,
        "ec8_2_batch1_cpu_us": 210.4, "ec8_2_batch1_us": 35.1,
        "box_cpus": 8, "box_memcpy_GBps": 11.2, "box_pyloop_ms": 102.4,
    }
    goals = ("goal_1_1_copy", "goal_2_2_copies", "xor3", "ec3_2", "ec8_4",
             "nfs_gateway", "nfs_gateway_C_client")
    for g in goals:
        row[f"cluster_{g}_write_MBps"] = 1234.5
        row[f"cluster_{g}_read_MBps"] = 2345.6
        row[f"cluster_{g}_spread_pct"] = 116.9
        row[f"cluster_{g}_write_reps_MBps"] = [402.3, 399.8, 434.9, 431.3,
                                               428.9]
        row[f"cluster_{g}_read_reps_MBps"] = [1797.6, 1773.6, 1137.6,
                                              1733.3, 1855.0]
    for g in ("goal_2_2_copies", "ec8_4"):
        row[f"cluster_{g}_write_target_MBps"] = 450.0
        row[f"cluster_{g}_write_target_met"] = False
    row["cluster_nfs_gateway_read_target_MBps"] = 199.5
    row["cluster_nfs_gateway_read_target_met"] = True
    for g in ("xor3", "ec3_2", "ec8_4"):
        row[f"cluster_{g}_write_phases"] = {
            "encode_ms": 1234.56, "stage_ms": 345.67, "send_ms": 4567.89,
            "ack_ms": 2345.67, "commit_ms": 123.45, "wall_ms": 5678.9,
            "reps": 5,
            # round 7: the send/encode busy-fraction ratio (<= 1.0 is
            # the shm-ring target; its verdict lives in the decimals)
            # plus the named dominant phase (the acceptance question
            # "if not send, what bounds the row now" answered in-row)
            "send_over_encode": 0.87, "dominant": "encode",
        }
        # adaptive write-window fiducials (round 6: depth settled +
        # segment/credit/coalesce deltas per striped row)
        row[f"cluster_{g}_write_window"] = {
            "depth": 8, "max_depth": 8, "segments": 1234,
            "credit_waits": 56, "commits_coalesced": 12,
        }
    # read-path microscope fiducials (this round: ISSUE 18) — healthy
    # striped read phase breakdowns + the ec(8,4) degraded-read
    # (parity recovery) variant row
    for g in ("xor3", "ec3_2", "ec8_4"):
        row[f"cluster_{g}_read_phases"] = {
            "locate_ms": 123.45, "dial_ms": 23.45, "wait_ms": 345.67,
            "net_ms": 2345.67, "decode_ms": 1234.56,
            "gather_ms": 456.78, "wall_ms": 3456.78, "reps": 5,
            "dominant": "net",
        }
    row["cluster_ec8_4_degraded_read_read_MBps"] = 987.6
    row["cluster_ec8_4_degraded_read_spread_pct"] = 24.3
    row["cluster_ec8_4_degraded_read_read_reps_MBps"] = [980.1, 987.6,
                                                         995.2]
    row["cluster_ec8_4_degraded_read_read_phases"] = {
        "locate_ms": 234.56, "dial_ms": 34.56, "wait_ms": 456.78,
        "net_ms": 1456.78, "decode_ms": 2345.67, "gather_ms": 345.67,
        "wall_ms": 4567.89, "reps": 5, "dominant": "decode",
    }
    row["cluster_ec8_4_write_trace"] = {
        "rep_MBps": 431.2, "wall_ms": 297.123, "coverage_pct": 94.7,
        "by_role_ms": {"client": 401.2, "chunkserver": 233.4,
                       "master": 12.9},
        "spans": 64,
    }
    # shm-ring A/B fiducial (round 7: same-host shared-memory data
    # plane on vs LZ_SHM_RING=0 scatterv)
    row["cluster_ec8_4_write_shm"] = {
        "on_MBps": 512.3, "off_MBps": 431.2, "delta_pct": 18.8,
        "desc_parts": 1536, "engaged": True,
    }
    row["cluster_dbench8_MBps"] = 330.3
    row["cluster_dbench8_ops_per_s"] = 990.9
    row["cluster_dbench8_MBps_reps"] = [351.6, 330.3, 324.6]
    row["cluster_4k_read_native_us"] = 184.8
    row["cluster_4k_read_loop_us"] = 484.6
    # slo/flight-recorder fiducials (PR 3): worst-case-ish shape — a
    # degraded round with breaches in every class
    row["cluster_health_status"] = "degraded"
    row["cluster_slo_breaches"] = 1234
    row["cluster_slow_ops"] = 48
    row["cluster_slo_breaches_by_class"] = {
        "read": 400, "write": 400, "locate": 234, "replicate": 100,
        "nfs": 100,
    }
    # rebuild subsystem fiducials (round 6: RebuildEngine bench row)
    row["cluster_rebuild_MBps"] = 1234.5
    row["cluster_rebuild_s"] = 12.34
    row["cluster_rebuild_parts"] = 48
    # s3 gateway fiducials (this round: the third protocol front door)
    row["cluster_s3_put_MBps"] = 123.4
    row["cluster_s3_get_MBps"] = 234.5
    row["cluster_s3_list_ops"] = 45.6
    row["cluster_s3_spread_pct"] = 33.3
    row["cluster_s3_put_reps_MBps"] = [120.1, 123.4, 130.9]
    row["cluster_s3_get_reps_MBps"] = [230.0, 234.5, 240.1]
    row["cluster_s3_list_ops_reps"] = [44.1, 45.6, 47.0]
    # locate storm fiducials (round 7: shadow read replicas — the
    # metadata-plane A/B with its 1.8x aggregate-QPS target verdict)
    row["cluster_locate_qps"] = {
        "primary": 12345.6, "replica_topo": 23456.7, "x": 1.9,
        "target_x": 1.8, "target_met": True,
        "shadow_served": 123456, "stale_retries": 12,
    }
    row["cluster_locate_p99_ms"] = {"primary": 12.34, "replica_topo": 10.56}
    # per-tenant QoS A/B fiducial (this round: fair-share admission) —
    # victim p99 off->on under an abuser flood with its bound verdict
    row["cluster_qos_victim_p99_ms"] = {
        "off": 187.5, "on": 6.2, "bound_ms": 250.0,
        "abuser_sheds": 312, "target_met": True,
    }
    # hot-spot A/B fiducial (this round: the heat loop's adaptive
    # replication) — one viral 1-copy chunk, LZ_HEAT off vs on
    row["cluster_hotspot_read_MBps"] = {
        "off": 812.4, "on": 934.7, "copies": 3, "boost_s": 1.85,
        "target_met": True,
    }
    # failover RTO fiducial (this round: ISSUE 19) — the kill-primary
    # drill's detect->elect->promote->first-acked-write outage
    row["cluster_failover_rto_s"] = {
        "rto_s": 3.77, "promote_s": 0.34, "epoch": 1,
        "acked": 11, "lost": 0, "target_met": True,
    }
    row["cluster_locate_storm_detail"] = {
        "files": 100000, "servers": 1000, "populate_s": 4.2,
        "cs_ingest": {"real_cs": 128, "parts_each": 2000, "ingest_s": 1.9},
        "loop_stalls": 0, "shadow_lag": 0,
    }
    # bench-trajectory regression guard (this round): worst-case-ish —
    # a round where several fiducials regressed past tolerance
    row["bench_prev_round"] = 11
    row["bench_deltas_pct"] = {
        f"cluster_{g}_write_MBps": -31.5
        for g in ("ec8_4", "ec3_2", "xor3", "goal_2_2_copies")
    }
    row["bench_regressions"] = [
        "cluster_ec8_4_write_MBps", "cluster_ec3_2_write_MBps",
        "cluster_goal_2_2_copies_write_MBps", "cluster_xor3_write_MBps",
        "cluster_dbench8_ops_per_s",
    ]
    return row


def test_summary_line_fits_driver_tail():
    line = json.dumps(bench._summary_row(_fat_row()))
    assert len(line) <= bench.SUMMARY_BUDGET_BYTES, len(line)
    assert len(line) < 2000  # the driver's hard tail window
    parsed = json.loads(line)
    assert parsed["summary"] == 1 and parsed["full"] == "BENCH_FULL.json"
    # the verdict-bearing fields survived the compaction
    assert parsed["cluster_ec8_4_write_target_met"] is False
    assert "cluster_ec8_4_write_phases" in parsed
    # instruments on the drop ladder may be cut on a worst-case round,
    # but then the cut is RECORDED — never silent, never mid-JSON
    assert (
        parsed.get("cluster_ec8_4_write_trace", {}).get("coverage_pct")
        == 94.7
        or "ec8_4_write_trace" in parsed.get("dropped", [])
    )
    # write-window fiducials ride the tail for the target row only
    # (xor3/ec3_2 window dicts stay in BENCH_FULL.json); under budget
    # pressure the dict may drop, but then the drop is RECORDED
    assert (
        parsed.get("cluster_ec8_4_write_window", {}).get("depth") == 8
        or "ec8_4_write_window" in parsed.get("dropped", [])
    )
    assert not any("xor3_write_window" in k for k in parsed)
    # the shm on/off A/B delta rides the tail (or its drop is recorded),
    # and the send/encode ratio survives int compaction with decimals
    assert (
        parsed.get("cluster_ec8_4_write_shm", {}).get("delta_pct") == 18.8
        or "ec8_4_write_shm" in parsed.get("dropped", [])
    )
    if "cluster_ec8_4_write_phases" in parsed:
        assert parsed["cluster_ec8_4_write_phases"][
            "send_over_encode"] == 0.87
        assert parsed["cluster_ec8_4_write_phases"]["dominant"] == "encode"
    # the read-phase fiducials (ISSUE 18): the ec(8,4) roofline rides
    # the tail (or its drop is recorded); xor3/ec3_2 read phases are
    # full-file-only, per-rep arrays likewise
    assert (
        parsed.get("cluster_ec8_4_read_phases", {}).get("dominant")
        == "net"
        or "ec8_4_read_phases" in parsed.get("dropped", [])
    )
    if "cluster_ec8_4_read_phases" in parsed:
        # integer-ms compaction, dominant preserved
        assert parsed["cluster_ec8_4_read_phases"]["net_ms"] == 2346
    assert (
        parsed.get("cluster_ec8_4_degraded_read_read_phases", {})
        .get("dominant") == "decode"
        or "ec8_4_degraded_read_read_phases"
        in parsed.get("dropped", [])
    )
    assert not any("xor3_read_phases" in k for k in parsed)
    assert not any("ec3_2_read_phases" in k for k in parsed)
    # the degraded-read throughput scalar always rides (it is a
    # _read_MBps key, never on the drop ladder)
    assert parsed["cluster_ec8_4_degraded_read_read_MBps"] == 987.6
    assert "cluster_ec8_4_degraded_read_read_reps_MBps" not in parsed
    # slo fiducials ride the tail: noise attribution from the artifact
    assert parsed["cluster_health_status"] == "degraded"
    assert parsed["cluster_slo_breaches"] == 1234
    assert parsed["cluster_slow_ops"] == 48
    # the rebuild row survives compaction (RebuildEngine fiducials)
    assert parsed["cluster_rebuild_MBps"] == 1234.5
    assert parsed["cluster_rebuild_s"] == 12.34
    # the s3 gateway row rides the tail (this round's new front door);
    # on a worst-case round it may drop — recorded, never silent — and
    # per-rep arrays stay in BENCH_FULL.json either way
    for skey, sval in (("cluster_s3_put_MBps", 123.4),
                       ("cluster_s3_get_MBps", 234.5),
                       ("cluster_s3_list_ops", 45.6)):
        assert (parsed.get(skey) == sval
                or "s3_*" in parsed.get("dropped", []))
    assert "cluster_s3_put_reps_MBps" not in parsed
    # the locate-storm A/B verdict rides the tail (or its drop is
    # recorded); the detail dict is full-file-only
    assert (
        parsed.get("cluster_locate_qps", {}).get("target_met") is True
        or "locate_qps" in parsed.get("dropped", [])
    )
    assert "cluster_locate_storm_detail" not in parsed
    # the QoS A/B verdict rides the tail (or its drop is recorded)
    assert (
        parsed.get("cluster_qos_victim_p99_ms", {}).get("target_met")
        is True
        or "qos_victim_p99_ms" in parsed.get("dropped", [])
    )
    # the hot-spot A/B verdict rides the tail (or its drop is recorded)
    assert (
        parsed.get("cluster_hotspot_read_MBps", {}).get("target_met")
        is True
        or "hotspot_read_MBps" in parsed.get("dropped", [])
    )
    # the failover RTO verdict rides the tail (or its drop is recorded);
    # it sits LATE on the ladder — this round's headline fiducial
    assert (
        parsed.get("cluster_failover_rto_s", {}).get("lost") == 0
        or "failover_rto_s" in parsed.get("dropped", [])
    )
    # the C-client NFS row is full-file-only (decision-note input):
    # it must never crowd verdict-bearing rows out of the tail
    assert not any("C_client" in k for k in parsed)
    # the regression guard's verdict rides the tail (or its drop is
    # recorded); the full per-key delta map is full-file-only
    assert (
        parsed.get("bench_regressions") == _fat_row()["bench_regressions"]
        or "bench_regressions" in parsed.get("dropped", [])
    )
    assert parsed.get("bench_prev_round") == 11
    assert "bench_deltas_pct" not in parsed


def test_bench_delta_guard():
    """Round-over-round fiducial comparison: direction-aware deltas,
    tolerance-gated regressions, metric-mismatch guard on `value`."""
    prev = {
        "metric": "kernelA", "value": 1000.0,
        "cluster_ec8_4_write_MBps": 400.0,
        "cluster_dbench8_ops_per_s": 900.0,
        "reconstruct_1shard_cpu_ms": 100.0,
        "cluster_4k_read_native_us": 200.0,
        "box_memcpy_GBps": 10.0,
        "cluster_ec8_4_write_phases": {"send_ms": 1.0},  # non-scalar: skip
    }
    row = {
        "metric": "kernelA", "value": 990.0,          # -1%: fine
        "cluster_ec8_4_write_MBps": 250.0,            # -37.5%: regression
        "cluster_dbench8_ops_per_s": 1200.0,          # +33%: improvement
        "reconstruct_1shard_cpu_ms": 140.0,           # +40% latency: regression
        "cluster_4k_read_native_us": 190.0,           # faster: fine
        "box_memcpy_GBps": 9.5,
        "cluster_ec8_4_write_phases": {"send_ms": 2.0},
        "cluster_error": "oops",                      # non-numeric: skip
    }
    deltas, regs = bench.bench_deltas(row, prev)
    assert regs == [
        "cluster_ec8_4_write_MBps", "reconstruct_1shard_cpu_ms",
    ]
    assert deltas["cluster_ec8_4_write_MBps"] == -37.5
    assert deltas["cluster_dbench8_ops_per_s"] == pytest.approx(33.3, 0.1)
    assert "cluster_ec8_4_write_phases" not in deltas
    # a changed kernel metric makes `value` incomparable
    d2, _ = bench.bench_deltas({**row, "metric": "kernelB"}, prev)
    assert "value" not in d2


def test_bench_round_self_record_and_reload(tmp_path):
    """bench self-records its round file (numbered past any existing
    file, parseable or not) and the next run loads it back as the
    comparison base; a driver-captured tail cut mid-JSON contributes
    nothing (the pre-guard trajectory)."""
    # a truncated driver capture like the real BENCH_r05.json
    (tmp_path / "BENCH_r05.json").write_text(json.dumps({
        "n": 5, "tail": 'y_write_reps_MBps": [721.7, 773.6], "clus',
    }))
    assert bench._load_prev_round(str(tmp_path)) is None
    row = {"metric": "kernelA", "value": 100.0,
           "cluster_ec8_4_write_MBps": 400.0}
    bench._bench_guard(row, str(tmp_path))
    assert "bench_guard_error" not in row
    assert (tmp_path / "BENCH_r06.json").exists()
    n, prev_row = bench._load_prev_round(str(tmp_path))
    assert n == 6 and prev_row["cluster_ec8_4_write_MBps"] == 400.0
    # the next round compares against it and flags the regression
    row2 = {"metric": "kernelA", "value": 99.0,
            "cluster_ec8_4_write_MBps": 100.0}
    bench._bench_guard(row2, str(tmp_path))
    assert row2["bench_prev_round"] == 6
    assert row2["bench_regressions"] == ["cluster_ec8_4_write_MBps"]
    assert (tmp_path / "BENCH_r07.json").exists()
    # a driver tail whose LAST line is whole JSON is minable
    (tmp_path / "BENCH_r08.json").write_text(json.dumps({
        "n": 8,
        "tail": 'garbage {"cut": \n'
                + json.dumps({"summary": 1, "value": 50.0,
                              "metric": "kernelA"}) + "\n",
    }))
    n, mined = bench._load_prev_round(str(tmp_path))
    assert n == 8 and mined["value"] == 50.0


def test_bench_guard_fresh_baseline(tmp_path, capsys):
    """An empty BENCH trajectory must record a fresh round cleanly and
    SAY so — an explicit first-round DELTA line + bench_prev_round=0 in
    the row — instead of silently printing no DELTA output (which reads
    as 'guard never ran' in the driver tail)."""
    row = {"metric": "kernelA", "value": 100.0}
    bench._bench_guard(row, str(tmp_path))
    out = capsys.readouterr().out
    assert "DELTA" in out and "fresh baseline" in out
    assert row["bench_prev_round"] == 0
    assert "bench_guard_error" not in row
    assert (tmp_path / "BENCH_r01.json").exists()
    # a recorded-but-empty round is skipped as a compare base (nothing
    # to diff against), but numbering still advances past it
    (tmp_path / "BENCH_r02.json").write_text(
        json.dumps({"n": 2, "self_recorded": True, "row": {}}))
    row2 = {"metric": "kernelA", "value": 99.0}
    bench._bench_guard(row2, str(tmp_path))
    assert row2["bench_prev_round"] == 1  # compared against r01, not r02
    assert (tmp_path / "BENCH_r03.json").exists()


def test_summary_budget_guard_drops_not_truncates():
    """A pathologically fat round trims whole keys (recorded in
    ``dropped``) instead of being cut mid-JSON by the tail window."""
    row = _fat_row()
    row["kernel_ladder"] = {
        f"config-{i}": "RESOURCE_EXHAUSTED: " + "x" * 80 for i in range(12)
    }
    s = bench._summary_row(row)
    line = json.dumps(s)
    assert len(line) <= bench.SUMMARY_BUDGET_BYTES
    assert json.loads(line) == s  # whole, valid JSON
    assert "kernel_ladder" in s.get("dropped", []) or "kernel_ladder" in s


def test_summary_immune_to_unknown_row_keys():
    """Subsystems that add FILES but no fiducials (e.g. the invariant
    lint engine) must not be able to regress the tail summary: the
    summary is allowlist-built, so arbitrary new row keys — however
    many, however fat — change NOTHING about the emitted line. This
    pins that property structurally instead of hoping each new
    subsystem remembers it."""
    base = bench._summary_row(_fat_row())
    row = _fat_row()
    for i in range(50):
        row[f"lint_findings_shard_{i}"] = {"rule": "x" * 120, "n": i}
    row["lint_waivers"] = ["cross-await-race"] * 100
    polluted = bench._summary_row(row)
    assert polluted == base  # byte-identical: unknown keys never ride
    assert len(json.dumps(polluted)) <= bench.SUMMARY_BUDGET_BYTES


def test_summary_keeps_targets_under_any_drop():
    row = _fat_row()
    row["kernel_ladder"] = {f"c{i}": "e" * 200 for i in range(20)}
    s = bench._summary_row(row)
    # target verdicts are never on the drop ladder
    assert "cluster_ec8_4_write_target_met" in s
    assert "cluster_goal_2_2_copies_write_target_met" in s
    # nor are the scalar slo fiducials (only the per-class split may
    # drop under pressure)
    assert "cluster_health_status" in s
    assert "cluster_slo_breaches" in s
