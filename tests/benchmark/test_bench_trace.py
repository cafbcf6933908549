"""The reduction from a trace to numbers, on the recorded trace kept
beside it, and the roofline arithmetic on hand-worked shapes."""

import json
import os

import pytest

import manifest
import rooflines

tr = manifest.load_module("trace", "reduce.py")
with open(os.path.join(manifest.HERE, "trace",
                       "recorded_v5e_stream_write.json")) as f:
    REC = json.load(f)
T0, T1 = REC["t0_ns"], REC["t1_ns"]
PEAKS = manifest.peaks_for("TPU v5 lite")


def brute_busy_ns(events, t0, t1):
    """Busy time by marking every 100 ns slot (independent of union)."""
    step = 100
    slots = bytearray((t1 - t0) // step + 1)
    for _n, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        for s in range((a - t0) // step, max((b - t0 - 1) // step + 1, 0)):
            slots[s] = 1
    return sum(slots) * step


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert tr.union([]) == []


def test_busy_union_and_idle_share_on_the_recorded_trace():
    red = tr.reduce(REC, T0, T1)
    ops = REC["device"]["/device:TPU:0"]
    brute = brute_busy_ns(ops, T0, T1)
    assert red["busy_s"] * 1e9 == pytest.approx(brute, rel=0.02)
    assert red["window_s"] == pytest.approx(0.4)
    assert red["idle_pct"] == pytest.approx(
        100 * (1 - red["busy_s"] / 0.4))
    assert 98.0 < red["idle_pct"] < 99.5  # the chip all but idle


def test_per_program_sums_on_the_recorded_trace():
    red = tr.reduce(REC, T0, T1)
    assert list(red["program_s"]) == ["jit_apply_gf"]
    assert red["program_n"]["jit_apply_gf"] == len(REC["programs"]) == 41
    assert red["program_s"]["jit_apply_gf"] == pytest.approx(
        sum(e[2] for e in REC["programs"]) / 1e9)
    # ops run inside programs: their union cannot exceed the programs'
    assert red["busy_s"] <= red["program_s"]["jit_apply_gf"] * 1.001


def test_top_device_ops_are_named_shortly():
    red = tr.reduce(REC, T0, T1)
    names = [n for n, _s in red["device_ops"]]
    assert names[0].startswith("%fusion") and "u8[4,1048576]" in names[0]
    assert all(len(n) <= 96 for n in names)
    secs = [s for _n, s in red["device_ops"]]
    assert secs == sorted(secs, reverse=True) and len(secs) <= 10


def test_gap_attribution_on_the_recorded_trace():
    red = tr.reduce(REC, T0, T1)
    gaps = red["idle_gaps"]
    assert 1 <= len(gaps) <= 10
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert sum(g[1] for g in gaps) <= red["window_s"] - red["busy_s"] + 1e-9
    assert {g[0] for g in gaps} <= {"bench.encode", "bench.op.write",
                                    "bench.op.create", "bench.op.unlink",
                                    "bench.op.setattr", "no bench span"}
    assert "bench.encode" in {g[0] for g in gaps}


def test_gap_attribution_by_hand():
    ev = {"device": {"/device:TPU:0": [["k", 100, 50], ["k", 400, 100]]},
          "programs": [["jit_k(1)", 100, 50], ["jit_k(1)", 400, 100]],
          "host": [["bench.window", 0, 1000], ["bench.op.write", 0, 1000],
                   ["bench.encode", 160, 230], ["bench.op.read", 600, 100]]}
    red = tr.reduce(ev, 0, 1000)
    assert red["busy_s"] == pytest.approx(150e-9)
    assert red["idle_pct"] == pytest.approx(85.0)
    assert red["program_s"] == {"jit_k": pytest.approx(150e-9)}
    # gaps: [0,100) [150,400) [500,1000); the encode call covers 230 of
    # the 250 ns gap, so that gap is the boundary's; the others the op's
    assert red["idle_gaps"] == [["bench.op.write", pytest.approx(500e-9)],
                                ["bench.encode", pytest.approx(250e-9)],
                                ["bench.op.write", pytest.approx(100e-9)]]


def test_overlap_of_interval_lists():
    assert tr.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert tr.overlap([(0, 10)], [(10, 20)]) == 0
    assert tr.overlap([], [(0, 5)]) == 0
    assert tr.overlap([(0, 4), (6, 9)], [(1, 2), (3, 7), (8, 20)]) == 4


def test_device_time_under_a_span_by_hand():
    ev = {"device": {"/device:TPU:0": [["k", 100, 50], ["other", 400, 100]]},
          "programs": [["jit_renamed(1)", 100, 50], ["jit_other(2)", 400, 100]],
          "host": [["bench.window", 0, 1000], ["bench.encode", 90, 80],
                   ["bench.encode", 120, 100], ["bench.recover", 450, 20]]}
    red = tr.reduce(ev, 0, 1000)
    # whatever the program is called: what ran while the span was open
    assert red["span_device_s"]["bench.encode"] == pytest.approx(50e-9)
    assert red["span_device_s"]["bench.recover"] == pytest.approx(20e-9)
    assert red["span_device_s"]["bench.window"] == pytest.approx(150e-9)


def test_device_time_of_the_recorded_trace_lies_under_its_encode_spans():
    red = tr.reduce(REC, T0, T1)
    assert red["span_device_s"]["bench.encode"] == pytest.approx(
        red["busy_s"], rel=1e-6)
    assert red["span_device_s"].get("bench.recover", 0.0) == 0.0


def test_roofline_readers_read_by_span_not_by_program_name():
    class Tap:
        encode_calls = [(8, 4, 8, 1 << 20, 0.01)] * 41
        recover_calls = [(8, 4, 8, 1, 1 << 20, 0.01)] * 10

    red = tr.reduce(REC, T0, T1)
    renamed = dict(REC, programs=[["jit_some_new_kernel(7)", a, d]
                                  for _n, a, d in REC["programs"]])
    ctx = {"tap": Tap(), "trace": red, "peaks": PEAKS}
    enc = manifest.load_reader("encode_kernel_roofline")
    rec = manifest.load_reader("recover_kernel_roofline")
    share = enc(ctx)
    assert 5.0 < share < 100.0
    assert enc(dict(ctx, trace=tr.reduce(renamed, T0, T1))) == \
        pytest.approx(share)
    assert rec(ctx) is None      # no device time under bench.recover
    assert enc(dict(ctx, peaks=None)) is None


def test_window_defaults_to_the_events_span():
    ev = {"device": {"p": [["k", 10, 5]]}, "programs": [],
          "host": [["bench.op.x", 0, 40]]}
    assert tr.window_of(ev) == (0, 40)
    assert tr.reduce(ev)["window_s"] == pytest.approx(40e-9)


def test_gf_product_cost_by_hand():
    # ec(8,4) over 1 MiB streams: 12 MiB moved, 2*8*4 ops a byte column
    nbytes, ops = rooflines.gf_product_cost(8, 4, 1 << 20)
    assert nbytes == 12 * (1 << 20) and ops == 64 * (1 << 20)
    least, bound = rooflines.least_seconds(nbytes, ops, PEAKS)
    assert bound == "bytes"
    assert least == pytest.approx(12 * (1 << 20) / 819e9)
    # a product so wide that arithmetic bounds it: 2*r*w/(r+w) > 393e12/819e9
    nbytes, ops = rooflines.gf_product_cost(512, 512, 1024)
    assert rooflines.least_seconds(nbytes, ops, PEAKS)[1] == "ops"


def test_roofline_share_by_hand():
    calls = [(8, 4, 1 << 20)] * 41
    least = 41 * 12 * (1 << 20) / 819e9
    share, bound = rooflines.roofline_share_pct(calls, 2 * least, PEAKS)
    assert share == pytest.approx(50.0) and bound == "bytes"
    assert rooflines.roofline_share_pct([], 1.0, PEAKS) is None
    assert rooflines.roofline_share_pct(calls, 0.0, PEAKS) is None


def test_roofline_of_the_recorded_programs_is_a_share():
    red = tr.reduce(REC, T0, T1)
    calls = [(8, 4, 1 << 20)] * red["program_n"]["jit_apply_gf"]
    share, bound = rooflines.roofline_share_pct(
        calls, red["program_s"]["jit_apply_gf"], PEAKS)
    assert bound == "bytes" and 5.0 < share < 100.0


def test_readers_return_nothing_where_there_is_nothing_to_read():
    class Tap:
        encode_calls, recover_calls = [], []

    ctx = {"window_s": 1.0, "ops": [], "phases": {"write": {}, "read": {}},
           "tap": Tap(), "trace": None, "config": {}, "peaks": PEAKS}
    for m in manifest.load_manifest()["per_layer"]:
        assert manifest.load_reader(m["name"])(ctx) is None, m["name"]
