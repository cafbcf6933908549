"""``tools/profile_spans``: the program's spans beside the device's
operations, on events written by hand."""

from lizardfs_tpu.tools import profile_spans as ps

MS = 1_000_000
BASE = 1_790_000_000 * 1_000_000_000  # the rings' clock, far from 0


def h(name, start_ms, dur_ms, **stats):
    return [name, int(start_ms * MS), int(dur_ms * MS), stats]


HOST = [
    h("bench.window", 0, 100),
    h("bench.op.write", 1, 40),
    h("lz.client.pwrite", 2, 38, t_ns=BASE + 2 * MS - 900, bytes=2097152),
    h("lz.client.grant", 3, 10),
    h("lz.client.encode", 13, 10),
    h("lz.encoder.boundary", 14, 8),
    h("lz.encoder.boundary", 55, 12),
    h("lz.encoder.dev_run", 16, 1),
    h("lz.encoder.dev_fetch", 17, 5),
    h("lz.client.send", 23, 15),
    h("bench.op.write", 50, 40),
    h("lz.client.pwrite", 51, 38, t_ns=BASE + 51 * MS - 1100, bytes=2097152),
    h("lz.encoder.dev_run", 60, 1),
    h("lz.encoder.dev_fetch", 61, 5),
    h("lz.client.getattr", 95, 2),  # outside any bench.op span
]
PROGRAMS = [["jit_apply_gf(1)", int(18 * MS), int(0.06 * MS)],   # in fetch
            ["jit_apply_gf(1)", int(60.5 * MS), int(0.06 * MS)],  # in run
            ["jit_apply_gf(1)", int(59 * MS), int(0.06 * MS)],  # leads it
            ["jit_other(2)", int(80 * MS), int(0.06 * MS)]]       # astray
DEVICE = [[n.split("(")[0], s, d] for n, s, d in PROGRAMS]


def test_clock_is_the_roots_t_ns_less_their_start():
    got = ps.clock(HOST)
    assert got["roots"] == 2
    assert got["offset_ns"] == BASE - 1100
    assert got["spread_ns"] == [0, 200]
    assert ps.clock([h("lz.client.grant", 0, 1)]) is None


def test_nesting_counts_the_spans_inside_the_callers():
    got = ps.nesting(HOST, "bench.op.")
    assert got["lz_spans"] == 12 and got["inside"] == 11
    assert got["outside_by_name"] == {"lz.client.getattr": 1}


def test_launches_place_each_program_run():
    got = ps.launches(HOST, PROGRAMS)
    lead = got.pop("lead_ms")
    assert got == {"runs": 4, "in_fetch": 1, "run_to_fetch_end": 1,
                   "in_boundary": 1, "astray": 1}
    # dev_run starts at 16 and 60: the runs at 18, 60.5 and 59 start 2
    # and 0.5 ms after theirs, and 1 ms BEFORE it (the device's clock
    # leads the host's by at least that)
    assert [round(v, 2) for v in (lead[0], lead[2], lead[-1])] == [
        -2.0, -0.5, 1.0]


def test_gaps_are_named_by_the_innermost_covering_span():
    got = ps.gaps(HOST, DEVICE, 0, 100 * MS, top=3)
    assert [round(g["gap_ms"], 2) for g in got] == [40.94, 19.94, 19.44]
    # 18.06..59: the first pwrite (to 40) covers just over half of
    # it, and nothing inside that pwrite does
    assert got[0]["span"] == "lz.client.pwrite"
    # 80.06..100: the second pwrite ends at 89 (under half)
    assert got[1]["span"] == "no lz span"
    # 60.56..80: the second pwrite covers it all; nothing inside does
    assert got[2]["span"] == "lz.client.pwrite"
    # 0..18: pwrite (2..40) covers 16 of 18, grant 10 of 18: grant is
    # the innermost that covers half
    first = ps.gaps(HOST, DEVICE[:1], 0, 18 * MS, top=1)[0]
    assert first["span"] == "lz.client.grant"
    assert round(first["span_ms"], 1) == 10.0


def test_report_takes_the_benchmarks_window_and_sums_by_name():
    rep = ps.report({"host": HOST, "device": DEVICE, "programs": PROGRAMS})
    assert rep["window_ns"] == [0, 100 * MS]
    assert rep["lz_spans"]["lz.client.pwrite"] == {"n": 2, "ms": 76.0}
    assert rep["clock"]["roots"] == 2 and len(rep["gaps"]) == 5
