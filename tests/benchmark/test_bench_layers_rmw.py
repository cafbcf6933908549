"""The two readers of the read-modify-write branch's counts
(``PhaseBreakdown.count``, which ``ctx["phases"]`` carries beside
``reps``), each on a hand-made context: the number it gives from known
rows, and None where the program has no such count (the parent of the
PR that brought them), where the side closed no op, or where no byte
was handed in."""

import pytest

import manifest
from tap import TapCounts

M = manifest.load_manifest()
CELL = "ec32-stream-write"

# one 64 MiB chunk written in 32 sequential pwrites of 2 MiB at ec(3,2):
# 21 start inside a 192 KiB stripe and read its 1 or 2 live blocks back
# (11 x 128 KiB + 10 x 64 KiB); every call re-encodes whole stripes, the
# last one as far as the chunk goes
WRITE = {
    "reps": 32, "wall_ms": 1300.0, "self_ms": 60.0, "rmw_read_ms": 90.0,
    "rmw_patch_ms": 30.0, "encode_ms": 250.0, "send_ms": 500.0,
    "rmw_reads": 21, "rmw_read_bytes": 2 * 2**20,
    "rmw_region_bytes": 69568 * 1024, "payload_bytes": 64 * 2**20,
}
EXPECT = {
    "write_rmw_calls_pct": 100.0 * 21 / 32,
    "write_rmw_extra_bytes_pct":
        100.0 * (2048 + 69568 - 65536) / 65536,
}
# what the parent's program charges: phase rows and reps, no counts
PARENT = {k: v for k, v in WRITE.items()
          if k.endswith("_ms") or k == "reps"}


def ctx_of(write):
    return {"window_s": 10.0, "phases": {"write": write, "read": {}},
            "ops": [], "tap": None, "trace": None, "config": {},
            "peaks": None}


def test_the_counter_metrics_are_these_two():
    """These two at the least: any PR may add a reader of a count."""
    assert {m["name"] for m in M["per_layer"]
            if m["source"] == "program_counter"} >= set(EXPECT)


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_on_known_rows(name):
    assert manifest.load_reader(name)(ctx_of(WRITE)) == \
        pytest.approx(EXPECT[name])
    aligned = dict(WRITE, rmw_reads=0, rmw_read_bytes=0,
                   rmw_region_bytes=WRITE["payload_bytes"])
    assert manifest.load_reader(name)(ctx_of(aligned)) == 0.0


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_finds_nothing_on_the_parents_program(name):
    assert manifest.load_reader(name)(ctx_of(PARENT)) is None


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_finds_nothing_where_no_op_closed(name):
    read = manifest.load_reader(name)
    assert read(ctx_of(dict(WRITE, reps=0))) is None
    assert read(ctx_of({})) is None


def test_no_payload_is_no_base():
    assert manifest.load_reader("write_rmw_extra_bytes_pct")(
        ctx_of(dict(WRITE, payload_bytes=0))) is None


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_entry_of_the_metric(name):
    entry = next(m for m in M["per_layer"] if m["name"] == name)
    assert entry == {
        "name": name, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "client write path",
        "moves": "write_MBps", "workloads": [CELL]}


def test_the_cell_reads_the_generic_write_metrics_too():
    cell = manifest.Cell(M, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"write_MBps", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= set(EXPECT) | {
        "write_encode_busy_pct", "write_send_busy_pct",
        "encode_kernel_roofline", "device_idle_pct.write"}
    assert cell.mix["transfer_bytes"] == 2 * 2**20
    assert cell.mix["sizes"] == {"fixed": 256 * 2**20}
    assert cell.mix["sessions"] == 4
    goal, = cell.config["goals"]
    stripe = goal["k"] * cell.config["block_bytes"]
    assert cell.mix["transfer_bytes"] % stripe, "no whole stripes"
    assert (cell.config["chunk_bytes"] // cell.config["block_bytes"]) \
        % goal["k"], "the chunk's last stripe is short"


# -- the readers that waited for PRs 26 and 30 (PR 33) --------------------
#
# The readers that waited for PRs 26 and 30: the windowed whole-chunk
# write's counts (``ec84-put``) and the read-modify-write branch's two
# spans (``ec32-stream-write``), each on a hand-made context: the number
# it gives from known rows, and None where the program charges no such
# row (the parent of the PR that brought it), where the side closed no
# op, or where the base is empty. And every reader the manifest names,
# whoever added it, finds nothing to read in a run that did nothing.

# 10 PUTs of 128 MiB: 20 whole chunks, 19 through the window, 8
# segments each, 30 of which waited for credits; 12 parts a segment
PUT = {
    "reps": 10, "wall_ms": 9500.0, "self_ms": 50.0, "encode_ms": 7000.0,
    "send_ms": 6500.0, "window_chunks": 19, "fallback_chunks": 1,
    "window_segments": 152, "window_credit_waits": 30,
    "window_depth_sum": 400, "ring_parts": 1800, "socket_parts": 24,
}
# one 64 MiB chunk at ec(3,2) in 32 pwrites, 21 of which read back
RMW = {
    "reps": 32, "wall_ms": 1300.0, "self_ms": 60.0, "rmw_read_ms": 105.0,
    "rmw_patch_ms": 40.0, "encode_ms": 250.0, "send_ms": 500.0,
    "rmw_reads": 21, "rmw_read_bytes": 2 * 2**20,
    "rmw_region_bytes": 69568 * 1024, "payload_bytes": 64 * 2**20,
}
WAITING = {
    "write_window_chunks_pct": (PUT, 95.0, "ec84-put", "program_counter"),
    "write_credit_waits_pct": (PUT, 100.0 * 30 / 152, "ec84-put",
                               "program_counter"),
    "write_ring_parts_pct": (PUT, 100.0 * 1800 / 1824, "ec84-put",
                             "program_counter"),
    "write_rmw_read_ms": (RMW, 5.0, "ec32-stream-write", "program_span"),
    "write_rmw_patch_ms": (RMW, 1.25, "ec32-stream-write", "program_span"),
}
# what made the reader's base, zeroed: nothing to take a share of
NO_BASE = {
    "write_window_chunks_pct": {"window_chunks": 0, "fallback_chunks": 0},
    "write_credit_waits_pct": {"window_segments": 0},
    "write_ring_parts_pct": {"ring_parts": 0, "socket_parts": 0},
    "write_rmw_read_ms": {"rmw_reads": 0},
}


def ctx_waiting(write, read=None):
    return {"window_s": 10.0, "phases": {"write": write, "read": read or {}},
            "ops": [], "tap": TapCounts((), ()), "trace": None, "config": {},
            "peaks": None, "t_open": 0.0, "t_close": 10.0, "master": {},
            "rebuild": None}


@pytest.mark.parametrize("name", sorted(WAITING))
def test_waiting_reader_on_known_rows(name):
    rows, want, _cell, _source = WAITING[name]
    assert manifest.load_reader(name)(ctx_waiting(rows)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(WAITING))
def test_reader_finds_nothing_on_a_program_without_the_row(name):
    rows = WAITING[name][0]
    spans_only = {k: v for k, v in rows.items()
                  if k == "reps" or (k.endswith("_ms")
                                     and not k.startswith("rmw_"))}
    assert manifest.load_reader(name)(ctx_waiting(spans_only)) is None
    assert manifest.load_reader(name)(ctx_waiting(dict(rows, reps=0))) is None


@pytest.mark.parametrize("name", sorted(NO_BASE))
def test_reader_leaves_an_empty_base_out(name):
    rows = dict(WAITING[name][0], **NO_BASE[name])
    assert manifest.load_reader(name)(ctx_waiting(rows)) is None


@pytest.mark.parametrize("name", sorted(WAITING))
def test_entry_of_the_waiting_metric(name):
    _rows, _want, cell, source = WAITING[name]
    entry = next(m for m in M["per_layer"] if m["name"] == name)
    assert (entry["source"], entry["moves"]) == (source, "write_MBps")
    assert cell in entry["workloads"], "at least this cell: more may join"
    assert entry["unit"] == ("ms" if name.endswith("_ms") else "%")


@pytest.mark.parametrize("name", sorted(m["name"] for m in M["per_layer"]))
def test_every_reader_finds_nothing_in_a_run_that_did_nothing(name):
    """No reader raises on an empty context, and none returns 0 for a
    share of something that is not there."""
    assert manifest.load_reader(name)(ctx_waiting({})) is None
