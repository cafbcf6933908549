"""The two readers of the read-modify-write branch's counts
(``PhaseBreakdown.count``, which ``ctx["phases"]`` carries beside
``reps``), each on a hand-made context: the number it gives from known
rows, and None where the program has no such count (the parent of the
PR that brought them), where the side closed no op, or where no byte
was handed in."""

import pytest

import manifest

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
    assert {m["name"] for m in M["per_layer"]
            if m["source"] == "program_counter"} == set(EXPECT)


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
    assert {m["name"] for m in cell.per_layer} == set(EXPECT) | {
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
