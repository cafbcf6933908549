"""The ``mixed-goals`` cell (configuration ``mixed-goals-13cs``: two
copies, ``$xor3``, ``$ec(3,2)`` and ``$ec(8,4)`` side by side, a
directory each, under ``stream-write``) and its four readers:
``xor_kernel_roofline`` (xor parity's device time under ``bench.xor``
against its bytes), ``xor_device_wait_pct`` (its fetch leg over its four
legs), ``write_MBps.xor`` and ``write_MBps.copies`` (the bytes the
chunkservers acknowledged under the family, over the window). On a
planted ``ctx`` each gives the number the rows ask for, and None on a
program without the rows or counts (the parent's), where no op closed
or where the base is empty; the entries are appended after every earlier
one; the configuration gives each goal one writer."""

import pytest

import manifest
import test_bench_loop
from tap import TapCounts

M = manifest.load_manifest()
CELL = "mixed-goals"
NEW = ["xor_kernel_roofline", "xor_device_wait_pct", "write_MBps.xor",
       "write_MBps.copies"]
ENTRIES = {
    "xor_kernel_roofline": ("%", "device_trace", "kernels"),
    "xor_device_wait_pct": ("%", "program_span", "encoder boundary"),
    "write_MBps.xor": ("MB/s", "program_counter", "client write path"),
    "write_MBps.copies": ("MB/s", "program_counter", "chunkserver and wire"),
}
WINDOW_S = 20.0


def planted(**write) -> dict:
    """A 20 s window of four writers: 6,000 MB of copies and 5,000 MB of
    xor3 acknowledged; xor's four legs 40 + 60 + 20 + 80 ms; four xor
    calls of 3 parts of 1 MiB with 100 us of device time under them."""
    rows = {"reps": 3000, "wall_ms": 90000.0, "self_ms": 900.0,
            "copies_payload_bytes": 6_000_000_000,
            "xor_payload_bytes": 5_000_000_000,
            "ec_payload_bytes": 12_000_000_000, "chain_parts": 1500,
            "xor_boundary_ms": 210.0, "xor_dev_stage_ms": 40.0,
            "xor_dev_put_ms": 60.0, "xor_dev_run_ms": 20.0,
            "xor_dev_fetch_ms": 80.0}
    rows.update(write)
    return {"window_s": WINDOW_S, "phases": {"write": rows,
                                             "read": {"reps": 0}},
            "ops": [], "config": {},
            "tap": TapCounts((), (), tuple((3, 1 << 20, 0.01)
                                           for _ in range(4))),
            "trace": {"span_device_s": {"bench.xor": 1e-4}},
            "peaks": manifest.peaks_for("TPU v5 lite")}


def test_readers_on_known_rows():
    ctx = planted()
    read = {name: manifest.load_reader(name) for name in NEW}
    assert read["write_MBps.copies"](ctx) == pytest.approx(6000 / WINDOW_S)
    assert read["write_MBps.xor"](ctx) == pytest.approx(5000 / WINDOW_S)
    assert read["xor_device_wait_pct"](ctx) == pytest.approx(
        100.0 * 80 / (40 + 60 + 20 + 80))
    # three parts read and one written, 1 MiB each, four calls, over
    # 819 GB/s: bytes-bound, as rooflines.xor_cost says
    least = 4 * 4 * (1 << 20) / ctx["peaks"]["hbm_bytes_per_s"]
    assert read["xor_kernel_roofline"](ctx) == pytest.approx(
        100 * least / 1e-4)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_on_the_parents_program(name):
    """The parent counts no family and charges no xor row (its xor call
    had no span), and a run traced there may hold no xor call under
    ``bench.xor``: None, and the line leaves the metric out."""
    ctx = planted()
    rows = ctx["phases"]["write"]
    for key in [k for k in rows if k.startswith("xor_")
                or k.endswith("_payload_bytes") or k == "chain_parts"]:
        del rows[key]
    ctx["tap"] = TapCounts((), ())
    assert manifest.load_reader(name)(ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_where_nothing_ran(name):
    idle = planted(reps=0, copies_payload_bytes=0, xor_payload_bytes=0,
                   xor_dev_stage_ms=0.0, xor_dev_put_ms=0.0,
                   xor_dev_run_ms=0.0, xor_dev_fetch_ms=0.0)
    idle["tap"] = TapCounts((), ())
    idle["trace"] = None
    assert manifest.load_reader(name)(idle) is None


def test_no_xor_call_is_no_base_and_no_copy_reads_zero():
    ctx = planted(xor_dev_stage_ms=0.0, xor_dev_put_ms=0.0,
                  xor_dev_run_ms=0.0, xor_dev_fetch_ms=0.0,
                  copies_payload_bytes=0)
    assert manifest.load_reader("xor_device_wait_pct")(ctx) is None
    assert manifest.load_reader("write_MBps.copies")(ctx) == 0.0
    assert manifest.load_reader("xor_kernel_roofline")(
        dict(ctx, trace={"span_device_s": {}})) is None


@pytest.mark.parametrize("name", NEW)
def test_entry_of_the_new_metric(name):
    unit, source, layer = ENTRIES[name]
    entry, = [m for m in M["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": "higher",
                     "source": source, "layer": layer,
                     "moves": "write_MBps", "workloads": [CELL]}


def test_the_four_are_appended_after_every_earlier_entry():
    names = [m["name"] for m in M["per_layer"]]
    assert names[-len(NEW):] == NEW
    # the loop meter's fifteen keep their order with these after them
    assert test_bench_loop.in_order(test_bench_loop.NAMES, M["per_layer"])


def test_the_cell_and_its_metrics():
    entry, = [w for w in M["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "mixed-goals-13cs", "stream-write", 1)
    cell = manifest.Cell(M, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"write_MBps", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(NEW) | {
        "write_encode_busy_pct", "write_send_busy_pct",
        "device_idle_pct.write"}
    assert all(m["moves"] == "write_MBps" for m in cell.per_layer)
    # the configuration is the last one, the cell the last one
    assert M["configs"][-1]["name"] == "mixed-goals-13cs"
    assert M["workloads"][-1]["name"] == CELL


def test_each_goal_has_one_writer_on_the_ec84_cluster():
    cell = manifest.Cell(M, CELL)
    cfg, plain = cell.config, manifest.Cell(M, "ec84-stream-write").config
    forms = {g["name"]: {k: v for k, v in g.items()
                         if k not in ("id", "name", "expr")}
             for g in cfg["goals"]}
    assert forms == {"copies2": {"copies": 2}, "xor3": {"xor": 3},
                     "ec32": {"k": 3, "m": 2}, "ec84": {"k": 8, "m": 4}}
    assert {g["name"]: g["expr"] for g in cfg["goals"]} == {
        "copies2": "_ _", "xor3": "$xor3", "ec32": "$ec(3,2)",
        "ec84": "$ec(8,4)"}
    assert len({g["id"] for g in cfg["goals"]}) == 4
    # session s works in directory s % 4: one writer a goal, at full
    # size and in the rehearsal
    assert [d["goal"] for d in cfg["directories"]] == list(forms)
    assert cell.mix["sessions"] == len(cfg["directories"]) == 4
    assert cfg["rehearsal"]["processes"] == 4
    # the most parts a goal keeps, and one spare
    assert cfg["chunkservers"] == 8 + 4 + 1
    # the writers are ec84-13cs's: the same geometry, transfers and files
    for key in ("block_bytes", "chunk_bytes", "transfer_bytes",
                "file_bytes"):
        assert cfg[key] == plain[key], key
    assert set(cfg["guarantees"]) == set(plain["guarantees"])
