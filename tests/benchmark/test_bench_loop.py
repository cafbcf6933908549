"""The fifteen readers of the client's loop meter (PR 36):
``benchmark/layers/_loop.py`` and the five families
``client_loop_busy_pct`` / ``client_loop_delay_ms`` /
``client_loop_offcpu_pct`` / ``client_loop_named_pct`` / ``wake_ms``,
each as ``.write``, ``.ops`` and ``.read``. On a planted ``ctx`` each
gives the number the rows ask for wherever the loop's counts ride (the
side that closed no op, the other, both), and None on the rows of a
program without the counts (the parent's); every entry names cells
that report the metric it moves; and the mixed cell at its rehearsal's
size, in process, gives all five of its variant.

A file of its own: the files that were here when the PR started are the
accepted benchmark's, and not this PR's to edit."""

import asyncio
import time

import pytest

import generator
import manifest

from tests.test_cluster import Cluster, EC_GOAL

M = manifest.load_manifest()
FAMILIES = {
    # name: (unit, better, source, what the planted rows give)
    "client_loop_busy_pct": ("%", "lower", "program_counter", 50.0),
    "client_loop_delay_ms": ("ms", "lower", "program_counter", 1.0),
    "client_loop_offcpu_pct": ("%", "lower", "program_counter", 25.0),
    "client_loop_named_pct": ("%", "higher", "program_span", 15.0),
    "wake_ms": ("ms", "lower", "program_span", 2.0),
}
VARIANTS = {
    "write": ("write_MBps", ["ec84-stream-write", "ec32-stream-write",
                             "ec84-put"]),
    "ops": ("ops_per_s", ["ec32-small-files", "ec84-s3-mixed"]),
    "read": ("read_MBps", ["ec84-degraded-read"]),
}
NAMES = [f"{fam}.{var}" for fam in FAMILIES for var in VARIANTS]
COUNTS = {"loop_turns": 1000, "loop_busy_us": 10_000_000,
          "loop_turn_sq_us2": 40_000_000_000, "loop_offcpu_us": 2_500_000}


def planted(where: str | None) -> dict:
    """Twenty seconds: 150 writes, 30 lookups and 20 unlinks closed, no
    read; the holds and the wakes on both sides; the loop's counts on
    the side that closed no op, on the other, half on each, nowhere."""
    read = {"reps": 0, "wall_ms": 0.0, "self_ms": 0.0, "lookups": 30,
            "get_xattrs": 0, "lookup_ms": 660.0, "wake_ms": 100.0,
            "copy_hold_ms": 1000.0}
    write = {"reps": 150, "wall_ms": 9000.0, "self_ms": 90.0, "unlinks": 20,
             "encode_ms": 4000.0, "wake_ms": 300.0, "ingest_hold_ms": 400.0,
             "rmw_patch_hold_ms": 100.0}
    if where == "read":
        read.update(COUNTS)
    elif where == "write":
        write.update(COUNTS)
    elif where == "both":
        read.update({k: v // 2 for k, v in COUNTS.items()})
        write.update({k: v - v // 2 for k, v in COUNTS.items()})
    return {"window_s": 20.0, "phases": {"write": write, "read": read},
            "ops": [], "trace": None}


@pytest.mark.parametrize("where", ["read", "write", "both"])
@pytest.mark.parametrize("name", NAMES)
def test_a_reader_counts_the_loop_once_wherever_its_counts_ride(name, where):
    want = FAMILIES[name.rsplit(".", 1)[0]][3]
    assert manifest.load_reader(name)(planted(where)) == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_a_parent_without_the_counts_reads_none(name):
    """The parent's rows: no count, no hold, no wake. Nothing raises,
    nothing is reported."""
    ctx = planted(None)
    for side in ctx["phases"].values():
        for key in [k for k in side if k.endswith("_hold_ms")
                    or k == "wake_ms"]:
            del side[key]
    assert manifest.load_reader(name)(ctx) is None
    assert manifest.load_reader(name)(
        {"window_s": 20.0, "phases": {"write": {}, "read": {}}}) is None


def test_no_op_closed_and_no_busy_time_read_none():
    ctx = planted("read")
    for side in ctx["phases"].values():
        side.update(reps=0, lookups=0, unlinks=0)
    assert manifest.load_reader("wake_ms.ops")(ctx) is None
    ctx["phases"]["read"].update(loop_busy_us=0, loop_offcpu_us=0)
    assert manifest.load_reader("client_loop_offcpu_pct.ops")(ctx) is None
    assert manifest.load_reader("client_loop_named_pct.ops")(ctx) is None
    assert manifest.load_reader("client_loop_busy_pct.ops")(ctx) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_an_entry_names_cells_that_report_what_it_moves(name):
    entry, = [m for m in M["per_layer"] if m["name"] == name]
    family, variant = name.rsplit(".", 1)
    unit, better, source, _ = FAMILIES[family]
    moves, cells = VARIANTS[variant]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": "client loop and GIL",
                     "moves": moves, "workloads": cells}
    for cell_name in cells:
        cell = manifest.Cell(M, cell_name)
        assert moves in {m["name"] for m in cell.end_to_end}
        assert name in {m["name"] for m in cell.per_layer}


def in_order(names: list, entries: list) -> bool:
    """Every name among the entries, in that order, anywhere: later
    entries go at the end of ``per_layer``, after these."""
    it = iter(m["name"] for m in entries)
    return all(name in it for name in names)


def test_the_new_entries_are_the_last_fifteen_and_the_rebuild_cell_has_none():
    # in their order, and no longer last: later entries follow them
    assert in_order(NAMES, M["per_layer"])
    rebuild = manifest.Cell(M, "ec84-rebuild-under-write")
    assert not [m["name"] for m in rebuild.per_layer
                if m["layer"] == "client loop and GIL"]


@pytest.mark.parametrize("appended", [1, 2])
def test_an_entry_appended_after_the_fifteen_keeps_them_in_order(appended):
    later = [{"name": f"later_{i}.ops", "layer": "client write path"}
             for i in range(appended)]
    assert in_order(NAMES, M["per_layer"] + later)
    assert in_order(NAMES, later + M["per_layer"])
    # the order is held: the fifteen turned round are not found in it
    assert not in_order(NAMES[1:] + NAMES[:1], M["per_layer"] + later)
    assert not in_order(NAMES, [m for m in M["per_layer"] + later
                                if m["name"] != NAMES[7]])


@pytest.mark.asyncio
async def test_the_mixed_cell_rehearsed_gives_all_five(tmp_path):
    """``ec84-s3-mixed`` at its rehearsal's size under the one
    generator, four sessions on one loop: the worker's sum of every
    session's rows over the window gives each ``.ops`` reader something
    to read, and the loop is counted once (busy at most 100 %)."""
    cell = manifest.Cell(M, "ec84-s3-mixed")
    manifest.rehearsal_of(cell)
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start(health_interval=0.5)
    try:
        clients = [await cluster.client() for _ in range(4)]
        goal = {"id": EC_GOAL, "name": "ec32", "expr": "$ec(3,2)",
                "k": 3, "m": 2}
        dirs = []
        for entry in cell.config["directories"]:
            d = await clients[0].mkdir(1, entry["name"])
            await clients[0].setgoal(d.inode, EC_GOAL)
            dirs.append(generator.Directory(entry["name"], d.inode, goal))
        t = generator.Traffic(dict(cell.mix, sessions=4, objects=6), 36,
                              clients, dirs, None,
                              int(cell.config["chunk_bytes"]))
        step, = cell.mix["steps"]
        verb = t.verbs[step["verb"]]
        for fault in t.faults:
            await fault.apply(t)
        t.recording = True
        before = [(c.write_phases.snapshot(), c.read_phases.snapshot())
                  for c in clients]
        t_open = time.monotonic()

        async def session(s: int) -> None:
            for _ in range(20):
                await verb.do(t, s, t._state(s), step, False)

        await asyncio.gather(*(session(s) for s in range(4)))
        window_s = time.monotonic() - t_open
        # benchmark/worker.py's sum, as it stands there
        phases = {"write": {}, "read": {}}
        for c, (w0, r0) in zip(clients, before):
            for key, snap0, snap1 in (
                    ("write", w0, c.write_phases.snapshot()),
                    ("read", r0, c.read_phases.snapshot())):
                for name, val in snap1.items():
                    phases[key][name] = phases[key].get(name, 0) + val \
                        - snap0.get(name, 0)
        assert all(op.ok for op in t.ops) and len(t.ops) == 80
        ctx = {"window_s": window_s, "phases": phases, "ops": t.ops}
        got = {m["name"]: manifest.load_reader(m["name"])(ctx)
               for m in cell.per_layer
               if m["layer"] == "client loop and GIL"}
        assert sorted(got) == sorted(f"{fam}.ops" for fam in FAMILIES)
        assert all(v is not None for v in got.values()), got
        assert 0.0 < got["client_loop_busy_pct.ops"] <= 100.0
        assert 0.0 <= got["client_loop_offcpu_pct.ops"] <= 100.0
        assert 0.0 < got["client_loop_named_pct.ops"] <= 100.0
        assert got["client_loop_delay_ms.ops"] > 0.0
        assert got["wake_ms.ops"] > 0.0
    finally:
        await cluster.stop()
