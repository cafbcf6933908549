"""The readers of the windowed write's trips to a worker thread:
``write_window_trips_per_seg.ops`` (the PUTs of ``ec84-s3-mixed``) and
``write_window_trips_per_seg.write`` (``ec84-put``), each
``window_trips / window_segments`` on the write side of
``ctx["phases"]``. On a planted ``ctx`` each gives the number the counts
ask for, and None on a parent's rows without ``window_trips`` or where
no segment went through the window; the cells each is meant for report
the end-to-end metric it moves; and the mixed cell at its rehearsal's
size, in process, gives the ``.ops`` reader something to read.

A file of its own: the files that were here when the PR started are the
accepted benchmark's, and not this PR's to edit."""

import asyncio
import time

import pytest

import generator
import manifest

from tests.test_cluster import Cluster, WIDE_EC_GOAL

M = manifest.load_manifest()
# reader -> (the cell it reads, the end-to-end metric it moves)
READERS = {
    "write_window_trips_per_seg.ops": ("ec84-s3-mixed", "ops_per_s"),
    "write_window_trips_per_seg.write": ("ec84-put", "write_MBps"),
}


def planted(**write) -> dict:
    rows = {"reps": 150, "wall_ms": 9000.0, "self_ms": 90.0,
            "window_segments": 1050, "window_trips": 1092}
    rows.update(write)
    return {"window_s": 20.0, "phases": {"write": rows, "read": {"reps": 0}},
            "ops": [], "trace": None}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_gives_trips_a_segment(name):
    read = manifest.load_reader(name)
    assert read(planted()) == pytest.approx(1092 / 1050)
    assert read(planted(window_trips=1050)) == pytest.approx(1.0)


@pytest.mark.parametrize("case", ["parent", "no_segment", "no_op"])
@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_with_nothing_to_read_gives_none(name, case):
    ctx = planted()
    rows = ctx["phases"]["write"]
    if case == "parent":
        del rows["window_trips"]
    elif case == "no_segment":
        rows.update(window_segments=0, window_trips=0)
    else:
        rows["reps"] = 0
    assert manifest.load_reader(name)(ctx) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_cell_a_reader_is_meant_for_reports_what_it_moves(name):
    cell_name, moves = READERS[name]
    cell = manifest.Cell(M, cell_name)
    assert moves in {m["name"] for m in cell.end_to_end}
    entries = [m for m in M["per_layer"] if m["name"] == name]
    for entry in entries:
        assert entry == {"name": name, "unit": "trips/seg", "better": "lower",
                         "source": "program_counter",
                         "layer": "client write path", "moves": moves,
                         "workloads": [cell_name]}


@pytest.mark.asyncio
async def test_the_mixed_cell_rehearsed_reads_a_trip_a_segment(tmp_path):
    """``ec84-s3-mixed`` at its rehearsal's size under the one
    generator, four sessions on one loop at $ec(8,4): every PUT's chunk
    goes through the window in seven segments, and the reader reads a
    trip a segment or a little more (a loop reap where a gate was
    shut)."""
    cell = manifest.Cell(M, "ec84-s3-mixed")
    manifest.rehearsal_of(cell)
    cluster = Cluster(tmp_path, n_cs=13)
    await cluster.start(health_interval=0.5)
    try:
        clients = [await cluster.client() for _ in range(4)]
        goal = cell.config["goals"][0]
        dirs = []
        for entry in cell.config["directories"]:
            d = await clients[0].mkdir(1, entry["name"])
            await clients[0].setgoal(d.inode, WIDE_EC_GOAL)
            dirs.append(generator.Directory(entry["name"], d.inode, goal))
        t = generator.Traffic(dict(cell.mix, sessions=4, objects=6), 38,
                              clients, dirs, None,
                              int(cell.config["chunk_bytes"]))
        step, = cell.mix["steps"]
        verb = t.verbs[step["verb"]]
        for fault in t.faults:
            await fault.apply(t)
        t.recording = True
        before = [c.write_phases.snapshot() for c in clients]
        t_open = time.monotonic()

        async def session(s: int) -> None:
            for _ in range(20):
                await verb.do(t, s, t._state(s), step, False)

        await asyncio.gather(*(session(s) for s in range(4)))
        write = {}
        for c, w0 in zip(clients, before):
            for name, val in c.write_phases.snapshot().items():
                write[name] = write.get(name, 0) + val - w0.get(name, 0)
        assert all(op.ok for op in t.ops) and len(t.ops) == 80
        puts = sum(op.cls == "write" for op in t.ops)
        assert puts and write["window_chunks"] == puts
        assert write["window_segments"] == 7 * puts
        ctx = {"window_s": time.monotonic() - t_open, "ops": t.ops,
               "phases": {"write": write, "read": {}}}
        got = manifest.load_reader("write_window_trips_per_seg.ops")(ctx)
        assert got == write["window_trips"] / write["window_segments"]
        assert 1.0 <= got < 2.0
    finally:
        await cluster.stop()
