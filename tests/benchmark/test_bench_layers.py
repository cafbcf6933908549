"""The per-layer readers of the program's span tree, each on a
hand-made context: the number it gives from known phase rows, and None
where the program has no such phase (the parent of the PR that brought
them), where the side closed no op, or where the base is empty."""

import pytest

import manifest

M = manifest.load_manifest()

# one window of 10 s over 4 sessions; rows as ctx["phases"] carries them
WRITE = {
    "reps": 100, "wall_ms": 9000.0, "self_ms": 450.0,
    "getattr_ms": 300.0, "grant_ms": 2000.0, "grant_srv_ms": 1500.0,
    "commit_ms": 700.0, "encode_ms": 1750.0, "split_ms": 1200.0,
    "boundary_ms": 630.0, "dev_stage_ms": 30.0, "dev_put_ms": 60.0,
    "dev_run_ms": 40.0, "dev_fetch_ms": 500.0, "send_ms": 4000.0,
    "ack_ms": 200.0, "part_ms": 40000.0, "hop_ms": 3000.0,
    "part_dial_ms": 1000.0, "part_init_ms": 6000.0, "part_data_ms": 20000.0,
}
READ = {
    "reps": 400, "wall_ms": 8000.0, "self_ms": 240.0, "locate_ms": 500.0,
    "dev_stage_ms": 100.0, "dev_put_ms": 100.0, "dev_run_ms": 50.0,
    "dev_fetch_ms": 750.0,
}
EXPECT = {
    "write_master_busy_pct": 100.0 * (300 + 2000 + 700) / 1e3 / 10.0,
    "write_stage_busy_pct": 100.0 * (1750 - 630) / 1e3 / 10.0,
    "encode_device_wait_pct": 100.0 * 500 / 630,
    "write_part_queue_pct": 100.0 * (3000 + 1000 + 6000) / 40000,
    "write_unattributed_pct.write": 5.0,
    "write_unattributed_pct.ops": 5.0,
    "write_grant_ms.small": 20.0,
    "write_grant_srv_ms.small": 15.0,
    "write_wire_ms.small": 42.0,
    "recover_device_wait_pct": 75.0,
    "read_locate_busy_pct": 5.0,
    "read_unattributed_pct.read": 3.0,
}
# what the parent's program charged: its five write and six read phases
PARENT = {
    "write": {"reps": 100, "wall_ms": 9000.0, "encode_ms": 1750.0,
              "stage_ms": 0.0, "send_ms": 4000.0, "ack_ms": 200.0,
              "commit_ms": 0.0},
    "read": {"reps": 400, "wall_ms": 8000.0, "locate_ms": 500.0,
             "dial_ms": 1.0, "wait_ms": 2.0, "net_ms": 9000.0,
             "decode_ms": 900.0, "gather_ms": 100.0},
}
# the parent has these phases under the same names, so it reads them too
PARENT_READS = {"write_wire_ms.small": 42.0, "read_locate_busy_pct": 5.0}


def ctx_of(write, read):
    return {"window_s": 10.0, "phases": {"write": write, "read": read},
            "ops": [], "tap": None, "trace": None, "config": {},
            "peaks": None}


def test_every_new_reader_has_a_case():
    """At least these: any PR may add a reader of the program's spans
    with a case of its own (``test_bench_layers_rmw.py`` holds
    those of PRs 26 and 30)."""
    spans = {m["name"] for m in M["per_layer"]
             if m["source"] == "program_span"}
    assert spans >= set(EXPECT)


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_on_known_rows(name):
    read = manifest.load_reader(name)
    assert read(ctx_of(WRITE, READ)) == pytest.approx(EXPECT[name])


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_finds_nothing_on_the_parents_program(name):
    """The parent charges no such phase: the reader returns None and
    does not raise, and the result line leaves the metric out."""
    got = manifest.load_reader(name)(ctx_of(PARENT["write"], PARENT["read"]))
    if name in PARENT_READS:
        assert got == pytest.approx(PARENT_READS[name])
    else:
        assert got is None


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_finds_nothing_where_no_op_closed(name):
    idle = ctx_of(dict(WRITE, reps=0), dict(READ, reps=0))
    assert manifest.load_reader(name)(idle) is None
    assert manifest.load_reader(name)(ctx_of({}, {})) is None


def test_shares_with_an_empty_base_are_left_out():
    no_parts = dict(WRITE, part_ms=0.0)
    assert manifest.load_reader("write_part_queue_pct")(
        ctx_of(no_parts, READ)) is None
    no_calls = dict(READ, dev_stage_ms=0.0, dev_put_ms=0.0, dev_run_ms=0.0,
                    dev_fetch_ms=0.0)
    assert manifest.load_reader("recover_device_wait_pct")(
        ctx_of(WRITE, no_calls)) is None


def test_cells_of_the_new_metrics():
    cells = {m["name"]: m["workloads"] for m in M["per_layer"]}
    for name in EXPECT:
        want = ("ec32-small-files" if name.endswith((".small", ".ops"))
                else "ec84-degraded-read"
                if name.startswith(("read_", "recover_"))
                else "ec84-stream-write")
        assert want in cells[name], name  # and may read in more
