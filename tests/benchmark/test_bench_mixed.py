"""The cell ``ec84-s3-mixed`` (PR 34) as ``BENCHMARK.json`` names it:
MinIO ``warp mixed`` on ``ec84-13cs-put``'s cluster. Its configuration,
its mix, its verb and its set-up action are files found by name; every
block of 20 operations holds the source's shares; each class makes the
gateway's ``Client`` calls in the handler's order; and each of the
cell's own readers gives the number known rows ask for, and None where
the program (the parent's) or the window has nothing for it to read.

A file of its own: the files that were here when the PR started are the
accepted benchmark's, and not this PR's to edit."""

import asyncio
import types

import numpy as np
import pytest

import generator
import manifest
from tap import TapCounts

M = manifest.load_manifest()
MIXED = "ec84-s3-mixed"
PUT_CELL = "ec84-put"
SHARES = {"get": 9, "stat": 6, "put": 3, "delete": 2}


def test_the_mixed_cell_and_its_metrics():
    entry = next(w for w in M["workloads"] if w["name"] == MIXED)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "ec84-13cs-mixed", "warp-mixed", 1)
    cell = manifest.Cell(M, MIXED)
    e2e = {m["name"] for m in cell.end_to_end}
    assert {"ops_per_s", "setup_s"} <= e2e <= {"ops_per_s", "op_p95_ms",
                                               "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= {
        "get_MBps.mixed", "put_MBps.mixed", "get_p95_ms.mixed",
        "put_p95_ms.mixed", "meta_p95_ms.mixed", "read_gather_chunks_pct",
        "read_cache_hit_pct", "read_waves_ms.get", "read_self_ms.get",
        "delete_srv_ms.mixed", "encode_kernel_roofline.ops",
        "device_idle_pct.ops", "master_rpc_ms_per_op", "encode_call_ms.small"}
    assert all(m["moves"] == "ops_per_s" for m in cell.per_layer)
    # no byte rate of the cell is held to a bound: both are per layer
    for rate in ("write_MBps", "read_MBps"):
        assert MIXED not in next(
            m for m in M["end_to_end"] if m["name"] == rate)["workloads"]


def test_the_configuration_is_warp_mixed_on_the_ec84_cluster():
    entry = next(c for c in M["configs"] if c["name"] == "ec84-13cs-mixed")
    cfg = manifest.Cell(M, MIXED).config
    put = manifest.Cell(M, PUT_CELL).config
    assert entry["reduced"] == ["objects"] == list(cfg["reduced"])
    assert "2,500" in cfg["reduced"]["objects"]
    assert cfg["source"].startswith("MinIO warp mixed (github.com/minio/warp")
    assert cfg["source"] != put["source"]
    detail = cfg["source_detail"]["traffic"]
    for flag in ("--obj.size 10MiB", "--objects 2500", "--concurrent 20",
                 "--get-distrib 45", "--stat-distrib 30", "--put-distrib 15",
                 "--delete-distrib 10"):
        assert flag in detail, flag
    # the cluster and the geometry are ec84-13cs-put's, unchanged
    for key in ("goals", "directories", "block_bytes", "chunk_bytes",
                "chunkservers", "encoder"):
        assert cfg[key] == put[key], key
    # the source's own numbers: size, concurrency and shares uncut
    assert (cfg["clients"], cfg["object_bytes"], cfg["objects"]) == (
        20, 10 * 2**20, 256)
    assert cfg["distribution"] == SHARES
    total = sum(SHARES.values())
    assert [100 * n // total for n in SHARES.values()] == [45, 30, 15, 10]
    assert SHARES["delete"] <= SHARES["put"], "the pool never drains"
    assert {k: cfg["guarantees"][k] for k in put["guarantees"]} == \
        put["guarantees"]
    assert set(cfg["guarantees"]) == set(put["guarantees"]) | {
        "get_whole", "delete"}
    assert len(cfg["assumed"]) >= 6


def test_the_mixed_mix_takes_its_numbers_from_the_configuration():
    cell = manifest.Cell(M, MIXED)
    mix = cell.mix
    assert mix["loop"] == "closed" and mix["sessions"] == 20
    assert mix["sizes"] == {"fixed": 10 * 2**20} and mix["objects"] == 256
    assert mix["steps"] == [{"verb": "warp_mixed_op", "distribution": SHARES}]
    assert mix["faults"] == ["prepare_objects"] and "preload" not in mix
    assert "events" not in mix and "redundancy_cap_s" not in mix
    chk = mix["check"]
    assert (chk["retain_share"], chk["retain_bytes"], chk["disk_chunks"],
            chk["readback_files"]) == (0.02, 384 * 2**20, 6, 2)
    # an object is one chunk that is not whole: 20 blocks a data part,
    # which the window's eight places cut into seven segments
    goal, = cell.config["goals"]
    blocks = mix["sizes"]["fixed"] // cell.config["block_bytes"] // goal["k"]
    assert blocks == 20 and -(-blocks // -(-blocks // 8)) == 7
    assert callable(generator.load_fault("prepare_objects").apply)
    manifest.rehearsal_of(cell)
    assert (cell.mix["sessions"], cell.mix["objects"]) == (4, 12)
    assert cell.mix["sizes"] == {"fixed": 10 * 2**20}, "the shape is kept"


@pytest.mark.parametrize("seed", (0, 7, 2147483659, 3000000001))
def test_every_block_of_twenty_holds_the_sources_shares(seed):
    """9 GET, 6 STAT, 3 PUT and 2 DELETE in every block of 20, whatever
    the seed and the session; only the order differs, block by block."""
    verb = generator.load_verb("warp_mixed_op")
    plan = generator.plan(manifest.Cell(M, MIXED).mix, seed)
    orders = set()
    for sp in plan.sessions[:4]:
        st = {"rng": np.random.default_rng(sp.seed)}
        for _ in range(5):
            block = [verb.next_class(st, SHARES) for _ in range(20)]
            assert {c: block.count(c) for c in SHARES} == SHARES
            orders.add(tuple(block))
        assert not st["warp_mixed.block"], "a fresh shuffle a block"
    assert len(orders) == 20
    a = verb.block_of(np.random.default_rng(seed), SHARES)
    assert a == verb.block_of(np.random.default_rng(seed), SHARES)


class Gateway:
    """A client that answers nothing and keeps the calls it was sent."""

    def __init__(self):
        self.calls = []

    def __getattr__(self, name):
        async def call(*args):
            self.calls.append(name)
            return {"lookup": types.SimpleNamespace(inode=7, length=5),
                    "create": types.SimpleNamespace(inode=7),
                    "read_file": b"12345"}.get(name)
        return call


def test_each_class_makes_the_gateways_calls_in_its_order():
    """GET, HEAD, PUT and DELETE as ``s3/server.py``'s handlers make
    them, one timed operation each, classed for the harness; a DELETE
    takes its key out of the pool before it unlinks."""
    cell = manifest.Cell(M, MIXED)
    mix = dict(cell.mix, sessions=1, sizes={"fixed": 5})
    c = Gateway()
    dirs = [generator.Directory("staging", 2, {}),
            generator.Directory("bucket", 3, {})]
    t = generator.Traffic(mix, 5, [c], dirs, None, 1 << 26)
    verb, st = t.verbs["warp_mixed_op"], t._state(0)
    t.recording = True
    seen_by_unlink = []

    async def go():
        await verb.put(t, 0, st, False)
        key, = [f.name for f in verb.pool(t).live]
        await verb.get(t, 0, st, False)
        await verb.stat(t, 0, st, False)
        orig = c.unlink

        async def unlink(*args):
            seen_by_unlink.append(list(verb.pool(t).live))
            return await orig(*args)

        c.unlink = unlink
        await verb.delete(t, 0, st, False)
        return key

    key = asyncio.run(go())
    assert c.calls == [
        "create", "settrashtime", "write_file", "set_xattr", "rename",
        "lookup", "get_xattr", "read_file", "lookup", "get_xattr", "unlink"]
    assert [(o.cls, o.nbytes, o.metadata, o.ok) for o in t.ops] == [
        ("write", 5, False, True), ("read", 5, False, True),
        ("stat", 0, True, True), ("delete", 0, True, True)]
    assert seen_by_unlink == [[]] and list(t.unlinked) == [key]
    assert not t.model.live() and not verb.pool(t).held
    assert t.getattr_seen == [(key, 5, 5)] * 2
    assert [r.data for r in t.retained] == [b"12345"]

# -- the cell's readers ---------------------------------------------------
#
# One window of 10 s that mixes the classes: ops as the harness records
# them, the read side's rows and counts (40 GETs, each one chunk's range
# on a read plan; HEAD's two calls count themselves and close no rep),
# the write side's (15 PUTs; 10 unlinks with the master's handler time).

def mixed_op(cls, start, ms, nbytes=0, ok=True):
    return types.SimpleNamespace(cls=cls, start=start, end=start + ms / 1e3,
                                 nbytes=nbytes, ok=ok,
                                 metadata=cls in ("stat", "delete"))


OBJ = 10 * 2**20
MIXED_OPS = (
    [mixed_op("read", 0.1 * i, 100.0 + i, OBJ) for i in range(40)]
    + [mixed_op("write", 0.5 * i, 400.0 + 10 * i, OBJ) for i in range(15)]
    + [mixed_op("stat", 0.3 * i, 1.0 + i) for i in range(30)]
    + [mixed_op("delete", 0.9 * i, 2.0 + 4 * i) for i in range(10)]
    # under way at the close: in the tails, not in the rates
    + [mixed_op("read", 9.95, 120.0, OBJ), mixed_op("write", 9.9, 700.0, OBJ)]
    + [mixed_op("read", 3.0, 5000.0, OBJ, ok=False)])
MIXED_READ = {
    "reps": 41, "wall_ms": 4510.0, "self_ms": 205.0, "waves_ms": 1640.0,
    "gather_ms": 900.0, "lookup_ms": 80.0, "lookup_srv_ms": 7.0,
    "gather_chunks": 0, "planned_chunks": 41, "cache_hit_blocks": 0,
    "cache_miss_blocks": 0, "cache_bypass_blocks": 41 * 160,
    "read_bytes": 41 * OBJ, "lookups": 71, "get_xattrs": 71,
}
MIXED_WRITE = {
    "reps": 16, "wall_ms": 8000.0, "self_ms": 80.0, "encode_ms": 3000.0,
    "unlink_ms": 12.0, "unlink_srv_ms": 4.5, "unlinks": 10,
}
MIXED_EXPECT = {
    "get_MBps.mixed": (40 * OBJ / 1e6 / 10.0, "host_clock", "MB/s"),
    "put_MBps.mixed": (15 * OBJ / 1e6 / 10.0, "host_clock", "MB/s"),
    "get_p95_ms.mixed": (137.0, "host_clock", "ms"),    # 39th of 41
    "put_p95_ms.mixed": (700.0, "host_clock", "ms"),    # 16th of 16
    "meta_p95_ms.mixed": (30.0, "host_clock", "ms"),    # 38th of 40
    "read_gather_chunks_pct": (0.0, "program_counter", "%"),
    "read_cache_hit_pct": (0.0, "program_counter", "%"),
    "read_waves_ms.get": (40.0, "program_span", "ms"),
    "read_self_ms.get": (5.0, "program_span", "ms"),
    "delete_srv_ms.mixed": (0.45, "program_span", "ms"),
}
# the parent's program: phase rows and reps, none of this PR's counts
# or call rows
MIXED_PARENT = (
    {k: v for k, v in MIXED_WRITE.items() if not k.startswith("unlink")},
    {k: v for k, v in MIXED_READ.items()
     if k == "reps" or (k.endswith("_ms") and not k.startswith("lookup"))})


def ctx_mixed(write=MIXED_WRITE, read=MIXED_READ, ops=MIXED_OPS):
    return {"window_s": 10.0, "phases": {"write": write, "read": read},
            "ops": list(ops), "tap": TapCounts((), ()), "trace": None,
            "config": {}, "peaks": None, "t_open": 0.0, "t_close": 10.0,
            "master": {}, "rebuild": None}


@pytest.mark.parametrize("name", sorted(MIXED_EXPECT))
def test_mixed_reader_on_known_rows(name):
    assert manifest.load_reader(name)(ctx_mixed()) == \
        pytest.approx(MIXED_EXPECT[name][0])


def test_mixed_shares_where_both_paths_and_the_cache_served():
    read = dict(MIXED_READ, gather_chunks=30, planned_chunks=10,
                cache_hit_blocks=48, cache_miss_blocks=16,
                cache_bypass_blocks=0)
    assert manifest.load_reader("read_gather_chunks_pct")(
        ctx_mixed(read=read)) == 75.0
    assert manifest.load_reader("read_cache_hit_pct")(
        ctx_mixed(read=read)) == 75.0


@pytest.mark.parametrize("name", sorted(
    n for n, (_v, source, _u) in MIXED_EXPECT.items()
    if source != "host_clock"))
def test_mixed_reader_finds_nothing_on_the_parents_program(name):
    write, read = MIXED_PARENT
    got = manifest.load_reader(name)(ctx_mixed(write, read))
    if name in ("read_waves_ms.get", "read_self_ms.get"):
        # rows the parent charges too: the reader reads them there
        assert got == pytest.approx(MIXED_EXPECT[name][0])
    else:
        assert got is None


@pytest.mark.parametrize("name", sorted(MIXED_EXPECT))
def test_mixed_reader_finds_nothing_without_its_class(name):
    """No operation of the class, no rep on the side, or an empty base:
    None, not 0."""
    only_stats = [o for o in MIXED_OPS if o.cls == "stat"]
    empty = ctx_mixed(dict(MIXED_WRITE, reps=0, unlinks=0),
                      dict(MIXED_READ, reps=0, planned_chunks=0,
                           cache_bypass_blocks=0), only_stats)
    got = manifest.load_reader(name)(empty)
    if name == "meta_p95_ms.mixed":
        assert got == pytest.approx(29.0)   # 29th of 30 STATs
    else:
        assert got is None


@pytest.mark.parametrize("name", sorted(MIXED_EXPECT) + [
    "encode_kernel_roofline.ops"])
def test_entry_of_the_mixed_metric(name):
    _want, source, unit = MIXED_EXPECT.get(
        name, (None, "device_trace", "%"))
    entry = next(m for m in M["per_layer"] if m["name"] == name)
    assert (entry["source"], entry["unit"], entry["moves"]) == (
        source, unit, "ops_per_s")
    assert "ec84-s3-mixed" in entry["workloads"]
    assert entry["better"] == ("lower" if unit == "ms" else "higher")


def test_the_ops_roofline_counts_the_calls_shapes_as_the_write_cells_do():
    """Two lines over ``_lib.encode_roofline``: the same bytes and
    operations from the calls' shapes over the device time under
    ``bench.encode``, here a PUT's seven segments."""
    calls = tuple([(8, 4, 8, 196608, 0.01)] * 6 + [(8, 4, 8, 131072, 0.01)])
    ctx = dict(ctx_mixed(), tap=TapCounts(calls, ()),
               trace={"span_device_s": {"bench.encode": 1e-4}},
               peaks=manifest.peaks_for("TPU v5 lite"))
    got = manifest.load_reader("encode_kernel_roofline.ops")(ctx)
    assert got == manifest.load_reader("encode_kernel_roofline")(ctx)
    nbytes = 12 * (6 * 196608 + 131072)
    assert got == pytest.approx(100.0 * nbytes / 819e9 / 1e-4)
    assert manifest.load_reader("encode_kernel_roofline.ops")(
        dict(ctx, peaks=None)) is None
