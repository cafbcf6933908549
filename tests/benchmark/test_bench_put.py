"""The cell ``ec84-put`` as ``BENCHMARK.json`` names it: its
configuration, its mix and its verb are files found by name, the mix
takes its numbers from the configuration, and its plan is a pure
function of the seed."""

import pytest

import generator
import manifest

M = manifest.load_manifest()
CELL = "ec84-put"


def test_the_cell_and_its_metrics():
    entry = next(w for w in M["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "ec84-13cs-put", "put-whole", 1)
    cell = manifest.Cell(M, CELL)
    assert {m["name"] for m in cell.end_to_end} == {"write_MBps", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= {
        "write_encode_busy_pct", "write_send_busy_pct",
        "encode_boundary_MBps", "encode_kernel_roofline",
        "device_idle_pct.write"}
    assert not any(w["chips"] == 4 for w in M["workloads"])


def test_the_configuration_is_warp_put_on_the_ec84_cluster():
    entry = next(c for c in M["configs"] if c["name"] == "ec84-13cs-put")
    cfg = manifest.Cell(M, CELL).config
    assert entry["reduced"] == [] and cfg["reduced"] == {}
    assert cfg["source"].startswith("MinIO warp put (github.com/minio/warp")
    goal, = cfg["goals"]
    assert (goal["id"], goal["expr"], goal["k"], goal["m"]) == (
        12, "$ec(8,4)", 8, 4)
    # two directories of the one goal: objects are staged, then renamed
    assert [(d["name"], d["goal"]) for d in cfg["directories"]] == [
        ("staging", "ec84"), ("bucket", "ec84")]
    assert (cfg["chunkservers"], cfg["block_bytes"], cfg["chunk_bytes"]) == (
        13, 65536, 64 * 2**20)
    base = manifest.Cell(M, "ec84-stream-write").config["guarantees"]
    assert {k: cfg["guarantees"][k] for k in base} == base
    assert set(cfg["guarantees"]) == set(base) | {"publish"}


def test_the_mix_takes_its_numbers_from_the_configuration():
    cell = manifest.Cell(M, CELL)
    mix = cell.mix
    assert mix["loop"] == "closed" and mix["sessions"] == 8
    assert mix["sizes"] == {"fixed": 2 * cell.config["chunk_bytes"]}
    assert "transfer_bytes" not in mix and "preload" not in mix
    assert mix["steps"] == [{"verb": "put_whole", "warm_chunks": 1}]
    assert (mix["check"]["disk_chunks"], mix["check"]["readback_files"]) \
        == (6, 2)
    manifest.rehearsal_of(cell)
    assert cell.mix["sessions"] == 2
    # the toy object still takes the windowed whole-chunk write
    assert cell.mix["sizes"]["fixed"] == 9 * 2**20 >= 8 * 2**20


@pytest.mark.parametrize("seed", (0, 7, 2147483659, 3000000001))
def test_plan_is_pure_in_the_seed(seed):
    mix = manifest.Cell(M, CELL).mix
    a, b = generator.plan(mix, seed), generator.plan(mix, seed)
    assert a == b and len(a.sessions) == 8
    assert a.sizes == [128 * 2**20] and a.warm_loops == 1
    assert a.pool_bytes == (128 + 64) * 2**20
    assert a.sessions != generator.plan(mix, seed + 1).sessions


def test_the_verb_is_a_timed_write():
    verb = generator.load_verb("put_whole")
    assert verb.CLASS == "write" and callable(verb.do)
    assert not getattr(verb, "METADATA", False)
