"""Each traffic mix is data the one generator reads, and its plan is a
pure function of the seed."""

import glob
import json
import os
from collections import Counter

import numpy as np
import pytest

import generator
import manifest
from reference.fsmodel import Model, make_pool

M = manifest.load_manifest()
MIXES = sorted({w["traffic"] for w in M["workloads"]})
SEEDS = (0, 7, 2147483659, 3000000001)


def mix(name):
    """The mix as a cell runs it: the configuration's values put in."""
    cell = next(w["name"] for w in M["workloads"] if w["traffic"] == name)
    return manifest.Cell(M, cell).mix


def test_every_traffic_file_is_some_cells():
    files = {os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(manifest.HERE, "traffic", "*.json"))}
    assert files == set(MIXES)


@pytest.mark.parametrize("name", MIXES)
def test_every_verb_and_fault_of_a_mix_is_a_file(name):
    m = mix(name)
    verbs = generator.verbs_of(m["steps"] + m["check"].get("make_live", []))
    assert verbs
    for v in verbs:
        mod = generator.load_verb(v)
        assert callable(mod.do)
        if getattr(mod, "METADATA", False):
            assert mod.CLASS
    assert {"retain_share", "retain_bytes", "disk_chunks",
            "readback_files"} <= set(m["check"])
    for action in m.get("faults", []):
        assert callable(generator.load_fault(action).apply)


def test_verbs_of_walks_nested_steps():
    steps = ["a", {"verb": "b", "x": 1},
             {"repeat": 2, "steps": ["c", {"each": "batch", "steps": ["d"]}]}]
    assert generator.verbs_of(steps) == ["a", "b", "c", "d"]


def test_barrier_releases_all_and_lets_a_session_leave():
    import asyncio

    async def go():
        bar = generator.Barrier(3)
        order = []

        async def party(i, rounds):
            for r in range(rounds):
                await bar.wait()
                order.append((r, i))
            await bar.leave()

        # the third party stops after one round; the others go on
        await asyncio.wait_for(asyncio.gather(
            party(0, 3), party(1, 3), party(2, 1)), 5.0)
        return order

    order = asyncio.run(go())
    assert sorted(order) == [(0, 0), (0, 1), (0, 2), (1, 0), (1, 1),
                             (2, 0), (2, 1)]
    assert {r for r, _i in order[:3]} == {0}


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_plan_is_a_pure_function_of_the_seed(name, seed):
    a, b = generator.plan(mix(name), seed), generator.plan(mix(name), seed)
    assert a == b
    assert len(a.sessions) == mix(name)["sessions"]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes_in_another_order(name):
    plans = [generator.plan(mix(name), s) for s in SEEDS]
    assert all(p.sizes == plans[0].sizes for p in plans)
    if len(plans[0].sizes) > 1:
        orders = {tuple(p.sessions[0].size_order) for p in plans}
        assert len(orders) == len(SEEDS)
        for p in plans:
            for sp in p.sessions:
                assert Counter(sp.size_order) == Counter(
                    range(len(p.sizes)))


def test_loguniform_set_spans_its_ends():
    sizes = generator.size_set(
        {"loguniform": {"min": 16384, "max": 1048576, "count": 48}})
    assert sizes[0] == 16384 and sizes[-1] == 1048576 and len(sizes) == 48
    assert sizes == sorted(sizes)
    ratios = [b / a for a, b in zip(sizes, sizes[1:])]
    assert max(ratios) / min(ratios) < 1.01


def test_warm_loops_cover_every_size():
    m = {"sessions": 5, "sizes": {"loguniform": {
        "min": 1024, "max": 65536, "count": 12}}}
    p = generator.plan(m, 1)
    met = {(s + i * m["sessions"]) % len(p.sizes)
           for s in range(m["sessions"]) for i in range(p.warm_loops)}
    assert met == set(range(len(p.sizes)))


def test_the_mixes_take_their_sizes_from_the_configuration():
    sw, sf = mix("stream-write"), mix("small-files")
    assert sw["sizes"] == {"fixed": 268435456}
    assert sw["transfer_bytes"] == 2097152 and sw["sessions"] == 4
    assert sf["sizes"] == {"fixed": 3901} and sf["sessions"] == 12
    assert sf["steps"][0]["repeat"] == 8


@pytest.mark.parametrize("seed", SEEDS)
def test_pool_is_a_pure_function_of_the_seed(seed):
    a, b = make_pool(seed, 4096 + 3), make_pool(seed, 4096 + 3)
    assert a.dtype == np.uint8 and len(a) == 4099 and np.array_equal(a, b)
    assert not np.array_equal(a, make_pool(seed + 1, 4099))


def test_model_follows_create_write_unlink():
    m = Model(make_pool(5, 1 << 16))
    f = m.create("a", 11)
    assert m.bytes_of(f).size == 0
    m.write("a", 64, 1000)
    assert np.array_equal(m.bytes_of(f), m.pool[64:1064])
    assert np.array_equal(m.bytes_of(f, 900, 500), m.pool[964:1064])
    with pytest.raises(KeyError):
        m.create("a", 12)
    m.write("a", 64, 3000)          # a sequential write extends it
    assert np.array_equal(m.bytes_of(f), m.pool[64:3064])
    assert m.create("b", 12, dir=1).dir == 1 and f.dir == 0
    m.unlink("a")
    assert [g.name for g in m.live()] == ["b"]


def test_percentile_and_rates_are_over_all_the_work():
    import worker

    ops = [generator.Op("write", 0.0, 1.0, 100_000_000, True),
           generator.Op("write", 0.5, 9.9, 100_000_000, True),
           generator.Op("write", 9.0, 10.4, 100_000_000, True),   # past the close
           generator.Op("read", 1.0, 2.0, 50_000_000, False)]     # failed
    e = worker.end_to_end(ops, 0.0, 10.0)
    assert e["write_MBps"] == pytest.approx(20.0)
    assert e["read_MBps"] == 0.0
    assert e["ops_per_s"] == pytest.approx(0.2)
    assert e["op_p95_ms"] == pytest.approx(9400.0)  # the failed op reads worst
    assert worker.percentile(list(range(1, 101)), 0.95) == 95
