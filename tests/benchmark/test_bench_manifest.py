"""BENCHMARK.json is well-formed and everything it names resolves to a
file by name."""

import json
import os
import re

import pytest

import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

M = manifest.load_manifest()
CELLS = [w["name"] for w in M["workloads"]]
METRICS = M["end_to_end"] + M["per_layer"]


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert os.path.getsize(os.path.join(manifest.REPO, "BENCHMARK.json")) \
        <= 64 * 1024
    budget = (2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 180 + 1200
    assert budget <= 43200


def test_paths_and_command():
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
        assert os.path.isdir(os.path.join(manifest.REPO, p))
    assert len(M["command"]) <= 32
    for word in M["command"]:
        assert not word.startswith("/") and ".." not in word
    prog = M["command"][1]
    assert any(prog.startswith(p + "/") for p in M["paths"])


def test_files_under_paths_have_plain_names():
    for p in M["paths"]:
        for root, dirs, files in os.walk(os.path.join(manifest.REPO, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for fn in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", fn), fn


@pytest.mark.parametrize("cfg", M["configs"], ids=lambda c: c["name"])
def test_config_entry(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"])
    assert 1 <= len(cfg["source"]) <= 200 and 1 <= len(cfg["why"]) <= 200
    assert any(cfg["file"].startswith(p + "/") for p in M["paths"])
    with open(os.path.join(manifest.REPO, cfg["file"])) as f:
        body = json.load(f)
    assert body["name"] == cfg["name"]
    assert len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        assert NAME.match(key)
        assert key in body["reduced"], "each cut is explained in the file"
        assert not key.endswith(("_dim", "_rank"))
    for must in ("goals", "directories", "encoder", "block_bytes",
                 "chunk_bytes", "chunkservers", "guarantees", "assumed",
                 "source_detail"):
        assert must in body
    assert body["source"] == cfg["source"]
    assert any(w["config"] == cfg["name"] for w in M["workloads"])
    goals = {g["name"] for g in body["goals"]}
    assert all(d["goal"] in goals for d in body["directories"])
    assert callable(manifest.load_module(
        "encoders", body["encoder"] + ".py").make)


def at_keys(value):
    """Every "@key" a mix refers to."""
    if isinstance(value, str) and value.startswith("@"):
        return {value[1:]}
    if isinstance(value, dict):
        value = list(value.values())
    if isinstance(value, list):
        return set().union(*map(at_keys, value)) if value else set()
    return set()


@pytest.mark.parametrize("cfg", M["configs"], ids=lambda c: c["name"])
def test_every_number_of_a_config_is_read(cfg):
    """No decorative keys: a number in a configuration's file is one
    the harness reads itself or one a mix of its cells refers to, so
    what ``reduced`` lists is what is timed."""
    with open(os.path.join(manifest.REPO, cfg["file"])) as f:
        body = json.load(f)
    used = {"block_bytes", "chunk_bytes", "chunkservers"}
    for w in M["workloads"]:
        if w["config"] == cfg["name"]:
            used |= at_keys(manifest.load_json(
                "traffic", w["traffic"] + ".json"))
    numbers = {k for k, v in body.items() if isinstance(v, (int, float))}
    assert numbers <= used, numbers - used
    assert set(cfg["reduced"]) <= used


def test_resolve_puts_the_configurations_values_in():
    mix = {"sessions": "@n", "steps": [{"repeat": "@r", "steps": ["a"]}],
           "sizes": {"fixed": "@b"}, "plain": "x", "n": 3}
    got = manifest.resolve(mix, {"n": 4, "r": 2, "b": 9})
    assert got == {"sessions": 4, "steps": [{"repeat": 2, "steps": ["a"]}],
                   "sizes": {"fixed": 9}, "plain": "x", "n": 3}
    with pytest.raises(KeyError):
        manifest.resolve({"a": "@missing"}, {})


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    w = next(x for x in M["workloads"] if x["name"] == name)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    cell = manifest.Cell(M, name)
    assert cell.mix["steps"] and cell.mix["sessions"] >= 1
    assert not at_keys(cell.mix), "every reference resolved"
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"


def test_cells_are_distinct():
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(
        1, len(CELLS) // 2)


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for cell in m.get("workloads", []):
        assert cell in CELLS
    if m in M["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        moved = next(e for e in M["end_to_end"] if e["name"] == m["moves"])
        for cell in m.get("workloads", CELLS):
            assert cell in moved.get("workloads", CELLS), \
                "a per-layer metric's cells report the metric it moves"
        assert callable(manifest.load_reader(m["name"]))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_metric_names_are_distinct():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    assert "setup_s" in names


def test_layers_are_named_alike():
    layers = {m["layer"] for m in M["per_layer"]}
    with open(os.path.join(manifest.REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert "\n" not in layer and layer in perf


def test_peaks_name_the_v5e():
    peaks = manifest.peaks_for("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["int8_ops_per_s"] == 393e12
    with pytest.raises(SystemExit):
        manifest.peaks_for("TPU v9 imaginary")
