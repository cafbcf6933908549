"""Finds everything a cell names, by name: ``BENCHMARK.json`` at the
root of the checkout names a cell's configuration, traffic mix and
per-layer metrics, and each is a file of its own under ``benchmark/``.
No table of names lives in code, so a later PR adds a deployment, a mix
or a metric by adding files and entries and edits nothing here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def load_manifest(path: str | None = None) -> dict:
    with open(path or os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def resolve(value, config: dict):
    """A mix with every "@key" replaced by the configuration's key."""
    if isinstance(value, str) and value.startswith("@"):
        return config[value[1:]]
    if isinstance(value, dict):
        return {k: resolve(v, config) for k, v in value.items()}
    if isinstance(value, list):
        return [resolve(v, config) for v in value]
    return value


class Cell:
    """One entry of ``workloads`` with the files it names: ``config``
    and ``traffic`` as the files hold them, ``mix`` the traffic with
    the configuration's values put in for its "@key" references."""

    def __init__(self, manifest: dict, name: str):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                             f"(have: {', '.join(sorted(cells))})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = next(c for c in manifest["configs"]
                   if c["name"] == self.entry["config"])
        with open(os.path.join(REPO, cfg["file"])) as f:
            self.config = json.load(f)
        self.traffic = load_json("traffic", self.entry["traffic"] + ".json")
        self.mix = resolve(self.traffic, self.config)
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in manifest["per_layer"]
                          if name in m.get("workloads", [name])]


def rehearsal_of(cell: Cell) -> None:
    """Cut the cell to toy size for the CPU rehearsal: each file's own
    ``rehearsal`` overrides, then the references resolved again."""
    cell.config = dict(cell.config, **cell.config.get("rehearsal", {}))
    cell.traffic = dict(cell.traffic, **cell.traffic.get("rehearsal", {}))
    cell.mix = resolve(cell.traffic, cell.config)


def load_module(*parts: str):
    """A module of the benchmark by its path under ``benchmark/`` (the
    directories ``trace`` and ``layers`` are not packages: ``trace``
    would shadow the standard library's, and a metric's name may hold a
    dot)."""
    path = os.path.join(HERE, *parts)
    if os.path.dirname(path) not in sys.path:
        sys.path.insert(0, os.path.dirname(path))  # a reader's own _lib
    name = "bench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric_name: str):
    """``benchmark/layers/<metric>.py`` -> its ``read(ctx)``."""
    return load_module("layers", metric_name + ".py").read


def peaks_for(device_kind: str) -> dict:
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         "benchmark/peaks.json: a device without "
                         "published peaks is an error, not a default")
    return table[device_kind]
