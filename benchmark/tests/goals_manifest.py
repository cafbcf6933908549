"""A throw-away manifest for the comparison of xor and copy goals: a
cell ``goals-stream-write`` that runs ``stream-write``'s sessions on a
configuration made from ``ec84-13cs``'s, whose two directories take a
goal of two copies and ``$xor3``, sessions in turn. No cell of the
benchmark runs these goals yet; this is how the harness is shown to
compare them (``test_controls_goals.py``). Made from the committed
files at run time, so it cannot go stale beside them:

    python3 benchmark/tests/goals_manifest.py <dir>    # prints the path
    python3 benchmark/run.py --manifest <path> --workload goals-stream-write ...
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "goals-stream-write"
FROM = "ec84-13cs"
GOALS = [{"id": 2, "name": "copies2", "expr": "_ _", "copies": 2},
         {"id": 3, "name": "xor3", "expr": "$xor3", "xor": 3}]


def make(into: str) -> str:
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    src = next(c for c in manifest["configs"] if c["name"] == FROM)
    with open(os.path.join(os.path.dirname(BENCH), src["file"])) as f:
        config = json.load(f)
    # xor3 keeps 4 parts and two copies 2: 5 servers leave one spare
    config.update(goals=GOALS, chunkservers=5, directories=[
        {"name": g["name"], "goal": g["name"]} for g in GOALS])
    os.makedirs(into, exist_ok=True)
    path = os.path.join(os.path.abspath(into), "goals.json")
    with open(path, "w") as f:
        json.dump(config, f)
    # an absolute path: manifest.Cell joins it onto the repo's root
    manifest["configs"].append(dict(src, name="goals", file=path))
    manifest["workloads"].append({
        "name": CELL, "config": "goals", "traffic": "stream-write",
        "chips": 1, "why": "two copies and xor3 side by side"})
    for m in manifest["end_to_end"]:
        if m["name"] == "write_MBps":
            m["workloads"].append(CELL)
    out = os.path.join(into, "BENCHMARK.goals.json")
    with open(out, "w") as f:
        json.dump(manifest, f)
    return out


if __name__ == "__main__":
    print(make(sys.argv[1]))
