"""A throw-away manifest for the control of the wait for full
redundancy: the cell ``ec84-rebuild-under-write`` as ``BENCHMARK.json``
and its mix name it, with ``hold_rebuilds`` applied in set-up, so that
one rebuild starts and waits for a byte a second of budget: redundancy
never comes back, and the run has to end at the mix's cap, non-zero and
with no result line. Made from the committed files at run time, so it
cannot go stale beside them:

    python3 benchmark/tests/held_manifest.py <dir>    # prints the path
    python3 benchmark/run.py --manifest <path> --workload ec84-rebuild-under-write ...
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "ec84-rebuild-under-write"


def make(into: str, cap_s: float | None = None) -> str:
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    with open(os.path.join(BENCH, "traffic", entry["traffic"] + ".json")) as f:
        mix = json.load(f)
    mix["faults"] = ["hold_rebuilds"]
    if cap_s is not None:
        mix["redundancy_cap_s"] = cap_s
        mix["rehearsal"]["redundancy_cap_s"] = cap_s
    os.makedirs(into, exist_ok=True)
    held = os.path.join(os.path.abspath(into), "stream-write-kill-held")
    with open(held + ".json", "w") as f:
        json.dump(mix, f)
    # an absolute name: manifest.Cell joins it onto benchmark/traffic/,
    # and a join with an absolute path is that path
    entry["traffic"] = held
    path = os.path.join(into, "BENCHMARK.held.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return path


if __name__ == "__main__":
    print(make(sys.argv[1], float(sys.argv[2]) if len(sys.argv) > 2 else None))
