"""A throw-away manifest for the test that a daemon which stands still
fails no sound run: the named cell as ``BENCHMARK.json`` and its mix
name it, with the event ``stall_seeded`` a fifth into the window. Made
from the committed files at run time, so it cannot go stale beside
them:

    python3 benchmark/tests/stalled_manifest.py <dir> <cell>   # prints the path
    python3 benchmark/run.py --manifest <path> --workload <cell> ...
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def make(into: str, cell: str) -> str:
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(w for w in manifest["workloads"] if w["name"] == cell)
    with open(os.path.join(BENCH, "traffic", entry["traffic"] + ".json")) as f:
        mix = json.load(f)
    mix["events"] = mix.get("events", []) + [
        {"at_share": 0.2, "fault": "stall_seeded"}]
    os.makedirs(into, exist_ok=True)
    stalled = os.path.join(os.path.abspath(into), entry["traffic"] + "-stalled")
    with open(stalled + ".json", "w") as f:
        json.dump(mix, f)
    # an absolute name: manifest.Cell joins it onto benchmark/traffic/,
    # and a join with an absolute path is that path
    entry["traffic"] = stalled
    path = os.path.join(into, "BENCHMARK.stalled.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return path


if __name__ == "__main__":
    print(make(sys.argv[1], sys.argv[2]))
