"""The one line a run prints last on standard output, with exactly the
keys the benchmark's contract fixes; ``checks``, each number compared
beside its limit, comes last."""

from __future__ import annotations

import json

KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACE_DEVICE_KEYS = ("busy_s", "window_s")


def build(correct: bool, attempted: int, failed: int, metrics: dict,
          device: dict, checks: dict, breakdown: dict | None = None) -> dict:
    for k in DEVICE_KEYS:
        if k not in device:
            raise ValueError(f"device lacks {k}")
    for name, m in metrics.items():
        if set(m) != {"value", "unit"} or m["value"] is None:
            raise ValueError(f"metric {name} is not a value with a unit")
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = {
            "device_ops": [list(x) for x in breakdown["device_ops"][:10]],
            "idle_gaps": [list(x) for x in breakdown["idle_gaps"][:10]],
        }
    line["checks"] = checks
    return line


def dumps(line: dict) -> str:
    return json.dumps(line)
