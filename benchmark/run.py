#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A launcher that never imports jax: it builds the native library where
the checkout lacks it, then runs one worker process (worker.py), which
owns the chip, starts the cell's deployment as real processes and
drives it through ``lizardfs_tpu.client.client.Client``. The worker's
last standard-output line is the result; a run that cannot be a chip
result (no TPU, wrong encoder, a compile inside the window) exits
non-zero and prints none.

Arguments of the benchmark's own, never passed by the driver:
  --rehearse-cpu   the same run at toy size on the CPU platform, the
                   device encoder in interpret mode; prints
                   "REHEARSAL (cpu) — not a chip result", never a result
  --control NAME   puts a broken guarantee in the encoder's place, to
                   show that ``correct`` comes out false (tap.py), or
                   in the client's (copy-flip: worker.py)
  --fault NAME     tests only: breaks the timed path underneath
  --keep-trace DIR keeps a traced run's extracted events as JSON
  --manifest PATH  another BENCHMARK.json (the throw-away cell of the
                   README's checklist)
"""

from __future__ import annotations

import argparse
import contextlib
import os
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def main() -> int:
    t0 = time.time()
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true")
    p.add_argument("--control")
    p.add_argument("--fault")
    p.add_argument("--keep-trace")
    p.add_argument("--manifest")
    args = p.parse_args()
    if not os.path.isdir(os.path.join(REPO, "lizardfs_tpu")):
        print(f"FAIL: no program to measure: {REPO}/lizardfs_tpu is not "
              "there", file=sys.stderr)
        return 2
    if not os.path.exists(os.path.join(REPO, "native", "libec_native.so")):
        made = subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                              stdout=subprocess.DEVNULL)
        if made.returncode:
            print("FAIL: make -C native", file=sys.stderr)
            return 2
        print(f"native library built in {time.time() - t0:.1f}s", flush=True)
    cmd = [sys.executable, os.path.join(HERE, "worker.py"),
           "--workload", args.workload, "--seed", str(args.seed),
           "--seconds", str(args.seconds), "--trace", str(args.trace),
           "--t0", repr(t0)]
    for flag in ("control", "fault", "keep_trace", "manifest"):
        if getattr(args, flag):
            cmd += ["--" + flag.replace("_", "-"), getattr(args, flag)]
    if args.rehearse_cpu:
        cmd.append("--rehearse-cpu")
    env = dict(os.environ)
    env.pop("LIZARDFS_TPU_ENCODER", None)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    # own session: whatever the worker leaves behind (it stops its
    # daemons itself) dies with the group below
    proc = subprocess.Popen(cmd, start_new_session=True, env=env, cwd=REPO)
    try:
        return proc.wait()
    finally:
        with contextlib.suppress(ProcessLookupError, PermissionError):
            os.killpg(proc.pid, signal.SIGKILL)
        with contextlib.suppress(Exception):
            proc.wait(timeout=10)


if __name__ == "__main__":
    sys.exit(main())
