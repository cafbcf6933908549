"""The comparison that decides ``correct`` has to fail what it should.

Run by hand (each case starts a real master and chunkservers, so these
are kept out of the tier-1 run, which collects ``tests/`` only):

    python -m pytest benchmark/tests -q

Every case is a whole run of a cell through ``run.py --rehearse-cpu``:
the harness's look for a chip skipped, toy sizes, the device encoder on
the CPU platform, and underneath it either the control (a guarantee of
the configuration broken in the encoder's place) or a fault planted in
the timed path: a step that leaves the state unchanged (write-noop),
half of the work left out (write-half), an answer altered where it is
produced (read-flip, encode-flip, recover-flip). The cells run on one
chip, so there is no exchange between chips to leave out. The last case
is the other way round: a chunkserver that stands still inside the
window (``stall_seeded``) is no fault of the program, and the run has to
end as a sound one.
"""

import json
import os
import subprocess
import sys

import pytest

from stalled_manifest import make as stalled

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(os.path.dirname(HERE), "run.py")
MARK = "rehearsal line (not a result): "


def rehearse(workload: str, *extra: str, seconds: str = "2") -> dict:
    done = subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", "2147483777",
         "--seconds", seconds, "--trace", "0", "--rehearse-cpu", *extra],
        capture_output=True, text=True, timeout=600)
    lines = [ln for ln in done.stdout.splitlines() if MARK in ln]
    assert lines, done.stdout[-2000:] + done.stderr[-2000:]
    assert done.stdout.rstrip().endswith("not a chip result")
    return json.loads(lines[-1].split(MARK, 1)[1])


def failing(line: dict) -> set:
    return {n for n, c in line["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("workload", [
    "ec84-stream-write", "ec32-small-files", "ec84-degraded-read"])
def test_sound_run_is_correct(workload):
    line = rehearse(workload)
    assert line["correct"] is True and not failing(line)
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("workload,control,caught_by", [
    ("ec84-stream-write", "parity-short", "stored_wrong_bytes"),
    ("ec32-small-files", "parity-short", "stored_wrong_bytes"),
    ("ec84-degraded-read", "recover-approx", "read_wrong_bytes"),
])
def test_control_comes_out_not_correct(workload, control, caught_by):
    line = rehearse(workload, "--control", control)
    assert line["correct"] is False
    assert caught_by in failing(line)


@pytest.mark.parametrize("workload,fault,caught_by", [
    ("ec84-stream-write", "write-noop", "length_wrong"),
    ("ec84-stream-write", "write-half", "length_wrong"),
    ("ec84-stream-write", "encode-flip", "stored_wrong_bytes"),
    ("ec32-small-files", "write-noop", "getattr_wrong"),
    ("ec32-small-files", "write-half", "read_wrong_bytes"),
    ("ec32-small-files", "read-flip", "read_wrong_bytes"),
    ("ec32-small-files", "encode-flip", "stored_wrong_bytes"),
    ("ec84-degraded-read", "read-flip", "read_wrong_bytes"),
    ("ec84-degraded-read", "recover-flip", "read_wrong_bytes"),
])
def test_planted_fault_comes_out_not_correct(workload, fault, caught_by):
    line = rehearse(workload, "--fault", fault)
    assert line["correct"] is False
    assert caught_by in failing(line)


@pytest.mark.parametrize("workload", [
    "ec32-stream-write", "ec32-small-files", "ec84-degraded-read"])
def test_a_server_standing_still_fails_no_sound_run(workload, tmp_path):
    """A part read that waits past the clients' wave timeout is decoded
    from parity across the boundary: set-up has warmed that program, so
    nothing compiles inside the window (the worker would exit 4)."""
    done = subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", "2147483777",
         "--seconds", "10", "--trace", "0", "--rehearse-cpu", "--manifest",
         stalled(str(tmp_path), workload)],
        capture_output=True, text=True, timeout=600)
    assert "warmed the decode a slow or lost part would force" in done.stdout
    assert "compiled or loaded inside the measured window" not in done.stdout
    assert done.returncode == 0, done.stdout[-2000:]
    line = json.loads([ln for ln in done.stdout.splitlines()
                       if MARK in ln][-1].split(MARK, 1)[1])
    assert line["correct"] is True and line["failed"] == 0
