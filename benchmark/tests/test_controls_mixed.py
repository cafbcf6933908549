"""``ec84-s3-mixed``'s comparison has to fail what it should: the same
whole toy runs as ``test_controls.py`` (by hand, each starts daemons),
under the cell's rehearsal: 4 clients on a pool of 12 objects of the
source's own 10 MiB, so every PUT is the seven-segment windowed write
and every GET a whole-object read. The last case is the other way
round: a chunkserver that stands still inside the window forces the
decodes ``prepare_objects`` warmed, and the run ends as a sound one.

    python -m pytest benchmark/tests/test_controls_mixed.py -q
"""

import json
import subprocess
import sys

from stalled_manifest import make as stalled
from test_controls import MARK, RUN, failing, rehearse

CELL = "ec84-s3-mixed"


def test_sound_run_is_correct():
    line = rehearse(CELL)
    assert line["correct"] is True and not failing(line)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"ops_per_s", "setup_s"} <= set(line["metrics"])


def test_parity_short_comes_out_not_correct():
    line = rehearse(CELL, "--control", "parity-short")
    assert line["correct"] is False
    assert {"stored_wrong_bytes", "stored_wrong_crcs"} <= failing(line)


def test_read_flip_comes_out_not_correct():
    """The client's ``read_file`` altered: GET answers are compared."""
    line = rehearse(CELL, "--fault", "read-flip")
    assert line["correct"] is False
    assert "read_wrong_bytes" in failing(line)


def test_a_chunkserver_that_stands_still_fails_no_sound_run(tmp_path):
    done = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "2147483777",
         "--seconds", "10", "--trace", "0", "--rehearse-cpu", "--manifest",
         stalled(str(tmp_path), CELL)],
        capture_output=True, text=True, timeout=600)
    assert "compiled or loaded inside the measured window" not in done.stdout
    assert done.returncode == 0, done.stdout[-2000:]
    line = json.loads([ln for ln in done.stdout.splitlines()
                       if MARK in ln][-1].split(MARK, 1)[1])
    assert line["correct"] is True and line["failed"] == 0
