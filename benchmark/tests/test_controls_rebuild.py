"""``ec84-rebuild-under-write``'s comparison and its wait have to fail
what they should: the same whole toy runs as ``test_controls.py`` (by
hand, each starts daemons), under the cell's rehearsal: 2 writers,
files of 5 MiB, a chunkserver SIGKILLed once 64 MiB are written (the
mix's rehearsal `at_bytes`), inside a 3 s window.

    python -m pytest benchmark/tests/test_controls_rebuild.py -q
"""

import re
import subprocess
import sys

from held_manifest import make
from test_controls import RUN, failing, rehearse

CELL = "ec84-rebuild-under-write"


def test_sound_run_is_correct_and_reports_the_rebuild():
    line = rehearse(CELL, seconds="3")
    assert line["correct"] is True and not failing(line)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"rebuild_MBps", "setup_s"}
    assert line["metrics"]["rebuild_MBps"]["value"] > 0


def test_parity_short_comes_out_not_correct():
    line = rehearse(CELL, "--control", "parity-short", seconds="3")
    assert line["correct"] is False
    assert {"stored_wrong_bytes", "stored_wrong_crcs"} <= failing(line)


def test_an_altered_rebuilt_part_comes_out_not_correct():
    """The comparison reads the part files the rebuild made: a byte
    altered in each of them, after full redundancy, is found."""
    line = rehearse(CELL, "--fault", "rebuilt-flip", seconds="3")
    assert line["correct"] is False
    # the stored CRC words are left as they were: the bytes alone differ
    assert failing(line) == {"stored_wrong_bytes"}
    assert 1 <= line["checks"]["stored_wrong_bytes"]["value"] <= 12


def test_encode_flip_comes_out_not_correct():
    line = rehearse(CELL, "--fault", "encode-flip", seconds="3")
    assert line["correct"] is False
    assert "stored_wrong_bytes" in failing(line)


def test_rebuilds_held_back_end_at_the_cap_with_no_result(tmp_path):
    held = make(str(tmp_path), cap_s=5)
    done = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "2147483777",
         "--seconds", "3", "--trace", "0", "--rehearse-cpu", "--manifest",
         held], capture_output=True, text=True, timeout=600)
    assert done.returncode == 6
    assert "rehearsal line" not in done.stdout
    assert re.search(r"FAIL: no full redundancy 5s after the close",
                     done.stdout)
    assert "stopping the daemons" in done.stdout
