"""The comparison of xor and copy goals has to fail what it should: the
same whole toy runs as ``test_controls.py`` (by hand, each starts
daemons), on the throw-away cell of ``goals_manifest.py``: two writers,
one in a directory of two copies and one in a directory at ``$xor3``.

    python -m pytest benchmark/tests/test_controls_goals.py -q
"""

import json
import re
import subprocess
import sys

import pytest

from goals_manifest import CELL, make
from test_controls import MARK, RUN, failing


def rehearse(tmp_path, *extra: str, trace: str = "0") -> tuple[dict, str]:
    done = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "2147483777",
         "--seconds", "3", "--trace", trace, "--rehearse-cpu", "--manifest",
         make(str(tmp_path)), *extra],
        capture_output=True, text=True, timeout=600)
    lines = [ln for ln in done.stdout.splitlines() if MARK in ln]
    assert lines, done.stdout[-3000:] + done.stderr[-2000:]
    return json.loads(lines[-1].split(MARK, 1)[1]), done.stdout


@pytest.mark.parametrize("trace", ["0", "1"])
def test_sound_run_is_correct_and_the_tap_counts_the_xor_calls(tmp_path,
                                                                trace):
    line, out = rehearse(tmp_path, trace=trace)
    assert line["correct"] is True and not failing(line)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert all(c["value"] == 0 for c in line["checks"].values())
    xor = int(re.search(r"encode, \d+ recover and (\d+) xor calls", out)[1])
    assert xor > 0, "the xor3 writer's parity crossed the boundary"


def test_parity_short_on_xor_comes_out_not_correct(tmp_path):
    line, _out = rehearse(tmp_path, "--control", "parity-short")
    assert line["correct"] is False
    assert line["checks"]["stored_wrong_bytes"]["value"] > 0


def test_one_copy_a_byte_off_comes_out_not_correct(tmp_path):
    line, _out = rehearse(tmp_path, "--control", "copy-flip")
    assert line["correct"] is False
    assert line["checks"]["stored_wrong_bytes"]["value"] > 0
