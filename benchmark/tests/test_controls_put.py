"""``ec84-put``'s comparison has to fail what it should: the same whole
toy runs as ``test_controls.py`` (by hand, each starts daemons), under
the cell's rehearsal: 2 writers, objects of 9 MiB, which still take the
windowed whole-chunk write, so the control sits under ``encode_into``.

    python -m pytest benchmark/tests/test_controls_put.py -q
"""

from test_controls import failing, rehearse

CELL = "ec84-put"


def test_sound_run_is_correct():
    line = rehearse(CELL)
    assert line["correct"] is True and not failing(line)
    assert line["attempted"] > 0 and line["failed"] == 0


def test_parity_short_comes_out_not_correct():
    line = rehearse(CELL, "--control", "parity-short")
    assert line["correct"] is False
    assert {"stored_wrong_bytes", "stored_wrong_crcs"} <= failing(line)


def test_encode_flip_comes_out_not_correct():
    line = rehearse(CELL, "--fault", "encode-flip")
    assert line["correct"] is False
    assert "stored_wrong_bytes" in failing(line)
