"""The comparison that decides ``correct`` fails what it should, on
part files built here from the reference (no daemon runs)."""

import os
import struct

import numpy as np
import pytest

import checks
import resultline
from reference import layout

K, M, BLOCK = 3, 2, 65536


def write_parts(tmp, data, spoil=None):
    parts = layout.expected_parts(data, K, M, BLOCK)
    lens = layout.part_lengths(K, M, len(data), BLOCK)
    files = {}
    for p, stream in enumerate(parts):
        nblocks = -(-lens[p] // BLOCK)
        body = stream[:nblocks * BLOCK].copy()
        crcs = layout.block_crcs(body, BLOCK)
        if spoil == ("byte", p):
            body[5] ^= 0x40
        if spoil == ("crc", p):
            crcs[0] ^= 1
        if spoil == ("short", p):
            body = body[:BLOCK // 2]
        table = b"".join(struct.pack(">I", c) for c in crcs).ljust(4096, b"\0")
        os.makedirs(os.path.join(tmp, "01"), exist_ok=True)  # low byte of the id
        path = os.path.join(tmp, "01", f"chunk_{1:016X}_P"
                            f"{layout.ec_part_id(K, M, p):08X}_{1:08X}.liz")
        with open(path, "wb") as f:
            f.write(b"LIZTPU10".ljust(1024, b"\0") + table + body.tobytes())
        files[p] = path
    return files


@pytest.fixture
def data():
    return np.random.default_rng(23).integers(0, 256, 5 * BLOCK + 77, np.uint8)


def test_sound_parts_compare_equal(tmp_path, data):
    files = write_parts(str(tmp_path), data)
    assert checks.check_chunk(data, K, M, BLOCK, files) == (0, 0)
    found = layout.find_part_files([str(tmp_path)], 1,
                                   layout.ec_part_id(K, M, 4))
    assert found == [(0, files[4])]


@pytest.mark.parametrize("part", [0, K, K + M - 1])
def test_one_altered_byte_is_caught_in_data_and_parity(tmp_path, data, part):
    files = write_parts(str(tmp_path), data, spoil=("byte", part))
    assert checks.check_chunk(data, K, M, BLOCK, files) == (1, 0)


def test_altered_crc_word_and_short_part_are_caught(tmp_path, data):
    files = write_parts(str(tmp_path), data, spoil=("crc", 1))
    assert checks.check_chunk(data, K, M, BLOCK, files) == (0, 1)
    files = write_parts(str(tmp_path), data, spoil=("short", K))
    bad_bytes, _ = checks.check_chunk(data, K, M, BLOCK, files)
    assert bad_bytes >= BLOCK // 2


def test_parity_short_control_is_caught(tmp_path, data):
    # the control of tap.py: the last parity part stored as zeros
    files = write_parts(str(tmp_path), data)
    with open(files[K + M - 1], "r+b") as f:
        f.seek(layout.HEADER_BYTES)
        f.write(b"\0" * (2 * BLOCK))
    bad_bytes, _ = checks.check_chunk(data, K, M, BLOCK, files)
    assert bad_bytes > BLOCK


def test_wrong_bytes_counts_differences_and_length():
    a = np.arange(10, dtype=np.uint8)
    assert checks.wrong_bytes(a.tobytes(), a) == 0
    b = a.copy()
    b[3] ^= 1
    assert checks.wrong_bytes(b.tobytes(), a) == 1
    assert checks.wrong_bytes(a[:6].tobytes(), a) == 4


def test_all_within_is_exact():
    ok = {n: {"value": 0, "limit": 0} for n in checks.NAMES}
    assert checks.all_within(ok)
    ok["stored_wrong_bytes"] = {"value": 1, "limit": 0}
    assert not checks.all_within(ok)


def test_result_line_has_exactly_the_contracts_keys():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1}
    metrics = {"write_MBps": {"value": 1.5, "unit": "MB/s"},
               "setup_s": {"value": 2.0, "unit": "s"}}
    compared = {n: {"value": 0, "limit": 0} for n in checks.NAMES}
    line = resultline.build(True, 10, 0, metrics, device, compared)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    traced = resultline.build(
        True, 10, 0, metrics, dict(device, busy_s=0.1, window_s=1.0), compared,
        {"device_ops": [["a", 0.1]] * 12, "idle_gaps": [["b", 0.2]],
         "more": 1})
    assert list(traced) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "checks"]
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(traced["breakdown"]["device_ops"]) == 10
    assert "\n" not in resultline.dumps(traced)
    with pytest.raises(ValueError):
        resultline.build(True, 1, 0, {"x": {"value": None, "unit": "s"}},
                         device, compared)
    with pytest.raises(ValueError):
        resultline.build(True, 1, 0, metrics, {"platform": "tpu"}, compared)
