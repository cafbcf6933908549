"""The plain reference against published vectors and its own laws."""

import itertools
import zlib

import numpy as np
import pytest

from reference import gf256, layout

# ISO/IEC 18004 (QR code), Annex A: powers of alpha = 2 in GF(2^8)
# modulo x^8 + x^4 + x^3 + x^2 + 1
ANTILOG = {0: 1, 1: 2, 7: 128, 8: 29, 9: 58, 10: 116, 11: 232, 12: 205,
           13: 135, 14: 19, 15: 38, 25: 3, 50: 5, 254: 142}


@pytest.mark.parametrize("power,value", sorted(ANTILOG.items()))
def test_published_antilog_table(power, value):
    assert int(gf256.EXP[power]) == value
    assert int(gf256.LOG[value]) == power


def test_field_laws():
    for a in (1, 2, 3, 29, 142, 255):
        assert gf256.mul(a, gf256.inv(a)) == 1
        assert gf256.mul(a, 1) == a and gf256.mul(a, 0) == 0
        row = gf256.mul_row(a)
        assert [int(row[x]) for x in (0, 1, 2, 77)] == [
            gf256.mul(a, x) for x in (0, 1, 2, 77)]
    assert gf256.mul(2, 128) == 29  # x * x^7 reduces by 0x11d


def test_isal_vandermonde_rows():
    # gf_gen_rs_matrix: parity row r holds (2^r)^j
    rows = gf256.parity_rows(8, 4)
    assert rows[0].tolist() == [1] * 8
    assert rows[1].tolist() == [1, 2, 4, 8, 16, 32, 64, 128]
    assert rows[2].tolist() == [1, 4, 16, 64, 29, 116, 205, 19]
    assert rows[3, :3].tolist() == [1, 8, 64]


@pytest.mark.parametrize("k,m", [(8, 4), (3, 2)])
def test_any_k_of_k_plus_m_recover(k, m):
    rng = np.random.default_rng(k * 100 + m)
    data = [rng.integers(0, 256, 96, dtype=np.uint8) for _ in range(k)]
    parts = data + gf256.encode(k, m, data)
    for lost in itertools.combinations(range(k + m), m):
        have = {i: parts[i] for i in range(k + m) if i not in lost}
        got = gf256.recover(k, m, have, list(lost))
        for i in lost:
            assert np.array_equal(got[i], parts[i]), lost


def test_crc32_published_check_value():
    # the CRC-32/ISO-HDLC check value of "123456789"
    assert zlib.crc32(b"123456789") == 0xCBF43926
    assert layout.block_crcs(np.frombuffer(b"123456789", np.uint8), 9) == [
        0xCBF43926]


def test_part_ids_as_upstream_packs_them():
    # chunk_part_type.h: type * 64 + part; goal.h: ec(k,m) = 10+32(k-2)+(m-1)
    assert layout.ec_part_id(3, 2, 0) == (10 + 32 + 1) * 64
    assert layout.ec_part_id(8, 4, 11) == (10 + 32 * 6 + 3) * 64 + 11


@pytest.mark.parametrize("length", [1, 65536, 65537, 3 * 65536, 1_000_000])
def test_striping_round_trips(length):
    k, m, block = 3, 2, 65536
    data = np.random.default_rng(length).integers(0, 256, length, np.uint8)
    parts = layout.expected_parts(data, k, m, block)
    lens = layout.part_lengths(k, m, length, block)
    assert sum(lens[:k]) == length and lens[k:] == [max(lens[:k])] * m
    # block i lies in data part i % k at slot i // k
    back = np.zeros(len(parts[0]) * k, np.uint8)
    for i in range(len(back) // block):
        back[i * block:(i + 1) * block] = parts[i % k][
            (i // k) * block:(i // k + 1) * block]
    assert np.array_equal(back[:length], data)
    assert not back[length:].any()
    want = gf256.encode(k, m, parts[:k])
    assert all(np.array_equal(a, b) for a, b in zip(parts[k:], want))


def test_chunk_spans():
    assert layout.chunk_spans(10, 4) == [(0, 4), (4, 8), (8, 10)]
    assert layout.chunk_spans(0, 4) == []


# -- xor and copy goals, worked by hand at a block of 4 bytes -------------

def test_xor_and_copy_part_ids_as_upstream_packs_them():
    # goal.h: xor2..xor9 are slice types 2..9, a standard copy type 0;
    # chunk_part_type.h: type * 64 + part; xor parity is part 0
    assert layout.xor_part_id(3, 0) == 192 and layout.xor_part_id(2, 2) == 130
    assert layout.part_ids({"xor": 3}) == [192, 193, 194, 195]
    assert layout.part_ids({"copies": 2}) == [0, 0]
    assert layout.part_ids({"k": 3, "m": 2}) == [
        layout.ec_part_id(3, 2, p) for p in range(5)]


def test_xor_parity_and_part_lengths_by_hand():
    data = np.arange(1, 15, dtype=np.uint8)          # 14 bytes, 4 blocks
    b = [data[0:4], data[4:8], data[8:12], np.array([13, 14, 0, 0], np.uint8)]
    parity, one, two = layout.goal_parts({"xor": 2}, data, 4)
    # block i of the chunk in data part 1 + i % 2
    assert one.tolist() == b[0].tolist() + b[2].tolist()
    assert two.tolist() == b[1].tolist() + b[3].tolist()
    assert parity.tolist() == (b[0] ^ b[1]).tolist() + (b[2] ^ b[3]).tolist()
    assert parity.tolist() == [4, 4, 4, 12, 4, 4, 11, 12]
    # a parity part is as long as the longest data part
    assert layout.goal_part_lengths({"xor": 2}, 14, 4) == [8, 8, 6]
    assert layout.goal_part_lengths({"xor": 3}, 10, 4) == [4, 4, 4, 2]
    assert layout.goal_part_lengths({"xor": 3}, 3, 4) == [3, 3, 0, 0]


def test_a_copy_is_the_chunk_whole_with_its_crcs():
    data = np.arange(10, dtype=np.uint8)
    copy, = layout.goal_parts({"copies": 3}, data, 4)
    assert copy.tolist() == list(range(10)) + [0, 0]
    assert layout.goal_part_lengths({"copies": 3}, 10, 4) == [10]
    assert layout.block_crcs(copy, 4) == [
        zlib.crc32(bytes([0, 1, 2, 3])), zlib.crc32(bytes([4, 5, 6, 7])),
        zlib.crc32(bytes([8, 9, 0, 0]))]


@pytest.mark.parametrize("n", [2, 3, 9])
def test_xor_parity_recovers_any_one_part(n):
    data = np.random.default_rng(n).integers(0, 256, 7 * 65536 + 5, np.uint8)
    parts = layout.goal_parts({"xor": n}, data, 65536)
    for lost in range(n + 1):
        rest = [p for i, p in enumerate(parts) if i != lost]
        assert np.array_equal(np.bitwise_xor.reduce(np.stack(rest)),
                              parts[lost])
