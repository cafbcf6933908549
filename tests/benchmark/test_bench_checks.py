"""The comparison that decides ``correct`` fails what it should, on
part files built here from the reference (no daemon runs)."""

import os
import struct
import types

import numpy as np
import pytest

import checks
import resultline
from reference import layout

K, M, BLOCK = 3, 2, 65536
GOAL = {"k": K, "m": M}


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
    assert checks.check_chunk(data, GOAL, BLOCK, files.items()) == (0, 0)
    found = layout.find_part_files([str(tmp_path)], 1,
                                   layout.ec_part_id(K, M, 4))
    assert found == [(0, files[4])]


@pytest.mark.parametrize("part", [0, K, K + M - 1])
def test_one_altered_byte_is_caught_in_data_and_parity(tmp_path, data, part):
    files = write_parts(str(tmp_path), data, spoil=("byte", part))
    assert checks.check_chunk(data, GOAL, BLOCK, files.items()) == (1, 0)


def test_altered_crc_word_and_short_part_are_caught(tmp_path, data):
    files = write_parts(str(tmp_path), data, spoil=("crc", 1))
    assert checks.check_chunk(data, GOAL, BLOCK, files.items()) == (0, 1)
    files = write_parts(str(tmp_path), data, spoil=("short", K))
    bad_bytes, _ = checks.check_chunk(data, GOAL, BLOCK, files.items())
    assert bad_bytes >= BLOCK // 2


def test_parity_short_control_is_caught(tmp_path, data):
    # the control of tap.py: the last parity part stored as zeros
    files = write_parts(str(tmp_path), data)
    with open(files[K + M - 1], "r+b") as f:
        f.seek(layout.HEADER_BYTES)
        f.write(b"\0" * (2 * BLOCK))
    bad_bytes, _ = checks.check_chunk(data, GOAL, BLOCK, files.items())
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


# -- xor and copy goals ----------------------------------------------------

def write_goal_parts(dirs, data, goal, chunk_id=1, spoil=None):
    """The goal's part files of one chunk, one a server directory in
    turn; ``spoil`` = (file number, "byte" | "zero") alters one."""
    streams = layout.goal_parts(goal, data, BLOCK)
    lens = layout.goal_part_lengths(goal, len(data), BLOCK)
    ids = layout.part_ids(goal)
    for n, (pid, home) in enumerate(zip(ids, dirs)):
        p = pid % 64
        nblocks = -(-lens[p] // BLOCK)
        body = streams[p][:nblocks * BLOCK].copy()
        if spoil == (n, "byte"):
            body[7] ^= 1
        if spoil == (n, "zero"):
            body[:] = 0
        crcs = layout.block_crcs(body, BLOCK)   # as the writer sent them
        table = b"".join(struct.pack(">I", c) for c in crcs).ljust(4096, b"\0")
        sub = os.path.join(home, f"{chunk_id & 0xFF:02X}")
        os.makedirs(sub, exist_ok=True)
        with open(os.path.join(sub, f"chunk_{chunk_id:016X}_P{pid:08X}_"
                               f"{1:08X}.liz"), "wb") as f:
            f.write(b"LIZTPU10".ljust(1024, b"\0") + table + body.tobytes())


def chunk_at(dirs, goal, ports=None, chunk_id=1):
    ids = layout.part_ids(goal)
    ports = ports or range(9000, 9000 + len(ids))
    return types.SimpleNamespace(chunk_id=chunk_id, locations=[
        types.SimpleNamespace(part_id=pid, addr=types.SimpleNamespace(port=p))
        for pid, p in zip(ids, ports)])


GOALS = [{"xor": 3}, {"copies": 2}, {"copies": 3}, {"k": 3, "m": 2}]


@pytest.mark.parametrize("goal", GOALS, ids=str)
def test_a_goals_sound_parts_compare_equal(tmp_path, data, goal):
    dirs = [str(tmp_path / f"cs{i}") for i in range(6)]
    write_goal_parts(dirs, data, goal)
    ok, files = checks.stored_parts(chunk_at(dirs, goal), goal, 0, dirs)
    assert ok and len(files) == len(layout.part_ids(goal))
    assert checks.check_chunk(data, goal, BLOCK, files) == (0, 0)


@pytest.mark.parametrize("goal,spoil", [
    ({"xor": 3}, (0, "zero")),        # parity-short: xor's parity as zeros
    ({"xor": 3}, (2, "byte")),
    ({"copies": 2}, (1, "byte")),     # copy-flip: one copy a byte off
    ({"copies": 3}, (0, "byte"))], ids=str)
def test_one_altered_part_of_a_goal_is_caught(tmp_path, data, goal, spoil):
    dirs = [str(tmp_path / f"cs{i}") for i in range(6)]
    write_goal_parts(dirs, data, goal, spoil=spoil)
    ok, files = checks.stored_parts(chunk_at(dirs, goal), goal, 0, dirs)
    bad_bytes, bad_crcs = checks.check_chunk(data, goal, BLOCK, files)
    assert ok and bad_bytes >= 1 and bad_crcs >= 1
    per_part = checks.check_parts(data, goal, BLOCK, files)
    assert sum(1 for _p, b, _c in per_part if b) == 1


def test_copies_on_one_server_or_one_short_are_parts_wrong(tmp_path, data):
    goal = {"copies": 2}
    dirs = [str(tmp_path / f"cs{i}") for i in range(3)]
    write_goal_parts(dirs[:1] * 2, data, goal)        # both in one place
    ok, _files = checks.stored_parts(chunk_at(dirs, goal), goal, 0, dirs)
    assert not ok
    write_goal_parts(dirs[1:2], data, goal)            # one more elsewhere
    ok, _files = checks.stored_parts(chunk_at(dirs, goal), goal, 0, dirs)
    assert ok
    ok, _files = checks.stored_parts(chunk_at(dirs, goal, [9000, 9000]),
                                     goal, 0, dirs)
    assert not ok, "the master names one server twice"
    info = chunk_at(dirs, goal)
    info.locations = info.locations[:1]
    assert not checks.stored_parts(info, goal, 0, dirs)[0]
    # the victim's copy gone with it, where the mix lost a server
    assert not checks.stored_parts(info, goal, 1, dirs)[0], \
        "two files on disk for the one place the master names"


def test_a_xor_part_of_another_type_is_parts_wrong(tmp_path, data):
    goal = {"xor": 3}
    dirs = [str(tmp_path / f"cs{i}") for i in range(4)]
    write_goal_parts(dirs, data, goal)
    info = chunk_at(dirs, goal)
    info.locations[1].part_id = layout.xor_part_id(2, 1)
    assert not checks.stored_parts(info, goal, 0, dirs)[0]


@pytest.mark.parametrize("control", [None, "parity-short", "copy-flip"])
@pytest.mark.asyncio
async def test_two_copies_and_xor3_written_through_the_client(tmp_path,
                                                              control):
    """``stream-write``'s sessions at its rehearsal's size, one in a
    directory of two copies and one at ``$xor3``, in process: a sound
    window reads 0 on every count of the comparison and the tap counts
    the xor calls; parity-short stores xor's parity as zeros and
    copy-flip one copy a byte off, and each is caught on the disk."""
    import generator
    import manifest
    import worker
    from tap import EncoderTap

    from lizardfs_tpu.core.encoder import CpuChunkEncoder
    from tests.test_cluster import STD2_GOAL, XOR_GOAL, Cluster

    cell = manifest.Cell(manifest.load_manifest(), "ec84-stream-write")
    manifest.rehearsal_of(cell)
    cluster = Cluster(tmp_path, n_cs=5)
    await cluster.start(health_interval=0.5)
    enc = CpuChunkEncoder()
    tap = EncoderTap(enc, control=control)
    try:
        clients = [await cluster.client() for _ in range(3)]
        checker = clients.pop()
        for c in clients:
            c.encoder = enc
            if control == "copy-flip":
                worker.break_client(c, control)
        dirs = []
        for gid, goal in ((STD2_GOAL, {"copies": 2}), (XOR_GOAL, {"xor": 3})):
            d = await checker.mkdir(1, f"g{gid}")
            await checker.setgoal(d.inode, gid)
            dirs.append(generator.Directory(f"g{gid}", d.inode, goal))
        homes = types.SimpleNamespace(live_cs_dirs=lambda: [
            str(tmp_path / f"cs{i}") for i in range(5)])
        # every chunk on the disks compared, not a sample: each goal's
        # are among them whatever the window's length
        mix = dict(cell.mix, sessions=2,
                   check=dict(cell.mix["check"], disk_chunks=10**6))
        t = generator.Traffic(mix, 2147483801, clients, dirs, homes,
                              int(cell.config["chunk_bytes"]))
        await t.setup(on_warm=tap.reset)
        tap.reset()
        await t.run(1.0)
        got = await checks.compare(t, checker, cell.config, 2147483801)
    finally:
        tap.remove()
        await cluster.stop()
    assert t.ops and all(op.ok for op in t.ops)
    assert {f.dir for f in t.model.live() if f.length} == {0, 1}
    assert tap.xor_calls and not tap.encode_calls
    wrong = {n for n, c in got.items() if c["value"] > c["limit"]}
    if control is None:
        assert checks.all_within(got), got
    else:
        assert "stored_wrong_bytes" in wrong, got
