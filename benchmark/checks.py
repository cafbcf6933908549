"""What decides ``correct``: the comparison of what the timed window
produced with the plain reference, once the window has closed.

Every number compared is an exact count with the limit 0:

  read_wrong_bytes      bytes of the retained answers of the window's
                        reads (a seeded sample, the longest in it,
                        degraded ones among them) that differ from the
                        model's contents
  getattr_wrong         lengths ``getattr`` reported inside the window
                        that differ from the model's
  names_wrong           names the master lists in the run's directory
                        that the model does not hold, and the reverse
  length_wrong          live files whose length at the master differs
  parts_wrong           sampled chunks of live files whose parts are not
                        what the goal keeps on distinct chunkservers
                        (k + m for $ec(k,m), N + 1 for xorN, N files of
                        the one id for N copies; less the killed
                        server's, where the mix killed one), or whose
                        part files are not where the master says
  stored_wrong_bytes    bytes of those chunks' part files on the
                        chunkservers' disks, parity parts included, that
                        differ from the reference's striping and
                        Reed-Solomon or XOR parity of the model's
                        contents, or, for each copy, from the contents
  stored_wrong_crcs     CRC words of those part files that differ from
                        the CRC32 of the reference's blocks
  readback_wrong_bytes  bytes of sampled live files read back cold after
                        the close that differ from the model's
  unchecked             sampled items the comparison could not read, and
                        rebuilt chunks the mix asks for that the run
                        does not have

Where the window killed a server and the master rebuilt what it held,
the mix's ``check.rebuilt_chunks`` more chunks are drawn from those of
which a part was rebuilt, so that the rebuilt part files are compared
in every run, not by the sample's luck; ``parts_wrong`` then asks for
every part on distinct live servers, the victim's directory left out.

The reference (``benchmark/reference``) imports nothing of the program
and takes nothing the program made.
"""

from __future__ import annotations

from collections import Counter

import numpy as np

from reference import layout

NAMES = ("read_wrong_bytes", "getattr_wrong", "names_wrong", "length_wrong",
         "parts_wrong", "stored_wrong_bytes", "stored_wrong_crcs",
         "readback_wrong_bytes", "unchecked")


def wrong_bytes(got, want: np.ndarray) -> int:
    got = np.frombuffer(got, dtype=np.uint8) if not isinstance(
        got, np.ndarray) else got
    n = min(len(got), len(want))
    return int(np.count_nonzero(got[:n] != want[:n])) + abs(len(got) - len(want))


def sample_with_first(items: list, n: int, rng) -> list:
    """The first item (the longest, as the callers sort) and a seeded
    draw of n - 1 of the others, in their order."""
    n = min(n, len(items))
    if n <= 0:
        return []
    rest = sorted(rng.choice(np.arange(1, len(items)), size=n - 1,
                             replace=False))
    return items[:1] + [items[i] for i in rest]


def check_chunk(data: np.ndarray, goal: dict, block: int,
                part_files) -> tuple[int, int]:
    """(wrong bytes, wrong CRC words) of one chunk's stored parts
    against the reference; ``part_files``: (part index, path) pairs."""
    per_part = check_parts(data, goal, block, part_files)
    return (sum(b for _p, b, _c in per_part), sum(c for _p, _b, c in per_part))


def check_parts(data: np.ndarray, goal: dict, block: int,
                part_files) -> list[tuple[int, int, int]]:
    """(part index, wrong bytes, wrong CRC words) of each part file of
    the (part index, path) pairs, against the goal's stream of that
    index: every copy of a copy goal against the chunk's bytes whole."""
    want_parts = layout.goal_parts(goal, data, block)
    live = layout.goal_part_lengths(goal, len(data), block)
    out = []
    for p, path in part_files:
        bad_bytes = bad_crcs = 0
        body, table = layout.read_part_file(path, block)
        want = want_parts[p]
        if len(body) < live[p]:
            bad_bytes += live[p] - len(body)
        n = min(len(body), len(want))
        bad_bytes += int(np.count_nonzero(body[:n] != want[:n]))
        bad_bytes += int(np.count_nonzero(body[n:]))  # past the end: zeros
        nblocks = -(-live[p] // block)
        want_crcs = layout.block_crcs(want[:nblocks * block], block)
        bad_crcs += sum(1 for a, b in zip(table[:nblocks], want_crcs) if a != b)
        bad_crcs += max(nblocks - len(table), 0)
        out.append((p, bad_bytes, bad_crcs))
    return out


async def chunk_table(traffic, client, config: dict) -> dict:
    """Chunk id -> (goal, chunk length, block) for every chunk of the
    model's live files: the master is asked for the id alone, the
    length and the goal are the harness's own."""
    block, chunk_bytes = int(config["block_bytes"]), int(config["chunk_bytes"])
    out = {}
    for f in traffic.model.live():
        if not f.length or f.name in traffic.uncertain:
            continue
        goal = traffic.dirs[f.dir].goal
        for ci, (a, b) in enumerate(layout.chunk_spans(f.length, chunk_bytes)):
            info = await client.chunk_info(f.inode, ci)
            out[info.chunk_id] = (goal, b - a, block)
    return out


def stored_parts(info, goal: dict, lost: int, cs_dirs: list[str]):
    """(parts as the goal keeps them, (part index, path) of each part
    file the master names): the master's places are as many as the
    goal keeps (less ``lost``), on distinct servers, of the goal's ids,
    and each part file lies on exactly as many servers as places name
    its id."""
    want_ids = layout.part_ids(goal)
    got_ids = [loc.part_id for loc in info.locations]
    n = len(want_ids) - lost
    ports = {loc.addr.port for loc in info.locations}
    ok = (len(got_ids) == n and len(ports) == n
          and not Counter(got_ids) - Counter(want_ids))
    files, homes = [], set()
    for pid in sorted(set(got_ids) & set(want_ids)):
        found = layout.find_part_files(cs_dirs, info.chunk_id, pid)
        if len(found) != got_ids.count(pid):
            ok = False
            continue
        homes.update(home for home, _path in found)
        files += [(pid % 64, path) for _home, path in found]
    return ok and len(homes) == len(files) == n, files


async def rebuilt_picks(traffic, client, chunks: list, taken: list, n: int,
                        rng) -> list:
    """A seeded draw of ``n`` of the chunks, beyond those taken, of
    which the master's records say a part was rebuilt."""
    rebuilt = {cid for cid, _part in traffic.rebuilt_parts}
    skip = {(f.name, ci) for f, ci in taken}
    have = []
    for f, ci in chunks:
        if (f.name, ci) not in skip and \
                (await client.chunk_info(f.inode, ci)).chunk_id in rebuilt:
            have.append((f, ci))
    return [have[i] for i in sorted(rng.choice(
        len(have), size=min(n, len(have)), replace=False))]


async def compare(traffic, client, config: dict, seed: int,
                  notes: dict | None = None) -> dict:
    """Run the whole comparison; returns name -> {"value", "limit"}.
    ``notes`` takes what it saw of rebuilt parts, for the log."""
    block, chunk_bytes = int(config["block_bytes"]), int(config["chunk_bytes"])
    model, chk = traffic.model, traffic.mix["check"]
    v = dict.fromkeys(NAMES, 0)
    rng = np.random.default_rng([int(seed), 0x63686B])

    for r in traffic.retained:
        if r.name in traffic.uncertain:
            continue
        # a file unlinked since: a name is written once, so what it
        # held when read is what the model's record of it last held
        f = model.files.get(r.name) or traffic.unlinked.get(r.name)
        if f is None:
            v["unchecked"] += 1
            continue
        v["read_wrong_bytes"] += wrong_bytes(
            r.data, model.bytes_of(f, r.offset, r.size))
    v["getattr_wrong"] = sum(1 for _n, seen, want in traffic.getattr_seen
                             if seen != want)

    live = [f for f in model.live() if f.name not in traffic.uncertain]
    listed = {e.name for d in traffic.dirs
              for e in await client.readdir(d.inode)} - traffic.uncertain
    v["names_wrong"] = len(listed ^ {f.name for f in live})
    for f in live:
        if int((await client.getattr(f.inode)).length) != f.length:
            v["length_wrong"] += 1

    # chunks of live files, a seeded sample with the longest file in it
    chunks = [(f, ci) for f in sorted(live, key=lambda f: -f.length)
              for ci in range(-(-f.length // chunk_bytes))]
    picks = sample_with_first(chunks, int(chk["disk_chunks"]), rng)
    want = int(chk.get("rebuilt_chunks", 0))
    if want:
        more = await rebuilt_picks(traffic, client, chunks, picks, want, rng)
        v["unchecked"] += want - len(more)
        picks = picks + more
    cs_dirs = traffic.cluster.live_cs_dirs()
    rebuilt = {"chunks": 0, "parts": 0, "wrong_bytes": 0, "wrong_crcs": 0}
    for f, ci in picks:
        goal = traffic.dirs[f.dir].goal
        try:
            info = await client.chunk_info(f.inode, ci)
            lost = 1 if (f.name, ci) in traffic.lost_part_chunks else 0
            ok, files = stored_parts(info, goal, lost, cs_dirs)
            if not ok:
                v["parts_wrong"] += 1
            span = layout.chunk_spans(f.length, chunk_bytes)[ci]
            data = model.bytes_of(f, span[0], span[1] - span[0])
            per_part = check_parts(data, goal, block, files)
            v["stored_wrong_bytes"] += sum(b for _p, b, _c in per_part)
            v["stored_wrong_crcs"] += sum(c for _p, _b, c in per_part)
            mine = [(b, c) for p, b, c in per_part
                    if (info.chunk_id, p) in traffic.rebuilt_parts]
            rebuilt["chunks"] += bool(mine)
            rebuilt["parts"] += len(mine)
            rebuilt["wrong_bytes"] += sum(b for b, _c in mine)
            rebuilt["wrong_crcs"] += sum(c for _b, c in mine)
        except (OSError, ValueError, RuntimeError):
            v["unchecked"] += 1
    if notes is not None:
        notes["rebuilt"] = rebuilt

    for f in sample_with_first(sorted(live, key=lambda f: -f.length),
                               int(chk["readback_files"]), rng):
        try:
            client.cache.invalidate(f.inode)
            got = await client.read_file(f.inode, 0, f.length)
            v["readback_wrong_bytes"] += wrong_bytes(got, model.bytes_of(f))
        except Exception:  # noqa: BLE001 - an answer that never comes
            v["unchecked"] += 1
    return {name: {"value": v[name], "limit": 0} for name in NAMES}


def all_within(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
