"""The one general traffic generator. A traffic mix is a data file,
``benchmark/traffic/<name>.json``; this module reads it and drives the
clients. Everything a mix names is a file found by name:

  steps    verbs, each ``benchmark/traffic/verbs/<verb>.py`` with
           ``async def do(t, s, st, arg, warm)`` and, where it times an
           operation, ``CLASS`` (and ``METADATA = True`` for a call the
           master alone answers)
  faults   set-up actions after the preload, each
           ``benchmark/traffic/faults/<fault>.py`` with
           ``async def apply(t)``
  events   the same fault files, fired inside the window

so a later PR adds a verb or a fault as a file and edits nothing here.
A mix is:

  sessions        closed-loop sessions, one ``Client`` each, all sharing
                  the process's encoder; session s works in directory
                  s % len(directories) of the configuration
  steps           what a session repeats. A step is a verb's name, or
                  {"verb": name, ...its own parameters}, or
                  {"repeat": n, "steps": [...]}, or
                  {"each": "batch", "steps": [...]}: the steps once for
                  each file of the session's current batch
  sizes           the file sizes: {"fixed": bytes} or {"loguniform":
                  {"min", "max", "count"}}: a fixed set of ``count``
                  sizes in geometric steps. Every seed gets the same
                  set, each session in a seeded order of its own
  transfer_bytes  the size of one sequential read or write call
  preload         {"files", "bytes"}: files written during set-up
  events          what happens inside the window beside the sessions,
                  each fired once, as a task of its own: no session's
                  operation holds it and it holds none. Either
                  {"at_share": 0-1, "fault": name}: ``at_share`` of the
                  window's seconds after its open; or {"at_bytes": B,
                  "by_share": 0-1, "fault": name}: once the bytes the
                  sessions' writes acknowledged since the open reach B,
                  so that what the fault finds does not hang on the
                  writers' rate, or at ``by_share`` of the window where
                  that has not come, noted as "at_bytes not reached". A
                  mix without the key runs as one that never had it
  redundancy_cap_s  the one key that selects the wait: a mix that
                  carries it has the worker poll the master from a kill
                  inside the window and wait, that many seconds after
                  the close at the most, for full redundancy before it
                  compares. A mix without it, whatever its events do,
                  runs as a plain window
  check           how much the comparison samples (see checks.py);
                  ``make_live`` names the steps that make a file, run
                  once a session after the close where the window left
                  fewer than ``min_live`` files with data to compare

A string value "@key" anywhere in a mix stands for the configuration's
``key``, so a size or a count the source fixes is kept in one place.

The generator sees clients, a seed and a stop time; it never sees a
cell's name. ``plan`` is a pure function of (mix, seed): sizes, their
order, where each file's bytes lie in the pool.
"""

from __future__ import annotations

import asyncio
import contextlib
import math
import time
from dataclasses import dataclass, field

import numpy as np

import manifest
from reference.fsmodel import Model, make_pool


def verbs_of(steps: list) -> list[str]:
    """Every verb a list of steps names, nested ones included."""
    out = []
    for step in steps:
        if isinstance(step, str):
            out.append(step)
        elif "verb" in step:
            out.append(step["verb"])
        else:
            out.extend(verbs_of(step["steps"]))
    return out


def load_verb(name: str):
    return manifest.load_module("traffic", "verbs", name + ".py")


def load_fault(name: str):
    return manifest.load_module("traffic", "faults", name + ".py")


def size_set(sizes: dict) -> list[int]:
    if "fixed" in sizes:
        return [int(sizes["fixed"])]
    lu = sizes["loguniform"]
    lo, hi, n = int(lu["min"]), int(lu["max"]), int(lu["count"])
    return [int(round(lo * (hi / lo) ** (i / (n - 1)))) for i in range(n)]


@dataclass
class SessionPlan:
    size_order: list[int]          # indices into the size set, cycled
    seed: int                      # of this session's offset/retain draws


@dataclass
class Plan:
    sizes: list[int]
    pool_bytes: int
    slack: int
    preload_offsets: list[int]
    sessions: list[SessionPlan]
    warm_loops: int


def plan(mix: dict, seed: int) -> Plan:
    sizes = size_set(mix["sizes"]) if "sizes" in mix else []
    pre = mix.get("preload") or {}
    biggest = max(sizes + [int(pre.get("bytes", 0))])
    slack = 64 * 2**20
    n_sess = int(mix["sessions"])
    root = np.random.SeedSequence([int(seed), 0x6C697A])
    kids = root.spawn(n_sess + 1)
    rng = np.random.default_rng(kids[-1])
    preload_offsets = [int(rng.integers(0, slack // 64)) * 64
                       for _ in range(int(pre.get("files", 0)))]
    sessions = []
    for s in range(n_sess):
        r = np.random.default_rng(kids[s])
        sessions.append(SessionPlan(
            size_order=[int(i) for i in r.permutation(len(sizes))],
            seed=int(r.integers(0, 2**31)),
        ))
    warm_loops = max(1, math.ceil(len(sizes) / n_sess)) if sizes else 1
    return Plan(sizes, biggest + slack, slack, preload_offsets, sessions,
                warm_loops)


@dataclass
class Op:
    cls: str
    start: float
    end: float
    nbytes: int
    ok: bool
    metadata: bool = False


@dataclass
class Retained:
    name: str
    offset: int
    size: int
    data: bytes
    degraded: bool = False


@dataclass
class Directory:
    name: str
    inode: int
    goal: dict      # {"id", "name", "expr"} and {"k", "m"}, {"xor"} or {"copies"}


class Barrier:
    """All sessions still looping meet here, as mdtest's ranks do
    between its phases. A session that stops leaves, so that the rest
    are not held."""

    def __init__(self, parties: int):
        self.parties, self.count, self.gen = parties, 0, 0
        self.cond = asyncio.Condition()

    def _release(self) -> None:
        self.count = 0
        self.gen += 1
        self.cond.notify_all()

    async def wait(self) -> None:
        async with self.cond:
            gen = self.gen
            self.count += 1
            if self.count >= self.parties:
                self._release()
            else:
                await self.cond.wait_for(lambda: self.gen != gen)

    async def leave(self) -> None:
        async with self.cond:
            self.parties -= 1
            if self.count and self.count >= self.parties:
                self._release()


@dataclass
class Traffic:
    """State of one run of a mix; the worker reads the results off it."""

    mix: dict
    seed: int
    clients: list
    dirs: list                     # Directory, session s uses s % len
    cluster: object
    chunk_bytes: int
    annotate: object = None        # name -> context manager (traced runs)
    plan: Plan = field(init=False)
    model: Model = field(init=False)
    ops: list = field(default_factory=list)
    retained: list = field(default_factory=list)
    getattr_seen: list = field(default_factory=list)
    preloaded: list = field(default_factory=list)
    degraded_chunks: set = field(default_factory=set)   # (name, chunk index)
    victim: str | None = None
    uncertain: set = field(default_factory=set)         # names an op failed on
    unlinked: dict = field(default_factory=dict)        # name -> File, as last held
    lost_part_chunks: set = field(default_factory=set)  # any part on the victim
    kill_at: float | None = None   # monotonic: when an event killed the victim
    rebuilt_parts: set = field(default_factory=set)     # (chunk id, part id) since
    shared: dict = field(default_factory=dict)          # session -> its batch
    recording: bool = False
    stop_at: float = math.inf
    written: int = 0               # bytes of writes acknowledged inside the window
    byte_waits: list = field(default_factory=list)      # (bytes, asyncio.Event)
    notes: list = field(default_factory=list)           # for the worker's log
    retained_bytes: int = 0
    longest_retained: int = 0
    states: dict = field(default_factory=dict)          # per session
    barrier: Barrier | None = None

    def __post_init__(self):
        self.verbs = {v: load_verb(v) for v in verbs_of(
            self.mix["steps"] + self.mix["check"].get("make_live", []))}
        self.faults = [load_fault(a) for a in self.mix.get("faults", [])]
        self.events, self.byte_events = [], []
        for e in self.mix.get("events", []):
            if "at_bytes" in e:
                self.byte_events.append((int(e["at_bytes"]),
                                         float(e["by_share"]), e["fault"],
                                         load_fault(e["fault"])))
            else:
                self.events.append((float(e["at_share"]),
                                    load_fault(e["fault"])))
        self.plan = plan(self.mix, self.seed)
        self.model = Model(make_pool(self.seed, self.plan.pool_bytes))

    # -- set-up ---------------------------------------------------------

    async def setup(self, on_warm=None) -> None:
        """Preload, set-up faults, then the warm-up run of the mix's own
        steps; ``on_warm`` is called between the two, so that a caller
        can tell what the warm-up run drove from what came before."""
        pre = self.mix.get("preload")
        if pre:
            await self._preload(int(pre["files"]), int(pre["bytes"]))
        for fault in self.faults:
            await fault.apply(self)
        if on_warm is not None:
            on_warm()
        await self._run(warm=True)

    async def _preload(self, n: int, nbytes: int) -> None:
        async def one(j: int) -> None:
            s = j % len(self.clients)
            c, d = self.clients[s], self.dir_of(s)
            name = f"p{j}"
            attr = await c.create(d.inode, name)
            f = self.model.create(name, attr.inode, self.dirs.index(d))
            off = self.plan.preload_offsets[j]
            await c.write_file(attr.inode, self.model.pool[off:off + nbytes])
            self.model.write(name, off, nbytes)
            self.preloaded.append(f)

        lanes = len(self.clients)
        for a in range(0, n, lanes):
            await asyncio.gather(*(one(j) for j in range(a, min(a + lanes, n))))
        self.preloaded.sort(key=lambda f: int(f.name[1:]))

    # -- the window -----------------------------------------------------

    async def run(self, seconds: float) -> tuple[float, float]:
        """Drive every session until ``seconds`` have passed; returns
        the window's (open, close) on the monotonic clock. Ops under
        way at the close run to their end and are not counted in."""
        self.recording = True
        t_open = time.monotonic()
        self.stop_at = t_open + seconds
        events = [asyncio.create_task(self._event(t_open + share * seconds,
                                                  fault))
                  for share, fault in self.events]
        events += [asyncio.create_task(self._byte_event(
            t_open, nbytes, t_open + share * seconds, name, fault))
            for nbytes, share, name, fault in self.byte_events]
        try:
            await self._run(warm=False)
            await asyncio.gather(*events)   # an event that failed fails the run
        finally:
            for task in events:
                task.cancel()
        self.recording = False
        return t_open, self.stop_at

    async def _event(self, at: float, fault) -> None:
        await asyncio.sleep(max(at - time.monotonic(), 0.0))
        await fault.apply(self)

    async def _byte_event(self, t_open: float, nbytes: int, by: float,
                          name: str, fault) -> None:
        """Fire once ``nbytes`` of writes have been acknowledged since
        the open, or at ``by`` where they have not."""
        reached = asyncio.Event()
        self.byte_waits.append((nbytes, reached))
        self._wake_byte_waits()
        with contextlib.suppress(asyncio.TimeoutError):
            await asyncio.wait_for(reached.wait(),
                                   max(by - time.monotonic(), 0.0))
        at, written = time.monotonic() - t_open, self.written
        if reached.is_set():
            self.notes.append(f"event {name}: at_bytes {nbytes} reached, "
                              f"{written} B acknowledged {at:.3f}s into "
                              "the window")
        else:
            self.notes.append(f"event {name}: at_bytes not reached: "
                              f"{written} of {nbytes} B acknowledged; fired "
                              f"on by_share, {at:.3f}s into the window")
        await fault.apply(self)

    def _wake_byte_waits(self) -> None:
        for nbytes, reached in self.byte_waits:
            if self.written >= nbytes:
                reached.set()

    async def make_live(self) -> int:
        """After the close: where the window left too few files with
        data for the comparison, each session makes one more through
        the same verbs, untimed. Returns how many were made."""
        chk = self.mix["check"]
        have = sum(1 for f in self.model.live() if f.length
                   and f.name not in self.uncertain)
        if have >= int(chk.get("min_live", 0)) or not chk.get("make_live"):
            return 0
        self.stop_at = math.inf
        await asyncio.gather(*(
            self._steps(chk["make_live"], s, self._state(s), False)
            for s in range(len(self.clients))))
        return len(self.clients)

    def running(self) -> bool:
        return time.monotonic() < self.stop_at

    async def _run(self, warm: bool) -> None:
        self.barrier = Barrier(len(self.clients))
        await asyncio.gather(*(
            self._session(s, warm) for s in range(len(self.clients))))

    async def timed(self, cls: str, nbytes: int, coro, metadata=False):
        """Await one operation of a verb, timed and classed."""
        span = self.annotate("bench.op." + cls) if (
            self.annotate and self.recording) else contextlib.nullcontext()
        t0 = time.monotonic()
        ok = True
        try:
            with span:
                return await coro
        except Exception:
            ok = False
            raise
        finally:
            if self.recording:
                self.ops.append(Op(cls, t0, time.monotonic(), nbytes, ok,
                                   metadata))
                if ok and cls == "write":
                    self.written += nbytes
                    self._wake_byte_waits()

    async def _session(self, s: int, warm: bool) -> None:
        st = self._state(s)
        loops = 0
        try:
            while (loops < self.plan.warm_loops) if warm else self.running():
                if not await self._steps(self.mix["steps"], s, st, warm):
                    return
                loops += 1
        finally:
            await self.barrier.leave()

    async def _steps(self, steps: list, s: int, st: dict, warm: bool) -> bool:
        """Run the steps in order; False once the window has closed."""
        for step in steps:
            if not warm and not self.running():
                return False
            if isinstance(step, dict) and "repeat" in step:
                for _ in range(int(step["repeat"])):
                    if not await self._steps(step["steps"], s, st, warm):
                        return False
                continue
            if isinstance(step, dict) and "each" in step:
                for f in list(st[step["each"]]):
                    st["cur"] = f
                    if not await self._steps(step["steps"], s, st, warm):
                        return False
                continue
            name, arg = (step, {}) if isinstance(step, str) \
                else (step["verb"], step)
            try:
                await self.verbs[name].do(self, s, st, arg, warm)
            except Exception as e:  # noqa: BLE001 - an op failed
                if warm:
                    raise
                # counted as failed by timed(); what the file holds now
                # is not known, so the comparison leaves it out, and
                # the verbs that follow find no current file
                st["errors"].append(f"{name}: {type(e).__name__}: {e}")
                if st["cur"] is not None:
                    self.uncertain.add(st["cur"].name)
                st["cur"] = None
        return True

    def _state(self, s: int) -> dict:
        if s not in self.states:
            self.states[s] = {
                "seq": 0, "cur": None, "mine": [], "made": [], "batch": [],
                "errors": [], "size_pos": 0, "file_pos": 0, "warm_i": 0,
                "rng": np.random.default_rng(self.plan.sessions[s].seed),
            }
        return self.states[s]

    # -- what the verbs use ----------------------------------------------

    def dir_of(self, s: int) -> Directory:
        return self.dirs[s % len(self.dirs)]

    def next_size(self, s: int, st: dict, warm: bool) -> int:
        sizes = self.plan.sizes
        if warm:
            # between them the sessions' warm loops meet every size once
            i = (s + st["warm_i"] * len(self.clients)) % len(sizes)
            st["warm_i"] += 1
            return sizes[i]
        i = self.plan.sessions[s].size_order[st["size_pos"] % len(sizes)]
        st["size_pos"] += 1
        return sizes[i]

    def retain(self, st, f, offset, size, data) -> None:
        """Keep an answer for the comparison after the close: a seeded
        share of them under a byte cap, and always the longest so far."""
        if not self.recording:
            return
        chk = self.mix["check"]
        draw = float(st["rng"].random())
        if size <= self.longest_retained and (
            draw >= float(chk["retain_share"])
            or self.retained_bytes + size > int(chk["retain_bytes"])
        ):
            return
        degraded = any(
            (f.name, ci) in self.degraded_chunks
            for ci in range(offset // self.chunk_bytes,
                            (offset + max(size, 1) - 1) // self.chunk_bytes + 1))
        self.retained.append(Retained(f.name, offset, size, data, degraded))
        self.retained_bytes += size
        self.longest_retained = max(self.longest_retained, size)

    def session_errors(self) -> list[str]:
        return [e for st in self.states.values() for e in st["errors"]]
