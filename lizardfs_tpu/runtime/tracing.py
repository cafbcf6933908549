"""Request-scoped distributed tracing across the three roles.

The reference ships per-daemon charts and an oplog but nothing
request-scoped; closing a cross-process throughput gap (the ec(8,4)
write target) needs attribution past the client boundary. This module
is the L0 piece: trace ids, span records, a bounded per-process span
ring (oplog-style), and the client-side timeline merge.

Propagation:
  * master RPCs carry the trace id as a skew-tolerant TRAILING field on
    the wire messages (proto/messages.py ``trace_id``; the codec
    default-fills missing trailing fields, so a peer predating the
    field still decodes — version-skew pinned in tests/test_tracing.py),
  * the native data plane carries it as an OPTIONAL trailing u64 on
    request frames (native/wire.h "trace propagation" contract); the
    C++ server records per-op receive/disk/send timestamps into its own
    ring, drained into the chunkserver's SpanRing
    (chunkserver/server.py trace_spans).

Each daemon's ring is dumped over the admin link
(``lizardfs-admin <addr> trace-dump``) and merged client-side with
:func:`merge_timeline` into a per-request timeline, so one ec(8,4)
write rep decomposes into client encode/stage/send, chunkserver
recv/disk-commit, and ack segments across processes.

One primitive, :func:`span`, is used at every layer boundary of a
client op. It charges the ambient op's phase rows
(``runtime.metrics.PhaseBreakdown``), records the ring span with its
parent and its self time, and — once the process that owns the chip
has registered ``jax.profiler.TraceAnnotation`` — opens a profiler
annotation ``lz.<layer>.<name>``, so a traced run has the program's
spans in the same ``.xplane.pb`` as the device's operations.

Cost contract: with ``LZ_TRACE=0`` no ids are issued,
``current_trace_id()`` is 0 everywhere, and :func:`span` charges its
phase row and does nothing else (the phase rows are the always-on
counters). The loop meter (:class:`LoopMeter`) is such a counter: its
four counts run with ``LZ_TRACE=0`` too, at two clock readings a side
of every poll; the ``<name>_hold`` rows and the ``wake`` spans need the
span tree and are laid only while tracing is enabled. The measured cost
of the default (on) is in ``doc/operations.md`` ("Request tracing").

Clocks: ring spans carry CLOCK_REALTIME epoch seconds (C side:
microseconds via clock_gettime) so same-host cross-process merges line
up; durations are monotonic (``perf_counter``). An op's root
annotation carries ``t_ns``, its opening on ``time.time_ns()``'s
clock, so the profile's clock can be laid on the rings' axis.
"""

from __future__ import annotations

import asyncio
import contextvars
import functools
import itertools
import os
import time
from collections import deque
from threading import get_ident
from time import perf_counter, thread_time

# process-wide kill switch: LZ_TRACE=0 disables issuing trace ids, which
# short-circuits every record path (spans are only recorded for nonzero
# trace ids)
from lizardfs_tpu.constants import env_flag

_ENABLED = env_flag("LZ_TRACE")

# what this task is serving: the innermost open Span, or the _Anchor
# of a trace no span of which is open; either gives (trace_id, span_id)
CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "lz_trace", default=None
)

# the logical op in flight: where its spans charge phases and record
# (an OpSink). A contextvar (not a global) keeps concurrent clients in
# one process (in-process test clusters, gateways) from cross-charging;
# tasks and to_thread copy it, run_in_executor does not (Hop).
PHASE_SINK: contextvars.ContextVar = contextvars.ContextVar(
    "lz_phase_sink", default=None
)

# jax.profiler.TraceAnnotation, registered by the process that owns the
# chip (core/encoder.py) with the check that a profiler session is
# live; a daemon never imports jax and leaves it None
_ANNOTATE = None
_ANNOTATING = None


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> None:
    """Test/ops hook mirroring the LZ_TRACE env gate."""
    global _ENABLED
    _ENABLED = bool(on)


_ID_MASK = (1 << 63) - 1


def _seed_ids() -> None:
    """Trace and span ids count up from one random base per process
    (a getrandom call per id was a system call on every span)."""
    global _ids
    _ids = itertools.count(int.from_bytes(os.urandom(8), "big") & _ID_MASK)


_seed_ids()
os.register_at_fork(after_in_child=_seed_ids)


def new_id() -> int:
    # 63-bit nonzero: fits i64/u64 everywhere, 0 stays "untraced"
    return (next(_ids) & _ID_MASK) or 1


def register_annotator(factory, active=lambda: True) -> None:
    """``factory(name, **metadata)`` -> context manager; from now on
    every span opened while ``active()`` (a profiler session is live:
    one atomic load) is also an annotation ``lz.<layer>.<name>``."""
    global _ANNOTATE, _ANNOTATING
    _ANNOTATE, _ANNOTATING = factory, active


class _Anchor:
    """A trace with no span open (start_trace / adopt_trace): the
    parent of whatever opens under it."""

    __slots__ = ("trace_id",)
    span_id = 0

    def __init__(self, trace_id: int):
        self.trace_id = trace_id

    def cover(self, a: float, b: float) -> None:
        pass


def current_trace_id() -> int:
    cur = CURRENT.get()
    return cur.trace_id if cur is not None else 0


def start_trace() -> int:
    """Begin a new trace in this task's context; returns the trace id
    (0 when tracing is disabled — callers pass it through untouched)."""
    if not _ENABLED:
        return 0
    tid = new_id()
    CURRENT.set(_Anchor(tid))
    return tid


def ensure_trace() -> int:
    """Current trace id, starting a fresh trace if none is active."""
    tid = current_trace_id()
    return tid if tid else start_trace()


def begin() -> tuple[int, bool]:
    """Join the active trace or start a fresh one.

    Returns ``(trace_id, started)``; pass ``started`` to :func:`end`
    when the operation finishes so an op that STARTED its trace clears
    the context again — otherwise every later top-level op in the same
    task would silently reuse the first op's id and merge unrelated
    requests into one timeline."""
    tid = current_trace_id()
    if tid:
        return tid, False
    return start_trace(), True


def end(started: bool) -> None:
    if started:
        clear_trace()


def adopt_trace(tid: int) -> None:
    """Join an existing trace whose id arrived on the wire (e.g. the
    RebuildEngine's per-rebuild id riding MatocsReplicate) so every
    downstream op in this task propagates it."""
    if _ENABLED and tid:
        CURRENT.set(_Anchor(tid))


def clear_trace() -> None:
    CURRENT.set(None)


class SpanRing:
    """Bounded in-memory span ring, one per daemon/client (the oplog
    model applied to spans). Records are plain dicts so dumps are
    JSON-ready for the admin link.

    ``dropped`` counts spans evicted by the bound — observability of
    the observability layer: silent trace loss under load would
    otherwise read as "the op recorded nothing". Daemons mirror it
    into their registry as ``span_ring_dropped`` so it rides
    ``/metrics`` (``lizardfs_span_ring_dropped_total``)."""

    def __init__(self, maxlen: int = 2048):
        self._ring: deque = deque(maxlen=maxlen)
        self.dropped = 0
        self._drop_counter = None  # optional Metrics counter mirror

    def attach_drop_counter(self, counter) -> None:
        """Mirror evictions into a ``Metrics`` counter (daemon wiring);
        evictions that predate the attach are folded in once."""
        self._drop_counter = counter
        if self.dropped > counter.total:
            counter.inc(self.dropped - counter.total)

    def record(
        self,
        trace_id: int,
        name: str,
        t0: float,
        t1: float,
        role: str = "",
        parent_id: int = 0,
        *,
        bucket: str | None = None,
        **attrs,
    ) -> int:
        """Record one finished span; no-op (returns 0) for trace id 0,
        which is what every call site passes when tracing is off.
        ``bucket`` is the attribution bucket the site names for itself
        (queue, disk, net, compute)."""
        if not trace_id:
            return 0
        span_id = new_id()
        rec = {
            "trace_id": trace_id,
            "span_id": span_id,
            "parent_id": parent_id,
            "role": role,
            "name": name,
            "t0": t0,
            "t1": t1,
        }
        if bucket is not None:
            rec["bucket"] = bucket
        if attrs:
            rec["attrs"] = attrs
        self.push(rec)
        return span_id

    def push(self, rec) -> None:
        """Append one finished record, or a closed :class:`Span` (kept
        as it is: its record is made when the ring is dumped)."""
        if len(self._ring) == self._ring.maxlen:
            self.dropped += 1
            if self._drop_counter is not None:
                self._drop_counter.inc()
        self._ring.append(rec)

    def dump(self, trace_id: int | None = None) -> list[dict]:
        return [s if isinstance(s, dict) else s.record()
                for s in list(self._ring)
                if not trace_id or trace_id == (
                    s["trace_id"] if isinstance(s, dict) else s.trace_id)]

    def clear(self) -> None:
        self._ring.clear()

    def __len__(self) -> int:
        return len(self._ring)


class OpSink:
    """Where the spans of one component's logical ops land: its phase
    rows, its span ring under its role, and (for the ``dial`` gate) its
    metrics registry. An op's root span installs it as ambient."""

    __slots__ = ("phases", "ring", "role", "metrics")

    def __init__(self, phases, ring=None, role: str = "", metrics=None):
        self.phases = phases
        self.ring = ring
        self.role = role
        self.metrics = metrics


# --- the loop meter -------------------------------------------------------------

# the loop that runs on a thread, by the thread's ident: what a span
# that opens there, or a stamp made there, reads the turn from
_METERS: dict = {}

LOOP_COUNTS = ("loop_turns", "loop_busy_us", "loop_turn_sq_us2",
               "loop_offcpu_us")


class LoopMeter:
    """Every turn of one asyncio loop, stamped where the loop polls.

    It stands in for the loop's selector (``loop._selector``): its
    :meth:`select` reads the clocks on entry and on return and hands
    everything else to the selector it wraps. A *turn* is from
    ``select()``'s return to the next ``select()``'s entry: the time the
    loop was away from its poll, during which nothing that became ready
    can be served. Exact, not sampled; a turn costs two ``perf_counter``
    and two ``thread_time`` readings and a few additions.

    The counts are additive integers of µs, so that a window is the
    difference of two readings: ``turns`` closed, ``busy_us`` (Σ turn
    lengths), ``sq_us2`` (Σ of squared turn lengths: over twice a
    window it is what an event that becomes ready at a random instant
    waits for the next poll), ``offcpu_us`` (Σ over turns of wall less
    the loop thread's CPU time: the thread waiting for the GIL or
    blocked in a call that is not the poll). ``turn`` is the open
    turn's ordinal and ``opened`` when it opened (``perf_counter``):
    what a span reads to tell whether it gave the loop back, and the
    beat a daemon's stall sampler reads. Whoever is in ``watchers``
    (a daemon, for its ``loop_lag_ms``) has its ``longest_us`` raised
    to the longest turn since it set it to 0.

    What it cannot see: the loop thread's wait to get the GIL back as
    it leaves the poll is inside ``select()`` and counts as polling.

    One a loop (:func:`attach_meter`), gone when the loop closes. The
    counts reach a reader through the phase rows of ONE rider, the
    first that asked (:meth:`ride`), so that a sum over the sessions of
    a loop counts the loop once; when the rider leaves, what it showed
    stays in its rows and the next in line shows the rest."""

    __slots__ = ("loop", "ident", "turn", "opened", "turns", "busy_us",
                 "sq_us2", "offcpu_us", "watchers", "_cpu", "_selector",
                 "_select", "_riders", "register", "unregister", "modify",
                 "get_key", "get_map")

    def __init__(self, loop, selector):
        self.loop = loop
        self.ident = get_ident()
        self._selector = selector
        self._select = selector.select
        # what the loop asks of its selector beside select and close
        for name in ("register", "unregister", "modify", "get_key",
                     "get_map"):
            setattr(self, name, getattr(selector, name))
        self.turn = self.turns = 0
        self.busy_us = self.sq_us2 = self.offcpu_us = 0
        self.watchers: list = []
        self._riders: list = []
        self._cpu = thread_time()
        self.opened = perf_counter()

    def __getattr__(self, name):
        return getattr(self._selector, name)

    def select(self, timeout=None):
        wall = perf_counter() - self.opened
        off = int((wall - (thread_time() - self._cpu)) * 1e6)
        us = int(wall * 1e6)
        self.turns += 1
        self.busy_us += us
        self.sq_us2 += us * us
        if off > 0:
            self.offcpu_us += off
        for watcher in self.watchers:
            if us > watcher.longest_us:
                watcher.longest_us = us
        try:
            return self._select(timeout)
        finally:
            self._cpu = thread_time()
            self.opened = perf_counter()
            self.turn += 1

    def close(self) -> None:
        """The loop closes its selector as the last thing it does."""
        self.detach()
        self._selector.close()

    def detach(self) -> None:
        if _METERS.get(self.ident) is self:
            del _METERS[self.ident]
        if getattr(self.loop, "_selector", None) is self:
            self.loop._selector = self._selector
        for rows in self._riders[:1]:
            rows.settle()
        self._riders.clear()

    def counts(self) -> dict:
        return dict(zip(LOOP_COUNTS, (self.turns, self.busy_us, self.sq_us2,
                                      self.offcpu_us)))

    def ride(self, rows) -> None:
        """``rows`` (a ``PhaseBreakdown``) shows the loop's counts from
        now on, or waits in line behind the rows that do."""
        if rows not in self._riders:
            self._riders.append(rows)
            if len(self._riders) == 1:
                rows.carry(self.counts)

    def leave(self, rows) -> None:
        if rows not in self._riders:
            return
        first = self._riders[0] is rows
        self._riders.remove(rows)
        if first:
            rows.settle()
            if self._riders:
                self._riders[0].carry(self.counts)


def attach_meter() -> LoopMeter | None:
    """The meter of the loop that runs here, attached now if it has
    none; None on a loop that offers no place to stand at its poll
    (the counts are then absent and their readers give None)."""
    loop = asyncio.get_running_loop()
    meter = _METERS.get(get_ident())
    if meter is not None:
        if meter.loop is loop:
            return meter
        meter.detach()  # a loop that ended here and was never closed
    selector = getattr(loop, "_selector", None)
    if not callable(getattr(selector, "select", None)):
        return None
    meter = _METERS[get_ident()] = LoopMeter(loop, selector)
    loop._selector = meter
    return meter


def loop_meter() -> LoopMeter | None:
    """The meter of the loop that runs on this thread, if one does."""
    return _METERS.get(get_ident())


class Span:
    """The one span primitive: a context manager (or a ``begin()`` /
    ``end()`` pair) round one layer's share of an op; ``span`` is its
    name at the call sites.

    On exit it (a) charges ``phase`` on the ambient op's phase rows,
    (b) records the ring span with its parent, its ``bucket`` and its
    self time (duration less the union of what its children cover, so
    parallel children count once), (c) adds its interval to its
    parent's covered union; while open it is the parent of what runs
    inside it (tasks and ``to_thread`` copy the context; :class:`Hop`
    takes it into executor threads) and, while a profiler session is
    live in a process that registered an annotator, a profiler
    annotation ``lz.<layer>.<name>``.

    ``sink`` makes it an op's root: it installs the sink as ambient,
    starts a trace unless one is active, and closes one rep of the
    sink's phase rows with its wall and self time. With ``LZ_TRACE=0``
    a span charges its phase row (and a root its wall) and does
    nothing else.

    A span that opens and closes on a metered loop's thread inside one
    turn (:class:`LoopMeter`) never gave the loop back: it held it for
    its whole length, and charges its self time (nested synchronous
    spans count once) to the ambient op under ``<name>_hold``. One that
    suspends inside charges none: the Python it runs on the loop
    between its awaits stays in the loop's unnamed remainder.

    It runs some fifty times in a small write on a loop that twelve
    sessions share, so it allocates little: the ring keeps the closed
    span itself and makes the record when somebody dumps it."""

    __slots__ = ("name", "layer", "phase", "bucket", "sink", "attrs",
                 "p0", "w0", "dur", "self_s", "role", "trace_id", "span_id",
                 "parent", "covered", "_prev", "_prev_sink", "_ann",
                 "_meter", "_turn")

    def __init__(self, name: str, *, layer: str = "client",
                 phase: str | None = None, bucket: str | None = None,
                 sink: OpSink | None = None, **attrs):
        self.name = name
        self.layer = layer
        self.phase = phase
        self.bucket = bucket
        self.sink = sink
        self.attrs = attrs
        self.trace_id = self.span_id = 0
        self._ann = None

    def cover(self, a: float, b: float) -> None:
        # list.append is atomic: children close on other threads too
        self.covered.append((a, b))

    def begin(self, at: float | None = None) -> "Span":
        """Open the span; ``at`` (a ``perf_counter`` reading) opens it
        in the past, for a wait that is known only once it is over."""
        now = time.perf_counter()
        self.p0 = now if at is None else at
        if self.sink is not None:
            self._prev_sink = PHASE_SINK.get()
            PHASE_SINK.set(self.sink)
        if not _ENABLED:
            return self
        parent = self._prev = CURRENT.get()
        if parent is None:
            if self.sink is None:
                return self  # no op in flight: the phase row only
            parent = _Anchor(new_id())  # an op root starts its trace
        self.parent = parent
        self.trace_id = parent.trace_id
        self.span_id = new_id()
        self.covered = []
        self.w0 = time.time() - (now - self.p0)
        CURRENT.set(self)
        # the turn it opens in, on a metered loop's own thread (a span
        # laid after the fact held nothing)
        meter = self._meter = _METERS.get(get_ident()) if at is None else None
        if meter is not None:
            self._turn = meter.turn
        if _ANNOTATE is not None and at is None and _ANNOTATING():
            meta = self.attrs
            if self.sink is not None:
                # an op root says when it opened on time.time_ns()'s
                # clock: the profile's offset from the rings' axis
                meta = dict(meta, t_ns=int(self.w0 * 1e9))
            self._ann = _ANNOTATE(f"lz.{self.layer}.{self.name}", **meta)
            self._ann.__enter__()
        return self

    def end(self, at: float | None = None) -> None:
        dur = (time.perf_counter() if at is None else at) - self.p0
        if dur < 0.0:
            dur = 0.0
        sink = PHASE_SINK.get()
        if self.phase is not None and sink is not None:
            sink.phases.add(self.phase, dur)
        self_s = None
        if self.span_id:
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
            CURRENT.set(self._prev)
            w0 = self.w0
            self.parent.cover(w0, w0 + dur)
            self_s = dur
            if self.covered:  # most spans are leaves
                w1 = w0 + dur
                self_s = max(dur - _union_seconds(
                    [(max(a, w0), min(b, w1)) for a, b in self.covered
                     if b > w0 and a < w1]), 0.0)
            meter = self._meter
            if (meter is not None and meter.turn == self._turn
                    and at is None and sink is not None
                    and get_ident() == meter.ident):
                sink.phases.add(self.name + "_hold", self_s)
            if sink is not None and sink.ring is not None:
                self.dur, self.self_s, self.role = dur, self_s, sink.role
                sink.ring.push(self)
        if self.sink is not None:
            # an op root closes one rep: wall, and the self time no
            # child span covers ("phases sum to wall" at the top level)
            self.sink.phases.add_wall(dur, self_s)
            PHASE_SINK.set(self._prev_sink)

    __enter__ = begin

    def __exit__(self, *exc) -> bool:
        self.end()
        return False

    def record(self) -> dict:
        """The closed span as the ring's plain, JSON-ready record."""
        rec = {
            "trace_id": self.trace_id, "span_id": self.span_id,
            "parent_id": self.parent.span_id, "role": self.role,
            "name": self.name, "t0": self.w0, "t1": self.w0 + self.dur,
            "self_ms": round(self.self_s * 1e3, 3),
        }
        if self.bucket is not None:
            rec["bucket"] = self.bucket
        if self.attrs:
            rec["attrs"] = self.attrs
        return rec


span = Span


# the trip this worker thread is serving (Hop.__call__): where the
# native call it makes says when C saw its end (native_end)
_HOP: contextvars.ContextVar = contextvars.ContextVar("lz_hop", default=None)


class Hop:
    """One trip to a worker thread and back, for the op in flight.

    Made in the task that hands the work over: it takes what no
    executor carries (``run_in_executor`` copies no context), the open
    span, the ambient sink and when the trip was submitted. Called in
    the worker: what it took becomes ambient, the wait for the thread
    is a span of its own (``hop``; ``hop_compute`` where the work is
    the encoder's or a copy and not the wire's), and the last thing it
    does is stamp the clock. :meth:`wake`, in the coroutine that
    waited, as it runs again: the way back as a ``wake`` span under the
    span that waited, from that stamp, or, where the native call the
    worker made reported its own end (:func:`native_end`), from there,
    with the worker's wait to get the GIL back as ``wake_gil`` under
    it. ``func`` and ``args`` are a ``functools.partial``'s, for
    whoever names the work by them (runtime/detsched.py)."""

    __slots__ = ("func", "args", "phase", "cur", "sink", "submitted",
                 "meter", "done", "turn", "c_end", "gil_at")

    def __init__(self, func, *args, phase: str = "hop"):
        self.func = func
        self.args = args
        self.phase = phase
        self.cur, self.sink = CURRENT.get(), PHASE_SINK.get()
        self.meter = _METERS.get(get_ident())
        self.done = self.c_end = self.gil_at = None
        self.submitted = perf_counter()

    def __call__(self):
        if self.cur is None and self.sink is None:
            return self.func(*self.args)  # no op in flight to charge
        CURRENT.set(self.cur)
        PHASE_SINK.set(self.sink)
        _HOP.set(self)
        span(self.phase, layer="wire", phase=self.phase,
             bucket="queue").begin(at=self.submitted).end()
        try:
            return self.func(*self.args)
        finally:
            # pooled threads serve many requests: leak nothing into the next
            CURRENT.set(None)
            PHASE_SINK.set(None)
            _HOP.set(None)
            meter = self.meter
            self.turn = meter.turn if meter is not None else -1
            self.done = perf_counter()

    def wake(self) -> None:
        if self.done is None:
            return  # the worker has not ended: nobody was woken
        at = self.done if self.c_end is None else self.c_end
        wake("thread", (at, self.turn), self.gil_at)


async def hop(func, *args, executor=None, phase: str = "hop"):
    """``func(*args)`` on a thread of ``executor`` (None: the loop's
    own, as ``asyncio.to_thread``), under a copy of this task's
    context: the one way the client path goes to a thread and comes
    back (:class:`Hop`)."""
    trip = Hop(func, *args, phase=phase)
    try:
        return await asyncio.get_running_loop().run_in_executor(
            executor, functools.partial(contextvars.copy_context().run, trip))
    finally:
        trip.wake()


def native_end(at: float, now: float) -> None:
    """In a worker, after a native call that reports its own end on the
    steady clock: C saw the work end at ``at``; ``now`` is the worker's
    first reading after the call, once it had the GIL back."""
    trip = _HOP.get()
    if trip is not None:
        trip.c_end, trip.gil_at = min(at, now), now


def stamp():
    """Now, and the open turn of the loop that runs here, for a
    :func:`wake` laid later; None while tracing is disabled."""
    if not _ENABLED:
        return None
    meter = _METERS.get(get_ident())
    return (perf_counter(), meter.turn if meter is not None else -1)


def wake(after: str, stamped, gil_at: float | None = None) -> None:
    """The way back to the coroutine that runs this, as a span laid
    after the fact under the open one (bucket ``queue``): from
    ``stamped`` (a :func:`stamp`: where the work it waited for was
    done) to now. ``after`` says what it waited for (``"thread"``,
    ``"rpc"``), ``turns`` how many turns of its loop opened meanwhile;
    ``gil_at`` closes a ``wake_gil`` under it. Needs the span tree:
    nothing is laid while tracing is disabled."""
    if stamped is None or not _ENABLED:
        return
    at, turn = stamped
    meter = _METERS.get(get_ident())
    attrs = {"after": after}
    if meter is not None and turn >= 0:
        attrs["turns"] = meter.turn - turn
    way = span("wake", layer="loop", phase="wake", bucket="queue",
               **attrs).begin(at=at)
    if gil_at is not None:
        span("wake_gil", layer="loop", phase="wake_gil",
             bucket="queue").begin(at=at).end(at=gil_at)
    way.end()


def _union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Total length of the union of [t0, t1] intervals."""
    total = 0.0
    end = float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def merge_timeline(
    spans: list[dict], trace_id: int | None = None,
    wall_name: str | None = None,
) -> dict:
    """Merge spans (from any number of role rings) into one per-request
    timeline.

    ``wall_name`` names the root span whose [t0, t1] is the rep's wall
    time; it is EXCLUDED from coverage (a root span trivially covers
    100%) — coverage is the union of the remaining segments over the
    wall, the honest "how much of the rep can we attribute" number.
    Without a matching root the wall is the overall span envelope.
    """
    if trace_id:
        spans = [s for s in spans if s["trace_id"] == trace_id]
    if not spans:
        return {"trace_id": trace_id or 0, "segments": [],
                "wall_ms": 0.0, "coverage_pct": 0.0, "by_role_ms": {}}
    root = None
    if wall_name is not None:
        for s in spans:
            if s["name"] == wall_name and (
                root is None or s["t1"] - s["t0"] > root["t1"] - root["t0"]
            ):
                root = s
    segs = [s for s in spans if s is not root]
    t_lo = root["t0"] if root else min(s["t0"] for s in spans)
    t_hi = root["t1"] if root else max(s["t1"] for s in spans)
    wall = max(t_hi - t_lo, 1e-9)
    covered = _union_seconds(
        [(max(s["t0"], t_lo), min(s["t1"], t_hi)) for s in segs
         if s["t1"] > t_lo and s["t0"] < t_hi]
    )
    by_role: dict[str, float] = {}
    segments = []
    for s in sorted(segs, key=lambda x: (x["t0"], x["t1"])):
        dur = s["t1"] - s["t0"]
        by_role[s["role"]] = by_role.get(s["role"], 0.0) + dur
        segments.append({
            "role": s["role"], "name": s["name"],
            "start_ms": round((s["t0"] - t_lo) * 1e3, 3),
            "dur_ms": round(dur * 1e3, 3),
            "span_id": s.get("span_id", 0),
            "parent_id": s.get("parent_id", 0),
            **({k: s[k] for k in ("bucket", "self_ms", "attrs") if k in s}),
        })
    return {
        "trace_id": spans[0]["trace_id"],
        "wall_ms": round(wall * 1e3, 3),
        "coverage_pct": round(100.0 * covered / wall, 1),
        "by_role_ms": {
            r: round(v * 1e3, 3) for r, v in sorted(by_role.items())
        },
        "segments": segments,
    }


def format_timeline(timeline: dict) -> str:
    """Human-readable one-line-per-segment rendering (admin CLI)."""
    lines = [
        # 0x prefix: an all-digit bare hex id would reparse as decimal
        f"trace 0x{timeline.get('trace_id', 0):x}  "
        f"wall {timeline.get('wall_ms', 0.0):.2f} ms  "
        f"coverage {timeline.get('coverage_pct', 0.0):.1f}%"
    ]
    for seg in timeline.get("segments", ()):
        lines.append(
            f"  {seg['start_ms']:>10.3f} ms  +{seg['dur_ms']:<10.3f} "
            f"{seg['role']:<12s} {seg['name']}"
        )
    return "\n".join(lines)


# --- queue-wait gates ---------------------------------------------------------


def phase_t0() -> tuple[float, float]:
    """(perf_counter, wall) anchor for :func:`charge_queue_wait` —
    durations stay monotonic-accurate while span endpoints stay
    epoch-aligned."""
    return (time.perf_counter(), time.time())


def charge_queue_wait(
    metrics, ring, gate: str, tenant: str, t0: tuple[float, float],
    *, role: str = "", trace_id: int | None = None,
) -> float:
    """Charge one finished queue wait: a ``queue_wait{gate,tenant}``
    labeled timing on the owning component's registry plus a
    ``queue_wait:<gate>`` span on its ring (attribution's queue
    bucket). Explicit registry/ring arguments — in-process clusters run
    master + chunkservers + clients in one interpreter, so a
    process-global sink would misattribute the wait. Returns the
    seconds charged."""
    seconds = max(time.perf_counter() - t0[0], 0.0)
    tid = current_trace_id() if trace_id is None else trace_id
    if metrics is not None:
        metrics.labeled_timing(
            "queue_wait", {"gate": gate, "tenant": tenant or "default"},
            help="time ops spent waiting at an admission/credit gate "
                 "(DRR disk gate, write-window credits, shed retries, "
                 "connection dials) before doing any work",
        ).record(seconds, trace_id=tid)
    if ring is not None and tid:
        cur = CURRENT.get()
        ring.record(
            tid, f"queue_wait:{gate}", t0[1], t0[1] + seconds,
            role=role, parent_id=cur.span_id if cur is not None else 0,
            bucket="queue", gate=gate,
        )
    return seconds


# --- latency attribution -----------------------------------------------------

ATTRIBUTION_BUCKETS = ("queue", "disk", "net", "compute", "unattributed")


def _merge_intervals(ivs: list) -> list:
    """Sorted disjoint union of [a, b) intervals."""
    out: list = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _subtract_intervals(ivs: list, claimed: list) -> list:
    """``ivs`` minus ``claimed`` (both sorted disjoint unions)."""
    out = []
    for a, b in ivs:
        cur = a
        for ca, cb in claimed:
            if cb <= cur or ca >= b:
                continue
            if ca > cur:
                out.append((cur, ca))
            cur = max(cur, cb)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def attribute_timeline(timeline: dict) -> dict:
    """Decompose a :func:`merge_timeline` result into queue / disk /
    net / compute / unattributed-gap milliseconds that sum EXACTLY to
    the op's wall time.

    Every wall instant lands in at most one bucket: per-bucket span
    unions are resolved in priority order (queue > disk > net >
    compute), each later bucket only claiming instants no
    higher-priority bucket covered — overlapping spans can never push
    the sum past 100%. A segment's bucket is the one its site named
    when it recorded the span (``bucket``); one that names none — a
    span this table has not been told about — surfaces as
    unattributed, which is honest. Segments are clamped to the wall window, so a
    clock-skewed ring (a chunkserver span leaking past the client
    wall) cannot produce negative gaps; zero/negative-duration
    segments are skipped. Chunkserver spans carrying the native
    plane's ``queue_us``/``disk_us``/``net_us`` attrs are split into
    synthetic sub-intervals in that order instead of classifying the
    envelope, so one ``cs_read`` op feeds three buckets."""
    wall_ms = float(timeline.get("wall_ms", 0.0) or 0.0)
    buckets = {b: 0.0 for b in ATTRIBUTION_BUCKETS}
    out = {
        "trace_id": timeline.get("trace_id", 0),
        "wall_ms": round(wall_ms, 3),
        "buckets_ms": buckets,
        "pct": {b: 0.0 for b in ATTRIBUTION_BUCKETS},
        "dominant": "unattributed",
    }
    if wall_ms <= 0.0:
        return out
    per_bucket: dict[str, list] = {}
    for seg in timeline.get("segments", ()):
        try:
            s = float(seg.get("start_ms", 0.0))
            e = s + float(seg.get("dur_ms", 0.0))
        except (TypeError, ValueError):
            continue
        s = min(max(s, 0.0), wall_ms)
        e = min(max(e, 0.0), wall_ms)
        if e <= s:
            continue
        attrs = seg.get("attrs") or {}
        if any(k in attrs for k in ("queue_us", "disk_us", "net_us")):
            cursor = s
            for key, bucket in (
                ("queue_us", "queue"), ("disk_us", "disk"),
                ("net_us", "net"),
            ):
                dur = min(
                    max(float(attrs.get(key, 0) or 0), 0.0) / 1e3,
                    e - cursor,
                )
                if dur > 0.0:
                    per_bucket.setdefault(bucket, []).append(
                        (cursor, cursor + dur)
                    )
                    cursor += dur
            continue
        bucket = seg.get("bucket")
        if bucket in ATTRIBUTION_BUCKETS[:-1]:
            per_bucket.setdefault(bucket, []).append((s, e))
    claimed: list = []
    covered = 0.0
    for bucket in ("queue", "disk", "net", "compute"):
        ivs = _merge_intervals(per_bucket.get(bucket, []))
        own = _subtract_intervals(ivs, claimed)
        got = sum(b - a for a, b in own)
        buckets[bucket] = round(got, 3)
        covered += got
        claimed = _merge_intervals(claimed + ivs)
    buckets["unattributed"] = round(max(wall_ms - covered, 0.0), 3)
    out["pct"] = {
        b: round(100.0 * v / wall_ms, 1) for b, v in buckets.items()
    }
    out["dominant"] = max(buckets, key=lambda b: buckets[b])
    return out


def format_attribution(attr: dict) -> str:
    """One-block rendering (`trace-dump --attribute`, slowops)."""
    lines = [
        f"attribution 0x{attr.get('trace_id', 0):x}  "
        f"wall {attr.get('wall_ms', 0.0):.2f} ms  "
        f"dominant {attr.get('dominant', '?')}"
    ]
    buckets = attr.get("buckets_ms", {})
    pct = attr.get("pct", {})
    for b in ATTRIBUTION_BUCKETS:
        lines.append(
            f"  {b:<14s} {buckets.get(b, 0.0):>10.3f} ms "
            f"{pct.get(b, 0.0):>6.1f}%"
        )
    return "\n".join(lines)
