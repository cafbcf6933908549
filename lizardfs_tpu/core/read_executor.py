"""Wave-scheduled network execution of read plans.

The async analog of the reference's ReadPlanExecutor (reference:
src/common/read_plan_executor.cc): start wave 0's reads, fire the next
wave when a wave timeout expires or a read fails, finish as soon as the
plan says enough parts arrived, then post-process (recovery). Used by
the client read path and by the chunkserver replicator (both read chunk
parts from chunkservers).
"""

from __future__ import annotations

import asyncio
import logging

import numpy as np

from lizardfs_tpu.constants import MFSBLOCKSIZE
from lizardfs_tpu.core.plans import SliceReadPlan
from lizardfs_tpu.ops import crc32 as crc_mod
from lizardfs_tpu.proto import framing
from lizardfs_tpu.proto import messages as m
from lizardfs_tpu.proto import status as st
from lizardfs_tpu.runtime import accounting
from lizardfs_tpu.runtime import faults as _faults
from lizardfs_tpu.runtime import tracing

log = logging.getLogger("read_executor")

DEFAULT_WAVE_TIMEOUT = 0.5
DEFAULT_TOTAL_TIMEOUT = 30.0


class ReadError(Exception):
    """``crc`` marks end-to-end checksum rejections (the part's bytes
    arrived but are corrupt) — the signal the client's damaged-part
    reporting keys off, distinct from a merely unreachable holder."""

    def __init__(self, msg: str, crc: bool = False):
        self.crc = crc
        super().__init__(msg)


async def read_part_range(
    addr: tuple[str, int],
    chunk_id: int,
    version: int,
    part_id: int,
    offset: int,
    size: int,
    into: np.ndarray | None = None,
    into_offset: int = 0,
    fresh: bool = False,
) -> np.ndarray:
    """Read one range of one part from one chunkserver, verifying piece
    CRCs (ReadOperationExecutor analog). Connections come from the
    process-wide pool and are returned after a clean, fully-drained
    exchange (ConnectionPool analog). Every outcome feeds the shared
    per-chunkserver health scores (chunkserver_stats.cc analog).
    ``fresh``: the native exchange dials, once, whatever its pool holds
    idle (a native wave found nothing idle, or a dead socket)."""
    from lizardfs_tpu.core.conn_pool import GLOBAL_POOL
    from lizardfs_tpu.core.cs_stats import GLOBAL_STATS

    out = into if into is not None else np.zeros(size, dtype=np.uint8)
    if size == 0:
        return out[into_offset:into_offset]

    # bulk reads run the whole exchange in C++ off the event loop
    # (framing + CRC + scatter with the GIL released)
    from lizardfs_tpu.core import native_io

    if (
        native_io.available()
        and size >= native_io.NATIVE_READ_THRESHOLD
        # armed faults: the C++ exchange cannot be instrumented, so the
        # hookable asyncio path below serves (LZ_FAULTS unset: no change)
        and not _faults.ACTIVE
    ):
        # scatter straight into the caller's buffer whenever it is
        # contiguous: each op owns a disjoint region, and the cancel
        # path below aborts the socket and JOINS the executor thread, so
        # by the time execute_plan's finally finishes (it gathers every
        # cancelled task) no thread can still be writing the plan buffer
        # that post-processing reads. This removes a private-buffer
        # allocation + an on-loop memcpy per part (64 MiB per EC chunk).
        scatter_direct = (
            into is not None and out.flags.c_contiguous
            and out.dtype == np.uint8
        )
        if scatter_direct:
            tmp = out[into_offset : into_offset + size]  # view, no copy
        else:
            tmp = np.empty(size, dtype=np.uint8)
        # when scattering into the CALLER's buffer, the uninterruptible
        # executor thread must not outlive this coroutine: a cancelled
        # or failed attempt would otherwise keep writing `out` while a
        # retry refills the same region. The cell lets us shut the
        # socket down (killing the thread's recv) and join it.
        cell: dict = {}
        # the native exchange is charged as the op's net phase at the
        # await (parallel part reads overlap, so net busy-time may
        # exceed wall — the PhaseBreakdown pipelining contract); the
        # wait for its worker thread is a hop span under it
        net = tracing.span(
            "net", layer="wire", phase="net", bucket="net", part=part_id,
            bytes=size, plane="native",
        ).begin()
        # a bare future (the cancel path below joins it): the trip
        # carries the open span and the sink into the worker thread
        # (run_in_executor drops context), its way back is laid here
        trip = native_io.partial_with_trace(
            native_io.read_part_blocking,
            addr, chunk_id, version, part_id, offset, size, tmp,
            cell if scatter_direct else None, fresh,
        )
        fut = asyncio.get_running_loop().run_in_executor(
            native_io.EXECUTOR, trip)
        try:
            try:
                await asyncio.shield(fut)
            finally:
                trip.wake()
                net.end()
            GLOBAL_STATS.record_success(addr)
            if not scatter_direct:
                out[into_offset : into_offset + size] = tmp
            return out
        except asyncio.CancelledError:
            if scatter_direct:
                native_io.abort_read(cell)
                # a thread inside the exchange is joined: its recv fails
                # now. One that has published no socket is dialling (a
                # dead holder: for up to 30 s) or has ended, and finds
                # the cell aborted before it asks for a byte
                if "sock" in cell:
                    try:
                        await asyncio.wait_for(asyncio.shield(fut), 10.0)
                    except (Exception, asyncio.CancelledError):
                        pass
                else:
                    fut.add_done_callback(
                        lambda f: f.cancelled() or f.exception())
            raise
        except native_io.NativeIOError as e:
            GLOBAL_STATS.record_failure(addr)
            raise ReadError(str(e), crc="crc" in str(e).lower()) from None
        except (OSError, ConnectionError) as e:
            GLOBAL_STATS.record_failure(addr)
            raise ReadError(f"native read failed: {e}") from None

    conn = await GLOBAL_POOL.acquire(addr)
    clean = False
    cancelled = False
    # the whole framed exchange (request send + piece recv/CRC loop) is
    # net busy-time of the ambient logical op
    net = tracing.span(
        "net", layer="wire", phase="net", bucket="net", part=part_id,
        bytes=size, plane="asyncio",
    ).begin()
    try:
        await framing.send_message(
            conn.writer,
            m.CltocsRead(
                req_id=1,
                chunk_id=chunk_id,
                version=version,
                part_id=part_id,
                offset=offset,
                size=size,
                trace_id=tracing.current_trace_id(),
                # per-session attribution on the chunkserver: the
                # process-wide session identity (accounting.py), the
                # module-function analog of the thread-local trace id
                session_id=accounting.wire_session(),
            ),
        )
        received = 0
        while True:
            msg = await framing.read_message(conn.reader)
            if isinstance(msg, m.CstoclReadData):
                data = np.frombuffer(msg.data, dtype=np.uint8)
                if crc_mod.crc32(msg.data) != msg.crc:
                    raise ReadError(
                        "piece CRC mismatch from chunkserver", crc=True
                    )
                rel = msg.offset - offset
                if rel < 0 or rel + len(data) > size:
                    raise ReadError("piece outside requested range")
                out[into_offset + rel : into_offset + rel + len(data)] = data
                received += len(data)
            elif isinstance(msg, m.CstoclReadStatus):
                clean = True  # stream fully drained, even on error status
                if msg.status != st.OK:
                    GLOBAL_STATS.record_failure(addr)
                    raise ReadError(
                        f"read failed: {st.name(msg.status)}",
                        crc=msg.status == st.CRC_ERROR,
                    )
                if received < size:
                    GLOBAL_STATS.record_failure(addr)
                    raise ReadError(
                        f"short read: {received} of {size} bytes"
                    )
                GLOBAL_STATS.record_success(addr)
                return out
            else:
                raise ReadError(f"unexpected message {type(msg).__name__}")
    except asyncio.CancelledError:
        cancelled = True
        raise
    finally:
        net.end()
        if clean:
            GLOBAL_POOL.release(addr, conn)
        else:
            # a CANCELLED read (wave straggler made redundant, plan
            # aborted by a different part's failure) is not this
            # server's defect — only real failures count
            if not cancelled:
                GLOBAL_STATS.record_failure(addr)
            GLOBAL_POOL.discard(conn)


def _wave_goes_native(ops: list) -> bool:
    """Whether one native call serves a wave's part reads: it pays from
    two ops on, where each is one :func:`read_part_range` would hand to
    the native bulk exchange on a worker thread of its own (the one
    call takes the place of those threads)."""
    from lizardfs_tpu.core import native_io

    return (
        len(ops) >= 2
        and native_io.parts_wave_available()
        and not _faults.ACTIVE  # as in read_part_range
        and all(
            op.request_size >= native_io.NATIVE_READ_THRESHOLD
            and op.request_offset % MFSBLOCKSIZE == 0
            for op in ops
        )
    )


# What one worker thread reads of a native wave. The eight 256 KiB parts
# of a 2 MiB read at $ec(8,4) share one worker: eight threads' starts
# and their turns at the GIL cost more than the bytes. A rebuild's or a
# whole chunk's 8 MiB parts take a worker each, as before the native
# wave: one thread's recv and CRC pass over 64 MiB took 53 ms where
# eight take 13 (PERF.md section 6, PR 32, which has the sweep this
# number is from). Decided from the bytes the wave holds.
WAVE_WORKER_BYTES = 8 * 2**20


def _worker_loads(ops: list) -> list[list]:
    """A wave's ops, in order, in runs of at most WAVE_WORKER_BYTES
    (a run is at least one op): one native call and one worker a run."""
    loads: list[list] = []
    room = 0
    for op in ops:
        if not loads or op.request_size > room:
            loads.append([])
            room = WAVE_WORKER_BYTES
        loads[-1].append(op)
        room -= op.request_size
    return loads


class _NativeWave:
    """A wave in flight as native calls, one a worker: each call's
    state (``native_io.PartsWave``) beside the plan's ops it was built
    for, index for index, and which of them ``execute_plan`` has yet to
    settle."""

    __slots__ = ("calls", "unsettled")

    def __init__(self, calls: list):
        self.calls = calls  # [(PartsWave, its ops)]
        self.unsettled = {(c, i) for c, (_, ops) in enumerate(calls)
                          for i in range(len(ops))}

    def ended(self):
        """(op, holder, rc) of each part that has ended since the last
        look: all that are left, once the workers have returned."""
        for c, i in sorted(self.unsettled):
            wave, ops = self.calls[c]
            rc = wave.outcome(i)
            if rc is None:
                continue
            self.unsettled.discard((c, i))
            yield ops[i], wave.addrs[i], rc

    def parts_read(self) -> tuple[int, int]:
        """(parts the calls read whole, parts the wave asked for)."""
        return (
            sum(req.rc == 0 for wave, _ in self.calls for req in wave.reqs),
            sum(len(ops) for _, ops in self.calls),
        )


async def execute_plan(
    plan: SliceReadPlan,
    chunk_id: int,
    version: int,
    locations: dict[int, tuple[tuple[str, int], int]],
    wave_timeout: float = DEFAULT_WAVE_TIMEOUT,
    total_timeout: float = DEFAULT_TOTAL_TIMEOUT,
    buffer: np.ndarray | None = None,
    on_part_failure=None,
    count=None,
) -> np.ndarray:
    """Execute a plan; returns the post-processed result bytes.

    locations: slice part index -> ((host, port), wire part_id).
    ``buffer`` (optional, C-contiguous uint8 of plan.buffer_size) lets
    the caller provide the scatter target so successful single-op plans
    write the result in place.
    ``on_part_failure`` (optional ``fn(part, wire_part_id, addr, exc)``)
    observes every per-part failure as it happens — the client threads
    its damaged-part reporter through here so a CRC-rejected part is
    reported to the master even when the read itself recovers.
    ``count`` (optional ``fn(name, n)``) receives the native waves'
    counts: ``wave_native`` (a wave its native calls served whole),
    ``wave_native_parts`` (parts such calls read), and
    ``wave_native_fallback`` (a wave that went native and had a part
    the call did not read: per-part reads served in its place).

    A wave of two or more bulk reads is ONE native call on ONE worker
    thread for every WAVE_WORKER_BYTES it holds (:func:`_worker_loads`:
    a 2 MiB read's eight parts are one call, a rebuild's 8 MiB parts a
    call each; :func:`_wave_goes_native`, ``native_io.PartsWave``); any
    other wave is a :func:`read_part_range` task a part, and so is a
    part of a native wave that needs a dial: the pool holds no idle
    socket to its holder, or the one it held had died. A native call
    stays in flight past the wave timeout, as a slow per-part task
    does: the parts it has finished are harvested at every wake-up (C
    publishes each part's rc as the part ends), so a straggler holds
    back neither the next wave nor the parts that came with it.
    """
    from lizardfs_tpu.core import native_io
    from lizardfs_tpu.core.cs_stats import GLOBAL_STATS

    if buffer is None:
        buffer = np.zeros(plan.buffer_size, dtype=np.uint8)
    else:
        assert buffer.size == plan.buffer_size and buffer.dtype == np.uint8
    available: list[int] = []
    unreadable: list[int] = []
    # a per-part task -> its part; a native call's future -> its wave
    pending: dict[asyncio.Future, int | _NativeWave] = {}
    trips: dict[asyncio.Future, tracing.Hop] = {}  # a native call's trip
    native_waves: list[_NativeWave] = []  # all started, for the counts
    max_wave = max((op.wave for op in plan.read_operations), default=0)
    loop = asyncio.get_running_loop()
    deadline = loop.time() + total_timeout
    current_wave = -1

    def start_wave(w: int):
        ops = []
        for op in plan.read_operations:
            if op.wave != w:
                continue
            if op.part not in locations:
                unreadable.append(op.part)
                continue
            ops.append(op)
        # an op of no bytes (part_sizes clipped it) never reaches the
        # wire: read_part_range answers it at once
        wired = [op for op in ops if op.request_size]
        if buffer.flags.c_contiguous and _wave_goes_native(wired):
            ops = [op for op in ops if not op.request_size]
            # the calls outlive the wave timeout (see above); the plan's
            # own deadline ends them
            max_ms = max(int((deadline - loop.time()) * 1e3), 1)
            native = _NativeWave([
                (native_io.PartsWave(
                    [locations[op.part][0] for op in load],
                    chunk_id, version,
                    [locations[op.part][1] for op in load],
                    [op.request_offset for op in load],
                    [op.request_size for op in load],
                    buffer,
                    [op.buffer_offset for op in load],
                    max_ms,
                ), load)
                for load in _worker_loads(wired)
            ])
            native_waves.append(native)
            for wave, _ in native.calls:
                if not wave.live:
                    continue  # the pool has no socket for any of them
                # the open `waves` span and the sink ride into the
                # worker, whose wait is one `hop`; the way back is laid
                # where the plan sees the call done
                trip = native_io.partial_with_trace(
                    native_io.read_parts_wave_blocking, wave)
                fut = loop.run_in_executor(native_io.EXECUTOR, trip)
                pending[fut] = native
                trips[fut] = trip
            harvest(native)  # the parts the pool had no socket for
        for op in ops:
            start_part(op)

    def start_part(op, fresh: bool = False) -> None:
        addr, wire_part_id = locations[op.part]
        task = asyncio.ensure_future(
            read_part_range(
                addr,
                chunk_id,
                version,
                wire_part_id,
                op.request_offset,
                op.request_size,
                into=buffer,
                into_offset=op.buffer_offset,
                fresh=fresh,
            )
        )
        pending[task] = op.part

    def harvest(native: _NativeWave) -> None:
        for op, addr, rc in native.ended():
            if rc == 0:
                GLOBAL_STATS.record_success(addr)
                settle(op.part, None)
            elif rc == -1:
                # no idle socket, or a dead one (and then the pool's
                # others to that server are dead too): no verdict on
                # the holder before a task of the part's own has
                # dialled it, which also leaves the pool one deeper
                start_part(op, fresh=True)
            else:
                GLOBAL_STATS.record_failure(addr)
                settle(op.part, ReadError(
                    str(native_io.NativeIOError(rc, "read")),
                    crc=rc in (-3, st.CRC_ERROR),
                ))

    def settle(part: int, exc: BaseException | None) -> None:
        if exc is None:
            available.append(part)
            return
        log.debug("part %d failed: %s", part, exc)
        if on_part_failure is not None and part in locations:
            addr, wire_part_id = locations[part]
            try:
                on_part_failure(part, wire_part_id, addr, exc)
            except Exception:  # noqa: BLE001
                log.debug("part-failure observer failed", exc_info=True)
        unreadable.append(part)
        if not plan.is_finishing_possible(unreadable):
            raise ReadError(f"too many failed parts: {unreadable}")

    # the waves' part reads run in parallel: one span holds them, so
    # that the op's top level stays serial (net and dial nest in it)
    waves = tracing.span(
        "waves", layer="wire", phase="waves", bucket="net"
    ).begin()
    cancelled = False
    try:
        # inside the try: a native part that fails at once is settled
        # where its wave starts, and a plan that cannot finish raises
        # there with the wave's other parts in flight
        current_wave = 0
        start_wave(0)
        wave_start = loop.time()
        while not plan.is_reading_finished(available):
            if not pending:
                # everything in flight resolved; fire the next wave now
                if current_wave >= max_wave:
                    raise ReadError(
                        f"no more parts to try (available={available}, "
                        f"unreadable={unreadable})"
                    )
                current_wave += 1
                start_wave(current_wave)
                wave_start = loop.time()
                continue
            now = loop.time()
            if now >= deadline:
                raise ReadError("read plan timed out")
            if current_wave < max_wave:
                timeout = min(wave_start + wave_timeout - now, deadline - now)
            else:
                timeout = deadline - now
            done, _ = await asyncio.wait(
                pending.keys(),
                timeout=max(timeout, 0.001),
                return_when=asyncio.FIRST_COMPLETED,
            )
            for task in done:
                what = pending.pop(task)
                if isinstance(what, _NativeWave):
                    trips.pop(task).wake()
                    task.result()  # the worker raises for no part's sake
                else:
                    settle(what, task.exception())
            # the parts the native calls have finished, whether their
            # worker has returned or not (a finished part counts before
            # its call ends)
            for native in native_waves:
                harvest(native)
            # wave timeout: stragglers trigger the next wave (reference
            # startReadsForWave, read_plan_executor.cc:162-176)
            if (
                current_wave < max_wave
                and loop.time() - wave_start >= wave_timeout
            ):
                current_wave += 1
                start_wave(current_wave)
                wave_start = loop.time()
    except asyncio.CancelledError:
        cancelled = True
        raise
    finally:
        # no byte may land in the plan buffer once this returns: cancel
        # the tasks (read_part_range aborts its socket and joins its
        # thread), shut a native wave's sockets and join its worker
        for native in native_waves:
            for wave, _ in native.calls:
                # a call whose parts have all ended has returned or is
                # about to (a wake-up for another call found the plan
                # finished): its sockets are whole, and stay so
                if wave.in_flight():
                    native_io.abort_parts_gather(wave.cell)
        joins = []
        for task, what in pending.items():
            if isinstance(what, _NativeWave):
                joins.append(asyncio.wait_for(asyncio.shield(task), 10.0))
            else:
                task.cancel()
                joins.append(task)
        if joins:
            await asyncio.gather(*joins, return_exceptions=True)
        waves.end()
        if count is not None and not cancelled:
            for native in native_waves:
                ok, asked = native.parts_read()
                count("wave_native_parts", ok)
                count("wave_native" if ok == asked
                      else "wave_native_fallback", 1)

    # postprocess is the decode leg: parity recovery / block CRC checks
    # for striped plans (a plain pass-through for healthy std reads).
    # It runs on the event loop, so while it holds the boundary every
    # other session of this loop stands still: the span shows it
    with tracing.span("decode", phase="decode", bucket="compute"):
        result = plan.postprocess(buffer, available)
    return result
