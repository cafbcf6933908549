"""RebuildEngine: the master's explicit rebuild scheduler.

Elevates the endangered-FIFO -> replicator handoff (master/chunks.py
``health_work`` + chunkserver ``MatocsReplicate``) into a first-class
subsystem (reference analog: the replication limits + priority queues
of chunks.cc:1807-2200, made explicit):

  * **priority classes** — lost (one more failure loses data) >
    endangered (degraded but with margin) > rebalance (placement
    moves); higher classes always drain first,
  * **token-bucket throttle** — a cluster-wide rebuild bytes/s budget
    plus a concurrent-rebuild cap, both runtime-tunable through the
    tweaks registry (``rebuild_bps`` / ``rebuild_concurrency``, set via
    ``lizardfs-admin tweaks-set`` or SIGHUP-reloaded scripts), so a
    mass-rebuild after a server loss cannot starve client IO,
  * **progress/ETA accounting** — queued/active/completed/failed
    counts, bytes rebuilt, a sliding-window rebuild rate and the ETA it
    implies for the queued backlog,
  * **observability** — every rebuild carries a trace id (the
    executing chunkserver records its replication span under the same
    id, runtime/tracing.py) and lands in the ``replicate`` SLO class;
    the whole state is served by ``lizardfs-admin rebuild-status`` and
    the webui.

The engine schedules; the master executes (``_replicate_part`` /
``_move_part``) and reports back via :meth:`finished`.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

from lizardfs_tpu.runtime.limiter import TokenBucket

PRIORITY_LOST = 0
PRIORITY_ENDANGERED = 1
PRIORITY_REBALANCE = 2
PRIORITY_NAMES = {
    PRIORITY_LOST: "lost",
    PRIORITY_ENDANGERED: "endangered",
    PRIORITY_REBALANCE: "rebalance",
}

# sliding window over which the rebuild byte rate (and so the ETA) is
# computed
RATE_WINDOW_S = 30.0


@dataclass
class Rebuild:
    """One scheduled rebuild (a part replication or a placement move)."""

    chunk_id: int
    part: int
    priority: int
    kind: str = "replicate"  # "replicate" | "move"
    bytes_est: int = 0
    src_cs: int = 0  # moves: the holder being drained
    dst_cs: int = 0  # the target (a replicate's once it is chosen)
    trace_id: int = 0
    queued_at: float = field(default_factory=time.monotonic)
    started_at: float = 0.0

    @property
    def key(self) -> tuple[int, int]:
        return (self.chunk_id, self.part)

    def to_dict(self, now: float) -> dict:
        return {
            "chunk_id": self.chunk_id,
            "part": self.part,
            "class": PRIORITY_NAMES.get(self.priority, "?"),
            "kind": self.kind,
            "bytes": self.bytes_est,
            "trace_id": self.trace_id,
            "running_s": round(now - self.started_at, 2)
            if self.started_at else 0.0,
        }


def classify(chunk, state) -> int:
    """Priority class of a repair work item from its redundancy state
    (master/chunks.py RedundancyState): a chunk whose NEXT failure
    loses data is 'lost'-class work; anything else degraded is
    'endangered'."""
    if not state.is_readable:
        return PRIORITY_LOST  # only stale-version/filerepair can help
    if not state.missing_parts or state.boost_only:
        # heat-boost copies (base goal already satisfied) are placement
        # work: they must never outrank real repairs in the queue
        return PRIORITY_REBALANCE
    from lizardfs_tpu.core import geometry

    t = geometry.SliceType(chunk.slice_type)
    if t.is_standard:
        # a single live copy under a multi-copy goal: one more loss is
        # data loss
        live = len(chunk.parts_by_index().get(0, []))
        return PRIORITY_LOST if live <= 1 and chunk.copies > 1 \
            else PRIORITY_ENDANGERED
    return PRIORITY_LOST if not state.is_safe else PRIORITY_ENDANGERED


class RebuildEngine:
    def __init__(self, metrics=None, tweaks=None):
        self.metrics = metrics
        # throttle knobs ride the daemon tweaks registry so they are
        # admin/SIGHUP tunable without a restart (0 bps = unlimited)
        if tweaks is not None:
            self._bps = tweaks.register("rebuild_bps", 0)
            self._max_active = tweaks.register("rebuild_concurrency", 8)
        else:  # unit tests / detached use
            class _V:  # noqa: N801 - tiny value cell
                def __init__(self, v):
                    self.value = v

            self._bps = _V(0)
            self._max_active = _V(8)
        self.bucket = TokenBucket(0.0)
        self.queues: dict[int, deque[Rebuild]] = {
            p: deque() for p in PRIORITY_NAMES
        }
        self._queued: dict[tuple[int, int], Rebuild] = {}
        self.active: dict[tuple[int, int], Rebuild] = {}
        self.recent: deque[dict] = deque(maxlen=32)
        self.completed = 0
        self.failed = 0
        self.bytes_rebuilt = 0
        self._rate_events: deque[tuple[float, int]] = deque()

    # --- scheduling ---------------------------------------------------------

    def submit(self, rb: Rebuild) -> bool:
        """Queue a rebuild; False when (chunk, part) is already queued
        or running (the endangered FIFO re-marks aggressively). A
        resubmission at a HIGHER priority class upgrades the queued
        entry in place — a chunk that degrades further while waiting
        (second server lost) must not sit behind the backlog of the
        class it no longer belongs to."""
        if rb.key in self.active:
            return False
        queued = self._queued.get(rb.key)
        if queued is not None:
            if rb.priority < queued.priority:
                self.queues[queued.priority].remove(queued)
                queued.priority = rb.priority
                self.queues[queued.priority].append(queued)
            return False
        self.queues[rb.priority].append(rb)
        self._queued[rb.key] = rb
        if self.metrics is not None:
            self.metrics.counter(
                "rebuilds_queued",
                help="rebuilds accepted by the RebuildEngine scheduler",
            ).inc()
        return True

    def next_batch(self) -> list[Rebuild]:
        """Pop launchable rebuilds: strict priority order, bounded by
        the concurrency cap. The caller launches each and MUST report
        via :meth:`finished`."""
        out: list[Rebuild] = []
        cap = max(int(self._max_active.value), 1)
        now = time.monotonic()
        for prio in sorted(self.queues):
            q = self.queues[prio]
            while q and len(self.active) + len(out) < cap:
                rb = q.popleft()
                self._queued.pop(rb.key, None)
                rb.started_at = now
                out.append(rb)
        for rb in out:
            self.active[rb.key] = rb
        return out

    async def throttle(self, nbytes: int) -> None:
        """Pace a rebuild's bytes against the cluster budget (awaits
        until the token bucket allows; rate 0 = unlimited). The rate is
        re-read from the tweak each time so tweaks-set applies to the
        next rebuild, not the next restart."""
        self.bucket.rate = float(self._bps.value)
        self.bucket.burst = max(self.bucket.rate, 1.0)
        await self.bucket.acquire(nbytes)

    def skipped(self, rb: Rebuild) -> None:
        """A launched rebuild that never attempted work (no eligible
        target, link gone, chunk re-locked): release the slot without
        counting a failure — the health tick resubmits when the
        condition clears, and a no-op must not page anyone via
        lizardfs_rebuilds_failed_total."""
        self.active.pop(rb.key, None)

    def finished(self, rb: Rebuild, ok: bool, nbytes: int = 0) -> None:
        self.active.pop(rb.key, None)
        now = time.monotonic()
        if ok:
            self.completed += 1
            n = nbytes or rb.bytes_est
            self.bytes_rebuilt += n
            self._rate_events.append((now, n))
            if self.metrics is not None:
                self.metrics.counter(
                    "rebuilds_completed",
                    help="rebuilds that wrote their part successfully",
                ).inc()
                self.metrics.counter(
                    "rebuild_bytes",
                    help="bytes of parts rebuilt by the engine",
                ).inc(float(n))
        else:
            self.failed += 1
            if self.metrics is not None:
                self.metrics.counter(
                    "rebuilds_failed",
                    help="rebuilds that errored or timed out",
                ).inc()
        self.recent.appendleft({
            "chunk_id": rb.chunk_id, "part": rb.part, "kind": rb.kind,
            "class": PRIORITY_NAMES.get(rb.priority, "?"),
            "ok": ok, "ms": round((now - rb.started_at) * 1e3, 1),
            "bytes": nbytes or rb.bytes_est, "trace_id": rb.trace_id,
        })

    # --- accounting ---------------------------------------------------------

    def rate_bps(self, now: float | None = None) -> float:
        now = time.monotonic() if now is None else now
        while self._rate_events and \
                self._rate_events[0][0] < now - RATE_WINDOW_S:
            self._rate_events.popleft()
        total = sum(n for _, n in self._rate_events)
        return total / RATE_WINDOW_S

    def status(self) -> dict:
        """The ``rebuild-status`` document: queue depths by class,
        active rebuilds, throttle config, measured rate + backlog ETA,
        recent completions."""
        now = time.monotonic()
        pending_bytes = sum(
            rb.bytes_est for q in self.queues.values() for rb in q
        ) + sum(rb.bytes_est for rb in self.active.values())
        rate = self.rate_bps(now)
        eta = round(pending_bytes / rate, 1) if rate > 0 else None
        return {
            "queued": {
                PRIORITY_NAMES[p]: len(q) for p, q in self.queues.items()
            },
            "active": [rb.to_dict(now) for rb in self.active.values()],
            "throttle": {
                "rebuild_bps": int(self._bps.value),
                "rebuild_concurrency": int(self._max_active.value),
            },
            "completed": self.completed,
            "failed": self.failed,
            "bytes_rebuilt": self.bytes_rebuilt,
            "rate_bps": round(rate, 1),
            "pending_bytes": pending_bytes,
            "eta_s": eta,
            "recent": list(self.recent)[:16],
        }
