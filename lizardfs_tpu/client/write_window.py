"""Adaptive write window for the striped whole-chunk write.

PR 1's phase telemetry blamed the ec(8,4) write gap on stripe-serial
round trips: one ack barrier per stripe segment. This controller is an
**adaptive N-deep window** (the classic pipeline-depth/flow-control
shape from striped-storage systems — cf. the chain-replication write
executor in the LizardFS reference and credit-based stripe writers in
Colossus-style systems):

* up to ``depth`` stripe segments ride unacknowledged per chunk write
  (``MAX_DEPTH`` caps it);
* **credit-based flow control**: a :class:`CreditBucket` per
  chunkserver bounds unacknowledged bulk frames per connection, and
  one shared byte bucket bounds total staged bytes across every
  concurrent chunk write of the client (both from
  ``runtime/limiter.py``) — credits return when commit acks arrive;
* **adaptation from live PhaseBreakdown busy fractions**: every
  collected segment feeds encode/send EWMAs; an encode-bound pipeline
  shrinks the window (deeper buffering cannot help a compute
  bottleneck), a send-bound one grows it (keep the wire busy);
* **commit coalescing**: finished chunks queue their WriteChunkEnd
  records here and flush as ONE ``CltomaWriteChunkEndBatch`` master
  round trip per window flush instead of one handshake per chunk.

Depth/credit/coalesce counters register into the supplied Metrics
registry (Prometheus-exported wherever the owner exposes it).
"""

from __future__ import annotations

from lizardfs_tpu.runtime.limiter import CreditBucket

# adaptation hysteresis: one phase must out-busy the other by this
# factor (over the EWMA) before the depth moves — a noisy 50/50 split
# must not make the window oscillate
_ADAPT_RATIO = 1.3
# observations between depth moves: segments are short; adapting on
# every one would chase scheduling noise
_ADAPT_EVERY = 4
_EWMA_ALPHA = 0.3
# depth ceiling: unacknowledged segments a chunk write may have in
# flight, and the number of segments a chunk is cut into
MAX_DEPTH = 8
# depth floor the controller shrinks to (where the ceiling allows): at
# depth 1 a segment is reaped right after its own send, so the
# chunkservers' work on it never overlaps the next encode; double
# buffering is the least window that pipelines at all
MIN_DEPTH = 2
# client-wide staging budget across all in-flight windowed segments
BUDGET_BYTES = 128 * 2**20


class WriteWindow:
    """Shared, client-wide window state (one instance per Client)."""

    def __init__(self, metrics=None):
        self.max_depth = MAX_DEPTH
        # start double-buffered and adapt from there
        self.depth = min(2, self.max_depth)
        # per-chunkserver credit capacity: how many unacked bulk frames
        # one connection may carry; the window ceiling, so a single
        # writer is never credit-bound before it is depth-bound, while
        # concurrent writers to the same server share the cap
        self.cs_credits = self.max_depth
        self._cs: dict[tuple[str, int], CreditBucket] = {}
        self._budget = CreditBucket(float(BUDGET_BYTES))
        self._enc_ewma = 0.0
        self._send_ewma = 0.0
        self._since_adapt = 0
        # commit coalescing: chunk-end records queued by _write_chunk,
        # flushed by the client as one CltomaWriteChunkEndBatch; the
        # batch size bound keeps chunk locks from outliving the window
        self.pending_ends: list[dict] = []
        self.commit_batch = max(self.max_depth, 2)
        self._m_depth = self._m_waits = None
        self._m_segments = self._m_coalesced = None
        if metrics is not None:
            self._m_depth = metrics.gauge(
                "write_window_depth",
                help="current adaptive write-window depth (segments in "
                     "flight per striped chunk write)",
            )
            self._m_depth.set(float(self.depth))
            metrics.gauge(
                "write_window_depth_max",
                help="write-window depth ceiling",
            ).set(float(self.max_depth))
            self._m_waits = metrics.counter(
                "write_window_credit_waits",
                help="segment sends that blocked on chunkserver or byte "
                     "credits (backpressure events)",
            )
            self._m_segments = metrics.counter(
                "write_window_segments",
                help="stripe segments sent through the windowed path",
            )
            self._m_coalesced = metrics.counter(
                "write_commits_coalesced",
                help="WriteChunkEnd round trips saved by commit "
                     "coalescing (batched ends minus flushes)",
            )

    # --- credits ---------------------------------------------------------

    def _bucket(self, addr: tuple[str, int]) -> CreditBucket:
        b = self._cs.get(addr)
        if b is None:
            b = self._cs[addr] = CreditBucket(float(self.cs_credits))
            if len(self._cs) > 4096:
                # long-lived mounts see unboundedly many servers; only
                # idle (full) buckets are safe to forget
                for a in [a for a, bk in self._cs.items()
                          if bk.available >= bk.capacity and a != addr]:
                    del self._cs[a]
        return b

    def try_acquire(self, addrs, nbytes: float) -> bool:
        """All-or-nothing: one send credit per chunkserver plus
        ``nbytes`` from the shared staging budget, without waiting.
        False leaves every bucket untouched. This is the windowed
        sender's primary path — a writer holding outstanding segments
        must NEVER block here (it would hold credits while waiting for
        credits: two concurrent chunk writes that jointly exhaust a
        bucket would deadlock), it reaps its oldest acks instead."""
        taken = []
        ok = True
        for addr in addrs:
            if self._bucket(addr).try_acquire(1.0):
                taken.append(addr)
            else:
                ok = False
                break
        if ok and not self._budget.try_acquire(float(nbytes)):
            ok = False
        if not ok:
            for addr in taken:
                self._bucket(addr).release(1.0)
        return ok

    async def acquire(self, addrs, nbytes: float) -> None:
        """Blocking acquire — callers must hold NO outstanding
        segments (see try_acquire): then every credit holder is either
        an outstanding writer (which always reaps and releases) or
        another blocked acquirer. Buckets are taken in one GLOBAL
        order (sorted addrs, shared budget last), so blocked-acquirer
        wait chains strictly ascend and can never cycle — two sessions
        whose part layouts order the same chunkservers differently
        would otherwise hold-and-wait on each other."""
        taken = []
        try:
            for addr in sorted(addrs):
                await self._bucket(addr).acquire(1.0)
                taken.append(addr)
            await self._budget.acquire(float(nbytes))
        except BaseException:
            for addr in taken:
                self._bucket(addr).release(1.0)
            raise

    def note_segment(self, waited: bool) -> None:
        if self._m_segments is not None:
            self._m_segments.inc()
        if waited and self._m_waits is not None:
            self._m_waits.inc()

    def release(self, addrs, nbytes: float) -> None:
        for addr in addrs:
            self._bucket(addr).release(1.0)
        self._budget.release(float(nbytes))

    # --- adaptation ------------------------------------------------------

    def observe(self, encode_s: float, send_s: float) -> None:
        """Feed one collected segment's busy split; adapt depth with
        hysteresis. encode-bound -> shrink to ``MIN_DEPTH`` (buffering
        cannot beat a compute bottleneck, but it still hides the acks),
        send-bound -> grow (keep the wire busy)."""
        self._enc_ewma += _EWMA_ALPHA * (encode_s - self._enc_ewma)
        self._send_ewma += _EWMA_ALPHA * (send_s - self._send_ewma)
        self._since_adapt += 1
        if self._since_adapt < _ADAPT_EVERY:
            return
        self._since_adapt = 0
        if (self._send_ewma > self._enc_ewma * _ADAPT_RATIO
                and self.depth < self.max_depth):
            self.depth += 1
        elif (self._enc_ewma > self._send_ewma * _ADAPT_RATIO
                and self.depth > min(MIN_DEPTH, self.max_depth)):
            self.depth -= 1
        if self._m_depth is not None:
            self._m_depth.set(float(self.depth))

    # --- commit coalescing ----------------------------------------------

    def queue_end(self, chunk_id: int, inode: int, chunk_index: int,
                  file_length: int, status: int) -> bool:
        """Queue one chunk's end-of-write record; True = the queue hit
        the batch bound and the caller should flush now."""
        self.pending_ends.append({
            "chunk_id": chunk_id, "inode": inode,
            "chunk_index": chunk_index, "file_length": file_length,
            "status": status,
        })
        return len(self.pending_ends) >= self.commit_batch

    def drain_ends(self) -> list[dict]:
        batch, self.pending_ends = self.pending_ends, []
        return batch

    def requeue_ends(self, batch: list[dict]) -> None:
        """Put a failed flush's records back (oldest first) so a later
        flush retries them — a drained-and-dropped batch would silently
        lose ANOTHER concurrent write's commits."""
        self.pending_ends[:0] = batch

    def note_coalesced(self, batch_len: int) -> None:
        """Count round trips saved — only after the batch RPC landed
        (a requeued batch must not double-count on retry)."""
        if batch_len > 1 and self._m_coalesced is not None:
            self._m_coalesced.inc(batch_len - 1)
