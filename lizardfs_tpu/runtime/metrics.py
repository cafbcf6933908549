"""In-process time-series metrics — the charts subsystem, modernized.

The reference keeps RRD-like fixed-range in-memory series per daemon and
renders them to GIF/CSV over the admin protocol (reference:
src/common/charts.cc, chartsdata.cc registrations). Same data model
here — counters and gauges sampled into fixed-size rings at five
resolutions spanning two minutes to three months — exported as JSON over
the admin link instead of server-rendered images.

Derived series reproduce the reference's chart calc ops (reference:
src/common/charts.h:26-42 CHARTS_CALC / ADD/SUB/MIN/MAX/MUL/DIV and
charts.cc get_dataf): an RPN expression over series names and constants,
evaluated elementwise at any resolution, either ad hoc
(:meth:`Metrics.eval_rpn`) or registered by name
(:meth:`Metrics.define`) so it exports like a first-class series.
"""

from __future__ import annotations

import threading
import time
from collections import deque

# (name, sampling period s, ring length) — spans: 2 min, 3 h, 1 day,
# 1 week, 3 months (the reference's short/medium/long/verylong ranges,
# charts.cc RANGE sampling)
RESOLUTIONS = (
    ("sec", 1.0, 120),
    ("min", 60.0, 180),
    ("tenmin", 600.0, 144),
    ("hour", 3600.0, 168),
    ("day", 86400.0, 92),
)

RESOLUTION_NAMES = tuple(r[0] for r in RESOLUTIONS)

RPN_OPS = ("ADD", "SUB", "MUL", "DIV", "MIN", "MAX")


def _prom_name(name: str) -> str:
    """Series name -> valid Prometheus metric-name fragment."""
    return "".join(
        c if c.isalnum() or c == "_" else "_" for c in name
    )


def _prom_value(v: float) -> str:
    # integral values print without the trailing ".0" scrapers choke on
    # less often than one would hope
    f = float(v)
    return str(int(f)) if f.is_integer() else repr(f)


def _prom_help(text: str) -> str:
    """Escape a HELP string per exposition format 0.0.4 (backslash and
    line feed are the only escapes on HELP lines)."""
    return text.replace("\\", "\\\\").replace("\n", "\\n")


class Series:
    def __init__(self, name: str, kind: str = "counter"):
        self.name = name
        self.kind = kind  # counter: rate per tick; gauge: last value
        self.total = 0.0
        self.value = 0.0  # gauges
        self._rings = {
            rname: deque(maxlen=size) for rname, _, size in RESOLUTIONS
        }
        self._last_total = {rname: 0.0 for rname, _, _ in RESOLUTIONS}
        self._last_ts = {rname: 0.0 for rname, _, _ in RESOLUTIONS}

    def inc(self, n: float = 1.0) -> None:
        self.total += n

    def set(self, v: float) -> None:
        self.value = v

    def sample(self, now: float) -> None:
        for rname, period, _ in RESOLUTIONS:
            if now - self._last_ts[rname] >= period:
                if self.kind == "counter":
                    self._rings[rname].append(self.total - self._last_total[rname])
                    self._last_total[rname] = self.total
                else:
                    self._rings[rname].append(self.value)
                self._last_ts[rname] = now

    def to_dict(self, resolution: str = "sec") -> dict:
        return {
            "name": self.name,
            "kind": self.kind,
            "total": self.total if self.kind == "counter" else self.value,
            "resolution": resolution,
            "points": list(self._rings.get(resolution, ())),
        }


class Timing:
    """Latency histogram with log2 buckets (request_log.h scope-timing
    analog): record() costs one int_log2 + two adds; export gives
    count/sum/max plus per-bucket counts for percentile estimates.

    A nonzero ``trace_id`` passed to :meth:`record` becomes the
    histogram's EXEMPLAR — the trace of the slowest recent op — so a
    hot cell on the metrics page links straight to a ``trace-dump``
    timeline. The exemplar decays: a newer op replaces it when it is at
    least as slow, or when the stored one is older than a minute (a
    one-off spike must not pin a stale id forever)."""

    # bucket i covers [2^i, 2^(i+1)) microseconds; 20 buckets = 1us..1s+
    NBUCKETS = 20
    EXEMPLAR_TTL_S = 60.0

    __slots__ = ("name", "count", "total_us", "max_us", "buckets",
                 "exemplar_trace_id", "exemplar_us", "exemplar_ts")

    def __init__(self, name: str):
        self.name = name
        self.count = 0
        self.total_us = 0.0
        self.max_us = 0.0
        self.buckets = [0] * self.NBUCKETS
        self.exemplar_trace_id = 0
        self.exemplar_us = 0.0
        self.exemplar_ts = 0.0

    def record(self, seconds: float, trace_id: int = 0) -> None:
        us = seconds * 1e6
        self.count += 1
        self.total_us += us
        if us > self.max_us:
            self.max_us = us
        b = max(int(us), 1).bit_length() - 1
        self.buckets[min(b, self.NBUCKETS - 1)] += 1
        if trace_id:
            now = time.monotonic()
            if (
                us >= self.exemplar_us
                or now - self.exemplar_ts > self.EXEMPLAR_TTL_S
            ):
                self.exemplar_trace_id = trace_id
                self.exemplar_us = us
                self.exemplar_ts = now

    def quantile_us(self, q: float) -> float:
        """Upper-bound estimate of the q-quantile latency from the log2
        buckets (the p99 the `top` view renders). Exact to within one
        bucket (a factor of 2), which is the honest resolution a
        20-bucket histogram has."""
        if not self.count:
            return 0.0
        want = q * self.count
        cum = 0
        for i, n in enumerate(self.buckets):
            cum += n
            if cum >= want:
                return float(2 ** (i + 1))
        return self.max_us

    def to_dict(self) -> dict:
        out = {
            "name": self.name, "kind": "timing", "count": self.count,
            "avg_us": round(self.total_us / self.count, 1) if self.count
            else 0.0,
            "max_us": round(self.max_us, 1),
            "buckets_us_log2": list(self.buckets),
        }
        if self.exemplar_trace_id:
            out["exemplar_trace_id"] = f"0x{self.exemplar_trace_id:x}"
            out["exemplar_us"] = round(self.exemplar_us, 1)
        return out


# The span tree of a client op, as phase name -> the phase it nests
# in (None: a direct child of the op's root). The top level is what
# has to sum to wall, with the root's self time, in a serial
# execution; nested phases split their parent and are never summed
# with it. ``runtime/tracing.span`` charges them; doc/operations.md
# ("Write phase accounting", "Read-path microscope") says what each
# covers.
_BOUNDARY_PHASES = {
    "boundary": None,           # one call across the ChunkEncoder boundary
    "dev_stage": "boundary",    # bit matrix, np.stack
    "dev_put": "boundary",      # the two device_put calls, until they return
    "dev_run": "boundary",      # apply_gf, until it returns
    "dev_fetch": "boundary",    # np.asarray: upload, kernel, download, wake-up
    # xor parity's call (xor2..xor9 goals) and its four legs, as above,
    # under rows of their own
    "xor_boundary": None, "xor_dev_stage": "xor_boundary",
    "xor_dev_put": "xor_boundary", "xor_dev_run": "xor_boundary",
    "xor_dev_fetch": "xor_boundary",
}
WRITE_PHASES = {
    # write_file's copy of its argument, before the first RPC
    "ingest": None,
    "getattr": None, "lock": None, "grant": None, "grant_srv": "grant",
    "wait": "grant",            # a BUSY shed's backoff, inside its RPC
    "rmw_read": None,
    # a partial-stripe write's region: allocated, the read-back
    # assembled into it, the caller's bytes laid over it
    "rmw_patch": None,
    "stage": None, "throttle": None,
    "encode": None, "split": "encode",
    **{k: v or "encode" for k, v in _BOUNDARY_PHASES.items()},
    "send": None, "part": "send", "hop": "part", "part_dial": "part",
    "part_init": "part", "part_data": "part", "part_ack": "part",
    "part_end": "part",
    "ack": None, "commit": None,
    # the two gates of the windowed whole-chunk write: a segment's wait
    # for chunkserver credits and staging bytes (the reaps of older
    # segments' acks it makes meanwhile lie under it), and a chunk's
    # wait for one of write_file's two places
    "credit": None, "chunk_gate": None,
    # the read-back of a partial-stripe write, under rmw_read
    "waves": "rmw_read", "dial": "waves", "net": "waves",
    "decode": "rmw_read",
}
# What the write path counts beside its times (PhaseBreakdown.count):
# pwrite calls that read stripes back, the live bytes they asked of the
# chunkservers, the data bytes of the regions encoded and sent, and the
# bytes the callers handed pwrite (charged where the rep closes); then
# what write_file's striped chunks did: chunks the window carried to
# the end and chunks that took the whole-part sends (not eligible, or
# the window raised), segments sent and those that waited at the credit
# gate, part-segments handed over as shm-ring descriptors and sent by
# socket copy, the window's live depth summed at each segment (its
# mean is window_depth_sum / window_segments), and the window's trips
# to a worker thread (one a segment, one more for each reap a full
# ring or a shut credit gate makes on its own). Where a pwrite's or a
# write_file's chunk is acknowledged (Client._count_acked): its bytes
# by the goal's family, and its parts written through a relay chain.
WRITE_COUNTS = ("rmw_reads", "rmw_read_bytes", "rmw_region_bytes",
                "payload_bytes",
                "window_chunks", "fallback_chunks", "window_segments",
                "window_credit_waits", "ring_parts", "socket_parts",
                "window_depth_sum", "window_trips",
                "copies_payload_bytes", "xor_payload_bytes",
                "ec_payload_bytes", "chain_parts",
                # unlink calls (their rows: CallRows, below)
                "unlinks")
READ_PHASES = {
    "locate": None, "locate_srv": "locate", "wait": None, "plan": None,
    # the plan's part reads, in parallel: net and dial sum over them
    "waves": None, "dial": "waves", "net": "waves", "hop": "net",
    "decode": None,
    **{k: v or "decode" for k, v in _BOUNDARY_PHASES.items()},
    "gather": None, "copy": None,
}
# What the read path counts beside its times, where the decision is
# made: chunk ranges the one native gather served whole
# (stripe_gather_fast) and chunk ranges a read plan's waves served (no
# caller's buffer to land in, a part missing, slow or refused, a
# standard copy); 64 KiB blocks the BlockCache answered, blocks a read
# that asked it had to fetch, and blocks of reads that pass it by
# (bulk, or an inode flagged NOCACHE); the bytes read_file returned;
# lookup and get_xattr calls (their rows: CallRows, below).
READ_COUNTS = ("gather_chunks", "planned_chunks", "cache_hit_blocks",
               "cache_miss_blocks", "cache_bypass_blocks", "read_bytes",
               "lookups", "get_xattrs")


class PhaseBreakdown:
    """Per-phase busy-time accounting for a multi-phase operation (the
    client write and read paths).

    Each ``add`` charges seconds spent *inside* one phase; ``add_wall``
    closes one rep (one whole operation) with its end-to-end time and
    the root's self time, the part of it no child span covered. Phases
    form a tree (``phases``: name -> parent, None at the top level):
    in a serial execution the top-level totals plus ``self`` sum to
    the wall total; in a pipelined execution phases overlap, so the sum
    legitimately exceeds wall time — the gap IS the overlap win. A
    phase the tree does not name is kept under its name and counted
    nowhere else. ``add`` is called from worker threads too and loses
    no update. ``count`` counts what the operation did beside what it
    took (calls of a branch, bytes); the names in ``counts`` read 0
    until they are charged. ``snapshot`` returns cumulative totals,
    times as ``<phase>_ms`` and counts under their own names; subtract
    two snapshots (:func:`phase_delta`) to scope the breakdown to a
    measured interval (bench reps). :meth:`carry` lets the rows show
    counts somebody else keeps (the loop meter's, runtime/tracing.py):
    what the source has counted since, read where a snapshot is made,
    and kept as plain counts once :meth:`settle` ends it."""

    __slots__ = ("name", "phases", "totals_s", "counts", "wall_s", "self_s",
                 "reps", "_lock", "_carried")

    def __init__(self, name: str, phases, counts=()):
        self.name = name
        self.phases = (dict(phases) if isinstance(phases, dict)
                       else {p: None for p in phases})
        self.totals_s = {p: 0.0 for p in self.phases}
        self.counts = dict.fromkeys(counts, 0)
        self.wall_s = 0.0
        self.self_s = 0.0
        self.reps = 0
        self._lock = threading.Lock()
        self._carried = None    # (source, what it read when carry began)

    @property
    def top_level(self) -> tuple[str, ...]:
        return tuple(p for p, parent in self.phases.items() if parent is None)

    def add(self, phase: str, seconds: float) -> None:
        with self._lock:
            self.totals_s[phase] = self.totals_s.get(phase, 0.0) + seconds

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counts[name] = self.counts.get(name, 0) + n

    def add_wall(self, seconds: float, self_seconds: float | None = None) -> None:
        with self._lock:
            self.wall_s += seconds
            self.self_s += self_seconds or 0.0
            self.reps += 1

    def carry(self, source) -> None:
        """Show, from now on, what ``source()`` (name -> count, only
        ever rising) counts beyond what it reads now."""
        self.settle()
        self._carried = (source, source())

    def _carried_counts(self) -> dict:
        if self._carried is None:
            return {}
        source, base = self._carried
        return {name: n - base[name] for name, n in source().items()}

    def settle(self) -> None:
        """End :meth:`carry`: what was shown stays, as counts."""
        shown, self._carried = self._carried_counts(), None
        for name, n in shown.items():
            self.count(name, n)

    def snapshot(self) -> dict:
        with self._lock:
            out = {f"{p}_ms": round(v * 1e3, 2)
                   for p, v in self.totals_s.items()}
            out["self_ms"] = round(self.self_s * 1e3, 2)
            out["wall_ms"] = round(self.wall_s * 1e3, 2)
            out["reps"] = self.reps
            out.update(self.counts)
        for name, n in self._carried_counts().items():
            out[name] = out.get(name, 0) + n
        return out


class CallRows:
    """The rows of a metadata call that is an op of its own (lookup,
    get_xattr, unlink), kept on the breakdown of the side it belongs
    to: the call's root span and the master's handler time laid under
    it charge there under their own names (``unlink``, ``unlink_srv``:
    rows the side's tree does not name), and where a read or a write
    closes a rep the call counts itself (``calls``): reps, wall and
    self stay the side's own ops'."""

    __slots__ = ("add", "count", "calls")

    def __init__(self, rows: PhaseBreakdown, count, calls: str):
        self.add = rows.add
        self.count = count      # the side's own: (name, n=1)
        self.calls = calls

    def add_wall(self, seconds: float, self_seconds: float | None = None) -> None:
        self.count(self.calls)


def top_level_ms(snapshot: dict, phases: dict) -> dict:
    """The top-level phases of a snapshot (or a delta), name -> ms:
    what a "dominant phase" is chosen from, so a nested phase is never
    ranked against its own parent."""
    return {p: snapshot.get(f"{p}_ms", 0.0)
            for p, parent in phases.items() if parent is None}


def phase_delta(after: dict, before: dict) -> dict:
    """Elementwise ``after - before`` of two :meth:`PhaseBreakdown.snapshot`
    dicts (same keys): times rounded back to centi-ms, ``reps`` and the
    counts exact."""
    return {
        k: round(after[k] - before.get(k, 0), 2) if k.endswith("_ms")
        else after[k] - before.get(k, 0)
        for k in after
    }


def _label_value(v) -> str:
    """Sanitize a label value for the 0.0.4 exposition (quotes and
    backslashes would need escaping; names stay simpler without them)."""
    return "".join(
        c if c not in '"\\\n' else "_" for c in str(v)
    )


# Per-family cap on distinct label combinations: a label value drawn
# from an unbounded domain (session ids, file names) must not grow the
# registry — and the scrape page — without bound. Past the cap, new
# combinations fold into the same label NAMES with every value
# "other", so totals stay truthful while cardinality stays fixed.
LABEL_VARIANT_CAP = 256


class Metrics:
    def __init__(self):
        self.series: dict[str, Series] = {}
        self.derived: dict[str, str] = {}  # name -> RPN expression
        self.timings: dict[str, Timing] = {}
        # labeled counter families (faults_injected{site,action} style):
        # family name -> {sorted (label, value) tuple -> Series}. One
        # HELP/TYPE block per family on the Prometheus page, one sample
        # line per label combination.
        self.labeled: dict[str, dict[tuple, Series]] = {}
        # labeled Timing families (session_ops{session,op} style): one
        # HELP/TYPE histogram block per family, per-combination
        # bucket/_sum/_count samples, trace-id exemplars on +Inf
        self.labeled_timings: dict[str, dict[tuple, Timing]] = {}
        # per-series HELP text (Prometheus exposition); series without
        # an explicit entry export an auto-generated line so every
        # scraped metric carries help (the metrics-lint contract)
        self.help: dict[str, str] = {}

    def describe(self, name: str, help: str | None) -> None:
        if help:
            self.help[name] = help

    def help_for(self, name: str, kind: str = "series") -> str:
        return self.help.get(name) or f"lizardfs {kind} {name}"

    def timing(self, name: str, help: str | None = None) -> Timing:
        t = self.timings.get(name)
        if t is None:
            t = self.timings[name] = Timing(name)
        self.describe(name, help)
        return t

    def counter(self, name: str, help: str | None = None) -> Series:
        s = self.series.get(name)
        if s is None:
            s = self.series[name] = Series(name, "counter")
        self.describe(name, help)
        return s

    def gauge(self, name: str, help: str | None = None) -> Series:
        s = self.series.get(name)
        if s is None:
            s = self.series[name] = Series(name, "gauge")
        self.describe(name, help)
        return s

    @staticmethod
    def _label_key(variants: dict, labels: dict) -> tuple:
        """Sorted, sanitized (label, value) key for one combination,
        folding NEW combinations past LABEL_VARIANT_CAP into the
        all-"other" overflow bucket (same label names, bounded page)."""
        key = tuple(sorted(
            (str(k), _label_value(v)) for k, v in labels.items()
        ))
        if key not in variants and len(variants) >= LABEL_VARIANT_CAP:
            key = tuple((k, "other") for k, _ in key)
        return key

    def labeled_counter(
        self, family: str, labels: dict, help: str | None = None
    ) -> Series:
        """One Series per (family, label-set) combination, exported as a
        single Prometheus counter family with per-combination samples."""
        variants = self.labeled.setdefault(family, {})
        key = self._label_key(variants, labels)
        s = variants.get(key)
        if s is None:
            decorated = family + "{" + ",".join(
                f'{k}="{v}"' for k, v in key
            ) + "}"
            s = variants[key] = Series(decorated, "counter")
        self.describe(family, help)
        return s

    def labeled_timing(
        self, family: str, labels: dict, help: str | None = None
    ) -> Timing:
        """One :class:`Timing` per (family, label-set) combination —
        the labeled-histogram family behind per-session op accounting.
        Exports as ONE Prometheus histogram family whose per-
        combination ``_bucket``/``_sum``/``_count`` samples carry the
        labels, with the slowest recent op's trace id as an OpenMetrics
        exemplar on the ``+Inf`` bucket (so a hot cell links straight
        to ``trace-dump``). Cardinality is bounded by
        ``LABEL_VARIANT_CAP`` — overflow combinations fold into the
        all-"other" bucket."""
        variants = self.labeled_timings.setdefault(family, {})
        key = self._label_key(variants, labels)
        t = variants.get(key)
        if t is None:
            decorated = family + "{" + ",".join(
                f'{k}="{v}"' for k, v in key
            ) + "}"
            t = variants[key] = Timing(decorated)
        self.describe(family, help)
        return t

    def define(self, name: str, expr: str, help: str | None = None) -> None:
        """Register a derived series: RPN over series names/constants,
        e.g. ``"bytes_read bytes_written ADD"``. Validated eagerly by a
        full evaluation (shape errors, unknown names, nesting depth)."""
        if name in self.series:
            raise ValueError(f"{name!r} is an existing series")
        self.eval_rpn(expr)  # raises ValueError on malformed exprs
        self.derived[name] = expr
        self.describe(name, help)

    def drop_labeled(self, family: str, label: str, value) -> None:
        """Retire every variant of ``family`` (counter or timing) whose
        label set carries ``label="value"``. Departed-session cleanup:
        a long-lived master with session churn would otherwise fill the
        LABEL_VARIANT_CAP with dead variants and fold every NEW
        session into "other" — losing exactly the p99/exemplar cells
        the `top` view exists for. Prometheus handles series
        disappearing (same as a process restart)."""
        pair = (str(label), _label_value(value))
        for table in (self.labeled, self.labeled_timings):
            variants = table.get(family)
            if not variants:
                continue
            for key in [k for k in variants if pair in k]:
                del variants[key]

    def history(self, name: str, resolution: str = "sec") -> list[float]:
        """One series' retained ring at a resolution (the metrics-
        history view `top`/`health` trends render; [] for unknown
        names). Counters yield per-tick rates, gauges sampled values —
        exactly what the rings hold."""
        s = self.series.get(name)
        if s is None:
            return []
        return [float(v) for v in s._rings.get(resolution, ())]

    def sample_all(self, now: float | None = None) -> None:
        now = time.monotonic() if now is None else now
        for s in self.series.values():
            s.sample(now)
        for variants in self.labeled.values():
            for s in variants.values():
                s.sample(now)

    # --- derived-series evaluation (charts.h calc ops) -------------------

    def _parse_rpn(self, expr: str) -> list[str]:
        tokens = expr.split()
        if not tokens:
            raise ValueError("empty RPN expression")
        depth = 0
        for t in tokens:
            if t in RPN_OPS:
                if depth < 2:
                    raise ValueError(f"RPN stack underflow at {t!r}")
                depth -= 1
            else:
                if t not in self.series and t not in self.derived:
                    try:
                        float(t)
                    except ValueError:
                        raise ValueError(f"unknown series {t!r}") from None
                depth += 1
        if depth != 1:
            raise ValueError(f"RPN leaves {depth} values on the stack")
        return tokens

    def eval_rpn(self, expr: str, resolution: str = "sec",
                 _depth: int = 0) -> list[float]:
        """Evaluate an RPN expression elementwise at one resolution.

        Series are right-aligned (most recent sample last); a shorter
        operand is padded with leading zeros. DIV by zero yields 0,
        matching the reference's chart division semantics."""
        if _depth > 8:
            # catches definition cycles too (a cycle can only arise via
            # redefinition; to_dict degrades that series to an error)
            raise ValueError("derived series nested too deeply")
        # stack entries: (is_constant, points) — only true constants
        # broadcast; a series that happens to hold one sample right-
        # aligns and zero-pads like any other series
        stack: list[tuple[bool, list[float]]] = []
        for t in self._parse_rpn(expr):
            if t in RPN_OPS:
                (cb, b), (ca, a) = stack.pop(), stack.pop()
                n = max(len(a), len(b))
                a = a * n if ca and n > 1 else [0.0] * (n - len(a)) + a
                b = b * n if cb and n > 1 else [0.0] * (n - len(b)) + b
                if t == "ADD":
                    r = [x + y for x, y in zip(a, b)]
                elif t == "SUB":
                    r = [x - y for x, y in zip(a, b)]
                elif t == "MUL":
                    r = [x * y for x, y in zip(a, b)]
                elif t == "DIV":
                    r = [x / y if y else 0.0 for x, y in zip(a, b)]
                elif t == "MIN":
                    r = [min(x, y) for x, y in zip(a, b)]
                else:  # MAX
                    r = [max(x, y) for x, y in zip(a, b)]
                stack.append((ca and cb, r))
            elif t in self.series:
                stack.append(
                    (False,
                     [float(v) for v in self.series[t]._rings[resolution]])
                )
            elif t in self.derived:
                stack.append(
                    (False,
                     self.eval_rpn(self.derived[t], resolution, _depth + 1))
                )
            else:
                stack.append((True, [float(t)]))
        return stack[0][1]

    def to_prometheus(self, prefix: str = "lizardfs") -> str:
        """Prometheus text exposition (format 0.0.4) of the registry.

        Counters export as ``<prefix>_<name>_total``, gauges as
        ``<prefix>_<name>``, derived series as gauges of their most
        recent value, and :class:`Timing` histograms as native
        Prometheus histograms in microseconds: bucket i of the log2
        table covers [2^i, 2^(i+1)) us, so the cumulative ``le`` bound
        of bucket i is 2^(i+1). Served at the webui ``/metrics``
        endpoint and over the admin link (``metrics-prom``)."""
        lines: list[str] = []

        def emit(name: str, mtype: str, value, help_text: str = "",
                 suffix: str = "") -> None:
            lines.append(f"# HELP {name} {_prom_help(help_text or name)}")
            lines.append(f"# TYPE {name} {mtype}")
            lines.append(f"{name}{suffix} {_prom_value(value)}")

        for name, s in sorted(self.series.items()):
            pname = f"{prefix}_{_prom_name(name)}"
            if s.kind == "counter":
                emit(pname + "_total", "counter", s.total,
                     self.help_for(name, "counter"))
            else:
                emit(pname, "gauge", s.value, self.help_for(name, "gauge"))
        for family, variants in sorted(self.labeled.items()):
            pname = f"{prefix}_{_prom_name(family)}_total"
            lines.append(
                f"# HELP {pname} "
                f"{_prom_help(self.help_for(family, 'counter'))}"
            )
            lines.append(f"# TYPE {pname} counter")
            for key, s in sorted(variants.items()):
                suffix = "{" + ",".join(f'{k}="{v}"' for k, v in key) + "}"
                lines.append(f"{pname}{suffix} {_prom_value(s.total)}")
        for name, expr in sorted(self.derived.items()):
            pname = f"{prefix}_{_prom_name(name)}"
            try:
                points = self.eval_rpn(expr)
            except ValueError:
                continue  # a bad redefinition must not poison the page
            emit(pname, "gauge", points[-1] if points else 0.0,
                 self.help_for(name, "derived series"))
        for name, t in sorted(self.timings.items()):
            pname = f"{prefix}_timing_{_prom_name(name)}_us"
            lines.append(
                f"# HELP {pname} "
                f"{_prom_help(self.help_for(name, 'latency histogram'))}"
            )
            lines.append(f"# TYPE {pname} histogram")
            cum = 0
            for i, n in enumerate(t.buckets):
                cum += n
                lines.append(f'{pname}_bucket{{le="{2 ** (i + 1)}"}} {cum}')
            lines.append(f'{pname}_bucket{{le="+Inf"}} {t.count}')
            lines.append(f"{pname}_sum {_prom_value(t.total_us)}")
            lines.append(f"{pname}_count {t.count}")
        for family, variants in sorted(self.labeled_timings.items()):
            pname = f"{prefix}_{_prom_name(family)}_us"
            lines.append(
                f"# HELP {pname} "
                f"{_prom_help(self.help_for(family, 'latency histogram'))}"
            )
            lines.append(f"# TYPE {pname} histogram")
            for key, t in sorted(variants.items()):
                lbl = ",".join(f'{k}="{v}"' for k, v in key)
                cum = 0
                for i, n in enumerate(t.buckets):
                    cum += n
                    lines.append(
                        f'{pname}_bucket{{{lbl},le="{2 ** (i + 1)}"}} {cum}'
                    )
                inf = f'{pname}_bucket{{{lbl},le="+Inf"}} {t.count}'
                if t.exemplar_trace_id:
                    # OpenMetrics exemplar: the slowest recent op's
                    # trace id + its latency, the hot-cell -> trace-dump
                    # link (0.0.4-only scrapers may drop the suffix;
                    # metrics-lint validates the syntax)
                    inf += (
                        f' # {{trace_id="0x{t.exemplar_trace_id:x}"}} '
                        f"{_prom_value(round(t.exemplar_us, 1))}"
                    )
                lines.append(inf)
                lines.append(f"{pname}_sum{{{lbl}}} {_prom_value(t.total_us)}")
                lines.append(f"{pname}_count{{{lbl}}} {t.count}")
        return "\n".join(lines) + "\n"

    def to_dict(self, resolution: str = "sec") -> dict:
        out = {
            name: s.to_dict(resolution)
            for name, s in sorted(self.series.items())
        }
        for variants in self.labeled.values():
            for s in variants.values():
                out[s.name] = s.to_dict(resolution)
        for name, expr in sorted(self.derived.items()):
            try:
                points = self.eval_rpn(expr, resolution)
                err = None
            except ValueError as e:
                # a bad redefinition must not poison the whole export
                points, err = [], str(e)
            out[name] = {
                "name": name, "kind": "derived", "expr": expr,
                "total": points[-1] if points else 0.0,
                "resolution": resolution, "points": points,
            }
            if err is not None:
                out[name]["error"] = err
        for name, t in sorted(self.timings.items()):
            out[f"timing.{name}"] = t.to_dict()
        for variants in self.labeled_timings.values():
            for t in variants.values():
                out[f"timing.{t.name}"] = t.to_dict()
        return out
