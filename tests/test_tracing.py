"""Cross-role request tracing + Prometheus exposition.

Covers the PR-2 observability subsystem: span rings and timeline
merging (runtime/tracing.py), trailing-trace-field version skew (the
codec must serve peers that predate the field), the in-process-cluster
e2e (one write yields merged client+chunkserver+master spans), the
admin `trace-dump` command, and the Prometheus text format.
"""

import asyncio
import json

import pytest

from lizardfs_tpu.proto import framing, messages as m
from lizardfs_tpu.runtime import tracing
from lizardfs_tpu.runtime.metrics import Metrics, phase_delta

from tests.test_cluster import Cluster, EC_GOAL


# --- span ring + merge -----------------------------------------------------


def test_span_ring_records_and_bounds():
    ring = tracing.SpanRing(maxlen=4)
    for i in range(10):
        ring.record(7, f"op{i}", float(i), float(i) + 0.5, role="client")
    assert len(ring) == 4  # bounded, oldest evicted
    assert [s["name"] for s in ring.dump()] == ["op6", "op7", "op8", "op9"]
    # per-trace filter
    ring.record(9, "other", 0.0, 1.0, role="master")
    assert [s["name"] for s in ring.dump(9)] == ["other"]
    # trace id 0 never records (the disabled-path contract)
    before = len(ring)
    assert ring.record(0, "noop", 0.0, 1.0) == 0
    assert len(ring) == before


def test_trace_context_and_disable():
    tracing.clear_trace()
    assert tracing.current_trace_id() == 0
    tid = tracing.start_trace()
    assert tid != 0 and tracing.current_trace_id() == tid
    assert tracing.ensure_trace() == tid  # no new trace under an active one
    tracing.clear_trace()
    tracing.set_enabled(False)
    try:
        assert tracing.start_trace() == 0
        assert tracing.ensure_trace() == 0
    finally:
        tracing.set_enabled(True)


def test_merge_timeline_coverage():
    ring = tracing.SpanRing()
    tid = 42
    # root span = the rep wall: [0, 1.0]
    ring.record(tid, "write_file", 100.0, 101.0, role="client")
    # phase segments covering 90% of it, with overlap (union must dedupe)
    ring.record(tid, "encode", 100.0, 100.4, role="client")
    ring.record(tid, "send", 100.2, 100.7, role="client")
    ring.record(tid, "cs_write_bulk", 100.7, 100.9, role="chunkserver")
    tl = tracing.merge_timeline(ring.dump(), tid, wall_name="write_file")
    assert tl["wall_ms"] == pytest.approx(1000.0)
    assert tl["coverage_pct"] == pytest.approx(90.0)
    # root excluded from segments/by-role (it would trivially cover 100%)
    assert all(s["name"] != "write_file" for s in tl["segments"])
    assert tl["by_role_ms"]["chunkserver"] == pytest.approx(200.0)
    # client busy time sums raw durations (overlap is real concurrency)
    assert tl["by_role_ms"]["client"] == pytest.approx(900.0)
    # formatting smoke: one line per segment + header
    text = tracing.format_timeline(tl)
    assert "coverage 90.0%" in text and text.count("\n") == 3


def test_merge_timeline_empty_and_no_root():
    assert tracing.merge_timeline([], 5)["coverage_pct"] == 0.0
    spans = [{"trace_id": 3, "span_id": 1, "parent_id": 0, "role": "x",
              "name": "a", "t0": 10.0, "t1": 11.0}]
    tl = tracing.merge_timeline(spans, 3, wall_name="missing-root")
    # envelope fallback: the single span IS the wall -> full coverage
    assert tl["coverage_pct"] == pytest.approx(100.0)


# --- version skew: peers without the trailing trace field ------------------


def test_trailing_trace_field_version_skew():
    """A sender that predates ``trace_id`` still decodes (default 0);
    a frame cut inside a REQUIRED field still fails the parse."""
    msg = m.CltomaReadChunk(
        req_id=1, inode=2, chunk_index=3, uid=0, gids=[0], trace_id=77
    )
    body = msg.pack_body()
    old = body[:-8]  # exactly the pre-trace encoding
    decoded = m.CltomaReadChunk.parse(old)
    assert decoded.trace_id == 0
    assert (decoded.req_id, decoded.inode, decoded.chunk_index) == (1, 2, 3)
    # roundtrip with the field present
    assert m.CltomaReadChunk.parse(body).trace_id == 77
    # cut mid-required-field: still an error, not a zero-fill
    with pytest.raises(Exception):
        m.CltomaReadChunk.parse(old[:-2])

    # same for the data-plane WriteInit and the all-scalar WriteChunkEnd
    wi = m.CltocsWriteInit(
        req_id=1, chunk_id=9, version=1, part_id=64, chain=[], create=True,
        trace_id=55,
    )
    old_wi = wi.pack_body()[:-8]
    assert m.CltocsWriteInit.parse(old_wi).trace_id == 0
    assert m.CltocsWriteInit.parse(old_wi).create is True
    end = m.CltomaWriteChunkEnd(
        req_id=1, chunk_id=9, inode=2, chunk_index=0, file_length=10,
        status=0, trace_id=11,
    )
    old_end = end.pack_body()[:-8]
    decoded_end = m.CltomaWriteChunkEnd.parse(old_end)
    assert decoded_end.trace_id == 0 and decoded_end.file_length == 10
    # constructors may omit the optional trailing field too (call sites
    # predating the addition keep working)
    assert m.CltomaReadChunk(
        req_id=1, inode=2, chunk_index=3, uid=0, gids=[]
    ).trace_id == 0
    # the OTHER skew direction: an UNTRACED new sender elides the
    # default-valued trailing field entirely, so its encoding is
    # byte-identical to the pre-trace schema and an OLD receiver
    # (strict trailing-bytes check) still parses it
    untraced = m.CltomaReadChunk(
        req_id=1, inode=2, chunk_index=3, uid=0, gids=[0], trace_id=0
    )
    assert untraced.pack_body() == old
    assert m.CltocsWriteInit(
        req_id=1, chunk_id=9, version=1, part_id=64, chain=[], create=True,
    ).pack_body() == old_wi


def test_begin_end_scopes_trace_per_op():
    """An op that STARTED its trace clears the context on exit; two
    sequential top-level ops in one task get distinct trace ids, while
    an op under a caller-held trace joins it and leaves it in place."""
    tracing.clear_trace()
    tid1, fresh1 = tracing.begin()
    assert fresh1 and tid1 != 0
    tracing.end(fresh1)
    assert tracing.current_trace_id() == 0
    tid2, fresh2 = tracing.begin()
    tracing.end(fresh2)
    assert tid2 != tid1
    # nested: the inner op joins and must NOT clear the outer trace
    outer = tracing.start_trace()
    inner, fresh = tracing.begin()
    assert inner == outer and not fresh
    tracing.end(fresh)
    assert tracing.current_trace_id() == outer
    tracing.clear_trace()


@pytest.mark.asyncio
async def test_skewed_peer_is_served(tmp_path):
    """E2E skew: a hand-framed CltomaReadChunk WITHOUT the trailing
    trace field, sent over a real master connection, is decoded and
    answered (rolling-upgrade contract)."""
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "skew.bin")
        await c.write_file(f.inode, b"x" * 1000)

        reader, writer = await asyncio.open_connection(
            "127.0.0.1", cluster.master.port
        )
        try:
            await framing.send_message(
                writer,
                m.CltomaRegister(req_id=1, session_id=0, info="old-peer",
                                 password=""),
            )
            reply = await framing.read_message(reader)
            assert reply.status == 0
            # old-schema frame: an untraced message's pack IS the
            # pre-trace encoding (trailing defaults are elided); build
            # the exact bytes an old peer would send by packing the
            # required prefix by hand
            msg = m.CltomaReadChunk(
                req_id=2, inode=f.inode, chunk_index=0, uid=0, gids=[0],
                trace_id=77,  # pack WITH the field...
            )
            body = msg.pack_body()[:-8]  # ...then strip it: old schema
            assert body == m.CltomaReadChunk(
                req_id=2, inode=f.inode, chunk_index=0, uid=0, gids=[0],
            ).pack_body()  # untraced pack == old encoding (elision)
            frame = framing.HEADER.pack(
                m.CltomaReadChunk.MSG_TYPE, len(body) + 1
            ) + bytes([framing.PROTO_VERSION]) + body
            writer.write(frame)
            await writer.drain()
            reply = await asyncio.wait_for(framing.read_message(reader), 10)
            assert isinstance(reply, m.MatoclReadChunk)
            assert reply.status == 0 and reply.file_length == 1000
        finally:
            writer.close()
    finally:
        await cluster.stop()

# --- the span primitive: parents, self time, sink, annotator ---------------


def _sink():
    from lizardfs_tpu.runtime.metrics import PhaseBreakdown

    ph = PhaseBreakdown("t", {"a": None, "b": None, "c": "a"})
    ring = tracing.SpanRing()
    return tracing.OpSink(ph, ring, "client"), ph, ring


def _by_name(ring):
    out = {}
    for s in ring.dump():
        out.setdefault(s["name"], []).append(s)
    return out


def _busy(seconds: float) -> None:
    import time
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_span_parents_and_self_time_serial_and_nested():
    tracing.clear_trace()
    sink, ph, ring = _sink()
    with tracing.span("op", sink=sink, bytes=7) as root:
        with tracing.span("a", phase="a", bucket="net"):
            _busy(0.004)
            with tracing.span("c", phase="c", bucket="compute"):
                _busy(0.003)
        with tracing.span("b", phase="b", bucket="queue"):
            _busy(0.002)
        _busy(0.002)  # nobody's but the root's
    assert tracing.current_trace_id() == 0, "a root's own trace ends with it"
    spans = _by_name(ring)
    op, a, b, c = (spans[n][0] for n in ("op", "a", "b", "c"))
    assert op["parent_id"] == 0 and op["span_id"] == root.span_id
    assert a["parent_id"] == b["parent_id"] == op["span_id"]
    assert c["parent_id"] == a["span_id"]
    assert len({s["trace_id"] for s in ring.dump()}) == 1
    assert (a["bucket"], b["bucket"], c["bucket"]) == (
        "net", "queue", "compute")
    assert op["attrs"] == {"bytes": 7}
    dur = lambda s: (s["t1"] - s["t0"]) * 1e3  # noqa: E731
    # self time: duration less what the children cover
    assert a["self_ms"] == pytest.approx(dur(a) - dur(c), abs=0.05)
    assert c["self_ms"] == pytest.approx(dur(c), abs=0.01)
    assert op["self_ms"] == pytest.approx(dur(op) - dur(a) - dur(b), abs=0.05)
    assert op["self_ms"] >= 1.9
    # phase rows: each span charged once; the root closed one rep with
    # its wall and self time, and the top level sums to wall with it
    snap = ph.snapshot()
    assert snap["reps"] == 1
    assert snap["a_ms"] == pytest.approx(dur(a), abs=0.05)
    assert snap["c_ms"] == pytest.approx(dur(c), abs=0.05)
    assert snap["wall_ms"] == pytest.approx(dur(op), abs=0.05)
    assert snap["self_ms"] == pytest.approx(op["self_ms"], abs=0.02)
    top = sum(snap[f"{p}_ms"] for p in ph.top_level)
    assert ph.top_level == ("a", "b")
    assert top + snap["self_ms"] == pytest.approx(snap["wall_ms"], rel=0.02)


@pytest.mark.asyncio
async def test_span_parallel_children_count_once():
    """Children that overlap (a gather) cover their union of the
    parent once: its self time never goes negative, and its phase row
    holds its own duration, not the children's sum."""
    tracing.clear_trace()
    sink, ph, ring = _sink()

    async def child(delay):
        with tracing.span("c", phase="c"):
            await asyncio.sleep(delay)

    with tracing.span("op", sink=sink):
        with tracing.span("a", phase="a"):
            await asyncio.gather(child(0.02), child(0.03), child(0.03))
    spans = _by_name(ring)
    a = spans["a"][0]
    assert len(spans["c"]) == 3
    assert all(c["parent_id"] == a["span_id"] for c in spans["c"])
    dur_a = (a["t1"] - a["t0"]) * 1e3
    summed = sum((c["t1"] - c["t0"]) * 1e3 for c in spans["c"])
    assert summed > dur_a * 2  # busy time legitimately passes wall
    assert 0.0 <= a["self_ms"] < dur_a * 0.5
    snap = ph.snapshot()
    assert snap["c_ms"] == pytest.approx(summed, abs=0.1)
    assert snap["a_ms"] == pytest.approx(dur_a, abs=0.1)


@pytest.mark.asyncio
async def test_span_children_in_to_thread_and_executor_thread():
    """to_thread copies the context; an executor hop carries the open
    span and the sink by hand (native_io.partial_with_trace) and turns
    the wait for the thread into a ``hop`` span of its own."""
    from lizardfs_tpu.core import native_io

    tracing.clear_trace()
    sink, ph, ring = _sink()

    def work(name):
        with tracing.span(name, phase="c"):
            _busy(0.002)
        return tracing.current_trace_id()

    with tracing.span("op", sink=sink) as root:
        with tracing.span("a", phase="a"):
            tid_thread = await asyncio.to_thread(work, "in_to_thread")
        with tracing.span("b", phase="b"):
            tid_exec = await asyncio.get_running_loop().run_in_executor(
                native_io.EXECUTOR,
                native_io.partial_with_trace(work, "in_executor"),
            )
            # what the pool thread was given it gave back
            assert await asyncio.get_running_loop().run_in_executor(
                native_io.EXECUTOR, tracing.current_trace_id) == 0
    assert tid_thread == tid_exec == root.trace_id != 0
    spans = _by_name(ring)
    assert spans["in_to_thread"][0]["parent_id"] == spans["a"][0]["span_id"]
    assert spans["in_executor"][0]["parent_id"] == spans["b"][0]["span_id"]
    hop = spans["hop"][0]
    assert hop["parent_id"] == spans["b"][0]["span_id"]
    assert hop["bucket"] == "queue"
    assert hop["t1"] <= spans["in_executor"][0]["t0"] + 1e-3
    snap = ph.snapshot()
    assert snap["c_ms"] >= 3.9 and snap["hop_ms"] >= 0.0  # both charged


def test_span_without_an_op_charges_nothing_and_records_nothing():
    tracing.clear_trace()
    with tracing.span("dial", phase="dial") as sp:
        pass
    assert sp.span_id == 0 and tracing.current_trace_id() == 0


def test_span_begin_end_pair_and_retroactive_open():
    import time

    tracing.clear_trace()
    sink, ph, ring = _sink()
    root = tracing.span("op", sink=sink).begin()
    t_wait = time.perf_counter()
    _busy(0.003)
    # a wait known only once it is over opens in the past
    tracing.span("b", phase="b", bucket="queue").begin(at=t_wait).end()
    root.end()
    b = _by_name(ring)["b"][0]
    assert (b["t1"] - b["t0"]) * 1e3 >= 2.9
    assert ph.snapshot()["b_ms"] >= 2.9
    assert _by_name(ring)["op"][0]["self_ms"] < 1.0


def test_annotator_called_only_when_registered(monkeypatch):
    """No profiler name is built while no process has registered the
    annotation; once one has, every span opens ``lz.<layer>.<name>``
    with its attributes, an op root adding ``t_ns`` (its opening on
    time.time_ns()'s clock), and closes it."""
    import time

    tracing.clear_trace()
    calls = []

    class Ann:
        def __init__(self, name, **meta):
            calls.append(["new", name, meta])

        def __enter__(self):
            calls.append(["enter"])

        def __exit__(self, *exc):
            calls.append(["exit"])

    sink, _ph, _ring = _sink()
    monkeypatch.setattr(tracing, "_ANNOTATE", None)
    with tracing.span("op", sink=sink):
        with tracing.span("a", layer="encoder", phase="a", k=3):
            pass
    assert calls == []
    tracing.register_annotator(Ann)
    t_before = time.time_ns()
    with tracing.span("op", sink=sink, bytes=1):
        with tracing.span("a", layer="encoder", phase="a", k=3):
            pass
    news = [c for c in calls if c[0] == "new"]
    assert [c[1] for c in news] == ["lz.client.op", "lz.encoder.a"]
    assert news[1][2] == {"k": 3}
    assert news[0][2]["bytes"] == 1
    assert abs(news[0][2]["t_ns"] - t_before) < 50_000_000
    assert [c[0] for c in calls].count("enter") == 2
    assert [c[0] for c in calls].count("exit") == 2
    # a span outside any op opens none (it has no trace to belong to)
    del calls[:]
    with tracing.span("dial", phase="dial"):
        pass
    assert calls == []
    # registered, but no profiler session is live: one check, no name
    # built, nothing opened (what an untraced run pays)
    live = []
    tracing.register_annotator(Ann, lambda: bool(live))
    with tracing.span("op", sink=sink):
        pass
    assert calls == []
    live.append(1)
    with tracing.span("op", sink=sink):
        pass
    assert [c[0] for c in calls] == ["new", "enter", "exit"]


def test_lz_trace_off_leaves_ring_empty_and_phase_rows_charged():
    tracing.clear_trace()
    sink, ph, ring = _sink()
    tracing.set_enabled(False)
    try:
        with tracing.span("op", sink=sink) as root:
            with tracing.span("a", phase="a"):
                _busy(0.002)
            assert tracing.current_trace_id() == 0
            assert tracing.PHASE_SINK.get() is sink
    finally:
        tracing.set_enabled(True)
    assert tracing.PHASE_SINK.get() is None
    assert len(ring) == 0 and root.span_id == 0 and root.trace_id == 0
    snap = ph.snapshot()
    assert snap["reps"] == 1 and snap["a_ms"] >= 1.9
    assert snap["wall_ms"] >= snap["a_ms"] and snap["self_ms"] == 0.0


def test_ids_come_from_one_seeded_counter():
    import lizardfs_tpu.runtime.tracing as mod

    assert not hasattr(mod, "secrets")
    ids = [tracing.new_id() for _ in range(1000)]
    assert len(set(ids)) == 1000 and all(0 < i < 2**63 for i in ids)
    assert ids == sorted(ids) or ids[0] > ids[-1]  # a counter (may wrap)


def test_phase_charges_from_worker_threads_lose_no_update():
    """PhaseBreakdown.add is a read-modify-write reached from the loop,
    to_thread workers and the native-io pool at once."""
    import sys
    import threading

    from lizardfs_tpu.runtime.metrics import PhaseBreakdown

    ph = PhaseBreakdown("t", ("a",))
    n_threads, n_adds = 16, 4000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(n_adds):
                ph.add("a", 1.0)
                ph.add("late", 1.0)  # a phase the tree does not name
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert ph.totals_s["a"] == n_threads * n_adds
    assert ph.totals_s["late"] == n_threads * n_adds


def test_srv_us_version_skew():
    """``srv_us`` trails the grant and locate replies: a master that
    predates it is decoded (0: the client charges nothing), a reply
    that carries none is byte-identical to the old encoding, and a cut
    inside a required field still fails."""
    for cls, extra in ((m.MatoclWriteChunk, {}),
                       (m.MatoclReadChunk, {"meta_version": 9})):
        fields = dict(req_id=1, status=0, chunk_id=5, version=2,
                      file_length=10, locations=[], **extra)
        body = cls(srv_us=1234, **fields).pack_body()
        assert cls.parse(body).srv_us == 1234
        old = body[:-4]  # exactly the encoding before the field
        decoded = cls.parse(old)
        assert decoded.srv_us == 0 and decoded.chunk_id == 5
        assert decoded.file_length == 10
        assert cls(**fields).pack_body() == old
        with pytest.raises(Exception):
            cls.parse(body[:20])  # cut inside file_length: no zero-fill


@pytest.mark.parametrize("cls,fields,where,width", [
    (m.MatoclStatusReply, dict(req_id=1, status=0, meta_version=9,
                               retry_after_ms=0), None, 4),
    (m.MatoclXattrReply, dict(req_id=1, status=0, value=b"etag"), None, 4),
    (m.MatoclAttrReply, dict(req_id=1, status=0), "attr", 4),
], ids=lambda v: getattr(v, "__name__", None))
def test_srv_us_trails_the_metadata_replies(cls, fields, where, width):
    """``srv_us`` also trails the status reply (unlink), the xattr
    reply and, as the consistency token does, the Attr that ends an
    attr reply (lookup): a master that predates it is decoded as 0, and
    a reply that carries none is the encoding before the field."""
    attr = dict(inode=7, ftype=1, mode=0o644, uid=0, gid=0, atime=1, mtime=2,
                ctime=3, nlink=1, length=10, goal=1, trash_time=0, eattr=0,
                meta_version=9)

    def make(srv_us):
        if where:
            return cls(attr=m.Attr(srv_us=srv_us, **attr), **fields)
        return cls(srv_us=srv_us, **fields)

    def stamp(msg):
        return getattr(msg, where).srv_us if where else msg.srv_us

    body = make(1234).pack_body()
    assert stamp(cls.parse(body)) == 1234
    old = body[:-width]
    decoded = cls.parse(old)
    assert stamp(decoded) == 0 and decoded.req_id == 1
    if where:
        assert decoded.attr.length == 10 and decoded.attr.meta_version == 9
    plain = make(0).pack_body()
    assert old.startswith(plain) and cls.parse(plain).req_id == 1


@pytest.mark.asyncio
async def test_metadata_calls_are_ops_with_the_handler_inside(tmp_path):
    """``lookup``, ``get_xattr`` and ``unlink`` called as ops of their
    own are root spans with the RPC and the master's stamped handler
    time under them; their rows land on the read side (lookup,
    get_xattr) and the write side (unlink) under their own names, they
    count themselves, and close no rep there. Inside another op a call
    is a plain span of that op. The master counts the delete commands
    an unlink of a file with trash time 0 leaves to its holders."""
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start(health_interval=0.2)
    try:
        c = await cluster.client()
        f = await c.create(1, "obj")
        await c.settrashtime(f.inode, 0)
        await c.pwrite(f.inode, 0, b"x" * 1000)
        await c.set_xattr(f.inode, "user.etag", b"abc")
        w0, r0 = c.write_phases.snapshot(), c.read_phases.snapshot()
        c.trace_ring.clear()
        attr = await c.lookup(1, "obj")
        assert await c.get_xattr(attr.inode, "user.etag") == b"abc"
        await c.unlink(1, "obj")
        w = phase_delta(c.write_phases.snapshot(), w0)
        r = phase_delta(c.read_phases.snapshot(), r0)
        assert (r["lookups"], r["get_xattrs"], w["unlinks"]) == (1, 1, 1)
        assert r["reps"] == w["reps"] == 0 and r["wall_ms"] == w["wall_ms"] == 0
        assert 0 < r["lookup_srv_ms"] <= r["lookup_ms"]
        assert 0 < r["get_xattr_srv_ms"] <= r["get_xattr_ms"]
        assert 0 < w["unlink_srv_ms"] <= w["unlink_ms"]
        assert c.op_counters["unlinks"] == c.op_counters["lookups"] == 1
        spans = c.trace_ring.dump()
        by_name = {s["name"]: s for s in spans}
        for call, rpc in (("lookup", "CltomaLookup"),
                          ("get_xattr", "CltomaGetXattr"),
                          ("unlink", "CltomaUnlink")):
            root = by_name[call]
            assert root["parent_id"] == 0 and root["attrs"]["srv_us"] >= 1
            assert by_name[rpc]["parent_id"] == root["span_id"]
            srv = by_name[call + "_srv"]
            assert srv["parent_id"] == root["span_id"]
            assert root["t0"] <= srv["t0"] and srv["t1"] <= root["t1"] + 1e-4
        assert len({by_name[n]["trace_id"]
                    for n in ("lookup", "get_xattr", "unlink")}) == 3
        # inside another op: a span of that op, no root, no call counted
        c.trace_ring.clear()
        with tracing.span("read_file", sink=c._read_op):
            with pytest.raises(Exception):
                await c.lookup(1, "obj")    # gone: the span still closes
        inner = {s["name"]: s for s in c.trace_ring.dump()}
        assert inner["lookup"]["parent_id"] == inner["read_file"]["span_id"]
        assert c.read_phases.snapshot()["lookups"] == r0["lookups"] + 1
        # the master's side of the unlink: one command a holder of a part
        for _ in range(100):
            sent = cluster.master.metrics.series.get("chunk_deletes_sent")
            if sent is not None and sent.total:
                break
            await asyncio.sleep(0.05)
        assert sent.total == 1          # goal 1: one copy, one holder
        assert cluster.master.metrics.series[
            "chunk_deletes_pending"].value == 0
    finally:
        await cluster.stop()


def test_content_gen_version_skew():
    """``content_gen`` trails the locate reply behind ``srv_us``: a
    master that predates it is decoded (0: the client's tag is then the
    chunk's id and version alone), a reply that carries none is
    byte-identical to the encoding before the field, and a cut inside
    the field still fails."""
    fields = dict(req_id=1, status=0, chunk_id=5, version=2,
                  file_length=10, locations=[], meta_version=9, srv_us=77)
    body = m.MatoclReadChunk(content_gen=41, **fields).pack_body()
    assert m.MatoclReadChunk.parse(body).content_gen == 41
    old = body[:-8]  # exactly the encoding before the field
    decoded = m.MatoclReadChunk.parse(old)
    assert decoded.content_gen == 0 and decoded.srv_us == 77
    assert decoded.meta_version == 9 and decoded.chunk_id == 5
    assert m.MatoclReadChunk(**fields).pack_body() == old
    # a master that predates both trailing fields
    assert m.MatoclReadChunk.parse(body[:-12]).content_gen == 0
    with pytest.raises(Exception):
        m.MatoclReadChunk.parse(body[:-3])  # cut inside the field

    from lizardfs_tpu.client.client import Client

    assert Client._chunk_tag(decoded) == (5, 2, 0)
    assert Client._chunk_tag(m.MatoclReadChunk.parse(body)) == (5, 2, 41)


@pytest.mark.asyncio
async def test_master_without_srv_us_is_served(tmp_path, monkeypatch):
    """E2E skew: against a master that stamps no ``srv_us`` (one that
    predates it) a traced pwrite and read work, charge no server
    phase, and still have their grant / locate spans; against this
    master the same ops carry the stamp inside those spans."""
    from lizardfs_tpu.master.server import MasterServer

    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "srv.bin")
        monkeypatch.setattr(
            MasterServer, "_stamp_srv", staticmethod(lambda reply, dt: None))
        await c.pwrite(f.inode, 0, b"x" * 1000)
        assert await c.read_file(f.inode, 0, 1000) == b"x" * 1000
        w, r = c.write_phases.snapshot(), c.read_phases.snapshot()
        assert w["grant_ms"] > 0 and w["grant_srv_ms"] == 0
        assert r["locate_ms"] > 0 and r["locate_srv_ms"] == 0
        names = {s["name"] for s in c.trace_ring.dump()}
        assert {"grant", "locate"} <= names
        assert not {"grant_srv", "locate_srv"} & names
        monkeypatch.undo()
        c.trace_ring.clear()
        await c.pwrite(f.inode, 0, b"y" * 1000)
        c._locate_cache.clear()
        assert await c.read_file(f.inode, 0, 1000) == b"y" * 1000
        w, r = c.write_phases.snapshot(), c.read_phases.snapshot()
        assert 0 < w["grant_srv_ms"] <= w["grant_ms"]
        assert 0 < r["locate_srv_ms"] <= r["locate_ms"]
        spans = {s["name"]: s for s in c.trace_ring.dump()}
        assert spans["grant"]["attrs"]["srv_us"] >= 1
        assert spans["grant_srv"]["parent_id"] == spans["grant"]["span_id"]
        assert spans["grant"]["t0"] <= spans["grant_srv"]["t0"]
        assert spans["grant_srv"]["t1"] <= spans["grant"]["t1"] + 1e-4
        assert spans["locate_srv"]["parent_id"] == spans["locate"]["span_id"]
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_traced_pwrite_is_one_tree_that_attributes(tmp_path):
    """A merged timeline of one traced pwrite (the client's ring and
    every daemon's) has every span but the root carrying a parent that
    is in the timeline, and its attribution leaves little of the wall
    to no bucket."""
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "tree.bin")
        await c.setgoal(f.inode, EC_GOAL)
        payload = b"p" * (3 * 2**20)
        await c.pwrite(f.inode, 0, payload)  # chunk made, pools warm
        c.trace_ring.clear()
        tid = tracing.start_trace()
        try:
            await c.pwrite(f.inode, len(payload), payload)
        finally:
            tracing.clear_trace()
        client_spans = c.trace_ring.dump(tid)
        ids = {s["span_id"] for s in client_spans}
        roots = [s for s in client_spans if s["parent_id"] not in ids]
        assert [s["name"] for s in roots] == ["pwrite"]
        assert roots[0]["parent_id"] == 0
        names = {s["name"] for s in client_spans}
        assert {"getattr", "grant", "encode", "split", "send",
                "part", "commit", "CltomaWriteChunk"} <= names
        spans = list(client_spans) + cluster.master.trace_spans(tid)
        for cs in cluster.chunkservers:
            spans += cs.trace_spans(tid)
        tl = tracing.merge_timeline(spans, tid, wall_name="pwrite")
        seg_ids = {s["span_id"] for s in tl["segments"]} | {
            roots[0]["span_id"]}
        client_segs = [s for s in tl["segments"] if s["role"] == "client"]
        assert client_segs and all(
            s["parent_id"] in seg_ids for s in client_segs)
        assert {s["role"] for s in tl["segments"]} >= {
            "client", "master", "chunkserver"}
        attr = tracing.attribute_timeline(tl)
        # under 10 % on a quiet box and on the chip (PERF.md); a 20 ms
        # op beside five other test workers gets room for the loop's
        # scheduling delays, which are the root's self time
        assert attr["pct"]["unattributed"] < 25.0, attr
        assert sum(attr["buckets_ms"].values()) == pytest.approx(
            attr["wall_ms"], abs=0.01)
    finally:
        await cluster.stop()



# --- e2e: one write yields a merged cross-role trace -----------------------


@pytest.mark.asyncio
async def test_traced_write_merges_across_roles(tmp_path):
    cluster = Cluster(tmp_path, n_cs=6)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "traced.bin")
        await c.setgoal(f.inode, EC_GOAL)  # ec(3,2): striped data plane
        tid = tracing.start_trace()
        try:
            # >= native threshold so the native data plane (when built)
            # records per-op receive/disk timestamps too
            await c.write_file(f.inode, b"t" * (9 * 2**20))
        finally:
            tracing.clear_trace()
        spans = list(c.trace_ring.dump(tid))
        spans += cluster.master.trace_spans(tid)
        for cs in cluster.chunkservers:
            spans += cs.trace_spans(tid)
        roles = {s["role"] for s in spans}
        assert {"client", "chunkserver", "master"} <= roles, roles
        names = {s["name"] for s in spans}
        assert "write_file" in names  # the rep's wall/root span
        assert "CltomaWriteChunk" in names  # master grant under the trace
        tl = tracing.merge_timeline(spans, tid, wall_name="write_file")
        assert tl["wall_ms"] > 0
        # require substantial attribution despite CI load
        assert tl["coverage_pct"] >= 50.0, tl
        assert set(tl["by_role_ms"]) >= {"client", "chunkserver"}
    finally:
        await cluster.stop()


@pytest.mark.asyncio
async def test_admin_trace_dump_and_metrics_prom(tmp_path):
    """`lizardfs-admin trace-dump` + `metrics-prom` over the admin link
    on both master and chunkserver ports."""
    cluster = Cluster(tmp_path, n_cs=3)
    await cluster.start()
    try:
        c = await cluster.client()
        f = await c.create(1, "dump.bin")
        tid = tracing.start_trace()
        try:
            await c.write_file(f.inode, b"d" * 300_000)
        finally:
            tracing.clear_trace()

        async def admin(port, command, payload="{}"):
            r, w = await asyncio.open_connection("127.0.0.1", port)
            await framing.send_message(
                w, m.AdminCommand(req_id=1, command=command, json=payload)
            )
            reply = await framing.read_message(r)
            w.close()
            return reply

        reply = await admin(
            cluster.master.port, "trace-dump",
            json.dumps({"trace_id": tid}),
        )
        assert reply.status == 0
        spans = json.loads(reply.json)["spans"]
        assert spans and all(s["trace_id"] == tid for s in spans)
        assert all(s["role"] == "master" for s in spans)
        # bad trace id -> EINVAL, not a crash
        reply = await admin(
            cluster.master.port, "trace-dump", json.dumps({"trace_id": "x"})
        )
        assert reply.status != 0

        for port in (cluster.master.port, cluster.chunkservers[0].port):
            reply = await admin(port, "metrics-prom")
            assert reply.status == 0
            text = json.loads(reply.json)["text"]
            _validate_prometheus(text)
    finally:
        await cluster.stop()


# --- prometheus text format ------------------------------------------------


def _validate_prometheus(text: str) -> None:
    """Structural validation of exposition-format 0.0.4 text."""
    assert text.endswith("\n")
    seen_types = {}
    seen_help = set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            name, _, help_text = line[len("# HELP "):].partition(" ")
            assert help_text, f"empty HELP for {name}"
            seen_help.add(name)
            continue
        if line.startswith("# TYPE "):
            _, _, name, mtype = line.split(" ")
            assert mtype in ("counter", "gauge", "histogram")
            # HELP precedes TYPE for every series (metrics-lint rule)
            assert name in seen_help, f"TYPE without HELP: {name}"
            seen_types[name] = mtype
            continue
        assert not line.startswith("#")
        name_part, _, value = line.rpartition(" ")
        float(value)  # parseable sample value
        base = name_part.split("{")[0]
        assert base[0].isalpha()
        assert all(ch.isalnum() or ch in "_:" for ch in base)
    assert seen_types, "no TYPE lines"


def test_prometheus_exposition_format():
    mt = Metrics()
    mt.counter("bytes_read").inc(1000)
    mt.gauge("loop_lag_ms").set(1.5)
    mt.counter("ops.read").inc(3)  # dots must sanitize
    mt.sample_all(1.0)
    mt.define("total", "bytes_read 2 MUL")
    t = mt.timing("CltomaCreate")
    for us in (1, 3, 100, 5000, 5000, 2_000_000):
        t.record(us / 1e6)
    text = mt.to_prometheus()
    _validate_prometheus(text)
    assert "lizardfs_bytes_read_total 1000" in text
    assert "lizardfs_loop_lag_ms 1.5" in text
    assert "lizardfs_ops_read_total 3" in text  # sanitized name
    # derived series export as gauges of their latest value
    assert "lizardfs_total 2000" in text
    # histogram: cumulative monotone buckets, +Inf == count, sum/count
    lines = [l for l in text.splitlines()
             if l.startswith("lizardfs_timing_CltomaCreate_us")]
    buckets = [l for l in lines if "_bucket{" in l]
    counts = [int(l.rpartition(" ")[2]) for l in buckets]
    assert counts == sorted(counts)
    assert buckets[-1].startswith(
        'lizardfs_timing_CltomaCreate_us_bucket{le="+Inf"}'
    )
    assert counts[-1] == 6
    assert any(l.startswith("lizardfs_timing_CltomaCreate_us_sum") for l in lines)
    assert "lizardfs_timing_CltomaCreate_us_count 6" in lines
    # bucket i covers [2^i, 2^(i+1)) us -> a 3 us sample lands in le="4"
    le4 = next(l for l in buckets if 'le="4"' in l)
    assert int(le4.rpartition(" ")[2]) == 2  # the 1us + 3us samples
