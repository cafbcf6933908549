"""``telemetry-coverage``: every client-facing verb maps to a trace
span, an SLO class (or a reasoned waiver), a fault choke point, and
metrics — statically.

PRs 2/3/8/10 built the conventions one at a time: the master RPC loop
traces + times every dispatched op, the chunkserver data plane charges
read/write spans and objectives, the NFS and S3 gateways begin a span
and observe their own SLO class at ONE dispatch boundary, and the fault
engine's frame choke points cover every proto message generically. Each
new verb since then was hand-audited against that matrix at review
time. This checker turns the audit into a standing gate:

* **the verb inventory is total** — every client-facing catalog class
  (``Cltoma*`` master RPCs, ``Cltocs*`` data-plane requests) must have
  an inventory entry below, and every entry must still name a catalog
  class. Adding a verb without deciding its telemetry story fails lint.
* **SLO mapping is real** — an entry either names a class from
  ``runtime/slo.py``'s ``OP_CLASSES`` (and the verb's handler file must
  actually ``observe`` that class) or carries a waiver REASON saying
  why the verb has no latency objective.
* **the fault path exists** — each verb's choke point must be an
  inventoried ``runtime/faults.py`` site whose implementing file really
  consults it (a renamed site string otherwise leaves the verb
  undrillable while the inventory still claims coverage).
* **the generic instruments stand** — the per-surface span/metric
  anchors (master per-op timing + span record, chunkserver op spans,
  gateway boundary spans) must exist in the handler sources; deleting
  or renaming one fails here, not in a post-incident review.
* **no dead objectives** — every ``OP_CLASSES`` entry must be observed
  by at least one surface (a class nobody feeds burns no rate yet
  still reads "healthy" on dashboards).
"""

from __future__ import annotations

import ast
import os
import re

from lizardfs_tpu.tools.lint.engine import Finding

RULE = "telemetry-coverage"

# ---- surfaces --------------------------------------------------------------
MASTER = "lizardfs_tpu/master/server.py"
CS = "lizardfs_tpu/chunkserver/server.py"
NFS = "lizardfs_tpu/nfs/server.py"
S3 = "lizardfs_tpu/s3/server.py"
FRAMING = "lizardfs_tpu/proto/framing.py"

# fault site -> the file that consults it (runtime/faults.py names the
# site; the implementing file must pass the literal to the engine)
SITE_IMPL = {
    "frame_send": FRAMING,
    "frame_recv": FRAMING,
    "disk_pread": "lizardfs_tpu/chunkserver/chunk_store.py",
    "disk_pwrite": "lizardfs_tpu/chunkserver/chunk_store.py",
    # every dialer (pool, RPC links, client data plane) funnels through
    # faults.dial_point — the literal lives with the choke point
    "dial": "lizardfs_tpu/runtime/faults.py",
    "serve_read": CS,
    "http_recv": S3,
    "http_send": S3,
}

# ---- the verb inventory ----------------------------------------------------
# verb -> SLO class its handler surface must observe
SLO_CLASSES = {
    # chunk grant / commit RPCs are the master's latency-critical class
    "CltomaReadChunk": "locate",
    "CltomaWriteChunk": "locate",
    "CltomaWriteChunkEnd": "locate",
    "CltomaWriteChunkEndBatch": "locate",
    # data plane: the chunkserver charges read/write objectives
    "CltocsRead": "read",
    "CltocsReadBulk": "read",
    "CltocsWriteData": "write",
    "CltocsWriteBulk": "write",
    "CltocsWriteBulkPart": "write",
    "CltocsShmWritePart": "write",
    "CltocsWriteEnd": "write",
    "CltocsWriteInit": "write",
}

_META = (
    "namespace metadata RPC — per-op latency histogram + master span "
    "cover it; the latency objective rides the locate class (chunk "
    "grants) by design, metadata breaches surface via the per-op "
    "timings and the health rollup"
)
_SESSION = (
    "session/control RPC — fires once per mount or failover, not on "
    "the request path; per-op timing + trace span only"
)
_ADMIN = (
    "operator/introspection verb — human-paced, budget-bounded "
    "server-side; per-op timing + trace span only"
)
_TAPE = (
    "tape-tier verb — latency is dominated by the archival backend and "
    "bounded by the caller's deadline; recall progress is tracked via "
    "tape_* health counts, not a latency objective"
)

# verb -> why it carries NO latency objective (the reason is the
# waiver; an empty reason fails lint)
SLO_WAIVERS = {
    **{v: _META for v in (
        "CltomaLookup", "CltomaGetattr", "CltomaMkdir", "CltomaCreate",
        "CltomaReaddir", "CltomaUnlink", "CltomaRmdir", "CltomaRename",
        "CltomaSetGoal", "CltomaSetEattr", "CltomaTruncate",
        "CltomaSetattr", "CltomaSymlink", "CltomaReadlink", "CltomaLink",
        "CltomaSnapshot", "CltomaSetXattr", "CltomaGetXattr",
        "CltomaListXattr", "CltomaStatFs", "CltomaAccess",
        "CltomaSetAcl", "CltomaGetAcl", "CltomaSetRichAcl",
        "CltomaGetRichAcl", "CltomaLockOp", "CltomaOpen", "CltomaRelease",
        "CltomaSetQuota", "CltomaGetQuota", "CltomaAppendChunks",
    )},
    **{v: _SESSION for v in (
        "CltomaRegister", "CltomaGoodbye", "CltomaIoLimitRequest",
    )},
    "CltomaSessionStats": (
        "periodic best-effort workload-summary push (gateway -> "
        "master, ~1/5s) feeding the `top` rollup — telemetry about "
        "telemetry; per-op timing + master span cover it"
    ),
    **{v: _ADMIN for v in (
        "CltomaTrashList", "CltomaUndelete", "CltomaFileRepair",
        "CltomaChunkDamaged",
    )},
    **{v: _TAPE for v in (
        "CltomaTapeInfo", "CltomaTapeDemote", "CltomaTapeRecall",
    )},
    "CltocsPrefetch": (
        "fire-and-forget page-cache hint with no reply frame — there "
        "is no completion to time"
    ),
    "CltocsShmInit": (
        "one-shot ring negotiation per (client, chunkserver) pair, "
        "acked via CstoclWriteStatus; not a data op"
    ),
}

# per-verb fault choke point (default: the frame plane covers every
# proto message at recv time)
VERB_SITES = {
    "CltocsRead": "serve_read",
    "CltocsReadBulk": "serve_read",
    "CltocsWriteData": "disk_pwrite",
    "CltocsWriteBulk": "disk_pwrite",
    "CltocsWriteBulkPart": "disk_pwrite",
    "CltocsShmWritePart": "disk_pwrite",
}
DEFAULT_SITE = "frame_recv"

# generic per-surface instruments: (file, regex, what broke if absent)
DAEMON = "lizardfs_tpu/runtime/daemon.py"
CLIENT = "lizardfs_tpu/client/client.py"
HEAT = "lizardfs_tpu/master/heat.py"
ELECTION = "lizardfs_tpu/ha/election.py"
SLO = "lizardfs_tpu/runtime/slo.py"
TRACING = "lizardfs_tpu/runtime/tracing.py"
NATIVE_SERVE = "lizardfs_tpu/chunkserver/native_serve.py"
ANCHORS = (
    (MASTER, r"metrics\.timing\(type\(msg\)\.__name__\)",
     "master per-op latency histograms (request_log analog)"),
    (MASTER, r"trace_ring\.record\(", "master RPC span recording"),
    (CS, r"trace_ring\.record\(", "chunkserver op span recording"),
    (CS, r"slo\.observe\(", "chunkserver data-plane SLO accounting"),
    (NFS, r"tracing\.begin\(\)", "NFS gateway boundary span"),
    (NFS, r"observe\(\s*\n?\s*[\"']nfs[\"']", "NFS SLO class accounting"),
    (S3, r"tracing\.begin\(\)", "S3 gateway boundary span"),
    (S3, r"observe\(\s*\n?\s*[\"']s3[\"']", "S3 SLO class accounting"),
    # per-session op accounting (ISSUE 14): the master RPC loop and
    # the chunkserver data plane must keep charging the originating
    # session, or `top` silently reads empty
    (MASTER, r"session_ops\.record\(",
     "master per-session op accounting (`top` rollup input)"),
    (CS, r"session_ops\.record\(",
     "chunkserver per-session data-plane accounting"),
    (MASTER, r"def top_report\(", "master cluster-wide `top` rollup"),
    # gateway observability surfaces: both front doors must keep their
    # /metrics + /healthz HTTP endpoints AND their master stats push —
    # a deleted endpoint is a lint failure, not a dashboard mystery
    (NFS, r"[\"']/metrics[\"']", "NFS gateway /metrics endpoint"),
    (NFS, r"[\"']/healthz[\"']", "NFS gateway /healthz endpoint"),
    (NFS, r"gateway_stats_push_loop\(",
     "NFS gateway workload-summary push (CltomaSessionStats)"),
    (S3, r"_op_metrics", "S3 gateway /metrics endpoint"),
    (S3, r"_op_healthz", "S3 gateway /healthz endpoint"),
    (S3, r"gateway_stats_push_loop\(",
     "S3 gateway workload-summary push (CltomaSessionStats)"),
    # the always-on sampling profiler's dump path (admin `profile`)
    (DAEMON, r"profiler\.collapsed\(",
     "daemon profiler collapsed-stack dump (admin `profile`)"),
    # multi-tenant QoS (ISSUE 15): the shed/throttle labeled counter
    # families and the BUSY handling chain must stand on every surface
    # — deleting any of them silently un-instruments load shedding
    (MASTER, r"labeled_counter\(\s*\n?\s*[\"']qos_shed[\"']",
     "master per-tenant shed counter (qos_shed{tenant,op})"),
    (CS, r"labeled_counter\(\s*\n?\s*[\"']qos_throttle[\"']",
     "chunkserver per-tenant throttle counter (qos_throttle{tenant})"),
    (CLIENT, r"st\.BUSY",
     "client BUSY (QoS shed) backoff-retry handling"),
    (CLIENT, r"qos_busy_waits",
     "client shed-retry counter (qos_busy_waits)"),
    (S3, r"st\.BUSY", "S3 gateway BUSY -> 503 SlowDown mapping"),
    (NFS, r"NFS3ERR_JUKEBOX",
     "NFS gateway BUSY -> JUKEBOX delay mapping"),
    # cluster heat loop (ISSUE 17): the lizardfs_heat_* families, the
    # heat section of `health`, and the SLO→QoS auto-arm chain are
    # standing surfaces — deleting any of them silently blinds the
    # heat map or disarms the second auto-arm action
    (HEAT, r"labeled_counter\(\s*\n?\s*[\"']heat_ops[\"']",
     "heat sketch per-key op counter (heat_ops{kind,key})"),
    (HEAT, r"labeled_counter\(\s*\n?\s*[\"']heat_bytes[\"']",
     "heat sketch per-key byte counter (heat_bytes{kind,key})"),
    (HEAT, r"labeled_timing\(\s*\n?\s*[\"']heat_hot_ops[\"']",
     "hot-key latency histogram with trace-id exemplars (heat_hot_ops)"),
    (MASTER, r"[\"']heat[\"']:\s*heat_doc",
     "heat section of the cluster `health` rollup"),
    (MASTER, r"def _slo_qos_arm\(",
     "SLO burn-rate breach -> QoS pressure auto-arm action"),
    (MASTER, r"labeled_counter\(\s*\n?\s*[\"']slo_qos_armed[\"']",
     "auto-armed QoS pressure counter (slo_qos_armed{tenant,op})"),
    (SLO, r"qos_arm\(",
     "SLO engine second auto-arm hook (breach -> qos_arm call)"),
    (CS, r"_heat_fold_json\(",
     "chunkserver per-chunk heat heartbeat fold (heat map input)"),
    # read-path microscope (ISSUE 18): phase-instrumented reads, the
    # queue-wait gates, and the attribution engine are standing
    # surfaces — losing any leg silently blanks a `top` column, a
    # queue_wait family, or the slowops/incident attribution embed
    (CLIENT, r"span\(\"read_file\", sink=self\._read_op\)",
     "client read root span: sink activation at the read_file boundary "
     "and exactly-once wall/rep accounting (PhaseBreakdown)"),
    (TRACING, r"sink\.phases\.add_wall\(",
     "the span primitive's root close (wall + self time, once an op)"),
    (CLIENT, r"charge_queue_wait\(",
     "client queue-wait gates (dial / busy_retry / write_credit)"),
    (CS, r"charge_queue_wait\(",
     "chunkserver DRR disk-gate queue-wait charge (drr_disk gate)"),
    (CS, r"queue_us",
     "chunkserver native trace-slot queue-wait fold (queue_us slot)"),
    (TRACING, r"def attribute_timeline\(",
     "latency attribution engine (queue/disk/net/compute buckets)"),
    (TRACING, r"def charge_queue_wait\(",
     "shared queue-wait charge helper (metric + ambient trace span)"),
    (SLO, r"attribute_timeline\(",
     "slowops/incident latency-attribution embed"),
    (MASTER, r"read_phases",
     "per-session read-phase lift into the `top` rollup"),
    (NATIVE_SERVE, r"lz_serve_trace3",
     "native 10-slot trace drain (queue_us-bearing slot contract)"),
    # autopilot failover (ISSUE 19): the lizardfs_ha_* families, the
    # `ha` section of health/admin, and the epoch fence are standing
    # surfaces — losing a gauge blinds the operator mid-incident, and
    # losing the fence silently re-opens the split-brain window
    (MASTER, r"gauge\(\s*\n?\s*[\"']ha_epoch[\"']",
     "HA epoch gauge on every personality (lizardfs_ha_epoch)"),
    (MASTER, r"gauge\(\s*\n?\s*[\"']ha_is_active[\"']",
     "HA active-posture gauge (lizardfs_ha_is_active)"),
    (MASTER, r"counter\([\"']ha_fenced[\"']\)\.inc\(",
     "zombie ex-primary fence counter (lizardfs_ha_fenced_total)"),
    (MASTER, r"def _ha_status\(",
     "the `ha` admin command / health section (failover posture)"),
    (MASTER, r"[\"']ha[\"']:\s*self\._ha_status\(\)",
     "ha section of the cluster `health` rollup"),
    (ELECTION, r"stale_votes_granted",
     "arbiter leaderless-relaxation counter in election status"),
)

# files searched for OP_CLASSES coverage (who feeds each objective)
SLO_SURFACES = (MASTER, CS, NFS, S3)


def extra_inputs(cfg) -> list[str]:
    root = cfg.root
    paths = {os.path.join(root, p) for p in SITE_IMPL.values()}
    paths.update(os.path.join(root, p) for p in SLO_SURFACES)
    paths.update(os.path.join(root, rel) for rel, _, _ in ANCHORS)
    paths.add(os.path.join(root, "lizardfs_tpu/runtime/slo.py"))
    paths.add(os.path.join(root, "lizardfs_tpu/runtime/faults.py"))
    if cfg.messages_path:
        paths.add(cfg.messages_path)
    return sorted(p for p in paths if os.path.exists(p))


def _tuple_of_strs(path: str, var: str) -> list[str]:
    """Module-level ``VAR = ("a", "b", ...)`` literal, without import."""
    try:
        with open(path, encoding="utf-8") as fh:
            tree = ast.parse(fh.read())
    except (OSError, SyntaxError):
        return []
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == var
        ):
            try:
                val = ast.literal_eval(node.value)
            except (ValueError, SyntaxError):
                return []
            if isinstance(val, (tuple, list)):
                return [v for v in val if isinstance(v, str)]
    return []


def _observes(text: str, cls: str) -> bool:
    return re.search(
        r"observe\(\s*\n?\s*[\"']" + re.escape(cls) + r"[\"']", text
    ) is not None


def check_global(cfg, collections: dict) -> list[Finding]:
    root = cfg.root
    findings: list[Finding] = []
    missing: set[str] = set()

    def read(rel: str) -> str:
        """Text of an inventoried surface file. An unreadable surface
        is a FINDING (reported once), never a silent skip — otherwise a
        renamed master/server.py would vacuously pass every check this
        rule makes about it."""
        try:
            with open(os.path.join(root, rel), encoding="utf-8") as fh:
                return fh.read()
        except OSError:
            if rel not in missing:
                missing.add(rel)
                findings.append(Finding(
                    RULE, rel, 0,
                    "telemetry surface file is missing/unreadable — the "
                    "inventory in tools/lint/telemetry.py names it; update "
                    "the inventory to the file's new home (every check "
                    "against it would otherwise pass vacuously)",
                ))
            return ""

    # inventory anchors are configurable so fixtures can exercise the
    # rule without a full tree
    slo_classes = getattr(cfg, "tc_slo_classes", SLO_CLASSES)
    slo_waivers = getattr(cfg, "tc_slo_waivers", SLO_WAIVERS)
    verb_sites = getattr(cfg, "tc_verb_sites", VERB_SITES)
    anchors = getattr(cfg, "tc_anchors", ANCHORS)
    site_impl = getattr(cfg, "tc_site_impl", SITE_IMPL)
    slo_path = getattr(
        cfg, "slo_path", os.path.join(root, "lizardfs_tpu/runtime/slo.py")
    )
    faults_path = getattr(
        cfg, "faults_path",
        os.path.join(root, "lizardfs_tpu/runtime/faults.py"),
    )

    # ---- catalog <-> inventory bijection ---------------------------------
    verbs: dict[str, int] = {}
    cat_rel = ""
    if cfg.messages_path and os.path.exists(cfg.messages_path):
        cat_rel = os.path.relpath(cfg.messages_path, root)
        try:
            with open(cfg.messages_path, encoding="utf-8") as fh:
                tree = ast.parse(fh.read())
        except (OSError, SyntaxError) as e:
            return [Finding(RULE, cat_rel, 0, f"cannot parse catalog: {e}")]
        for node in tree.body:
            if isinstance(node, ast.ClassDef) and node.name.startswith(
                ("Cltoma", "Cltocs")
            ):
                verbs[node.name] = node.lineno
    if not verbs:
        return findings

    op_classes = _tuple_of_strs(slo_path, "OP_CLASSES")
    fault_sites = _tuple_of_strs(faults_path, "SITES")
    master_text = read(MASTER)
    cs_text = read(CS)

    for verb, line in sorted(verbs.items()):
        handler_rel = MASTER if verb.startswith("Cltoma") else CS
        handler_text = master_text if handler_rel == MASTER else cs_text
        in_slo = verb in slo_classes
        in_waiver = verb in slo_waivers
        if not in_slo and not in_waiver:
            findings.append(Finding(
                RULE, cat_rel, line,
                f"{verb}: client-facing verb with no telemetry inventory "
                "entry — map it to an SLO class in tools/lint/telemetry.py "
                "(or add a waiver REASON there saying why it carries no "
                "latency objective)",
            ))
            continue
        if in_slo and in_waiver:
            findings.append(Finding(
                RULE, cat_rel, line,
                f"{verb}: both an SLO class and a waiver — pick one",
            ))
        if in_slo:
            cls = slo_classes[verb]
            if op_classes and cls not in op_classes:
                findings.append(Finding(
                    RULE, cat_rel, line,
                    f"{verb}: inventory maps it to SLO class {cls!r} which "
                    "runtime/slo.py OP_CLASSES does not define",
                ))
            elif handler_text and not _observes(handler_text, cls):
                findings.append(Finding(
                    RULE, cat_rel, line,
                    f"{verb}: inventory claims SLO class {cls!r} but "
                    f"{handler_rel} never observes it — the objective is "
                    "a dead letter for this verb",
                ))
        elif not str(slo_waivers[verb]).strip():
            findings.append(Finding(
                RULE, cat_rel, line,
                f"{verb}: SLO waiver with no reason — a reasonless waiver "
                "is not a waiver",
            ))
        # word-boundary match: CltomaWriteChunkEnd must not pass on the
        # strength of CltomaWriteChunkEndBatch still being handled
        if handler_text and not re.search(
            r"\b" + re.escape(verb) + r"\b", handler_text
        ):
            findings.append(Finding(
                RULE, cat_rel, line,
                f"{verb}: not referenced by its handler surface "
                f"({handler_rel}) — either a dead verb or a dispatch gap; "
                "remove it from the catalog or handle it",
            ))
        site = verb_sites.get(verb, DEFAULT_SITE)
        if fault_sites and site not in fault_sites:
            findings.append(Finding(
                RULE, cat_rel, line,
                f"{verb}: fault choke point {site!r} is not in "
                "runtime/faults.py SITES — the verb cannot be drilled",
            ))

    # ---- fault sites really consulted ------------------------------------
    # verb-mapped sites need a SITE_IMPL row; every SITE_IMPL row (not
    # just the ones a verb maps to today) must really pass its literal
    # to the fault engine, or a renamed "http_recv"/"disk_pread" string
    # leaves the site undrillable while the inventory still claims it
    checked_sites = {verb_sites.get(v, DEFAULT_SITE) for v in verbs}
    for site in sorted(checked_sites - set(site_impl)):
        findings.append(Finding(
            RULE, "lizardfs_tpu/tools/lint/telemetry.py", 0,
            f"fault site {site!r} has no SITE_IMPL mapping — name the "
            "file that consults it",
        ))
    for site, impl in sorted(site_impl.items()):
        text = read(impl)
        if text and f'"{site}"' not in text and f"'{site}'" not in text:
            findings.append(Finding(
                RULE, impl, 0,
                f"fault site {site!r} is claimed by the inventory but this "
                "file never passes the literal to the fault engine — the "
                "choke point is gone",
            ))

    # ---- generic instruments ---------------------------------------------
    for rel, pattern, what in anchors:
        text = read(rel)
        if text and re.search(pattern, text) is None:
            findings.append(Finding(
                RULE, rel, 0,
                f"missing instrument: {what} (expected /{pattern}/) — "
                "restore it or update the telemetry inventory with the "
                "new spelling",
            ))

    # ---- no dead objectives ----------------------------------------------
    if op_classes:
        surface_texts = [read(p) for p in SLO_SURFACES]
        for cls in op_classes:
            if not any(_observes(t, cls) for t in surface_texts if t):
                findings.append(Finding(
                    RULE, os.path.relpath(slo_path, root), 0,
                    f"SLO class {cls!r} is defined but no surface observes "
                    "it — dashboards read it as forever-healthy; feed it "
                    "or retire it",
                ))
    return findings
