"""``kill-switch``: every ``LZ_*`` environment variable is inventoried,
single-accessor, spelling-parity, documented, and test-referenced.

The four documented off spellings (``0 / off / false / no``, see
``constants.OFF_SPELLINGS``) were hand-policed into LZ_SHM_RING,
LZ_SHADOW_READS and friends across PRs 6/7 — and review still caught
parity misses twice. Worse, truthiness reads (``if os.environ.get(X)``)
invert the contract silently: ``LZ_TPU_ALLOW_CPU=0`` *enabled* the
escape hatch before this PR. This checker closes the class:

* Boolean switches may be read ONLY inside ``constants.env_flag`` —
  the one accessor that owns the spelling set. Everything else calls
  ``env_flag("LZ_X", default)`` (or a named helper that does), and each
  switch may have at most ONE such accessor call site: two ad-hoc
  ``env_flag`` calls for the same switch re-create the drift the rule
  exists to kill.
* Value vars (specs, sizes, depths) keep direct reads, but all reads
  of one var must live in a single function — one accessor per var.
* Every var must be registered below (switch / value / wildcard),
  mentioned in the ops doc inventory, and — for switches — referenced
  by at least one test under ``tests/`` (the equivalence test that
  pins kill-switch-off behavior).
* ``getenv("LZ_*")`` in ``native/`` must name an inventoried var too
  (C++ spelling parity itself is pinned by the existing server-side
  'off' tests).

Env var names must be string literals (or a literal-prefixed f-string
matching a wildcard entry like ``LZ_SLO_<CLASS>_MS``) — a computed name
is invisible to this inventory and to every grep an operator runs.
"""

from __future__ import annotations

import ast
import glob
import os
import re

from lizardfs_tpu.tools.lint import engine
from lizardfs_tpu.tools.lint.engine import Finding

RULE = "kill-switch"

# ---- the inventory ---------------------------------------------------------
# Boolean switches: read via constants.env_flag only; four-spelling off
# parity; must be documented + test-referenced.
SWITCHES = {
    "LZ_TRACE",            # request tracing (default on)
    "LZ_SLO",              # SLO engine (default on)
    "LZ_SHM_RING",         # same-host shared-memory data plane (on)
    "LZ_SHADOW_READS",     # shadow read replicas (on)
    "LZ_SHARDED_RECOVERY", # mesh-sharded rebuild compute (on)
    "LZ_TPU_ALLOW_CPU",    # encoder escape hatch (default OFF)
    "LZ_NO_UDS",           # disable same-host UDS fast path (default OFF)
    "LZ_S3",               # S3 object gateway (on; off refuses start)
    "LZ_S3_LIFECYCLE",     # master lifecycle tiering scanner (on)
    "LZ_TOP",              # per-session op accounting / `top` view (on)
    "LZ_PROF",             # always-on sampling profiler (on)
    "LZ_QOS",              # multi-tenant fair-share QoS plane (on)
    "LZ_HEAT",             # cluster heat map + adaptive replication (on)
    "LZ_HA",               # autopilot failover: election + fencing (on)
}

# Value vars: one read site each; documented; spelling rules N/A.
VALUES = {
    "LZ_FAULTS",                  # fault-injection rule spec (unset = off)
    "LZ_ROLE",                    # process role for fault attribution
    "LZ_NATIVE_SO",               # alternate native library path
    "LZ_CLIENT_SO",               # alternate C-client library path
    "LZ_SHM_RING_MB",             # shm segment size
    "LZ_DETSCHED",                # deterministic-scheduler seed (tests)
}

# Wildcard families: literal prefix of an f-string read.
WILDCARDS = {"LZ_SLO_"}  # LZ_SLO_<CLASS>_MS per-class thresholds

_NATIVE_GETENV = re.compile(r'getenv\(\s*"(LZ_[A-Z0-9_]*)"')


class _Read:
    def __init__(self, rel, func, line, var, prefix=None):
        self.rel = rel
        self.func = func  # enclosing function name or "<module>"
        self.line = line
        self.var = var  # None = dynamic name
        self.prefix = prefix  # literal f-string prefix if any


def _literal_name(node):
    """(var, prefix): var for a Constant str, prefix for a JoinedStr."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value, None
    if isinstance(node, ast.JoinedStr) and node.values:
        head = node.values[0]
        if isinstance(head, ast.Constant) and isinstance(head.value, str):
            return None, head.value
    return None, None


def _is_environ(node) -> bool:
    """os.environ / environ (from-imported) as a read receiver."""
    return (isinstance(node, ast.Attribute) and node.attr == "environ") or (
        isinstance(node, ast.Name) and node.id == "environ"
    )


def _collect(src):
    """(env_reads, env_flag_calls) for one SourceFile."""
    reads: list[_Read] = []
    flags: list[_Read] = []

    def walk(node, func):
        for child in ast.iter_child_nodes(node):
            cf = func
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                cf = child.name
            name_node = None
            sink = None
            if isinstance(child, ast.Call):
                f = child.func
                # attribute AND bare-name forms: `from os import
                # getenv/environ` must not bypass the gate
                if (
                    isinstance(f, ast.Attribute)
                    and (
                        (f.attr == "get" and _is_environ(f.value))
                        or f.attr == "getenv"
                    )
                ) or (isinstance(f, ast.Name) and f.id == "getenv"):
                    name_node = child.args[0] if child.args else None
                    sink = reads
                elif (isinstance(f, ast.Name) and f.id == "env_flag") or (
                    isinstance(f, ast.Attribute) and f.attr == "env_flag"
                ):
                    name_node = child.args[0] if child.args else None
                    sink = flags
            elif (
                isinstance(child, ast.Subscript)
                and isinstance(child.ctx, ast.Load)
                and _is_environ(child.value)
            ):
                name_node = child.slice
                sink = reads
            if sink is not None and name_node is not None:
                var, prefix = _literal_name(name_node)
                if (var and var.startswith("LZ_")) or (
                    prefix and prefix.startswith("LZ_")
                ):
                    sink.append(
                        _Read(src.rel, cf, child.lineno, var, prefix)
                    )
            walk(child, cf)

    walk(src.tree, "<module>")
    return reads, flags


def _match_wildcard(read, wildcards):
    probe = read.var or read.prefix or ""
    return next((w for w in wildcards if probe.startswith(w)), None)


def extra_inputs(cfg) -> list[str]:
    """Non-scanned inputs the global pass reads: the ops doc, every
    test file (switch-reference leg), and the native sources (getenv
    sweep). Folded into the engine's global-results cache key so a
    native/doc/tests edit re-runs this pass."""
    out = list(cfg.doc_paths or [])
    if cfg.tests_dir and os.path.isdir(cfg.tests_dir):
        out.extend(sorted(glob.glob(os.path.join(cfg.tests_dir, "*.py"))))
    out.extend(engine.native_sources(cfg.native_dir))
    return out


def collect_file(src) -> dict:
    """Cacheable per-file summary: every env read / env_flag call.
    The engine stores this in the per-file cache so a warm run never
    re-parses a file just to feed this checker's global pass."""
    reads, flags = _collect(src)
    ser = lambda rs: [[r.func, r.line, r.var, r.prefix] for r in rs]  # noqa: E731
    return {"reads": ser(reads), "flags": ser(flags)}


# the ONE file whose env_flag function may read boolean switches
# directly — a same-named function elsewhere is a re-implementation
# (its own spelling set = the drift this rule exists to kill)
ACCESSOR_FILES = ("lizardfs_tpu/constants.py",)


def check_global(cfg, collections: dict) -> list[Finding]:
    switches = getattr(cfg, "ks_switches", SWITCHES)
    values = getattr(cfg, "ks_values", VALUES)
    wildcards = getattr(cfg, "ks_wildcards", WILDCARDS)
    accessor_files = getattr(cfg, "ks_accessor_files", ACCESSOR_FILES)
    findings: list[Finding] = []
    reads: list[_Read] = []
    flags: list[_Read] = []
    for rel, col in collections.items():
        for func, line, var, prefix in col.get("reads", ()):
            reads.append(_Read(rel, func, line, var, prefix))
        for func, line, var, prefix in col.get("flags", ()):
            flags.append(_Read(rel, func, line, var, prefix))

    # ---- direct env reads -------------------------------------------------
    value_sites: dict[str, list[_Read]] = {}
    for rd in reads:
        wc = _match_wildcard(rd, wildcards)
        if rd.var is None:
            if wc is None:
                findings.append(Finding(
                    RULE, rd.rel, rd.line,
                    "LZ_* env read with a computed name — the inventory "
                    "(and operator greps) cannot see it; use a literal or "
                    "register a wildcard family",
                ))
            else:
                value_sites.setdefault(wc, []).append(rd)
            continue
        if rd.var in switches:
            if rd.func != "env_flag" or (
                rd.rel.replace("\\", "/") not in accessor_files
            ):
                findings.append(Finding(
                    RULE, rd.rel, rd.line,
                    f"{rd.var}: boolean kill switch read directly — route "
                    "through constants.env_flag (the one accessor honoring "
                    "the four documented off spellings: 0/off/false/no; "
                    "a same-named function elsewhere is a "
                    "re-implementation, not the accessor)",
                ))
            continue
        if rd.var in values:
            value_sites.setdefault(rd.var, []).append(rd)
            continue
        if wc is not None:
            value_sites.setdefault(wc, []).append(rd)
            continue
        findings.append(Finding(
            RULE, rd.rel, rd.line,
            f"{rd.var}: unregistered LZ_* env var — add it to the "
            "kill-switch checker inventory (switch or value), the ops-doc "
            "inventory, and (switches) an equivalence test",
        ))

    # one accessor per value var
    for var, sites in sorted(value_sites.items()):
        funcs = {(s.rel, s.func) for s in sites}
        if len(funcs) > 1:
            where = ", ".join(sorted(f"{r}:{fn}" for r, fn in funcs))
            for s in sites:
                findings.append(Finding(
                    RULE, s.rel, s.line,
                    f"{var}: read from {len(funcs)} functions ({where}) — "
                    "route every consumer through one accessor",
                ))

    # ---- env_flag call sites ---------------------------------------------
    flag_sites: dict[str, list[_Read]] = {}
    for fl in flags:
        if fl.var is None:
            findings.append(Finding(
                RULE, fl.rel, fl.line,
                "env_flag() with a computed name — switches must be "
                "literal so the inventory can see them",
            ))
            continue
        if fl.var not in switches:
            findings.append(Finding(
                RULE, fl.rel, fl.line,
                f"{fl.var}: env_flag() on a var not registered as a "
                "boolean switch",
            ))
            continue
        flag_sites.setdefault(fl.var, []).append(fl)
    for var, sites in sorted(flag_sites.items()):
        funcs = {(s.rel, s.func) for s in sites}
        if len(funcs) > 1:
            where = ", ".join(sorted(f"{r}:{fn}" for r, fn in funcs))
            for s in sites:
                findings.append(Finding(
                    RULE, s.rel, s.line,
                    f"{var}: env_flag called from {len(funcs)} places "
                    f"({where}) — one accessor per switch; export a named "
                    "helper and call that",
                ))

    # ---- native/ getenv sweep --------------------------------------------
    for path in engine.native_sources(cfg.native_dir):
        rel = os.path.relpath(path, cfg.root)
        try:
            with open(path, encoding="utf-8", errors="replace") as fh:
                for i, line in enumerate(fh, start=1):
                    for m in _NATIVE_GETENV.finditer(line):
                        var = m.group(1)
                        if var not in switches and var not in values:
                            findings.append(Finding(
                                RULE, rel, i,
                                f"{var}: native getenv of an "
                                "uninventoried LZ_* var",
                            ))
        except OSError:
            continue

    # ---- doc + test inventory --------------------------------------------
    doc_text = ""
    for dp in cfg.doc_paths or []:
        try:
            with open(dp, encoding="utf-8") as fh:
                doc_text += fh.read()
        except OSError:
            pass
    tests_text = ""
    if cfg.tests_dir and os.path.isdir(cfg.tests_dir):
        for tp in sorted(glob.glob(os.path.join(cfg.tests_dir, "*.py"))):
            try:
                with open(tp, encoding="utf-8") as fh:
                    tests_text += fh.read()
            except OSError:
                pass
    anchor = os.path.relpath(
        (cfg.doc_paths or [os.path.join(cfg.root, "doc")])[0], cfg.root
    )
    if cfg.doc_paths:
        for var in sorted(switches | values) + sorted(wildcards):
            # wildcards probe with the raw prefix ("LZ_SLO_"): trimming
            # the underscore would let the unrelated LZ_SLO switch row
            # satisfy the family's doc requirement
            if var not in doc_text:
                findings.append(Finding(
                    RULE, anchor, 0,
                    f"{var}: missing from the ops-doc env inventory",
                ))
    if cfg.tests_dir:
        for var in sorted(switches):
            if var not in tests_text:
                findings.append(Finding(
                    RULE, anchor, 0,
                    f"{var}: boolean switch with no test referencing it — "
                    "add an off-equivalence test under tests/",
                ))
    return findings
