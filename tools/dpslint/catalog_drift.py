"""Catalog-drift pass (rule ``doc-drift``).

One engine behind every code<->doc pin the repo accumulated
(tests/test_docs_drift.py now delegates here): metric names, span
catalog + call sites, health rules with severities, wire codecs,
directives, remediation actions + the default policy table, shard-map
schema fields — plus the two catalogs this tool itself introduces
(dpslint's RULE_CATALOG vs docs/STATIC_ANALYSIS.md, META_KEY_CATALOG vs
docs/WIRE_PROTOCOL.md's envelope-meta table).

Catalogs are extracted from the source FILES via ``ast`` — never by
importing the package — so the pass stays jax-free and runs in the
offline build environment at lint speed. Every pinned catalog is a pure
literal; ``tests/test_dpslint.py`` would fail loudly (extraction error)
if one stopped being extractable.

Each named check is independently callable (``CHECKS[name](ctx)``) so
the tier-1 drift tests can keep their one-failure-per-contract
granularity on top of the shared engine.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path

from .capability import META_KEY_CATALOG
from .core import RULE_CATALOG, Finding, SourceFile

_PKG = "distributed_parameter_server_for_ml_training_tpu"

# Regexes shared with the legacy drift tests (same semantics; see
# tests/test_docs_drift.py for the rationale comments).
REG_RE = re.compile(
    r'\.(?:counter|gauge|histogram)\(\s*"(dps_[a-z0-9_]+)"', re.S)
DOC_METRIC_RE = re.compile(r"dps_[a-z0-9_]+")
DOC_SPAN_RE = re.compile(
    r"`((?:worker|rpc|store|pipeline|trainer)\.[a-z_]+)`")
CALLSITE_RE = re.compile(r'trace_span\(\s*"([a-z_.]+)"', re.S)
DOC_RULE_RE = re.compile(
    r"\|\s*`([a-z_]+)`\s*\|\s*(critical|warning|info)\s*\|")
DOC_NAME_ROW_RE = re.compile(r"^\|\s*`([a-z0-9_\-]+)`\s*\|", re.M)
#: dpslint rule-table row in docs/STATIC_ANALYSIS.md: | `id` | severity |
DOC_LINT_RULE_RE = re.compile(
    r"\|\s*`([a-z\-]+)`\s*\|\s*(error|warning)\s*\|")

#: The sharding metric families pinned as an explicit contract on top of
#: the catch-all metric diff (ISSUE 9).
SHARDING_METRIC_FAMILIES = frozenset({
    "dps_shard_id", "dps_shard_count", "dps_shard_map_version",
    "dps_shard_replicas", "dps_replica_lag_steps",
    "dps_replica_lag_seconds"})


class DriftContext:
    """Lazily-loaded repo state shared by the checks."""

    def __init__(self, root: Path, sources: list[SourceFile]):
        self.root = Path(root)
        self.sources = sources
        self._docs: dict[str, str] = {}

    def doc(self, rel: str) -> str:
        if rel not in self._docs:
            self._docs[rel] = (self.root / rel).read_text()
        return self._docs[rel]

    def doc_line(self, rel: str, needle: str) -> int:
        """1-based line of the first occurrence (1 if absent)."""
        text = self.doc(rel)
        pos = text.find(needle)
        return 1 if pos < 0 else text.count("\n", 0, pos) + 1

    def catalog_node(self, rel: str, name: str) -> ast.AST:
        """The value node of module-level ``NAME = <literal>``."""
        path = self.root / rel
        for node in ast.parse(path.read_text()).body:
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == name:
                        return node.value
        raise LookupError(f"{rel} has no module-level {name}")

    def catalog(self, rel: str, name: str):
        """literal_eval of a catalog assignment (pure-literal contract)."""
        return ast.literal_eval(self.catalog_node(rel, name))


def _section(text: str, heading: str, stop: str = "\n#") -> str | None:
    """Doc text from ``heading`` to the next ``stop`` marker, or None.
    ``stop`` defaults to ANY next heading; pass ``"\\n## "`` to keep a
    section's own sub-headings inside it (the codec table lives under a
    ``###`` inside its ``##`` section)."""
    if heading not in text:
        return None
    rest = text.split(heading, 1)[1]
    return rest.split(stop, 1)[0]


def _diff(ctx: DriftContext, check: str, code: set, doc: set,
          doc_rel: str, what: str, anchor: str = "") -> list[Finding]:
    """Symmetric-difference findings for a both-directions pin."""
    line = ctx.doc_line(doc_rel, anchor) if anchor else 1
    out = []
    for name in sorted(code - doc):
        out.append(Finding(
            "doc-drift", doc_rel, line, f"{check}:{name}",
            f"{what} {name!r} exists in code but is absent from "
            f"{doc_rel}"))
    for name in sorted(doc - code):
        out.append(Finding(
            "doc-drift", doc_rel, line, f"{check}:{name}",
            f"{doc_rel} documents {what} {name!r} which no longer exists "
            f"in code (renamed or removed?)"))
    return out


# -- checks ------------------------------------------------------------------

def check_metrics(ctx: DriftContext) -> list[Finding]:
    registered = {m for s in ctx.sources for m in REG_RE.findall(s.text)}
    if not registered:
        return [Finding("doc-drift", f"{_PKG}", 1, "metrics:<none>",
                        "no dps_* instrument registrations found — the "
                        "registration regex rotted")]
    documented = set(DOC_METRIC_RE.findall(ctx.doc("docs/OBSERVABILITY.md")))
    return _diff(ctx, "metrics", registered, documented,
                 "docs/OBSERVABILITY.md", "metric")


def check_spans(ctx: DriftContext) -> list[Finding]:
    catalog = set(ctx.catalog(f"{_PKG}/telemetry/trace.py", "SPAN_CATALOG"))
    doc = {n for n in DOC_SPAN_RE.findall(ctx.doc("docs/OBSERVABILITY.md"))
           if not n.endswith(".py")}
    return _diff(ctx, "spans", catalog, doc, "docs/OBSERVABILITY.md",
                 "span")


def check_span_call_sites(ctx: DriftContext) -> list[Finding]:
    catalog = set(ctx.catalog(f"{_PKG}/telemetry/trace.py", "SPAN_CATALOG"))
    out = []
    for src in ctx.sources:
        for m in CALLSITE_RE.finditer(src.text):
            name = m.group(1)
            if name not in catalog:
                line = src.text.count("\n", 0, m.start()) + 1
                out.append(Finding(
                    "doc-drift", src.rel, line, f"span-site:{name}",
                    f"trace_span({name!r}) uses a name missing from "
                    f"SPAN_CATALOG (add it there AND to "
                    f"docs/OBSERVABILITY.md)"))
    return out


def check_health_rules(ctx: DriftContext) -> list[Finding]:
    catalog = {r: sev for r, (sev, _) in
               ctx.catalog(f"{_PKG}/telemetry/health.py",
                           "RULE_CATALOG").items()}
    doc_rows = dict(DOC_RULE_RE.findall(ctx.doc("docs/OBSERVABILITY.md")))
    out = _diff(ctx, "health-rule", set(catalog), set(doc_rows),
                "docs/OBSERVABILITY.md", "health rule")
    for rule in sorted(set(catalog) & set(doc_rows)):
        if catalog[rule] != doc_rows[rule]:
            out.append(Finding(
                "doc-drift", "docs/OBSERVABILITY.md",
                ctx.doc_line("docs/OBSERVABILITY.md", f"`{rule}`"),
                f"health-rule-severity:{rule}",
                f"health rule {rule!r} severity disagrees: code says "
                f"{catalog[rule]!r}, doc says {doc_rows[rule]!r}"))
    return out


def check_codecs(ctx: DriftContext) -> list[Finding]:
    catalog = set(ctx.catalog(f"{_PKG}/ops/compression.py",
                              "CODEC_CATALOG"))
    section = _section(ctx.doc("docs/WIRE_PROTOCOL.md"), "## Push codecs",
                       stop="\n## ")
    if section is None:
        return [Finding("doc-drift", "docs/WIRE_PROTOCOL.md", 1,
                        "codecs:<section>",
                        "'## Push codecs' section heading rotted")]
    doc = set(DOC_NAME_ROW_RE.findall(section))
    return _diff(ctx, "codec", catalog, doc, "docs/WIRE_PROTOCOL.md",
                 "codec", "## Push codecs")


def _table_check(ctx: DriftContext, check: str, src_rel: str,
                 catalog_name: str, doc_rel: str, heading: str,
                 what: str) -> list[Finding]:
    catalog = set(ctx.catalog(src_rel, catalog_name))
    section = _section(ctx.doc(doc_rel), heading)
    if section is None:
        return [Finding("doc-drift", doc_rel, 1, f"{check}:<section>",
                        f"{heading!r} section heading rotted in "
                        f"{doc_rel}")]
    doc = set(DOC_NAME_ROW_RE.findall(section))
    return _diff(ctx, check, catalog, doc, doc_rel, what, heading)


def check_directives(ctx: DriftContext) -> list[Finding]:
    return _table_check(ctx, "directive", f"{_PKG}/comms/service.py",
                        "DIRECTIVE_CATALOG", "docs/ROBUSTNESS.md",
                        "#### Directive catalog", "directive")


def check_actions(ctx: DriftContext) -> list[Finding]:
    return _table_check(ctx, "action", f"{_PKG}/telemetry/remediation.py",
                        "ACTION_CATALOG", "docs/ROBUSTNESS.md",
                        "#### Action catalog", "remediation action")


def check_policy_table(ctx: DriftContext) -> list[Finding]:
    health = set(ctx.catalog(f"{_PKG}/telemetry/health.py",
                             "RULE_CATALOG"))
    actions = set(ctx.catalog(f"{_PKG}/telemetry/remediation.py",
                              "ACTION_CATALOG"))
    code_policy = {r: tuple(a) for r, a in
                   ctx.catalog(f"{_PKG}/telemetry/remediation.py",
                               "DEFAULT_POLICY_RULES").items()}
    heading = "#### Policy table (defaults)"
    section = _section(ctx.doc("docs/ROBUSTNESS.md"), heading)
    if section is None:
        return [Finding("doc-drift", "docs/ROBUSTNESS.md", 1,
                        "policy:<section>",
                        f"{heading!r} section heading rotted")]
    line = ctx.doc_line("docs/ROBUSTNESS.md", heading)
    doc_policy = {}
    for rule, cell in re.findall(r"^\|\s*`([a-z_]+)`\s*\|\s*(.+?)\s*\|",
                                 section, re.M):
        doc_policy[rule] = tuple(re.findall(r"`([a-z_]+)`", cell))
    out = []
    if not doc_policy:
        return [Finding("doc-drift", "docs/ROBUSTNESS.md", line,
                        "policy:<rows>", "policy table has no rows — "
                        "format rotted")]
    for rule, acts in doc_policy.items():
        if rule not in health:
            out.append(Finding(
                "doc-drift", "docs/ROBUSTNESS.md", line,
                f"policy:{rule}",
                f"policy table maps unknown health rule {rule!r}"))
        for a in acts:
            if a not in actions:
                out.append(Finding(
                    "doc-drift", "docs/ROBUSTNESS.md", line,
                    f"policy:{rule}:{a}",
                    f"policy table maps {rule!r} to unknown action "
                    f"{a!r}"))
    if doc_policy != code_policy:
        for rule in sorted(set(doc_policy) ^ set(code_policy)) + sorted(
                r for r in set(doc_policy) & set(code_policy)
                if doc_policy[r] != code_policy[r]):
            out.append(Finding(
                "doc-drift", "docs/ROBUSTNESS.md", line,
                f"policy-row:{rule}",
                f"policy row {rule!r} disagrees with "
                f"DEFAULT_POLICY_RULES: doc="
                f"{doc_policy.get(rule)} code={code_policy.get(rule)}"))
    return out


def check_shard_map_fields(ctx: DriftContext) -> list[Finding]:
    return _table_check(ctx, "shard-field", f"{_PKG}/ps/sharding.py",
                        "SHARD_MAP_FIELDS", "docs/SHARDING.md",
                        "### Shard map schema", "shard-map field")


def check_sharding_metric_families(ctx: DriftContext) -> list[Finding]:
    registered = {m for s in ctx.sources for m in REG_RE.findall(s.text)}
    documented = set(DOC_METRIC_RE.findall(ctx.doc("docs/OBSERVABILITY.md")))
    out = []
    for name in sorted(SHARDING_METRIC_FAMILIES - registered):
        out.append(Finding(
            "doc-drift", f"{_PKG}/ps/sharding.py", 1,
            f"shard-metric:{name}",
            f"sharding metric family {name!r} is no longer registered"))
    for name in sorted(SHARDING_METRIC_FAMILIES - documented):
        out.append(Finding(
            "doc-drift", "docs/OBSERVABILITY.md", 1,
            f"shard-metric-doc:{name}",
            f"sharding metric family {name!r} missing from "
            f"docs/OBSERVABILITY.md"))
    return out


def check_lint_rules(ctx: DriftContext) -> list[Finding]:
    """dpslint's own catalog, same discipline: docs/STATIC_ANALYSIS.md's
    rule table pinned to core.RULE_CATALOG in both directions, with
    severities."""
    catalog = {r: sev for r, (sev, _) in RULE_CATALOG.items()}
    doc_rows = dict(DOC_LINT_RULE_RE.findall(
        ctx.doc("docs/STATIC_ANALYSIS.md")))
    out = _diff(ctx, "lint-rule", set(catalog), set(doc_rows),
                "docs/STATIC_ANALYSIS.md", "lint rule")
    for rule in sorted(set(catalog) & set(doc_rows)):
        if catalog[rule] != doc_rows[rule]:
            out.append(Finding(
                "doc-drift", "docs/STATIC_ANALYSIS.md",
                ctx.doc_line("docs/STATIC_ANALYSIS.md", f"`{rule}`"),
                f"lint-rule-severity:{rule}",
                f"lint rule {rule!r} severity disagrees: code says "
                f"{catalog[rule]!r}, doc says {doc_rows[rule]!r}"))
    return out


def check_op_classes(ctx: DriftContext) -> list[Finding]:
    return _table_check(ctx, "op-class",
                        f"{_PKG}/analysis/device_profile.py",
                        "OP_CLASSES", "docs/OBSERVABILITY.md",
                        "#### Op classes", "profiler op class")


def check_job_spec_fields(ctx: DriftContext) -> list[Finding]:
    return _table_check(ctx, "job-spec-field", f"{_PKG}/ps/tenancy.py",
                        "JOB_SPEC_FIELDS", "docs/TENANCY.md",
                        "### Job spec fields", "job spec field")


def check_fleet_rollup_fields(ctx: DriftContext) -> list[Finding]:
    """FLEET_ROLLUP_FIELDS pinned to docs/OBSERVABILITY.md's rollup-
    semantics table — a ``/fleet`` rollup field cannot appear without
    documented merge semantics, or stay documented after removal."""
    return _table_check(ctx, "fleet-rollup-field",
                        f"{_PKG}/telemetry/fleet.py",
                        "FLEET_ROLLUP_FIELDS", "docs/OBSERVABILITY.md",
                        "### Rollup semantics", "fleet rollup field")


def check_event_catalog(ctx: DriftContext) -> list[Finding]:
    """EVENT_CATALOG (telemetry/journal.py) pinned to the
    docs/OBSERVABILITY.md event-catalog table — a journal record type
    cannot exist without documented semantics (postmortems are read by
    humans who were not there), or stay documented after removal."""
    return _table_check(ctx, "journal-event",
                        f"{_PKG}/telemetry/journal.py",
                        "EVENT_CATALOG", "docs/OBSERVABILITY.md",
                        "### Event catalog", "journal event type")


def check_incident_manifest(ctx: DriftContext) -> list[Finding]:
    """MANIFEST_FIELDS (telemetry/incidents.py) pinned to the
    docs/OBSERVABILITY.md incident-manifest table."""
    return _table_check(ctx, "incident-manifest",
                        f"{_PKG}/telemetry/incidents.py",
                        "MANIFEST_FIELDS", "docs/OBSERVABILITY.md",
                        "### Incident manifest", "incident manifest field")


def check_meta_keys(ctx: DriftContext) -> list[Finding]:
    """META_KEY_CATALOG pinned to docs/WIRE_PROTOCOL.md's envelope-meta
    table — a wire field cannot be cataloged without being documented,
    or documented without existing."""
    heading = "### Envelope meta keys"
    section = _section(ctx.doc("docs/WIRE_PROTOCOL.md"), heading)
    if section is None:
        return [Finding("doc-drift", "docs/WIRE_PROTOCOL.md", 1,
                        "meta-key-doc:<section>",
                        f"{heading!r} section heading rotted in "
                        f"docs/WIRE_PROTOCOL.md")]
    doc = set(DOC_NAME_ROW_RE.findall(section))
    return _diff(ctx, "meta-key-doc", set(META_KEY_CATALOG), doc,
                 "docs/WIRE_PROTOCOL.md", "envelope-meta key", heading)


def check_goodput_categories(ctx: DriftContext) -> list[Finding]:
    """GOODPUT_CATEGORIES (telemetry/goodput.py) pinned to the
    docs/OBSERVABILITY.md goodput-categories table — a wall-clock
    category cannot be charged without documented semantics (the ledger
    is read by humans attributing badput), or stay documented after
    removal."""
    return _table_check(ctx, "goodput-category",
                        f"{_PKG}/telemetry/goodput.py",
                        "GOODPUT_CATEGORIES", "docs/OBSERVABILITY.md",
                        "### Goodput categories", "goodput category")


def check_attention_core_impls(ctx: DriftContext) -> list[Finding]:
    """ATTENTION_CORE_IMPLS (ops/attention.py), the values of
    ``dps_attention_core_total``'s ``impl`` label, pinned to the
    docs/OBSERVABILITY.md table: a snapshot's label must say which core
    the compiled program holds in words the doc explains."""
    return _table_check(ctx, "attention-core-impl",
                        f"{_PKG}/ops/attention.py",
                        "ATTENTION_CORE_IMPLS", "docs/OBSERVABILITY.md",
                        "#### Attention core implementations",
                        "attention core implementation")


def check_profile_record(ctx: DriftContext) -> list[Finding]:
    """PROFILE_RECORD_FIELDS (telemetry/proftrigger.py) pinned to the
    docs/OBSERVABILITY.md profile-ledger table — the committed
    PROFILE_*.json records are longitudinal evidence; their schema
    cannot drift undocumented."""
    return _table_check(ctx, "profile-record",
                        f"{_PKG}/telemetry/proftrigger.py",
                        "PROFILE_RECORD_FIELDS", "docs/OBSERVABILITY.md",
                        "### Profile ledger", "profile record field")


CHECKS = {
    "metrics": check_metrics,
    "spans": check_spans,
    "span-call-sites": check_span_call_sites,
    "health-rules": check_health_rules,
    "codecs": check_codecs,
    "directives": check_directives,
    "actions": check_actions,
    "policy-table": check_policy_table,
    "shard-map-fields": check_shard_map_fields,
    "sharding-metric-families": check_sharding_metric_families,
    "lint-rules": check_lint_rules,
    "op-classes": check_op_classes,
    "job-spec-fields": check_job_spec_fields,
    "meta-keys": check_meta_keys,
    "fleet-rollup-fields": check_fleet_rollup_fields,
    "event-catalog": check_event_catalog,
    "incident-manifest": check_incident_manifest,
    "goodput-categories": check_goodput_categories,
    "profile-record": check_profile_record,
    "attention-core-impls": check_attention_core_impls,
}


def run(sources: list[SourceFile], root: Path) -> list[Finding]:
    ctx = DriftContext(root, sources)
    findings: list[Finding] = []
    for fn in CHECKS.values():
        findings.extend(fn(ctx))
    return findings
