"""metric-discipline: the metric/span namespace cannot silently fork.

Subsumes scripts/check_metric_names.py (which is now a thin shim over this
module) and extends it:

- **kind conflicts** — one metric name registered as two instrument kinds
  anywhere in the tree. The runtime guard only fires when both sites run in
  ONE process; two processes would each run fine and corrupt the merged
  fleet document (observability/aggregate.py drops + reports the conflict
  — this rule keeps it from ever landing).
- **naming** — instrument names must be snake_case AND carry the ``ts_``
  namespace prefix (grep-ability; Prometheus exposition).
- **label cardinality** — label keys used at instrument call sites
  (``.inc``/``.set``/``.dec``/``.observe`` on module-level instruments)
  must come from the bounded-key allowlist. Keys like ``key=`` or
  ``session=`` create one series per key/session — unbounded memory in
  every process and a useless merged snapshot. Bounded new keys are added
  to ``ALLOWED_LABEL_KEYS`` deliberately, in review.
- **span names** — ``span("...")`` literals must match
  ``[a-z][a-z0-9_./]*`` so traces group cleanly in Perfetto (f-string
  constant fragments are checked too: ``span(f"rpc/{m}")`` passes,
  ``span(f"RPC {m}")`` does not).
- **docs table drift** — docs/API.md carries a GENERATED metrics
  reference table between markers (like the env-var table), rebuilt from
  a static scan of every instrument registration site by
  ``python scripts/tslint.py --regen-metric-docs``. A registration added,
  renamed, or re-worded without regenerating fails this rule — the table
  can never silently drift from the tree. (Projects without docs/API.md
  — fixture trees — skip this rule.)
"""

from __future__ import annotations

import ast
import os
import re
import sys

from torchstore_tpu.analysis.core import Finding, Project

RULE = "metric-discipline"

NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
METRIC_PREFIX = "ts_"
SPAN_NAME_RE = re.compile(r"^[a-z][a-z0-9_./]*$")
SPAN_FRAGMENT_RE = re.compile(r"^[a-z0-9_./]*$")
INSTRUMENT_CALLS = {"counter", "gauge", "histogram"}
_USE_METHODS = {"inc", "dec", "set", "observe"}

# Bounded label keys (fleet-size / enum cardinality). Adding a key here is a
# deliberate, reviewed act — ask "how many distinct values can this take in
# one process's lifetime?" before extending.
ALLOWED_LABEL_KEYS = {
    "op",
    "transport",
    "outcome",
    "volume",
    "channel",
    "stage",
    "kind",
    "replicas",
    "leg",
    "direction",
    "process",
    "volume_id",
    "task",
    "reason",
    "phase",
    "rule",
    # Faultpoint metrics: one series per (site, action) — both enums are
    # closed sets in faults.py (REGISTRY, ACTIONS).
    "point",
    "action",
    # Quant wire tier: one series per mode — a closed set
    # (state_dict_utils.QUANT_MODES).
    "fmt",
    # Control plane: one series per admission tenant (tenants are a small
    # deployment-configured cohort set, not per-key) and per reconcile
    # trigger — a closed set ("interval", "manual", "plan").
    "tenant",
    "trigger",
    # SLO violations: one series per configured TORCHSTORE_TPU_SLO_* knob
    # (a small operator-set family, observability/timeline.py).
    "slo",
    # Metadata mirror feed: one series per stamped segment source — the
    # coordinator plus one per index shard (metadata/mirror.py), a
    # deployment-sized closed set.
    "source",
    # Metadata-plane inflight: one series per controller shard
    # ("coord"/"s<i>" — bounded by controller_shards, metadata/router.py).
    "shard",
    # Trend plane: one series per detector in the stock catalog
    # (observability/detect.py default_detectors — a closed, code-reviewed
    # set; history-discipline pins each one to a registered instrument).
    "detector",
    # Device->host copies of a put: "chunked" or "whole", decided by
    # sharding.chunk_plan — a closed set of two.
    "path",
}


def collect_sites(root: str, project: Project | None = None):
    """Every (file, line, metric_name, kind) instrument call site with a
    string-literal first argument under the scanned tree. Kept
    signature-compatible with the old scripts/check_metric_names.py."""
    if project is None:
        project = Project(root)
    sites: list[tuple[str, int, str, str]] = []
    for sf in project.files:
        if sf.tree is None:
            print(
                f"check_metric_names: cannot parse {sf.abspath}: {sf.parse_error}",
                file=sys.stderr,
            )
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            kind = _call_name(node)
            if kind not in INSTRUMENT_CALLS or not node.args:
                continue
            first = node.args[0]
            if not (isinstance(first, ast.Constant) and isinstance(first.value, str)):
                continue  # dynamic names (registry internals) are not sites
            sites.append((sf.path, node.lineno, first.value, kind))
    return sites


def _call_name(node: ast.Call) -> str | None:
    func = node.func
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


# --- generated docs table (docs/API.md) -----------------------------------

METRIC_DOCS_BEGIN = (
    "<!-- tslint-metric-table:begin (generated by scripts/tslint.py "
    "--regen-metric-docs; do not edit by hand) -->"
)
METRIC_DOCS_END = "<!-- tslint-metric-table:end -->"


def collect_instruments(root: str, project: Project | None = None):
    """Every instrument registration with its help string:
    ``(path, line, name, kind, help)``. The second positional arg (or the
    ``help=`` keyword) is taken when it is a string literal."""
    if project is None:
        project = Project(root)
    out: list[tuple[str, int, str, str, str]] = []
    for sf in project.files:
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            kind = _call_name(node)
            if kind not in INSTRUMENT_CALLS or not node.args:
                continue
            first = node.args[0]
            if not (
                isinstance(first, ast.Constant)
                and isinstance(first.value, str)
            ):
                continue
            help_text = ""
            if len(node.args) > 1 and isinstance(node.args[1], ast.Constant):
                if isinstance(node.args[1].value, str):
                    help_text = node.args[1].value
            else:
                for kw in node.keywords:
                    if (
                        kw.arg == "help"
                        and isinstance(kw.value, ast.Constant)
                        and isinstance(kw.value.value, str)
                    ):
                        help_text = kw.value.value
            out.append((sf.path, node.lineno, first.value, kind, help_text))
    return out


def render_metric_table(instruments) -> str:
    """One row per metric NAME (registrations are get-or-create: many call
    sites share one instrument; the first non-empty help wins, matching
    MetricsRegistry semantics where the creator's help sticks)."""
    by_name: dict[str, tuple[str, str]] = {}
    for _path, _line, name, kind, help_text in instruments:
        kind_now, help_now = by_name.get(name, (kind, ""))
        by_name[name] = (kind_now, help_now or help_text)
    lines = [
        "| Metric | Kind | Description |",
        "|---|---|---|",
    ]
    for name, (kind, help_text) in sorted(by_name.items()):
        doc = " ".join(help_text.split()).replace("|", "\\|")
        lines.append(f"| `{name}` | {kind} | {doc} |")
    return "\n".join(lines)


def check_names(root: str, sites=None, project: Project | None = None) -> list[str]:
    """Namespace violations as strings (the historical shim contract)."""
    if sites is None:
        sites = collect_sites(root, project)
    problems: list[str] = []
    by_name: dict[str, dict[str, list[str]]] = {}
    for path, line, name, kind in sites:
        if not NAME_RE.match(name):
            problems.append(
                f"{path}:{line}: metric name {name!r} is not snake_case "
                "([a-z][a-z0-9_]*)"
            )
        by_name.setdefault(name, {}).setdefault(kind, []).append(f"{path}:{line}")
    for name, kinds in sorted(by_name.items()):
        if len(kinds) > 1:
            detail = "; ".join(
                f"{kind} at {', '.join(locs)}" for kind, locs in sorted(kinds.items())
            )
            problems.append(
                f"metric {name!r} registered with conflicting kinds: {detail}"
            )
    return problems


def check(project: Project) -> list[Finding]:
    findings: list[Finding] = []
    sites = collect_sites(project.root, project)

    # --- ported rules: snake_case + kind conflicts (+ ts_ prefix) ---------
    by_name: dict[str, dict[str, list[tuple[str, int]]]] = {}
    for path, line, name, kind in sites:
        if not NAME_RE.match(name):
            findings.append(
                Finding(
                    RULE,
                    path,
                    line,
                    f"metric name {name!r} is not snake_case ([a-z][a-z0-9_]*)",
                )
            )
        elif not name.startswith(METRIC_PREFIX):
            findings.append(
                Finding(
                    RULE,
                    path,
                    line,
                    f"metric name {name!r} lacks the {METRIC_PREFIX!r} "
                    "namespace prefix every store instrument carries",
                )
            )
        by_name.setdefault(name, {}).setdefault(kind, []).append((path, line))
    for name, kinds in sorted(by_name.items()):
        if len(kinds) > 1:
            detail = "; ".join(
                f"{kind} in {', '.join(sorted({p for p, _ in locs}))}"
                for kind, locs in sorted(kinds.items())
            )
            first_path, first_line = next(iter(sorted(kinds.items())))[1][0]
            findings.append(
                Finding(
                    RULE,
                    first_path,
                    first_line,
                    f"metric {name!r} registered with conflicting kinds: {detail}",
                )
            )

    # --- label cardinality on module-level instruments --------------------
    for sf in project.files:
        if sf.tree is None:
            continue
        instruments: set[str] = set()
        for node in sf.tree.body:
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                if _call_name(node.value) in INSTRUMENT_CALLS:
                    instruments.update(
                        t.id for t in node.targets if isinstance(t, ast.Name)
                    )
        if not instruments:
            continue
        for node in ast.walk(sf.tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _USE_METHODS
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in instruments
            ):
                continue
            for kw in node.keywords:
                if kw.arg is None or kw.arg == "n":
                    continue
                if kw.arg not in ALLOWED_LABEL_KEYS:
                    findings.append(
                        Finding(
                            RULE,
                            sf.path,
                            node.lineno,
                            f"label key {kw.arg!r} on instrument "
                            f"{node.func.value.id!r} is not in the bounded-"
                            "cardinality allowlist (one series per distinct "
                            "value; add to ALLOWED_LABEL_KEYS only if the "
                            "value set is provably small)",
                        )
                    )

    # --- span-name discipline ---------------------------------------------
    for sf in project.files:
        if sf.tree is None or sf.path == "torchstore_tpu/observability/tracing.py":
            continue
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call) and _call_name(node) == "span"):
                continue
            if not node.args:
                continue
            first = node.args[0]
            if isinstance(first, ast.Constant) and isinstance(first.value, str):
                if not SPAN_NAME_RE.match(first.value):
                    findings.append(
                        Finding(
                            RULE,
                            sf.path,
                            node.lineno,
                            f"span name {first.value!r} must match "
                            "[a-z][a-z0-9_./]* (lowercase dotted/slashed "
                            "path, no spaces)",
                        )
                    )
            elif isinstance(first, ast.JoinedStr):
                for part in first.values:
                    if isinstance(part, ast.Constant) and isinstance(part.value, str):
                        if not SPAN_FRAGMENT_RE.match(part.value):
                            findings.append(
                                Finding(
                                    RULE,
                                    sf.path,
                                    node.lineno,
                                    f"span name fragment {part.value!r} "
                                    "contains characters outside "
                                    "[a-z0-9_./]",
                                )
                            )
                            break

    # --- docs/API.md generated metrics table drift ------------------------
    docs_path = os.path.join(project.root, "docs", "API.md")
    rel = "docs/API.md"
    if os.path.exists(docs_path):
        with open(docs_path, encoding="utf-8") as f:
            docs = f.read()
        if METRIC_DOCS_BEGIN not in docs or METRIC_DOCS_END not in docs:
            findings.append(
                Finding(
                    RULE,
                    rel,
                    1,
                    "docs/API.md lacks the generated metrics-table "
                    "markers; run python scripts/tslint.py "
                    "--regen-metric-docs",
                )
            )
        else:
            block = (
                docs.split(METRIC_DOCS_BEGIN, 1)[1]
                .split(METRIC_DOCS_END, 1)[0]
                .strip()
            )
            expected = render_metric_table(
                collect_instruments(project.root, project)
            ).strip()
            if block != expected:
                findings.append(
                    Finding(
                        RULE,
                        rel,
                        1,
                        "docs/API.md metrics table is stale (does not "
                        "match the tree's instrument registrations); run "
                        "python scripts/tslint.py --regen-metric-docs",
                    )
                )
    return findings


def main() -> int:
    """Entry point kept for the scripts/check_metric_names.py shim."""
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    )
    sites = collect_sites(root)
    problems = check_names(root, sites)
    if problems:
        for problem in problems:
            print(f"check_metric_names: {problem}", file=sys.stderr)
        print(
            f"check_metric_names: FAILED ({len(problems)} problem(s) across "
            f"{len(sites)} instrument call sites)",
            file=sys.stderr,
        )
        return 1
    names = {name for _, _, name, _ in sites}
    print(
        f"check_metric_names: OK — {len(sites)} call sites, "
        f"{len(names)} distinct metric names, no conflicts"
    )
    return 0
