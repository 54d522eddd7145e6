"""stage-discipline: timeline stage labels come from the registered catalog.

The stage-attribution layer (observability/timeline.py) only answers
"which stage ate the p99 budget" if client and volume sites record their
wall-clock segments under the SAME stage catalog: a volume labeling its landing
bracket ``"landing_copy"`` while the client records ``"landing"`` splits
one stage into two digests and the dominant-stage vote silently fragments.
``ts.slo_report()``, the loadgen scoreboard merge, and the fleet_scale
bench all assume the catalog is closed.

Rule: every ``observe_stage(op, stage, ...)`` call site must pass the
stage as a STRING LITERAL naming an entry of
``observability.timeline.STAGE_CATALOG``:

- a literal outside the catalog is drift (add the stage to the catalog
  deliberately, in review, or use a registered one);
- a non-literal stage argument is flagged too — a free-string variable
  defeats the static guarantee (the runtime ValueError in
  ``StageQuantiles.observe`` is the backstop, but it fires in production,
  not in review).

``observability/timeline.py`` itself (the catalog's home: the module-level
helpers forward through these names) is exempt.
"""

from __future__ import annotations

import ast

from torchstore_tpu.analysis.core import Finding, Project, dotted_name

RULE = "stage-discipline"

_EXEMPT_FILES = ("torchstore_tpu/observability/timeline.py",)


def _catalog() -> frozenset[str]:
    from torchstore_tpu.observability.timeline import STAGE_CATALOG

    return STAGE_CATALOG


def _stage_arg(call: ast.Call) -> ast.expr | None:
    """The ``stage`` argument of an observe_stage(op, stage, dur) call."""
    if len(call.args) >= 2:
        return call.args[1]
    for kw in call.keywords:
        if kw.arg == "stage":
            return kw.value
    return None


def check(project: Project) -> list[Finding]:
    catalog = _catalog()
    findings: list[Finding] = []
    for sf in project.files:
        if sf.tree is None or sf.path in _EXEMPT_FILES:
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name is None or name.split(".")[-1] != "observe_stage":
                continue
            stage = _stage_arg(node)
            if stage is None:
                continue  # arity error: Python itself will fail louder
            if isinstance(stage, ast.Constant) and isinstance(
                stage.value, str
            ):
                if stage.value not in catalog:
                    findings.append(
                        Finding(
                            rule=RULE,
                            path=sf.path,
                            line=node.lineno,
                            message=(
                                f"stage {stage.value!r} is not in "
                                "observability.timeline.STAGE_CATALOG "
                                f"({sorted(catalog)}): free-string stage "
                                "labels fragment the dominant-stage "
                                "attribution — register the stage "
                                "deliberately or use a catalog entry"
                            ),
                        )
                    )
                continue
            findings.append(
                Finding(
                    rule=RULE,
                    path=sf.path,
                    line=node.lineno,
                    message=(
                        "observe_stage called with a non-literal stage: "
                        "the stage catalog is enforced statically — pass "
                        "a STAGE_CATALOG string literal so drift is "
                        "caught in review, not at runtime"
                    ),
                )
            )
    return findings
