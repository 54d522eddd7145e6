"""Tier-1 guard for the static-analysis suite (torchstore_tpu/analysis/).

Two layers:

1. **Checker self-tests on fixture snippets** — each of the eight rules must
   catch a seeded defect (a synthetic endpoint typo, a swallowed
   CancelledError, an unregistered env var, ...) and stay quiet on the
   matching clean snippet, so a refactor of the suite cannot silently turn
   a rule into a no-op.
2. **The zero-new-findings gate** — the full suite over THIS repo against
   the committed baseline (tslint_baseline.json) must report no new
   findings, and the orphan-task / cancellation-swallow rules must not be
   baselined away (their fixes landed with the checkers that found them).
"""

import asyncio
import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT))

from torchstore_tpu.analysis import (  # noqa: E402
    DEFAULT_BASELINE,
    Project,
    load_baseline,
    run_checks,
    save_baseline,
)
from torchstore_tpu.analysis.checkers import (  # noqa: E402
    CHECKERS,
    async_blocking,
    cancellation,
    endpoint_drift,
    env_registry,
    fork_safety,
    history_discipline,
    landing_copy,
    metric_discipline,
    orphan_task,
)


def _project(tmp_path, files: dict) -> Project:
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return Project(str(tmp_path))


def _msgs(findings, rule=None):
    return [f.message for f in findings if rule is None or f.rule == rule]


# --------------------------------------------------------------------------
# 1. endpoint-drift
# --------------------------------------------------------------------------

_ACTOR_SRC = """
    class Vol:
        @endpoint
        async def put(self, buffer, metas): ...

        @endpoint
        async def stats(self, include_volumes=False): ...
    """


def test_endpoint_drift_catches_typo_and_arity(tmp_path):
    proj = _project(
        tmp_path,
        {
            "torchstore_tpu/vol.py": _ACTOR_SRC,
            "torchstore_tpu/caller.py": """
                async def go(ref):
                    await ref.put.call_one(buf, metas)          # ok
                    await ref.putt.call_one(buf, metas)         # typo
                    await ref.put.call_one(buf)                 # missing arg
                    await ref.stats.call_one(include_volumes=True)  # ok kw
                    await ref.stats.call_one(bogus=True)        # unknown kw
                    put = volume.actor.put
                    await put.with_timeout(9).call_one(b, m)    # ok (alias)
                    await put.with_timeout(9).call_one()        # alias, bad arity
                """,
        },
    )
    found = endpoint_drift.check(proj)
    msgs = _msgs(found)
    assert any("unknown endpoint 'putt'" in m for m in msgs), msgs
    assert sum("endpoint 'put'" in m and "matches no endpoint" in m for m in msgs) == 2
    assert any("bogus" in m for m in msgs), msgs
    # exactly the four seeded defects, nothing else
    assert len(found) == 4, [f.render() for f in found]


def test_endpoint_drift_live_coverage_not_vacuous():
    """The real tree must expose a meaningful surface to the checker — a
    scan-scope regression would otherwise pass the gate vacuously."""
    proj = Project(str(REPO_ROOT))
    endpoints = endpoint_drift.collect_endpoints(proj)
    assert len(endpoints) >= 25, sorted(endpoints)
    assert "put" in endpoints and "reserve_prewarm" in endpoints
    assert endpoint_drift.check(proj) == []


# --------------------------------------------------------------------------
# 2. async-blocking
# --------------------------------------------------------------------------


def test_async_blocking_flags_blocking_calls(tmp_path):
    proj = _project(
        tmp_path,
        {
            "torchstore_tpu/m.py": """
                import asyncio, time, subprocess

                async def bad():
                    time.sleep(1)
                    subprocess.run(["true"])
                    open("/tmp/x")
                    fut.result()

                async def good(loop):
                    await asyncio.sleep(1)

                    def thunk():
                        time.sleep(1)  # executor thunk: exempt

                    await loop.run_in_executor(None, thunk)
                """,
        },
    )
    msgs = _msgs(async_blocking.check(proj))
    assert len(msgs) == 4, msgs
    assert all("'bad'" in m for m in msgs), msgs


# --------------------------------------------------------------------------
# 3. cancellation-swallow
# --------------------------------------------------------------------------


def test_cancellation_swallow_rules(tmp_path):
    proj = _project(
        tmp_path,
        {
            "torchstore_tpu/m.py": """
                import asyncio

                async def swallow_base():
                    try:
                        await x()
                    except BaseException:
                        pass  # seeded defect

                async def swallow_bare():
                    try:
                        await x()
                    except:
                        log()  # seeded defect

                async def swallow_cancel():
                    try:
                        await x()
                    except asyncio.CancelledError:
                        return  # seeded defect

                async def ok_reraise():
                    try:
                        await x()
                    except BaseException:
                        cleanup()
                        raise

                async def ok_forward_idiom():
                    try:
                        await x()
                    except asyncio.CancelledError:
                        raise
                    except BaseException as exc:
                        report(exc)

                def sync_is_exempt():
                    try:
                        run()
                    except BaseException:
                        pass
                """,
        },
    )
    found = cancellation.check(proj)
    assert len(found) == 3, [f.render() for f in found]
    assert {"swallow_base", "swallow_bare", "swallow_cancel"} == {
        m.split("async def ")[1].split("'")[1] for m in _msgs(found)
    }


# --------------------------------------------------------------------------
# 4. orphan-task
# --------------------------------------------------------------------------


def test_orphan_task_rules(tmp_path):
    proj = _project(
        tmp_path,
        {
            "torchstore_tpu/m.py": """
                import asyncio

                def fire_and_forget():
                    asyncio.create_task(work())  # seeded defect

                def discard_only(tasks):
                    t = asyncio.ensure_future(work())
                    tasks.add(t)
                    t.add_done_callback(tasks.discard)  # seeded defect

                def logged(tasks):
                    t = asyncio.create_task(work())
                    tasks.add(t)
                    t.add_done_callback(_log_failure)

                class C:
                    def owner_managed(self):
                        self._t = asyncio.create_task(work())

                async def awaited():
                    t = asyncio.create_task(work())
                    await t

                async def gathered():
                    t = asyncio.create_task(work())
                    await asyncio.gather(t)
                """,
        },
    )
    found = orphan_task.check(proj)
    assert len(found) == 2, [f.render() for f in found]
    assert any("fire-and-forget" in m for m in _msgs(found))
    assert any("set discard" in m for m in _msgs(found))


# --------------------------------------------------------------------------
# 5. fork-safety
# --------------------------------------------------------------------------


def test_fork_safety_rules(tmp_path):
    proj = _project(
        tmp_path,
        {
            "torchstore_tpu/bad.py": """
                import threading
                _registry = {}
                _lock = threading.Lock()
                RULE_TABLE = {"a": 1}   # constant convention: exempt
                _FROZEN = frozenset()   # immutable: exempt
                """,
            "torchstore_tpu/good.py": """
                _registry = {}

                def reinit_after_fork():
                    _registry.clear()
                """,
            "torchstore_tpu/pragma.py": """
                _cache = {}  # tslint: disable=fork-safety
                """,
            "scripts/tool.py": """
                _state = {}  # scripts never run inside forked actors
                """,
        },
    )
    found = fork_safety.check(proj)
    # the raw checker sees the pragma'd file too; suppression is run_checks' job
    assert {f.path for f in found} == {
        "torchstore_tpu/bad.py",
        "torchstore_tpu/pragma.py",
    }
    assert sum(f.path == "torchstore_tpu/bad.py" for f in found) == 2
    result = run_checks(str(tmp_path), rules=["fork-safety"], project=proj)
    assert {f.path for f in result.findings} == {"torchstore_tpu/bad.py"}


# --------------------------------------------------------------------------
# 6. env-registry
# --------------------------------------------------------------------------

_FIXTURE_CONFIG = """
    ENV_REGISTRY = (
        EnvVar("TORCHSTORE_TPU_FOO", "int", 7, "Foo knob."),
        EnvVar("TORCHSTORE_TPU_DEAD", "str", None, "Referenced nowhere."),
    )
    ENV_PREFIXES = ("TORCHSTORE_TPU_DYN_",)
    """


def test_env_registry_rules(tmp_path):
    proj = _project(
        tmp_path,
        {
            "torchstore_tpu/config.py": _FIXTURE_CONFIG,
            "torchstore_tpu/m.py": """
                import os
                ok = os.environ.get("TORCHSTORE_TPU_FOO", "7")
                unregistered = os.environ.get("TORCHSTORE_TPU_BAR")  # seeded
                dyn = os.environ.get("TORCHSTORE_TPU_DYN_THING")     # prefix ok
                drifted = os.environ.get("TORCHSTORE_TPU_FOO", "9")  # seeded
                """,
        },
    )
    msgs = _msgs(env_registry.check(proj))
    assert any("'TORCHSTORE_TPU_BAR'" in m and "not declared" in m for m in msgs), msgs
    assert any("'TORCHSTORE_TPU_DEAD'" in m and "dead knob" in m for m in msgs), msgs
    assert any("defaults must not fork" in m for m in msgs), msgs
    assert any("docs/API.md is missing" in m for m in msgs), msgs
    assert not any("TORCHSTORE_TPU_DYN_THING" in m for m in msgs), msgs
    assert len(msgs) == 4, msgs


def test_env_registry_bool_default_comparison(tmp_path):
    """bool registry defaults must compare by _env_bool semantics, not
    bool("0") truthiness: True vs "0" is drift, False vs "0" is not."""
    proj = _project(
        tmp_path,
        {
            "torchstore_tpu/config.py": """
                ENV_REGISTRY = (
                    EnvVar("TORCHSTORE_TPU_ON", "bool", True, "On knob."),
                    EnvVar("TORCHSTORE_TPU_OFF", "bool", False, "Off knob."),
                )
                """,
            "torchstore_tpu/m.py": """
                import os
                drift = os.environ.get("TORCHSTORE_TPU_ON", "0")   # seeded
                fine = os.environ.get("TORCHSTORE_TPU_OFF", "0")   # equivalent
                also = os.environ.get("TORCHSTORE_TPU_ON", "1")    # equivalent
                """,
        },
    )
    msgs = [
        m for m in _msgs(env_registry.check(proj)) if "defaults must not fork" in m
    ]
    assert len(msgs) == 1 and "TORCHSTORE_TPU_ON" in msgs[0], msgs


def test_env_registry_docs_block_roundtrip(tmp_path):
    entries, prefixes, _ = env_registry.parse_registry(
        textwrap.dedent(_FIXTURE_CONFIG)
    )
    assert [e.name for e in entries] == ["TORCHSTORE_TPU_FOO", "TORCHSTORE_TPU_DEAD"]
    assert prefixes == ["TORCHSTORE_TPU_DYN_"]
    table = env_registry.render_env_table(entries)
    proj = _project(
        tmp_path,
        {
            "torchstore_tpu/config.py": _FIXTURE_CONFIG,
            "torchstore_tpu/m.py": """
                import os
                a = os.environ.get("TORCHSTORE_TPU_FOO", "7")
                b = os.environ.get("TORCHSTORE_TPU_DEAD")
                """,
        },
    )
    docs = tmp_path / "docs" / "API.md"
    docs.parent.mkdir()
    docs.write_text(
        f"# API\n\n{env_registry.DOCS_BEGIN}\n{table}\n{env_registry.DOCS_END}\n"
    )
    assert env_registry.check(proj) == []
    # a stale table (entry edited without regen) is a finding
    docs.write_text(
        f"# API\n\n{env_registry.DOCS_BEGIN}\nstale\n{env_registry.DOCS_END}\n"
    )
    msgs = _msgs(env_registry.check(proj))
    assert any("stale" in m for m in msgs), msgs


# --------------------------------------------------------------------------
# 7. metric-discipline
# --------------------------------------------------------------------------


def test_metric_discipline_rules(tmp_path):
    proj = _project(
        tmp_path,
        {
            "torchstore_tpu/a.py": """
                from torchstore_tpu.observability import metrics as m
                _C = m.counter("ts_thing_total", "help")
                _BAD = m.gauge("Bad-Name", "not snake case")
                _NOPREFIX = m.counter("thing_total", "missing ts_")

                def use(key):
                    _C.inc(key=key)  # unbounded label: seeded defect
                    _C.inc(op="put")  # allowlisted: ok

                def trace():
                    with span("Bad Span"):  # seeded defect
                        pass
                    with span("rpc/put"):
                        pass
                """,
            "torchstore_tpu/b.py": """
                from torchstore_tpu.observability import metrics as m
                _G = m.gauge("ts_thing_total")
                """,
        },
    )
    msgs = _msgs(metric_discipline.check(proj))
    assert any("conflicting kinds" in m and "ts_thing_total" in m for m in msgs), msgs
    assert any("Bad-Name" in m and "snake_case" in m for m in msgs), msgs
    assert any("'thing_total'" in m and "prefix" in m for m in msgs), msgs
    assert any("label key 'key'" in m for m in msgs), msgs
    assert any("span name 'Bad Span'" in m for m in msgs), msgs
    assert len(msgs) == 5, msgs


def test_metric_docs_table_drift(tmp_path):
    """The generated docs/API.md metrics table is lint-enforced: missing
    markers, a stale table, and an up-to-date table each behave; fixture
    trees WITHOUT docs/API.md (every other test here) skip the rule."""
    src = {
        "torchstore_tpu/a.py": """
            from torchstore_tpu.observability import metrics as m
            _C = m.counter("ts_docs_total", "counted things")
            _G = m.gauge("ts_docs_gauge", "gauged things")
            """,
    }
    # No docs/API.md at all: rule silently skips (fixture-tree contract).
    proj = _project(tmp_path / "nodocs", src)
    assert _msgs(metric_discipline.check(proj)) == []
    # docs/API.md without markers: told to regen.
    proj = _project(
        tmp_path / "nomark", {**src, "docs/API.md": "# api\n"}
    )
    msgs = _msgs(metric_discipline.check(proj))
    assert any("markers" in m for m in msgs), msgs
    # Stale table between markers: drift finding.
    stale = (
        "# api\n\n"
        + metric_discipline.METRIC_DOCS_BEGIN
        + "\n| Metric | Kind | Description |\n|---|---|---|\n"
        + "| `ts_gone_total` | counter | deleted metric |\n"
        + metric_discipline.METRIC_DOCS_END
        + "\n"
    )
    proj = _project(tmp_path / "stale", {**src, "docs/API.md": stale})
    msgs = _msgs(metric_discipline.check(proj))
    assert any("stale" in m for m in msgs), msgs
    # Regenerated table: clean.
    proj = _project(tmp_path / "fresh", src)
    fresh_table = metric_discipline.render_metric_table(
        metric_discipline.collect_instruments(str(tmp_path / "fresh"), proj)
    )
    (tmp_path / "fresh" / "docs").mkdir()
    (tmp_path / "fresh" / "docs" / "API.md").write_text(
        "# api\n\n"
        + metric_discipline.METRIC_DOCS_BEGIN
        + "\n"
        + fresh_table
        + "\n"
        + metric_discipline.METRIC_DOCS_END
        + "\n"
    )
    assert _msgs(metric_discipline.check(proj)) == []
    assert "ts_docs_total" in fresh_table and "counted things" in fresh_table


# --------------------------------------------------------------------------
# history-discipline
# --------------------------------------------------------------------------


def test_history_discipline_rules(tmp_path):
    """Detector series selectors: literal + registered passes (including
    ``:rate`` derivations, label globs, and histogram ``_count`` series);
    a non-literal selector, a glob in the NAME part, and an unregistered
    name are each a finding."""
    proj = _project(
        tmp_path,
        {
            "torchstore_tpu/metrics_def.py": """
                from torchstore_tpu.observability import metrics as m
                _G = m.gauge("ts_landing_inflight", "open landing brackets")
                _C = m.counter("ts_client_ops_total", "client ops")
                _H = m.histogram("ts_op_seconds", "op latency")
                """,
            "torchstore_tpu/dets.py": """
                from torchstore_tpu.observability.detect import Detector

                SELECTOR = "ts_landing_inflight"

                GOOD = (
                    Detector(name="a", series="ts_landing_inflight", kind="sustained"),
                    Detector("b", 'ts_client_ops_total:rate{op="put"}', "ramp"),
                    Detector(name="c", series="ts_op_seconds_count", kind="drift"),
                    Detector(name="d", series='ts_landing_inflight{volume="*"}', kind="ramp"),
                    Detector(name="e", series="ts_landing_inflight*", kind="ramp"),
                )
                BAD_NONLITERAL = Detector(name="f", series=SELECTOR, kind="sustained")
                BAD_GLOB = Detector(name="g", series="ts_*_inflight", kind="sustained")
                BAD_UNREGISTERED = Detector(name="h", series="ts_gone_gauge", kind="drift")
                """,
        },
    )
    msgs = _msgs(history_discipline.check(proj))
    assert any("non-literal" in m for m in msgs), msgs
    assert any("globs the" in m and "ts_*_inflight" in m for m in msgs), msgs
    assert any(
        "does not resolve" in m and "ts_gone_gauge" in m for m in msgs
    ), msgs
    assert len(msgs) == 3, msgs


# --------------------------------------------------------------------------
# Framework: pragmas, baseline, runner
# --------------------------------------------------------------------------


def test_pragma_suppresses_findings(tmp_path):
    _project(
        tmp_path,
        {
            "torchstore_tpu/m.py": """
                import asyncio

                def spawn():
                    asyncio.create_task(work())  # tslint: disable=orphan-task
                """,
        },
    )
    result = run_checks(str(tmp_path), rules=["orphan-task"])
    assert result.findings == []


def test_file_pragma_suppresses_whole_file(tmp_path):
    _project(
        tmp_path,
        {
            "torchstore_tpu/m.py": """
                # tslint: disable-file=orphan-task
                import asyncio

                def spawn():
                    asyncio.create_task(work())
                """,
        },
    )
    result = run_checks(str(tmp_path), rules=["orphan-task"])
    assert result.findings == []


def test_baseline_splits_new_from_grandfathered(tmp_path):
    _project(
        tmp_path,
        {
            "torchstore_tpu/m.py": """
                import asyncio

                def one():
                    asyncio.create_task(work())
                """,
        },
    )
    # grandfather the current state
    result = run_checks(str(tmp_path), rules=["orphan-task"])
    assert len(result.new) == 1
    baseline = tmp_path / "baseline.json"
    save_baseline(str(baseline), result.findings)
    result = run_checks(
        str(tmp_path), rules=["orphan-task"], baseline_path=str(baseline)
    )
    assert result.new == [] and len(result.baselined) == 1
    # a SECOND, identical-message defect in the same file exceeds the count
    (tmp_path / "torchstore_tpu" / "m.py").write_text(
        textwrap.dedent(
            """
            import asyncio

            def one():
                asyncio.create_task(work())

            def two():
                asyncio.create_task(work())
            """
        )
    )
    result = run_checks(
        str(tmp_path), rules=["orphan-task"], baseline_path=str(baseline)
    )
    assert len(result.new) == 1 and len(result.baselined) == 1


def test_landing_copy_rules(tmp_path):
    """landing-copy: bare np.copyto in transport/landing modules is flagged;
    native.py and out-of-scope modules are exempt; the native helpers pass."""
    project = _project(
        tmp_path,
        {
            "torchstore_tpu/transport/somexport.py": """
                import numpy as np
                def land(dst, src):
                    np.copyto(dst, src)  # seeded defect
            """,
            "torchstore_tpu/client.py": """
                import numpy as np
                from torchstore_tpu.native import copy_into
                def land(dst, src):
                    copy_into(dst, src)  # the sanctioned path
            """,
            "torchstore_tpu/native.py": """
                import numpy as np
                def fallback(dst, src):
                    np.copyto(dst, src)  # the fallback IS allowed here
            """,
            "torchstore_tpu/torch_interop.py": """
                import numpy as np
                def convert(dst, src):
                    np.copyto(dst, src)  # out of scope (not a landing module)
            """,
        },
    )
    findings = landing_copy.check(project)
    assert len(findings) == 1
    assert findings[0].path == "torchstore_tpu/transport/somexport.py"
    assert "np.copyto" in findings[0].message


def test_landing_copy_pragma(tmp_path):
    project = _project(
        tmp_path,
        {
            "torchstore_tpu/transport/x.py": """
                import numpy as np
                def land(dst, src):
                    np.copyto(dst, src)  # tslint: disable=landing-copy
            """,
        },
    )
    result = run_checks(str(tmp_path), rules=["landing-copy"])
    assert result.new == []


def test_retry_discipline_flags_bare_sleep_retry_loop(tmp_path):
    """retry-discipline: a constant-delay sleep inside a try-bearing loop is
    the ad-hoc retry idiom RetryPolicy replaced; policy-derived delays,
    pacing loops without exception handling, sleep(0) yields, and closures
    merely DEFINED inside a loop all pass."""
    from torchstore_tpu.analysis.checkers import retry_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/bad.py": """
                import asyncio, time
                async def drain():
                    while True:
                        try:
                            await push()
                            return
                        except ConnectionError:
                            await asyncio.sleep(1.0)  # seeded defect
                def sync_drain():
                    for _ in range(3):
                        try:
                            return push()
                        except OSError:
                            time.sleep(0.5)  # seeded defect
            """,
            "torchstore_tpu/good.py": """
                import asyncio
                async def drain(policy):
                    deadline = policy.start()
                    attempt = 0
                    while policy.should_retry(attempt, deadline):
                        try:
                            await push()
                            return
                        except ConnectionError:
                            await asyncio.sleep(policy.backoff(attempt))
                            attempt += 1
                async def pace(interval):
                    while True:
                        await asyncio.sleep(interval)  # pacing, no except
                async def batched():
                    while True:
                        try:
                            await one()
                        except ValueError:
                            pass
                        await asyncio.sleep(0)  # cooperative yield
                async def definer():
                    while True:
                        try:
                            spawn(lambda: time.sleep(1.0))
                            async def helper():
                                await asyncio.sleep(2.0)  # closure: opaque
                            return helper
                        except RuntimeError:
                            raise
            """,
        },
    )
    findings = retry_discipline.check(project)
    assert sorted((f.path, f.line) for f in findings) == [
        ("torchstore_tpu/bad.py", 9),
        ("torchstore_tpu/bad.py", 15),
    ]


def test_retry_discipline_flags_unregistered_faultpoint(tmp_path):
    from torchstore_tpu.analysis.checkers import retry_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/sites.py": """
                from torchstore_tpu import faults
                async def serve():
                    await faults.afire("volume.put")       # registered
                    faults.fire("volume.typo")             # drift
                    faults.arm("contoller.notify", "raise")  # drift
                    faults.fire(dynamic_name)              # out of scope
            """,
        },
    )
    findings = retry_discipline.check(project)
    assert len(findings) == 2
    assert all("not in faults.REGISTRY" in f.message for f in findings)
    assert {f.line for f in findings} == {5, 6}


def test_unknown_rule_rejected(tmp_path):
    (tmp_path / "torchstore_tpu").mkdir()
    with pytest.raises(ValueError, match="unknown rule"):
        run_checks(str(tmp_path), rules=["no-such-rule"])


# --------------------------------------------------------------------------
# The tier-1 gate: zero NEW findings on this repo
# --------------------------------------------------------------------------


def test_repo_is_clean_against_baseline():
    baseline = REPO_ROOT / DEFAULT_BASELINE
    assert baseline.exists(), "tslint_baseline.json must be committed"
    result = run_checks(str(REPO_ROOT), baseline_path=str(baseline))
    assert result.new == [], "NEW tslint findings:\n" + "\n".join(
        f.render() for f in result.new
    )
    assert set(result.rules) == set(CHECKERS)


def test_orphan_and_cancellation_rules_not_baselined_away():
    """Acceptance: the orphan-task and cancellation-swallow fixes landed
    WITH their checkers enabled — no grandfathered findings for either."""
    grandfathered = load_baseline(str(REPO_ROOT / DEFAULT_BASELINE))
    offenders = [
        key
        for key in grandfathered
        if key[0] in ("orphan-task", "cancellation-swallow")
    ]
    assert offenders == []


def test_cli_json_runs_clean():
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "scripts" / "tslint.py"), "--json"],
        capture_output=True,
        text=True,
        cwd=str(REPO_ROOT),
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["new"] == 0
    assert sorted(doc["rules"]) == sorted(CHECKERS)


def test_cli_fail_on_new_reports_seeded_defect(tmp_path):
    """--fail-on-new gate mode: a synthetic endpoint typo added to a copy of
    the scan scope fails the run and names the typo."""
    _project(
        tmp_path,
        {
            "torchstore_tpu/vol.py": _ACTOR_SRC,
            "torchstore_tpu/caller.py": """
                async def go(ref):
                    await ref.putt.call_one(1, 2)
                """,
        },
    )
    proc = subprocess.run(
        [
            sys.executable,
            str(REPO_ROOT / "scripts" / "tslint.py"),
            "--fail-on-new",
            "--rules",
            "endpoint-drift",
            "--root",
            str(tmp_path),
            "--no-baseline",
        ],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "putt" in proc.stdout


# --------------------------------------------------------------------------
# one-sided-discipline
# --------------------------------------------------------------------------


def test_one_sided_discipline_flags_raw_segment_reads(tmp_path):
    """one-sided-discipline: raw seg.view/strided_view and frombuffer(mmap)
    reads in client/direct modules are flagged; the blessed accessors and
    out-of-scope modules (the transport itself, numpy dtype-views) pass."""
    from torchstore_tpu.analysis.checkers import one_sided

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/client.py": """
                import numpy as np
                def bad(seg, meta, plan):
                    a = seg.strided_view(meta, 0, None)  # seeded defect
                    b = seg.view(meta)  # seeded defect
                    c = np.frombuffer(seg.mmap, dtype=np.uint64)  # seeded
                    return a, b, c
                def fine(arr):
                    return arr.view(np.uint8)  # numpy dtype view: no segment
            """,
            "torchstore_tpu/direct_weight_sync.py": """
                from torchstore_tpu.transport import shared_memory as shm
                def good(seg, meta):
                    return shm.segment_read_view(seg, meta)  # blessed path
            """,
            "torchstore_tpu/transport/shared_memory.py": """
                def stamped_read(seg, meta):
                    return seg.strided_view(meta, 0, None)  # implements it
            """,
        },
    )
    findings = one_sided.check(project)
    assert len(findings) == 3
    assert all(f.path == "torchstore_tpu/client.py" for f in findings)
    assert all("segment_read_view" in f.message for f in findings)


def test_one_sided_discipline_pragma(tmp_path):
    project = _project(
        tmp_path,
        {
            "torchstore_tpu/direct_weight_sync.py": """
                def writer(seg, meta):
                    # writer side publishes the seqlock itself
                    return seg.view(meta)  # tslint: disable=one-sided-discipline
            """,
        },
    )
    result = run_checks(str(tmp_path), rules=["one-sided-discipline"])
    assert result.new == []


def test_stream_discipline_flags_raw_watermark_reads(tmp_path):
    """stream-discipline: raw ``["watermarks"]`` subscripts and
    ``.get("watermarks")`` in acquire-side modules are flagged; the
    blessed helpers' home (stream_sync.py) and out-of-scope modules (the
    controller implements the protocol) pass."""
    from torchstore_tpu.analysis.checkers import stream_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/weight_channel.py": """
                async def acquire(client, state, key, version):
                    wm = state["watermarks"][key]  # seeded defect
                    ok = state.get("watermarks")  # seeded defect
                    return wm, ok
            """,
            "torchstore_tpu/client.py": """
                from torchstore_tpu import stream_sync
                def fine(state, keys, version):
                    return stream_sync.inconsistent_keys(state, keys, version)
            """,
            "torchstore_tpu/stream_sync.py": """
                def watermark_of(state, key):
                    return (state.get("watermarks") or {}).get(key)
            """,
            "torchstore_tpu/controller.py": """
                def server_side(rec, key, version):
                    rec["watermarks"][key] = version  # protocol home
            """,
        },
    )
    findings = stream_discipline.check(project)
    assert len(findings) == 2
    assert all(f.path == "torchstore_tpu/weight_channel.py" for f in findings)
    assert all("watermark_of" in f.message for f in findings)


def test_stream_discipline_pragma(tmp_path):
    project = _project(
        tmp_path,
        {
            "torchstore_tpu/state_dict_utils.py": """
                def debug_dump(state):
                    return dict(state["watermarks"])  # tslint: disable=stream-discipline
            """,
        },
    )
    result = run_checks(str(tmp_path), rules=["stream-discipline"])
    assert result.new == []


def test_stream_discipline_live_tree_clean():
    """The live tree stays clean under the new rule (baseline stays
    empty): every acquire-side watermark check routes through
    stream_sync's blessed helpers."""
    root = str(pathlib.Path(__file__).resolve().parents[1])
    result = run_checks(root, rules=["stream-discipline"])
    assert _msgs(result.findings, "stream-discipline") == []


def test_quant_discipline_flags_raw_scale_access(tmp_path):
    """quant-discipline: raw ``["scales"]`` subscripts / ``.get("scales")``
    in data-plane modules are flagged; the codec's home
    (state_dict_utils.py) and the arena-layout module (landing.py) pass."""
    from torchstore_tpu.analysis.checkers import quant_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/weight_channel.py": """
                def bad(marker, key):
                    s = marker["quant"]["scales"][key]  # seeded defect
                    t = marker.get("scales")  # seeded defect
                    return s, t
            """,
            "torchstore_tpu/transport/bulk.py": """
                def also_bad(blob_meta):
                    return blob_meta["scales"]  # seeded defect
            """,
            "torchstore_tpu/state_dict_utils.py": """
                def codec_home(info):
                    return info["scales"]  # the blessed home
            """,
            "torchstore_tpu/transport/landing.py": """
                def layout_home(layout):
                    return layout["scales"]  # the layout module
            """,
        },
    )
    findings = quant_discipline.check(project)
    by_path = {}
    for f in findings:
        by_path.setdefault(f.path, 0)
        by_path[f.path] += 1
    assert by_path == {
        "torchstore_tpu/weight_channel.py": 2,
        "torchstore_tpu/transport/bulk.py": 1,
    }, by_path


def test_quant_discipline_pragma(tmp_path):
    project = _project(
        tmp_path,
        {
            "torchstore_tpu/client.py": """
                def debug_dump(info):
                    return dict(info["scales"])  # tslint: disable=quant-discipline
            """,
        },
    )
    result = run_checks(str(tmp_path), rules=["quant-discipline"])
    assert result.new == []


def test_quant_discipline_live_tree_clean():
    """The live tree stays clean under the new rule (baseline stays
    empty): scale tables are only ever touched by the codec in
    state_dict_utils and the layout math in transport/landing.py."""
    root = str(pathlib.Path(__file__).resolve().parents[1])
    result = run_checks(root, rules=["quant-discipline"])
    assert _msgs(result.findings, "quant-discipline") == []


def test_one_sided_discipline_live_tree_clean():
    """The live tree stays clean under the new rule (baseline stays empty):
    every client/direct segment read goes through the stamped helpers, and
    the one writer-side staging view carries its justified pragma."""
    root = str(pathlib.Path(__file__).resolve().parents[1])
    result = run_checks(root, rules=["one-sided-discipline"])
    assert _msgs(result.findings, "one-sided-discipline") == []


# --------------------------------------------------------------------------
# shard-discipline (ISSUE 14)
# --------------------------------------------------------------------------


def test_shard_discipline_flags_raw_index_access(tmp_path):
    """shard-discipline: raw ``.index`` / ``._key_gens`` touches in the
    scoped modules (controller.py, client.py) are flagged; the metadata
    package (the state's home) and str/list ``.index(...)`` method calls
    pass."""
    from torchstore_tpu.analysis.checkers import shard_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/controller.py": """
                class Controller:
                    async def peek(self, key):
                        infos = self.index.get(key)  # seeded defect
                        gen = self._key_gens.get(key, 0)  # seeded defect
                        return infos, gen

                    def fine(self, keys):
                        return keys.index("a")  # list.index: a CALL, exempt
            """,
            "torchstore_tpu/client.py": """
                def bad(core):
                    return core.index["k"]  # seeded defect
            """,
            "torchstore_tpu/metadata/index_core.py": """
                class IndexCore:
                    def get(self, key):
                        return self.index.get(key)  # the state's home
            """,
            "torchstore_tpu/storage_volume.py": """
                def unscoped(store):
                    return store.index  # outside the metadata plane
            """,
        },
    )
    findings = shard_discipline.check(project)
    by_path = {}
    for f in findings:
        by_path.setdefault(f.path, 0)
        by_path[f.path] += 1
    assert by_path == {
        "torchstore_tpu/controller.py": 2,
        "torchstore_tpu/client.py": 1,
    }, by_path


def test_shard_discipline_pragma(tmp_path):
    project = _project(
        tmp_path,
        {
            "torchstore_tpu/controller.py": """
                def debug_dump(core):
                    return dict(core.index)  # tslint: disable=shard-discipline
            """,
        },
    )
    result = run_checks(str(tmp_path), rules=["shard-discipline"])
    assert result.new == []


def test_shard_discipline_live_tree_clean():
    """The live tree stays clean under the new rule (baseline stays
    empty): after the metadata-plane refactor, controller.py reaches the
    index only through ``self.idx`` (IndexCore locally, the RemoteIndex
    fan-out when sharded) — the property that makes shards=N safe."""
    root = str(pathlib.Path(__file__).resolve().parents[1])
    result = run_checks(root, rules=["shard-discipline"])
    assert result.new == [], [str(f) for f in result.new]


# --------------------------------------------------------------------------
# 14. stage-discipline
# --------------------------------------------------------------------------


def test_stage_discipline_flags_uncataloged_and_nonliteral_stages(tmp_path):
    """stage-discipline: an ``observe_stage`` call with a literal stage
    outside STAGE_CATALOG is drift; a non-literal stage defeats the
    static guarantee; catalog entries pass; timeline.py itself (the
    catalog's home) is exempt."""
    from torchstore_tpu.analysis.checkers import stage_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/client.py": """
                from torchstore_tpu.observability import timeline as obs_timeline
                def fine(dur):
                    obs_timeline.observe_stage("get", "landing", dur)
                def drifted(dur):
                    obs_timeline.observe_stage("get", "landing_copy", dur)
                def laundered(stage, dur):
                    obs_timeline.observe_stage("get", stage, dur)
            """,
            "torchstore_tpu/observability/timeline.py": """
                def observe_stage(op, stage, dur_s):
                    _stages.observe(op, stage, dur_s)
            """,
        },
    )
    findings = stage_discipline.check(project)
    assert len(findings) == 2, [str(f) for f in findings]
    assert all(f.path == "torchstore_tpu/client.py" for f in findings)
    drift, nonliteral = sorted(findings, key=lambda f: f.line)
    assert "landing_copy" in drift.message
    assert "non-literal" in nonliteral.message


def test_stage_discipline_keyword_stage_checked(tmp_path):
    from torchstore_tpu.analysis.checkers import stage_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/storage_volume.py": """
                from torchstore_tpu.observability.timeline import observe_stage
                def serve(dur):
                    observe_stage("put", stage="stamp_verfy", dur_s=dur)
            """,
        },
    )
    findings = stage_discipline.check(project)
    assert len(findings) == 1
    assert "stamp_verfy" in findings[0].message


def test_stage_discipline_pragma(tmp_path):
    project = _project(
        tmp_path,
        {
            "torchstore_tpu/client.py": """
                from torchstore_tpu.observability import timeline as obs_timeline
                def experimental(dur):
                    obs_timeline.observe_stage("get", "prototype", dur)  # tslint: disable=stage-discipline
            """,
        },
    )
    result = run_checks(str(tmp_path), rules=["stage-discipline"])
    assert result.new == []


def test_stage_discipline_live_tree_clean():
    """The live tree stays clean under the new rule (baseline stays
    empty): every client- and volume-side stage segment records under a
    STAGE_CATALOG name, so the dominant-stage attribution in
    ``ts.slo_report()`` folds both sides into one stage catalog."""
    root = str(pathlib.Path(__file__).resolve().parents[1])
    result = run_checks(root, rules=["stage-discipline"])
    assert _msgs(result.findings, "stage-discipline") == []


# --------------------------------------------------------------------------
# 15. control-discipline
# --------------------------------------------------------------------------


def test_control_discipline_flags_silent_actuation(tmp_path):
    """control-discipline: an actuator call (``migrate_key``, a
    ``tier_sweep`` endpoint wrapper, a ``_relay_prefer`` re-parent)
    inside ``control/`` with no decision-audit call in the same function
    is flagged; functions routing through ``self._decision(...)`` or
    ``record("decision", ...)`` pass, as do the same primitives outside
    the control package (auto-repair owns its own event discipline)."""
    from torchstore_tpu.analysis.checkers import control_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/control/engine.py": """
                class Engine:
                    async def silent_move(self, key, src, dst):
                        return await self.host.idx.migrate_key(
                            key, src, dst, drop_src=True
                        )  # seeded defect: no decision event

                    async def silent_demote(self, ref, keys):
                        await ref.tier_sweep.call_one({}, keys)  # seeded defect

                    def silent_reparent(self, host, channel, order):
                        host._relay_prefer[channel] = tuple(order)  # seeded defect

                    async def audited_move(self, snap, action):
                        await self.host.idx.migrate_key(
                            action.subject, action.src, action.dst, drop_src=True
                        )
                        return self._decision(snap, action, "applied")

                    def audited_reparent(self, host, channel, order, recorder):
                        host._relay_prefer[channel] = tuple(order)
                        recorder.record("decision", "control/relay", order=order)
            """,
            "torchstore_tpu/metadata/index_core.py": """
                async def migrate_key(self, key, src, dst, drop_src):
                    return await self._do_migrate(key, src, dst, drop_src)
            """,
            "torchstore_tpu/controller.py": """
                async def auto_repair(idx, key, src, dst):
                    return await idx.migrate_key(key, src, dst, drop_src=False)
            """,
        },
    )
    findings = control_discipline.check(project)
    assert all(f.path == "torchstore_tpu/control/engine.py" for f in findings)
    flagged = sorted(
        (msg.split("'")[1], msg.split("'")[3])
        for msg in _msgs(findings, "control-discipline")
    )
    assert flagged == [
        ("_relay_prefer", "silent_reparent"),
        ("migrate_key", "silent_move"),
        ("tier_sweep", "silent_demote"),
    ], flagged


def test_control_discipline_nested_scope_not_credited(tmp_path):
    """The audit call must live in the SAME function scope as the
    actuation — a ``_decision`` call inside a nested closure does not
    license the enclosing function's silent actuation."""
    from torchstore_tpu.analysis.checkers import control_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/control/engine.py": """
                async def outer(idx, key, src, dst, snap, action):
                    def audit_later():
                        return _decision(snap, action, "applied")
                    await idx.migrate_key(key, src, dst, drop_src=True)
                    return audit_later
            """,
        },
    )
    findings = control_discipline.check(project)
    assert len(findings) == 1, _msgs(findings)
    assert "'outer'" in findings[0].message


def test_control_discipline_pragma(tmp_path):
    from torchstore_tpu.analysis.checkers import control_discipline  # noqa: F401

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/control/engine.py": """
                async def bootstrap_copy(idx, key, src, dst):
                    # Bootstrap pre-seeding, not a policy action.
                    return await idx.migrate_key(key, src, dst, drop_src=False)  # tslint: disable=control-discipline
            """,
        },
    )
    result = run_checks(str(tmp_path), rules=["control-discipline"])
    assert result.new == []


def test_control_discipline_autoscale_scope(tmp_path):
    """ISSUE 18: the rule also covers ``torchstore_tpu/autoscale/`` and
    the fleet actuators (drain marking, retire detach/drop, blob
    demote/archive endpoint wrappers) — a silent scale actuation is
    flagged, an audited one passes, and the same names outside both
    planes stay out of scope (the api-layer spawn executor owns its own
    event discipline)."""
    from torchstore_tpu.analysis.checkers import control_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/autoscale/engine.py": """
                class Engine:
                    async def silent_drain(self, host, vid, dst, key):
                        host.mark_draining(vid)  # seeded defect
                        await host.idx.migrate_key(
                            key, vid, dst, drop_src=True
                        )  # seeded defect: no decision event

                    async def silent_demote(self, ref):
                        await ref.blob_sweep.call_one(8)  # seeded defect

                    async def audited_retire(self, host, vid, snap, action):
                        await host.idx.detach_volume(vid)
                        await host.drop_volume(vid)
                        return self._decision(snap, action, "applied")
            """,
            "torchstore_tpu/api.py": """
                async def spawn_executor(controller, vid, ref, hostname):
                    return await controller.attach_volume.call_one(
                        vid, ref, hostname
                    )
            """,
        },
    )
    findings = control_discipline.check(project)
    assert all(
        f.path == "torchstore_tpu/autoscale/engine.py" for f in findings
    )
    flagged = sorted(
        (msg.split("'")[1], msg.split("'")[3])
        for msg in _msgs(findings, "control-discipline")
    )
    assert flagged == [
        ("blob_sweep", "silent_demote"),
        ("mark_draining", "silent_drain"),
        ("migrate_key", "silent_drain"),
    ], flagged


def test_control_discipline_live_tree_clean():
    """The live tree stays clean under the new rule (baseline stays
    empty): every engine actuator path returns through ``_decision()``,
    the single chokepoint that stamps ``ts_control_decisions_total`` and
    the ``decision`` flight-recorder event."""
    root = str(pathlib.Path(__file__).resolve().parents[1])
    result = run_checks(root, rules=["control-discipline"])
    assert result.new == [], [str(f) for f in result.new]


# --------------------------------------------------------------------------
# 17. bracket-discipline (flow-aware, ISSUE 19)
# --------------------------------------------------------------------------


def test_bracket_discipline_catches_pr7_begin_landing_verbatim(tmp_path):
    """The exact PR 7 review finding, now mechanical: the pre-fix
    ``_begin_landing`` body where ``faults.afire`` can raise after
    ``begin_writes`` + ``_landing_open`` have run, leaking the inflight
    count and the odd stamps forever."""
    from torchstore_tpu.analysis.checkers import bracket_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/storage_volume.py": """
                from torchstore_tpu import faults

                class StorageVolume:
                    async def _begin_landing(self, pairs):
                        cache = self._shm_cache()
                        if cache is not None:
                            cache.begin_writes(pairs)
                        self._landing_open()
                        await faults.afire("shm.landing_stamp")

                    def _end_landing(self, pairs):
                        cache = self._shm_cache()
                        if cache is not None:
                            cache.end_writes(pairs)
                        self._landing_close()
            """,
        },
    )
    findings = bracket_discipline.check(project)
    raise_escapes = [f for f in findings if "raise can escape" in f.message]
    assert raise_escapes, [f.render() for f in findings]
    kinds = {f.message.split(" bracket", 1)[0] for f in raise_escapes}
    # Both the per-entry stamp bracket and the volume-wide inflight
    # counter leak on the raise path.
    assert "stamp-writes" in kinds and "landing-inflight" in kinds, kinds
    # And the NORMAL exit is licensed — _begin_landing's contract is to
    # return with the bracket open for the caller's try/finally.
    assert not any("return path" in f.message for f in findings), [
        f.render() for f in findings
    ]


def test_bracket_discipline_fixed_begin_landing_passes(tmp_path):
    """The shipped PR 7 fix shape (except BaseException: close; raise)
    is clean, with the open inside the guarded region."""
    from torchstore_tpu.analysis.checkers import bracket_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/storage_volume.py": """
                from torchstore_tpu import faults

                class StorageVolume:
                    async def _begin_landing(self, pairs):
                        cache = self._shm_cache()
                        if cache is not None:
                            cache.begin_writes(pairs)
                        try:
                            self._landing_open()
                            await faults.afire("shm.landing_stamp")
                        except BaseException:
                            self._end_landing(pairs)
                            raise

                    def _end_landing(self, pairs):
                        cache = self._shm_cache()
                        if cache is not None:
                            cache.end_writes(pairs)
                        self._landing_close()
            """,
        },
    )
    assert bracket_discipline.check(project) == []


def test_bracket_discipline_caller_must_close_on_all_paths(tmp_path):
    """A CALLER holding the landing bracket (it contains both begin and
    end) must close on every path: the try/finally idiom passes, a bare
    sequence is flagged on the raise path."""
    from torchstore_tpu.analysis.checkers import bracket_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/storage_volume.py": """
                class StorageVolume:
                    async def put_ok(self, pairs, reqs):
                        await self._begin_landing(pairs)
                        try:
                            await self._land(reqs)
                        finally:
                            self._end_landing(pairs)

                    async def put_leaky(self, pairs, reqs):
                        await self._begin_landing(pairs)
                        await self._land(reqs)
                        self._end_landing(pairs)
            """,
        },
    )
    findings = bracket_discipline.check(project)
    assert len(findings) == 1, [f.render() for f in findings]
    assert "'put_leaky'" in findings[0].message
    assert "raise can escape" in findings[0].message


def test_bracket_discipline_lease_pairs_only_when_paired(tmp_path):
    """Acquire-only functions transfer lease ownership to their caller and
    are skipped; a function with both acquire and release must not leak
    on the return path."""
    from torchstore_tpu.analysis.checkers import bracket_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/weight_channel.py": """
                class Channel:
                    async def acquire_only(self, client, version):
                        return await client.lease_acquire("o", self.name, version)

                    async def leaky_paired(self, client, version):
                        lease = await client.lease_acquire("o", self.name, version)
                        if await self.fast_path(lease):
                            return lease["payload"]
                        await client.lease_release(lease["lease_id"])
                        return None
            """,
        },
    )
    findings = bracket_discipline.check(project)
    assert findings, "paired acquire/release with an escaping return must flag"
    assert all("'leaky_paired'" in f.message for f in findings), [
        f.render() for f in findings
    ]


def test_bracket_discipline_live_tree_clean():
    """The live tree is clean (baseline stays empty): every bracket open
    reaches its close on all paths, or carries a justified pragma (the
    lease handoff in weight_channel._pinned_lease)."""
    result = run_checks(str(REPO_ROOT), rules=["bracket-discipline"])
    assert result.new == [], [f.render() for f in result.new]


# --------------------------------------------------------------------------
# 18. epoch-discipline (flow-aware, ISSUE 19)
# --------------------------------------------------------------------------


def test_epoch_discipline_catches_missing_bump_on_one_branch(tmp_path):
    """The historical shape: a structural mutation whose epoch bump sits
    behind a condition the mutation does not share — one branch returns
    with clients still routing on the stale placement."""
    from torchstore_tpu.analysis.checkers import epoch_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/controller.py": """
                class Controller:
                    async def notify_delete_batch(self, keys):
                        by_volume = self.core.delete_keys(keys)
                        if self.quiet:
                            return by_volume
                        self._bump_epoch()
                        return by_volume
            """,
        },
    )
    findings = epoch_discipline.check(project)
    assert len(findings) == 1, [f.render() for f in findings]
    assert "'delete_keys'" in findings[0].message
    assert "'notify_delete_batch'" in findings[0].message


def test_epoch_discipline_bump_on_every_path_passes(tmp_path):
    """Unconditional bump after the mutation passes; so does a bump routed
    through the coordinator endpoint wrapper, and a mutation whose only
    bump-free paths are explicit raises (the abort is not client-visible)."""
    from torchstore_tpu.analysis.checkers import epoch_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/controller.py": """
                class Controller:
                    async def delete_finish(self, keys):
                        by_volume = self.core.delete_keys(keys)
                        self._bump_epoch()
                        return by_volume

                    async def guarded(self, keys):
                        if self.sharded:
                            raise RuntimeError("route via shards")
                        out = self.core.delete_keys(keys)
                        self._bump_epoch()
                        return out
            """,
            "torchstore_tpu/metadata/shards.py": """
                class ControllerShard:
                    async def on_structural(self):
                        await self.coordinator.bump_placement_epoch.call_one()

                    async def drop(self, vid):
                        self.core.detach_volume(vid)
                        await self.coordinator.bump_placement_epoch.call_one()
            """,
        },
    )
    assert epoch_discipline.check(project) == []


def test_epoch_discipline_out_of_scope_files_exempt(tmp_path):
    """The same call names outside the three structural-state files are
    someone else's protocol (e.g. the autoscale engine calls detach_volume
    through the controller endpoint, which owns the bump)."""
    from torchstore_tpu.analysis.checkers import epoch_discipline

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/autoscale/engine.py": """
                class Engine:
                    async def retire(self, ref, vid):
                        await ref.detach_volume.call_one(vid)
            """,
        },
    )
    assert epoch_discipline.check(project) == []


def test_epoch_discipline_live_tree_clean():
    """The live tree is clean (baseline stays empty): every raw structural
    mutation is post-dominated by a bump, or carries a pragma naming the
    protocol that owns it (conditional-bump gates, the sharded 3-phase
    delete)."""
    result = run_checks(str(REPO_ROOT), rules=["epoch-discipline"])
    assert result.new == [], [f.render() for f in result.new]


# --------------------------------------------------------------------------
# 19. await-atomicity (flow-aware, ISSUE 19)
# --------------------------------------------------------------------------


def test_await_atomicity_catches_await_inside_publish_bracket(tmp_path):
    """An ``await`` injected between ``_publish_open`` and
    ``_publish_close`` parks the metadata seqlock odd for an unbounded
    time — every reader burns its torn-read retries."""
    from torchstore_tpu.analysis.checkers import await_atomicity

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/metadata/stamped.py": """
                import asyncio

                class MetaStampWriter:
                    async def publish_now(self, blob):
                        seq = self._publish_open()
                        self.words[2] = len(blob)
                        await asyncio.sleep(0)
                        self._publish_close(seq)
            """,
        },
    )
    findings = await_atomicity.check(project)
    assert len(findings) == 1, [f.render() for f in findings]
    assert "await suspends" in findings[0].message
    assert "'publish_now'" in findings[0].message


def test_await_atomicity_blocking_call_in_bracket_flagged_sync_too(tmp_path):
    """async_blocking's table is reused: a known-blocking call between the
    open and close wedges readers even in a sync writer."""
    from torchstore_tpu.analysis.checkers import await_atomicity

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/metadata/stamped.py": """
                import time

                class MetaStampWriter:
                    def publish_now(self, blob):
                        seq = self._publish_open()
                        time.sleep(0.01)
                        self._publish_close(seq)
            """,
        },
    )
    findings = await_atomicity.check(project)
    assert len(findings) == 1, [f.render() for f in findings]
    assert "known-blocking call (sleep)" in findings[0].message


def test_await_atomicity_clean_bracket_and_awaits_outside_pass(tmp_path):
    from torchstore_tpu.analysis.checkers import await_atomicity

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/metadata/stamped.py": """
                import asyncio

                class MetaStampWriter:
                    async def publish_now(self, payload_fn):
                        blob = await asyncio.to_thread(payload_fn)
                        seq = self._publish_open()
                        self.words[2] = len(blob)
                        self._publish_close(seq)
                        await asyncio.sleep(0)
            """,
        },
    )
    assert await_atomicity.check(project) == []


def test_await_atomicity_catches_lock_skipping_dict_mutation(tmp_path):
    """The PR 18 ledger-singleton race shape: one async path mutates a
    shared dict under the module's asyncio.Lock, a second path mutates it
    with no lock held — the lock guards nothing."""
    from torchstore_tpu.analysis.checkers import await_atomicity

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/puller.py": """
                import asyncio

                class Puller:
                    def __init__(self):
                        self._conns = {}
                        self._lock = asyncio.Lock()

                    async def get_conn(self, key):
                        async with self._lock:
                            if key not in self._conns:
                                self._conns[key] = dial(key)
                        return self._conns[key]

                    async def close(self):
                        self._conns.clear()
            """,
        },
    )
    findings = await_atomicity.check(project)
    assert len(findings) == 1, [f.render() for f in findings]
    assert "'_conns'" in findings[0].message
    assert "'close'" in findings[0].message


def test_await_atomicity_lock_held_everywhere_passes(tmp_path):
    from torchstore_tpu.analysis.checkers import await_atomicity

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/puller.py": """
                import asyncio

                class Puller:
                    def __init__(self):
                        self._conns = {}
                        self._lock = asyncio.Lock()

                    async def get_conn(self, key):
                        async with self._lock:
                            if key not in self._conns:
                                self._conns[key] = dial(key)
                            return self._conns[key]

                    async def close(self):
                        async with self._lock:
                            self._conns.clear()

                    async def read_only_ok(self, key):
                        return self._conns.get(key)
            """,
        },
    )
    assert await_atomicity.check(project) == []


def test_await_atomicity_live_tree_clean():
    """The live tree is clean (baseline stays empty): the stamp-bracket
    landing path is deliberately NOT in the atomic set (holding across the
    awaited landing copy is the design), and every shared dict mutation
    takes its module's lock."""
    result = run_checks(str(REPO_ROOT), rules=["await-atomicity"])
    assert result.new == [], [f.render() for f in result.new]


# --------------------------------------------------------------------------
# 20. decision-flow (flow-aware, ISSUE 19)
# --------------------------------------------------------------------------


def test_decision_flow_catches_early_return_skipping_audit(tmp_path):
    """The control-discipline blind spot, closed: the function DOES call
    ``_decision`` (same scope — the old rule passes), but an early return
    between the actuation and the audit leaves an unrecorded mutation."""
    from torchstore_tpu.analysis.checkers import control_discipline, decision_flow

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/control/engine.py": """
                class Engine:
                    async def apply_move(self, snap, action):
                        await self.host.idx.migrate_key(
                            action.subject, action.src, action.dst, drop_src=True
                        )
                        if snap.quiet:
                            return None
                        return self._decision(snap, action, "applied")
            """,
        },
    )
    # Same-scope rule is blind to this by design...
    assert control_discipline.check(project) == []
    # ...the flow-aware rule is not.
    findings = decision_flow.check(project)
    assert len(findings) == 1, [f.render() for f in findings]
    assert "'migrate_key'" in findings[0].message
    assert "'apply_move'" in findings[0].message


def test_decision_flow_post_dominating_and_dominating_audits_pass(tmp_path):
    """Both sanctioned idioms pass: act-then-return-_decision on every
    branch (the _apply_* shape), and audit-before-act (the checkpoint
    shape). An exception edge out of the actuator is exempt — _apply's
    wrapper funnels the error through _decision itself."""
    from torchstore_tpu.analysis.checkers import decision_flow

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/autoscale/engine.py": """
                class Engine:
                    async def apply_retire(self, snap, action):
                        await self.ref.detach_volume.call_one(action.vid)
                        if snap.drop:
                            await self.ref.drop_volume.call_one(action.vid)
                            return self._decision(snap, action, "dropped")
                        return self._decision(snap, action, "detached")

                    async def checkpoint(self, snap, action, ref):
                        self._decision(snap, action, "archiving")
                        await ref.blob_archive.call_one(action.vid)
            """,
        },
    )
    assert decision_flow.check(project) == []


def test_decision_flow_relay_reparent_needs_audit_on_path(tmp_path):
    from torchstore_tpu.analysis.checkers import decision_flow

    project = _project(
        tmp_path,
        {
            "torchstore_tpu/control/engine.py": """
                class Engine:
                    def reparent(self, host, channel, order, snap, action):
                        host._relay_prefer[channel] = tuple(order)
                        if not self.verbose:
                            return
                        self._decision(snap, action, "reparented")
            """,
        },
    )
    findings = decision_flow.check(project)
    assert len(findings) == 1, [f.render() for f in findings]
    assert "'_relay_prefer'" in findings[0].message


def test_decision_flow_live_tree_clean():
    """The live tree is clean (baseline stays empty): every engine
    actuator is dominated or post-dominated by its decision event on
    every normal path."""
    result = run_checks(str(REPO_ROOT), rules=["decision-flow"])
    assert result.new == [], [f.render() for f in result.new]


# --------------------------------------------------------------------------
# Fixture completeness: every registered rule has a dirty AND a clean fixture
# --------------------------------------------------------------------------

_ENV_ENTRIES, _ENV_PREFIXES, _ = env_registry.parse_registry(
    textwrap.dedent(_FIXTURE_CONFIG)
)
_ENV_DOCS_OK = (
    "# API\n\n"
    + env_registry.DOCS_BEGIN
    + "\n"
    + env_registry.render_env_table(_ENV_ENTRIES)
    + "\n"
    + env_registry.DOCS_END
    + "\n"
)

_STAGE_TIMELINE_STUB = """
    def observe_stage(op, stage, dur_s):
        _stages.observe(op, stage, dur_s)
    """

# rule -> (dirty fixture files, clean fixture files). The meta-test below
# holds this table to the CHECKERS registry, so registering rule #21 without
# a detectable-defect fixture and a quiet fixture fails tier-1 immediately —
# a rule nobody can demonstrate firing is a no-op waiting to happen.
RULE_FIXTURES = {
    "endpoint-drift": (
        {
            "torchstore_tpu/vol.py": _ACTOR_SRC,
            "torchstore_tpu/caller.py": """
                async def go(ref, buf, metas):
                    await ref.putt.call_one(buf, metas)
                """,
        },
        {
            "torchstore_tpu/vol.py": _ACTOR_SRC,
            "torchstore_tpu/caller.py": """
                async def go(ref, buf, metas):
                    await ref.put.call_one(buf, metas)
                """,
        },
    ),
    "async-blocking": (
        {
            "torchstore_tpu/m.py": """
                import time
                async def f():
                    time.sleep(1)
                """,
        },
        {
            "torchstore_tpu/m.py": """
                import asyncio
                async def f():
                    await asyncio.sleep(1)
                """,
        },
    ),
    "cancellation-swallow": (
        {
            "torchstore_tpu/m.py": """
                async def f(op):
                    try:
                        await op()
                    except BaseException:
                        pass
                """,
        },
        {
            "torchstore_tpu/m.py": """
                async def f(op):
                    try:
                        await op()
                    except BaseException:
                        cleanup()
                        raise
                """,
        },
    ),
    "orphan-task": (
        {
            "torchstore_tpu/m.py": """
                import asyncio
                def spawn():
                    asyncio.create_task(work())
                """,
        },
        {
            "torchstore_tpu/m.py": """
                import asyncio
                async def spawn():
                    t = asyncio.create_task(work())
                    await t
                """,
        },
    ),
    "fork-safety": (
        {
            "torchstore_tpu/m.py": """
                import threading
                _registry = {}
                """,
        },
        {
            "torchstore_tpu/m.py": """
                _registry = {}

                def reinit_after_fork():
                    _registry.clear()
                """,
        },
    ),
    "env-registry": (
        {
            "torchstore_tpu/config.py": _FIXTURE_CONFIG,
            "torchstore_tpu/m.py": """
                import os
                bad = os.environ.get("TORCHSTORE_TPU_BAR")
                """,
        },
        {
            "torchstore_tpu/config.py": _FIXTURE_CONFIG,
            "torchstore_tpu/m.py": """
                import os
                ok = os.environ.get("TORCHSTORE_TPU_FOO", "7")
                dead = os.environ.get("TORCHSTORE_TPU_DEAD")
                """,
            "docs/API.md": _ENV_DOCS_OK,
        },
    ),
    "metric-discipline": (
        {
            "torchstore_tpu/m.py": """
                from torchstore_tpu.observability import metrics as m
                _BAD = m.gauge("Bad-Name", "not snake case")
                """,
        },
        {
            "torchstore_tpu/m.py": """
                from torchstore_tpu.observability import metrics as m
                _C = m.counter("ts_thing_total", "help")
                """,
        },
    ),
    "landing-copy": (
        {
            "torchstore_tpu/transport/somexport.py": """
                import numpy as np
                def land(dst, src):
                    np.copyto(dst, src)
                """,
        },
        {
            "torchstore_tpu/transport/somexport.py": """
                from torchstore_tpu.native import copy_into
                def land(dst, src):
                    copy_into(dst, src)
                """,
        },
    ),
    "retry-discipline": (
        {
            "torchstore_tpu/m.py": """
                import asyncio
                async def drain():
                    while True:
                        try:
                            await push()
                            return
                        except ConnectionError:
                            await asyncio.sleep(1.0)
                """,
        },
        {
            "torchstore_tpu/m.py": """
                import asyncio
                async def drain(policy):
                    attempt = 0
                    while policy.should_retry(attempt):
                        try:
                            await push()
                            return
                        except ConnectionError:
                            await asyncio.sleep(policy.backoff(attempt))
                            attempt += 1
                """,
        },
    ),
    "one-sided-discipline": (
        {
            "torchstore_tpu/client.py": """
                def bad(seg, meta):
                    return seg.view(meta)
                """,
        },
        {
            "torchstore_tpu/client.py": """
                from torchstore_tpu.transport import shared_memory as shm
                def good(seg, meta):
                    return shm.segment_read_view(seg, meta)
                """,
        },
    ),
    "stream-discipline": (
        {
            "torchstore_tpu/weight_channel.py": """
                async def acquire(state, key):
                    return state["watermarks"][key]
                """,
        },
        {
            "torchstore_tpu/weight_channel.py": """
                from torchstore_tpu import stream_sync
                def fine(state, keys, version):
                    return stream_sync.inconsistent_keys(state, keys, version)
                """,
        },
    ),
    "quant-discipline": (
        {
            "torchstore_tpu/weight_channel.py": """
                def bad(marker):
                    return marker.get("scales")
                """,
        },
        {
            "torchstore_tpu/state_dict_utils.py": """
                def codec_home(info):
                    return info["scales"]
                """,
        },
    ),
    "shard-discipline": (
        {
            "torchstore_tpu/controller.py": """
                class Controller:
                    async def peek(self, key):
                        return self.index.get(key)
                """,
        },
        {
            "torchstore_tpu/metadata/index_core.py": """
                class IndexCore:
                    def get(self, key):
                        return self.index.get(key)
                """,
        },
    ),
    "mirror-discipline": (
        {
            "torchstore_tpu/metadata/router.py": """
                from torchstore_tpu.metadata import stamped as stamped_mod
                def attach(desc):
                    return stamped_mod.MetaStampReader(
                        desc["segment"], desc["size"]
                    )
                """,
        },
        {
            "torchstore_tpu/metadata/router.py": """
                from torchstore_tpu.metadata import stamped as stamped_mod
                def attach(desc):
                    return stamped_mod.attach_reader(desc)
                """,
        },
    ),
    "stage-discipline": (
        {
            "torchstore_tpu/client.py": """
                from torchstore_tpu.observability import timeline as obs_timeline
                def drifted(dur):
                    obs_timeline.observe_stage("get", "landing_copy", dur)
                """,
            "torchstore_tpu/observability/timeline.py": _STAGE_TIMELINE_STUB,
        },
        {
            "torchstore_tpu/client.py": """
                from torchstore_tpu.observability import timeline as obs_timeline
                def fine(dur):
                    obs_timeline.observe_stage("get", "landing", dur)
                """,
            "torchstore_tpu/observability/timeline.py": _STAGE_TIMELINE_STUB,
        },
    ),
    "control-discipline": (
        {
            "torchstore_tpu/control/engine.py": """
                class Engine:
                    async def silent_move(self, key, src, dst):
                        return await self.host.idx.migrate_key(
                            key, src, dst, drop_src=True
                        )
                """,
        },
        {
            "torchstore_tpu/control/engine.py": """
                class Engine:
                    async def audited_move(self, snap, action):
                        await self.host.idx.migrate_key(
                            action.subject, action.src, action.dst, drop_src=True
                        )
                        return self._decision(snap, action, "applied")
                """,
        },
    ),
    "history-discipline": (
        {
            "torchstore_tpu/dets.py": """
                from torchstore_tpu.observability.detect import Detector
                SELECTOR = "ts_landing_inflight"
                BAD = Detector(name="f", series=SELECTOR, kind="sustained")
                """,
        },
        {
            "torchstore_tpu/metrics_def.py": """
                from torchstore_tpu.observability import metrics as m
                _G = m.gauge("ts_landing_inflight", "open landing brackets")
                """,
            "torchstore_tpu/dets.py": """
                from torchstore_tpu.observability.detect import Detector
                GOOD = Detector(
                    name="a", series="ts_landing_inflight", kind="sustained"
                )
                """,
        },
    ),
    "bracket-discipline": (
        {
            "torchstore_tpu/storage_volume.py": """
                class StorageVolume:
                    async def put_leaky(self, pairs, reqs):
                        await self._begin_landing(pairs)
                        await self._land(reqs)
                        self._end_landing(pairs)
                """,
        },
        {
            "torchstore_tpu/storage_volume.py": """
                class StorageVolume:
                    async def put_ok(self, pairs, reqs):
                        await self._begin_landing(pairs)
                        try:
                            await self._land(reqs)
                        finally:
                            self._end_landing(pairs)
                """,
        },
    ),
    "epoch-discipline": (
        {
            "torchstore_tpu/controller.py": """
                class Controller:
                    async def notify_delete_batch(self, keys):
                        by_volume = self.core.delete_keys(keys)
                        if self.loud:
                            self._bump_epoch()
                        return by_volume
                """,
        },
        {
            "torchstore_tpu/controller.py": """
                class Controller:
                    async def notify_delete_batch(self, keys):
                        by_volume = self.core.delete_keys(keys)
                        self._bump_epoch()
                        return by_volume
                """,
        },
    ),
    "await-atomicity": (
        {
            "torchstore_tpu/metadata/stamped.py": """
                import asyncio
                class MetaStampWriter:
                    async def publish_now(self, blob):
                        seq = self._publish_open()
                        await asyncio.sleep(0)
                        self._publish_close(seq)
                """,
        },
        {
            "torchstore_tpu/metadata/stamped.py": """
                class MetaStampWriter:
                    def publish_now(self, blob):
                        seq = self._publish_open()
                        self.words[2] = len(blob)
                        self._publish_close(seq)
                """,
        },
    ),
    "decision-flow": (
        {
            "torchstore_tpu/control/engine.py": """
                class Engine:
                    async def apply_move(self, snap, action):
                        await self.host.idx.migrate_key(
                            action.subject, action.src, action.dst, drop_src=True
                        )
                        if snap.quiet:
                            return None
                        return self._decision(snap, action, "applied")
                """,
        },
        {
            "torchstore_tpu/control/engine.py": """
                class Engine:
                    async def apply_move(self, snap, action):
                        await self.host.idx.migrate_key(
                            action.subject, action.src, action.dst, drop_src=True
                        )
                        return self._decision(snap, action, "applied")
                """,
        },
    ),
}


def test_rule_fixtures_cover_every_registered_rule():
    """Registering a rule without fixtures is itself a tier-1 failure."""
    assert set(RULE_FIXTURES) == set(CHECKERS), (
        "every rule in CHECKERS needs a (dirty, clean) entry in RULE_FIXTURES: "
        f"missing={sorted(set(CHECKERS) - set(RULE_FIXTURES))} "
        f"stale={sorted(set(RULE_FIXTURES) - set(CHECKERS))}"
    )
    assert len(CHECKERS) == 21, sorted(CHECKERS)


@pytest.mark.parametrize("rule", sorted(CHECKERS))
def test_rule_dirty_fixture_detects(rule, tmp_path):
    dirty, _clean = RULE_FIXTURES[rule]
    findings = CHECKERS[rule](_project(tmp_path, dirty))
    assert findings, f"{rule}: dirty fixture produced no finding"
    assert all(f.rule == rule for f in findings), [f.render() for f in findings]


@pytest.mark.parametrize("rule", sorted(CHECKERS))
def test_rule_clean_fixture_is_quiet(rule, tmp_path):
    _dirty, clean = RULE_FIXTURES[rule]
    findings = CHECKERS[rule](_project(tmp_path, clean))
    assert findings == [], [f.render() for f in findings]


# --------------------------------------------------------------------------
# Runtime budget, per-rule timing, SARIF (ISSUE 19 satellites)
# --------------------------------------------------------------------------


def test_full_gate_budget_timing_and_sarif(tmp_path):
    """One full 21-rule gate over the live tree, in a fresh interpreter the
    way CI runs it: must finish well under the 30 s budget (parallel
    checkers + the parse cache), expose per-rule wall time in the JSON
    report, and emit a SARIF 2.1.0 log whose rule table matches the
    registry — with zero results, because the tree is clean."""
    import time

    sarif_path = tmp_path / "gate.sarif"
    t0 = time.monotonic()
    proc = subprocess.run(
        [
            sys.executable,
            str(REPO_ROOT / "scripts" / "tslint.py"),
            "--fail-on-new",
            "--json",
            "--sarif",
            str(sarif_path),
        ],
        cwd=str(REPO_ROOT),
        capture_output=True,
        text=True,
    )
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert elapsed < 30.0, f"tslint gate took {elapsed:.1f}s (budget: 30s)"

    doc = json.loads(proc.stdout)
    assert len(doc["rules"]) == 21, doc["rules"]
    assert doc["new"] == 0
    assert set(doc["rule_seconds"]) == set(doc["rules"])
    assert all(v >= 0.0 for v in doc["rule_seconds"].values())

    sarif = json.loads(sarif_path.read_text())
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    rules = run["tool"]["driver"]["rules"]
    assert sorted(r["id"] for r in rules) == sorted(doc["rules"])
    assert all(r["shortDescription"]["text"] for r in rules)
    assert all(r["help"]["text"] for r in rules)
    assert run["results"] == []


def test_sarif_fingerprints_and_baseline_states(tmp_path):
    """SARIF results carry the repo's line-independent finding identity:
    the fingerprint survives the finding moving to another line, and a
    baselined finding is emitted as note/unchanged rather than error/new."""
    from torchstore_tpu.analysis.sarif import to_sarif

    src = """
        import asyncio

        def spawn():
            asyncio.create_task(work())
        """
    _project(tmp_path, {"torchstore_tpu/m.py": src})
    result = run_checks(str(tmp_path), rules=["orphan-task"])
    doc = to_sarif(result, CHECKERS)
    (res,) = doc["runs"][0]["results"]
    assert res["ruleId"] == "orphan-task"
    assert res["level"] == "error" and res["baselineState"] == "new"
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "torchstore_tpu/m.py"
    fp = res["partialFingerprints"]["tslintIdentity/v1"]

    # Shift the defect down three lines: identity (and fingerprint) stable.
    (tmp_path / "torchstore_tpu" / "m.py").write_text(
        "\n\n\n" + textwrap.dedent(src)
    )
    shifted = to_sarif(run_checks(str(tmp_path), rules=["orphan-task"]), CHECKERS)
    (res2,) = shifted["runs"][0]["results"]
    assert res2["partialFingerprints"]["tslintIdentity/v1"] == fp
    assert res2["locations"][0]["physicalLocation"]["region"]["startLine"] != loc[
        "region"
    ]["startLine"]

    # Grandfathered: same result, downgraded presentation.
    baseline = tmp_path / "baseline.json"
    save_baseline(str(baseline), result.findings)
    gated = run_checks(
        str(tmp_path), rules=["orphan-task"], baseline_path=str(baseline)
    )
    doc3 = to_sarif(gated, CHECKERS)
    (res3,) = doc3["runs"][0]["results"]
    assert res3["level"] == "note" and res3["baselineState"] == "unchanged"
    assert res3["partialFingerprints"]["tslintIdentity/v1"] == fp
