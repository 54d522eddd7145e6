"""Process-local metrics registry: counters, gauges, fixed-bucket histograms.

Zero dependencies, lock-protected, cheap enough for the data-plane hot path
(a counter inc is one dict get + add under a per-metric lock). Every process
in a store — clients, storage volumes, the controller — carries its own
registry; instruments are process-local by design (aggregation is the
scraper's job, exactly as with Prometheus client libraries). Volume/controller
registries are surfaced through their ``stats()`` endpoints, so
``controller.stats(include_volumes=True)`` collects the whole fleet.

Exporters:

- ``render_prometheus()`` — Prometheus text exposition format (v0.0.4).
- ``render_json()`` / ``snapshot()`` — machine-readable dict/JSON, the form
  ``ts.metrics_snapshot()`` returns and ``bench.py`` emits.

Env-gated periodic dumper: set ``TORCHSTORE_TPU_METRICS_DUMP=/path.json`` (or
``.prom`` for Prometheus text) and every process appends nothing — it
atomically REWRITES its own file (pid-suffixed when the base name is taken)
every ``TORCHSTORE_TPU_METRICS_INTERVAL_S`` seconds (default 60) and once at
exit, so a crashed run still leaves its last-known counters on disk.
"""

from __future__ import annotations

import atexit
import bisect
import json
import os
import threading
import time
from typing import Any, Optional

ENV_METRICS_DUMP = "TORCHSTORE_TPU_METRICS_DUMP"
ENV_METRICS_INTERVAL = "TORCHSTORE_TPU_METRICS_INTERVAL_S"

# (sorted (key, value) pairs) — the canonical identity of one labeled series.
LabelKey = "tuple[tuple[str, str], ...]"


def _label_key(labels: dict) -> tuple:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class Metric:
    """Base: one named instrument holding one series per label-set."""

    kind = "untyped"

    def __init__(self, name: str, help: str = "") -> None:
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._series: dict[tuple, Any] = {}

    def clear(self) -> None:
        with self._lock:
            self._series.clear()

    def _snapshot_series(self) -> list[dict]:
        with self._lock:
            return [
                {"labels": dict(key), "value": value}
                for key, value in self._series.items()
            ]

    def snapshot(self) -> dict:
        return {
            "kind": self.kind,
            "help": self.help,
            "series": self._snapshot_series(),
        }


class Counter(Metric):
    """Monotonic counter. ``inc(n)`` only; negative increments are rejected
    (that's what gauges are for)."""

    kind = "counter"

    def inc(self, n: float = 1, **labels) -> None:
        if n < 0:
            raise ValueError(f"counter {self.name} cannot decrease (n={n})")
        key = _label_key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0) + n

    def value(self, **labels) -> float:
        with self._lock:
            return self._series.get(_label_key(labels), 0)

    def total(self) -> float:
        """Sum across every label-set (convenience for tests/benches)."""
        with self._lock:
            return sum(self._series.values())


class Gauge(Metric):
    """Point-in-time value; settable, incrementable, decrementable."""

    kind = "gauge"

    def set(self, v: float, **labels) -> None:
        with self._lock:
            self._series[_label_key(labels)] = v

    def inc(self, n: float = 1, **labels) -> None:
        key = _label_key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0) + n

    def dec(self, n: float = 1, **labels) -> None:
        self.inc(-n, **labels)

    def value(self, **labels) -> float:
        with self._lock:
            return self._series.get(_label_key(labels), 0)


# Spans from microseconds (colocated gets) to minutes (model-scale DCN sync).
DEFAULT_LATENCY_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)
# 1 KB .. 16 GB in decade-ish steps (weight-sync payloads).
DEFAULT_BYTES_BUCKETS = (
    1024.0, 16384.0, 65536.0, 1 << 20, 16 << 20, 64 << 20, 256 << 20,
    1 << 30, 4 << 30, 16 << 30,
)


class Histogram(Metric):
    """Fixed-bucket histogram (Prometheus semantics: cumulative ``le``
    buckets plus ``sum``/``count``). Buckets are chosen at creation and
    never change, so ``observe`` is a binary search + two adds."""

    kind = "histogram"

    def __init__(
        self,
        name: str,
        help: str = "",
        buckets: Optional[tuple] = None,
    ) -> None:
        super().__init__(name, help)
        self.buckets = tuple(sorted(buckets or DEFAULT_LATENCY_BUCKETS))

    def observe(self, v: float, **labels) -> None:
        key = _label_key(labels)
        with self._lock:
            state = self._series.get(key)
            if state is None:
                state = self._series[key] = {
                    "counts": [0] * (len(self.buckets) + 1),
                    "sum": 0.0,
                    "count": 0,
                }
            state["counts"][bisect.bisect_left(self.buckets, v)] += 1
            state["sum"] += v
            state["count"] += 1

    def value(self, **labels) -> Optional[dict]:
        """{"sum", "count", "buckets": {le: cumulative_count}} or None."""
        with self._lock:
            state = self._series.get(_label_key(labels))
            if state is None:
                return None
            return self._cumulative(state)

    def _cumulative(self, state: dict) -> dict:
        out: dict[str, Any] = {"sum": state["sum"], "count": state["count"]}
        cum = 0
        buckets: dict[str, int] = {}
        for le, n in zip(self.buckets, state["counts"]):
            cum += n
            buckets[repr(le)] = cum
        buckets["+Inf"] = cum + state["counts"][-1]
        out["buckets"] = buckets
        return out

    def _snapshot_series(self) -> list[dict]:
        with self._lock:
            return [
                {"labels": dict(key), "value": self._cumulative(state)}
                for key, state in self._series.items()
            ]


class MetricsRegistry:
    """Named instruments, get-or-create. One per process (module singleton);
    tests may build private ones."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: dict[str, Metric] = {}

    def _get_or_create(self, cls, name: str, help: str, **kwargs) -> Metric:
        with self._lock:
            metric = self._metrics.get(name)
            if metric is None:
                metric = self._metrics[name] = cls(name, help, **kwargs)
            elif not isinstance(metric, cls):
                raise TypeError(
                    f"metric {name!r} already registered as {metric.kind}, "
                    f"not {cls.kind}"
                )
            return metric

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get_or_create(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get_or_create(Gauge, name, help)

    def histogram(
        self, name: str, help: str = "", buckets: Optional[tuple] = None
    ) -> Histogram:
        return self._get_or_create(Histogram, name, help, buckets=buckets)

    def get(self, name: str) -> Optional[Metric]:
        with self._lock:
            return self._metrics.get(name)

    def reset(self) -> None:
        """Zero every series. The Metric OBJECTS survive — instruments are
        cached in module globals all over the codebase, and reset (tests,
        bench warmup) must not orphan them from the registry."""
        with self._lock:
            metrics = list(self._metrics.values())
        for metric in metrics:
            metric.clear()

    # ---- exporters -------------------------------------------------------

    def snapshot(self) -> dict:
        """{metric_name: {"kind", "help", "series": [...]}} — plain data,
        JSON-serializable, stable field names."""
        with self._lock:
            metrics = dict(self._metrics)
        return {name: metric.snapshot() for name, metric in sorted(metrics.items())}

    def sample_values(self) -> list[tuple]:
        """Flat numeric view for the history sampler: one
        ``(name, kind, label_key, value)`` row per labeled series, where
        ``label_key`` is the canonical sorted ``((k, v), ...)`` tuple.
        Histograms are sampled as their ``<name>_count`` counter — the
        per-bucket vectors belong to scrapes, not 1 Hz retention."""
        with self._lock:
            metrics = list(self._metrics.values())
        rows: list[tuple] = []
        for metric in metrics:
            if isinstance(metric, Histogram):
                with metric._lock:
                    items = [
                        (key, float(state["count"]))
                        for key, state in metric._series.items()
                    ]
                name = metric.name + "_count"
                for key, count in items:
                    rows.append((name, "counter", key, count))
            else:
                with metric._lock:
                    items = [
                        (key, float(value))
                        for key, value in metric._series.items()
                    ]
                for key, value in items:
                    rows.append((metric.name, metric.kind, key, value))
        return rows

    def render_json(self) -> str:
        return json.dumps(
            {"ts": time.time(), "pid": os.getpid(), "metrics": self.snapshot()}
        )

    def render_prometheus(self) -> str:
        """Prometheus text exposition format (0.0.4)."""
        return render_prometheus_snapshot(self.snapshot())


def render_prometheus_snapshot(snapshot: dict) -> str:
    """Render any registry-shaped snapshot (``{name: {"kind", "help",
    "series"}}``) as Prometheus text — the local registry or a merged fleet
    snapshot (observability/aggregate.py) render identically."""
    lines: list[str] = []
    for name, snap in sorted(snapshot.items()):
        if snap.get("help"):
            lines.append(f"# HELP {name} {snap['help']}")
        lines.append(f"# TYPE {name} {snap['kind']}")
        for series in snap["series"]:
            labels = series["labels"]
            if snap["kind"] == "histogram":
                value = series["value"]
                for le, cum in value["buckets"].items():
                    lines.append(
                        f"{name}_bucket"
                        f"{_fmt_labels({**labels, 'le': le})} {cum}"
                    )
                lines.append(f"{name}_sum{_fmt_labels(labels)} {value['sum']}")
                lines.append(
                    f"{name}_count{_fmt_labels(labels)} {value['count']}"
                )
            else:
                lines.append(f"{name}{_fmt_labels(labels)} {series['value']}")
    return "\n".join(lines) + "\n"


def _fmt_labels(labels: dict) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{k}="{_escape(v)}"' for k, v in sorted(labels.items())
    )
    return "{" + inner + "}"


def _escape(v) -> str:
    return str(v).replace("\\", r"\\").replace('"', r"\"").replace("\n", r"\n")


# --------------------------------------------------------------------------
# process singleton + convenience accessors
# --------------------------------------------------------------------------

_registry = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return _registry


def counter(name: str, help: str = "") -> Counter:
    return _registry.counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:
    return _registry.gauge(name, help)


def histogram(
    name: str, help: str = "", buckets: Optional[tuple] = None
) -> Histogram:
    return _registry.histogram(name, help, buckets=buckets)


def metrics_snapshot() -> dict:
    """This process's full registry snapshot (see MetricsRegistry.snapshot)."""
    return _registry.snapshot()


def reset_metrics() -> None:
    _registry.reset()


# --------------------------------------------------------------------------
# env-gated periodic dumper
# --------------------------------------------------------------------------

# Fork story lives one level up: observability.reinit_after_fork() (called
# from actor children's _child_main) resets the started-flag and re-arms the
# dumper thread; the lock itself is never held across a spawn.
_dumper_lock = threading.Lock()  # tslint: disable=fork-safety
_dumper_started = False
_dumper_thread: Optional[threading.Thread] = None
_dump_path: Optional[str] = None


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except (PermissionError, OSError):
        return True  # exists (or unknowable) — treat the claim as live


def _resolve_dump_path(base: str) -> str:
    """Claim ``base`` for this process; concurrent processes (volume actors
    dump too) take a pid-suffixed sibling. Ownership is arbitrated through a
    ``<base>.owner`` sidecar recording the claimant's pid — NOT the dump
    file's existence: dumps persist across runs (output directories are
    reused), and a leftover file from a finished run must not divert a
    fresh run to a suffixed sibling while the base path serves stale data.
    A dead owner's claim is taken over; writes are atomic whole-file
    replaces, so even a (rare) double-takeover cannot interleave output."""
    root, ext = os.path.splitext(base)
    pid = os.getpid()
    pid_path = f"{root}.{pid}{ext or '.json'}"
    owner_path = f"{base}.owner"
    try:
        fd = os.open(owner_path, os.O_CREAT | os.O_EXCL | os.O_WRONLY, 0o644)
        os.write(fd, str(pid).encode())
        os.close(fd)
        return base
    except FileExistsError:
        try:
            with open(owner_path) as f:
                owner = int(f.read().strip() or 0)
        except (OSError, ValueError):
            owner = 0
        if owner == pid:
            return base
        if not owner or not _pid_alive(owner):
            try:
                tmp = f"{owner_path}.tmp.{pid}"
                with open(tmp, "w") as f:
                    f.write(str(pid))
                os.replace(tmp, owner_path)
                return base
            except OSError:
                pass
        return pid_path
    except OSError:
        return pid_path


def dump_metrics(path: Optional[str] = None) -> Optional[str]:
    """Atomically write this process's metrics to ``path`` (default: the
    claimed env-configured path). Format by extension: ``.prom`` gets
    Prometheus text, anything else JSON. Returns the path written or None."""
    global _dump_path
    if path is None:
        base = os.environ.get(ENV_METRICS_DUMP)
        if not base:
            return None
        if _dump_path is None:
            _dump_path = _resolve_dump_path(base)
        path = _dump_path
    payload = (
        _registry.render_prometheus()
        if path.endswith(".prom")
        else _registry.render_json()
    )
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            f.write(payload)
        os.replace(tmp, path)
    except OSError:
        return None
    return path


def maybe_start_dumper() -> bool:
    """Start the periodic dump thread once per process when
    ``TORCHSTORE_TPU_METRICS_DUMP`` is set. Idempotent; returns whether a
    dumper is running. Called from ``torchstore_tpu`` import."""
    global _dumper_started
    if not os.environ.get(ENV_METRICS_DUMP):
        return False
    with _dumper_lock:
        if _dumper_started:
            return True
        _dumper_started = True
    try:
        interval = float(os.environ.get(ENV_METRICS_INTERVAL, "60"))
    except ValueError:
        interval = 60.0
    interval = max(1.0, interval)

    def loop() -> None:
        while True:
            time.sleep(interval)
            dump_metrics()

    global _dumper_thread
    thread = threading.Thread(
        target=loop, name="torchstore-tpu-metrics-dump", daemon=True
    )
    thread.start()
    _dumper_thread = thread
    atexit.register(dump_metrics)
    return True


def reinit_dumper_after_fork() -> bool:
    """Re-arm the periodic dumper in an actor child. Under forkserver, fork
    copies the ``_dumper_started`` flag but NOT the dump thread (only the
    forking thread survives), so an inherited True flag means "claims to
    run, never dumps" — reset and start fresh. Under spawn, the child's own
    import already started a LIVE thread: starting another would double
    every dump; only the claimed path is dropped so the next tick
    re-resolves against the child's corrected env."""
    global _dumper_started, _dump_path, _dumper_thread
    with _dumper_lock:
        _dump_path = None
        if _dumper_thread is not None and _dumper_thread.is_alive():
            return True
        _dumper_started = False
        _dumper_thread = None
    return maybe_start_dumper()
