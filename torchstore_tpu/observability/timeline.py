"""Sync-timeline telemetry: rolling op quantiles, stage attribution, SLO
thresholds + scoreboard, and weight-sync generation reconstruction.

Facilities that turn the bench-only numbers (``overlap_ratio``,
``first_token``) and the fixed-bucket op histograms into production
signals:

- **Rolling quantile digests** (:class:`OpQuantiles`): per-op ring of the
  last ``WINDOW`` wall times with true p50/p99 published as gauges
  (``ts_op_p50_seconds`` / ``ts_op_p99_seconds``, labeled ``op=``). The
  fixed-bucket histograms stay (Prometheus-aggregatable); the digests add
  the exact quantiles an SLO needs, refreshed lazily (every
  ``REFRESH_EVERY`` observations) so the hot path pays one deque append.

- **Stage attribution** (:class:`StageQuantiles`, :func:`observe_stage`):
  client and volume ops record per-stage wall-clock segments — metadata
  resolve, transport wire, landing copy, stamp verify, watermark wait —
  into per-(op, stage) digests (``ts_op_stage_p50/p99_seconds{op,stage}``)
  plus rolling per-stage time totals. When an SLO blows, the totals answer
  the question an end-to-end timer can't: *which stage ate the budget*
  (:func:`dominant_stage`). Stage names MUST come from :data:`STAGE_CATALOG`
  — the ``stage-discipline`` tslint rule holds client and volume sites to
  the same stage catalog so digests from both sides fold together.

- **SLO thresholds** (``TORCHSTORE_TPU_SLO_*``): a typed family of
  operator-set bars. On breach the violation is logged (rate-limited per
  SLO) and counted in ``ts_slo_violations_total{slo=...}``. Shipped knobs:

      TORCHSTORE_TPU_SLO_PUT_P99_MS      rolling put p99 above this
      TORCHSTORE_TPU_SLO_GET_P99_MS      rolling get p99 above this
      TORCHSTORE_TPU_SLO_VERSION_LAG     subscriber version lag above this
      TORCHSTORE_TPU_SLO_FIRST_LAYER_MS  stream first-layer latency above
      TORCHSTORE_TPU_SLO_OVERLAP_MIN     stream overlap ratio BELOW this

  Unset = disabled; thresholds are re-read per check (one getenv) so live
  operators can retune a running fleet.

- **SLO scoreboard** (:func:`slo_report`): the live fold of all of the
  above — every configured ``TORCHSTORE_TPU_SLO_*`` threshold with its
  current value, violation count, violated flag, and (per violated SLO)
  the dominant stage with the per-stage breakdown. ``ts.slo_report()``
  wraps it with fleet overload signals (per-volume inflight landings,
  resident doorbell plans, metadata RPC inflight) — the inputs item 3's
  admission control consumes.

- **Generation reconstruction** (:func:`reconstruct`): folds a controller
  stream record (now timestamped — ``stream_begin`` -> per-key watermark
  landings -> ``stream_seal`` -> per-subscriber acquire acks) into one
  readable lifecycle: publish window, first-layer latency, landing
  timeline, and per-subscriber completion lag. ``ts.sync_timeline(key)``
  is the public entry point.

Live gauges the acquire side maintains (stream_sync.py): per-subscriber
``ts_stream_overlap_ratio`` / ``ts_stream_first_layer_seconds`` — the
production twins of the bench's ``overlap_ratio`` / ``first_token``.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Optional

from torchstore_tpu.observability import metrics as obs_metrics

# The blessed SLO knob family. Names are read via these literals (the
# env-registry lint cross-references them against config.ENV_REGISTRY);
# anything else under the TORCHSTORE_TPU_SLO_ prefix is accepted as an
# operator extension (registered dynamic prefix family).
SLO_PUT_P99_MS = "TORCHSTORE_TPU_SLO_PUT_P99_MS"
SLO_GET_P99_MS = "TORCHSTORE_TPU_SLO_GET_P99_MS"
SLO_VERSION_LAG = "TORCHSTORE_TPU_SLO_VERSION_LAG"
SLO_FIRST_LAYER_MS = "TORCHSTORE_TPU_SLO_FIRST_LAYER_MS"
SLO_OVERLAP_MIN = "TORCHSTORE_TPU_SLO_OVERLAP_MIN"

# The registered stage catalog. Every wall-clock segment recorded into the
# stage digests — client-side or volume-side — names one of these, so
# digests from both ends of a transfer fold into the same stage catalog (the
# ``stage-discipline`` tslint rule rejects free-string stage labels):
#
#   plan            metadata resolve: locate (RPC or stamped), plan/epoch
#                   validation, request building, placement selection
#   d2h             device->host: issuing the copies of a put batch's device
#                   arrays and waiting for their bytes
#   h2d             host->device: dispatching fetched bytes to the device
#   transport       the wire leg: handshake + frames + RPC data movement
#   landing         landing copies: bytes into store/destination memory
#   stamp_verify    one-sided seqlock checks (pre-copy match + post-copy
#                   re-gather) proving a read raced no landing
#   watermark_wait  streamed acquires blocked on per-key watermarks
#                   (wait_for_stream long-polls, stamped or RPC)
#   notify          the metadata commit: notify_put_batch / watermark step
STAGE_CATALOG = frozenset(
    {
        "plan",
        "d2h",
        "h2d",
        "transport",
        "landing",
        "stamp_verify",
        "watermark_wait",
        "notify",
    }
)

_SLO_VIOLATIONS = obs_metrics.counter(
    "ts_slo_violations_total",
    "SLO threshold breaches (TORCHSTORE_TPU_SLO_* family), by slo",
)
_P50 = obs_metrics.gauge(
    "ts_op_p50_seconds", "Rolling-window p50 wall time, by op"
)
_P99 = obs_metrics.gauge(
    "ts_op_p99_seconds", "Rolling-window p99 wall time, by op"
)
_STAGE_P50 = obs_metrics.gauge(
    "ts_op_stage_p50_seconds",
    "Rolling-window p50 stage wall time, by op and stage",
)
_STAGE_P99 = obs_metrics.gauge(
    "ts_op_stage_p99_seconds",
    "Rolling-window p99 stage wall time, by op and stage",
)
# The stage totals above decay (60 s half-life) and the digests are rolling:
# neither can be read as a difference over a window. This one never decays.
_STAGE_SECONDS = obs_metrics.histogram(
    "ts_op_stage_seconds",
    "Cumulative stage wall time (sum and count never decay), by op and stage",
)


def slo_threshold(env_name: str) -> Optional[float]:
    """The configured threshold, or None when unset/disabled. Read per
    check (not cached) so a live operator can retune a running process."""
    raw = os.environ.get(env_name)
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        return None


# Rate-limit state for SLO breach logs: slo name -> last log monotonic.
# Inherited pre-fork contents only delay a child's first breach log by one
# window — no correctness or resource impact, so no fork hook is needed.
_last_slo_log: dict[str, float] = {}  # tslint: disable=fork-safety
_SLO_LOG_EVERY_S = 5.0


def check_slo(
    env_name: str,
    value: float,
    worse: str = "above",
    **context,
) -> bool:
    """Check ``value`` against the env-configured threshold; on breach,
    bump ``ts_slo_violations_total{slo=...}`` and log (rate-limited).
    ``worse="above"`` breaches when value > threshold; ``"below"`` when
    value < threshold (e.g. overlap ratio). Returns whether it breached."""
    threshold = slo_threshold(env_name)
    if threshold is None:
        return False
    breached = value > threshold if worse == "above" else value < threshold
    if not breached:
        return False
    # slo_name() is THE label derivation: the violation counter's label
    # here and slo_report's lookup key must never diverge, or every
    # scoreboard violation count silently reads zero.
    slo = slo_name(env_name)
    _SLO_VIOLATIONS.inc(slo=slo)
    now = time.monotonic()
    if now - _last_slo_log.get(slo, 0.0) >= _SLO_LOG_EVERY_S:
        _last_slo_log[slo] = now
        from torchstore_tpu.logging import get_logger

        get_logger("torchstore_tpu.observability").warning(
            "SLO violation: %s=%.4g %s threshold %.4g%s",
            slo,
            value,
            "above" if worse == "above" else "below",
            threshold,
            f" ({context})" if context else "",
        )
    from torchstore_tpu.observability import recorder as obs_recorder

    obs_recorder.record(
        "slo", slo, value=round(float(value), 6), threshold=threshold
    )
    return True


class OpQuantiles:
    """Rolling per-op quantile digest: a bounded deque of recent wall
    times; p50/p99 gauges refreshed every REFRESH_EVERY observations (one
    sort of <= WINDOW samples, off the per-op critical path rhythm)."""

    WINDOW = 512
    REFRESH_EVERY = 32

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._samples: dict[str, collections.deque] = {}
        self._pending: dict[str, int] = {}

    def observe(self, op: str, dur_s: float) -> None:
        with self._lock:
            ring = self._samples.get(op)
            if ring is None:
                ring = self._samples[op] = collections.deque(
                    maxlen=self.WINDOW
                )
            ring.append(dur_s)
            pending = self._pending.get(op, 0) + 1
            if pending < self.REFRESH_EVERY and len(ring) != 1:
                self._pending[op] = pending
                return
            self._pending[op] = 0
            ordered = sorted(ring)
        p50 = ordered[len(ordered) // 2]
        p99 = ordered[min(len(ordered) - 1, int(len(ordered) * 0.99))]
        _P50.set(p50, op=op)
        _P99.set(p99, op=op)
        if op == "put":
            check_slo(SLO_PUT_P99_MS, p99 * 1e3, op=op)
        elif op == "get":
            check_slo(SLO_GET_P99_MS, p99 * 1e3, op=op)

    def quantiles(self, op: str, qs=(0.5, 0.99)) -> Optional[dict]:
        with self._lock:
            ring = self._samples.get(op)
            if not ring:
                return None
            ordered = sorted(ring)
        return {
            repr(q): ordered[min(len(ordered) - 1, int(len(ordered) * q))]
            for q in qs
        }

    def snapshot(self) -> dict:
        with self._lock:
            ops = list(self._samples)
        return {op: self.quantiles(op) for op in ops}

    def reset(self) -> None:
        with self._lock:
            self._samples.clear()
            self._pending.clear()


_quantiles = OpQuantiles()


def op_quantiles() -> OpQuantiles:
    return _quantiles


def observe_op(op: str, dur_s: float) -> None:
    """Feed one completed logical op into the rolling digests (and their
    p99 SLO checks). Called from the client's op completion path."""
    _quantiles.observe(op, dur_s)


# --------------------------------------------------------------------------
# stage attribution (per-(op, stage) digests + dominant-stage totals)
# --------------------------------------------------------------------------


class StageQuantiles:
    """Rolling per-(op, stage) wall-time digests plus decaying per-stage
    time totals. The digests publish ``ts_op_stage_p50/p99_seconds`` on the
    same lazy-refresh rhythm as :class:`OpQuantiles`; the totals are the
    attribution input: when an op's SLO blows, the stage holding the
    largest share of recent wall time is the *dominant* stage — the answer
    ``ts.slo_report()`` surfaces next to each violated threshold.

    Totals decay exponentially in WALL TIME (half-life ``HALF_LIFE_S``),
    applied lazily at each touch, so a stage that dominated an hour ago
    cannot outvote the stage dominating NOW. The decay must be time-based,
    not sample-count-based: stages record at different RATES (put's
    transport leg records once per replica, its plan leg once per batch) —
    a per-stage count-triggered decay would normalize the rate away and
    make steady-state totals proportional to mean segment duration instead
    of aggregate wall time, inverting the dominant-stage vote exactly on
    the long-running fleets this exists for."""

    WINDOW = 512
    REFRESH_EVERY = 32
    HALF_LIFE_S = 60.0

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # (op, stage) -> [ring, pending, total_s, last_decay_monotonic]
        self._state: dict[tuple, list] = {}

    @classmethod
    def _decay_locked(cls, state: list, now: float) -> None:
        dt = now - state[3]
        if dt > 0:
            state[2] *= 0.5 ** (dt / cls.HALF_LIFE_S)
            state[3] = now

    def observe(self, op: str, stage: str, dur_s: float) -> None:
        if stage not in STAGE_CATALOG:
            raise ValueError(
                f"unregistered stage {stage!r} (catalog: "
                f"{sorted(STAGE_CATALOG)}); register it in "
                "observability.timeline.STAGE_CATALOG"
            )
        now = time.monotonic()
        with self._lock:
            state = self._state.get((op, stage))
            if state is None:
                state = self._state[(op, stage)] = [
                    collections.deque(maxlen=self.WINDOW), 0, 0.0, now,
                ]
            ring, pending, _, _ = state
            ring.append(dur_s)
            self._decay_locked(state, now)
            state[2] += dur_s
            state[1] = pending + 1
            if state[1] < self.REFRESH_EVERY and len(ring) != 1:
                return
            state[1] = 0
            ordered = sorted(ring)
        p50 = ordered[len(ordered) // 2]
        p99 = ordered[min(len(ordered) - 1, int(len(ordered) * 0.99))]
        _STAGE_P50.set(p50, op=op, stage=stage)
        _STAGE_P99.set(p99, op=op, stage=stage)

    def breakdown(self, op: str) -> dict[str, dict]:
        """Per-stage view for one op: ``{stage: {"samples", "total_s",
        "p99_s", "share"}}`` with ``share`` the stage's fraction of the
        op's summed (decayed) stage time."""
        now = time.monotonic()
        with self._lock:
            rows = {}
            for (o, stage), state in self._state.items():
                if o != op:
                    continue
                # Decay every stage to the SAME instant before comparing:
                # an idle stage must not keep a stale (undecayed) total.
                self._decay_locked(state, now)
                rows[stage] = (list(state[0]), state[2])
        out: dict[str, dict] = {}
        grand = sum(total for _, total in rows.values()) or 0.0
        for stage, (samples, total) in rows.items():
            ordered = sorted(samples)
            out[stage] = {
                "samples": len(samples),
                "total_s": round(total, 6),
                "p99_s": (
                    ordered[min(len(ordered) - 1, int(len(ordered) * 0.99))]
                    if ordered
                    else None
                ),
                "share": round(total / grand, 4) if grand > 0 else 0.0,
            }
        return out

    def dominant(self, op: str) -> Optional[str]:
        """The stage holding the largest share of ``op``'s recent wall
        time, or None when nothing was recorded."""
        rows = self.breakdown(op)
        if not rows:
            return None
        return max(rows.items(), key=lambda kv: kv[1]["total_s"])[0]

    def snapshot(self) -> dict[str, dict]:
        with self._lock:
            ops = sorted({op for op, _ in self._state})
        return {op: self.breakdown(op) for op in ops}

    def reset(self) -> None:
        with self._lock:
            self._state.clear()


_stages = StageQuantiles()


def stage_quantiles() -> StageQuantiles:
    return _stages


def observe_stage(op: str, stage: str, dur_s: float) -> None:
    """Record one wall-clock stage segment of a logical op. ``stage`` MUST
    name a :data:`STAGE_CATALOG` entry (raises ValueError otherwise — the
    ``stage-discipline`` tslint rule catches drift statically; this is the
    loud runtime backstop). Also feeds the cumulative
    ``ts_op_stage_seconds{op,stage}`` histogram, whose sum and count can be
    read as a difference over a window."""
    _stages.observe(op, stage, dur_s)
    _STAGE_SECONDS.observe(dur_s, op=op, stage=stage)


def dominant_stage(op: str) -> Optional[str]:
    """Which stage of ``op`` recent wall time concentrated in."""
    return _stages.dominant(op)


# --------------------------------------------------------------------------
# SLO scoreboard
# --------------------------------------------------------------------------

# env knob -> (worse direction, the op whose stage digests attribute a
# breach, a callable producing the CURRENT value in threshold units).
def _p99_ms(op: str):
    def current() -> Optional[float]:
        qs = _quantiles.quantiles(op, qs=(0.99,))
        return None if qs is None else qs["0.99"] * 1e3

    return current


def _gauge_value(name: str, scale: float = 1.0):
    def current() -> Optional[float]:
        metric = obs_metrics.get_registry().get(name)
        if metric is None:
            return None
        series = metric.snapshot().get("series") or []
        if not series:
            return None
        # Labeled gauges (channel=...): the scoreboard reports the worst
        # series — an SLO is about the worst-off consumer.
        return max(float(s["value"]) for s in series) * scale

    return current


_SLO_TABLE: dict[str, tuple[str, Optional[str], Any]] = {
    SLO_PUT_P99_MS: ("above", "put", _p99_ms("put")),
    SLO_GET_P99_MS: ("above", "get", _p99_ms("get")),
    SLO_VERSION_LAG: (
        "above", None, _gauge_value("ts_weight_channel_version_lag"),
    ),
    SLO_FIRST_LAYER_MS: (
        "above", "stream", _gauge_value("ts_stream_first_layer_seconds", 1e3),
    ),
    SLO_OVERLAP_MIN: (
        "below", "stream", _gauge_value("ts_stream_overlap_ratio"),
    ),
}

_SLO_PREFIX = "TORCHSTORE_TPU_SLO_"


def slo_name(env_name: str) -> str:
    return env_name.rsplit(_SLO_PREFIX, 1)[-1].lower()


def slo_report() -> dict:
    """This process's live SLO scoreboard: every configured
    ``TORCHSTORE_TPU_SLO_*`` threshold (the blessed family plus any
    operator-extension knobs set under the prefix) with its current value,
    lifetime violation count, violated flag, and — for SLOs whose op has
    stage digests — the dominant stage with the full per-stage breakdown.

    Returns ``{"slos": {name: {...}}, "stages": {op: breakdown},
    "trends": {detector: result}, "generated_ts": wall_ts}``.
    ``ts.slo_report()`` wraps this with fleet
    overload signals; loadgen drivers ship it home per process and
    ``loadgen.report.merge_slo_reports`` folds driver scoreboards into the
    fleet view."""
    names = dict(_SLO_TABLE)
    for env_name in os.environ:
        if env_name.startswith(_SLO_PREFIX) and env_name not in names:
            names[env_name] = ("above", None, lambda: None)
    slos: dict[str, dict] = {}
    for env_name, (worse, op, current_fn) in names.items():
        threshold = slo_threshold(env_name)
        if threshold is None:
            continue
        name = slo_name(env_name)
        current = current_fn()
        violations = int(_SLO_VIOLATIONS.value(slo=name))
        violated = current is not None and (
            current > threshold if worse == "above" else current < threshold
        )
        entry: dict[str, Any] = {
            "env": env_name,
            "threshold": threshold,
            "worse": worse,
            "current": None if current is None else round(current, 4),
            "violations": violations,
            "violated": bool(violated),
            "op": op,
        }
        if op is not None and (violated or violations):
            entry["dominant_stage"] = _stages.dominant(op)
            entry["stages"] = _stages.breakdown(op)
        slos[name] = entry
    # Trend detectors over the local history rings: the "is this a burst
    # or a regime change" companion to the instantaneous gates above.
    # History may be disabled (TORCHSTORE_TPU_HISTORY=0) or mid-bootstrap;
    # the scoreboard must not care.
    try:
        from torchstore_tpu.observability import detect as obs_detect

        trends = obs_detect.evaluate_trends()
    except Exception:  # noqa: BLE001 - scoreboard survives without trends
        trends = {}
    return {
        "slos": slos,
        "stages": _stages.snapshot(),
        "trends": trends,
        "generated_ts": time.time(),
    }


# --------------------------------------------------------------------------
# generation reconstruction (controller stream records -> lifecycle)
# --------------------------------------------------------------------------


def reconstruct(state: Optional[dict]) -> Optional[dict]:
    """Fold a timestamped controller stream record (``stream_state``) into
    one generation lifecycle:

    ``{"version", "sealed", "begin_ts", "seal_ts", "publish_window_s",
    "first_layer_s", "landings": [{"key", "ts", "offset_s"}, ...],
    "subscribers": {sub: {"version", "ts", "completion_s"}}}``

    ``offset_s``/``completion_s`` are relative to ``begin_ts``. Returns
    None for a missing record; fields are None when the record predates
    the timestamping (controller upgrade mid-run)."""
    if state is None:
        return None
    begin_ts = state.get("begin_ts")
    seal_ts = state.get("seal_ts")
    landing_ts: dict = state.get("landing_ts") or {}
    landings = [
        {
            "key": key,
            "ts": ts,
            "offset_s": (
                round(ts - begin_ts, 6) if begin_ts is not None else None
            ),
        }
        for key, ts in sorted(landing_ts.items(), key=lambda kv: kv[1])
    ]
    first_layer_s = (
        round(landings[0]["ts"] - begin_ts, 6)
        if landings and begin_ts is not None
        else None
    )
    subscribers = {
        sub: {
            "version": ack.get("version"),
            "ts": ack.get("ts"),
            "completion_s": (
                round(ack["ts"] - begin_ts, 6)
                if begin_ts is not None and ack.get("ts") is not None
                else None
            ),
        }
        for sub, ack in (state.get("acks") or {}).items()
    }
    return {
        "version": state.get("version"),
        "sealed": state.get("sealed"),
        "begin_ts": begin_ts,
        "seal_ts": seal_ts,
        "publish_window_s": (
            round(seal_ts - begin_ts, 6)
            if begin_ts is not None and seal_ts is not None
            else None
        ),
        "first_layer_s": first_layer_s,
        "landings": landings,
        "subscribers": subscribers,
    }


def subscriber_id() -> str:
    """This process's identity in stream acquire acks (bounded: one entry
    per process per stream record)."""
    from torchstore_tpu.utils import get_hostname

    return f"{get_hostname()}:{os.getpid()}"
