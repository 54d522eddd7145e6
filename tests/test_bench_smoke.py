"""Tiny-size smoke test for bench.py (VERDICT r5: the round-5 bench crashed
AFTER all sections ran, so no headline was recorded and nothing failed in
CI). Executes the REAL ``run()`` code path — all three measured sections,
the latency loop, calibration, and the JSON assembly — on KB-scale tensors,
so a bench regression fails tier-1 instead of silently zeroing a round."""

import json
import pathlib
import sys

import numpy as np
import pytest

REPO_ROOT = str(pathlib.Path(__file__).resolve().parents[1])


@pytest.mark.anyio
async def test_bench_run_tiny(capsys):
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)

    result = await bench.run(
        n_tensors=2,
        tensor_mb=0.0625,
        iters=2,
        calib_mb=1,
        lat_iters=4,
        many_keys_n=16,
        many_keys_kb=4,
        recovery_n_keys=8,
        recovery_key_kb=4,
        ledger_keys=16,
        ledger_reps=2,
        streamed_layers=4,
        streamed_layer_kb=4,
        streamed_train_ms=5.0,
        streamed_decode_ms=5.0,
        streamed_iters=1,
        capacity_versions=4,
        capacity_keys=4,
        capacity_key_kb=4,
        delta_tensors=4,
        delta_tensor_kb=16,
        delta_versions=3,
        meta_shard_counts=(1, 2),
        meta_drivers=2,
        meta_logical=2,
        meta_duration_s=0.5,
        fleet_drivers=2,
        fleet_logical=4,
        fleet_duration_s=1.2,
        fleet_volumes=2,
        fleet_gate_ms=2000.0,
        placement_drivers=2,
        placement_logical=4,
        placement_duration_s=1.2,
        placement_volumes=2,
    )

    # The headline record: the exact contract the driver parses.
    assert result["metric"] == "state_dict_weight_sync_round_trip"
    assert result["unit"] == "GB/s"
    assert result["value"] > 0
    assert result["vs_baseline"] > 0
    assert 0 < result["calib_ratio"] <= 1.0
    assert result["host_memcpy_gbps"] > 0
    # Section stats carry the rerun-on-WARN policy's full output.
    for section in ("buffered", "direct", "direct_registered"):
        stats = result["sections"][section]
        assert stats["median"] > 0
        assert {"best", "warm_min", "warm_cv", "warn", "reruns"} <= set(stats)
    assert result["p50_put_ms"] > 0 and result["p50_get_ms"] > 0

    # Machine-readable metrics snapshot sourced from the new registry, with
    # nonzero per-transport byte counters from the run itself.
    metrics = result["metrics"]
    tbytes = metrics["ts_transport_bytes_total"]["series"]
    put_bytes = sum(
        s["value"] for s in tbytes if s["labels"].get("op") == "put"
    )
    assert put_bytes >= 2 * 0.0625 * 1024 * 1024

    # The merged fleet snapshot rides the record too: process-labeled
    # series covering the controller and the volume, no scrape errors.
    fleet = result["fleet"]
    assert fleet["errors"] == {}
    procs = {p["process"] for p in fleet["processes"]}
    assert {"client", "controller", "volume"} <= procs
    vol_puts = [
        s
        for s in fleet["metrics"]["ts_volume_put_ops_total"]["series"]
        if s["labels"].get("process") == "volume"
    ]
    assert vol_puts and sum(s["value"] for s in vol_puts) > 0

    # Cold-path acceptance keys ride the headline JSON (ISSUE 3): the
    # ratios at top level, the full section under "cold". At KB scale the
    # RATIO values are noise — only structure and positivity are asserted
    # here; the >= 2x bar is the full-scale BENCH run's contract.
    assert result["cold_vs_steady"] > 0
    assert result["cold_prewarmed_vs_steady"] > 0
    cold = result["cold"]
    for key in (
        "cold_gbps",
        "cold_prewarmed_gbps",
        "steady_gbps",
        "prewarm_seconds",
    ):
        assert cold[key] > 0, (key, cold)
    assert cold["prewarm"]["ok"] is True
    assert cold["prewarm"]["errors"] == {}

    # Many-keys section (ISSUE 5): headline stats at top level, the full
    # section dict alongside. At KB scale the VALUES are noise — structure
    # and positivity only; the >=2x-vs-pre-PR bar is the full-scale run's.
    assert result["many_keys_gbps"] > 0
    assert result["per_key_put_us"] > 0
    assert result["many_keys"]["n_keys"] == 16
    assert result["many_keys"]["put_s"] > 0

    # One-sided get leg (ISSUE 7): per-key get cost, delivered get rate,
    # distance from the memcpy ceiling, and the warm 1KB p50 — all present
    # and positive (the <=0.35 ms / <=2.5x bars are the full-scale run's).
    assert result["per_key_get_us"] > 0
    assert result["many_keys_get_gbps"] > 0
    assert result["get_memcpy_ratio"] > 0
    assert result["p50_get_1kb_ms"] > 0

    # Decision-telemetry overhead (ISSUE 10): the always-on recorder +
    # ledger cost on the warm one-sided get leg. KB-scale values are
    # noise — structure only; the <=2% bar is the full-scale run's.
    assert "ledger_overhead_pct" in result
    lo = result["ledger_overhead"]
    assert lo["on_us_per_key"] > 0 and lo["off_us_per_key"] > 0
    assert lo["n_keys"] == 16

    # Streamed-sync section (ISSUE 9): overlap metrics at top level, the
    # full section under "streamed_sync". At KB scale the VALUES are noise
    # — structure + positivity of the wall clocks only; the overlap_ratio
    # > 0 acceptance is the standalone section test's (larger sleeps).
    assert result["streamed_sync"]["barrier_s"] > 0
    assert result["streamed_sync"]["streamed_s"] > 0
    assert "overlap_ratio" in result
    assert "first_token_after_publish_ms" in result

    # Recovery section (ISSUE 6): time-to-heal keys at top level, full
    # timings under "recovery" — a real kill + quarantine + auto-repair.
    assert result["heal_s"] > 0
    assert result["failover_get_s"] > 0
    rec = result["recovery"]
    assert rec["detect_s"] > 0 and rec["rereplicate_s"] > 0
    assert rec["victim_keys"] > 0

    # Tiered-capacity section (ISSUE 12): headline keys at top level, the
    # full section under "capacity". KB-scale TIMES are noise — structure,
    # positivity, and the structural invariants (working set over budget,
    # bytes actually spilled, zero warm get RPCs) are asserted; the
    # latency bars are the full-scale run's bench_compare contract.
    assert result["warm_get_after_spill_us"] > 0
    assert result["fault_in_p50_ms"] > 0
    assert result["spilled_bytes_ratio"] > 0
    cap = result["capacity"]
    assert cap["working_set_mb"] >= 2 * cap["budget_mb"]
    assert cap["spilled_bytes"] > 0
    assert cap["warm_get_rpcs"] == 0
    assert cap["fault_in_keys"] > 0

    # Quantized + delta wire tier (ISSUE 13): headline keys at top level,
    # the full section under "delta_sync". KB-scale SPEEDUPS are noise —
    # structure plus the structural compression/error invariants only; the
    # >=2x / >=3x bars are the full-scale run's bench_compare contract.
    assert result["delta_speedup_int8_block"] > 0
    assert result["delta_speedup_delta"] > 0
    assert result["delta_wire_compression_delta"] > 5.0
    assert result["delta_max_abs_err"] >= 0
    ds = result["delta_sync"]
    assert ds["delta_wire_compression_int8_block"] > 3.0
    assert ds["delta_max_abs_err_none"] == 0.0

    # Fleet-scale section (ISSUE 15): the section ASSERTS its own gates
    # (p99 under the SLO, telemetry budget under load, induced-violation
    # stage attribution) — reaching here means they held at smoke scale;
    # the headline keys must still ride the record.
    assert result["fleet_ops_per_s"] > 0
    assert result["fleet_get_p99_ms"] > 0
    assert isinstance(result["fleet_ledger_overhead_pct"], float)
    fs = result["fleet_scale"]
    assert fs["logical_clients"] == 8 and fs["drivers"] == 2
    assert fs["violation"]["dominant_stage"] == "landing"
    assert fs["violation"]["violations"] > 0

    # Placement section (ISSUE 16): the section asserts its own gates
    # (control_plan non-empty on the skewed workload, decisions applied,
    # zero failed drivers / op errors while keys migrate mid-leg) —
    # reaching here means they held at smoke scale; the headline keys
    # must still ride the record. The >=70%-recovery / <=1.5x-isolation
    # bars are the full-scale run's bench_compare contract.
    assert result["rebalance_recovery_ratio"] > 0
    assert result["migration_bytes"] >= 0
    pl = result["placement"]
    assert pl["plan_actions"], pl
    assert pl["decisions"], pl
    assert pl["by_tenant_skewed_on"], pl

    # The whole record (what bench prints as its one stdout JSON line)
    # must serialize.
    json.dumps(result)


@pytest.mark.anyio
async def test_bench_many_keys_section_tiny():
    """The many-keys section standalone at KB scale: the real arena/plan
    path through a real fleet, so the section can never ship broken."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)

    out = await bench.many_keys_section(n_keys=24, key_kb=4, iters=2)
    assert out["n_keys"] == 24
    assert out["many_keys_gbps"] > 0
    assert out["per_key_put_us"] > 0
    assert out["per_key_get_us"] > 0
    assert out["get_gbps"] > 0 and out["get_memcpy_ratio"] > 0
    assert out["put_s"] > 0 and out["get_s"] > 0
    json.dumps(out)


@pytest.mark.anyio
async def test_bench_recovery_section_tiny():
    """The recovery section standalone (``bench.py --recovery``) at KB
    scale: a real volume kill under load, supervisor detection, failover
    get, and automatic re-replication — so time-to-heal can never ship
    broken."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)

    out = await bench.recovery_section(n_keys=8, key_kb=4)
    assert out["detect_s"] > 0
    assert out["first_get_s"] > 0
    assert out["rereplicate_s"] >= out["detect_s"]
    assert out["heal_s"] == out["rereplicate_s"]
    json.dumps(out)


@pytest.mark.anyio
async def test_bench_streamed_sync_section_tiny():
    """The streamed-sync section standalone (``bench.py --streamed-sync``)
    at small scale with compute sleeps large enough to dominate host
    noise: the streamed leg must demonstrably overlap acquire with
    publish (overlap_ratio > 0 — the ISSUE-9 acceptance shape) and beat
    the barrier wall clock."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)

    out = await bench.streamed_sync_section(
        n_layers=4, layer_kb=8, train_ms=40.0, decode_ms=40.0, iters=1
    )
    assert out["barrier_s"] > 0 and out["streamed_s"] > 0
    # Train (4 x 40 ms) + decode (4 x 40 ms) serialize on the barrier path
    # and overlap on the streamed one: the win must be visible even on a
    # noisy host, and the acquire must overlap the publish window.
    assert out["overlap_ratio"] > 0, out
    assert out["streamed_s"] < out["barrier_s"], out
    assert (
        out["first_token_after_publish_ms"]
        < out["barrier_first_token_after_publish_ms"]
    ), out
    json.dumps(out)


@pytest.mark.anyio
async def test_bench_cold_path_section_tiny():
    """The cold-path section standalone (what ``bench.py --cold-path``
    runs) at KB scale: real prewarm against real
    fleets, segments actually provisioned, both ratios computed — so the
    cold section can never ship broken (the r5 lesson)."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)

    cold = await bench.cold_path_section(
        n_tensors=2, tensor_mb=0.25, steady_iters=2
    )
    assert cold["prewarm"]["ok"] is True
    # 256 KB tensors sit at the arena threshold: both pack into ONE
    # provisioned arena segment (steady-state pipeline).
    assert cold["prewarm"]["segments"] == 1
    assert cold["prewarm"]["bytes"] == 2 * 256 * 1024
    assert cold["cold_gbps"] > 0 and cold["cold_prewarmed_gbps"] > 0
    assert cold["cold_vs_steady"] > 0
    assert cold["cold_prewarmed_vs_steady"] > 0
    json.dumps(cold)


@pytest.mark.anyio
async def test_bench_ledger_overhead_section_tiny():
    """The ledger_overhead section standalone at KB scale: real warm
    one-sided gets timed telemetry-on vs telemetry-off, and the toggles
    restored afterwards (a bench crash must never leave telemetry off)."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)
    from torchstore_tpu.observability import ledger as obs_ledger
    from torchstore_tpu.observability import recorder as obs_recorder

    out = await bench.ledger_overhead_section(n_keys=16, key_kb=4, reps=2)
    assert out["on_us_per_key"] > 0 and out["off_us_per_key"] > 0
    assert "overhead_pct" in out
    assert obs_ledger.ledger().enabled
    assert obs_recorder.recorder().enabled
    json.dumps(out)


@pytest.mark.anyio
async def test_bench_history_overhead_section_tiny():
    """The history_overhead section standalone at KB scale: real warm
    one-sided gets timed with the sampler+detectors hot (50 ms sweeps) vs
    disabled, and both the enabled flag and the interval env restored
    afterwards (a bench crash must never leave history off or stuck at
    the 20x sweep rate)."""
    import os

    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)
    from torchstore_tpu.observability import history as obs_history

    interval_before = os.environ.get(obs_history.ENV_HISTORY_INTERVAL)
    enabled_before = obs_history.series_store().enabled
    out = await bench.history_overhead_section(n_keys=16, key_kb=4, reps=2)
    assert out["on_us_per_key"] > 0 and out["off_us_per_key"] > 0
    assert "overhead_pct" in out
    assert out["sample_interval_s"] == 0.05
    # The ON legs actually retained series (the sampler ran hot).
    assert out["retained_series"] > 0
    assert os.environ.get(obs_history.ENV_HISTORY_INTERVAL) == interval_before
    assert obs_history.series_store().enabled == enabled_before
    json.dumps(out)


@pytest.mark.anyio
async def test_bench_capacity_section_tiny():
    """The capacity section standalone (``bench.py --capacity``) at KB
    scale: a real tier-enabled fleet whose working set is 2x the pool
    budget with one leased-hot version — the spill writer demotes the
    cold rest, the warm leased leg stays zero-RPC, and cold versions
    fault back in with the right bytes. The ISSUE-12 acceptance shape can
    never ship broken."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)

    out = await bench.capacity_section(n_versions=4, n_keys=4, key_kb=4)
    assert out["working_set_mb"] >= 2 * out["budget_mb"]
    assert out["spilled_bytes"] > 0 and out["spilled_bytes_ratio"] > 0
    # Warm leased-version reps issued ZERO get RPCs: the one-sided path
    # survived the spill sweep (the "unchanged warm latency" acceptance).
    assert out["warm_get_rpcs"] == 0, out
    assert out["warm_get_after_spill_us"] > 0
    assert out["fault_in_p50_ms"] > 0 and out["fault_in_keys"] > 0
    assert out["cold_versions_measured"], out
    json.dumps(out)


@pytest.mark.anyio
async def test_bench_delta_sync_section_tiny():
    """The delta_sync section standalone (``bench.py --delta-sync``) at KB
    scale: a real bulk-path fleet publishing at none / int8_block /
    int4_block+delta through the weight channel. Wire compression and the
    analytic dequant-error bound are structural (asserted inside the
    section too) — the ISSUE-13 acceptance shape can never ship broken.
    Speedups are not asserted here: at KB scale fixed costs dominate; the
    full-scale run + bench_compare own those numbers."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)

    out = await bench.delta_sync_section(
        n_tensors=4, tensor_kb=16, versions=4, dcn_gbps=0.05
    )
    assert out["delta_none_gbps"] > 0
    assert out["delta_max_abs_err_none"] == 0.0
    # Structural: int8 blobs are ~4x smaller than f32 (minus header/scale
    # overhead), the low-churn delta leg far smaller still.
    assert out["delta_wire_compression_int8_block"] > 3.0, out
    assert out["delta_wire_compression_int4_delta"] > 5.0, out
    # The in-section analytic bound already asserted; keep the headline
    # fields present and finite for bench_compare.
    for k in ("delta_speedup_int8_block", "delta_speedup_delta",
              "delta_max_abs_err"):
        assert isinstance(out[k], float) and out[k] >= 0, (k, out[k])
    json.dumps(out)


@pytest.mark.anyio
async def test_bench_fanout_section_tiny():
    """The fanout section standalone (``bench.py --fanout``) at KB scale:
    a real K-fleet broadcast against real per-"host" volumes, both legs
    measured from the traffic matrix — the ISSUE-11 acceptance bound
    (tree/p2p trainer-host egress <= 1.5/K) and the deep-hop overlap
    (first layers before the seal through >= 2 relay hops) can never
    ship broken."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)

    out = await bench.fanout_section(
        k_fleets=4, n_layers=4, layer_kb=16, train_ms=40.0
    )
    assert out["p2p_trainer_egress_mb"] > 0
    assert out["fanout_egress_ratio"] is not None
    # O(1) trainer-host egress: the acceptance bound, not just a trend.
    assert out["fanout_egress_ratio"] <= out["egress_bound"], out
    # The deepest fleet sits >= 2 relay hops from the origin and still
    # overlaps the publish window (layers flow per hop, not per version).
    assert out["relay_hops"] >= 2, out
    assert out["fanout_overlap_ratio"] > 0, out
    json.dumps(out)


@pytest.mark.anyio
async def test_bench_metadata_scale_section_tiny():
    """The metadata_scale section standalone (``bench.py
    --metadata-scale``) at tiny load: real multi-process drivers against a
    real 1-shard and 2-shard fleet — the fan-out spawn/drive/merge
    machinery behind the ISSUE-14 acceptance (>= 2.5x locate/notify
    throughput at 4 shards, measured at full scale) can never ship
    broken. At smoke scale the load is driver-bound, so only positivity
    and shape are asserted, never the scaling factor itself."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)

    out = await bench.metadata_scale_section(
        shard_counts=(1, 2), n_drivers=2, n_logical=2, duration_s=0.5
    )
    assert out["metadata_ops_per_s_1shard"] > 0, out
    assert out["metadata_ops_per_s_sharded"] > 0, out
    assert out["metadata_scale_x"] > 0, out
    for leg in out["legs"].values():
        assert leg["failed_drivers"] == 0, leg
        assert leg["mix"]["locate"] > 0 and leg["mix"]["notify"] > 0, leg
        assert leg["mix"]["poll"] > 0, leg
    json.dumps(out)


@pytest.mark.anyio
async def test_bench_fleet_scale_section_tiny():
    """The fleet_scale section standalone (``bench.py --fleet-scale``) at
    tiny load: real loadgen driver processes against a real 2-volume
    fleet. The section asserts its own acceptance gates internally — p99
    under the SLO gate, the under-load telemetry budget (<= 2% plus the
    run's own demonstrated measurement-noise floor), zero failed drivers
    / op errors, and the induced ``shm.landing_stamp`` violation naming
    the landing stage — so this smoke proves the assertions themselves
    can never ship broken. The >= 1k-clients-over->=8-drivers bar is the
    full-scale run's contract (its defaults: 8 x 128)."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)

    out = await bench.fleet_scale_section(
        n_drivers=2,
        n_logical=4,
        duration_s=1.2,
        n_volumes=2,
        shared_keys=16,
        rate_hz=10.0,
        get_p99_gate_ms=2000.0,
        overhead_reps=8,
        violation_duration_s=1.0,
    )
    assert out["fleet_ops_per_s"] > 0, out
    assert 0 < out["fleet_get_p99_ms"] < out["get_p99_gate_ms"], out
    assert out["by_op"]["get"]["count"] > 0, out
    assert out["by_op"]["put"]["count"] > 0, out
    assert out["violation"]["dominant_stage"] == "landing", out["violation"]
    assert out["violation"]["violations"] > 0, out["violation"]
    assert "noise_floor_pct" in out["ledger_overhead_under_load"], out
    json.dumps(out)


@pytest.mark.anyio
async def test_bench_placement_section_tiny():
    """The placement section standalone (``bench.py --placement``) at
    tiny load: real loadgen driver processes with tenant cohorts and a
    Zipf-skewed key pick against a real 2-volume fleet, the control
    engine planning and acting through ``ts.control_plan`` /
    ``ts.rebalance``. The section asserts its own acceptance internally
    — non-empty plan on skew, at least one decision applied, zero failed
    drivers / op errors while a rebalance rides inside the skewed leg —
    so this smoke proves those assertions can never ship broken. The
    >= 70% recovery / <= 1.5x isolation bars are the full-scale run's
    bench_compare contract."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)

    out = await bench.placement_section(
        n_drivers=2,
        n_logical=4,
        duration_s=1.2,
        n_volumes=2,
        value_kb=8.0,
        shared_keys=16,
        rate_hz=10.0,
        tenants=2,
        zipf_alpha=1.6,
        rebalance_rounds=2,
    )
    assert out["uniform_ops_per_s"] > 0, out
    assert out["skewed_on_ops_per_s"] > 0, out
    assert out["rebalance_recovery_ratio"] > 0, out
    assert out["plan_actions"], out
    acted = [
        d
        for d in out["decisions"]
        if str(d.get("outcome", "")).startswith(("applied", "deferred"))
    ]
    assert acted, out["decisions"]
    # Tenant labels flow through to the merged scoreboard: both cohorts
    # observed ops, and the quiet tenant carries its own get p99.
    tenants = out["by_tenant_skewed_on"]
    assert set(tenants) == {"t0", "t1"}, tenants
    assert all(row["count"] > 0 for row in tenants.values()), tenants
    assert out["migration_bytes"] >= 0, out
    json.dumps(out)


@pytest.mark.anyio
async def test_bench_autoscale_section_tiny():
    """The autoscale section standalone (``bench.py --autoscale``) at
    tiny load: real diurnal loadgen drivers against a real fleet, the
    autoscale engine scaling 1 -> N -> back while the sampler integrates
    volume-seconds, then blob checkpoint -> full teardown -> cold
    restore. The section asserts its own acceptance internally — zero
    failed drivers / op errors, p99 under the gate, the fleet actually
    breathed, the volume-seconds gate, byte-valid restore — so this
    smoke proves those assertions can never ship broken. The <= 0.60
    elasticity dividend is the full-scale run's bench_compare contract;
    the smoke's gate is relaxed (2-volume ceiling leaves little room)."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)

    out = await bench.autoscale_section(
        n_drivers=2,
        n_logical=4,
        period_s=3.0,
        periods=1.0,
        n_volumes_fixed=2,
        value_kb=8.0,
        shared_keys=8,
        base_rate_hz=1.0,
        peak_rate_hz=40.0,
        get_p99_gate_ms=2000.0,
        out_window_mb=0.5,
        idle_window_mb=0.25,
        ledger_window_s=1.0,
        volume_seconds_gate=1.05,
        autoscale_tick_s=0.3,
        settle_s=3.0,
    )
    assert out["peak_fleet"] > 1, out
    assert out["final_fleet"] < out["peak_fleet"], out
    assert 0 < out["autoscale_volume_seconds_ratio"] <= 1.05, out
    assert 0 < out["autoscale_get_p99_ms"] < out["get_p99_gate_ms"], out
    assert out["cold_restore_s"] > 0, out
    assert out["restored_keys"] > 0, out
    json.dumps(out)


@pytest.mark.anyio
async def test_bench_cross_host_section_tiny():
    """The cross_host section standalone (``bench.py --cross-host``) at KB
    scale: an emulated 3-host topology over a paced 0.2 Gbps DCN, real
    metadata mirrors fanned through the relay tree and a real push
    session staging layers ahead of the read. The ISSUE-20 acceptance
    trio — push first-layer >= 2x faster than doorbell-pull, zero warm
    metadata RPCs, index-host egress <= 1.5/K of delivered mirror bytes
    — is asserted here at smoke scale so it can never ship broken."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.remove(REPO_ROOT)

    out = await bench.cross_host_section(
        k_hosts=3, layer_kb=64, rounds=2, emulate_gbps=0.05
    )
    # Push-staged reads skip the paced wire entirely; even at 64 KB the
    # doorbell leg pays ~1.3 ms of emulated DCN the push leg does not.
    assert out["push_speedup"] >= 2.0, out
    assert out["push_serves"] > 0, out
    # Warm remote gets resolve everything against the local mirror: no
    # metadata RPC counter cell moved (dict of moved cells, empty = none).
    assert not out["warm_metadata_rpcs"], out
    # Relay tree: root serves one image copy regardless of subscribers.
    assert out["meta_egress_ratio"] <= out["meta_egress_bound"], out
    json.dumps(out)
