#!/usr/bin/env python
"""Drive a small store round trip and capture the decision-telemetry plane:
prints one JSON doc to stdout holding the traffic matrix, the SLO
scoreboard (``ts.slo_report()``), the control plane's dry-run view
(``ts.control_plan()`` — what the policy engine WOULD do over this
traffic), the elastic plane's dry-run view (``ts.autoscale_plan()`` plus
the live fleet size it solved against — a ``--watch`` run leaves a
fleet-size time series), and the fleet's retained time-series history
(``ts.history()``),
and writes the merged flight record to /tmp/ts_flight_record.json. Safe to
run anywhere a store can boot.

``--watch N`` keeps the store up and re-captures N times at ``--interval``
seconds, appending one JSON doc per line (JSONL) to ``--out`` (default
stdout) — a device run leaves a time-series artifact, not just a final
snapshot."""

import argparse
import asyncio
import json
import sys
import time

import numpy as np


async def _capture(ts, include_record: bool) -> dict:
    matrix = await ts.traffic_matrix(store_name="telemetry_capture")
    slo = await ts.slo_report(store_name="telemetry_capture")
    plan = await ts.control_plan(store_name="telemetry_capture")
    scale = await ts.autoscale_plan(store_name="telemetry_capture")
    doc = {
        "captured_ts": time.time(),
        "traffic": matrix,
        "slo": slo,
        "control_plan": plan,
        # The elastic plane's dry run: what the autoscaler WOULD do over
        # this traffic, plus the fleet view it solved against (live/
        # draining counts, idle-round hysteresis, blob-spill backlog). A
        # --watch run therefore leaves a fleet-size time series — one
        # fleet.volumes sample per capture line.
        "autoscale_plan": scale,
        "fleet_size": (scale.get("fleet") or {}).get("volumes"),
        "history": await ts.history(store_name="telemetry_capture"),
    }
    if include_record:
        doc["flight_record"] = await ts.flight_record(
            store_name="telemetry_capture"
        )
    return doc


async def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--watch", type=int, default=0, metavar="N",
        help="re-capture N times after the first (JSONL, one doc/line)",
    )
    parser.add_argument(
        "--interval", type=float, default=5.0, metavar="S",
        help="seconds between --watch captures (default 5)",
    )
    parser.add_argument(
        "--out", default=None, metavar="PATH",
        help="append captures to this file instead of stdout",
    )
    args = parser.parse_args()

    import torchstore_tpu as ts

    await ts.initialize(
        store_name="telemetry_capture",
        strategy=ts.SingletonStrategy(default_transport_type="shm"),
    )
    try:
        items = {
            f"cap/{i}": np.random.rand(65536).astype(np.float32)
            for i in range(16)
        }
        await ts.put_batch(items, store_name="telemetry_capture")
        dests = {k: np.empty_like(v) for k, v in items.items()}
        await ts.get_batch(dict(dests), store_name="telemetry_capture")
        await ts.get_batch(dict(dests), store_name="telemetry_capture")
        record = await ts.flight_record(store_name="telemetry_capture")

        # One-shot CLI between captures: nothing else runs on this loop,
        # so synchronous writes cannot stall concurrent work.
        def emit(doc: dict) -> None:
            line = json.dumps(doc)
            if args.out:
                with open(args.out, "a") as f:  # tslint: disable=async-blocking
                    f.write(line + "\n")
            else:
                print(line)

        doc = await _capture(ts, include_record=False)
        emit(doc)
        for i in range(max(0, args.watch)):
            # Keep traffic flowing so each re-capture sees a live window,
            # not a decaying ledger of the boot-time batch.
            await ts.get_batch(dict(dests), store_name="telemetry_capture")
            await asyncio.sleep(max(0.0, args.interval))
            emit(await _capture(ts, include_record=False))
        with open("/tmp/ts_flight_record.json", "w") as f:  # tslint: disable=async-blocking
            json.dump(record, f)
        n_hist = len(
            (doc["history"]["processes"].get("client") or {}).get("series")
            or {}
        )
        print(
            f"# captured {len(record['events'])} flight event(s), "
            f"{len(doc['traffic']['edges'])} matrix source host(s), "
            f"{len(doc['control_plan'].get('actions') or ())} planned "
            f"control action(s), {len(doc['autoscale_plan'].get('actions') or ())} "
            f"planned autoscale action(s) over {doc['fleet_size']} volume(s), "
            f"{n_hist} client history series, "
            f"{1 + max(0, args.watch)} capture(s)",
            file=sys.stderr,
        )
        return 0
    finally:
        await ts.shutdown("telemetry_capture")


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
