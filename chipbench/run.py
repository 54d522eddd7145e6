"""The chip benchmark's command: one run of one cell.

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in `BENCHMARK.json`: a configuration
(`chipbench/configs/<config>.json`, via the ``file`` the benchmark names)
under a traffic mix (`chipbench/traffic/<traffic>.json`). Everything else a
cell needs is a plug-in file found by the name the data gives: the mix's
``loop`` (`chipbench/loops/`) and ``path`` (`chipbench/paths/`), the
configuration's ``trainer`` (`chipbench/trainers/`), and one reader per
per-layer metric (`chipbench/layer_metrics/<metric>.py`). This file knows no
cell, configuration, mix or per-layer metric by name.

ONE process owns the chip(s) and plays trainer and generator; the
controller and the storage volume are the usual host-only actor children of
``ts.initialize()``. There is no CPU mode: without a TPU, or with fewer
chips than the cell asks for, the command exits non-zero and prints no
result. The last line of stdout is the result:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}}            (and "breakdown" with --trace 1)

``--trace 0`` gives the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (store spans on, a profiler trace of the window's first
cycles).

jax and torchstore_tpu are imported inside functions only: the actor
children re-import this file as their ``__main__`` and must stay off the
chip.
"""

import argparse
import asyncio
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
import traceback

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
STORE = "chipbench"
# Under the 1200 s a cell's first (compiling) run may take.
WATCHDOG_S = 1150.0
_COMPILES = {"requests": 0, "hits": 0, "listening": False}


def say(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------------------
# cells are data
# --------------------------------------------------------------------------


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_plugin(kind: str, name: str, root: str = ROOT):
    """The module `<root>/chipbench/<kind>/<name>.py`, found by name."""
    path = os.path.join(root, "chipbench", kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} plug-in {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{''.join(c if c.isalnum() else '_' for c in name)}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str, root: str = ROOT) -> dict:
    """Everything `BENCHMARK.json` and the data files say about one cell."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; there are {sorted(cells)}")
    cell = cells[workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def applies(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "root": root,
        "name": workload,
        "chips": cell["chips"],
        "config": load_json(os.path.join(root, config_entry["file"])),
        "mix": load_json(
            os.path.join(root, "chipbench", "traffic", f"{cell['traffic']}.json")
        ),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


# --------------------------------------------------------------------------
# what a loop sees of the harness
# --------------------------------------------------------------------------


def _listen_for_compiles() -> None:
    import jax

    if _COMPILES["listening"]:
        return
    _COMPILES["listening"] = True

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            _COMPILES["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            _COMPILES["hits"] += 1

    jax.monitoring.register_event_listener(on_event)


def counters() -> dict[str, float]:
    """This process's store counters, flat: ``name{k=v,...}`` -> value (a
    histogram gives ``name_sum`` and ``name_count``)."""
    import torchstore_tpu as ts

    out: dict[str, float] = {}
    for name, metric in ts.metrics_snapshot().items():
        for series in metric["series"]:
            labels = ",".join(f"{k}={v}" for k, v in sorted(series["labels"].items()))
            value = series["value"]
            if isinstance(value, dict):
                for part in ("sum", "count"):
                    if part in value:
                        out[f"{name}_{part}{{{labels}}}"] = float(value[part])
            elif isinstance(value, (int, float)):
                out[f"{name}{{{labels}}}"] = float(value)
    return out


class Session:
    """One run of one cell, as its loop drives it: the trainer, the path,
    the clock, the phases, the window."""

    def __init__(self, mix: dict, seconds: float, trace_dir):
        self.mix = mix
        self.seconds = seconds
        self.trace_dir = trace_dir  # None: no profiler, no store spans
        self.trainer = None
        self.path = None
        self.generator_shardings = None
        self.phases: list[dict] = []  # of measured cycles only
        self.collections: list[dict] = []  # Python's collector in the window
        self.setup_ends = None
        self.counters_before: dict = {}
        self.counters_after: dict = {}
        self.compiles_in_window = None
        self._compiles_at_window = 0
        self._measuring = False
        self._profiling = False

    # -- clock and phases ---------------------------------------------------

    @contextlib.contextmanager
    def phase(self, name: str, cycle: int):
        """Time one phase of a cycle on the host's clock, and write it into
        the profiler's trace (where one runs) so that the device's idle gaps
        can be named by what the host was doing."""
        import jax

        with jax.profiler.TraceAnnotation(f"chipbench/{name}"):
            start = time.perf_counter()
            try:
                yield
            finally:
                if self._measuring:
                    self.phases.append(
                        {
                            "name": name,
                            "cycle": cycle,
                            "start": start,
                            "end": time.perf_counter(),
                        }
                    )

    def reference(self):
        """What the generator must hold now: the trainer's params under the
        generator's shardings, moved by XLA's own reshard (none of the
        store's code). The very same buffers where the shardings agree. Across
        meshes `jax.device_put` takes the tree through the host (6.6 s for
        6.3 GB on four chips; PERF.md section 7 has the remedy)."""
        import jax

        return jax.device_put(self.trainer.params, self.generator_shardings)

    # -- warm-up ends when these stop moving ------------------------------------

    async def segments_created(self) -> float:
        """Fresh /dev/shm segments created so far, by any process of the
        store (the client's and the volume's counters)."""
        import torchstore_tpu as ts

        fleet = await ts.fleet_snapshot(store_name=STORE)
        series = fleet["metrics"].get("ts_shm_segments_created_total", {}).get(
            "series", []
        )
        return sum(s["value"] for s in series)

    def compile_requests(self) -> int:
        return _COMPILES["requests"]

    # -- the window -------------------------------------------------------------

    def begin_window(self) -> None:
        # A full pass of Python's collector over this process's heap (jax,
        # numpy, the store: all long-lived by now) takes 60-130 ms on the
        # chip's host and falls into whichever phase allocates the object
        # that trips it; at hundreds of leaves that is one acquire in five,
        # +14 % on that one (PERF.md, PR 22). As a serving process does after
        # its warm-up: collect once, then take what is alive out of the
        # collector's sight, so that a pass in the window walks only what
        # the window made. Set-up, not window.
        gc.collect()
        gc.freeze()
        self.setup_ends = time.perf_counter()
        self.counters_before = counters()
        self._compiles_at_window = _COMPILES["requests"]
        self._measuring = True
        gc.callbacks.append(self._on_collection)

    def cycle_begins(self, k: int) -> None:
        """Measured cycle ``k`` (from 0) is about to start."""
        import jax

        if self.trace_dir is not None and k == 0:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # annotations only: keep the host light
            jax.profiler.start_trace(
                os.path.join(self.trace_dir, "profile"), profiler_options=options
            )
            self._profiling = True

    def cycle_ends(self, k: int) -> None:
        if self._profiling and k + 1 >= self.mix["traced_cycles"]:
            self._stop_profiler()

    def end_window(self) -> None:
        self.leave_window()
        self.counters_after = counters()
        self.compiles_in_window = _COMPILES["requests"] - self._compiles_at_window

    def leave_window(self) -> None:
        """Undo what `begin_window` did to the process; on any way out."""
        self._stop_profiler()
        self._measuring = False
        if self._on_collection in gc.callbacks:
            gc.callbacks.remove(self._on_collection)
        gc.unfreeze()

    def _stop_profiler(self) -> None:
        import jax

        if self._profiling:
            self._profiling = False
            jax.profiler.stop_trace()

    def _on_collection(self, event: str, details: dict) -> None:
        """Python's garbage collector runs where it will; a pass inside a
        timed phase is host noise, and the info line says how much."""
        now = time.perf_counter()
        if event == "start":
            self.collections.append({"start": now, "generation": details["generation"]})
        elif self.collections and "end" not in self.collections[-1]:
            self.collections[-1]["end"] = now

    def per_cycle(self) -> dict:
        """For the info line: every phase's seconds, cycle by cycle, and the
        seconds of them that the garbage collector took (phases it never
        touched are left out of the second)."""
        done = [c for c in self.collections if "end" in c]
        seconds: dict[str, list] = {}
        collecting: dict[str, list] = {}
        for p in self.phases:
            seconds.setdefault(p["name"], []).append(round(p["end"] - p["start"], 4))
            inside = sum(
                max(0.0, min(c["end"], p["end"]) - max(c["start"], p["start"])) for c in done
            )
            collecting.setdefault(p["name"], []).append(round(inside, 4))
        return {
            "phase_s": seconds,
            "gc_s": {name: v for name, v in collecting.items() if any(v)},
            "gc_passes": {
                f"gen{g}": sum(c["generation"] == g for c in done) for g in (0, 1, 2)
            },
        }


@dataclasses.dataclass
class TracedRun:
    """What a per-layer metric's reader is given. Times are seconds; phases
    and spans share the host's ``perf_counter`` clock."""

    phases: list[dict]  # measured cycles: {"name", "cycle", "start", "end"}
    spans: list[dict]  # the store's spans in this process: {"name", "start", "end"}
    counters: dict  # store counters, after the window minus before it
    cycles: int  # measured cycles that completed
    planes: dict  # the profiler's trace (trace_reduce.load_xplane)
    device: dict  # trace_reduce.reduce_device of it
    step_program: str  # the trainer's step, as the device trace names it
    last_readings: list = dataclasses.field(default_factory=list)

    def phases_named(self, name: str) -> list[dict]:
        return [p for p in self.phases if p["name"] == name]

    def counter(self, prefix: str) -> float:
        """Sum over the window of every series whose flat name starts with
        ``prefix`` (``ts_meta_rpcs_total{`` for all its labels)."""
        return sum(v for k, v in self.counters.items() if k.startswith(prefix))

    def mean_per_phase(self, phase: str, reading) -> float | None:
        """Mean over the window's ``phase``s of ``reading(phase)``; phases for
        which it gives None are left out. The mean and not the median: a
        window is a whole number of rounds whose cycles differ by design of
        the mix (its file says how), and the end-to-end sample is a round's
        mean, so the layers add up to it. The readings stay in
        ``last_readings`` for the run's info line."""
        values = [v for v in map(reading, self.phases_named(phase)) if v is not None]
        self.last_readings = values
        return statistics.fmean(values) if values else None


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------


def _set_store_trace(path) -> None:
    """Turn the store's span tracing on (a path) or off (None) for this
    process and the actor children it starts. The collector reads the
    variable when it is imported, so it is re-armed here."""
    from torchstore_tpu.observability import tracing

    if path is None:
        os.environ.pop(tracing.ENV_TRACE, None)
    else:
        os.environ[tracing.ENV_TRACE] = path
    tracing.collector().reinit_after_fork()


async def run_cell(cell: dict, devices, seconds: float, seed: int, trace: bool) -> dict:
    """Set up, warm up, measure one window, reduce. Returns the result line's
    object plus an ``info`` entry for the lines printed before it."""
    import jax

    import torchstore_tpu as ts
    from chipbench import trace_reduce, trees

    config, mix, root = cell["config"], cell["mix"], cell["root"]
    trace_dir = None
    if trace:
        trace_dir = os.path.join(root, ".chipbench_tmp", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        _set_store_trace(os.path.join(trace_dir, "store_spans.json"))
    session = Session(mix, seconds, trace_dir)
    loop = load_plugin("loops", mix["loop"], root)
    trainer_plugin = load_plugin("trainers", config["trainer"]["plugin"], root)
    _listen_for_compiles()
    compiles_before = dict(_COMPILES)
    try:
        await ts.initialize(store_name=STORE)
        try:
            session.trainer = trainer_plugin.make(
                config, devices, config["rule_sets"][mix["trainer_rules"]], seed
            )
            session.generator_shardings = trees.shardings_for(
                session.trainer.params,
                config["rule_sets"][mix["generator_rules"]],
                devices,
            )
            session.path = load_plugin("paths", mix["path"], root).make(STORE, mix)
            await session.path.open()
            try:
                outcome = await loop.drive(session)
            finally:
                await session.path.close()
        finally:
            await ts.shutdown(STORE)

        samples = loop.samples(session.phases, mix)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
        device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak,
        }
        result = {
            "correct": outcome["failed"] == 0
            and outcome["attempted"] > 0
            and session.compiles_in_window == 0,
            "attempted": outcome["attempted"],
            "failed": outcome["failed"],
            "metrics": {},
            "device": device,
        }
        info = {
            "problems": outcome["problems"],
            "warmup_cycles": outcome["warmup_cycles"],
            "samples": samples,
            "compiles_in_window": session.compiles_in_window,
            "compile_requests": _COMPILES["requests"] - compiles_before["requests"],
            "compiled_anew": (_COMPILES["requests"] - compiles_before["requests"])
            - (_COMPILES["hits"] - compiles_before["hits"]),
            "weights_bytes": trees.tree_nbytes(session.trainer.params),
            "leaves": len(jax.tree.leaves(session.trainer.params)),
            **session.per_cycle(),
        }
        if not trace:
            run_level = {
                "setup_s": session.setup_ends - T_PROCESS,
                "peak_hbm_GB": peak / 1e9,
            }
            for metric in cell["end_to_end"]:
                name = metric["name"]
                if name in run_level:
                    value = run_level[name]
                elif samples.get(name):
                    value = statistics.median(samples[name])
                else:
                    continue
                result["metrics"][name] = {"value": value, "unit": metric["unit"]}
        else:
            planes = trace_reduce.load_xplane(
                trace_reduce.find_xplane(os.path.join(trace_dir, "profile"))
            )
            reduced = trace_reduce.reduce_device(planes, len(devices))
            run = TracedRun(
                phases=session.phases,
                spans=trace_reduce.load_spans(os.path.join(trace_dir, "store_spans.json")),
                counters={
                    k: v - session.counters_before.get(k, 0.0)
                    for k, v in session.counters_after.items()
                },
                cycles=outcome["attempted"] - outcome["failed"],
                planes=planes,
                device=reduced,
                step_program=trainer_plugin.STEP_PROGRAM,
            )
            info["per_phase"] = {}
            for metric in cell["per_layer"]:
                reader = load_plugin("layer_metrics", metric["name"], root)
                run.last_readings = []
                value = reader.read(run)
                if run.last_readings:
                    info["per_phase"][metric["name"]] = run.last_readings
                if value is not None:
                    result["metrics"][metric["name"]] = {
                        "value": value,
                        "unit": metric["unit"],
                    }
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
            info["counters"] = {k: v for k, v in run.counters.items() if v}
            # What the trace held, for whoever has to repair the reduction.
            info["trace_planes"] = {
                name: {line: len(events) for line, events in lines.items()}
                for name, lines in planes.items()
                if not name.startswith("/host:")
            }
            info["trace_modules"] = sorted(
                {
                    event[0]
                    for name, lines in planes.items()
                    if name.startswith(trace_reduce.DEVICE_PLANE_PREFIX)
                    for event in lines.get(trace_reduce.MODULES_LINE, [])
                }
            )[:12]
        result["info"] = info
        return result
    finally:
        session.leave_window()
        if trace:
            _set_store_trace(None)
            shutil.rmtree(trace_dir, ignore_errors=True)


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from chipbench import procs

    procs.arm_watchdog(WATCHDOG_S)
    cell = load_cell(args.workload)
    peaks = load_json(os.path.join(HERE, "peaks.json"))

    # The TPU runtime logs under /tmp/tpu_logs unless told otherwise; a run
    # writes only inside its checkout.
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".chipbench_tmp", "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chipbench: jax found no TPU (platform {devices[0].platform!r}); "
            "the benchmark has no CPU mode",
            file=sys.stderr,
        )
        return 1
    if len(devices) < cell["chips"]:
        print(
            f"chipbench: {args.workload} needs {cell['chips']} chips, jax sees "
            f"{len(devices)}",
            file=sys.stderr,
        )
        return 1
    devices = devices[: cell["chips"]]
    if devices[0].device_kind not in peaks["devices"]:
        print(
            f"chipbench: no peaks for device kind {devices[0].device_kind!r} in "
            "chipbench/peaks.json",
            file=sys.stderr,
        )
        return 1

    from torchstore_tpu.utils import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    say(
        f"cell {args.workload}: seed {args.seed}, {args.seconds:g} s, trace "
        f"{args.trace}, {devices[0].device_kind} x {len(devices)}"
    )
    result = None
    try:
        result = asyncio.run(
            run_cell(cell, devices, args.seconds, args.seed, bool(args.trace))
        )
    except Exception:  # noqa: BLE001 - the boundary: report, then fail with no result
        traceback.print_exc()
    clean = procs.leave_no_process()
    if result is None or not clean:
        return 1
    report(result)
    return 0


def report(result: dict) -> None:
    """Print what a run found; the result is the last line."""
    info = result.pop("info")
    for problem in info.pop("problems"):
        say(f"FAILED CHECK: {problem}")
    for name, values in info.pop("samples").items():
        say(f"{name}: n={len(values)} " + " ".join(f"{v:.4f}" for v in values))
    say("info: " + json.dumps(info))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
