"""The benchmark at a tiny size on CPU devices, through the functions the
command calls (`run.load_cell`, `run.run_cell`, `run.report`), and the
contract's rules for `BENCHMARK.json`. Nothing here is a device number."""

import importlib.util
import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO_ROOT)

from chipbench import run, trace_reduce  # noqa: E402

BENCH = run.load_json(os.path.join(REPO_ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


# --------------------------------------------------------------------------
# a tiny copy of the benchmark
# --------------------------------------------------------------------------


def shrink(config: dict) -> dict:
    """The same configuration file with toy sizes: the only thing a test
    changes is data."""
    if config["trainer"]["plugin"] == "llama_model":
        config["trainer"].update(preset="tiny_moe", train_tokens=[1, 16], prompt_len=8)
        config["num_hidden_layers"] = 2
    else:
        config.update(
            hidden_size=64, intermediate_size=32, num_experts=4, vocab_size=128,
            num_hidden_layers=2,
        )
        config["assumed"]["kv_proj_size"] = 64
    return config


@pytest.fixture
def tiny_root(tmp_path):
    """A directory that holds only `BENCHMARK.json` and `chipbench/`, with
    every configuration shrunk."""
    shutil.copytree(
        os.path.join(REPO_ROOT, "chipbench"),
        tmp_path / "chipbench",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    shutil.copy(os.path.join(REPO_ROOT, "BENCHMARK.json"), tmp_path)
    for entry in BENCH["configs"]:
        path = tmp_path / entry["file"]
        path.write_text(json.dumps(shrink(json.loads(path.read_text()))))
    return str(tmp_path)


@pytest.fixture
def cpu_as_device(monkeypatch):
    """On the CPU the profiler's trace has no device plane: hand the
    reduction the host's XLA threads as device 0..n (steering in the test,
    not an option of the program)."""
    real = trace_reduce.load_xplane

    def load(path):
        planes = real(path)
        host = planes["/host:CPU"]
        ops = [e for line, ev in host.items() if line.startswith("tf_XLA") for e in ev]
        modules = [e for e in host.get("python", []) if e[0].startswith("PjitFunction")]
        for i in range(4):
            planes[f"/device:TPU:{i}"] = {"XLA Ops": ops, "XLA Modules": modules}
        return planes

    monkeypatch.setattr(trace_reduce, "load_xplane", load)


async def run_tiny(root: str, workload: str, trace: bool, seconds: float = 0.3) -> dict:
    cell = run.load_cell(workload, root=root)
    devices = jax.devices()[: cell["chips"]]
    return await run.run_cell(cell, devices, seconds, seed=3, trace=trace)


# --------------------------------------------------------------------------
# the cells
# --------------------------------------------------------------------------


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
@pytest.mark.parametrize("workload", CELLS)
async def test_cell_runs_at_tiny_size(tiny_root, cpu_as_device, capsys, workload, trace):
    result = await run_tiny(tiny_root, workload, trace)
    cell = run.load_cell(workload, root=tiny_root)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] >= 1
    assert result["info"]["compiles_in_window"] == 0
    assert not result["info"]["problems"]
    wanted = cell["per_layer"] if trace else cell["end_to_end"]
    units = {m["name"]: m["unit"] for m in wanted}
    for name, reading in result["metrics"].items():
        assert set(reading) == {"value", "unit"} and reading["unit"] == units[name]
        assert isinstance(reading["value"], float)
    if trace:
        # A reader that finds nothing leaves its metric out; the device's
        # and the benchmark's own are always there.
        assert {"device_idle_share", "h2d_tail_s", "acquire_stall_s"} <= set(result["metrics"])
        assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in result["breakdown"].values())
        # ... and the store's trace is off again for whoever runs next.
        assert "TORCHSTORE_TPU_TRACE" not in os.environ
    else:
        assert set(result["metrics"]) == set(units)
    assert result["device"]["count"] == cell["chips"]
    # The info line has every phase cycle by cycle, and the collector's share.
    assert len(result["info"]["phase_s"]["acquire"]) == result["attempted"]
    assert set(result["info"]["gc_s"]) <= set(result["info"]["phase_s"])

    # The last printed line is the result, with exactly the contract's keys.
    run.report(result)
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(last) == RESULT_KEYS | ({"breakdown"} if trace else set())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(last["device"])


async def test_direct_cell_on_the_host_staged_rung(tiny_root, monkeypatch):
    """What the chip does: the transfer engine does not serve TPU buffers,
    so the store picks host staging and the path's check holds it to that."""
    from torchstore_tpu.transport import device_transfer

    monkeypatch.setattr(device_transfer, "SERVED_PLATFORMS", frozenset())
    direct = next(w["name"] for w in BENCH["workloads"] if w["traffic"] == "direct")
    result = await run_tiny(tiny_root, direct, trace=False)
    assert result["correct"], result["info"]["problems"]


async def test_a_wrong_leaf_fails_the_cycle(tiny_root, monkeypatch):
    """The comparison decides ``correct``: a path that hands back one
    changed leaf makes every cycle fail."""
    workload = CELLS[0]
    cell = run.load_cell(workload, root=tiny_root)
    path_file = os.path.join(tiny_root, "chipbench", "paths", cell["mix"]["path"] + ".py")
    source = open(path_file).read().replace(
        'return got["params"], version',
        'tree = got["params"]\n'
        "        import jax\n"
        "        flat, treedef = jax.tree.flatten(tree)\n"
        "        flat[0] = flat[0] + 1\n"
        "        return jax.tree.unflatten(treedef, flat), version",
    )
    assert source != open(path_file).read()
    with open(path_file, "w") as f:
        f.write(source)
    with pytest.raises(RuntimeError, match="warm-up cycle 0 failed"):
        await run_tiny(tiny_root, workload, trace=False)


async def test_new_cell_is_new_files_only(tiny_root, cpu_as_device):
    """A configuration, a mix and a per-layer metric dropped in as new files,
    with entries appended to `BENCHMARK.json`, run with no edit to a file
    that was there."""
    before = {
        os.path.join(d, f): open(os.path.join(d, f), "rb").read()
        for d, _, files in os.walk(os.path.join(tiny_root, "chipbench"))
        for f in files
    }
    bench_path = os.path.join(tiny_root, "BENCHMARK.json")
    bench = run.load_json(bench_path)
    old = bench["workloads"][0]
    config = next(c for c in bench["configs"] if c["name"] == old["config"])
    new_config = dict(config, name="extra-config", file="chipbench/configs/extra-config.json")
    shutil.copy(os.path.join(tiny_root, config["file"]), os.path.join(tiny_root, new_config["file"]))
    mix = run.load_json(os.path.join(tiny_root, "chipbench", "traffic", old["traffic"] + ".json"))
    mix["traced_cycles"] = 1
    with open(os.path.join(tiny_root, "chipbench", "traffic", "extra-mix.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(tiny_root, "chipbench", "layer_metrics", "publishes.py"), "w") as f:
        f.write(
            'LAYER, UNIT, SOURCE, MOVES = "entry", "count", "host_clock", "publish_s"\n\n'
            "def read(run):\n    return float(len(run.phases_named('publish')))\n"
        )
    bench["configs"].append(new_config)
    bench["workloads"].append(
        dict(old, name="extra.cell", config="extra-config", traffic="extra-mix")
    )
    bench["per_layer"].append(
        {"name": "publishes", "unit": "count", "better": "higher", "source": "host_clock",
         "layer": "entry", "moves": "publish_s", "workloads": ["extra.cell"]}
    )
    with open(bench_path, "w") as f:
        json.dump(bench, f)

    result = await run_tiny(tiny_root, "extra.cell", trace=True)
    assert result["correct"]
    assert result["metrics"]["publishes"]["value"] == result["attempted"]
    # The metric is the new cell's alone, and no file that was there changed.
    assert "publishes" not in {m["name"] for m in run.load_cell(old["name"], root=tiny_root)["per_layer"]}
    for path, content in before.items():
        assert open(path, "rb").read() == content, path


def test_no_tpu_no_result():
    """The command has no CPU mode: non-zero exit and no result line."""
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELLS[0], "--seed", "0",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO_ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0
    assert not [line for line in done.stdout.splitlines() if line.startswith("{")]
    assert "no TPU" in done.stderr


# --------------------------------------------------------------------------
# BENCHMARK.json against the contract, and against the files it names
# --------------------------------------------------------------------------


def test_benchmark_json_meets_the_contract():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert len(BENCH["command"]) <= 32 and BENCH["paths"] == ["chipbench"]
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
    texts = BENCH["command"][:]
    for config in BENCH["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(config["name"]) and all(NAME.match(k) for k in config["reduced"])
        assert config["file"].startswith("chipbench/") and len(config["reduced"]) <= 16
        assert os.path.isfile(os.path.join(REPO_ROOT, config["file"]))
        texts += [config["source"], config["why"]]
    assert len({c["name"] for c in BENCH["configs"]}) == len(BENCH["configs"])
    assert len({c["file"] for c in BENCH["configs"]}) == len(BENCH["configs"])
    assert len({c["source"] for c in BENCH["configs"]}) == len(BENCH["configs"])
    assert {c["name"] for c in BENCH["configs"]} == {w["config"] for w in BENCH["workloads"]}

    assert 2 <= len(BENCH["workloads"]) <= 24
    for cell in BENCH["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
        assert cell["chips"] in (1, 4)
        texts.append(cell["why"])
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 4)

    end_to_end = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in end_to_end and end_to_end["setup_s"]["bound"] <= 0.1
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in end_to_end
        assert set(m.get("workloads", [])) <= set(CELLS)
        # ... and is reported only where the metric it moves is.
        assert set(m.get("workloads", CELLS)) <= set(end_to_end[m["moves"]].get("workloads", CELLS))
        texts.append(m["layer"])
    for name in CELLS:
        cell = run.load_cell(name)
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"], name
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for text in texts:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for _, _, files in os.walk(os.path.join(REPO_ROOT, "chipbench")):
        for f in files:
            if not f.endswith(".pyc"):
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def test_every_name_in_the_data_has_its_file():
    for m in BENCH["per_layer"]:
        reader = run.load_plugin("layer_metrics", m["name"])
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"]
        ), m["name"]
    for name in CELLS:
        cell = run.load_cell(name)
        mix, config = cell["mix"], cell["config"]
        assert hasattr(run.load_plugin("loops", mix["loop"]), "drive")
        assert hasattr(run.load_plugin("paths", mix["path"]), "make")
        trainer = run.load_plugin("trainers", config["trainer"]["plugin"])
        assert hasattr(trainer, "make") and trainer.STEP_PROGRAM
        assert {mix["trainer_rules"], mix["generator_rules"]} <= set(config["rule_sets"])
        entry = next(c for c in BENCH["configs"] if c["file"].endswith(config["name"] + ".json"))
        assert entry["source"] == config["source"]
        assert entry["reduced"] == list(config["reduced"])
    peaks = run.load_json(os.path.join(REPO_ROOT, "chipbench", "peaks.json"))
    assert "TPU v5 lite" in peaks["devices"]


def test_configurations_hold_the_published_sizes():
    """Mixtral's file against the repo's preset (it is not in the catalog);
    OLMoE's against the catalog entry, where the catalog is installed."""
    import jax.numpy as jnp

    from torchstore_tpu.models.llama import LlamaConfig

    for depth in (1, 2):
        config = run.load_json(
            os.path.join(REPO_ROOT, f"chipbench/configs/mixtral-8x7b-l{depth}.json")
        )
        preset = LlamaConfig.mixtral_8x7b()
        assert (
            config["vocab_size"], config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"],
            config["num_local_experts"], config["num_experts_per_tok"], config["rope_theta"],
            config["rms_norm_eps"],
        ) == (
            preset.vocab_size, preset.hidden_size, preset.intermediate_size, preset.num_heads,
            preset.num_kv_heads, preset.head_dim, preset.num_experts,
            preset.num_experts_per_tok, preset.rope_theta, preset.rms_eps,
        )
        model = run.load_plugin("trainers", "llama_model").model_config(config)
        assert model.num_layers == depth and model.param_dtype == jnp.bfloat16

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not installed here")
    rows = [json.loads(line) for line in open(catalog)]
    leaf_table = run.load_plugin("trainers", "leaf_table")
    for depth, leaves, nbytes in ((4, 807, 3768651776),):
        config = run.load_json(
            os.path.join(REPO_ROOT, f"chipbench/configs/olmoe-1b-7b-hf-l{depth}.json")
        )
        row = next(r for r in rows if config["source"].startswith(r["source_url"]))
        differs = {k for k, v in row["config"].items() if config.get(k, "missing") != v}
        assert differs == {"num_hidden_layers"} == set(config["reduced"])
        shapes = leaf_table.leaf_shapes(config)
        assert len(shapes) == leaves
        assert sum(2 * math.prod(s) for s in shapes.values()) == nbytes


# --------------------------------------------------------------------------
# the reduction, on a small recorded trace
# --------------------------------------------------------------------------


def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        doc = json.load(f)
    planes = {
        plane: {line: [tuple(e) for e in events] for line, events in lines.items()}
        for plane, lines in doc["planes"].items()
    }
    return planes, doc["spans"], doc["expected"]


def test_trace_reduce_on_the_recorded_trace():
    planes, spans, expected = recorded()
    reduced = trace_reduce.reduce_device(planes, 1)
    assert reduced["window_s"] == pytest.approx(expected["window_s"])
    assert reduced["busy_s"] == pytest.approx(expected["busy_s"])
    assert reduced["device_ops"][0][0] == expected["top_op"]
    assert dict(reduced["idle_gaps"]) == pytest.approx(expected["idle_gaps"])
    assert trace_reduce.program_seconds(planes, expected["program"]) == pytest.approx(
        expected["program_seconds"]
    )
    outer = trace_reduce.spans_within(spans, "put_batch", 0.0, 100.0)
    assert [
        trace_reduce.time_outside(s, spans, "transport.put") for s in outer
    ] == pytest.approx(expected["put_batch_self_s"])
    with pytest.raises(ValueError, match="2 chips"):
        trace_reduce.reduce_device(planes, 2)


def test_trace_reduce_on_a_trace_recorded_on_the_chip():
    with open(os.path.join(HERE, "recorded_trace_v5e.json")) as f:
        doc = json.load(f)
    planes = {
        plane: {line: [tuple(e) for e in events] for line, events in lines.items()}
        for plane, lines in doc["planes"].items()
    }
    reduced = trace_reduce.reduce_device(planes, 1)
    expected = doc["expected"]
    assert reduced["window_s"] == pytest.approx(0.738666 + 0.001482 - 0.050631, abs=2e-6)
    assert reduced["window_s"] == pytest.approx(expected["window_s"])
    assert reduced["busy_s"] == pytest.approx(expected["busy_s"])
    # 41 microseconds of work in 0.69 s: two compares, one step, the copies.
    assert 100 * (1 - reduced["busy_s"] / reduced["window_s"]) == pytest.approx(99.994, abs=1e-3)
    assert [op for op, _ in reduced["device_ops"]] == [op for op, _ in expected["device_ops"]]
    assert dict(reduced["idle_gaps"]) == pytest.approx(dict(expected["idle_gaps"]))
    assert trace_reduce.program_seconds(planes, "jit__lambda") == pytest.approx([1.6e-05], rel=0.05)


def test_intervals():
    assert trace_reduce.union([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert trace_reduce.gaps([(0, 2), (3, 4)], 1, 6) == [(2, 3), (4, 6)]
    assert trace_reduce.total(trace_reduce.clip([(0, 2), (3, 4)], 1, 3.5)) == 1.5
