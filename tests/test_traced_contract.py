"""The traced contract (ISSUE 25): a run of a cell of `BENCHMARK.json` prints
exactly the per-layer metrics the benchmark lists for that cell with
``--trace 1`` and exactly its end-to-end metrics with ``--trace 0``. The
driver refuses a whole PR as `output_malformed` for one metric missing from
one traced line, and a reader under `chipbench/layer_metrics/` that finds no
span leaves its metric out without a word: this holds the spans the readers
need in place, on the paths the chip takes.

The benchmark's own functions at a tiny size on CPU devices, steered as the
chip is: the transfer engine serves no platform (so the direct cell stages
through the host), and the chunking constants are small enough that the tiny
leaves leave in chunks. Nothing here is a device number."""

import json
import os

import pytest

jax = pytest.importorskip("jax")

from chipbench.tests.test_chipbench import (  # noqa: E402,F401 - fixtures
    BENCH,
    CELLS,
    cpu_as_device,
    run_tiny,
    tiny_root,
)


def listed(section: str, cell: str) -> set[str]:
    """The metrics of ``section`` that `BENCHMARK.json` lists for ``cell``
    (an entry without ``workloads`` counts for every cell)."""
    return {
        m["name"]
        for m in BENCH[section]
        if cell in m.get("workloads", CELLS)
    }


@pytest.fixture
def as_on_the_chip(monkeypatch):
    from torchstore_tpu import sharding as shd
    from torchstore_tpu.observability import metrics as obs_metrics
    from torchstore_tpu.transport import device_transfer

    monkeypatch.setattr(device_transfer, "SERVED_PLATFORMS", frozenset())
    monkeypatch.setattr(shd, "D2H_CHUNK_BYTES", 1 << 10)
    monkeypatch.setattr(shd, "D2H_CHUNK_THRESHOLD", 2 << 10)

    def chunked_bytes() -> float:
        series = obs_metrics.metrics_snapshot()["ts_d2h_bytes_total"]["series"]
        return sum(s["value"] for s in series if s["labels"] == {"path": "chunked"})

    return chunked_bytes


@pytest.mark.parametrize("trace", [True, False], ids=["trace1", "trace0"])
@pytest.mark.parametrize("cell", CELLS)
async def test_a_run_prints_the_metrics_the_benchmark_lists(
    tiny_root, cpu_as_device, as_on_the_chip, cell, trace
):
    before = as_on_the_chip()
    result = await run_tiny(tiny_root, cell, trace)
    assert result["correct"] and result["failed"] == 0, result["info"]["problems"]
    assert result["info"]["compiles_in_window"] == 0
    assert as_on_the_chip() > before, "no leaf of the tiny tree left in chunks"
    wanted = listed("per_layer" if trace else "end_to_end", cell)
    got = set(result["metrics"])
    assert got == wanted, (
        f"{cell} trace={int(trace)}: missing {sorted(wanted - got)}, "
        f"extra {sorted(got - wanted)}"
    )
    json.dumps(result["metrics"])  # what the last line prints
    assert "TORCHSTORE_TPU_TRACE" not in os.environ
