"""chip_smoke.py's phases at ``LlamaConfig.tiny()`` on CPU devices: the same
functions the chip runs at Llama-3-8B width, so the script's control flow,
store calls and checks are exercised by tier 1. What only a chip can show
(H2D that outlives ``device_put``'s return, the TPU transfer server) is the
script's own job: it has no CPU mode, and the last test holds it to that."""

import os
import subprocess
import sys

import pytest

import torchstore_tpu as ts
from torchstore_tpu.models.llama import LlamaConfig

jax = pytest.importorskip("jax")

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
try:
    import chip_smoke
finally:
    sys.path.remove(REPO_ROOT)


@pytest.fixture
async def store():
    await ts.initialize(store_name=chip_smoke.STORE)
    yield
    await ts.shutdown(chip_smoke.STORE)


@pytest.mark.parametrize(
    "phase, n_devices",
    [("buffered", 1), ("overwrite_hazard", 1), ("direct", 1), ("reshard", 4)],
)
async def test_phase(store, phase, n_devices):
    run_phase = getattr(chip_smoke, f"{phase}_phase")
    await run_phase(LlamaConfig.tiny(), jax.devices()[:n_devices])


async def test_direct_phase_on_an_unserved_platform(store, monkeypatch):
    """What the phase does on the chip: the transfer engine does not serve
    TPU buffers, so the store picks host staging and the phase holds it to
    that."""
    from torchstore_tpu.transport import device_transfer

    monkeypatch.setattr(device_transfer, "SERVED_PLATFORMS", frozenset())
    await chip_smoke.direct_phase(LlamaConfig.tiny(), jax.devices()[:1])


async def test_actor_children_are_host_only_and_stop():
    await ts.initialize(store_name=chip_smoke.STORE)
    try:
        pids = chip_smoke.actor_pids()
        chip_smoke.require_host_only(pids)
    finally:
        await ts.shutdown(chip_smoke.STORE)
    await chip_smoke.require_gone(pids)


async def test_leave_no_process_stops_the_fork_server():
    """``ts.shutdown()`` keeps the fork server warm; the script may not
    leave it running when it ends. Afterwards the next store starts one
    again."""
    await ts.initialize(store_name=chip_smoke.STORE)
    await ts.shutdown(chip_smoke.STORE)
    assert chip_smoke.descendants(os.getpid()), "no fork server to stop?"
    chip_smoke.leave_no_process()
    assert not chip_smoke.descendants(os.getpid())
    await ts.initialize(store_name=chip_smoke.STORE)
    try:
        assert len(chip_smoke.actor_pids()) == 2
    finally:
        await ts.shutdown(chip_smoke.STORE)


def test_leave_no_process_kills_and_reports_a_leftover(capfd):
    leftover = subprocess.Popen(["sleep", "600"])
    try:
        assert not chip_smoke.leave_no_process()
        assert f"had to kill {leftover.pid}: sleep 600" in capfd.readouterr().err
        assert leftover.wait(timeout=10) == -9
    finally:
        leftover.kill()


def test_script_has_no_cpu_mode():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout == "", "no result may be printed without a chip"
    assert "no TPU" in proc.stderr
