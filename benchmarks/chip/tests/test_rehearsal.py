"""CPU rehearsals: each cell's set-up and one round of its window, the check
and the metrics, through the harness's own code at tiny sizes (kernels in
Pallas interpret mode). Only the look for a chip is skipped."""

import json
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import run
import sut

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
PEAKS = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]

# tiny stand-ins for each mix's sizes: one batch tile of streams, few iterations
TINY = {
    "farm.mesh20x20": {"pe_streams": 400 * 128, "iterations": 2, "kernels": ["gsm"]},
    "farm.mesh4x4": {"pe_streams": 16 * 128, "iterations": 4},
    "recompile.mesh20x20": {"kernels": ["bitcount", "gsm", "fft"], "executed_sample": 2,
                            "executed_streams": 128, "iterations": 3},
}


@pytest.fixture
def rehearse(monkeypatch):
    """Run a cell at its tiny size; returns the result line's object."""
    monkeypatch.setattr(sut, "INTERPRET", True)
    monkeypatch.setattr(sut, "enable_cache", lambda: "off")    # no CPU programs in the checkout
    monkeypatch.setattr(sut, "lower", sut.lower)        # restored after a test swaps it
    monkeypatch.setattr(sut, "execute", sut.execute)
    monkeypatch.setattr(sut, "compile_loop", sut.compile_loop)

    def go(name, *, traced=False, seed=2**31 + 12345, **sizes):
        cell, config, mix, bench = run.load_cell(name)
        mix = {**mix, **TINY[name], **sizes}
        return run.run_cell(cell, config, mix, bench, PEAKS, jax.devices()[:1],
                            seed=seed, seconds=0, traced=traced)

    return go


@pytest.mark.parametrize("name", sorted(TINY))
def test_cell_rehearses(rehearse, name):
    result = rehearse(name)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    cell, _, _, bench = run.load_cell(name)
    assert set(result["metrics"]) == {m["name"] for m in run.metrics_for(bench, cell, "end_to_end")}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert all(c["value"] == 0 for c in result["checks"].values())


@pytest.mark.parametrize("name", ["farm.mesh4x4", "recompile.mesh20x20"])
def test_traced_rehearsal_reads_per_layer_metrics(rehearse, name):
    result = rehearse(name, traced=True)
    assert result["correct"]
    cell, _, _, bench = run.load_cell(name)
    names = {m["name"] for m in run.metrics_for(bench, cell, "per_layer")}
    assert set(result["metrics"]) <= names
    if name.startswith("recompile"):     # read from the run's own records
        assert set(result["metrics"]) == names
    else:                                 # no device plane on the CPU: no kernel time
        assert "cgra_sim_roofline" not in result["metrics"]
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_run_refuses_a_host_without_tpu():
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", "farm.mesh4x4",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr


class _FakeJax:
    def __init__(self, platform, kind, count=1):
        dev = type("Dev", (), {"platform": platform, "device_kind": kind, "id": 0})()
        self._devices = [dev] * count

    def devices(self):
        return self._devices


def test_chip_check_refuses_unknown_device_and_too_few_chips():
    with pytest.raises(SystemExit):
        run.chip_check(_FakeJax("tpu", "TPU v99"), 1)
    with pytest.raises(SystemExit):
        run.chip_check(_FakeJax("tpu", "TPU v5 lite"), 4)
    devices, peaks = run.chip_check(_FakeJax("tpu", "TPU v5 lite"), 1)
    assert len(devices) == 1 and peaks["hbm_bytes_per_s"] == 819e9
