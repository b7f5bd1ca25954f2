"""CPU rehearsals of the 50x50 cells: set-up, one round of the window, the
check and the metrics, through the harness's own code at tiny sizes (kernels
in Pallas interpret mode), traced and untraced. Only the look for a chip is
skipped."""

import json
from pathlib import Path

import jax
import pytest

import run
import sut

HERE = Path(__file__).resolve().parents[1]
PEAKS = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]

# one 128-stream batch tile on 2500 PEs, few iterations
TINY = {
    "farm.mesh50x50": {"pe_streams": 2500 * 128, "iterations": 2, "kernels": ["gsm"]},
    "recompile.mesh50x50": {"kernels": ["bitcount", "gsm"], "executed_sample": 1,
                            "executed_streams": 128, "iterations": 3},
}


@pytest.fixture
def rehearse(monkeypatch):
    """Run a cell at its tiny size; returns the result line's object."""
    monkeypatch.setattr(sut, "INTERPRET", True)
    monkeypatch.setattr(sut, "enable_cache", lambda: "off")    # no CPU programs in the checkout

    def go(name, *, traced, seed=2**31 + 54321):
        cell, config, mix, bench = run.load_cell(name)
        mix = {**mix, **TINY[name]}
        return run.run_cell(cell, config, mix, bench, PEAKS, jax.devices()[:1],
                            seed=seed, seconds=0, traced=traced)

    return go


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("name", sorted(TINY))
def test_mesh50x50_cell_rehearses(rehearse, name, traced):
    result = rehearse(name, traced=traced)
    assert result["correct"], result["checks"]
    assert all(c["value"] == 0 for c in result["checks"].values())
    assert result["attempted"] >= 1 and result["failed"] == 0
    cell, _, _, bench = run.load_cell(name)
    kind = "per_layer" if traced else "end_to_end"
    names = {m["name"] for m in run.metrics_for(bench, cell, kind)}
    got = result["metrics"]
    assert set(got) <= names
    assert all(v["value"] is not None for v in got.values())
    if not traced:
        assert set(got) == names
        if name.startswith("recompile"):
            assert got["ii_over_mii"]["value"] == 1.0
    elif name.startswith("recompile"):    # read from the run's own records and spans
        assert set(got) == names
        assert got["anneal_ms.recompile"]["value"] > 0
    else:                                  # no device plane on the CPU: no kernel time
        assert {"stage_ms.farm", "to_device_ms.farm", "to_host_ms.farm"} <= set(got)
        assert "cgra_sim_ps_per_pe_iter.farm" not in got


class _Trace:
    def __init__(self, kernel_s):
        self._kernel_s = kernel_s

    def kernel_s(self, kernel):
        return self._kernel_s if kernel == "cgra_sim" else 0.0


def test_ps_per_pe_iter_divides_kernel_time_by_fabric_work():
    cell, config, mix, _ = run.load_cell("farm.mesh50x50")
    units = [{"kernel": "gsm", "streams": 384, "iterations": 64}] * 3
    window = run.Run(cell, config, mix, PEAKS, {}, units, 20.0, _Trace(0.15))
    read = run.reader("cgra_sim_ps_per_pe_iter.farm")
    assert read(window) == pytest.approx(1e12 * 0.15 / (3 * 2500 * 384 * 64))
    window.trace = _Trace(0.0)
    assert read(window) is None
