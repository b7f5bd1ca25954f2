"""The reduction from a profiler trace to busy time, kernel time and the
breakdown: on hand-made intervals, and on a small trace recorded on a TPU
v5e by ``record_trace.py`` (gsm on the 4x4 mesh, 256 streams x 8
iterations, one window call)."""

from pathlib import Path

import pytest

import trace

FIXTURE = Path(__file__).resolve().parent / "data" / "gsm_4x4_tiny.xplane.pb"


def test_op_name_strips_the_hlo_text():
    assert trace.op_name("%cgra_sim_pallas.1 = f32[38,16,256]{2,1,0} custom-call(...)") == "cgra_sim_pallas"
    assert trace.op_name("%copy-start = (f32[4]) copy-start(%imm.1)") == "copy-start"
    assert trace.op_name("fusion") == "fusion"


def test_union_and_gaps():
    spans = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (5.5, 5.7)]
    assert trace.union_length(spans) == 4.0
    assert trace.gaps(spans, -1.0, 8.0) == [(-1.0, 0.0), (3.0, 5.0), (6.0, 8.0)]
    assert trace.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_synthetic_trace():
    t = trace.Trace(
        window=(100.0, 1100.0),
        device_ops={0: [("cgra_sim_pallas", 200.0, 500.0), ("copy", 480.0, 520.0),
                        ("cgra_sim_pallas", 1000.0, 1200.0)]},
        host_events=[("bench.window", 100.0, 1100.0), ("bench.call:fft", 100.0, 600.0),
                     ("bench.call:gsm", 600.0, 1100.0), ("XlaDelinearize", 520.0, 900.0),
                     ("np.asarray(jax.Array)", 300.0, 990.0)],
    )
    assert t.window_s == 1e-6
    assert t.busy_s == pytest.approx(420e-9)          # 200-520 and 1000-1100
    assert t.kernel_s("cgra_sim") == pytest.approx(400e-9)
    gaps = t.idle_gaps()
    assert [n for n, _ in gaps] == [
        "call:fft/untraced", "call:fft/XlaDelinearize", "call:gsm/XlaDelinearize"]
    assert [s for _, s in gaps] == pytest.approx([100e-9, 80e-9, 400e-9])
    b = t.breakdown()
    assert b["device_ops"][0][0] == "cgra_sim_pallas"


def test_recorded_trace():
    t = trace.reduce(str(FIXTURE), [0])
    assert 0 < t.busy_s < t.window_s
    kernel = t.kernel_s("cgra_sim")
    assert 0 < kernel <= t.busy_s
    assert RECORDED == pytest.approx(
        {"window_s": t.window_s, "busy_s": t.busy_s, "kernel_s": kernel}, rel=1e-9)
    names = [n for n, _ in t.breakdown()["idle_gaps"]]
    assert all(n.startswith("call:gsm/") for n in names)


RECORDED = {"window_s": 0.00408073, "busy_s": 3.7093e-05, "kernel_s": 3.6605e-05}
