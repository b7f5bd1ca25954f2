"""The per-layer metrics that read the program's own ``cgra_run`` spans:
on hand-made traces with known spans and device ops, on a trace without
them (a program that has no such spans), and in a traced CPU rehearsal."""

from types import SimpleNamespace

import pytest

import run
import trace
from test_rehearsal import rehearse  # noqa: F401  (fixture)

SPAN_METRICS = ["stage_ms.farm", "to_device_ms.farm", "to_host_ms.farm"]
IDLE = "idle_outside_program_pct.farm"


def _call(t0, stage, to_device, wait, to_host):
    """One call's host spans from ``t0`` (ns), steps of the given lengths."""
    spans, t = [], t0
    for name, length in [("stage", stage), ("to_device", to_device), ("launch", 1e6),
                         ("wait", wait), ("to_host", to_host), ("extract", 1e6)]:
        spans.append((f"cgra_run.{name}", t, t + length))
        t += length
    return [("bench.call:fft", t0, t), ("cgra_run", t0, t)] + spans, t


def _trace():
    """A 100 ms window: two calls, 2 ms of the harness's own time before
    each, kernels inside each call's wait, and an op of the device's own at
    95-96 ms, outside every call."""
    host = [("bench.window", 0.0, 100e6)]
    a, end_a = _call(2e6, 4e6, 20e6, 10e6, 6e6)            # 2 .. 44 ms
    b, end_b = _call(end_a + 2e6, 6e6, 30e6, 4e6, 8e6)     # 46 .. 96 ms
    assert (end_a, end_b) == (44e6, 96e6)
    ops = {0: [("cgra_sim", 27e6, 36e6), ("cgra_sim", 83e6, 86e6), ("copy", 95e6, 97e6)]}
    return trace.Trace((0.0, 100e6), ops, host + a + b)


def _read(name, t):
    return run.reader(name)(SimpleNamespace(trace=t))


def test_span_means_on_a_known_trace():
    t = _trace()
    assert _read("stage_ms.farm", t) == pytest.approx(5.0)
    assert _read("to_device_ms.farm", t) == pytest.approx(25.0)
    assert _read("to_host_ms.farm", t) == pytest.approx(7.0)


def test_idle_outside_program_on_a_known_trace():
    # covered: calls 2-44 and 46-96 ms, the device's op to 97 (cut at the
    # window's end, 100); idle outside: 0-2, 44-46, 97-100 = 7 ms of 100
    assert _read(IDLE, _trace()) == pytest.approx(7.0)


def test_spans_outside_the_window_do_not_count():
    t = _trace()
    late, _ = _call(150e6, 40e6, 1e6, 1e6, 1e6)
    t = trace.Trace(t.window, t.device_ops, t.host_events + late)
    assert _read("stage_ms.farm", t) == pytest.approx(5.0)


@pytest.mark.parametrize("name", SPAN_METRICS + [IDLE])
def test_no_program_spans_no_value(name):
    """A program without the spans (the bench's own spans and device ops
    only) gives no value, and no trace gives none either."""
    t = _trace()
    bare = trace.Trace(t.window, t.device_ops,
                       [e for e in t.host_events if not e[0].startswith("cgra_run")])
    assert _read(name, bare) is None
    assert _read(name, None) is None


def test_traced_rehearsal_reads_the_span_metrics(rehearse):
    """The CPU rehearsal: the spans' means read above 0, and the idle share
    reads a value although the CPU trace has no device plane."""
    result = rehearse("farm.mesh4x4", traced=True)
    assert result["correct"]
    metrics = result["metrics"]
    for name in SPAN_METRICS:
        assert metrics[name]["value"] > 0, name
        assert metrics[name]["unit"] == "ms"
    assert 0 <= metrics[IDLE]["value"] < 100
