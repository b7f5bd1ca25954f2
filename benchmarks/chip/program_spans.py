"""The program's own ``obs`` spans in a traced window.

``cgra_run`` (``repro.kernels.ops``) opens a span per call and one per step
of the call (``cgra_run.stage``, ``.to_device``, ``.launch``, ``.wait``,
``.to_host``, ``.extract``); while the profiler collects they sit on the
host plane, on the device's clock. A program without those spans gives no
value: every function here returns None then.
"""

PROGRAM = "cgra_run"


def in_window(trace, name: str) -> list[tuple[float, float]]:
    """``(start, end)`` in ns of every span called ``name`` that starts
    inside the window."""
    lo, hi = trace.window
    return [(s, e) for n, s, e in trace.host_events if n == name and lo <= s < hi]


def mean_ms(run, name: str) -> float | None:
    """Mean milliseconds of the span ``name`` over the window's calls."""
    spans = in_window(run.trace, name) if run.trace is not None else []
    return sum(e - s for s, e in spans) / len(spans) / 1e6 if spans else None
