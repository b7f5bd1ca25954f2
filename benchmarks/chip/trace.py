"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The trace holds one plane per device (``/device:TPU:<id>``), whose
``XLA Ops`` line has one event per device operation, and a ``/host:CPU``
plane with a line per host thread; the harness's own spans
(``jax.profiler.TraceAnnotation``) sit on the Python thread's line, on the
same clock as the device's events. The traced window is the harness's span
named ``WINDOW``.

* busy: the union of the device's operation intervals inside the window,
  averaged over the devices used; idle is the rest of the window;
* kernel time: the summed durations of the operations whose HLO name holds
  the kernel's name (``%cgra_sim_pallas.1 = ... custom-call(...)`` for the
  ``cgra_sim`` kernel);
* breakdown: the ten device operations that took most time, and the ten
  longest idle gaps, cut at the harness's spans and named by the host
  event that best fits each piece.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass

WINDOW = "bench.window"
SPAN = "bench."          # prefix of the harness's own spans
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(\.\d+)?(\s*=|$)")


def op_name(event_name: str) -> str:
    """``%cgra_sim_pallas.1 = f32[...] custom-call(...)`` -> ``cgra_sim_pallas``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def union_length(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def gaps(intervals: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, reach = [], lo
    for start, end in sorted(intervals):
        if start > reach:
            out.append((reach, min(start, hi)))
        reach = max(reach, end)
        if reach >= hi:
            break
    if reach < hi:
        out.append((reach, hi))
    return [(a, b) for a, b in out if b > a]


@dataclass
class Trace:
    """A reduced trace; times in nanoseconds on the trace's clock."""

    window: tuple[float, float]
    device_ops: dict[int, list[tuple[str, float, float]]]   # device -> (op, start, end)
    host_events: list[tuple[str, float, float]]              # every host event

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _clipped(self, ops):
        lo, hi = self.window
        return [(n, max(s, lo), min(e, hi)) for n, s, e in ops if e > lo and s < hi]

    @property
    def busy_s(self) -> float:
        busy = [union_length([(s, e) for _, s, e in self._clipped(ops)])
                for ops in self.device_ops.values()]
        return sum(busy) / len(busy) / 1e9

    def op_seconds(self) -> dict[str, float]:
        """Device seconds in the window per operation name, over all devices."""
        total: dict[str, float] = defaultdict(float)
        for ops in self.device_ops.values():
            for n, s, e in self._clipped(ops):
                total[n] += (e - s) / 1e9
        return dict(total)

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of every operation whose name holds ``kernel``."""
        return sum(t for n, t in self.op_seconds().items() if kernel in n)

    def idle_gaps(self) -> list[tuple[str, float]]:
        """The first device's idle gaps, each cut where the harness's spans
        (calls, requests) meet and named ``<span>/<event>``: the span, and
        the host event that best fits the piece, by overlap times the share
        of the event inside it (a wrapper that spans many pieces loses to
        the step inside it); ``untraced`` where none covers a tenth of it."""
        ops = next(iter(self.device_ops.values()))
        own = [(n[len(SPAN):], s, e) for n, s, e in self.host_events
               if n.startswith(SPAN) and n != WINDOW]
        other = [(n, s, e) for n, s, e in self.host_events if not n.startswith(SPAN)]
        out = []
        for a, b in gaps([(s, e) for _, s, e in self._clipped(ops)], *self.window):
            pieces = [(n, max(a, s), min(b, e)) for n, s, e in own if min(b, e) > max(a, s)]
            for span, lo, hi in pieces or [("", a, b)]:
                best, score = "untraced", 0.0
                for n, s, e in other:
                    overlap = min(hi, e) - max(lo, s)
                    if overlap >= 0.1 * (hi - lo) and overlap * overlap / (e - s) > score:
                        best, score = n, overlap * overlap / (e - s)
                out.append((f"{span}/{best}", (hi - lo) / 1e9))
        return out

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle_gaps(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [list(x) for x in ops], "idle_gaps": [list(x) for x in idle]}


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, found {len(paths)}")
    return paths[0]


def reduce(path: str, device_ids: list[int]) -> Trace:
    """Read the trace at ``path`` for the devices ``device_ids``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: dict[int, list[tuple[str, float, float]]] = {d: [] for d in device_ids}
    host: list[tuple[str, float, float]] = []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m and int(m.group(1)) in device_ops:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[int(m.group(1))] += [
                        (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    ]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} span in the trace, found {len(windows)}")
    return Trace(windows[0], device_ops, host)
