"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload farm.mesh20x20 --seed 7 --seconds 10 --trace 0

Reads the cell from ``BENCHMARK.json``, its configuration from
``configs/<config>.json`` and its traffic from ``traffic/<mix>.json``; the
mix's ``loop`` picks the generator in ``loops.py``. Set-up (start-up, mapping,
lowering, warm-up, and compiling where the cache is cold) is ``setup_s``.
Then the window runs for ``--seconds`` (to the end of a whole round), and the
check compares what the window produced with ``reference.py``.

``--trace 0`` prints the cell's end-to-end metrics. ``--trace 1`` takes a
profiler trace of the window and prints the cell's per-layer metrics, each
read by ``metrics/<name>.py``, with the device's busy and window seconds.
The last line of stdout is one JSON object; the numbers compared, each with
its limit, end stderr and the result line's ``checks``.

With no TPU, fewer chips than the cell asks for, or a device missing from
``peaks.json``, it exits 1 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))


@dataclass
class Run:
    """What a per-layer metric's reader may read."""

    cell: dict
    config: dict
    mix: dict
    peaks: dict
    graphs: dict                 # kernel -> suite.Graph
    units: list[dict]            # one record per call or request of the window
    window_s: float
    trace: object = None         # trace.Trace of the window, with --trace 1


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """The cell, its configuration file, its mix file and BENCHMARK.json."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / entry["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, config, mix, bench


def metrics_for(bench: dict, cell: dict, kind: str) -> list[dict]:
    return [
        m for m in bench[kind]
        if cell["name"] in m.get("workloads", [cell["name"]])
    ]


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name.replace('.', '_')}", HERE / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def chip_check(jax, chips: int) -> tuple[list, dict]:
    """The devices this cell runs on and their peaks; exits when there is
    no TPU, too few chips, or no peaks for the device."""
    devices = jax.devices()
    kind = devices[0].device_kind
    print(f"run.py: platform {devices[0].platform}, device_kind {kind!r}, "
          f"{len(devices)} devices", file=sys.stderr)
    if devices[0].platform != "tpu":
        sys.exit(f"run.py: needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        sys.exit(f"run.py: the cell needs {chips} chips, JAX found {len(devices)}")
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind not in peaks:
        sys.exit(f"run.py: no peaks for device_kind {kind!r} in peaks.json")
    return devices[:chips], peaks[kind]


def run_cell(cell, config, mix, bench, peaks, devices, *, seed, seconds, traced,
             trace_dir=None):
    """Set up, run the window, check; returns the result line's object. A
    trace goes to ``trace_dir`` when given (and stays), else to a temporary
    directory that is removed once read."""
    import jax

    import loops
    import sut
    import trace as tracing

    cache = sut.enable_cache()
    compiles = CompileClock(jax)
    loop = loops.LOOPS[mix["loop"]](config, mix, seed)
    started = time.perf_counter()
    loop.setup()
    setup_s = time.perf_counter() - T_START
    print(f"run.py: set-up {setup_s:.3f} s, of which {started - T_START:.3f} s to start "
          f"(imports, devices) and {compiles.count} compiles taking {compiles.seconds:.3f} s; "
          f"compile cache {cache}", file=sys.stderr)

    keep = trace_dir is not None
    if traced and not keep:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    if traced:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        compiled_before = compiles.count
        # the traced window holds what runs after the timed one too: the
        # compile loop's sampled programs are its only device work
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            t0, t1 = loop.run_window(seconds)
            in_window = compiles.count - compiled_before
            loop.after_window()
    finally:
        if traced:
            jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    loop.release()

    window = None
    if traced:
        window = tracing.reduce(tracing.find_xplane(trace_dir), [d.id for d in devices])
        if not keep:
            shutil.rmtree(trace_dir, ignore_errors=True)
    checks, counts = loop.check()
    correct = all(value <= limit for value, limit in checks.values())

    run = Run(cell, config, mix, peaks, loop.graphs, loop.units(), t1 - t0, window)
    metrics: dict[str, dict] = {}
    if traced:
        for m in metrics_for(bench, cell, "per_layer"):
            value = reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        measured = {**loop.end_to_end(t0, t1), "setup_s": setup_s}
        for m in metrics_for(bench, cell, "end_to_end"):
            metrics[m["name"]] = {"value": measured[m["name"]], "unit": m["unit"]}

    units = run.units
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    result = {
        "correct": correct,
        "attempted": len(units),
        "failed": sum(1 for u in units if not u.get("ok", True)),
        "metrics": metrics,
        "device": device,
    }
    if traced:
        device["busy_s"] = window.busy_s
        device["window_s"] = window.window_s
        result["breakdown"] = window.breakdown()
    print(f"run.py: window {t1 - t0:.3f} s, {len(units)} units, {in_window} compiles "
          f"in the window; {json.dumps(counts)}", file=sys.stderr)
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    result["checks"] = {name: {"value": v, "limit": lim} for name, (v, lim) in checks.items()}
    return result


class CompileClock:
    """Counts JAX's backend compiles (persistent-cache reads included)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax) -> None:
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    cell, config, mix, bench = load_cell(args.workload)

    import jax

    devices, peaks = chip_check(jax, cell["chips"])
    result = run_cell(cell, config, mix, bench, peaks, devices, seed=args.seed,
                      seconds=args.seconds, traced=bool(args.trace))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
