"""Records the small chip trace that ``test_trace.py`` reduces.

    python3 benchmarks/chip/tests/record_trace.py OUT.xplane.pb

Runs the farm on the 4x4 mesh at a tiny size (gsm only, 256 streams x 8
iterations, one round) through ``run.run_cell`` with a trace, on the TPU,
and copies the trace to ``OUT.xplane.pb``; prints the reduced numbers that
the test pins.
"""

import json
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402
import trace  # noqa: E402


def main() -> int:
    import jax

    cell, config, mix, bench = run.load_cell("farm.mesh4x4")
    mix = {**mix, "kernels": ["gsm"], "pe_streams": 16 * 256, "iterations": 8}
    devices, peaks = run.chip_check(jax, 1)
    out = tempfile.mkdtemp(prefix="record-trace-")
    result = run.run_cell(cell, config, mix, bench, peaks, devices, seed=5,
                          seconds=0, traced=True, trace_dir=out)
    shutil.copy(trace.find_xplane(out), sys.argv[1])
    shutil.rmtree(out)
    t = trace.reduce(sys.argv[1], [d.id for d in devices])
    print(json.dumps({"result": result, "window": t.window, "busy_s": t.busy_s,
                      "kernel_s": t.kernel_s("cgra_sim"), "ops": t.op_seconds(),
                      "breakdown": t.breakdown()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
