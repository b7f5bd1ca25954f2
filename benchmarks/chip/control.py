"""The lower-precision control: the reference in bfloat16 in the program's place.

    python3 benchmarks/chip/control.py --workload farm.mesh20x20 --seeds 11 12 13 --seconds 5

Runs the cell once per seed, set-up, window and check as ``run.py`` does,
with every execution of a lowered loop replaced by ``reference.interpret``
rounding each value to bfloat16, one step below the float32 the
configurations state. Each run has to come out not correct: its numbers
compared set the upper readings of the limits (``PERF.md``). The last line
is a JSON list of ``{"seed", "correct", "checks"}``. ``run.py`` never runs it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import reference  # noqa: E402
import run  # noqa: E402
import sut  # noqa: E402
from suite import table3_graph  # noqa: E402


def put_in_place(config: dict) -> None:
    """Make ``sut.lower`` hand back the loop's plain graph and ``sut.execute``
    run the bfloat16 reference on it."""
    graphs = {k: table3_graph(k, *v) for k, v in config["kernels"].items()}
    sut.lower = lambda mapping: graphs[mapping.dfg.name]
    sut.execute = lambda graph, inputs, iters: reference.interpret(graph, inputs, iters, bf16=True)[0]


def controls(cell, config, mix, bench, peaks, devices, seeds, seconds) -> list[dict]:
    put_in_place(config)
    out = []
    for seed in seeds:
        result = run.run_cell(cell, config, mix, bench, peaks, devices,
                              seed=seed, seconds=seconds, traced=False)
        out.append({"seed": seed, "correct": result["correct"], "checks": result["checks"]})
    return out


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    args = p.parse_args(argv)
    cell, config, mix, bench = run.load_cell(args.workload)

    import jax

    devices, peaks = run.chip_check(jax, cell["chips"])
    print(json.dumps(controls(cell, config, mix, bench, peaks, devices, args.seeds, args.seconds)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
