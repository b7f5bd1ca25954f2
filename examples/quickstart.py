"""Quickstart: map a loop DFG onto a CGRA through the ``repro.api`` compiler,
validate it by execution, and run it batched through the Pallas kernel.

    PYTHONPATH=src python examples/quickstart.py
    PYTHONPATH=src python examples/quickstart.py --profile deterministic-ci
    PYTHONPATH=src python examples/quickstart.py --cache-dir /tmp/repro-maps

This is the pattern to copy: resolve a :class:`repro.api.CompileOptions`
(profile + flag overrides, one shared flag set across every CLI), bind a
:class:`repro.api.Compiler` session to a target, and read the structured
:class:`repro.api.CompileResult`. With ``--cache-dir`` the session exercises
the persistent mapping cache exactly like the batch service does — a second
run is served from disk instead of re-solved.
"""

import argparse

import jax
import numpy as np

from repro import obs
from repro.api import Compiler, add_cli_args, options_from_args
from repro.core import CGRA, running_example
from repro.core.simulate import check_equivalence
from repro.kernels.ops import cgra_run, compile_program, enable_compile_cache

ap = argparse.ArgumentParser()
add_cli_args(ap)                      # --profile/--cache-dir/--deterministic/...
args = ap.parse_args()
options = options_from_args(args)     # THE resolution path (DESIGN.md §11.1)

# 1. the paper's running example: 14-op loop body with two loop-carried deps
dfg = running_example()
compiler = Compiler(CGRA(2, 2), options)

# 2. decoupled mapping: SMT time solution -> monomorphism space solution
# (--trace OUT.json records the compile's span tree, DESIGN.md §15)
with obs.session(getattr(args, "trace_out", None), enable=options.trace):
    result = compiler.compile(dfg)
assert result.ok, result.reason
m = result.mapping
print(m.pretty())
print(
    f"time phase {result.phases.time_s*1e3:.1f} ms, "
    f"space phase {result.phases.space_s*1e3:.1f} ms "
    f"(II={result.ii}, mII={result.m_ii}, source={result.source})"
)

# 3. validate by execution: cycle-accurate modulo-scheduled run == reference
report = check_equivalence(m, num_iters=8)
print(f"functional equivalence OK over {report.cycles} cycles; "
      f"max register pressure {max(report.max_register_pressure.values())}")

# 4. run 256 independent instances of the loop through the Pallas CGRA kernel:
# compiled on a TPU, in the Pallas interpreter anywhere else
enable_compile_cache()
interpret = jax.default_backend() != "tpu"
print(f"cgra_sim on {jax.devices()[0].device_kind}: "
      f"{'interpret mode' if interpret else 'compiled'}")
prog = compile_program(m)
rng = np.random.default_rng(0)
inputs = {
    v: rng.uniform(-2, 2, (8, 256)).astype(np.float32)
    for v in dfg.nodes
    if dfg.ops[v] == "input"
}
outs, trace = cgra_run(prog, inputs, num_iters=8, interpret=interpret)
# read the sink node's stream from the trace (the running example's final op)
sink = max(dfg.nodes, key=lambda v: m.t_abs[v])
cycles = [m.t_abs[sink] + it * m.ii for it in range(8)]
stream = np.asarray(trace)[cycles, m.placement[sink], :]
print(f"pallas cgra_sim: sink node {sink} stream {stream.shape} (iters x batch); "
      f"sample: {stream[:3, 0]}")
