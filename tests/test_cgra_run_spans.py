"""The ``obs`` spans of ``cgra_run`` and ``compile_program``, as a JAX
profiler trace sees them (CPU, kernel in Pallas interpret mode).

A call splits into six steps, each a span nested under ``cgra_run``, in
order; the byte attributes equal the arrays' sizes (the host stages and
sends the input rows, the device builds the planes), and a traced call
returns what an untraced one does.
"""

import numpy as np
import pytest

from repro import obs
from repro.api import Compiler, resolve_options
from repro.core import CGRA
from repro.core.benchsuite import load_suite
from repro.kernels.ops import (
    build_injection,
    cgra_run,
    compile_program,
    program_tables,
)

STEPS = ["cgra_run.stage", "cgra_run.to_device", "cgra_run.launch",
         "cgra_run.wait", "cgra_run.to_host", "cgra_run.extract"]
ITERS, STREAMS = 4, 128


@pytest.fixture(scope="module")
def gsm():
    dfg = load_suite(["gsm"])["gsm"]
    res = Compiler(CGRA(4, 4), resolve_options("deterministic-ci", jobs=1)).compile(dfg)
    assert res.ok, res.reason
    rng = np.random.default_rng(7)
    inputs = {v: rng.uniform(-4, 4, (ITERS, STREAMS)).astype(np.float32)
              for v in dfg.nodes if dfg.ops[v] == "input"}
    return res.mapping, inputs


def _spans(events, prefix):
    return [e for e in events if e[0] == prefix or e[0].startswith(prefix + ".")]


def test_cgra_run_spans_nest_in_order(gsm, profiled):
    mapping, inputs = gsm
    program = compile_program(mapping)
    with profiled() as events:
        outs, trace = cgra_run(program, inputs, ITERS, interpret=True)
    spans = _spans(events, "cgra_run")
    assert [name for name, *_ in spans] == ["cgra_run"] + STEPS
    (_, start, end, stats), steps = spans[0], spans[1:]
    for (_, s0, e0, _), (_, s1, _, _) in zip(steps, steps[1:]):
        assert s0 <= e0 <= s1                         # in order, not overlapping
    assert start <= steps[0][1] and steps[-1][2] <= end
    assert stats == {"kernel": "gsm", "pes": 16, "iters": ITERS,
                     "streams": STREAMS, "cycles": trace.shape[0]}

    # what is staged and sent is the input rows, their cycles and PEs and
    # active; the planes the kernel reads are built on the device
    inj, active = build_injection(program, inputs, ITERS)
    n_rows = len(inputs) * ITERS
    staged = 4 * n_rows * STREAMS + 2 * 4 * n_rows + active.nbytes
    got = {name: s for name, _, _, s in steps}
    assert got["cgra_run.stage"] == {"bytes": staged}
    assert got["cgra_run.to_device"] == {
        "table_bytes": sum(t.nbytes for t in program_tables(program)),
        "inj_bytes": staged,
        "plane_bytes": inj.nbytes + active.nbytes}
    assert got["cgra_run.to_host"] == {"bytes": trace.nbytes}
    assert got["cgra_run.extract"] == {"bytes": sum(o.nbytes for o in outs.values())}
    assert got["cgra_run.launch"] == got["cgra_run.wait"] == {}


def test_traced_call_returns_what_an_untraced_one_does(gsm, profiled):
    mapping, inputs = gsm
    program = compile_program(mapping)
    plain_outs, plain_trace = cgra_run(program, inputs, ITERS, interpret=True)
    with profiled():
        outs, trace = cgra_run(program, inputs, ITERS, interpret=True)
    np.testing.assert_array_equal(trace, plain_trace)
    assert outs.keys() == plain_outs.keys()
    for v in outs:
        np.testing.assert_array_equal(outs[v], plain_outs[v])


def test_compile_program_emits_lower(gsm, profiled):
    mapping, _ = gsm
    with profiled() as events:
        program = compile_program(mapping)
    (lower,) = [e for e in events if e[0] == "lower"]
    assert lower[3] == {"kernel": "gsm", "ii": program.ii, "ring": program.ring,
                        "pes": 16, "route_pairs": len(program.route_pairs),
                        "table_bytes": sum(t.nbytes for t in program_tables(program))}


def test_cgra_run_spans_reach_the_tracer(gsm):
    """The same spans go to an installed tracer, in the same order."""
    mapping, inputs = gsm
    program = compile_program(mapping)
    with obs.tracing() as tracer:
        cgra_run(program, inputs, ITERS, interpret=True)
    events = sorted(tracer.events, key=lambda e: (e["ts"], -e["dur"]))
    assert [e["name"] for e in events] == ["cgra_run"] + STEPS
