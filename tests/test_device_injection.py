"""The injection plane built on the device (``stage_injection`` on the host,
``place_injection`` on the device) against ``build_injection``'s host plane,
bit for bit, and ``cgra_run`` against the reference without a host plane."""

import numpy as np
import pytest

from repro.api import Compiler, resolve_options
from repro.core import CGRA
from repro.core.benchsuite import load_suite
from repro.core.dfg import DFG, Edge
from repro.core.mapper import Mapping
from repro.kernels import ops
from repro.kernels.ops import (
    build_injection,
    cgra_run,
    compile_program,
    place_injection,
    stage_injection,
)
from repro.kernels.ref import cgra_sim_reference

BATCH = 130    # not a multiple of the kernel's 128-lane tile


def _suite_program(name: str, side: int):
    dfg = load_suite([name])[name]
    res = Compiler(CGRA(side, side), resolve_options("deterministic-ci", jobs=1)).compile(dfg)
    assert res.ok, res.reason
    return compile_program(res.mapping)


def _late_input_program():
    """A hand-made mapping on 2x2 at II 2 whose second input fires at cycle
    2 + 2 it: past the first II, on kernel step 0 of the next stage."""
    dfg = DFG(
        num_nodes=6,
        edges=[Edge(0, 1), Edge(1, 2), Edge(2, 4), Edge(3, 4), Edge(4, 5)],
        ops=["input", "mov", "mov", "input", "add", "store"],
        name="late_input",
    )
    dfg.validate()
    mapping = Mapping(dfg=dfg, cgra=CGRA(2, 2), ii=2,
                      t_abs=[0, 1, 2, 2, 3, 4], placement=[0, 0, 1, 2, 3, 3])
    assert mapping.validate() == []
    return compile_program(mapping)


def _inputs(program, iters: int, batch: int, seed: int = 0):
    m = program.mapping
    rng = np.random.default_rng(seed)
    return {v: rng.uniform(-4, 4, (iters, batch)).astype(np.float32)
            for v in m.dfg.nodes if m.dfg.ops[v] == "input"}


PROGRAMS = {
    "gsm@4x4": lambda: _suite_program("gsm", 4),
    "fft@4x4": lambda: _suite_program("fft", 4),
    "particlefilter@4x4": lambda: _suite_program("particlefilter", 4),
    "fft@20x20": lambda: _suite_program("fft", 20),
    "late_input@2x2": _late_input_program,
}


@pytest.fixture(scope="module", params=list(PROGRAMS))
def program(request):
    return PROGRAMS[request.param]()


@pytest.mark.parametrize("iters", [1, 3])
def test_device_plane_equals_host_plane(program, iters):
    inputs = _inputs(program, iters, BATCH)
    want_inj, want_active = build_injection(program, inputs, iters)
    staged = stage_injection(program, inputs, iters)
    C, pes = staged.active.shape
    inj = place_injection(staged.rows, staged.cycle, staged.pe,
                          num_cycles=C, pes=pes, batch=staged.batch)
    assert inj.dtype == want_inj.dtype
    np.testing.assert_array_equal(np.asarray(inj), want_inj)
    np.testing.assert_array_equal(staged.active, want_active)
    # what crosses is the rows, not the plane
    n_rows = sum(r.shape[0] for r in staged.rows)
    assert staged.cycle.size == staged.pe.size == n_rows
    assert staged.nbytes == 4 * n_rows * (BATCH + 2) + want_active.nbytes


def test_late_input_wraps_past_the_first_ii():
    program = _late_input_program()
    staged = stage_injection(program, _inputs(program, 3, 8), 3)
    assert staged.cycle.tolist() == [0, 2, 4, 2, 4, 6]
    assert staged.pe.tolist() == [0, 0, 0, 2, 2, 2]


def test_staged_rows_are_the_callers_arrays():
    """f32 inputs of exactly ``iters`` rows go up as they are, uncopied."""
    program = _late_input_program()
    inputs = _inputs(program, 4, 8)
    staged = stage_injection(program, inputs, 4)
    for row, v in zip(staged.rows, (0, 3)):
        assert np.shares_memory(row, inputs[v])


def test_cgra_run_builds_no_host_plane(monkeypatch):
    """``cgra_run`` returns the reference's outputs and trace with
    ``build_injection`` unavailable to it."""
    program = _late_input_program()
    iters = 5
    inputs = _inputs(program, iters, 16, seed=3)
    want_outs, want_trace = cgra_sim_reference(program, inputs, iters)

    def refuse(*_args, **_kwargs):
        raise AssertionError("cgra_run built the host injection plane")

    monkeypatch.setattr(ops, "build_injection", refuse)
    outs, trace = cgra_run(program, inputs, iters, batch_tile=8, interpret=True)
    np.testing.assert_array_equal(trace, want_trace)
    assert outs.keys() == want_outs.keys() == {5}
    np.testing.assert_array_equal(outs[5], want_outs[5])
    # the late input reaches the store: x0 moved twice, plus x1
    np.testing.assert_array_equal(outs[5], inputs[0] + inputs[3])
