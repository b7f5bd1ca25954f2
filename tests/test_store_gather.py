"""The store rows gathered on the device (``take_stores``) and brought back
alone: ``cgra_run``'s outputs against ``cgra_sim_reference``'s, bit for bit,
with the trace left on the device as a ``jax.Array`` (CPU, kernel in Pallas
interpret mode)."""

import jax
import numpy as np
import pytest

from repro.api import Compiler, resolve_options
from repro.core import CGRA
from repro.core.benchsuite import load_suite
from repro.core.dfg import DFG, Edge
from repro.core.mapper import Mapping
from repro.kernels import ops
from repro.kernels.ops import cgra_run, compile_program, node_rows, take_stores
from repro.kernels.ref import cgra_sim_reference

ITERS, STREAMS = 3, 16


def _suite_program(name: str, side: int):
    dfg = load_suite([name])[name]
    res = Compiler(CGRA(side, side), resolve_options("deterministic-ci", jobs=1)).compile(dfg)
    assert res.ok, res.reason
    return compile_program(res.mapping)


def _hand_program(ops_: list[str], edges, t_abs, placement, ii: int):
    dfg = DFG(num_nodes=len(ops_), edges=[Edge(*e) for e in edges], ops=ops_,
              name="hand")
    dfg.validate()
    mapping = Mapping(dfg=dfg, cgra=CGRA(2, 2), ii=ii, t_abs=t_abs,
                      placement=placement)
    assert mapping.validate() == []
    return compile_program(mapping)


def _inputs(program, seed: int = 0):
    m = program.mapping
    rng = np.random.default_rng(seed)
    return {v: rng.uniform(-4, 4, (ITERS, STREAMS)).astype(np.float32)
            for v in m.dfg.nodes if m.dfg.ops[v] == "input"}


# (program, number of store nodes)
PROGRAMS = {
    "fft@4x4": (lambda: _suite_program("fft", 4), 2),
    "particlefilter@4x4": (lambda: _suite_program("particlefilter", 4), 2),
    "gsm@4x4": (lambda: _suite_program("gsm", 4), 1),
    "fft@20x20": (lambda: _suite_program("fft", 20), 2),
    # two stores on one PE, one II apart: rows of the same PE, other cycles
    "two_stores_one_pe@2x2": (lambda: _hand_program(
        ["input", "store", "store"], [(0, 1), (0, 2)],
        t_abs=[0, 1, 2], placement=[0, 1, 1], ii=2), 2),
}


@pytest.mark.parametrize("name", list(PROGRAMS))
def test_stores_equal_the_reference(name):
    make, n_stores = PROGRAMS[name]
    program = make()
    inputs = _inputs(program)
    want_outs, want_trace = cgra_sim_reference(program, inputs, ITERS)
    outs, trace = cgra_run(program, inputs, ITERS, interpret=True)

    assert len(outs) == n_stores
    assert list(outs) == list(want_outs)          # store nodes in graph order
    for v, want in want_outs.items():
        assert isinstance(outs[v], np.ndarray) and outs[v].dtype == np.float32
        assert outs[v].shape == (ITERS, STREAMS) and outs[v].flags.writeable
        np.testing.assert_array_equal(outs[v], want)
    assert isinstance(trace, jax.Array)
    np.testing.assert_array_equal(np.asarray(trace), want_trace)


def test_loop_without_a_store_gathers_nothing(monkeypatch):
    """A hand-made mapping with no store node: ``{}``, and no gather."""
    program = _hand_program(["input", "mov"], [(0, 1)], t_abs=[0, 1],
                            placement=[0, 1], ii=1)
    inputs = _inputs(program)

    def refuse(*_args, **_kwargs):
        raise AssertionError("cgra_run gathered rows of a loop with no store")

    monkeypatch.setattr(ops, "take_stores", refuse)
    outs, trace = cgra_run(program, inputs, ITERS, interpret=True)
    assert outs == {}
    _, want_trace = cgra_sim_reference(program, inputs, ITERS)
    np.testing.assert_array_equal(np.asarray(trace), want_trace)


@pytest.mark.parametrize("iters", [1, 4])
def test_take_stores_reads_each_rows_cycle_and_pe(iters):
    """``take_stores`` is ``trace[cycle, pe]``, row i at (cycle[i], pe[i]),
    with the store rows ``node_rows`` gives, node by node."""
    program = PROGRAMS["two_stores_one_pe@2x2"][0]()
    stores, cycle, pe = node_rows(program.mapping, "store", iters)
    assert stores == [1, 2]
    assert cycle.tolist() == [1 + 2 * it for it in range(iters)] + [2 + 2 * it for it in range(iters)]
    assert pe.tolist() == [1] * (2 * iters)
    C = ops.num_cycles(program, iters)
    trace = np.random.default_rng(1).standard_normal((C, 4, 8)).astype(np.float32)
    rows = take_stores(trace, cycle, pe)
    assert rows.shape == (2 * iters, 8)
    np.testing.assert_array_equal(np.asarray(rows), trace[cycle, pe])
