"""cgra_sim's neighbour-shift routing against both executors, on every
topology, a route-through mapping and an anneal-mapped fabric above 400 PEs
(CPU, kernel in Pallas interpret mode).

Each case runs ``cgra_run`` on seeded random streams and compares, value for
value, its whole trace with ``kernels.ref.cgra_sim_reference`` and its store
outputs with the cycle-accurate Python executor
(``core.simulate.execute_mapping``) and with ``interpret_dfg``.
"""

import numpy as np
import pytest

from repro.api import Compiler, resolve_options
from repro.core import CGRA, DFG, get_preset, map_dfg
from repro.core.benchsuite import load_suite, opcode_cover_dfg, route_stress_dfg
from repro.core.simulate import execute_mapping, interpret_dfg
from repro.kernels.ops import cgra_run, compile_program
from repro.kernels.ref import cgra_sim_reference

STREAMS = 128
LANES = (0, 57, 127)       # streams the scalar executors replay


def _inputs(dfg, iters, seed):
    rng = np.random.default_rng(seed)
    return {v: rng.uniform(-4, 4, (iters, STREAMS)).astype(np.float32)
            for v in dfg.nodes if dfg.ops[v] == "input"}


def _check(mapping, original, iters, seed=0):
    """Run the mapping on the kernel; the trace must equal the reference's
    and the stores those of both scalar executors, value for value."""
    program = compile_program(mapping)
    inputs = _inputs(mapping.dfg, iters, seed)
    outs, trace = cgra_run(program, inputs, iters, interpret=True)
    _, want = cgra_sim_reference(program, inputs, iters)
    np.testing.assert_array_equal(trace, want)
    for lane in LANES:
        streams = {v: [float(x) for x in inputs[v][:, lane]] for v in inputs}
        executed = execute_mapping(mapping, streams, iters).outputs
        interpreted = interpret_dfg(original, streams, iters)
        assert set(interpreted) <= set(outs)
        for v, stream in interpreted.items():
            np.testing.assert_array_equal(outs[v][:, lane], np.float32(stream))
            np.testing.assert_array_equal(outs[v][:, lane], np.float32(executed[v]))
    return program


@pytest.mark.parametrize("topology,kernel", [
    ("mesh", "gsm"),
    ("torus", "crc32"),       # reads over the wrap links: offsets +-3, +-12
    ("diagonal", "sha2"),     # offsets +-3, +-5
    ("one-hop", "opcover"),   # offsets +-2, +-8
])
def test_shift_routing_matches_executors_on_every_topology(topology, kernel):
    dfg = (opcode_cover_dfg() if kernel == "opcover"
           else load_suite([kernel])[kernel])
    cgra = CGRA(4, 4, topology=topology)
    res = Compiler(cgra, resolve_options("deterministic-ci", jobs=1)).compile(dfg)
    assert res.ok, res.reason
    program = _check(res.mapping, dfg, iters=4)
    # every offset is a real link of the fabric
    adjacency = cgra.adjacency
    for k in range(program.ii):
        for pe in range(cgra.num_pes):
            for slot in range(2):
                p = program.route[k, pe, slot]
                if p >= 0:
                    assert adjacency[pe][pe + program.route_pairs[p, 1]]
    if topology == "torus":
        assert {3, 12} & {abs(d) for d in program.offsets}


def test_shift_routing_runs_a_route_through_mapping():
    dfg = route_stress_dfg()
    cgra = get_preset("onehop_split_4x4").cgra()
    res = map_dfg(dfg, cgra, deterministic=True, max_route_hops=2, max_ii=6)
    assert res.ok and res.mapping.routes, res.reason
    _check(res.mapping, dfg, iters=5, seed=3)


def test_shift_routing_runs_an_anneal_mapping_above_400_pes():
    """21x21 (441 PEs) is past the size where ``space_backend="auto"``
    leaves the exact engine."""
    dfg = load_suite(["gsm"])["gsm"]
    res = Compiler(CGRA(21, 21), resolve_options("deterministic-ci", jobs=1)).compile(dfg)
    assert res.ok, res.reason
    assert res.space_backend == "anneal"
    program = _check(res.mapping, dfg, iters=3, seed=5)
    assert program.num_pes == 441


def test_lowering_at_50x50_holds_nothing_of_pes_squared():
    """A 2500-PE program holds its routing as O(II x pes) integers: every
    table is sized by II, pes and small constants, never by pes**2."""
    dfg = load_suite(["fft"])["fft"]
    res = Compiler(CGRA(50, 50), resolve_options("deterministic-ci", jobs=1)).compile(dfg)
    assert res.ok, res.reason
    program = compile_program(res.mapping)
    pes, ii = program.num_pes, program.ii
    assert pes == 2500
    arrays = {k: v for k, v in vars(program).items() if isinstance(v, np.ndarray)}
    assert set(arrays) >= {"route_pairs", "route", "op_sel", "imm"}
    for name, a in arrays.items():
        assert a.size <= ii * pes * 21, (name, a.shape)
        assert all(d < pes * 2 for d in a.shape), (name, a.shape)
    assert program.route.shape == (ii, pes, 2) and program.route.dtype == np.int32
    assert set(program.offsets) <= {-50, -1, 0, 1, 50}
    assert len(program.route_pairs) <= 2 * res.mapping.dfg.num_nodes


def test_program_without_operand_reads_runs():
    """A loop of inputs and constants alone has no route pairs; the kernel
    still runs it (its pair table is never empty)."""
    dfg = DFG(num_nodes=2, edges=[], ops=["input", "const"], name="flat",
              imms=[0.0, 2.5])
    dfg.validate()
    res = map_dfg(dfg, CGRA(2, 2), deterministic=True)
    assert res.ok, res.reason
    program = compile_program(res.mapping)
    assert program.route_pairs.shape == (0, 2) and program.offsets == ()
    inputs = _inputs(res.mapping.dfg, 3, seed=9)
    _, trace = cgra_run(program, inputs, 3, interpret=True)
    _, want = cgra_sim_reference(program, inputs, 3)
    np.testing.assert_array_equal(trace, want)
