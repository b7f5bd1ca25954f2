"""cgra_sim Pallas kernel vs both oracles, swept over shapes and op mixes.

These run the kernel in Pallas interpret mode on the CPU; chip_smoke.py runs
the compiled kernel on a TPU and tests/test_chip_compile.py compiles it for
a described v5e.
"""

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Compiler, resolve_options
from repro.core import CGRA, map_dfg, running_example
from repro.core.benchsuite import load_suite, opcode_cover_dfg
from repro.core.dfg import DFG, Edge
from repro.core.simulate import interpret_dfg
from repro.kernels import cgra_sim
from repro.kernels.ops import compile_program, cgra_run
from repro.kernels.ref import cgra_sim_reference


def _run_and_compare(dfg, cgra, num_iters, batch, batch_tile=None, seed=0):
    res = map_dfg(dfg, cgra, time_budget_s=30)
    assert res.ok, res.reason
    prog = compile_program(res.mapping)
    rng = np.random.default_rng(seed)
    inputs = {
        v: rng.uniform(-4, 4, (num_iters, batch)).astype(np.float32).round(2)
        for v in dfg.nodes
        if dfg.ops[v] == "input"
    }
    outs_k, trace_k = cgra_run(
        prog, inputs, num_iters, batch_tile=batch_tile or batch, interpret=True
    )
    outs_r, trace_r = cgra_sim_reference(prog, inputs, num_iters)
    np.testing.assert_array_equal(trace_k, trace_r)
    # cross-check against the scalar interpreter on lane 0
    ref = interpret_dfg(
        dfg, {v: [float(x) for x in inputs[v][:, 0]] for v in inputs}, num_iters
    )
    for v, stream in ref.items():
        np.testing.assert_allclose(
            outs_k[v][:, 0], np.asarray(stream, np.float32), rtol=1e-6, atol=1e-6
        )
    return prog


@pytest.mark.parametrize("batch,batch_tile", [(8, 8), (32, 16), (128, 128)])
def test_running_example_shapes(batch, batch_tile):
    _run_and_compare(running_example(), CGRA(2, 2), 5, batch, batch_tile)


@pytest.mark.parametrize("grid", [(2, 2), (3, 3), (4, 4)])
def test_grid_sweep(grid):
    _run_and_compare(running_example(), CGRA(*grid), 4, 8)


def test_all_float_ops_covered():
    """DFG touching every opcode, chained like real straight-line code."""
    _run_and_compare(opcode_cover_dfg(), CGRA(3, 3), 3, 8)


def test_scalar_oracle_computes_in_f32():
    """A sum that is an integer in f32 but falls just below it in f64 must
    reach the bitwise ops as that integer in every executor."""
    d = DFG(
        num_nodes=5,
        edges=[Edge(0, 2), Edge(1, 2), Edge(2, 3), Edge(3, 4)],
        ops=["input", "input", "add", "not", "store"],
        name="f32sum",
    )
    d.validate()
    res = map_dfg(d, CGRA(2, 2), deterministic=True)
    prog = compile_program(res.mapping)
    inputs = {0: np.array([[0.37], [-1.67], [2.83]], np.float32),
              1: np.array([[-3.37], [-0.33], [-0.83]], np.float32)}
    want = [float(~n & 0xFFFF) for n in (3, 2, 2)]    # f32 sums -3, -2, 2
    ref = interpret_dfg(d, {v: [float(x) for x in inputs[v][:, 0]] for v in inputs}, 3)
    assert ref == {4: want}
    outs, _ = cgra_run(prog, inputs, 3, batch_tile=1, interpret=True)
    np.testing.assert_array_equal(outs[4][:, 0], want)


@pytest.mark.parametrize("ulps_off", [-2, -1, 0, 1, 2])
def test_division_rounds_like_ieee(ulps_off):
    """A TPU's f32 division is 1-2 ulp off for about a third of quotients;
    the kernel's div rounds any quotient a few ulp off to numpy's result."""
    rng = np.random.default_rng(ulps_off + 10)
    n = 100_000

    def draw():
        mag = rng.uniform(1, 2, n) * 2.0 ** rng.integers(-37, 37, n)
        return (mag * rng.choice([-1, 1], n)).astype(np.float32)

    a, b = draw(), draw()
    b[:1000] = 2.0 ** rng.integers(-20, 20, 1000)                # exact quotients
    a[1000:2000] = rng.uniform(-4, 4, 1000).round(2)             # the smoke's data
    b[1000:2000] = rng.uniform(0.01, 4, 1000).round(2)
    a[a == 0] = 0.01       # 0 / b is exact on every backend; no ulp to be off
    want = a / b
    off = (want.view(np.int32) + ulps_off).view(np.float32)
    got = np.asarray(jax.jit(cgra_sim._round_quotient)(a, b, off))
    np.testing.assert_array_equal(got, want)


def test_recurrence_semantics_through_kernel():
    """phi accumulation across iterations must flow through the ring buffer."""
    d = DFG(
        num_nodes=4,
        edges=[Edge(0, 1), Edge(1, 2), Edge(2, 1, 1), Edge(2, 3)],
        ops=["input", "phi", "mov", "store"],
        name="accum",
    )
    d.validate()
    prog = _run_and_compare(d, CGRA(2, 2), 6, 8)
    # the carried operand's ring delay equals its schedule distance
    m = prog.mapping
    delta = (m.t_abs[1] - m.t_abs[2]) + m.ii  # edge 2 -> 1, distance 1
    assert 1 <= delta <= prog.ring


def test_vmem_budget_accounting():
    """vmem_bytes is one grid step's footprint, the kernel's vmem_limit_bytes:
    one kernel step's routing blocks, double-buffered, not all II of them."""
    fft = load_suite(["fft"])["fft"]
    res = Compiler(CGRA(20, 20), resolve_options("deterministic-ci", jobs=1)).compile(fft)
    prog = compile_program(res.mapping)
    assert (prog.ii, prog.ring, prog.num_pes) == (7, 7, 400)
    got = prog.vmem_bytes(batch_tile=128)
    assert got == cgra_sim.vmem_footprint(400, 7, 128)
    route_block = 400 * 2816 * 4          # [pes, ring*pes], lanes padded to 128
    assert 4 * route_block < got < 2 * prog.ii * route_block
    # compiling this program for a v5e needed a 27.25 MiB limit (bisected);
    # a v5e core has 128 MiB of VMEM
    assert 27.25 * 2**20 <= got <= 64 * 2**20


def test_vmem_over_capacity_is_refused(monkeypatch):
    """On a TPU a program that needs more VMEM than the chip has raises before
    compiling; the batch tile is never shrunk to make it fit."""
    pes, ring, ii, C, B = 400, 7, 7, 8, 128
    need = cgra_sim.vmem_footprint(pes, ring, B)
    monkeypatch.setattr(cgra_sim.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        cgra_sim.pltpu, "get_tpu_info",
        lambda: SimpleNamespace(vmem_capacity_bytes=need - 1),
    )
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    shapes = [f32((ii, pes, ring * pes)), f32((ii, pes, ring * pes)),
              f32((ii, pes, cgra_sim.NOPS)), f32((ii, 1, pes)),
              f32((C, pes, B)), f32((C, 1, pes))]
    run = functools.partial(cgra_sim.cgra_sim_pallas, ii=ii, ring=ring,
                            num_cycles=C, batch_tile=B)
    with pytest.raises(ValueError, match="MiB of VMEM"):
        jax.eval_shape(run, *shapes)


def test_compiled_mode_is_the_default():
    """cgra_run compiles for the TPU unless told to interpret, so on the CPU
    backend a missing chip raises instead of silently interpreting."""
    if jax.default_backend() != "cpu":
        pytest.skip("checks the CPU backend's refusal")
    res = map_dfg(running_example(), CGRA(2, 2), deterministic=True)
    prog = compile_program(res.mapping)
    dfg = res.mapping.dfg
    inputs = {v: np.ones((2, 8), np.float32) for v in dfg.nodes
              if dfg.ops[v] == "input"}
    with pytest.raises(ValueError, match="interpret mode"):
        cgra_run(prog, inputs, 2)
