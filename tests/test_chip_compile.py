"""Compile the cgra_sim kernel for a described TPU v5e, with no chip attached.

The TPU compiler ships with JAX and compiles for a topology that is described
rather than present. That refuses what interpret mode accepts: block shapes
the chip cannot tile and kernels that overrun their scoped VMEM. Nothing
runs here, so this says nothing about results or times; chip_smoke.py does.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and the test workers all import this file.
"""

import re

import pytest

import jax
import jax.numpy as jnp

from repro.api import Compiler, resolve_options
from repro.core import CGRA
from repro.core.benchsuite import load_suite
from repro.kernels.cgra_sim import NOPS, cgra_sim_pallas
from repro.kernels.ops import compile_program, num_cycles, place_injection

BATCH, BATCH_TILE, ITERS = 256, 128, 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one; keep it out of the cache
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


# the farm cells' fabrics and loops: fft at 4x4 and 20x20, and at 50x50
# particlefilter, the deepest ring (11) of the suite
@pytest.fixture(scope="module", params=[(4, "fft"), (20, "fft"), (50, "particlefilter")],
                ids=["4x4", "20x20", "50x50"])
def fft_program(request):
    n, kernel = request.param
    dfg = load_suite([kernel])[kernel]
    comp = Compiler(CGRA(n, n), resolve_options("deterministic-ci", jobs=1))
    res = comp.compile(dfg)
    assert res.ok, res.reason
    program = compile_program(res.mapping)
    if n == 50:
        assert program.ring == 11
    return program


@pytest.fixture
def kernel_shapes(fft_program, one_chip):
    p = fft_program
    C, pes = num_cycles(p, ITERS), p.num_pes

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return [shape(*p.route_pairs.shape, dtype=jnp.int32),
            shape(p.ii, pes, 2, dtype=jnp.int32),
            shape(p.ii, pes, NOPS), shape(p.ii, 1, pes),
            shape(C, pes, BATCH), shape(C, 1, pes)]


def test_cgra_sim_compiles_for_v5e(fft_program, kernel_shapes):
    p = fft_program
    compiled = cgra_sim_pallas.lower(
        *kernel_shapes, ii=p.ii, ring=p.ring, num_cycles=num_cycles(p, ITERS),
        batch_tile=BATCH_TILE, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_cgra_sim_custom_call_is_named_by_the_kernel(fft_program, kernel_shapes):
    """The kernel's HLO instruction, the name a device trace shows, comes
    from the ``pallas_call``'s own ``name``, not from the jitted wrapper."""
    p = fft_program
    text = cgra_sim_pallas.lower(
        *kernel_shapes, ii=p.ii, ring=p.ring, num_cycles=num_cycles(p, ITERS),
        batch_tile=BATCH_TILE, interpret=False,
    ).compile().as_text()
    (call,) = [line for line in text.splitlines()
               if "custom-call(" in line and "tpu_custom_call" in line]
    assert re.search(r"%cgra_sim(\.\d+)? = \S+ custom-call\(", call), call[:200]
    assert 'op_name="jit(cgra_sim_pallas)/cgra_sim/pallas_call"' in call


def test_injection_plane_builds_for_v5e(fft_program, one_chip):
    """``place_injection`` at the farm's sizes (64 iterations, 819 200
    PE-streams a call, 384 streams at 50x50) compiles for the chip and holds
    no second plane."""
    p = fft_program
    m = p.mapping
    iters, batch = 64, 384 if p.num_pes == 2500 else 819_200 // p.num_pes
    C = num_cycles(p, iters)
    n_in = sum(1 for v in m.dfg.nodes if m.dfg.ops[v] == "input")
    rows = tuple(jax.ShapeDtypeStruct((iters, batch), jnp.float32, sharding=one_chip)
                 for _ in range(n_in))
    index = jax.ShapeDtypeStruct((n_in * iters,), jnp.int32, sharding=one_chip)
    compiled = place_injection.lower(
        rows, index, index, num_cycles=C, pes=p.num_pes, batch=batch).compile()
    mem = compiled.memory_analysis()
    plane = 4 * C * (-(-p.num_pes // 8) * 8) * batch     # PEs tiled by 8 rows
    assert mem.output_size_in_bytes == plane
    assert mem.temp_size_in_bytes < plane // 10
