"""Compile the cgra_sim kernel for a described TPU v5e, with no chip attached.

The TPU compiler ships with JAX and compiles for a topology that is described
rather than present. That refuses what interpret mode accepts: block shapes
the chip cannot tile and kernels that overrun their scoped VMEM. Nothing
runs here, so this says nothing about results or times; chip_smoke.py does.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and the test workers all import this file.
"""

import pytest

import jax
import jax.numpy as jnp

from repro.api import Compiler, resolve_options
from repro.core import CGRA
from repro.core.benchsuite import load_suite
from repro.kernels.cgra_sim import NOPS, cgra_sim_pallas
from repro.kernels.ops import compile_program, num_cycles

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


@pytest.fixture(scope="module", params=[4, 20], ids=["4x4", "20x20"])
def fft_program(request):
    fft = load_suite(["fft"])["fft"]
    comp = Compiler(CGRA(request.param, request.param),
                    resolve_options("deterministic-ci", jobs=1))
    res = comp.compile(fft)
    assert res.ok, res.reason
    return compile_program(res.mapping)


@pytest.fixture
def kernel_shapes(fft_program, one_chip):
    p = fft_program
    C, pes = num_cycles(p, ITERS), p.num_pes

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    return [f32(p.ii, pes, p.ring * pes), f32(p.ii, pes, p.ring * pes),
            f32(p.ii, pes, NOPS), f32(p.ii, 1, pes),
            f32(C, pes, BATCH), f32(C, 1, pes)]


def test_cgra_sim_compiles_for_v5e(fft_program, kernel_shapes):
    p = fft_program
    compiled = cgra_sim_pallas.lower(
        *kernel_shapes, ii=p.ii, ring=p.ring, num_cycles=num_cycles(p, ITERS),
        batch_tile=BATCH_TILE, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
