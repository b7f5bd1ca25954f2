"""The system under test, as the benchmark drives it.

This is the one module of the benchmark that imports the program: the
compiler (``repro.api.Compiler``), the lowering (``compile_program``) and the
executor (``cgra_run``). The rest of the benchmark hands it plain graphs and
arrays and judges what comes back against ``reference.py``.

``INTERPRET`` runs the kernel in Pallas interpret mode; only the CPU
rehearsal in ``tests/`` sets it.
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from repro.api import Compiler, resolve_options  # noqa: E402
from repro.core import CGRA, DFG, Edge  # noqa: E402
from repro.kernels.ops import cgra_run, compile_program  # noqa: E402

from suite import Graph  # noqa: E402

INTERPRET = False


def enable_cache() -> str:
    """JAX's persistent compilation cache in ``.jax_cache`` at the root of
    this checkout, keeping every compile, also those under JAX's default
    one-second threshold. It is the directory the program's
    ``enable_compile_cache`` picks where ``$JAX_COMPILATION_CACHE_DIR`` is
    unset; set here so that a machine-wide variable cannot make two
    checkouts share one cache."""
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def to_dfg(graph: Graph) -> DFG:
    return DFG(
        num_nodes=graph.num_nodes,
        edges=[Edge(s, t, d) for s, t, d in graph.edges],
        ops=list(graph.ops),
        name=graph.name,
        imms=list(graph.imms),
    )


def compile_loop(graph: Graph, rows: int, cols: int, profile: str):
    """One cold compile; returns the ``CompileResult`` (``.mapping`` when ok)."""
    options = resolve_options(profile, jobs=1)
    return Compiler(CGRA(rows, cols), options).compile(to_dfg(graph))


def lower(mapping):
    return compile_program(mapping)


def execute(program, inputs: dict[int, np.ndarray], iters: int) -> dict[int, np.ndarray]:
    """Run a lowered program over the streams of ``inputs``; the store
    outputs ``[iters, streams]`` come back to the host."""
    outs, _trace = cgra_run(program, inputs, iters, interpret=INTERPRET)
    return outs
