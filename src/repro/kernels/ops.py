"""Jit'd wrappers and program compilation for the Pallas kernels.

``compile_program`` lowers a space-time Mapping (core/mapper.py) into the
dense one-hot tables the cgra_sim kernel consumes — the step where the CGRA's
crossbar and opcode decoders become MXU/VPU-friendly tensors (DESIGN.md §3).

``cgra_run`` executes a compiled program over batched input streams and
returns per-store-node outputs, via the Pallas kernel. It runs compiled for
the TPU by default; CPU callers (the tests) pass ``interpret=True``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dfg import DFG
from repro.core.mapper import Mapping
from repro.core.simulate import OPCODES, _operands

from .cgra_sim import KERNEL_OPS, NOPS, cgra_sim_pallas, vmem_footprint

assert list(KERNEL_OPS) == list(OPCODES), "kernel/oracle opcode tables diverged"


@dataclass
class CGRAProgram:
    """Dense, device-ready encoding of one mapped loop kernel."""

    mapping: Mapping
    ii: int
    ring: int
    num_pes: int
    # one-hot tables, per kernel step
    route_a: np.ndarray    # [II, pes, ring*pes] f32
    route_b: np.ndarray    # [II, pes, ring*pes] f32
    op_sel: np.ndarray     # [II, pes, NOPS] f32
    imm: np.ndarray        # [II, pes] f32
    # integer views (used by ref.py and the injection builder)
    op_id: np.ndarray      # [II, pes] int32 (-1 = idle)
    node_at: np.ndarray    # [II, pes] int32 (-1 = idle)
    src_pe: np.ndarray     # [II, pes, 2] int32
    src_delta: np.ndarray  # [II, pes, 2] int32 (cycles since operand produced)

    def vmem_bytes(self, batch_tile: int) -> int:
        """VMEM one kernel grid step needs (the kernel's ``vmem_limit_bytes``)."""
        return vmem_footprint(self.num_pes, self.ring, batch_tile)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps the cache there and
    this sets nothing. Otherwise the cache goes to ``.jax_cache`` at the root
    of this checkout: a fixed path, so a later run finds what an earlier one
    compiled. Call it before the first compile; importing does not.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def compile_program(mapping: Mapping) -> CGRAProgram:
    dfg, cgra, ii = mapping.dfg, mapping.cgra, mapping.ii
    pes = cgra.num_pes
    labels, t_abs, placement = mapping.labels, mapping.t_abs, mapping.placement

    # operand delay: value produced delta cycles before consumption
    deltas: list[list[int]] = [[] for _ in dfg.nodes]
    srcs: list[list[int]] = [[] for _ in dfg.nodes]
    for v in dfg.nodes:
        for e in _operands(dfg, v):
            delta = (t_abs[v] - t_abs[e.src]) + e.distance * ii
            if delta < 1:
                raise AssertionError(f"non-causal operand on edge {e}")
            deltas[v].append(delta)
            srcs[v].append(placement[e.src])
    ring = max((d for ds in deltas for d in ds), default=1)

    route_a = np.zeros((ii, pes, ring * pes), np.float32)
    route_b = np.zeros((ii, pes, ring * pes), np.float32)
    op_sel = np.zeros((ii, pes, NOPS), np.float32)
    imm = np.zeros((ii, pes), np.float32)
    op_id = np.full((ii, pes), -1, np.int32)
    node_at = np.full((ii, pes), -1, np.int32)
    src_pe = np.full((ii, pes, 2), -1, np.int32)
    src_delta = np.zeros((ii, pes, 2), np.int32)

    for v in dfg.nodes:
        k, pe = labels[v], placement[v]
        op = dfg.ops[v]
        op_sel[k, pe, OPCODES[op]] = 1.0
        op_id[k, pe] = OPCODES[op]
        node_at[k, pe] = v
        imm[k, pe] = dfg.imms[v]
        for slot, (sp, dl) in enumerate(zip(srcs[v], deltas[v])):
            # ring slot dl-1 holds the value produced dl cycles ago
            flat = (dl - 1) * pes + sp
            (route_a if slot == 0 else route_b)[k, pe, flat] = 1.0
            src_pe[k, pe, slot] = sp
            src_delta[k, pe, slot] = dl

    return CGRAProgram(
        mapping=mapping, ii=ii, ring=ring, num_pes=pes,
        route_a=route_a, route_b=route_b, op_sel=op_sel, imm=imm,
        op_id=op_id, node_at=node_at, src_pe=src_pe, src_delta=src_delta,
    )


def num_cycles(program: CGRAProgram, num_iters: int) -> int:
    return program.mapping.schedule_length + (num_iters - 1) * program.ii


def build_injection(
    program: CGRAProgram, inputs: dict[int, np.ndarray], num_iters: int
) -> tuple[np.ndarray, np.ndarray]:
    """Input-node value injection [C, pes, B] and firing mask [C, pes]."""
    m = program.mapping
    C = num_cycles(program, num_iters)
    batch = next(iter(inputs.values())).shape[1] if inputs else 1
    inj = np.zeros((C, program.num_pes, batch), np.float32)
    active = np.zeros((C, program.num_pes), np.float32)
    for v in m.dfg.nodes:
        pe = m.placement[v]
        for it in range(num_iters):
            c = m.t_abs[v] + it * m.ii
            active[c, pe] = 1.0
            if m.dfg.ops[v] == "input":
                inj[c, pe, :] = inputs[v][it]
    return inj, active


def cgra_launch(
    program: CGRAProgram,
    inj: np.ndarray | jax.Array,      # [C, pes, B] f32 (build_injection)
    active: np.ndarray | jax.Array,   # [C, pes] f32
    *,
    batch_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Launch the kernel on staged injections; returns the device trace [C, pes, B]."""
    C, pes, batch = inj.shape
    return cgra_sim_pallas(
        jnp.asarray(program.route_a),
        jnp.asarray(program.route_b),
        jnp.asarray(program.op_sel),
        jnp.asarray(program.imm.reshape(program.ii, 1, pes)),
        jnp.asarray(inj),
        jnp.asarray(active.reshape(C, 1, pes)),
        ii=program.ii,
        ring=program.ring,
        num_cycles=C,
        batch_tile=min(batch_tile, batch),
        interpret=interpret,
    )


def cgra_run(
    program: CGRAProgram,
    inputs: dict[int, np.ndarray],   # input node -> [num_iters, B] f32
    num_iters: int,
    *,
    batch_tile: int = 128,
    interpret: bool = False,
) -> tuple[dict[int, np.ndarray], np.ndarray]:
    """Execute on the Pallas kernel; returns (store outputs, full trace).

    Compiled for the TPU unless ``interpret=True``; on a CPU backend the
    compiled path raises rather than falling back to the interpreter.
    """
    inj, active = build_injection(program, inputs, num_iters)
    trace = np.asarray(
        cgra_launch(program, inj, active, batch_tile=batch_tile, interpret=interpret)
    )
    m = program.mapping
    outs: dict[int, np.ndarray] = {}
    for v in m.dfg.nodes:
        if m.dfg.ops[v] == "store":
            cyc = m.t_abs[v] + np.arange(num_iters) * m.ii
            outs[v] = trace[cyc, m.placement[v], :]
    return outs, trace
