"""Jit'd wrappers and program compilation for the Pallas kernels.

``compile_program`` lowers a space-time Mapping (core/mapper.py) into the
tables the cgra_sim kernel consumes — the step where the CGRA's crossbar and
opcode decoders become VPU-friendly integer codes and one-hot rows
(DESIGN.md §17). Each operand read is a route pair: how many cycles ago its
value was produced, and the PE offset of its producer. The program keeps
its distinct pairs, and per kernel step and PE the index of each operand's
pair, so it grows as II x pes, never as pes**2.

``cgra_run`` executes a compiled program over batched input streams and
returns per-store-node outputs, via the Pallas kernel. It runs compiled for
the TPU by default; CPU callers (the tests) pass ``interpret=True``. The
kernel reads a dense injection plane ``[C, pes, B]`` that is zero but for one
row per input node and iteration. ``cgra_run`` ships only those rows, with
each row's cycle and PE (``stage_injection``), and ``place_injection`` builds
the plane from them on the device. ``build_injection`` builds the same plane
on the host: the reference the tests and ``ref.py`` hold it to. The kernel's
trace ``[C, pes, B]`` stays on the device: ``take_stores`` gathers the store
rows from it there, one per store node and iteration, and only those rows
come back to the host.

Both record ``repro.obs`` spans, which also land in a JAX profiler trace
while one is collecting: ``lower`` around ``compile_program``, and
``cgra_run`` with one child per step of a call (``cgra_run.stage``,
``.to_device``, ``.launch``, ``.wait``, ``.to_host``, ``.extract``).
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from pathlib import Path
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.dfg import DFG
from repro.core.mapper import Mapping
from repro.core.simulate import OPCODES, _operands

from .cgra_sim import KERNEL_OPS, NOPS, cgra_sim_pallas, vmem_footprint

assert list(KERNEL_OPS) == list(OPCODES), "kernel/oracle opcode tables diverged"


@dataclass
class CGRAProgram:
    """Device-ready encoding of one mapped loop kernel."""

    mapping: Mapping
    ii: int
    ring: int
    num_pes: int
    # the distinct operand reads: (delta, offset), the value produced delta
    # cycles ago (1..ring) at PE pe + offset
    route_pairs: np.ndarray  # [P, 2] int32
    # tables, per kernel step
    route: np.ndarray      # [II, pes, 2] int32: route pair of operand a, b (-1 = none)
    op_sel: np.ndarray     # [II, pes, NOPS] f32 one-hot
    imm: np.ndarray        # [II, pes] f32
    # integer views (used by ref.py and the injection builder)
    op_id: np.ndarray      # [II, pes] int32 (-1 = idle)
    node_at: np.ndarray    # [II, pes] int32 (-1 = idle)
    src_pe: np.ndarray     # [II, pes, 2] int32
    src_delta: np.ndarray  # [II, pes, 2] int32 (cycles since operand produced)

    @property
    def offsets(self) -> tuple[int, ...]:
        """The distinct PE offsets (``src_pe - pe``) the program reads over."""
        return tuple(sorted({int(d) for d in self.route_pairs[:, 1]}))

    def vmem_bytes(self, batch_tile: int) -> int:
        """VMEM one kernel grid step needs (the kernel's ``vmem_limit_bytes``)."""
        return vmem_footprint(self.num_pes, self.ring, batch_tile,
                              max(1, len(self.route_pairs)))


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
    """Lower a mapping to the kernel's tables, inside an ``obs`` span
    ``lower`` (``kernel``, ``ii``, ``pes``; ``ring``, ``route_pairs``: the
    number of distinct pairs, ``table_bytes``: the program tables' bytes)."""
    with obs.span("lower", kernel=mapping.dfg.name, ii=mapping.ii,
                  pes=mapping.cgra.num_pes) as sp:
        program = _lower(mapping)
        sp.set(ring=program.ring, route_pairs=len(program.route_pairs),
               table_bytes=sum(t.nbytes for t in program_tables(program)))
    return program


def _lower(mapping: Mapping) -> CGRAProgram:
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

    pairs = sorted({(dl, sp - placement[v])
                    for v in dfg.nodes for sp, dl in zip(srcs[v], deltas[v])})
    pair_of = {pair: i for i, pair in enumerate(pairs)}
    route = np.full((ii, pes, 2), -1, np.int32)
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
            route[k, pe, slot] = pair_of[dl, sp - pe]
            src_pe[k, pe, slot] = sp
            src_delta[k, pe, slot] = dl

    return CGRAProgram(
        mapping=mapping, ii=ii, ring=ring, num_pes=pes,
        route_pairs=np.array(pairs, np.int32).reshape(-1, 2), route=route,
        op_sel=op_sel, imm=imm,
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


class StagedInjection(NamedTuple):
    """What ``cgra_run`` stages on the host in place of ``build_injection``'s
    planes: the rows ``place_injection`` writes into the injection plane on
    the device, and the firing mask, which is small."""

    rows: tuple[np.ndarray, ...]   # per input node, [iters, B] f32: its values
    cycle: np.ndarray              # [n_in * iters] int32: each row's cycle
    pe: np.ndarray                 # [n_in * iters] int32: each row's PE
    active: np.ndarray             # [C, pes] f32, as build_injection's
    batch: int

    @property
    def nbytes(self) -> int:
        return (sum(r.nbytes for r in self.rows) + self.cycle.nbytes
                + self.pe.nbytes + self.active.nbytes)


def node_rows(
    mapping: Mapping, op: str, num_iters: int
) -> tuple[list[int], np.ndarray, np.ndarray]:
    """The nodes of opcode ``op`` in graph order, and the (cycle, PE) of each
    node's row per iteration, node by node: cycle ``t_abs[v] + it * ii``
    and PE ``placement[v]``, as int32 vectors of ``len(nodes) * num_iters``."""
    m = mapping
    nodes = [v for v in m.dfg.nodes if m.dfg.ops[v] == op]
    its = np.arange(num_iters)
    cycle = np.array([m.t_abs[v] + it * m.ii for v in nodes for it in its], np.int32)
    pe = np.repeat(np.array([m.placement[v] for v in nodes], np.int32), num_iters)
    return nodes, cycle, pe


def stage_injection(
    program: CGRAProgram, inputs: dict[int, np.ndarray], num_iters: int
) -> StagedInjection:
    """The input values as rows, node by node in graph order, each row's
    cycle and PE (``node_rows``), and the firing mask. The rows are
    ``inputs[v]`` themselves where they are f32 already."""
    m = program.mapping
    its = np.arange(num_iters)
    nodes, cycle, pe = node_rows(m, "input", num_iters)
    rows = tuple(np.asarray(inputs[v][:num_iters], np.float32) for v in nodes)
    active = np.zeros((num_cycles(program, num_iters), program.num_pes), np.float32)
    active[np.add.outer(m.t_abs, its * m.ii), np.array(m.placement)[:, None]] = 1.0
    batch = next(iter(inputs.values())).shape[1] if inputs else 1
    return StagedInjection(rows, cycle, pe, active, batch)


@functools.partial(jax.jit, static_argnames=("num_cycles", "pes", "batch"))
def place_injection(
    rows: tuple[jax.Array, ...],   # per input node, [iters, B] f32
    cycle: jax.Array,              # [n_in * iters] int32
    pe: jax.Array,                 # [n_in * iters] int32
    *,
    num_cycles: int,
    pes: int,
    batch: int,
) -> jax.Array:
    """The injection plane ``[C, pes, B]`` built on the device: zeros, and
    each staged row at its (cycle, PE). A mapping holds one node per PE and
    kernel step, so no two rows share a (cycle, PE)."""
    plane = jnp.zeros((num_cycles, pes, batch), jnp.float32)
    if not rows:
        return plane
    return plane.at[cycle, pe].set(jnp.concatenate(rows), unique_indices=True,
                                   mode="promise_in_bounds")


@jax.jit
def take_stores(
    trace: jax.Array,   # [C, pes, B] f32, the kernel's output
    cycle: jax.Array,   # [n_store * iters] int32
    pe: jax.Array,      # [n_store * iters] int32
) -> jax.Array:
    """The store rows ``[n_store * iters, B]`` gathered from the trace on the
    device, row i at (``cycle[i]``, ``pe[i]``). The indices are traced, so
    each trace shape compiles once. One row slice at a time: for long rows
    (B = 51 200) XLA's gather copies the whole trace first, and this does not."""
    return jax.lax.map(lambda at: trace[at[0], at[1]], (cycle, pe))


def program_tables(program: CGRAProgram) -> tuple[np.ndarray, ...]:
    """The kernel's first four operands: the route pairs, the route, opcode
    and immediate tables, the immediates as ``[II, 1, pes]``."""
    return (program.route_pairs, program.route, program.op_sel,
            program.imm.reshape(program.ii, 1, program.num_pes))


def kernel_operands(
    program: CGRAProgram, inj: np.ndarray | jax.Array, active: np.ndarray | jax.Array
) -> tuple:
    """The kernel's six operands in its order: the four program tables, the
    injections ``[C, pes, B]`` and the firing mask as ``[C, 1, pes]``."""
    C, pes, _ = inj.shape
    return (*program_tables(program), inj, active.reshape(C, 1, pes))


def _dispatch(program: CGRAProgram, operands, batch_tile: int, interpret: bool) -> jax.Array:
    C, _, batch = operands[4].shape
    return cgra_sim_pallas(
        *operands,
        ii=program.ii,
        ring=program.ring,
        num_cycles=C,
        batch_tile=min(batch_tile, batch),
        interpret=interpret,
    )


def cgra_launch(
    program: CGRAProgram,
    inj: np.ndarray | jax.Array,      # [C, pes, B] f32 (build_injection)
    active: np.ndarray | jax.Array,   # [C, pes] f32
    *,
    batch_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Launch the kernel on staged injections; returns the device trace
    [C, pes, B] without waiting for it."""
    operands = tuple(jnp.asarray(x) for x in kernel_operands(program, inj, active))
    return _dispatch(program, operands, batch_tile, interpret)


def cgra_run(
    program: CGRAProgram,
    inputs: dict[int, np.ndarray],   # input node -> [num_iters, B] f32
    num_iters: int,
    *,
    batch_tile: int = 128,
    interpret: bool = False,
) -> tuple[dict[int, np.ndarray], jax.Array]:
    """Execute on the Pallas kernel; returns (store outputs, full trace).

    The store outputs are host arrays ``[num_iters, B]``, one per store node.
    The trace ``[C, pes, B]`` is the device array the kernel wrote: it stays
    on the device, and a caller that reads it converts it
    (``np.asarray(trace)``).

    Compiled for the TPU unless ``interpret=True``; on a CPU backend the
    compiled path raises rather than falling back to the interpreter.

    A call runs in explicit steps, each in its own ``obs`` span under
    ``cgra_run`` (``kernel``, ``pes``, ``streams``, ``iters``, ``cycles``),
    traced or not:

    * ``cgra_run.stage``: ``stage_injection`` on the host (``bytes``: the
      input rows, their cycles and PEs, and active);
    * ``cgra_run.to_device``: the program tables, the staged rows and active
      to the device, and ``place_injection`` building inj there from the
      rows, waited for together (``table_bytes``; ``inj_bytes``: the staged
      bytes sent; ``plane_bytes``: inj and active as the kernel reads them);
    * ``cgra_run.launch``: the kernel's dispatch (and its compile, if any),
      then ``take_stores``'s, which gathers the store rows on the device (a
      loop with no store gathers nothing);
    * ``cgra_run.wait``: until the kernel and the gathered rows are ready;
    * ``cgra_run.to_host``: the store rows back to the host (``bytes``;
      ``trace_bytes``: the trace, which stays on the device);
    * ``cgra_run.extract``: the rows copied and split into one output per
      store node (``bytes``).
    """
    m = program.mapping
    with obs.span("cgra_run", kernel=m.dfg.name, pes=program.num_pes,
                  iters=num_iters) as run:
        with obs.span("cgra_run.stage") as sp:
            staged = stage_injection(program, inputs, num_iters)
            sp.set(bytes=staged.nbytes)
        C, pes = staged.active.shape
        run.set(streams=staged.batch, cycles=C)

        tables = program_tables(program)
        with obs.span("cgra_run.to_device",
                      table_bytes=sum(x.nbytes for x in tables),
                      inj_bytes=staged.nbytes,
                      plane_bytes=4 * C * pes * (staged.batch + 1)):
            tables, rows, cycle, pe, active = jax.device_put(
                (tables, staged.rows, staged.cycle, staged.pe,
                 staged.active.reshape(C, 1, pes)))
            inj = place_injection(rows, cycle, pe, num_cycles=C, pes=pes,
                                  batch=staged.batch)
            operands = jax.block_until_ready((*tables, inj, active))
        with obs.span("cgra_run.launch"):
            trace = _dispatch(program, operands, batch_tile, interpret)
            stores, store_cycle, store_pe = node_rows(m, "store", num_iters)
            gathered = (take_stores(trace, store_cycle, store_pe) if stores
                        else np.empty((0, staged.batch), np.float32))
        with obs.span("cgra_run.wait"):
            jax.block_until_ready((trace, gathered))
        with obs.span("cgra_run.to_host", bytes=gathered.nbytes,
                      trace_bytes=trace.nbytes):
            host = np.asarray(gathered)

        with obs.span("cgra_run.extract") as sp:
            # the transferred rows are read-only; the outputs are a copy
            split = np.array(host).reshape(len(stores), num_iters, staged.batch)
            outs = dict(zip(stores, split))
            sp.set(bytes=split.nbytes)
    return outs, trace
