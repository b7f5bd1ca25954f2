"""The numpy oracle for the cgra_sim kernel.

``cgra_sim_reference`` executes the same compiled program as the cgra_sim
kernel but with a structurally different method: integer-indexed reads from
the full value trace (no ring buffer, no route pairs or shifts), so it
validates the kernel's routing/ring logic rather than sharing it. Its ALU
takes the op semantics from the op table, as ``core.simulate.alu`` and the
kernel do (bit-identical in f32 by construction).
"""

from __future__ import annotations

import numpy as np

from repro.core.opcodes import OPS, operands

from .ops import CGRAProgram, build_injection, num_cycles

_F = np.float32


def alu(code: int, a: np.ndarray, b: np.ndarray, imm: float, inj: np.ndarray) -> np.ndarray:
    """The numpy ALU: opcode ``code``'s semantics from the op table
    (``core/opcodes.py``) on f32 rows."""
    v = operands(a, b, _F(imm), inj, xp=np, to_int=lambda x: x.astype(np.int64),
                 word=lambda x: x.astype(_F))
    return OPS[code].semantics(v)


def cgra_sim_reference(
    program: CGRAProgram,
    inputs: dict[int, np.ndarray],
    num_iters: int,
) -> tuple[dict[int, np.ndarray], np.ndarray]:
    """Trace-indexed reference execution; returns (store outputs, trace)."""
    inj, active = build_injection(program, inputs, num_iters)
    C = num_cycles(program, num_iters)
    pes = program.num_pes
    batch = inj.shape[2]
    trace = np.zeros((C, pes, batch), _F)
    for c in range(C):
        k = c % program.ii
        for pe in range(pes):
            if active[c, pe] == 0.0:
                continue
            oid = int(program.op_id[k, pe])
            ops_ab = []
            for slot in range(2):
                sp = int(program.src_pe[k, pe, slot])
                dl = int(program.src_delta[k, pe, slot])
                if sp < 0 or c - dl < 0:
                    ops_ab.append(np.zeros(batch, _F))
                else:
                    ops_ab.append(trace[c - dl, sp, :])
            val = alu(
                oid, ops_ab[0], ops_ab[1], float(program.imm[k, pe]), inj[c, pe]
            )
            trace[c, pe, :] = val.astype(_F)
    m = program.mapping
    outs: dict[int, np.ndarray] = {}
    for v in m.dfg.nodes:
        if m.dfg.ops[v] == "store":
            cyc = m.t_abs[v] + np.arange(num_iters) * m.ii
            outs[v] = trace[cyc, m.placement[v], :]
    return outs, trace
