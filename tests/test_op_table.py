"""The op table (core/opcodes.py) as each executor runs it.

Every opcode is checked in the scalar ALU (``simulate.alu``), the numpy
reference's ALU (``kernels.ref.alu``) and the kernel's candidate function
(``cgra_sim._alu_all``) on jnp arrays, against results written out by hand
here, on the operands where the semantics have edges: a zero divisor,
negative and non-integer words, words above 65 535 for the 16-bit ops,
shift counts of 8 or more, equal operands, f32 rounding and overflow, and
``phi`` accumulating over iterations. The arity test checks ``DFG.validate``
against arities written out here too.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dfg import DFG, Edge
from repro.core.opcodes import OPCODES
from repro.core.simulate import alu
from repro.kernels import cgra_sim, ref

INF = float("inf")

# op -> rows of (a, b, imm, inj, result); every operand is an f32 word
CASES = {
    "input": [(7.0, 3.0, 9.0, -2.5, -2.5), (0.0, 0.0, 0.0, 70000.25, 70000.25)],
    "const": [(7.0, 3.0, -1.25, 5.0, -1.25),
              (0.0, 0.0, 0.1, 0.0, 0.10000000149011612)],   # imm rounds to f32
    "load": [(-3.5, 9.0, 0.0, 1.0, -3.5), (70000.25, 0.0, 2.0, 0.0, 70000.25)],
    "store": [(-3.5, 9.0, 0.0, 1.0, -3.5), (70000.25, 0.0, 2.0, 0.0, 70000.25)],
    "add": [(1.5, -2.25, 0.0, 0.0, -0.75), (65535.0, 1.0, 0.0, 0.0, 65536.0),
            (16777216.0, 1.0, 0.0, 0.0, 16777216.0)],      # rounds in f32
    "sub": [(1.5, 2.25, 0.0, 0.0, -0.75), (-0.5, -0.5, 0.0, 0.0, 0.0),
            (16777216.0, -1.0, 0.0, 0.0, 16777216.0)],
    "mul": [(1.5, -2.5, 0.0, 0.0, -3.75), (-4.0, 0.0, 0.0, 0.0, 0.0),
            (2.0 ** 127, 4.0, 0.0, 0.0, INF)],               # f32 overflow
    "div": [(7.0, 2.0, 0.0, 0.0, 3.5), (1.0, 3.0, 0.0, 0.0, 0.3333333432674408),
            (5.0, 0.0, 0.0, 0.0, 0.0), (-5.0, -0.0, 0.0, 0.0, 0.0),
            (0.0, 4.0, 0.0, 0.0, 0.0), (-9.0, 0.5, 0.0, 0.0, -18.0)],
    "and": [(12.75, 10.0, 0.0, 0.0, 8.0), (-12.75, 10.0, 0.0, 0.0, 8.0),
            (65548.0, 10.0, 0.0, 0.0, 8.0), (70000.0, 70000.0, 0.0, 0.0, 4464.0)],
    "or": [(12.0, 3.5, 0.0, 0.0, 15.0), (-1.0, -2.0, 0.0, 0.0, 3.0),
           (65535.0, 65536.0, 0.0, 0.0, 65535.0), (0.75, 0.25, 0.0, 0.0, 0.0)],
    "xor": [(12.0, 10.0, 0.0, 0.0, 6.0), (-5.5, 5.0, 0.0, 0.0, 0.0),
            (131071.0, 1.0, 0.0, 0.0, 65534.0)],
    "shl": [(3.0, 2.0, 0.0, 0.0, 12.0), (1.0, 8.0, 0.0, 0.0, 1.0),
            (1.0, 9.0, 0.0, 0.0, 2.0), (1.0, 15.0, 0.0, 0.0, 128.0),
            (40000.0, 1.0, 0.0, 0.0, 14464.0), (1.5, 3.75, 0.0, 0.0, 8.0),
            (-3.0, -2.0, 0.0, 0.0, 12.0)],
    "shr": [(256.0, 4.0, 0.0, 0.0, 16.0), (256.0, 12.0, 0.0, 0.0, 16.0),
            (-255.5, 1.0, 0.0, 0.0, 127.0), (65792.0, 8.0, 0.0, 0.0, 256.0)],
    "min": [(1.5, -2.0, 0.0, 0.0, -2.0), (3.0, 3.0, 0.0, 0.0, 3.0),
            (-0.5, -0.25, 0.0, 0.0, -0.5), (70000.5, 65536.0, 0.0, 0.0, 65536.0)],
    "max": [(1.5, -2.0, 0.0, 0.0, 1.5), (3.0, 3.0, 0.0, 0.0, 3.0),
            (-0.5, -0.25, 0.0, 0.0, -0.25), (70000.5, 65536.0, 0.0, 0.0, 70000.5)],
    "neg": [(2.5, 9.0, 0.0, 0.0, -2.5), (-70000.0, 0.0, 0.0, 0.0, 70000.0)],
    "not": [(0.0, 0.0, 0.0, 0.0, 65535.0), (1.75, 0.0, 0.0, 0.0, 65534.0),
            (-1.0, 0.0, 0.0, 0.0, 65534.0), (65536.0, 0.0, 0.0, 0.0, 65535.0),
            (70000.0, 0.0, 0.0, 0.0, 61071.0)],
    "abs": [(-2.5, 0.0, 0.0, 0.0, 2.5), (3.0, -4.0, 0.0, 0.0, 3.0),
            (-70000.5, 0.0, 0.0, 0.0, 70000.5)],
    "mov": [(-3.5, 9.0, 0.0, 1.0, -3.5), (70000.25, 0.0, 2.0, 0.0, 70000.25)],
    # a running sum: the carried operand is 0 on the first iteration, then
    # each result comes back as the next iteration's b
    "phi": [(1.5, 0.0, 0.0, 0.0, 1.5), (2.25, 1.5, 0.0, 0.0, 3.75),
            (-0.75, 3.75, 0.0, 0.0, 3.0), (0.5, 3.0, 0.0, 0.0, 3.5)],
    "cmp": [(2.0, 1.0, 0.0, 0.0, 1.0), (1.0, 2.0, 0.0, 0.0, 0.0),
            (3.0, 3.0, 0.0, 0.0, 0.0), (-0.5, -0.75, 0.0, 0.0, 1.0)],
}

ARITY = {
    "input": 0, "const": 0, "load": 1, "store": 1, "add": 2, "sub": 2,
    "mul": 2, "div": 2, "and": 2, "or": 2, "xor": 2, "shl": 2, "shr": 2,
    "min": 2, "max": 2, "neg": 1, "not": 1, "abs": 1, "mov": 1, "phi": 2,
    "cmp": 2,
}


def _scalar(op, rows):
    return [alu(op, a, b, imm, inj) for a, b, imm, inj in rows]


def _numpy(op, rows):
    f32 = np.float32
    with np.errstate(over="ignore", invalid="ignore"):
        return [float(ref.alu(OPCODES[op], np.array([a], f32), np.array([b], f32),
                              imm, np.array([inj], f32))[0])
                for a, b, imm, inj in rows]


def _kernel(op, rows):
    a, b, imm, inj = (jnp.array(col, jnp.float32)[:, None] for col in zip(*rows))
    out = cgra_sim._alu_all(a, b, imm, inj)[OPCODES[op]]
    return [float(x) for x in np.asarray(out)[:, 0]]


EXECUTORS = {"scalar": _scalar, "numpy": _numpy, "kernel": _kernel}


def test_cases_cover_the_op_table():
    assert sorted(CASES) == sorted(OPCODES) == sorted(ARITY)


@pytest.mark.parametrize("executor", sorted(EXECUTORS))
@pytest.mark.parametrize("op", list(CASES))
def test_op_semantics(op, executor):
    rows = CASES[op]
    got = EXECUTORS[executor](op, [row[:4] for row in rows])
    assert got == [row[4] for row in rows]


def _node_with_preds(op: str, n: int) -> DFG:
    """Node ``n`` of opcode ``op`` fed by ``n`` input nodes."""
    return DFG(num_nodes=n + 1, edges=[Edge(i, n) for i in range(n)],
               ops=["input"] * n + [op], name=f"{op}{n}")


@pytest.mark.parametrize("op", list(ARITY))
def test_op_arity_enforced(op):
    _node_with_preds(op, ARITY[op]).validate()
    with pytest.raises(ValueError, match="arity"):
        _node_with_preds(op, ARITY[op] + 1).validate()
