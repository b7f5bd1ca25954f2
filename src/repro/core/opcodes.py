"""The CGRA's operation set, defined once.

``OPS`` holds one entry per opcode: its name, its arity (how many operands it
reads), the capability class a PE needs to run it (``core/cgra.py``) and its
semantics. An op's code is its position in ``OPS``; that is the number the
kernel's tables carry (``OPCODES``). Everything else reads this table:
``DFG.validate`` the arity, the mapper ``op_class``, and the three executors
the semantics: ``simulate.alu`` on Python floats, ``kernels.ref.alu`` on
numpy rows, and the cgra_sim kernel on jnp blocks, where every entry is
evaluated and each PE selects its own.

The datapath word is a float32. Arithmetic works on the words. The bitwise
ops work on the low 16 bits of the integer part of ``|x|``, and the shifts
shift by that of ``|b|`` mod 8. Each entry is written over the ``Operands``
an executor builds once per step with ``operands``: the words, those 16-bit
views, and the executor's own array namespace, integer-to-word conversion and
division. This module imports neither numpy nor jax; each executor brings
its own.
"""

from __future__ import annotations

import operator
from typing import Any, Callable, NamedTuple


class Operands(NamedTuple):
    """What an op's semantics read; ``operands`` builds it."""

    a: Any            # first operand word (0 where the op reads none)
    b: Any            # second operand word
    imm: Any          # the node's immediate
    inj: Any          # the value injected into an input node
    ia: Any           # low 16 bits of int(|a|)
    ib: Any           # low 16 bits of int(|b|)
    sh: Any           # shift count, ib % 8
    safe_b: Any       # b, with 1 where b is 0
    xp: Any           # abs, where, minimum, maximum, broadcast_to, shape
    word: Callable    # an integer or boolean result as a word
    divide: Callable  # a / b for a nonzero b


def operands(a, b, imm, inj, *, xp, to_int: Callable, word: Callable,
             divide: Callable = operator.truediv) -> Operands:
    """The ``Operands`` of one step: ``to_int`` truncates a word to an
    integer of the executor's own type, ``divide`` is IEEE division unless
    the executor brings its own."""
    ia = to_int(xp.abs(a)) & 0xFFFF
    ib = to_int(xp.abs(b)) & 0xFFFF
    return Operands(a, b, imm, inj, ia, ib, ib % 8, xp.where(b != 0, b, 1.0),
                    xp, word, divide)


class Op(NamedTuple):
    name: str
    arity: int
    cls: str                                # capability class: alu | mem | mul
    semantics: Callable[[Operands], Any]


OPS: tuple[Op, ...] = (
    Op("input", 0, "alu", lambda v: v.inj),   # live-in: a streamed value
    Op("const", 0, "alu", lambda v: v.xp.broadcast_to(v.imm, v.xp.shape(v.a))),
    Op("load", 1, "mem", lambda v: v.a),      # the address operand's value
    Op("store", 1, "mem", lambda v: v.a),     # address folded into imm
    Op("add", 2, "alu", lambda v: v.a + v.b),
    Op("sub", 2, "alu", lambda v: v.a - v.b),
    Op("mul", 2, "mul", lambda v: v.a * v.b),
    Op("div", 2, "mul", lambda v: v.xp.where(v.b != 0, v.divide(v.a, v.safe_b), 0.0)),
    Op("and", 2, "alu", lambda v: v.word(v.ia & v.ib)),
    Op("or", 2, "alu", lambda v: v.word(v.ia | v.ib)),
    Op("xor", 2, "alu", lambda v: v.word(v.ia ^ v.ib)),
    Op("shl", 2, "alu", lambda v: v.word((v.ia << v.sh) & 0xFFFF)),
    Op("shr", 2, "alu", lambda v: v.word(v.ia >> v.sh)),
    Op("min", 2, "alu", lambda v: v.xp.minimum(v.a, v.b)),
    Op("max", 2, "alu", lambda v: v.xp.maximum(v.a, v.b)),
    Op("neg", 1, "alu", lambda v: -v.a),
    Op("not", 1, "alu", lambda v: v.word(~v.ia & 0xFFFF)),
    Op("abs", 1, "alu", lambda v: v.xp.abs(v.a)),
    Op("mov", 1, "alu", lambda v: v.a),       # copy / route-through
    # loop-carried merge: accumulates (the carried operand is 0 on the first
    # iteration), which keeps recurrences live for equivalence testing
    Op("phi", 2, "alu", lambda v: v.a + v.b),
    Op("cmp", 2, "alu", lambda v: v.word(v.a > v.b)),
)

OPCODES: dict[str, int] = {op.name: code for code, op in enumerate(OPS)}
OP_ARITY: dict[str, int] = {op.name: op.arity for op in OPS}


def op_class(op: str) -> str:
    """Capability class an op needs: ``mem`` | ``mul`` | ``alu`` (``alu``
    for a name outside the table, which ``DFG.validate`` rejects)."""
    code = OPCODES.get(op)
    return "alu" if code is None else OPS[code].cls
