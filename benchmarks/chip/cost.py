"""What the algorithm of a mapped loop needs, counted from the loop itself.

The counts come from the graph and the call's sizes, never from the dense
tables the program builds or from the shapes in a trace, so they stay the
same whatever implements the kernel.
"""

from __future__ import annotations

from suite import Graph

WORD = 4            # bytes of one float32 value
PROGRAM_WORDS = 4   # per node: opcode, two operand references, immediate


def call_bytes(graph: Graph, streams: int, iters: int) -> int:
    """Bytes one execution of ``graph`` over ``streams`` x ``iters`` must
    move: every input value read once, every store value written once, and
    the program's integer encoding."""
    values = len(graph.nodes_of("input")) + len(graph.nodes_of("store"))
    return values * WORD * streams * iters + PROGRAM_WORDS * WORD * graph.num_nodes


def call_ops(graph: Graph, streams: int, iters: int) -> int:
    """Node firings that compute (every node but inputs and constants)."""
    firing = graph.num_nodes - len(graph.nodes_of("input")) - len(graph.nodes_of("const"))
    return firing * streams * iters
