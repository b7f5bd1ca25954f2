"""Plain reference of what a mapped loop computes, and of what a mapping is.

``interpret`` runs a loop body iteration by iteration over many streams at
once, in numpy float32: the semantics the configurations state ("store
outputs equal a plain interpretation of the DFG's f32 semantics"). It reads
only the graph and the inputs. Nothing here imports the program: neither its
mapper, its lowering, nor its own interpreter.

``check_mapping`` holds a served mapping to the paper's fabric model: one node
per PE and kernel step, every operand produced at least one cycle before it is
read, and every producer on the consumer's own PE or a mesh neighbour.
"""

from __future__ import annotations

import math

import ml_dtypes
import numpy as np

from suite import Graph

# The f32 guarantee holds for values that are finite, normal (or zero) and
# below 2**31 in magnitude, where the bitwise ops' integer casts agree.
INT_LIMIT = 2.0 ** 31


def operands(graph: Graph, v: int) -> list[tuple[int, int]]:
    """``(src, distance)`` of ``v``'s operands in operand order: by distance,
    then by source node."""
    return [(s, d) for d, s in sorted((d, s) for s, t, d in graph.edges if t == v)]


def topo_order(graph: Graph) -> list[int]:
    indeg = [0] * graph.num_nodes
    succ: list[list[int]] = [[] for _ in range(graph.num_nodes)]
    for s, t, d in graph.edges:
        if d == 0:
            succ[s].append(t)
            indeg[t] += 1
    order, ready = [], [v for v in range(graph.num_nodes) if indeg[v] == 0]
    while ready:
        v = ready.pop()
        order.append(v)
        for w in succ[v]:
            indeg[w] -= 1
            if indeg[w] == 0:
                ready.append(w)
    if len(order) != graph.num_nodes:
        raise ValueError(f"{graph.name}: dependency cycle inside one iteration")
    return order


def to_bf16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16 and widened back: the control's precision."""
    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def _alu(op: str, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    if op in ("load", "store", "mov"):
        return a
    if op in ("add", "phi"):       # phi accumulates its carried operand
        return a + b
    if op == "sub":
        return a - b
    if op == "mul":
        return a * b
    if op == "div":
        return np.where(b != 0, a / np.where(b != 0, b, 1), 0).astype(np.float32)
    if op == "min":
        return np.minimum(a, b)
    if op == "max":
        return np.maximum(a, b)
    if op == "neg":
        return -a
    if op == "abs":
        return np.abs(a)
    if op == "cmp":
        return (a > b).astype(np.float32)
    # bitwise ops act on the low 16 bits of |x| truncated to an integer
    ia = np.abs(a).astype(np.int64) & 0xFFFF
    ib = np.abs(b).astype(np.int64) & 0xFFFF
    if op == "and":
        r = ia & ib
    elif op == "or":
        r = ia | ib
    elif op == "xor":
        r = ia ^ ib
    elif op == "shl":
        r = (ia << (ib % 8)) & 0xFFFF
    elif op == "shr":
        r = ia >> (ib % 8)
    elif op == "not":
        r = ~ia & 0xFFFF
    else:
        raise ValueError(f"unknown op {op!r}")
    return r.astype(np.float32)


def interpret(
    graph: Graph,
    inputs: dict[int, np.ndarray],
    iters: int,
    *,
    bf16: bool = False,
) -> tuple[dict[int, np.ndarray], int]:
    """Run ``iters`` iterations of the loop over the streams of ``inputs``.

    ``inputs`` maps each input node to ``[iters, streams]`` float32. Returns
    the store nodes' outputs ``[iters, streams]`` and the number of values
    computed outside the domain the f32 guarantee covers (non-finite,
    subnormal, or of magnitude 2**31 or more). With ``bf16`` every value is
    rounded to bfloat16: the lower-precision control.
    """
    rnd = to_bf16 if bf16 else (lambda x: x)
    order = topo_order(graph)
    ops = [operands(graph, v) for v in range(graph.num_nodes)]
    streams = next(iter(inputs.values())).shape[1]
    zero = np.zeros(streams, np.float32)
    history: list[list[np.ndarray]] = []
    outs = {v: np.empty((iters, streams), np.float32) for v in graph.nodes_of("store")}
    bad = 0
    for it in range(iters):
        cur: list[np.ndarray] = [zero] * graph.num_nodes
        for v in order:
            op = graph.ops[v]
            if op == "input":
                val = rnd(np.asarray(inputs[v][it], np.float32))
            elif op == "const":
                val = rnd(np.full(streams, graph.imms[v], np.float32))
            else:
                args = [
                    cur[s] if d == 0 else (history[it - d][s] if it >= d else zero)
                    for s, d in ops[v]
                ]
                a = args[0] if args else zero
                b = args[1] if len(args) > 1 else zero
                with np.errstate(all="ignore"):
                    val = rnd(_alu(op, a, b))
            mag = np.abs(val)
            bad += int(np.count_nonzero(
                ~np.isfinite(val) | (mag >= INT_LIMIT)
                | ((mag > 0) & (mag < np.finfo(np.float32).tiny))
            ))
            cur[v] = val
            if op == "store":
                outs[v][it] = val
        history.append(cur)
    return outs, bad


def mismatches(got: np.ndarray, want: np.ndarray) -> int:
    """Values not equal to the reference's (a NaN never equals)."""
    return int(np.count_nonzero(~(got == want)))


def rec_ii(graph: Graph) -> int:
    """Smallest II at which ``t_dst >= t_src + 1 - II * distance`` has a
    solution for every edge (no positive cycle): the recurrence bound."""
    n = graph.num_nodes
    for ii in range(1, n + 2):
        dist = [0] * n
        for _ in range(n + 1):
            changed = False
            for s, t, d in graph.edges:
                w = dist[s] + 1 - ii * d
                if w > dist[t]:
                    dist[t], changed = w, True
            if not changed:
                return ii
    return n + 1


def min_ii(graph: Graph, num_pes: int) -> int:
    """mII = max(ResII, RecII) on a homogeneous fabric of ``num_pes`` PEs."""
    return max(math.ceil(graph.num_nodes / num_pes), rec_ii(graph))


def check_mapping(
    graph: Graph, rows: int, cols: int, ii: int, t_abs: list[int], placement: list[int]
) -> list[str]:
    """What is wrong with a mapping of ``graph`` onto a ``rows`` x ``cols``
    mesh at initiation interval ``ii`` (empty when nothing is)."""
    errs: list[str] = []
    n = graph.num_nodes
    if len(t_abs) != n or len(placement) != n:
        return [f"mapping covers {len(placement)} nodes, the loop has {n}"]
    if ii < min_ii(graph, rows * cols):
        errs.append(f"II {ii} below mII {min_ii(graph, rows * cols)}")
    slots: dict[tuple[int, int], int] = {}
    for v in range(n):
        pe = placement[v]
        if not 0 <= pe < rows * cols:
            errs.append(f"node {v} on PE {pe}, outside the fabric")
        if t_abs[v] < 0:
            errs.append(f"node {v} at negative time {t_abs[v]}")
        key = (pe, t_abs[v] % ii)
        if key in slots:
            errs.append(f"nodes {slots[key]} and {v} share PE {pe} at step {key[1]}")
        slots[key] = v
    for s, t, d in graph.edges:
        if t_abs[t] + d * ii - t_abs[s] < 1:
            errs.append(f"edge {s}->{t} (distance {d}) read before it is produced")
        (rs, cs), (rt, ct) = divmod(placement[s], cols), divmod(placement[t], cols)
        if abs(rs - rt) + abs(cs - ct) > 1:
            errs.append(f"edge {s}->{t} joins PEs {placement[s]} and {placement[t]}, not neighbours")
    return errs
