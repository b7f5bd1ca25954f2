"""The Table III loop bodies as plain graphs: the benchmark's own workload.

A copy of the generator in ``repro.core.benchsuite`` (``make_benchmark_dfg``),
kept here so that a change to the program's suite cannot change what the
benchmark runs. Each loop matches its Table III node count and recurrence
length (RecII); the authors' own DFGs are not published. A graph is a plain
``Graph``; ``run.py`` turns it into the compiler's ``DFG``.
"""

from __future__ import annotations

import random
import zlib
from dataclasses import dataclass

# opcode -> number of operands
ARITY = {
    "input": 0, "const": 0, "load": 1, "store": 1, "add": 2, "sub": 2,
    "mul": 2, "div": 2, "and": 2, "or": 2, "xor": 2, "shl": 2, "shr": 2,
    "min": 2, "max": 2, "neg": 1, "not": 1, "abs": 1, "mov": 1, "phi": 2,
    "cmp": 2,
}
_BINOPS = ["add", "sub", "mul", "xor", "and", "or", "shl", "shr", "min", "max"]
_UNOPS = ["neg", "not", "abs", "mov"]


@dataclass(frozen=True)
class Graph:
    """A loop body: ``edges`` are ``(src, dst, distance)``; distance 0 is a
    dependency inside one iteration, d >= 1 a value from d iterations back."""

    name: str
    ops: tuple[str, ...]
    edges: tuple[tuple[int, int, int], ...]
    imms: tuple[float, ...]

    @property
    def num_nodes(self) -> int:
        return len(self.ops)

    def nodes_of(self, op: str) -> list[int]:
        return [v for v, o in enumerate(self.ops) if o == op]


def table3_graph(name: str, num_nodes: int, rec: int) -> Graph:
    """The loop ``name`` with ``num_nodes`` nodes and RecII ``rec``: the same
    graph as ``repro.core.benchsuite.make_benchmark_dfg(name, num_nodes, rec)``."""
    rng = random.Random(zlib.crc32(name.encode()))
    ops: list[str] = []
    edges: list[tuple[int, int, int]] = []
    n_inputs = max(2, min(num_nodes // 5, num_nodes - rec - 1))
    ops += ["input"] * n_inputs
    inputs = list(range(n_inputs))

    # recurrence chain c0 (phi) -> ... -> c_{rec-1} -(carried)-> c0
    chain = list(range(n_inputs, n_inputs + rec))
    ops.append("phi")
    edges.append((rng.choice(inputs), chain[0], 0))
    for i, v in enumerate(chain[1:], start=1):
        ops.append(rng.choice(_BINOPS))
        edges.append((chain[i - 1], v, 0))
        if rng.random() < 0.6:
            edges.append((rng.choice(inputs), v, 0))
    edges.append((chain[-1], chain[0], 1))

    # the rest: a layered DAG biased towards recent producers, stores last
    for v in range(n_inputs + rec, num_nodes):
        pool = list(range(v))
        weights = [1.0 + 3.0 * (p / max(1, v - 1)) for p in pool]
        k = 2 if rng.random() < 0.7 else 1
        preds = _weighted_sample(rng, pool, weights, k)
        if v == num_nodes - 1 or (num_nodes - v <= 2 and rng.random() < 0.7):
            ops.append("store")
            preds = preds[:1]
        else:
            ops.append(rng.choice(_BINOPS) if len(preds) == 2 else rng.choice(_UNOPS))
        edges += [(p, v, 0) for p in preds]

    return Graph(name, tuple(ops), tuple(edges), (0.0,) * num_nodes)


def _weighted_sample(rng: random.Random, pool: list[int], weights: list[float], k: int) -> list[int]:
    chosen: list[int] = []
    pool, weights = list(pool), list(weights)
    for _ in range(min(k, len(pool))):
        r = rng.random() * sum(weights)
        acc = 0.0
        for i, w in enumerate(weights):
            acc += w
            if r <= acc:
                chosen.append(pool.pop(i))
                weights.pop(i)
                break
    return chosen
