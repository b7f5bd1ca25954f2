"""The traffic generator: one for every mix, driven by the mix's data file.

A mix (``traffic/<mix>.json``) names its ``loop`` and that loop's parameters:

* ``execute``: a closed loop with one client. Each unit is one call that runs
  a mapped loop over ``pe_streams / PEs`` streams x ``iterations`` through
  the program's executor, with inputs drawn from the seed on the host and the
  store outputs back on the host. Calls rotate through ``kernels``.
* ``compile``: a closed loop with one client. Each request is a cold compile
  of one loop (mapping, then lowering to a device program). Every kernel of
  ``kernels`` comes once in each round, in an order drawn from the seed.

Both close their window at the end of the first whole round after the
requested seconds, so every run does the same mix of work. Setup maps,
lowers and warms up exactly the programs the window uses.
"""

from __future__ import annotations

import math
import sys
import time

import jax
import numpy as np

import reference
import sut
from suite import Graph, table3_graph

WARMUP = 2**32     # input stream key of warm-up calls, never a window call's


def percentile(values: list[float], p: float) -> float:
    """The value at rank ``round(p / 100 * (n - 1))`` of the sorted values
    (``p`` in [0, 100])."""
    xs = sorted(values)
    rank = max(0, min(len(xs) - 1, int(round(p / 100.0 * (len(xs) - 1)))))
    return xs[rank]


def geomean(values: list[float]) -> float:
    return math.exp(sum(math.log(v) for v in values) / len(values))


def draw_inputs(graph: Graph, iters: int, streams: int, bounds, seed: int, *key: int):
    """Inputs of one call, ``[iters, streams]`` float32 per input node, drawn
    uniformly from ``bounds`` by ``(seed, *key)``: the same key gives the
    same inputs, so the check can draw them again after the window."""
    lo, hi = bounds
    nodes = graph.nodes_of("input")
    rng = np.random.default_rng([seed % 2**64, *key])
    x = rng.random((len(nodes), iters, streams), dtype=np.float32)
    x *= np.float32(hi - lo)
    x += np.float32(lo)
    return {v: x[i] for i, v in enumerate(nodes)}


class Loop:
    """What the two loops share: the fabric, the kernels and the seed."""

    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        fabric = config["fabric"]
        self.rows, self.cols = fabric["rows"], fabric["cols"]
        self.pes = self.rows * self.cols
        self.profile = config["profile"]
        self.mix = mix
        self.seed = seed
        self.iters = mix["iterations"]
        self.bounds = tuple(mix["input_range"])
        self.graphs = {k: table3_graph(k, *config["kernels"][k]) for k in mix["kernels"]}

    def compile(self, graph: Graph):
        res = sut.compile_loop(graph, self.rows, self.cols, self.profile)
        if not res.ok:
            raise RuntimeError(f"{graph.name} did not map onto {self.rows}x{self.cols}: {res.reason}")
        return res


class ExecuteLoop(Loop):
    """The execution farm: mapped loops run over many streams, call after call."""

    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        super().__init__(config, mix, seed)
        if mix["pe_streams"] % self.pes:
            raise ValueError(f"pe_streams {mix['pe_streams']} is not a multiple of {self.pes} PEs")
        self.streams = mix["pe_streams"] // self.pes
        self.calls: list[dict] = []

    def setup(self) -> None:
        self.programs = {}
        for i, (name, graph) in enumerate(self.graphs.items()):
            self.programs[name] = sut.lower(self.compile(graph).mapping)
            warm = draw_inputs(graph, self.iters, self.streams, self.bounds, self.seed, WARMUP, i)
            sut.execute(self.programs[name], warm, self.iters)

    def run_window(self, seconds: float) -> tuple[float, float]:
        t0 = time.perf_counter()
        while True:
            for name, graph in self.graphs.items():
                k = len(self.calls)
                inputs = draw_inputs(graph, self.iters, self.streams, self.bounds, self.seed, k)
                with jax.profiler.TraceAnnotation(f"bench.call:{name}"):
                    start = time.perf_counter()
                    outs = sut.execute(self.programs[name], inputs, self.iters)
                    end = time.perf_counter()
                self.calls.append({"kernel": name, "start": start, "end": end, "outs": outs})
            if end - t0 >= seconds:
                return t0, end

    def after_window(self) -> None:
        pass

    def release(self) -> None:
        del self.programs

    def units(self) -> list[dict]:
        return [
            {"kernel": c["kernel"], "start": c["start"], "end": c["end"],
             "streams": self.streams, "iterations": self.iters}
            for c in self.calls
        ]

    def end_to_end(self, t0: float, t1: float) -> dict[str, float]:
        work = len(self.calls) * self.streams * self.iters
        return {"exec_rate": work / (t1 - t0)}

    def check(self) -> tuple[dict[str, tuple[int, int]], dict[str, int]]:
        """Every call's store outputs, on a seeded sample of its streams (all
        of them where there are no more than ``checked_streams``), against
        the reference run on the same inputs."""
        n = min(self.streams, self.mix["checked_streams"])
        bad = compared = out_of_domain = 0
        for k, call in enumerate(self.calls):
            graph = self.graphs[call["kernel"]]
            inputs = draw_inputs(graph, self.iters, self.streams, self.bounds, self.seed, k)
            cols = np.sort(np.random.default_rng([self.seed % 2**64, k, 1]).choice(
                self.streams, n, replace=False))
            want, odd = reference.interpret(
                graph, {v: x[:, cols] for v, x in inputs.items()}, self.iters)
            for v, w in want.items():
                bad += reference.mismatches(call["outs"][v][:, cols], w)
                compared += w.size
            out_of_domain += odd
        return (
            {"store_mismatches": (bad, 0)},
            {"calls": len(self.calls), "store_values_compared": compared,
             "reference_values_out_of_domain": out_of_domain,
             "call_s": [round(c["end"] - c["start"], 4) for c in self.calls]},
        )


class CompileLoop(Loop):
    """The recompile service: one cold compile after another."""

    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        super().__init__(config, mix, seed)
        self.streams = mix["executed_streams"]
        self.requests: list[dict] = []
        self.sample: list[tuple[int, object]] = []    # (request, program)
        self.executed: list[tuple[int, dict]] = []    # (request, store outputs)

    def setup(self) -> None:
        for graph in self.graphs.values():
            sut.lower(self.compile(graph).mapping)

    def run_window(self, seconds: float) -> tuple[float, float]:
        order = np.random.default_rng([self.seed % 2**64, 1])
        keep = np.random.default_rng([self.seed % 2**64, 2])
        names = list(self.graphs)
        size = self.mix["executed_sample"]
        t0 = time.perf_counter()
        while True:
            for i in order.permutation(len(names)):
                graph = self.graphs[names[i]]
                with jax.profiler.TraceAnnotation(f"bench.request:{graph.name}"):
                    start = time.perf_counter()
                    res = sut.compile_loop(graph, self.rows, self.cols, self.profile)
                    mapped = time.perf_counter()
                    program = sut.lower(res.mapping) if res.ok else None
                    end = time.perf_counter()
                m = res.mapping
                k = len(self.requests)
                self.requests.append({
                    "kernel": graph.name, "start": start, "end": end, "ok": res.ok,
                    "time_s": res.phases.time_s, "space_s": res.phases.space_s,
                    "lower_s": end - mapped,
                    "mapping": (m.ii, list(m.t_abs), list(m.placement)) if res.ok else None,
                })
                # a uniform seeded sample of the served programs (reservoir)
                if program is not None:
                    j = k if k < size else int(keep.integers(0, k + 1))
                    if j < size:
                        if j < len(self.sample):
                            self.sample[j] = (k, program)
                        else:
                            self.sample.append((k, program))
            if end - t0 >= seconds:
                return t0, end

    def after_window(self) -> None:
        """Run the sampled programs on the chip (compiling them if need be)."""
        for k, program in self.sample:
            graph = self.graphs[self.requests[k]["kernel"]]
            inputs = draw_inputs(graph, self.iters, self.streams, self.bounds, self.seed, k)
            with jax.profiler.TraceAnnotation(f"bench.execute:{graph.name}"):
                try:
                    outs = sut.execute(program, inputs, self.iters)
                except Exception as exc:   # a program that cannot run answers nothing
                    print(f"loops: request {k} ({graph.name}) did not run: {exc!r}", file=sys.stderr)
                    outs = {}
            self.executed.append((k, outs))

    def release(self) -> None:
        self.sample.clear()

    def units(self) -> list[dict]:
        return [{k: v for k, v in r.items() if k != "mapping"} for r in self.requests]

    def end_to_end(self, t0: float, t1: float) -> dict[str, float]:
        served = [r for r in self.requests if r["ok"]]
        return {
            "compile_p95_ms": 1e3 * percentile([r["end"] - r["start"] for r in self.requests], 95),
            "ii_over_mii": geomean([
                r["mapping"][0] / reference.min_ii(self.graphs[r["kernel"]], self.pes)
                for r in served
            ]),
        }

    def check(self) -> tuple[dict[str, tuple[int, int]], dict[str, int]]:
        """Every served mapping against the fabric model, every request
        answered, and the sampled programs' store outputs against the
        reference."""
        invalid = failed = bad = compared = 0
        for r in self.requests:
            if not r["ok"]:
                failed += 1
            elif reference.check_mapping(self.graphs[r["kernel"]], self.rows, self.cols, *r["mapping"]):
                invalid += 1
        for k, outs in self.executed:
            graph = self.graphs[self.requests[k]["kernel"]]
            inputs = draw_inputs(graph, self.iters, self.streams, self.bounds, self.seed, k)
            want, _ = reference.interpret(graph, inputs, self.iters)
            for v, w in want.items():
                bad += reference.mismatches(outs[v], w) if v in outs else w.size
                compared += w.size
        return (
            {"failed_requests": (failed, 0), "invalid_mappings": (invalid, 0),
             "store_mismatches": (bad, 0)},
            {"requests": len(self.requests), "executed_programs": len(self.executed),
             "store_values_compared": compared},
        )


LOOPS = {"execute": ExecuteLoop, "compile": CompileLoop}
