"""Smoke run of the compiled CGRA execution path on one TPU.

    python chip_smoke.py

Maps five Table III suite kernels, and a chain through the ALU opcodes, through
``repro.api.Compiler`` onto the paper's 4x4 CGRA and onto a 20x20 one, lowers
each mapping with ``compile_program`` and executes it with
``cgra_run(..., interpret=False)`` over 2048 independent streams x 64 loop
iterations. Each phase checks the whole trace,
every stream, for equality with ``kernels.ref.cgra_sim_reference``, and the
store outputs of sampled streams against ``core.simulate.interpret_dfg``.

The seconds it prints are smoke timings, not metrics. The first run
compiles; a second finds its programs in the persistent compilation cache
(``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` in this checkout).

With no TPU, or when any phase fails, it exits nonzero and prints no result
line. On success the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import Compiler, resolve_options  # noqa: E402
from repro.core import CGRA  # noqa: E402
from repro.core.benchsuite import load_suite, opcode_cover_dfg  # noqa: E402
from repro.core.opcodes import OPS  # noqa: E402
from repro.core.simulate import interpret_dfg  # noqa: E402
from repro.kernels.ops import (  # noqa: E402
    build_injection,
    cgra_launch,
    cgra_run,
    compile_program,
    enable_compile_cache,
    stage_injection,
)
from repro.kernels.ref import cgra_sim_reference  # noqa: E402

# Between them: loop-carried recurrences (a phi chain in every kernel), all
# six bitwise ops, mul, and the deepest register ring of the suite
# (particlefilter, ring 11 at 20x20). No suite kernel divides or compares,
# so "opcover" adds a chain through 19 of the 21 opcodes (not load, phi).
KERNELS = ("fft", "crc32", "sha2", "gsm", "particlefilter")
FABRICS = ((4, 4), (20, 20))
STREAMS, ITERS, SEED = 2048, 64, 0
SAMPLED_STREAMS = 4
# the program's arrays the kernel reads: route pairs and codes, opcodes, imms
PROGRAM_TABLES = ("route_pairs", "route", "op_sel", "imm")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache reads included)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.seconds += duration


def check_trace(prog, got: np.ndarray, want: np.ndarray) -> None:
    """Exact equality; on a mismatch name the first (cycle, PE) and its op."""
    bad = got != want
    if not bad.any():
        return
    c, pe, lane = (int(i[0]) for i in np.nonzero(bad))
    op = OPS[prog.op_id[c % prog.ii, pe]].name
    raise AssertionError(
        f"trace differs from cgra_sim_reference at {int(bad.sum())} of "
        f"{bad.size} values; first at cycle {c}, PE {pe}, stream {lane} "
        f"({op}): got {got[c, pe, lane]!r}, want {want[c, pe, lane]!r}"
    )


def run_phase(name, dfg, fabric, compile_clock, rng) -> dict:
    t0 = time.perf_counter()
    res = Compiler(CGRA(*fabric), resolve_options("deterministic-ci", jobs=1)).compile(dfg)
    map_s = time.perf_counter() - t0
    if not res.ok:
        raise RuntimeError(f"{name} did not map onto {fabric}: {res.reason}")
    prog = compile_program(res.mapping)
    inputs = {
        v: rng.uniform(-4, 4, (ITERS, STREAMS)).astype(np.float32).round(2)
        for v in dfg.nodes
        if dfg.ops[v] == "input"
    }

    compiled_before = compile_clock.seconds
    t0 = time.perf_counter()
    outs, trace = cgra_run(prog, inputs, ITERS, interpret=False)
    run_s = time.perf_counter() - t0
    compile_s = compile_clock.seconds - compiled_before
    trace = np.asarray(trace)   # cgra_run leaves it on the device

    # the same kernel again on streams and tables already on the device
    inj, active = build_injection(prog, inputs, ITERS)
    on_device = dataclasses.replace(
        prog, **{f: jax.device_put(getattr(prog, f))
                 for f in PROGRAM_TABLES}
    )
    inj, active = jax.device_put(inj), jax.device_put(active)
    jax.block_until_ready(cgra_launch(on_device, inj, active))
    t0 = time.perf_counter()
    jax.block_until_ready(cgra_launch(on_device, inj, active))
    execute_s = time.perf_counter() - t0
    del inj, active, on_device

    _, want = cgra_sim_reference(prog, inputs, ITERS)
    check_trace(prog, trace, want)
    for lane in rng.choice(STREAMS, SAMPLED_STREAMS, replace=False):
        ref = interpret_dfg(
            dfg, {v: [float(x) for x in inputs[v][:, lane]] for v in inputs}, ITERS
        )
        for v, stream in ref.items():
            np.testing.assert_allclose(
                outs[v][:, lane], np.asarray(stream, np.float32), rtol=1e-6, atol=1e-6,
                err_msg=f"{name} {fabric}: store {v}, stream {lane} vs interpret_dfg",
            )

    tables = sum(getattr(prog, f).nbytes for f in PROGRAM_TABLES)
    cycles = trace.shape[0]
    # what cgra_run sends: the input rows, their cycles and PEs, and active
    staged = stage_injection(prog, inputs, ITERS).nbytes
    return {
        "kernel": name,
        "fabric": f"{fabric[0]}x{fabric[1]}",
        "ii": prog.ii,
        "ring": prog.ring,
        "pes": prog.num_pes,
        "streams": STREAMS,
        "iterations": ITERS,
        "cycles": cycles,
        "map_s": map_s,
        "compile_s": compile_s,
        "run_s": run_s,
        "execute_s": execute_s,
        "bytes_to_device": tables + staged,
        # what cgra_run brings back: the store rows
        "bytes_from_device": sum(o.nbytes for o in outs.values()),
        "match": "trace == cgra_sim_reference, sampled stores == interpret_dfg",
    }


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    compile_clock = CompileClock()
    rng = np.random.default_rng(SEED)
    dfgs = {**load_suite(list(KERNELS)), "opcover": opcode_cover_dfg()}
    print(f"smoke timings, not metrics; compile cache {cache}", flush=True)
    for fabric in FABRICS:
        for name, dfg in dfgs.items():
            row = run_phase(name, dfg, fabric, compile_clock, rng)
            row["peak_bytes_in_use"] = dev.memory_stats()["peak_bytes_in_use"]
            print(json.dumps(row), flush=True)
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
