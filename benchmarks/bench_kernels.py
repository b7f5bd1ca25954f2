"""Kernel micro-benchmarks in Pallas interpret mode on the CPU.

These rows time the interpreter and the numpy reference, not a device: they
are correctness-path timings. ``chip_smoke.py`` runs the compiled kernel on
a TPU.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _time_call(fn, *args, iters=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run() -> list[dict]:
    from repro.api import Compiler, resolve_options
    from repro.core import CGRA, running_example
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ops import cgra_run, compile_program
    from repro.kernels.ref import cgra_sim_reference, reference_attention

    rows = []

    # cgra_sim: mapped running example, batch sweep
    comp = Compiler(CGRA(2, 2), resolve_options("fast", time_budget_s=30.0))
    res = comp.compile(running_example())
    prog = compile_program(res.mapping)
    rng = np.random.default_rng(0)
    for batch in (64, 256):
        inputs = {
            v: rng.uniform(-2, 2, (8, batch)).astype(np.float32)
            for v in res.mapping.dfg.nodes
            if res.mapping.dfg.ops[v] == "input"
        }
        us = _time_call(
            lambda: cgra_run(prog, inputs, 8, batch_tile=64, interpret=True)[0]
        )
        rows.append({"name": f"cgra_sim_pallas_b{batch}", "us_per_call": round(us, 1),
                     "derived": f"II={prog.ii},ring={prog.ring}"})
        us_ref = _time_call(lambda: cgra_sim_reference(prog, inputs, 8)[0])
        rows.append({"name": f"cgra_sim_ref_b{batch}", "us_per_call": round(us_ref, 1),
                     "derived": ""})

    # flash attention vs reference
    q = jnp.asarray(rng.standard_normal((1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    us = _time_call(lambda: flash_attention(q, k, v, interpret=True))
    rows.append({"name": "flash_attention_interp_s256", "us_per_call": round(us, 1),
                 "derived": "b1,h4/2,d64"})
    us = _time_call(lambda: reference_attention(q, k, v))
    rows.append({"name": "attention_reference_s256", "us_per_call": round(us, 1),
                 "derived": ""})
    for r in rows:
        print(r, flush=True)
    return rows
