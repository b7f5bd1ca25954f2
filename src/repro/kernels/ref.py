"""Pure-jnp oracles for the Pallas kernels.

``cgra_sim_reference`` executes the same compiled program as the cgra_sim
kernel but with a structurally different method: integer-indexed reads from
the full value trace (no ring buffer, no route pairs or shifts), so it
validates the kernel's routing/ring logic rather than sharing it. Scalar semantics are the
same ALU as core.simulate (bit-identical in f32 by construction).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.simulate import OPCODES

from .ops import CGRAProgram, build_injection, num_cycles

_F = np.float32


def reference_attention(
    q: jax.Array,   # [B, Hq, S, D]
    k: jax.Array,   # [B, Hkv, S, D]
    v: jax.Array,   # [B, Hkv, S, D]
    *,
    sm_scale: float | None = None,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
) -> jax.Array:
    """Direct-softmax oracle for kernels/flash_attention.py (f32 math)."""
    b, hq, s_len, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    if sm_scale is None:
        sm_scale = d ** -0.5
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * sm_scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = jnp.arange(s_len)[:, None]
    k_pos = jnp.arange(s_len)[None, :]
    mask = jnp.ones((s_len, s_len), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows: softmax of all -1e30 is uniform garbage; zero them
    p = jnp.where(mask.any(-1)[:, None], p, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _alu_np(op_id: int, a: np.ndarray, b: np.ndarray, imm: float, inj: np.ndarray) -> np.ndarray:
    names = {v: k for k, v in OPCODES.items()}
    op = names[op_id]
    ia = np.abs(a).astype(np.int64) & 0xFFFF
    ib = np.abs(b).astype(np.int64) & 0xFFFF
    sh = ib % 8
    if op == "input":
        return inj
    if op == "const":
        return np.full_like(a, _F(imm))
    if op in ("load", "store", "mov"):
        return a
    if op == "phi":
        return a + b
    if op == "add":
        return a + b
    if op == "sub":
        return a - b
    if op == "mul":
        return a * b
    if op == "div":
        return np.where(b != 0, a / np.where(b != 0, b, 1.0), _F(0)).astype(_F)
    if op == "and":
        return (ia & ib).astype(_F)
    if op == "or":
        return (ia | ib).astype(_F)
    if op == "xor":
        return (ia ^ ib).astype(_F)
    if op == "shl":
        return ((ia << sh) & 0xFFFF).astype(_F)
    if op == "shr":
        return (ia >> sh).astype(_F)
    if op == "min":
        return np.minimum(a, b)
    if op == "max":
        return np.maximum(a, b)
    if op == "neg":
        return -a
    if op == "not":
        return (~ia & 0xFFFF).astype(_F)
    if op == "abs":
        return np.abs(a)
    if op == "cmp":
        return (a > b).astype(_F)
    raise ValueError(op)


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
            val = _alu_np(
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
