"""Pallas TPU kernel: fused flash attention for the LM model zoo.

Supports the attention variants the assigned architectures need:

  * causal masking (decoder LMs)
  * sliding-window masking (gemma2 local layers, hymba SWA)
  * logit soft-capping (gemma2: s <- cap * tanh(s / cap))
  * GQA via a q-heads-per-kv-head group factor

Standard online-softmax tiling: grid (batch*q_heads, q blocks, kv blocks) with
the kv dimension innermost/sequential; running max / denominator / accumulator
live in VMEM scratch in f32. Block shapes default to (128, 128) so the
q-block x d and kv-block x d tiles are MXU-aligned.

The tests validate the kernel on the CPU with interpret=True against
ref.reference_attention; it runs compiled on a TPU by default. The model
zoo uses the pure-jnp reference by default (portable + SPMD-partitionable);
this kernel is the TPU hot-path drop-in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,      # [1, bq, d]
    k_ref,      # [1, bkv, d]
    v_ref,      # [1, bkv, d]
    o_ref,      # [1, bq, d]
    m_ref,      # [bq, 128] scratch (running max, lane-broadcast)
    l_ref,      # [bq, 128] scratch (running denominator)
    acc_ref,    # [bq, d]   scratch (weighted value accumulator)
    *,
    sm_scale: float,
    causal: bool,
    window: int | None,
    softcap: float | None,
    block_q: int,
    block_kv: int,
    kv_steps: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]                            # [bq, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)        # [bq, 1]
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (all NEG_INF): exp(NEG_INF - NEG_INF) would be 1
    safe = m_new > NEG_INF / 2
    p = jnp.where(safe, jnp.exp(s - m_new), 0.0)
    alpha = jnp.where(safe, jnp.exp(m_prev - m_new), 0.0)

    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "softcap", "sm_scale", "block_q", "block_kv",
        "interpret",
    ),
)
def flash_attention(
    q: jax.Array,   # [B, Hq, S, D]
    k: jax.Array,   # [B, Hkv, S, D]
    v: jax.Array,   # [B, Hkv, S, D]
    *,
    sm_scale: float | None = None,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, hq, s_len, d = q.shape
    _, hkv, _, _ = k.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    group = hq // hkv
    if sm_scale is None:
        sm_scale = d ** -0.5
    bq = min(block_q, s_len)
    bkv = min(block_kv, s_len)
    if s_len % bq or s_len % bkv:
        raise ValueError(f"seq len {s_len} not divisible by blocks {bq},{bkv}")
    q_steps = s_len // bq
    kv_steps = s_len // bkv

    qf = q.reshape(b * hq, s_len, d)
    kf = k.reshape(b * hkv, s_len, d)
    vf = v.reshape(b * hkv, s_len, d)

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale, causal=causal, window=window, softcap=softcap,
        block_q=bq, block_kv=bkv, kv_steps=kv_steps,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, q_steps, kv_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, qi, ki, grp=group: (h // grp, ki, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, qi, ki, grp=group: (h // grp, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, qi, ki: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, s_len, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, s_len, d)
