"""Mixture-of-Experts FFN with expert parallelism (DeepSeek-style).

Routing: top-k over router scores (softmax or sigmoid per config), optional
shared experts that always fire, capacity-bounded dispatch (tokens over
capacity are dropped — standard GShard/Switch semantics), plus a Switch-style
load-balance auxiliary loss.

Distribution (the EP design): expert weights are sharded over the 'model' mesh
axis; activations arrive replicated across 'model' (they are sharded over
'data'/'pod' only). Each model-shard computes *its* experts' contribution for
all local tokens via a sort-based capacity-buffer dispatch — entirely local
gathers/scatters — and one psum over 'model' combines routed + shared-expert
partial outputs. Compared to all-to-all EP this trades some redundant router
compute (replicated, negligible) for a single fused all-reduce that overlaps
with the shared-expert matmul; the a2a variant is evaluated in the §Perf
hillclimb. Under a single device (smoke tests) the same code runs with the
whole expert set local and the psum skipped.

Token->buffer slots are computed with the argsort/searchsorted rank trick so
no [tokens, experts] one-hot ever materialises — O(Tk log Tk) and shardable.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import dense_param


def moe_init(rng, cfg, dtype) -> dict:
    m = cfg.moe
    d, f = cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(rng, 8)
    e = m.num_experts
    p = {
        "router": dense_param(ks[0], d, e, jnp.float32),
        "expert_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32) / d**0.5).astype(dtype),
        "expert_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32) / d**0.5).astype(dtype),
        "expert_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32) / f**0.5).astype(dtype),
    }
    if m.num_shared > 0:
        fs = f * m.num_shared
        p["shared_gate"] = dense_param(ks[4], d, fs, dtype)
        p["shared_up"] = dense_param(ks[5], d, fs, dtype)
        p["shared_down"] = dense_param(ks[6], fs, d, dtype)
    return p


def _routing(params: dict, x_flat: jax.Array, cfg):
    """Top-k routing; identical (replicated) on every model shard."""
    m = cfg.moe
    logits = (x_flat.astype(jnp.float32)) @ params["router"]
    if m.score_fn == "sigmoid":           # deepseek-v3
        scores = jax.nn.sigmoid(logits)
    else:                                  # softmax (deepseek-moe-16b)
        scores = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(scores, m.top_k)      # [T, k]
    if m.normalize_gates:
        top_vals = top_vals / (top_vals.sum(-1, keepdims=True) + 1e-9)
    top_vals = top_vals * m.routed_scale
    # Switch-style load-balance aux loss
    e = m.num_experts
    density = jax.nn.one_hot(top_idx, e).sum(1).mean(0)       # frac routed / expert
    mean_prob = (scores / scores.sum(-1, keepdims=True)).mean(0)
    aux = e * jnp.sum(density * mean_prob) * m.aux_loss_coef
    return top_idx, top_vals.astype(jnp.float32), aux


def _dispatch_slots(expert_ids: jax.Array, capacity: int):
    """Rank of each assignment within its expert (sort-based, no one-hot)."""
    tk = expert_ids.shape[0]
    order = jnp.argsort(expert_ids)
    sorted_e = expert_ids[order]
    seg_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank_sorted = jnp.arange(tk) - seg_start
    slots = jnp.zeros(tk, jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    return slots, slots < capacity


def moe_ffn(
    params: dict,
    x: jax.Array,             # [batch_loc, seq, d] (replicated over 'model')
    cfg,
    *,
    model_axis: str | None = None,   # inside shard_map: the EP psum axis
) -> tuple[jax.Array, jax.Array]:
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    x_flat = x.reshape(t, d)
    top_idx, gates, aux = _routing(params, x_flat, cfg)      # [T,k]

    e = m.num_experts
    if model_axis is not None:
        # model_axis may be a tuple (full-EP serving mode: experts sharded
        # over every mesh axis, weights stationary, activations replicated)
        axes = model_axis if isinstance(model_axis, tuple) else (model_axis,)
        n_shards, shard = 1, 0
        for a in axes:
            n_shards = n_shards * jax.lax.axis_size(a)
        for a in axes:
            shard = shard * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    else:
        n_shards, shard = 1, 0
    e_loc = params["expert_up"].shape[0]                     # E/shards (sharded in)
    capacity = max(8, int(t * m.top_k * m.capacity_factor) // e)

    flat_e = top_idx.reshape(-1)                             # [T*k]
    flat_gate = gates.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), m.top_k)
    slots, in_cap = _dispatch_slots(flat_e, capacity)

    local = (flat_e // e_loc) == shard
    valid = (local & in_cap).astype(jnp.float32)
    lin = ((flat_e % e_loc) * capacity + slots).astype(jnp.int32)
    lin = jnp.where(valid > 0, lin, 0)

    # dispatch: [E_loc*C, d] buffers via unique-slot scatter-add
    buf = jnp.zeros((e_loc * capacity, d), x.dtype)
    buf = buf.at[lin].add(x_flat[flat_tok] * valid[:, None].astype(x.dtype))
    buf = buf.reshape(e_loc, capacity, d)

    # batched expert SwiGLU (MXU-friendly [E_loc] batched matmuls)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["expert_gate"]))
    u = jnp.einsum("ecd,edf->ecf", buf, params["expert_up"])
    h = jnp.einsum("ecf,efd->ecd", g * u, params["expert_down"])
    h_flat = h.reshape(e_loc * capacity, d)

    # combine: gather back, weight by gate, accumulate per token
    contrib = h_flat[lin] * (flat_gate * valid)[:, None].astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[flat_tok].add(contrib)

    if m.num_shared > 0:
        # shared expert(s): d_ff sharded over 'model' => partial sums psum'd
        sg = jax.nn.silu(x_flat @ params["shared_gate"])
        su = x_flat @ params["shared_up"]
        out = out + (sg * su) @ params["shared_down"]

    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)
    return out.reshape(b, s, d), aux
