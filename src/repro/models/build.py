"""Decoder-LM assembly for dense/MoE families + dispatch to special families.

Layer stacks are scanned (params stacked on a leading layer axis, lax.scan
over them, jax.checkpoint remat inside) so full-size configs lower to compact
HLO for the 512-device dry-run. Per-layer heterogeneity that fits in arrays
(sliding-window sizes) rides along as scan inputs; structural heterogeneity
(dense-vs-MoE prefix layers) becomes separate stacks.

Distribution: attention/MLP math is plain jnp — XLA SPMD partitions it from
the parameter/activation shardings installed by sharding/rules.py. The MoE FFN
is a shard_map island (explicit EP + psum, models/moe.py) when a mesh is
given; single-device otherwise.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import attention as attn_mod
from .api import ArchConfig, ModelSpec
from .attention import (
    KVCache, MLACache, gqa_attention, gqa_init, make_kv_cache, make_mla_cache,
    mla_attention, mla_init,
)
from .layers import (
    cross_entropy_loss, dense_param, embed_param, geglu_mlp, gelu_mlp,
    gelu_mlp_init, rms_norm, softcap, swiglu_mlp, swiglu_mlp_init,
)
from .moe import moe_ffn, moe_init

P = jax.sharding.PartitionSpec


# ------------------------------------------------------------------ blocks

def block_init(rng, cfg: ArchConfig, kind: str) -> dict:
    ks = jax.random.split(rng, 4)
    d, dtype = cfg.d_model, cfg.dtype
    p: dict = {"attn_norm": jnp.zeros((d,), dtype)}
    if cfg.mla is not None:
        p["attn"] = mla_init(ks[0], cfg, dtype)
    else:
        p["attn"] = gqa_init(ks[0], cfg, dtype)
    p["ffn_norm"] = jnp.zeros((d,), dtype)
    if kind == "moe":
        p["moe"] = moe_init(ks[1], cfg, dtype)
    elif cfg.mlp_kind == "gelu":
        p["mlp"] = gelu_mlp_init(ks[1], d, cfg.d_ff, dtype)
    else:
        p["mlp"] = swiglu_mlp_init(ks[1], d, cfg.d_ff, dtype)
    if cfg.sandwich_norm:
        p["post_attn_norm"] = jnp.zeros((d,), dtype)
        p["post_ffn_norm"] = jnp.zeros((d,), dtype)
    return p


def block_apply(
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    cfg: ArchConfig,
    *,
    kind: str,
    window=None,
    prefix_len=None,
    cache=None,
    mesh=None,
    data_axes=("data",),
    model_axis="model",
):
    h = rms_norm(x, p["attn_norm"])
    if cfg.mla is not None:
        a, new_cache = mla_attention(p["attn"], h, positions, cfg, cache=cache)
    else:
        a, new_cache = gqa_attention(
            p["attn"], h, positions, cfg, window=window, cache=cache,
            prefix_len=prefix_len,
        )
    if cfg.sandwich_norm:
        a = rms_norm(a, p["post_attn_norm"])
    x = x + a

    h = rms_norm(x, p["ffn_norm"])
    aux = jnp.zeros((), jnp.float32)
    if kind == "moe":
        if mesh is not None:
            ep_mode = cfg.ep_over_data
            if ep_mode:
                # serving EP: experts over every axis, activations replicated
                ep_axes = (*data_axes, model_axis)
                x_spec, out_spec = P(), P()
                reduce_axes = ()
            else:
                ep_axes = model_axis
                x_spec = out_spec = P(data_axes, None, None)
                reduce_axes = data_axes
            if cfg.activation_constraints and not ep_mode:
                h = jax.lax.with_sharding_constraint(
                    h, jax.sharding.NamedSharding(mesh, x_spec)
                )
            moe_fn = functools.partial(moe_ffn, cfg=cfg, model_axis=ep_axes)

            def wrapped(params, hx):
                out, aux_l = moe_fn(params, hx)
                if reduce_axes:
                    aux_l = jax.lax.pmean(aux_l, reduce_axes)
                return out, aux_l

            specs_in = (
                {
                    "router": P(),
                    "expert_gate": P(ep_axes, None, None),
                    "expert_up": P(ep_axes, None, None),
                    "expert_down": P(ep_axes, None, None),
                    **(
                        {
                            "shared_gate": P(None, ep_axes),
                            "shared_up": P(None, ep_axes),
                            "shared_down": P(ep_axes, None),
                        }
                        if "shared_gate" in p["moe"]
                        else {}
                    ),
                },
                x_spec,
            )
            f, aux = jax.shard_map(
                wrapped, mesh=mesh, in_specs=specs_in,
                out_specs=(out_spec, P()),
            )(p["moe"], h)
        else:
            f, aux = moe_ffn(p["moe"], h, cfg, model_axis=None)
    elif cfg.mlp_kind == "gelu":
        f = gelu_mlp(p["mlp"], h)
    elif cfg.mlp_kind == "geglu":
        f = geglu_mlp(p["mlp"], h)
    else:
        f = swiglu_mlp(p["mlp"], h)
    if cfg.sandwich_norm:
        f = rms_norm(f, p["post_ffn_norm"])
    return x + f, new_cache, aux


# ------------------------------------------------------------- layer stacks

def stack_params(per_layer: list[dict]) -> dict:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)


def layer_windows(cfg: ArchConfig, num_layers: int, offset: int = 0) -> np.ndarray:
    """Per-layer sliding window (0 = global), as a scannable int32 array."""
    w = np.zeros(num_layers, np.int32)
    if cfg.window_pattern == "alternating" and cfg.sliding_window:
        for i in range(num_layers):
            if (i + offset) % 2 == 0:
                w[i] = cfg.sliding_window
    elif cfg.window_pattern == "hymba" and cfg.sliding_window:
        w[:] = cfg.sliding_window
        for g in (0, num_layers // 2, num_layers - 1):
            w[g] = 0
    return w


def apply_stack(
    stack: dict,
    windows: jax.Array,
    x: jax.Array,
    positions: jax.Array,
    cfg: ArchConfig,
    *,
    kind: str,
    caches=None,
    prefix_len=None,
    mesh=None,
    data_axes=("data",),
):
    """Scan (or unrolled loop) over a homogeneous layer stack."""
    num_layers = windows.shape[0]

    def body(carry, layer):
        xc, aux_acc = carry
        p_l, w_l, cache_l = layer
        out, new_cache, aux = block_apply(
            p_l, xc, positions, cfg, kind=kind, window=w_l, cache=cache_l,
            prefix_len=prefix_len, mesh=mesh, data_axes=data_axes,
        )
        if mesh is not None and cfg.activation_constraints:
            out = jax.lax.with_sharding_constraint(
                out, jax.sharding.NamedSharding(mesh, P(data_axes, None, None))
            )
        return (out, aux_acc + aux), new_cache

    if cfg.scan_layers:
        wrapped = jax.checkpoint(body) if cfg.remat else body
        (x, aux), new_caches = jax.lax.scan(
            wrapped, (x, jnp.zeros((), jnp.float32)), (stack, windows, caches)
        )
    else:
        wrapped = jax.checkpoint(body) if (cfg.remat and caches is None) else body
        aux = jnp.zeros((), jnp.float32)
        new_list = []
        for i in range(num_layers):
            p_l = jax.tree.map(lambda a: a[i], stack)
            cache_l = jax.tree.map(lambda a: a[i], caches) if caches is not None else None
            (x, aux), nc = wrapped((x, aux), (p_l, windows[i], cache_l))
            new_list.append(nc)
        new_caches = (
            jax.tree.map(lambda *xs: jnp.stack(xs), *new_list)
            if new_list and new_list[0] is not None
            else None
        )
    return x, aux, new_caches


# ----------------------------------------------------------- decoder LM

def _lm_init(rng, cfg: ArchConfig):
    ks = jax.random.split(rng, 8)
    n_dense = cfg.num_dense_layers if cfg.moe else cfg.num_layers
    n_moe = cfg.num_layers - n_dense if cfg.moe else 0
    params: dict = {
        "embed": embed_param(ks[0], cfg.vocab, cfg.d_model, cfg.dtype),
        "final_norm": jnp.zeros((cfg.d_model,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_param(ks[1], cfg.d_model, cfg.vocab, cfg.dtype)
    if n_dense:
        params["dense_stack"] = stack_params(
            [block_init(k, cfg, "dense") for k in jax.random.split(ks[2], n_dense)]
        )
    if n_moe:
        params["moe_stack"] = stack_params(
            [block_init(k, cfg, "moe") for k in jax.random.split(ks[3], n_moe)]
        )
    if cfg.mtp:
        params["mtp_proj"] = dense_param(ks[4], 2 * cfg.d_model, cfg.d_model, cfg.dtype)
        params["mtp_block"] = block_init(ks[5], cfg, "dense")
        params["mtp_norm"] = jnp.zeros((cfg.d_model,), cfg.dtype)
    if cfg.num_meta_tokens:
        params["meta_tokens"] = (
            jax.random.normal(ks[6], (cfg.num_meta_tokens, cfg.d_model), jnp.float32) * 0.02
        ).astype(cfg.dtype)
    return params


def _stacks(cfg: ArchConfig):
    n_dense = cfg.num_dense_layers if cfg.moe else cfg.num_layers
    n_moe = cfg.num_layers - n_dense if cfg.moe else 0
    out = []
    if n_dense:
        out.append(("dense_stack", "dense", n_dense, 0))
    if n_moe:
        out.append(("moe_stack", "moe", n_moe, n_dense))
    return out


def _embed(params, cfg, tokens):
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = (x.astype(jnp.float32) * cfg.d_model**0.5).astype(x.dtype)
    return x


def _unembed(params, cfg, x):
    x = rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    return softcap(logits, cfg.final_softcap)


def lm_forward(
    params, cfg: ArchConfig, tokens, *, caches=None, positions=None,
    mesh=None, data_axes=("data",), prefix_embeds=None,
):
    """Shared trunk: embeddings -> stacks -> hidden states (+ new caches)."""
    b, s = tokens.shape
    x = _embed(params, cfg, tokens)
    # prefixes (meta tokens / frontend embeds) are prepended on parallel
    # passes (train & prefill, s > 1); during decode they already sit in cache
    if params.get("meta_tokens") is not None and s > 1:
        meta = jnp.broadcast_to(
            params["meta_tokens"][None], (b, cfg.num_meta_tokens, cfg.d_model)
        ).astype(x.dtype)
        x = jnp.concatenate([meta, x], axis=1)
    if prefix_embeds is not None and s > 1:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    s_eff = x.shape[1]
    if positions is None:
        positions = jnp.arange(s_eff)
    prefix_len = (s_eff - s) if (cfg.prefix_lm and s_eff > s) else None
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: dict = {}
    for stack_name, kind, n_layers, offset in _stacks(cfg):
        windows = jnp.asarray(layer_windows(cfg, n_layers, offset))
        stack_caches = caches.get(stack_name) if caches is not None else None
        x, aux, nc = apply_stack(
            params[stack_name], windows, x, positions, cfg, kind=kind,
            caches=stack_caches, prefix_len=prefix_len, mesh=mesh,
            data_axes=data_axes,
        )
        aux_total += aux
        new_caches[stack_name] = nc
    return x, aux_total, (new_caches if caches is not None else None)


def lm_loss(params, cfg: ArchConfig, batch, *, mesh=None, data_axes=("data",)):
    tokens, labels = batch["tokens"], batch["labels"]
    prefix = batch.get("prefix_embeds")
    x, aux, _ = lm_forward(
        params, cfg, tokens, mesh=mesh, data_axes=data_axes, prefix_embeds=prefix,
    )
    # strip any prefix (meta tokens / frontend embeds) before the LM head
    strip = x.shape[1] - tokens.shape[1]
    if strip:
        x = x[:, strip:]
    logits = _unembed(params, cfg, x)
    loss = cross_entropy_loss(logits, labels)
    metrics = {"ce": loss, "aux": aux}
    if cfg.mtp:
        h = x[:, :-1]
        nxt = _embed(params, cfg, tokens[:, 1:])
        m_in = jnp.concatenate([h, nxt], axis=-1) @ params["mtp_proj"]
        m_in = rms_norm(m_in, params["mtp_norm"])
        pos = jnp.arange(m_in.shape[1])
        m_out = block_apply(
            params["mtp_block"], m_in, pos, cfg, kind="dense",
            mesh=mesh, data_axes=data_axes,
        )[0]
        mtp_logits = _unembed(params, cfg, m_out)
        mtp_loss = cross_entropy_loss(mtp_logits[:, :-1], labels[:, 2:])
        loss = loss + cfg.mtp_weight * mtp_loss
        metrics["mtp"] = mtp_loss
    loss = loss + aux
    return loss, metrics


# ----------------------------------------------------------- serve paths

def lm_make_caches(params, cfg: ArchConfig, batch: int, cache_len: int):
    caches = {}
    for stack_name, kind, n_layers, _ in _stacks(cfg):
        if cfg.mla is not None:
            one = make_mla_cache(cfg, batch, cache_len, cfg.dtype)
        else:
            one = make_kv_cache(cfg, batch, cache_len, cfg.dtype)
        caches[stack_name] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_layers, *a.shape)), one
        )
    return caches


def lm_decode_step(
    params, cfg: ArchConfig, token, caches, pos, *, mesh=None, data_axes=("data",)
):
    """One decode step: token [B,1] + caches at absolute position `pos`."""
    positions = jnp.reshape(jnp.asarray(pos), (1,))
    x, _, new_caches = lm_forward(
        params, cfg, token, caches=caches, positions=positions,
        mesh=mesh, data_axes=data_axes,
    )
    logits = _unembed(params, cfg, x)[:, -1]
    return logits, new_caches


def lm_prefill(params, cfg: ArchConfig, tokens, cache_len, *, mesh=None,
               data_axes=("data",)):
    """Parallel prefill that also fills decode caches: the whole prompt's k/v
    block is written at cache offset 0 in one dynamic_update_slice per layer
    (positions = arange(s), so attention is causal within the prompt)."""
    caches = lm_make_caches(params, cfg, tokens.shape[0], cache_len)
    x, _, new_caches = lm_forward(
        params, cfg, tokens, caches=caches, mesh=mesh, data_axes=data_axes,
    )
    logits = _unembed(params, cfg, x)[:, -1]
    return logits, new_caches
