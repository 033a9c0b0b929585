"""Mixture-of-experts FFN with top-k routing.

Two execution paths sharing the same parameters:

- ``moe_apply_local``: single-device reference (smoke tests, CPU experiments).
  Sort-based dispatch: assignments sorted by expert, scattered into fixed
  per-expert capacity buffers (static shapes, drop-on-overflow), batched
  expert GEMMs, weighted scatter-add combine.

- ``moe_apply_ep``: expert-parallel shard_map path.  Activations are
  replicated over the "model" axis (as in Megatron TP blocks), each model
  shard owns E/ep experts and processes the tokens routed to *its* experts
  only — dispatch needs no collective at all; the combine is one psum over
  "model", the same collective a dense TP FFN needs.  Per-chip buffers are
  (E_local, C, d) with C = T_local·top_k/E·capacity_factor.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import MoEConfig
from . import layers


def moe_init(key, d_model: int, cfg: MoEConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    params = {
        "router": layers.dense_init(ks[0], (d_model, cfg.n_experts), ("embed", "expert"), dtype=jnp.float32),
        "wg": layers.dense_init(ks[1], (cfg.n_experts, d_model, cfg.d_expert), ("expert", "embed", "mlp"), dtype=dtype),
        "wu": layers.dense_init(ks[2], (cfg.n_experts, d_model, cfg.d_expert), ("expert", "embed", "mlp"), dtype=dtype),
        "wd": layers.dense_init(ks[3], (cfg.n_experts, cfg.d_expert, d_model), ("expert", "mlp", "embed"), dtype=dtype),
    }
    if cfg.n_shared_experts:
        params["shared"] = layers.mlp_init(
            ks[4], d_model, cfg.d_expert * cfg.n_shared_experts, "swiglu", dtype
        )
    return params


def _route(params, x, cfg: MoEConfig):
    """Top-k routing with normalized combine weights + aux load-balance loss."""
    logits = x.astype(jnp.float32) @ params["router"]           # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.top_k)              # (T, k)
    top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-9)
    # GShard aux loss: E * mean_e(frac_tokens_e * mean_prob_e)
    t = x.shape[0]
    one_hot = jax.nn.one_hot(top_e[:, 0], cfg.n_experts)        # primary expert
    frac = one_hot.mean(axis=0)
    aux = cfg.n_experts * jnp.sum(frac * probs.mean(axis=0))
    return top_e, top_p, aux


def _capacity(t: int, cfg: MoEConfig, factor: float = 1.25) -> int:
    c = int(t * cfg.top_k / cfg.n_experts * factor) + 1
    return max(4, (c + 3) // 4 * 4)


def _expert_ffn(wg, wu, wd, xin):
    """Batched SwiGLU over (E, C, d) with (E, d, f)/(E, f, d) weights."""
    g = jnp.einsum("ecd,edf->ecf", xin, wg)
    u = jnp.einsum("ecd,edf->ecf", xin, wu)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)


def _dispatch_compute_combine(
    x, top_e, top_p, wg, wu, wd, n_experts: int, expert_offset, capacity: int
):
    """Sort-based dispatch for the expert block [offset, offset+E_block).

    Static shapes throughout; overflow beyond ``capacity`` is dropped (the
    standard GShard capacity policy).
    """
    t, d = x.shape
    k = top_e.shape[1]
    e_block = wg.shape[0]
    n_slots = e_block * capacity
    e_flat = top_e.reshape(-1) - expert_offset                   # (T*k,)
    tok_flat = jnp.repeat(jnp.arange(t), k)
    w_flat = top_p.reshape(-1)
    mine = (e_flat >= 0) & (e_flat < e_block)
    # sort assignments by (expert, arrival) — stable so token order persists
    sort_key = jnp.where(mine, e_flat, e_block)                  # foreign last
    order = jnp.argsort(sort_key, stable=True)
    e_sorted = sort_key[order]
    tok_sorted = tok_flat[order]
    w_sorted = w_flat[order]
    # position of each assignment within its expert group
    counts = jax.ops.segment_sum(
        jnp.ones_like(e_sorted), e_sorted, num_segments=e_block + 1
    )
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k) - starts[e_sorted]
    keep = (e_sorted < e_block) & (pos < capacity)
    slot = jnp.where(keep, e_sorted * capacity + pos, n_slots)
    # slot -> (token, weight) maps, built with small int/f32 scatters; the
    # big (T·k, d) per-ASSIGNMENT gather/scatter of the naive formulation
    # (~13x larger than the capacity buffers under EP) never materializes.
    slot_tok = jnp.zeros((n_slots + 1,), jnp.int32).at[slot].set(
        tok_sorted.astype(jnp.int32), mode="drop"
    )[:n_slots]
    slot_w = jnp.zeros((n_slots + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, w_sorted, 0.0), mode="drop"
    )[:n_slots]
    xin = jnp.take(x, slot_tok, axis=0)          # (E_block·C, d); empty slots
    out_buf = _expert_ffn(wg, wu, wd, xin.reshape(e_block, capacity, d))
    out_flat = out_buf.reshape(n_slots, d) * slot_w.astype(x.dtype)[:, None]
    return jax.ops.segment_sum(out_flat, slot_tok, num_segments=t)


def moe_apply_local(params, x, cfg: MoEConfig, capacity_factor: float = None):
    """Reference single-shard MoE. x: (T, d) -> (T, d), aux loss."""
    t, d = x.shape
    top_e, top_p, aux = _route(params, x, cfg)
    cap = _capacity(t, cfg, capacity_factor or cfg.capacity_factor)
    y = _dispatch_compute_combine(
        x, top_e, top_p, params["wg"], params["wu"], params["wd"],
        cfg.n_experts, 0, cap,
    )
    if "shared" in params:
        y = y + layers.mlp_apply(params["shared"], x, "swiglu")
    return y, aux


def make_moe_fn(mesh, cfg: MoEConfig, batch_axes, ep_axis: str = "model",
                capacity_factor: float = None, scatter_tokens: bool = False):
    """Sharded-MoE closure for transformer._mlp_block: experts live on
    ``ep_axis``, tokens shard on ``batch_axes`` and replicate over ep_axis
    (dispatch needs NO collective).

    ``scatter_tokens``: combine with psum_scatter instead of psum — the
    output lands TOKEN-SHARDED over ep_axis, which (a) halves the combine's
    link bytes (reduce-scatter vs ring all-reduce) and (b) is exactly the
    sequence-sharded residual layout the surrounding layers use, removing a
    reshard.  The shared experts then also run once per token instead of
    ep-times redundantly.  Requires tokens divisible by the ep size (train
    shapes; decode keeps the plain psum)."""
    from jax.sharding import PartitionSpec as P

    bspec = tuple(batch_axes) if batch_axes else None
    all_axes = tuple(mesh.axis_names)

    def body(p, x_local):
        y, aux = moe_apply_ep(
            p, x_local, cfg, ep_axis, capacity_factor,
            scatter_tokens=scatter_tokens,
        )
        aux = jax.lax.pmean(aux, all_axes)   # fully replicated scalar
        return y, aux

    out0 = (
        (tuple(batch_axes) + (ep_axis,)) if scatter_tokens
        else bspec
    )

    def moe_fn(params, x):
        in_specs = (
            {
                k: (P(ep_axis, None, None) if k in ("wg", "wu", "wd")
                    else jax.tree.map(lambda _: P(), v) if isinstance(v, dict)
                    else P())
                for k, v in params.items()
            },
            P(bspec, None),
        )
        out_specs = (P(out0, None), P())
        return jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )(params, x)

    return moe_fn


def moe_apply_ep(params, x, cfg: MoEConfig, ep_axis: str,
                 capacity_factor: float = None, scatter_tokens: bool = False):
    """Expert-parallel body — call inside shard_map with experts sharded on
    ``ep_axis`` and x replicated over it.  One psum (or psum_scatter, see
    make_moe_fn) over ep_axis total."""
    t, d = x.shape
    e_local = params["wg"].shape[0]
    rank = jax.lax.axis_index(ep_axis)
    my = rank * e_local
    top_e, top_p, aux = _route(params, x, cfg)
    cap = _capacity(t, cfg, capacity_factor or cfg.capacity_factor)
    y = _dispatch_compute_combine(
        x, top_e, top_p, params["wg"], params["wu"], params["wd"],
        cfg.n_experts, my, cap,
    )
    if scatter_tokens:
        y = jax.lax.psum_scatter(y, ep_axis, scatter_dimension=0, tiled=True)
        if "shared" in params:
            chunk = y.shape[0]
            x_loc = jax.lax.dynamic_slice_in_dim(x, rank * chunk, chunk, 0)
            y = y + layers.mlp_apply(params["shared"], x_loc, "swiglu")
    else:
        y = jax.lax.psum(y, ep_axis)
        if "shared" in params:
            y = y + layers.mlp_apply(params["shared"], x, "swiglu")
    aux = jax.lax.pmean(aux, ep_axis)
    return y, aux
