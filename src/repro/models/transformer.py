"""Decoder/encoder transformer LM: init, encode (train/prefill), decode.

Pure-functional, MaxText-style:

- layer parameters are STACKED (leading ``layers`` axis) and iterated with
  ``lax.scan`` — keeps the HLO size O(1) in depth (essential for 80-layer
  dry-run compiles) and composes with ``jax.checkpoint`` remat;
- every init returns (params, specs) where specs carry logical axis names
  (``embed``/``heads``/``kv_heads``/``mlp``/``vocab``/``expert``/``layers``)
  mapped to mesh axes by ``repro.distributed.sharding``;
- MoE layers run expert-parallel via shard_map when ``ep_axis`` is given
  (see ``repro.models.moe``); dense-prefix layers (Moonlight's first dense
  block) are unrolled separately from the scanned homogeneous stack;
- decode keeps a per-layer KV cache; the attention core is pluggable so the
  distributed sequence-parallel flash-decode (``repro.distributed``) can be
  swapped in for the local reference.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import LMConfig
from . import layers, moe as moe_lib


def _dtype(cfg: LMConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def padded_vocab(cfg: LMConfig) -> int:
    """Vocab rows padded to a shardable multiple (512 covers every mesh axis
    combination used here); padded logits are masked in lm_logits.  Standard
    TPU practice — pjit rejects uneven input shardings."""
    return (cfg.vocab_size + 511) // 512 * 512


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _norm_init(cfg: LMConfig, d: int):
    if cfg.norm == "layernorm":
        return {
            "w": layers.ones_init((d,), ("embed",)),
            "b": layers.zeros_init((d,), ("embed",)),
        }
    return {"w": layers.ones_init((d,), ("embed",))}


def _apply_norm(cfg: LMConfig, p, x):
    if cfg.norm == "layernorm":
        return layers.layernorm(x, p["w"], p["b"])
    return layers.rmsnorm(x, p["w"], cfg.rms_eps)


def _layer_init(key, cfg: LMConfig, use_moe: bool):
    hd = cfg.resolved_head_dim
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    dt = _dtype(cfg)
    attn: Dict[str, Any] = {
        "wq": layers.dense_init(ks[0], (d, cfg.n_heads, hd), ("embed", "heads", "head_dim"), dtype=dt),
        "wk": layers.dense_init(ks[1], (d, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim"), dtype=dt),
        "wv": layers.dense_init(ks[2], (d, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim"), dtype=dt),
        "wo": layers.dense_init(ks[3], (cfg.n_heads, hd, d), ("heads", "head_dim", "embed"), dtype=dt),
    }
    if cfg.qkv_bias:
        attn["bq"] = layers.zeros_init((cfg.n_heads, hd), ("heads", "head_dim"), dtype=dt)
        attn["bk"] = layers.zeros_init((cfg.n_kv_heads, hd), ("kv_heads", "head_dim"), dtype=dt)
        attn["bv"] = layers.zeros_init((cfg.n_kv_heads, hd), ("kv_heads", "head_dim"), dtype=dt)
    if cfg.qk_norm:
        attn["q_norm"] = layers.ones_init((hd,), ("head_dim",))
        attn["k_norm"] = layers.ones_init((hd,), ("head_dim",))
    out = {
        "attn": attn,
        "ln1": _norm_init(cfg, d),
        "ln2": _norm_init(cfg, d),
    }
    if use_moe:
        out["moe"] = moe_lib.moe_init(ks[4], d, cfg.moe, dtype=dt)
    else:
        d_ff = cfg.d_ff if cfg.moe is None else (cfg.moe.d_ff_dense or cfg.d_ff)
        out["mlp"] = layers.mlp_init(ks[4], d, d_ff, cfg.act, dtype=dt)
        if cfg.mlp_bias:
            out["mlp"]["bu"] = layers.zeros_init((d_ff,), ("mlp",), dtype=dt)
            out["mlp"]["bd"] = layers.zeros_init((d,), ("embed",), dtype=dt)
    return layers.split_tree(out)


def init_lm(key, cfg: LMConfig):
    """Returns (params, specs) with stacked scanned layers."""
    dt = _dtype(cfg)
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    n_prefix = cfg.moe.first_k_dense if cfg.moe is not None else 0
    n_scan = cfg.n_layers - n_prefix

    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    params["embed"], specs["embed"] = layers.dense_init(
        k_emb, (padded_vocab(cfg), cfg.d_model), ("vocab", "embed"), scale=0.02, dtype=dt
    )
    # dense-prefix layers (unrolled)
    if n_prefix:
        pk = jax.random.split(k_layers, n_prefix + 1)
        prefix = [_layer_init(pk[i], cfg, use_moe=False) for i in range(n_prefix)]
        params["prefix"] = [p for p, _ in prefix]
        specs["prefix"] = [s for _, s in prefix]
        k_layers = pk[-1]
    # scanned homogeneous stack
    scan_keys = jax.random.split(k_layers, n_scan)
    use_moe = cfg.moe is not None
    params["layers"] = jax.vmap(lambda k: _layer_init(k, cfg, use_moe)[0])(scan_keys)
    one_spec = _layer_init(scan_keys[0], cfg, use_moe)[1]
    specs["layers"] = jax.tree.map(
        lambda s: ("layers",) + s, one_spec, is_leaf=lambda s: isinstance(s, tuple)
    )
    params["final_norm"], specs["final_norm"] = layers.split_tree(
        {"n": _norm_init(cfg, cfg.d_model)}
    )
    params["final_norm"] = params["final_norm"]["n"]
    specs["final_norm"] = specs["final_norm"]["n"]
    if not cfg.tie_embeddings:
        params["lm_head"], specs["lm_head"] = layers.dense_init(
            k_head, (cfg.d_model, padded_vocab(cfg)), ("embed", "vocab"), scale=0.02, dtype=dt
        )
    return params, specs


# ---------------------------------------------------------------------------
# attention sub-block shared by encode/decode
# ---------------------------------------------------------------------------


def _project_qkv(cfg: LMConfig, attn, x, positions):
    q = jnp.einsum("...d,dhk->...hk", x, attn["wq"])
    k = jnp.einsum("...d,dhk->...hk", x, attn["wk"])
    v = jnp.einsum("...d,dhk->...hk", x, attn["wv"])
    if cfg.qkv_bias:
        q, k, v = q + attn["bq"], k + attn["bk"], v + attn["bv"]
    if cfg.qk_norm:
        q = layers.rmsnorm(q, attn["q_norm"], cfg.rms_eps)
        k = layers.rmsnorm(k, attn["k_norm"], cfg.rms_eps)
    q = layers.apply_rope(q, positions, cfg.rope_theta)
    k = layers.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mlp_block(cfg: LMConfig, layer_params, x2d, moe_fn):
    if "moe" in layer_params:
        fn = moe_fn if moe_fn is not None else (
            lambda p, x: moe_lib.moe_apply_local(p, x, cfg.moe)
        )
        return fn(layer_params["moe"], x2d)
    p = layer_params["mlp"]
    if cfg.mlp_bias:
        h = jax.nn.gelu(x2d @ p["wu"] + p["bu"], approximate=True)
        return h @ p["wd"] + p["bd"], jnp.zeros((), jnp.float32)
    return layers.mlp_apply(p, x2d, cfg.act), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# encode: training / prefill forward pass
# ---------------------------------------------------------------------------


def _constrain(h, act_spec):
    if act_spec is not None:
        h = jax.lax.with_sharding_constraint(h, act_spec)
    return h


def _encode_layer(cfg: LMConfig, moe_fn, attn_fn, act_spec, attn_spec, h, layer_params, positions, kv_mask):
    b, l, d = h.shape
    x = _apply_norm(cfg, layer_params["ln1"], h)
    q, k, v = _project_qkv(cfg, layer_params["attn"], x, positions)
    # inside the attention block activations shard by HEADS (Megatron TP);
    # the residual stream outside shards by sequence — GSPMD inserts the
    # boundary all-to-alls.
    q = _constrain(q, attn_spec)
    attn_out = attn_fn(q, k, v, kv_mask)
    attn_out = _constrain(attn_out, attn_spec)
    h = h + jnp.einsum("...hk,hkd->...d", attn_out, layer_params["attn"]["wo"])
    x2 = _apply_norm(cfg, layer_params["ln2"], h).reshape(b * l, d)
    ffn, aux = _mlp_block(cfg, layer_params, x2, moe_fn)
    # Megatron-style sequence sharding of the residual stream between layers:
    # the remat-saved per-layer carry shrinks by the model-axis size (86 GB ->
    # 5.4 GB/device on qwen1.5-110b train_4k); attention re-gathers KV only.
    return _constrain(h + ffn.reshape(b, l, d), act_spec), (k, v, aux)


def encode(
    params,
    tokens: jax.Array,                 # (B, L) int32
    cfg: LMConfig,
    *,
    positions: Optional[jax.Array] = None,
    kv_mask: Optional[jax.Array] = None,   # (B, L) valid-token mask
    moe_fn: Optional[Callable] = None,      # sharded MoE closure (repro.models.moe)
    q_chunk: int = 1024,
    return_kv: bool = False,
    act_spec=None,                          # PartitionSpec for the residual stream
    attn_spec=None,                         # PartitionSpec for (B, L, H, hd)
    attn_impl: str = "ref",                 # "ref" | "flash" (Pallas kernel)
    flash_block: Tuple[int, int] = (128, 128),
):
    """Full forward pass. Returns (hidden (B,L,d), aux_loss[, kv caches]).

    ``attn_impl='flash'`` routes the attention core through the Pallas
    flash-attention kernel.  ``kv_mask`` must then describe *trailing*
    padding only (valid tokens first) — it is collapsed to a per-example
    valid length that rides in SMEM; the CE pair tokenizer produces exactly
    this layout.
    """
    b, l = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
    h = _constrain(params["embed"][tokens].astype(_dtype(cfg)), act_spec)

    if attn_impl == "flash":
        from ..kernels.flash_attention.kernel import flash_attention

        def attn_fn(q, k, v, mask):
            return flash_attention(
                q, k, v, causal=cfg.causal,
                block_q=flash_block[0], block_k=flash_block[1],
                kv_lens=None if mask is None else mask.sum(-1).astype(jnp.int32),
            )
    elif attn_impl == "ref":
        def attn_fn(q, k, v, mask):
            return layers.attention_ref(
                q, k, v, causal=cfg.causal, q_chunk=q_chunk, kv_mask=mask
            )
    else:
        raise ValueError(f"unknown attn_impl '{attn_impl}' (ref|flash)")

    layer_fn = partial(_encode_layer, cfg, moe_fn, attn_fn, act_spec, attn_spec)
    if cfg.remat:
        layer_fn = jax.checkpoint(layer_fn, static_argnums=())

    aux_total = jnp.zeros((), jnp.float32)
    kvs = []
    for p in params.get("prefix", []):
        h, (k, v, aux) = layer_fn(h, p, positions, kv_mask)
        aux_total += aux
        if return_kv:
            kvs.append((k, v))

    def scan_body(carry, lp):
        h, aux_sum = carry
        h, (k, v, aux) = layer_fn(h, lp, positions, kv_mask)
        return (h, aux_sum + aux), (k, v) if return_kv else None

    (h, aux_total), scan_kv = jax.lax.scan(
        scan_body, (h, aux_total), params["layers"]
    )
    h = _apply_norm(cfg, params["final_norm"], h)
    if return_kv:
        return h, aux_total, (kvs, scan_kv)
    return h, aux_total


def lm_logits(params, hidden, cfg: LMConfig):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("...d,dv->...v", hidden, head)
    pv = padded_vocab(cfg)
    if pv != cfg.vocab_size:   # suppress the padded vocab rows
        mask = jnp.arange(pv) < cfg.vocab_size
        logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
    return logits


# ---------------------------------------------------------------------------
# decode: KV-cached single-token step
# ---------------------------------------------------------------------------


def init_cache(cfg: LMConfig, batch: int, max_len: int, dtype=None):
    """KV cache pytree: stacked (n_scan, B, S, n_kv, hd) + prefix list."""
    dt = dtype or _dtype(cfg)
    hd = cfg.resolved_head_dim
    n_prefix = cfg.moe.first_k_dense if cfg.moe is not None else 0
    n_scan = cfg.n_layers - n_prefix
    shape = (batch, max_len, cfg.n_kv_heads, hd)
    cache = {
        "k": jnp.zeros((n_scan,) + shape, dt),
        "v": jnp.zeros((n_scan,) + shape, dt),
    }
    if n_prefix:
        cache["prefix"] = [
            {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
            for _ in range(n_prefix)
        ]
    return cache


def _local_decode_core(q, k_new, v_new, ck, cv, pos):
    """Single-shard decode core: write new KV at ``pos``, attend over cache."""
    ck = jax.lax.dynamic_update_slice(ck, k_new[:, None], (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v_new[:, None], (0, pos, 0, 0))
    num, den, m = layers.decode_attention_local(
        q, ck, cv, shard_offset=jnp.zeros((), jnp.int32), kv_len=pos + 1
    )
    return (num / (den[..., None] + 1e-30)).astype(q.dtype), ck, cv


def _decode_layer(cfg, moe_fn, decode_core, h, lp, ck, cv, pos):
    """One decode layer. h: (B, d); ck/cv: (B, S, KV, hd).

    ``decode_core`` is pluggable: the local reference above, or the
    sequence-parallel shard_map core from repro.distributed."""
    x = _apply_norm(cfg, lp["ln1"], h)
    q, k, v = _project_qkv(cfg, lp["attn"], x[:, None, :], pos[None, None])
    o, ck, cv = decode_core(q[:, 0], k[:, 0], v[:, 0], ck, cv, pos)  # (B,H,hd)
    h = h + jnp.einsum("bhk,hkd->bd", o, lp["attn"]["wo"])
    x2 = _apply_norm(cfg, lp["ln2"], h)
    ffn, _ = _mlp_block(cfg, lp, x2, moe_fn)
    return h + ffn, ck, cv


def decode_step(
    params,
    cache,
    token: jax.Array,      # (B,) int32 — the newest token
    pos: jax.Array,        # () int32 — its position
    cfg: LMConfig,
    *,
    moe_fn: Optional[Callable] = None,
    decode_core: Callable = _local_decode_core,
):
    """One autoregressive step: returns (logits (B, V), updated cache)."""
    h = params["embed"][token].astype(_dtype(cfg))
    layer = partial(_decode_layer, cfg, moe_fn, decode_core)

    new_cache = dict(cache)
    if "prefix" in cache:
        new_prefix = []
        for lp, c in zip(params["prefix"], cache["prefix"]):
            h, ck, cv = layer(h, lp, c["k"], c["v"], pos)
            new_prefix.append({"k": ck, "v": cv})
        new_cache["prefix"] = new_prefix

    def scan_body(h, xs):
        lp, ck, cv = xs
        h, ck, cv = layer(h, lp, ck, cv, pos)
        return h, (ck, cv)

    h, (ks, vs) = jax.lax.scan(scan_body, h, (params["layers"], cache["k"], cache["v"]))
    new_cache["k"], new_cache["v"] = ks, vs
    h = _apply_norm(cfg, params["final_norm"], h)
    return lm_logits(params, h, cfg), new_cache
