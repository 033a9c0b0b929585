"""Cross-encoder scorer: f(q, i) = head(T(concat(q, [SEP], i))).

The CE jointly encodes the query-item token sequence (bidirectionally, as
entity-linking CEs do) and reads a scalar score off the [CLS] position.
This is the paper's f_theta; any LM backbone from the model zoo can serve.
The bulk-scoring entry points below are what the ADACUR engine and the
offline R_anc indexer call.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import LMConfig
from . import layers, transformer


def init_cross_encoder(key, cfg: LMConfig):
    k_lm, k_head = jax.random.split(key)
    params, specs = transformer.init_lm(k_lm, cfg)
    params["score_head"], specs["score_head"] = layers.dense_init(
        k_head, (cfg.d_model, 1), ("embed", "unit"), scale=0.02
    )
    return params, specs


def score_tokens(
    params,
    pair_tokens: jax.Array,          # (B, L) int32, [CLS] q [SEP] i [SEP]
    cfg: LMConfig,
    pad_id: int = 0,
    moe_fn=None,
    attn_impl: str = "ref",          # "flash": Pallas kernel, SMEM varlen mask
    flash_block=(128, 128),
) -> jax.Array:
    """Exact CE score for a batch of already-concatenated pairs -> (B,).

    Pair tokens are valid-first with trailing ``pad_id`` padding (what
    ``ZeshelLikeDataset.pair_tokens`` + bucket padding produce), so the
    flash path can mask per-example lengths instead of a (B, L) key mask.
    """
    kv_mask = pair_tokens != pad_id
    h, _ = transformer.encode(
        params, pair_tokens, cfg, kv_mask=kv_mask, moe_fn=moe_fn,
        attn_impl=attn_impl, flash_block=flash_block,
    )
    cls = h[:, 0, :].astype(jnp.float32)
    return (cls @ params["score_head"].astype(jnp.float32))[:, 0]


def build_pair_tokens(
    query_tokens: jax.Array,         # (B, Lq) int32, no internal padding
    item_tokens: jax.Array,          # (B, K, Li) int32, no internal padding
    *,
    pad_to: int,                     # bucket length >= Lq + Li + 3
    cls_id: int = 1,
    sep_id: int = 2,
    pad_id: int = 0,
) -> jax.Array:
    """In-trace pair assembly: ``[CLS] q [SEP] i [SEP]`` -> (B, K, pad_to).

    The traced counterpart of a host-side ``pair_fn``: device-resident
    scorers gather corpus token rows on device and concatenate them here,
    inside the engine's compiled program.  Inputs are valid-first fixed
    length, so the output keeps the trailing-padding contract
    :func:`score_tokens` relies on for per-example length masking.
    """
    b, lq = query_tokens.shape
    _, k, li = item_tokens.shape
    length = lq + li + 3
    if pad_to < length:
        raise ValueError(f"pad_to={pad_to} cannot hold a pair of length {length}")
    q = jnp.broadcast_to(query_tokens[:, None, :], (b, k, lq)).astype(jnp.int32)
    fill = lambda tok, n: jnp.full((b, k, n), tok, jnp.int32)
    return jnp.concatenate(
        [
            fill(cls_id, 1), q, fill(sep_id, 1),
            item_tokens.astype(jnp.int32), fill(sep_id, 1),
            fill(pad_id, pad_to - length),
        ],
        axis=-1,
    )


def score_pairs(
    params,
    pair_tokens: jax.Array,          # (B, K, L) — K items per query
    cfg: LMConfig,
    pad_id: int = 0,
    moe_fn=None,
    attn_impl: str = "ref",
    flash_block=(128, 128),
) -> jax.Array:
    """(B, K) scores: flattens the item axis into the CE batch."""
    b, k, l = pair_tokens.shape
    flat = score_tokens(
        params, pair_tokens.reshape(b * k, l), cfg, pad_id, moe_fn,
        attn_impl=attn_impl, flash_block=flash_block,
    )
    return flat.reshape(b, k)


def ranking_loss(
    params,
    pair_tokens: jax.Array,          # (B, K, L) — item 0 is the gold item
    cfg: LMConfig,
    pad_id: int = 0,
) -> jax.Array:
    """In-batch softmax ranking loss used by the end-to-end CE trainer."""
    scores = score_pairs(params, pair_tokens, cfg, pad_id)      # (B, K)
    logp = jax.nn.log_softmax(scores, axis=-1)
    return -logp[:, 0].mean()
