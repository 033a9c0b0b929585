"""Pure-jnp oracle: materialize S_hat, apply the mask, full top-k.

Accepts the same payload types as the fused op (fp32 / bf16 arrays or an
int8 :class:`QuantizedRanc`), dequantizing with the same per-column scale
factoring the kernels use — so the oracle and the fused paths compute the
same scores and, with the shared ascending-index tie-break of
``lax.top_k``, bit-equal rankings."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .quant import QuantizedRanc

NEG_INF = -1e30


def approx_topk_reference(
    e_q: jax.Array,       # (B, k_q)
    r_anc: jax.Array,     # (k_q, N) — or an int8 QuantizedRanc payload
    k: int,
    noise: jax.Array | None = None,   # (B, N) additive noise
    mask: jax.Array | None = None,    # (B, N) bool — True = suppress
    n_valid: int | None = None,       # real item count when N is padded
):
    if isinstance(r_anc, QuantizedRanc):
        scores = e_q.astype(jnp.float32) @ r_anc.codes.astype(jnp.float32)
        scores = scores * r_anc.col_scales()[None, :]
    else:
        scores = e_q.astype(jnp.float32) @ r_anc.astype(jnp.float32)  # (B, N)
    if noise is not None:
        scores = scores + noise.astype(jnp.float32)
    n = scores.shape[1]
    ids = jnp.arange(n)
    hit = jnp.zeros(scores.shape, bool)
    if mask is not None:
        hit = hit | mask
    if n_valid is not None:
        hit = hit | (ids >= n_valid)[None, :]
    scores = jnp.where(hit, NEG_INF, scores)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx.astype(jnp.int32)
