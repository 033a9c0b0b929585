"""Sequence-parallel flash-decode: KV cache sharded along SEQUENCE.

Why: decode_32k on qwen1.5-110b carries an 86 GB/batch-shard KV cache —
head-parallelism cannot shard it (kv_heads=8 < model=16), so the cache's
*sequence* axis is sharded over "model" (and over everything for the
batch=1 long_500k cell).  Each shard computes partial attention over its
local KV chunk plus a running max/denominator; shards combine with the
standard LSE-weighted psum (exactly FlashDecoding's split-K reduction,
mapped onto mesh axes).

One shard_map covers cache-update + attention so the new token's K/V are
written into the owning shard without any boundary resharding.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models import layers


def _decode_core_body(
    q,            # (Bl, H, hd)      — local batch shard, all heads
    k_new,        # (Bl, KV, hd)
    v_new,        # (Bl, KV, hd)
    ck,           # (Bl, Sl, KV, hd) — local sequence shard of the cache
    cv,
    pos,          # ()  int32        — global write/attend position
    *,
    seq_axes: Tuple[str, ...],
    local_len: int,
):
    # which shard owns position `pos`?
    shard_id = jax.lax.axis_index(seq_axes)
    offset = shard_id * local_len
    local_pos = jnp.clip(pos - offset, 0, local_len - 1)
    mine = (pos >= offset) & (pos < offset + local_len)
    # masked write via a SLICE-level select: a full-cache jnp.where makes
    # XLA's CPU fusion pass materialize an f32 copy of the whole stacked
    # cache (12.6 GB/device on moonshot decode_32k); selecting on the
    # one-token payload is equivalent and byte-free.
    zero = (0, local_pos, 0, 0)
    sl = lambda c: jax.lax.dynamic_slice(
        c, zero, (c.shape[0], 1) + c.shape[2:]
    )
    ck = jax.lax.dynamic_update_slice(
        ck, jnp.where(mine, k_new[:, None], sl(ck)), zero
    )
    cv = jax.lax.dynamic_update_slice(
        cv, jnp.where(mine, v_new[:, None], sl(cv)), zero
    )

    num, den, m = layers.decode_attention_local(
        q, ck, cv, shard_offset=offset, kv_len=pos + 1
    )
    # LSE-weighted combine across sequence shards
    m_glob = jax.lax.pmax(m, seq_axes)
    scale = jnp.exp(m - m_glob)
    num = jax.lax.psum(num * scale[..., None], seq_axes)
    den = jax.lax.psum(den * scale, seq_axes)
    o = num / (den[..., None] + 1e-30)
    return o.astype(q.dtype), ck, cv


def make_decode_core(
    mesh: Mesh,
    batch_axes: Tuple[str, ...],
    seq_axes: Tuple[str, ...],
    seq_len: int,
):
    """Build the decode_core(q, k_new, v_new, ck, cv, pos) shard_map closure.

    batch_axes shard the cache/batch dim; seq_axes shard the cache sequence
    dim (psum'd in the combine).  Any mesh axis in neither set sees
    replicated compute (e.g. "model" when it TPs the surrounding matmuls).
    """
    n_seq_shards = 1
    for a in seq_axes:
        n_seq_shards *= mesh.shape[a]
    if seq_len % n_seq_shards:
        raise ValueError(f"seq_len={seq_len} not divisible by seq shards {n_seq_shards}")
    local_len = seq_len // n_seq_shards

    bspec = tuple(batch_axes) if batch_axes else None
    sspec = tuple(seq_axes)
    body = partial(_decode_core_body, seq_axes=sspec, local_len=local_len)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None),          # q
            P(bspec, None, None),          # k_new
            P(bspec, None, None),          # v_new
            P(bspec, sspec, None, None),   # ck
            P(bspec, sspec, None, None),   # cv
            P(),                           # pos
        ),
        out_specs=(
            P(bspec, None, None),
            P(bspec, sspec, None, None),
            P(bspec, sspec, None, None),
        ),
        check_vma=False,
    )
