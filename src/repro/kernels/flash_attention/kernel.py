"""Flash attention Pallas TPU kernel (blocked online-softmax, GQA-aware).

TPU adaptation of the FlashAttention-2 schedule: the KV axis is the
innermost *sequential* grid dimension, so the (m, l, acc) running state
lives in VMEM scratch across KV steps; Q/K/V tiles stream HBM->VMEM once.
Block sizes default to 128/128 to align with the MXU 128x128 systolic array
and the (8,128) VREG lane layout.

Layout: q (BH, Lq, hd), k/v (BKV, Lk, hd) with BH = batch*n_heads and
BKV = batch*n_kv_heads; GQA is handled by the K/V index_map folding the
query-head index onto its KV group — no KV duplication in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(
    lens_ref,                     # (B,) int32 SMEM (scalar prefetch)
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    n_heads: int,
    q_offset: int,
):
    # per-example valid KV length — variable-length sequences packed into
    # one padded bucket (cross-encoder scoring); grid row bh = b*H + h
    kv_limit = lens_ref[pl.program_id(0) // n_heads]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                        # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                        # (bk, hd)
    v = v_ref[0].astype(jnp.float32)

    run = True
    if causal:
        # whole block above the diagonal -> no work (cheap static skip is not
        # possible: grid is dense; mask handles it, @pl.when saves the GEMM)
        run = (ki * block_k) <= (q_offset + qi * block_q + block_q - 1)

    @pl.when(run if causal else True)
    def _body():
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                            # (bq, bk)
        kv_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kv_pos < kv_limit
        if causal:
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask &= kv_pos <= q_pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_cur

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / (l_scr[...][:, None] + 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,           # (B, Lq, H, hd)
    k: jax.Array,           # (B, Lk, KV, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    kv_lens: jax.Array | None = None,   # (B,) int32 valid KV length / example
) -> jax.Array:
    """pallas_call wrapper; returns (B, Lq, H, hd).

    ``kv_lens`` masks each example's trailing padding (keys at positions >=
    kv_lens[b] never contribute): how variable-length query-item pairs are
    scored through one static padded bucket shape without retracing.  The
    (B,) lengths are a scalar-prefetch operand, resident in SMEM for the
    whole grid — no (B, Lk) mask in HBM.  Without ``kv_lens`` every key of
    the unpadded length is valid.
    """
    b, lq, h, hd = q.shape
    _, lk, n_kv, _ = k.shape
    q_per_kv = h // n_kv
    scale = 1.0 / (hd ** 0.5)
    q_offset = lk - lq          # right-aligned causal convention (decode chunks)

    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    lq_pad = pl.cdiv(lq, block_q) * block_q
    lk_pad = pl.cdiv(lk, block_k) * block_k
    if lq_pad != lq:
        q = jnp.pad(q, ((0, 0), (0, lq_pad - lq), (0, 0), (0, 0)))
    if lk_pad != lk:
        k = jnp.pad(k, ((0, 0), (0, lk_pad - lk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, lk_pad - lk), (0, 0), (0, 0)))

    # (B, L, H, hd) -> (B*H, L, hd) head-major layout
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, lq_pad, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(b * n_kv, lk_pad, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(b * n_kv, lk_pad, hd)

    if kv_lens is None:
        lens = jnp.full((b,), lk, jnp.int32)
    else:
        lens = jnp.minimum(kv_lens.astype(jnp.int32), lk)

    def q_map(bh, qi, ki, lens_ref):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki, lens_ref):
        # fold query head onto its KV group: bh = bi*H + hi
        bi = bh // h
        hi = bh % h
        return (bi * n_kv + hi // q_per_kv, ki, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, lq_pad // block_q, lk_pad // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, n_heads=h, q_offset=q_offset,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, lq_pad, hd), q.dtype),
        interpret=resolve_interpret(interpret),
        name="flash_attention",
    )(lens, qh, kh, vh)
    out = out.reshape(b, h, lq_pad, hd).transpose(0, 2, 1, 3)
    return out[:, :lq]
