"""Flash attention Pallas TPU kernel: two schedules, chosen from the shape.

**Online softmax** (causal calls, and KV too long for VMEM): the TPU
adaptation of the FlashAttention-2 schedule.  The KV axis is the innermost
*sequential* grid dimension, so the (m, l, acc) running state lives in VMEM
scratch across KV steps; Q/K/V tiles stream HBM->VMEM once.  Block sizes
default to 128/128 to align with the MXU 128x128 systolic array and the
(8,128) VREG lane layout.  Layout: q (BH, Lq, hd), k/v (BKV, Lk, hd) with
BH = batch*n_heads and BKV = batch*n_kv_heads; GQA is handled by the K/V
index_map folding the query-head index onto its KV group — no KV
duplication in HBM.

**Whole sequence** (bidirectional calls whose whole KV fits in VMEM: the
cross-encoder's short pairs): one grid step attends every head of a block
of ``bp`` pairs over the whole sequence, with an exact one-block softmax —
no running state across KV steps.  Blocks are (bp, heads, hd, L), sequence
on the lanes: the layout a TPU keeps a (B, L, heads, hd) projection output
in when hd is under 128 lanes, so no copy surrounds the call there, and
every load and store is lane-dense.  Scores are formed transposed, (Lk,
Lq), so the softmax reduces across sublanes and PV takes the probabilities
as they are.  Operands enter the MXU in their stored dtype, accumulating
in f32; the probabilities are cast to v's dtype for PV.  ``bp`` is the
largest power of two whose double-buffered q/k/v/o blocks and one head's
scores fit in the scoped VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import telemetry
from ..backend import resolve_interpret

NEG_INF = -1e30
# Mosaic's default scoped-VMEM limit of a v5e core: the whole-sequence
# schedule sizes its pair block to fit in it
SCOPED_VMEM_BYTES = 16 * 2**20


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


def _whole_sequence_kernel(
    lens_ref,                     # (B,) int32 SMEM (scalar prefetch)
    q_ref, k_ref, v_ref, o_ref,   # q/o (bp, H, hd, Lq), k/v (bp, KV, hd, Lk)
    *,
    scale: float,
    block_b: int,
):
    _, n_heads, _, lq = q_ref.shape
    _, n_kv, _, lk = k_ref.shape
    q_per_kv = n_heads // n_kv
    base = pl.program_id(0) * block_b

    def pair(i, carry):
        # scores kept transposed, (Lk, Lq): keys on sublanes, so the softmax
        # reduces across sublanes and PV takes p as it is
        kv_limit = lens_ref[base + i]
        key_valid = jax.lax.broadcasted_iota(jnp.int32, (lk, lq), 0) < kv_limit
        for g in range(n_kv):
            k = k_ref[i, g].T                                # (Lk, hd)
            v_t = v_ref[i, g]                                # (hd, Lk)
            for hi in range(g * q_per_kv, (g + 1) * q_per_kv):
                s_t = jnp.dot(k, q_ref[i, hi],
                              preferred_element_type=jnp.float32) * scale
                s_t = jnp.where(key_valid, s_t, NEG_INF)
                p_t = jnp.exp(s_t - jnp.max(s_t, axis=0, keepdims=True))
                l = jnp.sum(p_t, axis=0, keepdims=True)      # (1, Lq)
                o_t = jnp.dot(v_t, p_t.astype(v_t.dtype),
                              preferred_element_type=jnp.float32)   # (hd, Lq)
                o_ref[i, hi] = (o_t / l).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, block_b, pair, 0)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def pairs_per_step(b: int, lq: int, lk: int, n_heads: int, n_kv: int,
                   head_dim: int, itemsize: int) -> int:
    """Pairs per grid step of the whole-sequence schedule: the largest power
    of two, at most ``b``, whose double-buffered q/k/v/o blocks and one
    head's f32 scores (with their exp and mask) fit in scoped VMEM, each
    padded to its (sublane, 128-lane) tiles; 0 where not one pair fits."""
    rows = _round_up(head_dim, 32 // itemsize)
    pair_bytes = 2 * rows * itemsize * (
        2 * n_heads * _round_up(lq, 128) + 2 * n_kv * _round_up(lk, 128))
    score_bytes = 4 * _round_up(lk, 8) * _round_up(lq, 128) * 4
    fit = (SCOPED_VMEM_BYTES - score_bytes) // pair_bytes
    if fit < 1:
        return 0
    return 1 << (min(fit, b).bit_length() - 1)


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

    Bidirectional calls whose KV fits in VMEM take the whole-sequence
    schedule (``block_q``/``block_k`` unused), all others the online-softmax
    one; each traced call records its choice as a ``flash.schedule`` mark.
    """
    b, lq, h, hd = q.shape
    _, lk, n_kv, _ = k.shape
    if kv_lens is None:
        lens = jnp.full((b,), lk, jnp.int32)
    else:
        lens = jnp.minimum(kv_lens.astype(jnp.int32), lk)
    block_b = 0 if causal else pairs_per_step(
        b, lq, lk, h, n_kv, hd, q.dtype.itemsize)
    telemetry.mark(
        "flash.schedule",
        schedule="whole_sequence" if block_b else "online_softmax",
        pairs=b, seq=lk, heads=h,
    )
    interpret = resolve_interpret(interpret)
    if block_b:
        return _whole_sequence(q, k, v, lens, block_b, interpret)
    return _online_softmax(q, k, v, lens, causal, block_q, block_k, interpret)


def _whole_sequence(q, k, v, lens, block_b, interpret):
    b, lq, h, hd = q.shape
    _, lk, n_kv, _ = k.shape
    b_pad = pl.cdiv(b, block_b) * block_b
    # (B, L, heads, hd) -> (B, heads, hd, L): the layout a TPU gives the
    # projections' output when hd is under 128 lanes, so a bitcast there
    qt, kt, vt = (x.transpose(0, 2, 3, 1) for x in (q, k, v))
    if b_pad != b:
        pad = ((0, b_pad - b), (0, 0), (0, 0), (0, 0))
        qt, kt, vt = jnp.pad(qt, pad), jnp.pad(kt, pad), jnp.pad(vt, pad)
        lens = jnp.pad(lens, (0, b_pad - b), constant_values=lk)

    def block_map(bi, lens_ref):
        return (bi, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b_pad // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, h, hd, lq), block_map),
            pl.BlockSpec((block_b, n_kv, hd, lk), block_map),
            pl.BlockSpec((block_b, n_kv, hd, lk), block_map),
        ],
        out_specs=pl.BlockSpec((block_b, h, hd, lq), block_map),
    )
    out = pl.pallas_call(
        functools.partial(
            _whole_sequence_kernel, scale=1.0 / (hd ** 0.5), block_b=block_b,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(lens, qt, kt, vt)
    return out[:b].transpose(0, 3, 1, 2)


def _online_softmax(q, k, v, lens, causal, block_q, block_k, interpret):
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
        interpret=interpret,
        name="flash_attention",
    )(lens, qh, kh, vh)
    out = out.reshape(b, h, lq_pad, hd).transpose(0, 2, 1, 3)
    return out[:, :lq]
