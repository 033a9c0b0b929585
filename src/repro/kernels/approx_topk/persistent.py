"""Persistent ADACUR-round kernel: one payload pass per monitored round.

The staged engine runs each round's item-axis work as separate fused ops —
one ``approx_topk_op`` pass for Gumbel-top-k anchor *sampling* and (in the
early-exit monitor and at retrieval) a second pass for the *provisional*
top-k over the same ``e_q @ R_anc`` estimates.  Each pass re-streams the
entire quantized payload from HBM and re-runs the dequant+GEMM front end,
even though both consume the very same (B, T) score tiles.

This module fuses the whole round into ONE payload sweep:

  grid = (n_item_tiles,); each step:
    scores  = e_q @ dequant(codes[:, tile])        (MXU, computed ONCE)
    sample  = running top-k of scores + Gumbel     (mask suppressed)
    prov    = running top-k of scores              (eligibility-masked)

Round state — ``e_q`` and both running top-k accumulators — stays resident
in VMEM across grid steps (the accumulators are *revisited outputs*: their
block index maps are constant, the flash-attention accumulator pattern, so
Pallas keeps them on-chip and writes HBM once at the end).  Payload tiles
are the only HBM traffic, double-buffered by the Pallas pipeline.  The
exact-arithmetic stages (CE scoring, the incremental pinv via
``cur.block_pinv_extend_static``) have nothing to gain from tiling over
items and everything to lose in precision plumbing — they stay outside, in
plain fp32 XLA.

Two interchangeable backends, mirroring ops.py:

- ``pallas``: the persistent kernel above — what a TPU always runs
  (``interpret=True`` runs it under the Pallas interpreter on CPU);
- ``scan``: a lax.scan over item tiles carrying the running top-k lists —
  the fast CPU path and the executable spec.  When the caller passes a
  ``noise_key`` instead of a materialized (B, N) noise array, the scan
  additionally generates each tile's Gumbel rectangle *inside the loop*
  (``sampling.blocked_gumbel`` is a pure function of (key, global row,
  global item block), so per-tile generation is bit-equal to slicing a
  full-width field — the scan tile is kept NOISE_BLOCK-aligned to make the
  block coordinates line up).  The (B, N) noise matrix then never exists.

**Bitwise contracts** (identical to ops.py, asserted by the parity tests):
per-column fp32 contractions; noise keyed by global (row, item) coords;
exact score ties break by ascending item index.  Both backends merge the
running top-k with each tile by an explicit (max value, min item id)
selection rule over sentinel-initialized accumulators (NEG_INF values,
INT32_MAX ids), which is independent of buffer order and therefore equals
the staged flatten-then-top-k merge bit-for-bit — including fully-masked
degenerate rows, where the lowest masked item ids win just as they do in
the staged path.
"""

from __future__ import annotations

from functools import partial, reduce

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..backend import resolve_impl, resolve_interpret
from .kernel import (
    NEG_INF, as_kernel_mask, check_compiled_payload, pad_to_tile,
)
from .quant import HIGHEST, QuantizedRanc, unpack_int4


def _sampling():
    # deferred: core.engine imports this module, and importing
    # repro.core.sampling at module scope would run core/__init__ and
    # close the cycle when a caller imports the kernel package first
    from ...core import sampling

    return sampling

_SENTINEL_ID = jnp.iinfo(jnp.int32).max


def _merge_min_id(cv, ci, tv, ti, k):
    """Merge a carried top-k with a tile's top-k by (max value, min item id).

    The explicit min-id tie rule makes the merge independent of buffer
    order, so sentinel carry entries (NEG_INF value, INT32_MAX id) lose
    every comparison — including against fully-masked tiles, where the
    staged flatten-then-top-k yields the lowest masked item ids.  One
    vectorized lexicographic sort per merge (descending value, ascending
    id) selects the same pairs as the pallas kernel's iterative
    ``_select_min_id`` — ids are globally unique across carry and tile, so
    the lexicographic order is total — but costs O(k log k) vector work
    instead of k sequential selection steps per tile.
    """
    v = jnp.concatenate([cv, tv], axis=1)
    i = jnp.concatenate([ci, ti], axis=1)
    order = jnp.lexsort((i, -v), axis=1)[:, :k]
    return (
        jnp.take_along_axis(v, order, axis=1),
        jnp.take_along_axis(i, order, axis=1),
    )


def _persistent_scan(
    e_q, codes, scales, n, pack, k_sample, k_prov, mask, prov_mask,
    noise, noise_key, row_offset, col_offset, n_valid, tile,
):
    b, k_q = e_q.shape
    gen_noise = noise is None and noise_key is not None
    n_tiles = -(-n // tile)
    tile = -(-n // n_tiles)
    # keep tile boundaries byte-aligned for packed codes, and NOISE_BLOCK-
    # aligned when generating the Gumbel field per tile (block coords must
    # land on field-block boundaries)
    grain = _sampling().NOISE_BLOCK if gen_noise else pack
    if tile % grain:
        tile += grain - tile % grain
    codes, noise, mask, scales, n_pad = pad_to_tile(
        tile, codes, noise, mask, scales, pack=pack, n=n
    )
    if prov_mask is not None and prov_mask.shape[1] != n_pad:
        prov_mask = jnp.pad(
            prov_mask, ((0, 0), (0, n_pad - prov_mask.shape[1]))
        )
    n_tiles = n_pad // tile
    n_eff = n if n_valid is None else min(n_valid, n)
    e_q32 = e_q.astype(jnp.float32)
    arange_t = jnp.arange(tile, dtype=jnp.int32)

    def tile_lists(lo):
        r_tile = jax.lax.dynamic_slice(
            codes, (0, lo // pack), (k_q, tile // pack)
        )
        if pack == 2:
            r_tile = unpack_int4(r_tile)
        gemm = jnp.matmul(
            e_q32, r_tile.astype(jnp.float32), precision=HIGHEST
        )                                                       # (B, tile)
        if scales is not None:
            scale_t = jax.lax.dynamic_slice(scales, (lo,), (tile,))[None, :]

        def scaled():
            # each branch re-applies the scale to the shared GEMM output:
            # a single-consumer multiply feeding the branch's noise add, the
            # same dataflow the staged passes compile (XLA contracts it to
            # an FMA — sharing one scaled array across branches would block
            # that and drift the scores an ulp from the staged path)
            return gemm * scale_t if scales is not None else gemm

        gids = lo + arange_t
        base = (gids < n_eff)[None, :]
        out = []
        if k_sample is not None:
            s = scaled()
            if noise is not None:
                s = s + jax.lax.dynamic_slice(
                    noise, (0, lo), (b, tile)
                ).astype(jnp.float32)
            elif gen_noise:
                s = s + _sampling().blocked_gumbel(
                    noise_key, b, tile, row_offset, col_offset + lo
                )
            keep = base
            if mask is not None:
                keep = keep & ~jax.lax.dynamic_slice(mask, (0, lo), (b, tile))
            s = jnp.where(keep, s, NEG_INF)
            v, i = jax.lax.top_k(s, k_sample)
            out.append((v, lo + i.astype(jnp.int32)))
        if k_prov is not None:
            keep = base
            if prov_mask is not None:
                keep = keep & ~jax.lax.dynamic_slice(
                    prov_mask, (0, lo), (b, tile)
                )
            s = jnp.where(keep, scaled(), NEG_INF)
            v, i = jax.lax.top_k(s, k_prov)
            out.append((v, lo + i.astype(jnp.int32)))
        return tuple(out)

    ks = [k for k in (k_sample, k_prov) if k is not None]
    # one uniform scan over ALL tiles with a sentinel-initialized carry:
    # special-casing tile 0 outside the loop gives CPU XLA a second,
    # differently-fused copy of the score chain whose results drift an ulp
    # from the staged passes.  With every tile flowing through the same
    # compiled body the chain is bit-stable, and the min-id merge makes the
    # sentinel entries (NEG_INF, INT32_MAX) lose every comparison.
    init = tuple(
        (
            jnp.full((b, k), NEG_INF, jnp.float32),
            jnp.full((b, k), _SENTINEL_ID, jnp.int32),
        )
        for k in ks
    )

    def step(carry, lo):
        t = tile_lists(lo)
        merged = tuple(
            _merge_min_id(cv, ci, tv, ti, k)
            for (cv, ci), (tv, ti), k in zip(carry, t, ks)
        )
        return merged, None

    carry, _ = jax.lax.scan(
        step, init,
        jnp.arange(n_tiles, dtype=jnp.int32) * tile,
        unroll=min(4, n_tiles),
    )
    return carry


def _select_min_id(bufs, k):
    """k iterations of (max value, min item id) selection over the union of
    ``bufs`` — a list of (values (B, w), ids (B, w)) pairs.

    Explicitly encodes the ascending-item-id tie rule, so the result is
    independent of buffer order — in particular of where carried entries
    sit relative to the current tile's entries.  The buffers are scanned
    side by side rather than concatenated (a lane-unaligned concatenate
    does not lower in a TPU kernel), and each pick is written through an
    iota mask (nor does a scatter).
    """
    b = bufs[0][0].shape[0]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (b, k), 1)

    def take(i, carry):
        v, idx, bufs = carry
        m = reduce(
            jnp.maximum, [jnp.max(bv, axis=1) for bv, _ in bufs]
        )                                                      # (B,)
        g = reduce(jnp.minimum, [
            jnp.min(jnp.where(bv == m[:, None], bi, _SENTINEL_ID), axis=1)
            for bv, bi in bufs
        ])                                                     # (B,)
        v = jnp.where(lanes == i, m[:, None], v)
        idx = jnp.where(lanes == i, g[:, None], idx)
        out = []
        for bv, bi in bufs:
            sup = (bv == m[:, None]) & (bi == g[:, None])
            out.append((jnp.where(sup, NEG_INF, bv),
                        jnp.where(sup, _SENTINEL_ID, bi)))
        return v, idx, out

    v0 = jnp.full((b, k), NEG_INF, jnp.float32)
    i0 = jnp.zeros((b, k), jnp.int32)
    v, idx, _ = jax.lax.fori_loop(0, k, take, (v0, i0, list(bufs)))
    return v, idx


def _persistent_kernel(
    e_q_ref, codes_ref, *rest,
    tile, k_sample, k_prov, n_items, pack,
    has_scales, has_noise, has_mask, has_prov_mask,
):
    it = iter(rest)
    scales_ref = next(it) if has_scales else None
    noise_ref = next(it) if has_noise else None
    mask_ref = next(it) if has_mask else None
    prov_mask_ref = next(it) if has_prov_mask else None
    outs = list(it)
    ti = pl.program_id(0)
    e_q = e_q_ref[...].astype(jnp.float32)
    r = codes_ref[...]
    if pack == 2:
        r = unpack_int4(r)
    gemm = jax.lax.dot_general(
        e_q, r.astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )                                                           # (B, T)

    def scaled():
        # per-branch scale multiply — see _persistent_scan for why this is
        # not hoisted (FMA-contraction parity with the staged passes)
        return gemm * scales_ref[...] if scales_ref is not None else gemm

    gids = ti * tile + jax.lax.broadcasted_iota(jnp.int32, gemm.shape, 1)
    base = gids < n_items

    def run(s, v_ref, i_ref, k):
        # revisited-output accumulator: read the running top-k (garbage on
        # the first step — replaced by sentinels that lose every comparison),
        # merge with this tile, write back.  Constant block index keeps the
        # accumulator in VMEM for the whole grid.
        cv = jnp.where(ti == 0, NEG_INF, v_ref[...])
        ci = jnp.where(ti == 0, _SENTINEL_ID, i_ref[...])
        v, idx = _select_min_id([(cv, ci), (s, gids)], k)
        v_ref[...] = v
        i_ref[...] = idx

    o = iter(outs)
    if k_sample is not None:
        s = scaled()
        if noise_ref is not None:
            s = s + noise_ref[...].astype(jnp.float32)
        keep = base
        if mask_ref is not None:
            keep = keep & (mask_ref[...].astype(jnp.int32) == 0)
        run(jnp.where(keep, s, NEG_INF), next(o), next(o), k_sample)
    if k_prov is not None:
        keep = base
        if prov_mask_ref is not None:
            keep = keep & (prov_mask_ref[...].astype(jnp.int32) == 0)
        run(jnp.where(keep, scaled(), NEG_INF), next(o), next(o), k_prov)


def _persistent_pallas(
    e_q, codes, scales, n, pack, k_sample, k_prov, mask, prov_mask,
    noise, n_valid, tile, interpret,
):
    interpret = resolve_interpret(interpret)
    check_compiled_payload("persistent_round_sweep", pack, interpret)
    b, k_q = e_q.shape
    if pack > 1 and tile % pack:
        tile += pack - tile % pack
    codes, noise, mask, scales, n_pad = pad_to_tile(
        tile, codes, noise, mask, scales, pack=pack, n=n
    )
    if prov_mask is not None and prov_mask.shape[1] != n_pad:
        prov_mask = jnp.pad(
            prov_mask, ((0, 0), (0, n_pad - prov_mask.shape[1]))
        )
    n_tiles = n_pad // tile
    kernel = partial(
        _persistent_kernel, tile=tile, k_sample=k_sample, k_prov=k_prov,
        n_items=n if n_valid is None else min(n_valid, n), pack=pack,
        has_scales=scales is not None,
        has_noise=noise is not None, has_mask=mask is not None,
        has_prov_mask=prov_mask is not None,
    )
    in_specs = [
        pl.BlockSpec((b, k_q), lambda ti: (0, 0)),
        pl.BlockSpec((k_q, tile // pack), lambda ti: (0, ti)),
    ]
    inputs = [e_q, codes]
    if scales is not None:
        in_specs.append(pl.BlockSpec((1, tile), lambda ti: (0, ti)))
        inputs.append(scales[None, :])
    for extra in (
        noise,
        None if mask is None else as_kernel_mask(mask),
        None if prov_mask is None else as_kernel_mask(prov_mask),
    ):
        if extra is not None:
            in_specs.append(pl.BlockSpec((b, tile), lambda ti: (0, ti)))
            inputs.append(extra)
    out_specs, out_shape = [], []
    for k in (k_sample, k_prov):
        if k is not None:
            out_specs += [
                pl.BlockSpec((b, k), lambda ti: (0, 0)),
                pl.BlockSpec((b, k), lambda ti: (0, 0)),
            ]
            out_shape += [
                jax.ShapeDtypeStruct((b, k), jnp.float32),
                jax.ShapeDtypeStruct((b, k), jnp.int32),
            ]
    outs = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="persistent_round_sweep",
    )(*inputs)
    pairs = [(outs[i], outs[i + 1]) for i in range(0, len(outs), 2)]
    return tuple(pairs)


@partial(
    jax.jit,
    static_argnames=("k_sample", "k_prov", "tile", "interpret", "n_valid", "impl"),
)
def persistent_round_op(
    e_q,
    r_anc,
    *,
    k_sample: int | None = None,
    k_prov: int | None = None,
    mask=None,
    prov_mask=None,
    noise=None,
    noise_key=None,
    row_offset=0,
    col_offset=0,
    n_valid: int | None = None,
    tile: int = 512,
    interpret: bool | None = None,
    impl: str = "auto",
):
    """One fused payload sweep -> sampled top-k and/or provisional top-k.

    Returns ``(sample, prov)`` where each part is a ``(vals (B,k),
    idx (B,k))`` pair or ``None`` if its k was not requested.  Bit-identical
    to the corresponding staged calls:

    - ``sample`` == ``approx_topk_op(e_q, r_anc, k_sample, noise=noise,
      mask=mask, n_valid=n_valid)``
    - ``prov``   == ``approx_topk_op(e_q, r_anc, k_prov, mask=prov_mask,
      n_valid=n_valid)``

    but streams the payload from HBM once instead of twice.  ``noise_key``
    (+ global ``row_offset``/``col_offset``) may replace a materialized
    ``noise`` array: the scan backend then generates each tile's Gumbel
    rectangle inside the loop; the pallas backend materializes the identical
    field up front (TPU noise stays precomputed — in-kernel RNG cannot match
    ``jax.random`` bitwise).
    """
    if k_sample is None and k_prov is None:
        raise ValueError("persistent_round_op needs k_sample and/or k_prov")
    if isinstance(r_anc, QuantizedRanc):
        codes, scales = r_anc.codes, r_anc.col_scales()
        pack, n = r_anc.packing, r_anc.shape[1]
    else:
        codes, scales, pack, n = r_anc, None, 1, r_anc.shape[1]
    impl, interpret = resolve_impl(impl, interpret)
    if impl == "scan":
        pairs = _persistent_scan(
            e_q, codes, scales, n, pack, k_sample, k_prov, mask, prov_mask,
            noise, noise_key, row_offset, col_offset, n_valid, tile,
        )
    else:
        if noise is None and noise_key is not None:
            noise = _sampling().blocked_gumbel(
                noise_key, e_q.shape[0], n, row_offset, col_offset
            )
        pairs = _persistent_pallas(
            e_q, codes, scales, n, pack, k_sample, k_prov, mask, prov_mask,
            noise, n_valid, tile, interpret,
        )
    pairs = list(pairs)
    sample = pairs.pop(0) if k_sample is not None else None
    prov = pairs.pop(0) if k_prov is not None else None
    return sample, prov
