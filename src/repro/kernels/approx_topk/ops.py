"""Public fused approx-score->top-k op: tiled score+select + tiny merge.

Two interchangeable backends with identical semantics and identical memory
behavior (no (B, N) float score matrix is ever formed):

- ``pallas``: the TPU kernel in kernel.py — GEMM + mask + per-tile top-k in
  VMEM (``interpret=True`` runs the same kernel under the Pallas
  interpreter, useful for debugging the kernel itself);
- ``scan``: a lax.scan over item tiles in plain XLA — each step computes a
  (B, tile) score slab, masks it, and keeps its top-k.  This is the fast
  CPU path (the Pallas interpreter emulates the grid sequentially with
  per-step dispatch overhead; the scan compiles to one tight XLA loop) and
  doubles as an executable spec of the kernel.

``impl='auto'`` is the compiled kernel on a TPU and ``scan`` elsewhere;
the platform decides (``kernels/backend.py``), and a TPU never runs the
scan or the interpreter.

**Quantized payloads.**  ``r_anc`` may be a
:class:`~repro.kernels.approx_topk.quant.QuantizedRanc` (int8, packed
int4, or fp8 codes + per-item-tile fp32 scales).  Both backends then run a
fused dequant-matmul front end: each grid step loads a code tile, widens it
in registers (sign-extending nibbles for int4), contracts with ``e_q`` in
fp32 accumulation, and applies the per-column scale to the (B, T) GEMM
output — on TPU that is 4-8x fewer HBM bytes per step, and the fp32 R_anc
never exists anywhere.

**Deterministic tie-breaking.**  Exact score ties break by ascending item
index, in both backends: per tile the selection is index-stable
(``lax.top_k`` prefers the lower index; the kernel's iterative argmax takes
the first occurrence), and the cross-tile merge flattens tiles in ascending
order so ``lax.top_k`` over the flat buffer again prefers the lower global
id.  Fused and dense rankings are therefore bit-equal whenever their scores
are (asserted by the kernel parity tests), not merely set-equal.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..backend import resolve_impl
from .kernel import NEG_INF, approx_topk_tiles, pad_to_tile
from .quant import HIGHEST, QuantizedRanc, unpack_int4


def _scan_topk_tiles(e_q, r_anc, k, tile, noise, mask, n_valid,
                     scales=None, pack=1, n_cols=None):
    """lax.scan tiled reference with kernel-identical tie-breaks.

    ``tile`` is rebalanced so the last tile carries at most n_tiles-1 padded
    columns (a literal tile multiple can waste up to a whole tile of GEMM
    work — 23% at N=10k, tile=4096); a modest unroll amortizes the scan's
    per-step dispatch on CPU.  ``scales`` (N,), when given, is
    the quantized payload's per-column dequant scale, applied to each tile's
    GEMM output (scale rebalancing is free: scales are per *column*, so the
    scan tile width need not match the payload's quantization tile).
    ``pack=2`` streams packed int4 codes — each step slices tile/2 bytes and
    sign-extends the nibbles in registers (the rebalanced tile is rounded up
    to even so tile boundaries stay byte-aligned)."""
    b, k_q = e_q.shape
    n = r_anc.shape[1] * pack if n_cols is None else n_cols
    n_tiles = -(-n // tile)
    tile = -(-n // n_tiles)
    if pack > 1 and tile % pack:
        tile += pack - tile % pack
    r_anc, noise, mask, scales, n_pad = pad_to_tile(
        tile, r_anc, noise, mask, scales, pack=pack, n=n
    )
    n_tiles = n_pad // tile             # evenness rounding can shrink this
    n_eff = n if n_valid is None else min(n_valid, n)
    e_q32 = e_q.astype(jnp.float32)
    arange_t = jnp.arange(tile, dtype=jnp.int32)

    def step(_, lo):
        r_tile = jax.lax.dynamic_slice(
            r_anc, (0, lo // pack), (k_q, tile // pack)
        )
        if pack == 2:
            r_tile = unpack_int4(r_tile)
        scores = jnp.matmul(
            e_q32, r_tile.astype(jnp.float32), precision=HIGHEST
        )                                                       # (B, tile)
        if scales is not None:
            scores = scores * jax.lax.dynamic_slice(
                scales, (lo,), (tile,)
            )[None, :]
        if noise is not None:
            scores = scores + jax.lax.dynamic_slice(
                noise, (0, lo), (b, tile)
            ).astype(jnp.float32)
        gids = lo + arange_t
        keep = (gids < n_eff)[None, :]
        if mask is not None:
            keep = keep & ~jax.lax.dynamic_slice(mask, (0, lo), (b, tile))
        scores = jnp.where(keep, scores, NEG_INF)
        v, i = jax.lax.top_k(scores, k)
        return None, (v, lo + i.astype(jnp.int32))

    _, (vals, idx) = jax.lax.scan(
        step, None, jnp.arange(n_tiles, dtype=jnp.int32) * tile,
        unroll=min(4, n_tiles),
    )
    # (n_tiles, B, k) -> (B, n_tiles, k), matching the kernel layout
    return jnp.swapaxes(vals, 0, 1), jnp.swapaxes(idx, 0, 1)


@partial(
    jax.jit, static_argnames=("k", "tile", "interpret", "n_valid", "impl")
)
def approx_topk_op(
    e_q,
    r_anc,
    k: int,
    *,
    tile: int = 512,
    interpret: bool | None = None,
    noise=None,
    mask=None,
    n_valid: int | None = None,
    impl: str = "auto",
):
    """Fused  top-k(mask(e_q @ R_anc [+ noise]))  ->  (vals (B,k), idx (B,k)).

    ``r_anc`` is the (k_q, N) score matrix — fp32, bf16, or an int8
    :class:`QuantizedRanc` payload (dequantized tile-by-tile inside the
    kernel; see module docstring).
    ``mask`` (B, N) bool suppresses where True (the engine's selected
    anchors).
    ``noise`` (B, N), when given, is added to the scores before the top-k —
    passing Gumbel noise makes this an exact sample without replacement from
    softmax(S_hat) (Kool et al. 2019) with S_hat never materialized.
    ``n_valid`` suppresses padded item ids >= n_valid.
    Exact score ties break deterministically by ascending item index.
    """
    if isinstance(r_anc, QuantizedRanc):
        codes, scales = r_anc.codes, r_anc.col_scales()
        pack, n_cols = r_anc.packing, r_anc.shape[1]
    else:
        codes, scales = r_anc, None
        pack, n_cols = 1, None
    impl, interpret = resolve_impl(impl, interpret)
    if impl == "scan":
        vals, idx = _scan_topk_tiles(
            e_q, codes, k, tile, noise, mask, n_valid,
            scales=scales, pack=pack, n_cols=n_cols,
        )
    else:
        vals, idx = approx_topk_tiles(
            e_q, codes, k, tile=tile, interpret=interpret,
            noise=noise, mask=mask, n_valid=n_valid, scales=scales,
            pack=pack, n_cols=n_cols,
        )
    b, n_tiles, _ = vals.shape
    # merge: n_tiles*k ≪ N.  Tiles flatten in ascending order and lax.top_k
    # is index-stable, so equal values resolve to the lowest global id.
    flat_v = vals.reshape(b, n_tiles * k)
    flat_i = idx.reshape(b, n_tiles * k)
    top_v, pos = jax.lax.top_k(flat_v, k)
    top_i = jnp.take_along_axis(flat_i, pos, axis=1)
    return top_v, top_i
